"""Step functions for distributed full-graph GNN training (the paper's Trainer).

Three step flavors over one :class:`GNNTrainState`:

* ``train_step_sync``  — vanilla (bits=32) or Sylvie-S. Fresh quantized exchange
  both passes; also refreshes the Sylvie-A feature caches (a
  ``BoundedStaleness`` policy schedules exactly this step every ``eps_s``
  epochs) and *drains* the grad caches (a synchronous epoch leaves no
  in-flight boundary gradients).
* ``train_step_async`` — Sylvie-A: consumes cached halo features/gradients,
  emits fresh caches for the next step. A site fed by the node features
  ``x`` sends no boundary gradient (``SylvieComm``'s ``inputs``); its
  gradient cache reads zeros, as after a sync step.
* ``eval_step``        — full-precision synchronous exchange (accuracy metric).

What each halo-exchange site does — per-direction bit-widths, rounding mode,
boundary sampling — comes from an :class:`~repro.policy.base.EpochDecision`
(``decision.sites[i]`` at the i-th exchange). The decision is **static**: each
distinct decision traces its own executable, and the trainer caches compiled
steps per lattice-snapped decision so adaptive policies stay within a small
recompile budget. Omitting the decision falls back to the one global
``SylvieConfig`` choice (the Uniform degenerate case).

The decision (or config) also picks the exchange *schedule*: ``"blocking"``
consumes each halo where it is produced; ``"overlap"`` routes the same sites
through the issue/land double buffering of ``dist/overlap.py`` (bit-exact
under sync, the DESIGN §14 staleness contract under async). The schedule is
part of ``EpochDecision.step_key()``, so each schedule traces its own
executables within the same per-decision budget.

The steps also *emit telemetry for the policy loop*: ``state.site_stats`` is a
``(n_sites, 2)`` array of ``[sum of squared boundary-row ranges, live row
count]`` per exchange site, psum'd across partitions — the raw material for
AdaQP-style variance-budgeted bit assignment.

Weight gradients are all-reduced across partitions (Alg. 2 line 16): explicit
``lax.psum`` under shard_map; implicit via the stacked-axis contraction in the
simulated mode. When ``decision.ef_bits`` is set the reduced gradient then
passes through the EF21 compressor (``train/compression.py``) whose error /
estimate state lives in ``state.ef``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.staleness import HaloState
from ..core.sylvie import SCHEDULES, SylvieComm, SylvieConfig
from ..dist.backend import as_backend
from ..models import nn
from ..obs import TraceLog
from ..policy.base import EpochDecision, validate_decision
from . import optimizer as optlib
from .compression import EFState, ef_allreduce

# Trace instrumentation: step bodies append ("sync" | "async") here at trace
# time (the python body only runs when jit traces). tests/test_policy.py uses
# it to assert the recompile budget of adaptive policies; the TraceLog shim
# additionally counts ``retrace.train`` in the obs metrics registry.
TRACE_LOG = TraceLog("train")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GNNTrainState:
    params: dict
    opt_state: dict
    halo: HaloState
    step: jax.Array
    # EF21 compressed-all-reduce state (zeros / inert unless the epoch
    # decision sets ef_bits) and the per-site comm telemetry emitted by the
    # last step — (n_sites, 2): [sum of squared row ranges, live rows].
    ef: EFState
    site_stats: jax.Array
    # Per-epoch fault control block (repro.faults.plan.FaultCtl) — boolean
    # wire masks riding as *data*, set by the trainer each chaos epoch.
    # None = fault-free pytree structure, tracing the exact legacy program.
    faults: Optional[object] = None

    @staticmethod
    def create(model, opt, key, plan, stacked_parts=None):
        params = model.init(key)
        n_sites = len(model.comm_dims())
        return GNNTrainState(
            params=params, opt_state=opt.init(params),
            halo=HaloState.zeros(plan, model.comm_dims(),
                                 stacked_parts=stacked_parts),
            step=jnp.zeros((), jnp.int32),
            ef=EFState.zeros_like(params),
            site_stats=jnp.zeros((n_sites, 2), jnp.float32))


def _masked_loss(logits, y, mask, backend):
    s, c = nn.cross_entropy(logits, y, mask.astype(jnp.float32))
    return backend.psum(s) / jnp.maximum(backend.psum(c), 1.0)


def make_gnn_steps(model, cfg: SylvieConfig, opt: optlib.Optimizer,
                   backend=None, clip_norm: Optional[float] = None,
                   decision: Optional[EpochDecision] = None):
    """Builds (train_step_sync, train_step_async, eval_step). All three are pure
    and jit/shard_map-compatible; the caller decides which to invoke per epoch
    (a :class:`~repro.policy.base.CommPolicy` — ``GNNTrainer`` owns that loop).

    ``decision`` fixes the per-site communication schedule the steps are
    traced with; ``None`` builds the Uniform shim from ``cfg`` (bit-identical
    to the historical ``cfg.bits`` path). ``backend`` fixes the communicator
    (a :class:`repro.dist.backend.HaloBackend`; simulated stack by default).
    Steps built with a :class:`ShardMapBackend` must be wrapped via
    ``dist.api.shard_gnn_steps`` (or ``Runtime``) so their collectives find
    the mesh axes."""
    backend = as_backend(backend)
    n_sites = len(model.comm_dims())
    if decision is None:
        decision = EpochDecision.from_config(cfg, n_sites)
    decision = validate_decision(decision, n_sites)
    for sched in (cfg.schedule, decision.schedule):
        if sched not in SCHEDULES:
            raise ValueError(
                f"unknown schedule {sched!r}; known: {SCHEDULES}")
    sync_cfg = cfg if cfg.mode != "async" else cfg.replace(mode="sync")
    async_cfg = cfg.replace(mode="async")

    def _stats(comm):
        return backend.psum(jnp.stack(comm.site_stats))

    def _finish(state, params_grads, loss, new_halo, stats):
        # Alg. 2 line 16: weight gradients are all-reduced across partitions —
        # an explicit backend.psum under shard_map, the identity in the
        # simulated stack (whose contraction is already global).
        params_grads = jax.tree.map(backend.psum, params_grads)
        if decision.ef_bits is not None:
            # EF21 compression of the reduced gradient (deterministic, so the
            # error/estimate state stays replicated across partitions); wire
            # savings are accounted by compression.ef_wire_bytes.
            params_grads, new_ef = ef_allreduce(params_grads, state.ef,
                                                bits=decision.ef_bits)
        else:
            new_ef = state.ef
        if clip_norm is not None:
            params_grads, _ = optlib.clip_by_global_norm(params_grads, clip_norm)
        updates, new_opt = opt.update(params_grads, state.opt_state, state.params)
        new_params = optlib.apply_updates(state.params, updates)
        return GNNTrainState(new_params, new_opt, new_halo, state.step + 1,
                             new_ef, stats, state.faults), loss

    def train_step_sync(state: GNNTrainState, block, x, y, mask, key):
        TRACE_LOG.append("sync")

        def loss_fn(params):
            armed = state.faults is not None
            comm = SylvieComm(sync_cfg, block.plan, key, backend=backend,
                              decision=decision, collect_stats=True,
                              feat_caches=(state.halo.feats if armed else None),
                              fault_sites=(state.faults.sites if armed
                                           else None))
            logits = model.apply(params, block, x, comm)
            loss = _masked_loss(logits, y, mask, backend)
            caches = tuple(jax.lax.stop_gradient(c) for c in comm.new_feat_caches)
            return loss, (caches, _stats(comm))

        ((loss, (caches, stats)),
         grads) = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        new_halo = HaloState(feats=caches,
                             grads=tuple(jnp.zeros_like(f) for f in caches))
        return _finish(state, grads, loss, new_halo, stats)

    def train_step_async(state: GNNTrainState, block, x, y, mask, key):
        TRACE_LOG.append("async")

        def loss_fn(params, gslots):
            comm = SylvieComm(async_cfg, block.plan, key, backend=backend,
                              decision=decision, collect_stats=True,
                              feat_caches=state.halo.feats,
                              grad_ins=state.halo.grads, gslots=gslots,
                              fault_sites=(state.faults.sites
                                           if state.faults is not None
                                           else None),
                              inputs=x)
            logits = model.apply(params, block, x, comm)
            loss = _masked_loss(logits, y, mask, backend)
            caches = tuple(jax.lax.stop_gradient(c) for c in comm.new_feat_caches)
            return loss, (caches, _stats(comm))

        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
        ((loss, (caches, stats)),
         (pgrads, ggrads)) = grad_fn(state.params, state.halo.gslots())
        # A site fed by x never reads its gslot, so its slot here is zeros.
        # Passing the incoming cache through instead would keep that cache a
        # live argument of the step, which donates nothing, and still copy it.
        new_halo = HaloState(feats=caches, grads=ggrads)
        return _finish(state, pgrads, loss, new_halo, stats)

    def eval_step(params, block, x, y, mask, key):
        comm = SylvieComm(sync_cfg.replace(mode="vanilla", stochastic=False),
                          block.plan, key, backend=backend)
        logits = model.apply(params, block, x, comm)
        correct, count = nn.accuracy_counts(logits, y, mask.astype(jnp.float32))
        return backend.psum(correct), backend.psum(count)

    return train_step_sync, train_step_async, eval_step
