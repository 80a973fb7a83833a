"""GNN trainer: epoch loop, the CommPolicy loop, eval, checkpoint/restart,
EF21 gradient compression, metrics.

One :class:`GNNTrainer` drives either execution mode through a
:class:`repro.dist.runtime.Runtime`:
  * ``Runtime.simulated(...)`` (the default on 1 CPU device) — the stacked
    reference semantics used by tests/benchmarks;
  * ``Runtime.from_mesh(mesh)`` — shard_map, one partition per device (the
    production path).

The **policy loop** lives here. Once per epoch, *outside the trace*:

  1. telemetry is assembled from host-side observations (epoch index, the
     EMA-smoothed per-site range stats the previous step emitted, the val
     trajectory, the resume/elastic ``needs_sync`` flag);
  2. ``policy.decide(telemetry)`` maps it to an
     :class:`~repro.policy.base.EpochDecision` — per-site fwd/bwd bit-widths,
     rounding, boundary sampling, EF bits, and the sync/async choice;
  3. the decision is snapped to the lattice (``decision.snapped()``) and used
     as the key of a compiled-step cache, so jit compiles one executable per
     *distinct* decision — a drifting policy cannot trigger unbounded
     recompilation.

``SylvieConfig(bits=...)`` (no policy) degenerates to the ``Uniform`` policy
and is bit-identical to the historical static path. The paper's Bounded
Staleness Adaptor (§3.3) is ``policy=BoundedStaleness(eps_s)``; the old
``eps_s=`` kwarg survives as a deprecation shim that builds exactly that.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.exchange import exchange_bytes, wire_bytes
from ..core.sylvie import SylvieConfig
from ..dist.runtime import Runtime
from ..faults.backend import FaultyBackend
from ..faults.plan import FaultCtl, FaultPlan, RowGeometry
from ..models.gnn import blocks as B
from ..obs import profiler as obs_profiler
from ..policy.base import (CommPolicy, EpochDecision, SiteStats, Telemetry,
                           validate_decision)
from ..policy.builtin import BoundedStaleness, Uniform
from . import checkpoint as ckpt
from . import optimizer as optlib
from .compression import ef_wire_bytes
from .gnn_step import GNNTrainState, make_gnn_steps

# host spans also land in the JAX profiler's trace, on the device clock
obs_profiler.install()

# EMA smoothing factor for the per-site range stats fed back to policies —
# damps epoch-to-epoch jitter so adaptive bit assignments settle on one
# lattice point instead of oscillating (recompile budget).
STATS_EMA = 0.5


@dataclasses.dataclass
class EpochMetrics:
    epoch: int
    loss: float
    seconds: float
    mode: str
    comm_payload_mb: float
    comm_ec_mb: float
    val_acc: Optional[float] = None
    # exchange schedule actually traced this epoch ("blocking" | "overlap").
    schedule: str = "blocking"
    # per-site (fwd_bits, bwd_bits) actually used this epoch + the policy
    # that chose them (heterogeneous-bits accounting).
    bits_per_site: tuple = ()
    policy: str = ""
    ef_bits: Optional[int] = None
    # chaos accounting (unit = one scheduled drop/corrupt message). Invariant:
    # faults_injected == halos_reused + forced_syncs, exactly — a normal
    # faulty epoch recovers every unit from the stale cache, a recovery epoch
    # suppresses its whole schedule and retries synchronously. ``stall_s`` is
    # the modeled straggler critical-path extension (not wall clock).
    faults_injected: int = 0
    halos_reused: int = 0
    forced_syncs: int = 0
    stall_s: float = 0.0
    # measured whole-epoch wall time on the obs clock (decide + fault arming
    # + step + telemetry absorption), vs ``seconds`` = the step call alone
    # (dispatch + loss read-back). Deterministic under an injected FakeClock.
    wall_s: float = 0.0


class GNNTrainer:
    """Full-graph trainer over a partitioned graph.

    Example::

        pg, _ = datasets.load_partitioned("yelp_like@small", n_parts=4)
        tr = GNNTrainer(GCN(pg.x.shape[-1], 64, pg.n_classes), pg,
                        SylvieConfig(mode="async", bits=1),
                        policy=BoundedStaleness(eps_s=4))
        tr.fit(40); tr.evaluate("test")

    .. deprecated:: ``eps_s=k`` — the pre-policy staleness knob. It now
       builds ``policy=BoundedStaleness(eps_s=k, bits=cfg.effective_bits,
       stochastic=cfg.stochastic, boundary_sample_p=cfg.boundary_sample_p)``
       and warns; pass that policy yourself instead.
    """

    def __init__(self, model, pg, cfg: Optional[SylvieConfig] = None,
                 opt: Optional[optlib.Optimizer] = None,
                 policy: Optional[CommPolicy] = None,
                 eps_s: Optional[int] = None,
                 runtime: Optional[Runtime] = None, mesh=None, seed: int = 0,
                 ckpt_dir: Optional[str] = None, keep: int = 3,
                 fault_plan: Optional[FaultPlan] = None,
                 ckpt_every: Optional[int] = None):
        self.model = model
        self.pg = pg
        self.cfg = cfg = cfg if cfg is not None else SylvieConfig()
        if eps_s is not None:
            warnings.warn(
                "GNNTrainer(eps_s=...) is deprecated; pass "
                "policy=repro.policy.BoundedStaleness(eps_s) instead",
                DeprecationWarning, stacklevel=2)
            if policy is not None:
                raise ValueError("pass policy or eps_s, not both")
            policy = BoundedStaleness(
                eps_s=eps_s, bits=cfg.effective_bits,
                stochastic=cfg.stochastic,
                boundary_sample_p=cfg.boundary_sample_p)
        self.policy: CommPolicy = policy if policy is not None \
            else Uniform.from_config(cfg)
        p = pg.plan.n_parts
        if runtime is not None and mesh is not None:
            raise ValueError("pass runtime or mesh, not both "
                             "(mesh is shorthand for Runtime.from_mesh)")
        if runtime is None:
            runtime = (Runtime.from_mesh(mesh) if mesh is not None
                       else Runtime.simulated(p))
        if runtime.n_parts not in (None, p):
            raise ValueError(
                f"runtime is committed to {runtime.n_parts} partitions but the "
                f"graph was partitioned into {p}")
        # a chaos run is any of: fault_plan=..., or a runtime whose backend is
        # already a FaultyBackend (the plan is then discovered from it).
        if isinstance(runtime.backend, FaultyBackend):
            if fault_plan is not None and fault_plan != runtime.backend.plan:
                raise ValueError("runtime backend already carries a FaultPlan "
                                 "that differs from fault_plan")
            fault_plan = runtime.backend.plan
        elif fault_plan is not None:
            runtime = Runtime(FaultyBackend(runtime.backend, fault_plan))
        self.fault_plan = fault_plan
        self.ckpt_every = ckpt_every
        self.runtime = runtime
        self.mesh = runtime.mesh
        self.opt = opt or optlib.adam(1e-2)
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self.key = jax.random.PRNGKey(seed)

        self.block = B.build_block(
            pg, transposed=getattr(model, "transposed_edges", False))
        self.x = jnp.asarray(pg.x)
        self.y = jnp.asarray(pg.y)
        self.train_mask = jnp.asarray(pg.train_mask)
        self.val_mask = jnp.asarray(pg.val_mask)
        self.test_mask = jnp.asarray(pg.test_mask)
        self.site_dims = tuple(int(d) for d in model.comm_dims())
        self.n_sites = len(self.site_dims)
        self.state = GNNTrainState.create(self.model, self.opt, self.key,
                                          self.block.plan, stacked_parts=p)
        # compiled train steps per distinct (snapped) decision; eval is
        # decision-independent (always full precision) and built once.
        self._step_cache: dict = {}
        ts0, ta0, ev = make_gnn_steps(self.model, cfg, self.opt,
                                      backend=runtime.backend)
        _, _, self._ev = runtime.shard_gnn_steps(ts0, ta0, ev, self.state,
                                                 self.block)
        self.state, self.block, arrs = runtime.device_put_gnn(
            self.state, self.block,
            (self.x, self.y, self.train_mask, self.val_mask, self.test_mask))
        (self.x, self.y, self.train_mask, self.val_mask,
         self.test_mask) = arrs
        self.epoch = 0
        self.history: list[EpochMetrics] = []
        self._needs_sync = False
        self._site_stats: Optional[tuple[SiteStats, ...]] = None
        self._last_decision: Optional[EpochDecision] = None
        # chaos state: per-site consecutive-faulty-epoch counters (the
        # escalation rule watches their max) and the force-recovery latch set
        # when a site crosses ``fault_plan.escalate_after``.
        self._fault_geom = (RowGeometry.from_plan(self.block.plan)
                            if self.fault_plan is not None else None)
        self._site_staleness = np.zeros(self.n_sites, np.int64)
        self._force_recovery = False

    # ------------------------------------------------------------------
    # the policy loop
    # ------------------------------------------------------------------
    def _telemetry(self) -> Telemetry:
        return Telemetry(
            epoch=self.epoch, n_parts=self.pg.plan.n_parts,
            n_sites=self.n_sites, site_dims=self.site_dims,
            site_stats=self._site_stats,
            val_history=tuple(m.val_acc for m in self.history
                              if m.val_acc is not None),
            needs_sync=self._needs_sync, prev=self._last_decision,
            site_staleness=(tuple(int(x) for x in self._site_staleness)
                            if self.fault_plan is not None else ()))

    def _decide(self) -> EpochDecision:
        """Pure: telemetry -> snapped EpochDecision (callable speculatively,
        e.g. for byte accounting before any epoch ran). Mode invariants are
        enforced here, not trusted to the policy: vanilla pins 32-bit, only
        async mode may skip the synchronous step, epoch 0 always runs it (the
        zero-initialized halo caches must be warmed before any pipelined
        step), and a pending cache refresh (``needs_sync``) always wins."""
        d = self.policy.decide(self._telemetry()).snapped()
        d = validate_decision(d, self.n_sites)
        if self.cfg.mode == "vanilla":
            d = d.with_bits(32)
        sync = (bool(d.sync) or self.cfg.mode != "async" or self._needs_sync
                or self.epoch == 0)
        # the exchange schedule is an execution-mode choice, not a precision
        # one: the config owns it (policies cannot flip it mid-run, so one
        # trainer stays within the per-decision recompile budget).
        return dataclasses.replace(d, sync=sync, schedule=self.cfg.schedule)

    def _steps_for(self, decision: EpochDecision):
        """(train_sync, train_async) compiled for this decision. Cached on
        ``decision.step_key()`` (sync excluded — it picks *which* step runs),
        so distinct executables are bounded by distinct lattice points. A miss
        opens the ``build`` span; the new step is traced and compiled in the
        ``dispatch`` that first calls it."""
        key = decision.step_key()
        if key not in self._step_cache:
            with obs.span("build"):
                ts, ta, ev = make_gnn_steps(self.model, self.cfg, self.opt,
                                            backend=self.runtime.backend,
                                            decision=decision)
                ts, ta, _ = self.runtime.shard_gnn_steps(ts, ta, ev,
                                                         self.state,
                                                         self.block)
            self._step_cache[key] = (ts, ta)
        return self._step_cache[key]

    def _absorb_site_stats(self):
        """Fold the step's emitted (n_sites, 2) [sum range^2, live rows] into
        the EMA-smoothed SiteStats telemetry."""
        with obs.span("readback.stats"):
            raw = np.asarray(jax.device_get(self.state.site_stats))
        rows = self.block.plan.real_rows
        cur = []
        for i, d in enumerate(self.site_dims):
            mean_sq = float(raw[i, 0]) / max(float(raw[i, 1]), 1.0)
            if self._site_stats is not None:
                prev = self._site_stats[i].mean_range_sq
                mean_sq = STATS_EMA * prev + (1.0 - STATS_EMA) * mean_sq
            cur.append(SiteStats(dim=d, rows=rows, mean_range_sq=mean_sq))
        self._site_stats = tuple(cur)

    # ------------------------------------------------------------------
    # heterogeneous-bits comm accounting
    # ------------------------------------------------------------------
    def _bytes_per_epoch(self, bytes_fn,
                         decision: Optional[EpochDecision] = None):
        """Sum per-site, per-direction bytes under the epoch's actual
        decision (forward and backward exchanges may use different widths)."""
        if decision is None:
            decision = self._last_decision or self._decide()
        payload = ec = 0
        for d, sd in zip(self.site_dims, decision.sites):
            for bits in (sd.fwd_bits, sd.bwd_bits):
                pb, eb = bytes_fn(self.block.plan, d, bits,
                                  self.cfg.scale_dtype)
                payload += pb
                ec += eb
        if decision.ef_bits is not None:
            pb, eb = ef_wire_bytes(self.state.params, decision.ef_bits)
            payload += pb
            ec += eb
        return payload, ec

    def comm_bytes_per_epoch(self, decision: Optional[EpochDecision] = None
                             ) -> tuple[float, float]:
        """(payload, error-compensation) *true wire* bytes moved per epoch,
        totaled across partitions. Diagonal self-blocks and padding rows are
        excluded (Table 3). Defaults to the last epoch's decision (or the
        policy's next decision before any epoch ran)."""
        return self._bytes_per_epoch(exchange_bytes, decision)

    def wire_bytes_per_epoch(self, decision: Optional[EpochDecision] = None
                             ) -> tuple[float, float]:
        """Like :meth:`comm_bytes_per_epoch` but counting the rows the plan's
        layout actually ships (incl. bucket-alignment / pairwise padding) —
        the layout-efficiency number the compact plan optimizes."""
        return self._bytes_per_epoch(wire_bytes, decision)

    def _epoch_key(self):
        return jax.random.fold_in(self.key, self.epoch)

    # ------------------------------------------------------------------
    # chaos: arm the epoch's seeded fault schedule
    # ------------------------------------------------------------------
    def _arm_faults(self, decision: EpochDecision):
        """Draw this epoch's seeded fault set, expand it to wire masks in
        ``state.faults`` (data — armed epochs share one executable), and do
        the staleness-as-recovery bookkeeping.

        Returns ``(decision, injected, reused, forced, stall_s, escalate)``.
        A recovery epoch (the latch set by a previous escalation) suppresses
        the whole schedule — all-false masks, same pytree structure — and
        retries as a full-precision synchronous exchange; its scheduled units
        are accounted as ``forced_syncs``. Otherwise every scheduled unit is
        recovered from the stale cache (``halos_reused``), keeping
        ``faults_injected == halos_reused + forced_syncs`` exact."""
        plan = self.fault_plan
        ev = plan.events(self.epoch, self.n_sites, self.pg.plan.n_parts)
        injected = ev.n_injected
        escalate = False
        if self._force_recovery:
            decision = dataclasses.replace(decision.with_bits(32), sync=True)
            ctl = FaultCtl.clean(self._fault_geom, self.n_sites)
            reused, forced, stall = 0, injected, 0.0
            self._site_staleness[:] = 0
            self._force_recovery = False
        else:
            ctl = FaultCtl.expand(ev, self._fault_geom, self.n_sites)
            reused, forced = injected, 0
            stall = ev.stall_s(plan.delay_s)
            self._site_staleness = np.where(ev.faulty_sites(),
                                            self._site_staleness + 1, 0)
            if int(self._site_staleness.max(initial=0)) >= plan.escalate_after:
                escalate = True  # applied to the *next* epoch, below
        self.state = dataclasses.replace(
            self.state, faults=self.runtime.device_put_stacked(ctl))
        return decision, injected, reused, forced, stall, escalate

    def train_epoch(self) -> EpochMetrics:
        w0 = obs.clock()
        # the profiler's step marker ("train", step_num = epoch) around the
        # epoch span
        with jax.profiler.StepTraceAnnotation("train", step_num=self.epoch), \
                obs.span("epoch", {"epoch": self.epoch}):
            with obs.span("decide"):
                decision = self._decide()
            injected = reused = forced = 0
            stall = 0.0
            escalate = False
            if self.fault_plan is not None:
                (decision, injected, reused, forced, stall,
                 escalate) = self._arm_faults(decision)
                obs.count("faults.injected", injected)
                obs.count("faults.halos_reused", reused)
                obs.count("faults.forced_syncs", forced)
            ts, ta = self._steps_for(decision)
            fn = ts if decision.sync else ta
            t0 = obs.clock()
            with obs.span("step",
                          {"mode": "sync" if decision.sync else "async"}):
                with obs.span("dispatch"):
                    self.state, loss = fn(self.state, self.block, self.x,
                                          self.y, self.train_mask,
                                          self._epoch_key())
                with obs.span("readback.loss"):
                    loss = float(loss)
            dt = obs.clock() - t0
            self._needs_sync = False
            if escalate:
                # staleness-as-recovery escalation: some site has been faulted
                # for >= escalate_after consecutive epochs; the next epoch is a
                # forced full-precision synchronous retry (BoundedStaleness
                # also sees the counters via Telemetry.site_staleness).
                self._needs_sync = True
                self._force_recovery = True
            self._last_decision = decision
            self._absorb_site_stats()
            pb, eb = self.comm_bytes_per_epoch(decision)
            m = EpochMetrics(self.epoch, loss, dt,
                             "sync" if decision.sync else "async",
                             pb / 1e6, eb / 1e6,
                             schedule=decision.schedule,
                             bits_per_site=decision.bits_per_site(),
                             policy=self.policy.name,
                             ef_bits=decision.ef_bits,
                             faults_injected=injected, halos_reused=reused,
                             forced_syncs=forced, stall_s=stall)
        m.wall_s = obs.clock() - w0
        self.history.append(m)
        self.epoch += 1
        return m

    def compiled_step_text(self, sync: bool) -> str:
        """Optimized HLO of the sync or async train step under the last
        epoch's decision — shows which kernels and collectives it runs."""
        ts, ta = self._steps_for(self._last_decision or self._decide())
        return (ts if sync else ta).lower(
            self.state, self.block, self.x, self.y, self.train_mask,
            self._epoch_key()).compile().as_text()

    def evaluate(self, split: str = "val") -> float:
        mask = {"train": self.train_mask, "val": self.val_mask,
                "test": self.test_mask}[split]
        c, n = self._ev(self.state.params, self.block, self.x, self.y, mask,
                        self._epoch_key())
        return float(c) / max(float(n), 1.0)

    def fit(self, epochs: int, eval_every: int = 0) -> list[EpochMetrics]:
        # auto-checkpoint cadence: explicit ``ckpt_every`` epochs (preemption-
        # safe runs want every epoch) or 5 checkpoints over the run.
        every = self.ckpt_every if self.ckpt_every else max(1, epochs // 5)
        for _ in range(epochs):
            m = self.train_epoch()
            if eval_every and self.epoch % eval_every == 0:
                m.val_acc = self.evaluate("val")
            if self.ckpt_dir and self.epoch % every == 0:
                self.save()
        return self.history

    # ------------------------------------------------------------------
    def save(self):
        meta = dict(n_parts=self.pg.plan.n_parts, epoch=self.epoch,
                    mode=self.cfg.mode, policy=self.policy.name)
        ckpt.save(self.ckpt_dir, self.epoch, self.state, meta, keep=self.keep)

    def resume(self) -> bool:
        """Restore the latest checkpoint if present. Returns True if resumed.
        An elastic repartition (different n_parts) zeroes halo caches and
        forces one synchronous epoch (``Telemetry.needs_sync`` — every
        built-in policy honors it, and ``_decide`` enforces it regardless)."""
        step = ckpt.latest_step(self.ckpt_dir) if self.ckpt_dir else None
        if step is None:
            return False
        tree, meta, needs_sync = ckpt.restore(self.ckpt_dir, self.state)
        self.state = jax.tree.map(jnp.asarray, tree)
        self.state, self.block, _ = self.runtime.device_put_gnn(
            self.state, self.block, ())
        self.epoch = int(meta.get("epoch", step))
        self._needs_sync = needs_sync or \
            meta.get("n_parts") != self.pg.plan.n_parts
        if self.fault_plan is not None:
            # staleness counters are host state, not checkpointed — start the
            # resumed run conservatively clean (the first post-resume epoch is
            # synchronous anyway via needs_sync/epoch-0 rules only if flagged).
            self._site_staleness[:] = 0
            self._force_recovery = False
        return True
