"""Mesh-level GNN sharding: spec derivation, step wrapping, placement.

The production shard_map path. GNN runtime arrays are *stacked* with a leading
partition axis ``P`` (one partition per mesh device); this module derives the
``PartitionSpec`` trees for a :class:`~repro.train.gnn_step.GNNTrainState` /
:class:`~repro.models.gnn.blocks.GraphBlock` pair, wraps the three step
functions in ``jax.shard_map`` via :class:`~repro.dist.backend.ShardMapBackend`,
and places host arrays onto the mesh.

Sharding contract (one partition per device):
  * model params / optimizer state / step counter — replicated (``P()``).
    shard_map runs with replication checking OFF (``check_vma=False``):
    nothing reduces the replicated params' cotangents at the boundary, so the
    step functions all-reduce weight gradients with an explicit
    ``backend.psum`` (Alg. 2 line 16) — do not remove that psum.
  * halo caches, graph block arrays, features/labels/masks — sharded on the
    leading partition axis over every mesh axis (``P(axes)``). This covers
    both halo-buffer layouts: dense ``(P, P*h_pad, d)`` and compact
    ``(P, sum(bucket_sizes), d)`` buffers shard identically (the layout lives
    in ``PlanArrays``' static metadata, not in the spec tree).
  * PRNG keys and scalar losses — replicated.

Structure-only: spec trees are built from the state/block *instances* (pytree
prefixes), so this module never imports the train or model layers and stays
import-cycle-free below ``core``/``train``.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from .backend import (HaloBackend, ShardMapBackend, SimulatedBackend,  # noqa: F401
                      as_backend)

# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------


def flat_axes(mesh) -> tuple[str, ...]:
    """Every mesh axis, flattened into one partition axis (paper: N devices =
    N partitions)."""
    return tuple(mesh.axis_names)


def mesh_size(mesh) -> int:
    out = 1
    for a in mesh.axis_names:
        out *= mesh.shape[a]
    return out


def make_gnn_mesh(n_parts: int | None = None, axis_name: str = "parts"):
    """A 1-D ``(n_parts,)`` mesh — the canonical GNN topology (one partition
    per device). Defaults to every visible device."""
    n = n_parts if n_parts is not None else len(jax.devices())
    return jax.make_mesh((n,), (axis_name,), axis_types=(AxisType.Auto,))


# ---------------------------------------------------------------------------
# spec derivation (pytree prefixes)
# ---------------------------------------------------------------------------


def gnn_state_specs(state, axes) -> Any:
    """Spec prefix for a GNNTrainState: params/opt/step replicated, halo
    caches sharded on the leading partition axis. The EF21 compressor state
    and the psum'd per-site comm telemetry are replicated (the compressor is
    deterministic on the already-reduced gradient; the stats are reduced
    inside the step)."""
    return type(state)(params=P(), opt_state=P(), halo=P(axes), step=P(),
                       ef=P(), site_stats=P(),
                       # fault masks are (P, rows) wire masks — stacked like
                       # the halo buffers they condemn; None stays None (the
                       # fault-free structure).
                       faults=None if state.faults is None else P(axes))


def gnn_block_spec(axes) -> P:
    """Every GraphBlock array (edges, masks, plan, weights) is stacked."""
    return P(axes)


def gnn_data_spec(axes) -> P:
    """Features ``(P, n_local, d)``, labels and masks ``(P, n_local)``."""
    return P(axes)


# ---------------------------------------------------------------------------
# step wrapping + placement
# ---------------------------------------------------------------------------


def shard_gnn_steps(train_sync, train_async, eval_step, mesh, state, block):
    """Wrap the three GNN step functions (see ``train.gnn_step``) in
    ``jit(shard_map(...))`` over ``mesh``. The steps must have been built with
    a :class:`ShardMapBackend` for the same mesh so their internal exchanges
    and psums name these axes.

    Returns ``(train_sync, train_async, eval_step)`` wrapped; call signatures
    are unchanged.
    """
    del block  # the block spec is a pure prefix — kept for API symmetry
    axes = flat_axes(mesh)
    backend = ShardMapBackend(mesh)
    st = gnn_state_specs(state, axes)
    blk = gnn_block_spec(axes)
    data = gnn_data_spec(axes)
    rep = P()
    train_in = (st, blk, data, data, data, rep)
    ts = backend.shard(train_sync, in_specs=train_in, out_specs=(st, rep))
    ta = backend.shard(train_async, in_specs=train_in, out_specs=(st, rep))
    ev = backend.shard(eval_step, in_specs=(rep, blk, data, data, data, rep),
                       out_specs=(rep, rep))
    return ts, ta, ev


def shard_serve_fn(sweep_fn, mesh):
    """Wrap the serving sweep (see ``repro.serve.engine``) in
    ``jit(shard_map(...))``. Signature contract:
    ``sweep_fn(params, block, x, halo_caches, send_masks, key) ->
    (logits, layer_inputs, halo_caches)`` — params/key replicated, everything
    else stacked on the leading partition axis (the specs are pytree
    prefixes, so the halo-cache / mask / layer tuples need no per-leaf
    spelling)."""
    axes = flat_axes(mesh)
    backend = ShardMapBackend(mesh)
    sh, rep = P(axes), P()
    return backend.shard(sweep_fn, in_specs=(rep, sh, sh, sh, sh, rep),
                         out_specs=(sh, sh, sh))


def device_put_gnn(mesh, state, block, arrays=()):
    """Place (state, block, *arrays) onto ``mesh`` under the GNN sharding
    contract. ``arrays`` are per-node stacked arrays (x, y, masks, ...).

    Returns ``(state, block, arrays)`` device-resident.
    """
    axes = flat_axes(mesh)
    backend = ShardMapBackend(mesh)
    sharded, rep = P(axes), P()
    state_d = type(state)(
        params=backend.device_put(state.params, rep),
        opt_state=backend.device_put(state.opt_state, rep),
        halo=backend.device_put(state.halo, sharded),
        step=backend.device_put(state.step, rep),
        ef=backend.device_put(state.ef, rep),
        site_stats=backend.device_put(state.site_stats, rep),
        faults=(None if state.faults is None
                else backend.device_put(state.faults, sharded)))
    block_d = backend.device_put(block, sharded)
    arrays_d = tuple(backend.device_put(a, sharded) for a in arrays)
    return state_d, block_d, arrays_d
