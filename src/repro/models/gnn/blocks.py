"""GraphBlock: the device-side partitioned graph + message-passing primitives.

JAX is BCOO-only for sparse — all message passing here is explicit
gather-over-edge-index -> ``jax.ops.segment_sum``/``segment_max`` scatter, vmapped
over the leading partition axis (size 1 per device under shard_map; size P in the
simulated single-process mode). This IS the SpMM/SDDMM layer of the system; the
Pallas kernel in ``repro/kernels/spmm`` implements the same contract for the TPU
hot path.

The gathers over the edge list, the segment reductions and the per-edge
softmax run under ``jax.named_scope("aggregation")``: the compiled program's
``op_name`` metadata names every operation they lower to (``jvp(...)`` /
``transpose(jvp(...))`` in the backward), so a profile can be split by layer.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.exchange import PlanArrays
from ...core.scopes import scoped
from ...graph.partition import PartitionedGraph, PartitionShapeSpec
from . import so3

NEG = -1e30


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphBlock:
    """Static per-partition graph data (stacked leading axis P)."""

    edges: jax.Array                      # (P, E, 2) int32 [src_ext, dst_local]
    edge_mask: jax.Array                  # (P, E) bool
    node_mask: jax.Array                  # (P, n_local) bool
    plan: PlanArrays
    edge_weight: Optional[jax.Array] = None   # (P, E) — GCN-normalized A+I weights
    edge_attr: Optional[jax.Array] = None     # (P, E, d_e) — [dist | unit | sh...]
    n_local: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def n_parts(self):
        return self.plan.n_parts


def geometry_edge_attr(g, l_max: int = 2) -> np.ndarray:
    """Per-edge [dist, unit(3), sh((l_max+1)^2)] computed on the *global* graph
    (host-side, before partitioning — halo positions never move at runtime)."""
    src, dst = g.edge_index
    vec = g.pos[src] - g.pos[dst]
    dist = np.linalg.norm(vec, axis=-1, keepdims=True)
    unit = vec / np.maximum(dist, 1e-9)
    sh = so3.real_sh_np(unit, l_max)
    return np.concatenate([dist, unit, sh], axis=-1).astype(np.float32)


def build_block(pg: PartitionedGraph) -> GraphBlock:
    return GraphBlock(
        edges=jnp.asarray(pg.edges), edge_mask=jnp.asarray(pg.edge_mask),
        node_mask=jnp.asarray(pg.node_mask),
        plan=PlanArrays.from_plan(pg.plan),
        edge_weight=None if pg.edge_weight is None else jnp.asarray(pg.edge_weight),
        edge_attr=None if pg.edge_attr is None else jnp.asarray(pg.edge_attr),
        n_local=pg.plan.n_local)


def block_spec(spec: PartitionShapeSpec, d_edge_attr: int = 0,
               with_weight: bool = True, stacked_parts: int | None = None) -> GraphBlock:
    """ShapeDtypeStruct GraphBlock for the dry-run (no allocation)."""
    p = stacked_parts if stacked_parts is not None else spec.n_parts
    sds = jax.ShapeDtypeStruct
    return GraphBlock(
        edges=sds((p, spec.e_pad, 2), jnp.int32),
        edge_mask=sds((p, spec.e_pad), jnp.bool_),
        node_mask=sds((p, spec.n_local), jnp.bool_),
        plan=PlanArrays.from_spec(spec),
        edge_weight=sds((p, spec.e_pad), jnp.float32) if with_weight else None,
        edge_attr=sds((p, spec.e_pad, d_edge_attr), jnp.float32) if d_edge_attr else None,
        n_local=spec.n_local)


# --- message-passing primitives -------------------------------------------------
def halo_table(h: jax.Array, halo: jax.Array) -> jax.Array:
    """[local ; halo] feature table addressed by extended src indices."""
    return jnp.concatenate([h, halo], axis=1)


@scoped("aggregation")
def gather_src(block: GraphBlock, table: jax.Array) -> jax.Array:
    return jnp.take_along_axis(table, block.edges[..., 0:1], axis=1)


@scoped("aggregation")
def gather_dst(block: GraphBlock, h: jax.Array) -> jax.Array:
    return jnp.take_along_axis(h, block.edges[..., 1:2], axis=1)


def _seg(fn, msgs, dst, n_local):
    return jax.vmap(partial(fn, num_segments=n_local))(msgs, dst)


@scoped("aggregation")
def agg_sum(block: GraphBlock, msgs: jax.Array) -> jax.Array:
    msgs = jnp.where(block.edge_mask[..., None], msgs, 0)
    return _seg(jax.ops.segment_sum, msgs, block.edges[..., 1], block.n_local)


@scoped("aggregation")
def agg_max(block: GraphBlock, msgs: jax.Array) -> jax.Array:
    msgs = jnp.where(block.edge_mask[..., None], msgs, NEG)
    out = _seg(jax.ops.segment_max, msgs, block.edges[..., 1], block.n_local)
    return jnp.where(out <= NEG / 2, 0.0, out)


@scoped("aggregation")
def agg_min(block: GraphBlock, msgs: jax.Array) -> jax.Array:
    return -agg_max(block, -msgs)


@scoped("aggregation")
def degrees(block: GraphBlock) -> jax.Array:
    ones = block.edge_mask.astype(jnp.float32)
    return jax.vmap(partial(jax.ops.segment_sum, num_segments=block.n_local))(
        ones, block.edges[..., 1])


@scoped("aggregation")
def agg_mean(block: GraphBlock, msgs: jax.Array) -> jax.Array:
    s = agg_sum(block, msgs)
    d = degrees(block)
    return s / jnp.maximum(d, 1.0)[..., None]


@scoped("aggregation")
def agg_std(block: GraphBlock, msgs: jax.Array, eps: float = 1e-5) -> jax.Array:
    mu = agg_mean(block, msgs)
    mu2 = agg_mean(block, msgs * msgs)
    return jnp.sqrt(jnp.maximum(mu2 - mu * mu, 0.0) + eps)


@scoped("aggregation")
def edge_softmax(block: GraphBlock, scores: jax.Array) -> jax.Array:
    """Per-dst softmax over incoming edges; scores (P, E, H) -> alphas (P, E, H)."""
    dst = block.edges[..., 1]
    s = jnp.where(block.edge_mask[..., None], scores, NEG)
    smax = _seg(jax.ops.segment_max, s, dst, block.n_local)
    smax = jnp.where(smax <= NEG / 2, 0.0, smax)
    e = jnp.exp(s - jnp.take_along_axis(smax, dst[..., None], axis=1))
    e = jnp.where(block.edge_mask[..., None], e, 0.0)
    z = _seg(jax.ops.segment_sum, e, dst, block.n_local)
    return e / jnp.maximum(jnp.take_along_axis(z, dst[..., None], axis=1), 1e-16)
