"""GraphBlock: the device-side partitioned graph + message-passing primitives.

JAX is BCOO-only for sparse — all message passing here is explicit
gather-over-edge-index -> ``jax.ops.segment_sum``/``segment_max`` scatter, vmapped
over the leading partition axis (size 1 per device under shard_map; size P in the
simulated single-process mode). This IS the SpMM/SDDMM layer of the system; the
Pallas kernel in ``repro/kernels/spmm`` implements the same contract for the TPU
hot path.

Each partition's edges are sorted by destination (``partition_graph``), so
every segment reduction passes ``indices_are_sorted`` and the compiler sorts
nothing. GCN and GraphSAGE-mean, whose message is the source row times a
constant edge scalar, take :func:`agg_weighted`: the weighted gather and
the sorted scatter in one expression, whose ``custom_vjp`` transposes over
the edges sorted by source (``GraphBlock.edges_t``), so the backward scatter
is sorted too and no per-edge tensor is saved. Models whose message is a
per-edge function (GAT, PNA, MeshGraphNet, SchNet, NequIP) keep the per-edge
gather and scatter.

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

from ... import obs
from ...core.exchange import PlanArrays
from ...core.scopes import scoped
from ...graph.partition import PartitionedGraph, PartitionShapeSpec
from . import so3

NEG = -1e30


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphBlock:
    """Static per-partition graph data (stacked leading axis P)."""

    edges: jax.Array                      # (P, E, 2) int32 [src_ext, dst_local], sorted by dst
    edge_mask: jax.Array                  # (P, E) bool
    node_mask: jax.Array                  # (P, n_local) bool
    plan: PlanArrays
    edge_weight: Optional[jax.Array] = None   # (P, E) — GCN-normalized A+I weights
    edge_attr: Optional[jax.Array] = None     # (P, E, d_e) — [dist | unit | sh...]
    # the edges stably sorted by src_ext, for agg_weighted's backward; built
    # only for the models that read it (``transposed_edges``)
    edges_t: Optional[jax.Array] = None       # (P, E, 2) int32 [dst_local, src_ext]
    edge_weight_t: Optional[jax.Array] = None  # (P, E) edge_weight in that order
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


def build_block(pg: PartitionedGraph, transposed: bool = True) -> GraphBlock:
    """The device block of ``pg``; ``transposed`` adds the edges sorted by
    source, which only a model with ``transposed_edges`` reads, and only to
    differentiate. The padding edges address the last table row, so they
    stay last in that order too."""
    edges_t = weight_t = None
    if transposed:
        order = np.argsort(pg.edges[..., 0], axis=1, kind="stable")
        edges_t = jnp.asarray(np.take_along_axis(pg.edges[..., ::-1],
                                                 order[..., None], axis=1))
        if pg.edge_weight is not None:
            weight_t = jnp.asarray(np.take_along_axis(pg.edge_weight, order,
                                                      axis=1))
    return GraphBlock(
        edges=jnp.asarray(pg.edges), edge_mask=jnp.asarray(pg.edge_mask),
        node_mask=jnp.asarray(pg.node_mask),
        plan=PlanArrays.from_plan(pg.plan),
        edge_weight=None if pg.edge_weight is None else jnp.asarray(pg.edge_weight),
        edge_attr=None if pg.edge_attr is None else jnp.asarray(pg.edge_attr),
        edges_t=edges_t, edge_weight_t=weight_t, n_local=pg.plan.n_local)


def block_spec(spec: PartitionShapeSpec, d_edge_attr: int = 0,
               with_weight: bool = True, stacked_parts: int | None = None,
               transposed: bool = True) -> GraphBlock:
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
        edges_t=sds((p, spec.e_pad, 2), jnp.int32) if transposed else None,
        edge_weight_t=(sds((p, spec.e_pad), jnp.float32)
                       if transposed and with_weight else None),
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
    return jax.vmap(partial(fn, num_segments=n_local,
                            indices_are_sorted=True))(msgs, dst)


@scoped("aggregation")
def agg_sum(block: GraphBlock, msgs: jax.Array) -> jax.Array:
    msgs = jnp.where(block.edge_mask[..., None], msgs, 0)
    return _seg(jax.ops.segment_sum, msgs, block.edges[..., 1], block.n_local)


def _weighted_sum(table, src, w, dst, n_out: int):
    """``out[p, dst[p, e]] += w[p, e] * table[p, src[p, e]]``, ``dst``
    non-decreasing in each partition: (P, rows, d) -> (P, n_out, d). The
    partition axis is folded into the row indices, so the gather and the
    sorted scatter have no batch dimension and the compiler can fuse the
    gather and the weight into the scatter."""
    p, rows, d = table.shape
    base = jnp.arange(p, dtype=jnp.int32)[:, None]
    msgs = table.reshape(p * rows, d).at[(src + base * rows).reshape(-1)].get(
        mode="promise_in_bounds") * w.reshape(-1, 1)
    out = jax.ops.segment_sum(msgs, (dst + base * n_out).reshape(-1),
                              num_segments=p * n_out, indices_are_sorted=True,
                              mode="promise_in_bounds")
    return out.reshape(p, n_out, d)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _spmm(n_local, n_rows, table, fwd, bwd):
    return _weighted_sum(table, *fwd, n_local)


def _spmm_fwd(n_local, n_rows, table, fwd, bwd):
    return _weighted_sum(table, *fwd, n_local), bwd


def _spmm_bwd(n_local, n_rows, bwd, g):
    if bwd is None:
        raise ValueError("agg_weighted's backward reads the edges sorted by "
                         "source: build the block with transposed=True")
    with jax.named_scope("aggregation"):
        return _weighted_sum(g, *bwd, n_rows), None, None


_spmm.defvjp(_spmm_fwd, _spmm_bwd)


@scoped("aggregation")
def agg_weighted(block: GraphBlock, table: jax.Array,
                 mean: bool = False) -> jax.Array:
    """``sum_{e: u -> v} w_e * table[u]`` for every local node ``v``,
    (P, rows, d) -> (P, n_local, d): ``agg_sum`` over ``gather_src`` times
    the edge weight (GCN), or with ``mean=True`` ``agg_mean`` over
    ``gather_src`` (GraphSAGE-mean), each row's sum in edge order as theirs.
    Its transpose runs over ``block.edges_t`` and saves no per-edge tensor."""
    obs.count("aggregation.slotted")
    if mean:
        w = w_t = block.edge_mask.astype(table.dtype)
    elif block.edge_weight is None:
        raise ValueError("agg_weighted needs edge weights (gcn_normalize) "
                         "unless mean=True")
    else:
        w, w_t = block.edge_weight, block.edge_weight_t
    bwd = None
    if block.edges_t is not None:
        bwd = (block.edges_t[..., 0], w_t, block.edges_t[..., 1])
    out = _spmm(block.n_local, table.shape[1], table,
                (block.edges[..., 0], w, block.edges[..., 1]), bwd)
    if mean:
        out = out / jnp.maximum(degrees(block), 1.0)[..., None]
    return out


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
    """In-degree over the real edges, (P, n_local) float32: where each
    destination's run of the sorted edges ends, capped at the real edges
    (the padding addresses the last row), less where it starts."""
    n_real = block.edge_mask.sum(axis=1, keepdims=True)
    ends = jax.vmap(partial(jnp.searchsorted, side="right"), (0, None))(
        block.edges[..., 1], jnp.arange(block.n_local, dtype=jnp.int32))
    ends = jnp.minimum(ends, n_real)
    return jnp.diff(ends, axis=1, prepend=0).astype(jnp.float32)


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
