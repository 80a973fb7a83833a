"""Per-(architecture × input-shape × mesh) lowerable cells.

``build_cell`` returns a :class:`Cell` whose ``lower()`` runs
``jax.jit(step).lower(*ShapeDtypeStruct args)`` — no parameter or input data
is ever materialized (the 236B-param and 62M-edge cells lower from specs).

Step selection per shape (base.py): LM ``train_4k`` lowers the train step,
``prefill_32k`` the prefill, ``decode_32k``/``long_500k`` the one-token decode
(serve) step; GNN shapes lower the partition-parallel Sylvie train step; DLRM
shapes lower train / serve / retrieval.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import configs as configlib
from ..configs.base import ArchSpec, ShapeCell
from ..core.staleness import HaloState
from ..core.sylvie import SylvieConfig
from ..dist import api as dist
from ..graph.partition import analytic_partition_spec
from ..graph.sampling import SamplerShapes
from ..models.gnn import blocks as B
from ..models.lm import model as LM
from ..models.lm import sharding as lm_sharding
from ..models.recsys import dlrm as D
from ..train import optimizer as optlib
from ..train.gnn_step import GNNTrainState, make_gnn_steps
from . import mesh as meshlib

KEY_SDS = jax.ShapeDtypeStruct((2,), jnp.uint32)


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    step: str
    fn: Callable
    args: tuple
    n_devices: int
    model_flops: Optional[float]
    meta: dict = dataclasses.field(default_factory=dict)
    mesh: Any = None
    shard_ctx: Any = None        # LM activation-annotation context

    def lower(self):
        if self.shard_ctx is not None:
            LM.set_shard_ctx(self.shard_ctx)
            try:
                with jax.set_mesh(self.mesh):
                    return self.fn.lower(*self.args)
            finally:
                LM.set_shard_ctx(None)
        return self.fn.lower(*self.args)


def _sds(tree, mesh=None, specs=None):
    """Shape tree -> SDS tree, optionally with NamedShardings attached."""
    if specs is None:
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _lm_model_flops(cfg, cell: ShapeCell) -> float:
    s, b = cell.params["seq_len"], cell.params["global_batch"]
    n_act = cfg.param_count(active_only=True)
    # causal attention math: 2 matmuls x 2 flops x (S^2/2) x H x dh per layer
    attn = 0.0
    for _, _, lc, cnt in cfg.sub_layers():
        a = lc.attn
        dh = a.d_nope + a.d_rope if a.kind == "mla" else a.d_head
        span = min(s, a.window) if a.window else s
        attn += cnt * 2 * b * a.n_heads * dh * s * span
    if cell.step == "train":
        return 6.0 * n_act * b * s + 3.0 * attn
    if cell.step == "prefill":
        return 2.0 * n_act * b * s + attn
    # decode: one token against an S-token cache
    attn_dec = 0.0
    for _, _, lc, cnt in cfg.sub_layers():
        a = lc.attn
        dh = a.d_nope + a.d_rope if a.kind == "mla" else a.d_head
        span = min(s, a.window) if a.window else s
        attn_dec += cnt * 4 * b * a.n_heads * dh * span
    return 2.0 * n_act * b + attn_dec


def _reduce_depth(cfg, depth: int):
    """Shrink every count>1 segment to ``depth`` (cost-extrapolation probes:
    costs are base + count x body, so two depths recover the full-depth
    numbers exactly — see dryrun.run_cell)."""
    segs = tuple(dataclasses.replace(s, count=min(s.count, depth))
                 for s in cfg.segments)
    return dataclasses.replace(cfg, segments=segs)


def lm_scaled_count(cfg) -> int:
    """The count of the (single) scaled segment."""
    return max(s.count for s in cfg.segments)


def _lm_cell(spec: ArchSpec, cell: ShapeCell, mesh, *,
             unroll: bool = False, depth: Optional[int] = None) -> Cell:
    # unroll=True + depth=1/2 are the cost-extrapolation probes (HLO cost
    # analysis tallies a `while` body once, not x trip count); the default
    # scanned full-depth program is what actually deploys.
    cfg = spec.config()
    if depth is not None:
        cfg = _reduce_depth(cfg, depth)
    fsdp, mdl = lm_sharding.axes(mesh)
    s, b = cell.params["seq_len"], cell.params["global_batch"]

    params_shape = jax.eval_shape(lambda k: LM.init_params(k, cfg), KEY_SDS)
    p_specs = lm_sharding.param_specs(params_shape, cfg, mesh)
    params = _sds(params_shape, mesh, p_specs)
    dspec = NamedSharding(mesh, lm_sharding.data_spec(mesh))

    if cell.step == "train":
        opt = optlib.adam(1e-4)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        o_specs = {"m": p_specs, "v": p_specs, "t": P()}
        opt_sds = _sds(opt_shape, mesh, o_specs)
        state = (params, opt_sds,
                 jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=NamedSharding(mesh, P())))
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=dspec)
        labels = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=dspec)
        fn = jax.jit(LM.make_train_step(cfg, opt, unroll=unroll))
        args = (state, tokens, labels)
    elif cell.step == "prefill":
        cache_shape = LM.init_cache(cfg, b, s, as_spec=True)
        c_specs = lm_sharding.cache_specs(cache_shape, mesh, b)
        out_sh = (NamedSharding(mesh, lm_sharding.data_spec(mesh)),
                  jax.tree.map(lambda sp: NamedSharding(mesh, sp), c_specs))
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=dspec)
        fn = jax.jit(LM.make_prefill_step(cfg, b, s, unroll=unroll),
                     out_shardings=out_sh)
        args = (params, tokens)
    else:  # decode
        cache_shape = LM.init_cache(cfg, b, s, as_spec=True)
        c_specs = lm_sharding.cache_specs(cache_shape, mesh, b)
        caches = _sds(cache_shape, mesh, c_specs)
        tok_spec = NamedSharding(mesh, P(fsdp if b > 1 else None, None))
        token = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=tok_spec)
        pos = jax.ShapeDtypeStruct((), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        fn = jax.jit(LM.make_decode_step(cfg, unroll=unroll))
        args = (params, caches, token, pos)

    return Cell(spec.arch_id, cell.name, cell.step, fn, args,
                meshlib.n_devices(mesh), _lm_model_flops(cfg, cell),
                meta=dict(params=cfg.param_count(),
                          active_params=cfg.param_count(active_only=True)),
                mesh=mesh, shard_ctx=LM.shard_ctx_from_mesh(mesh))


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------


def gnn_cell_sizes(cell: ShapeCell) -> tuple[int, int, int]:
    """(n_nodes, n_edges, d_feat) of the array the runtime actually trains."""
    p = cell.params
    if cell.name == "minibatch_lg":
        ss = SamplerShapes(p["batch_nodes"], tuple(p["fanout"]))
        return ss.max_nodes, ss.max_edges, p["d_feat"]
    if cell.name == "molecule":
        return p["n_nodes"] * p["batch"], p["n_edges"] * p["batch"] * 2, \
            p["d_feat"]
    return p["n_nodes"], p["n_edges"], p["d_feat"]


def _gnn_model_flops(arch_name: str, model, n: int, e: int, d_in: int,
                     train: bool) -> float:
    """Analytic 'useful' FLOPs of one forward pass (x3 for fwd+bwd)."""
    f = 0.0
    name = arch_name.split("-")[0]
    if name in ("gcn", "graphsage"):
        dims = [d_in] + [model.d_hidden] * (model.n_layers - 1) + [model.d_out]
        for i in range(model.n_layers):
            f += 2 * e * dims[i] + 2 * n * dims[i] * dims[i + 1]
            if name == "graphsage":
                f += 2 * n * dims[i] * dims[i + 1]
    elif name == "gat":
        d = model.heads * model.d_hidden
        din = d_in
        for _ in range(model.n_layers):
            f += 2 * n * din * d + 4 * e * d + 2 * e * model.heads
            din = d
        f += 2 * n * din * model.d_out
    elif name == "pna":
        d = model.d_hidden
        f += 2 * n * d_in * d
        for _ in range(model.n_layers):
            f += 2 * e * 2 * d * d + 8 * e * d + 2 * n * 12 * d * d
    elif name == "meshgraphnet":
        d = model.d_hidden
        f += 2 * n * d_in * d + 2 * e * model.d_edge_in * d
        for _ in range(model.n_layers):
            f += 2 * e * (3 * d * d + d * d) + 2 * n * (2 * d * d + d * d)
    elif name == "schnet":
        d = model.d_hidden
        f += 2 * n * d_in * d
        for _ in range(model.n_interactions):
            f += 2 * e * (model.n_rbf * d + d * d) + 2 * e * d \
                + 2 * n * 3 * d * d
    elif name == "nequip":
        mul = model.mul
        n_paths = len(model.paths)
        f += 2 * n * d_in * mul
        tp = sum((2 * l1 + 1) * (2 * l2 + 1) * (2 * l3 + 1) * 2 * mul
                 for (l1, l2, l3) in model.paths)
        for _ in range(model.n_layers):
            f += e * tp + 2 * e * (model.n_rbf * mul + mul * n_paths * mul)
            f += 2 * n * 2 * mul * mul * (model.l_max + 1) ** 2
    else:
        f = 2 * e * 64 + 2 * n * d_in * 64
    return 3.0 * f if train else f


def _gnn_cell(spec: ArchSpec, cell: ShapeCell, mesh, *,
              sylvie_mode: str = "sync", bits: int = 1,
              n_classes: int = 16) -> Cell:
    arch = spec.config()
    n, e, d_feat = gnn_cell_sizes(cell)
    p_n = meshlib.n_devices(mesh)
    pspec = analytic_partition_spec(n, e, p_n)

    model = arch.make(d_feat, n_classes)
    block = B.block_spec(pspec, d_edge_attr=arch.d_edge_attr,
                         with_weight=True, stacked_parts=p_n,
                         transposed=getattr(model, "transposed_edges", False))
    opt = optlib.adam(1e-2)
    scfg = SylvieConfig(mode=sylvie_mode, bits=bits)
    backend = dist.ShardMapBackend(mesh)

    params_shape = jax.eval_shape(model.init, KEY_SDS)
    opt_shape = jax.eval_shape(opt.init, params_shape)
    halo = HaloState.zeros_spec(block.plan, model.comm_dims(),
                                stacked_parts=p_n)
    from ..train.compression import EFState
    ef_shape = jax.eval_shape(EFState.zeros_like, params_shape)
    state = GNNTrainState(params=_sds(params_shape), opt_state=_sds(opt_shape),
                          halo=halo, step=jax.ShapeDtypeStruct((), jnp.int32),
                          ef=_sds(ef_shape),
                          site_stats=jax.ShapeDtypeStruct(
                              (len(model.comm_dims()), 2), jnp.float32))
    x = jax.ShapeDtypeStruct((p_n, pspec.n_local, d_feat), jnp.float32)
    y = jax.ShapeDtypeStruct((p_n, pspec.n_local), jnp.int32)
    m = jax.ShapeDtypeStruct((p_n, pspec.n_local), jnp.bool_)

    ts, ta, ev = make_gnn_steps(model, scfg, opt, backend=backend)
    ts_w, ta_w, _ = dist.shard_gnn_steps(ts, ta, ev, mesh, state, block)
    fn = ta_w if sylvie_mode == "async" else ts_w
    args = (state, block, x, y, m, KEY_SDS)

    from ..core.exchange import exchange_bytes
    dims = model.comm_dims()
    # exchange_bytes totals across partitions; the cell meta reports per-device
    payload = sum(exchange_bytes(block.plan, d, bits)[0] for d in dims) // p_n
    ec = sum(exchange_bytes(block.plan, d, bits)[1] for d in dims) // p_n
    return Cell(spec.arch_id, cell.name, cell.step, fn, args, p_n,
                _gnn_model_flops(arch.name, model, n, e, d_feat, True),
                meta=dict(n_local=pspec.n_local, e_pad=pspec.e_pad,
                          h_pad=pspec.h_pad, halo_rows=pspec.halo_rows,
                          exchange_payload_bytes_per_part=payload,
                          exchange_ec_bytes_per_part=ec,
                          sylvie_mode=sylvie_mode, bits=bits))


# ---------------------------------------------------------------------------
# DLRM cells
# ---------------------------------------------------------------------------


def _dlrm_model_flops(cfg: D.DLRMConfig, cell: ShapeCell) -> float:
    b = cell.params.get("n_candidates", cell.params["batch"])
    dims = [cfg.n_dense, *cfg.bot_mlp]
    f = sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
    fpf = cfg.n_sparse + 1
    f += 2 * fpf * fpf * cfg.embed_dim       # dot interaction
    dims = [cfg.interaction_dim, *cfg.top_mlp]
    f += sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
    per_sample = f
    mult = 3.0 if cell.step == "train" else 1.0
    return mult * per_sample * b


def _dlrm_cell(spec: ArchSpec, cell: ShapeCell, mesh, *,
               qbits: Optional[int] = None) -> Cell:
    cfg = spec.config()
    if qbits is not None:
        cfg = dataclasses.replace(cfg, quantize_collective_bits=qbits)
    p_n = meshlib.n_devices(mesh)
    axes = meshlib.flat_axes(mesh)
    rpd = D.rows_per_device(cfg, p_n)
    table = jax.ShapeDtypeStruct((rpd * p_n, cfg.embed_dim), jnp.float32)
    dense_shape = jax.eval_shape(
        lambda k: D.init_dense_params(k, cfg), KEY_SDS)
    dense = _sds(dense_shape)
    shard, rep = P(axes), P()
    tspec = {"m": shard, "v": shard, "t": rep}

    if cell.step == "train":
        b = cell.params["batch"]
        opt = optlib.adam(1e-3)
        opt_d = _sds(jax.eval_shape(opt.init, dense_shape))
        opt_t = _sds(jax.eval_shape(opt.init, table))
        state = (dense, table, opt_d, opt_t,
                 jax.ShapeDtypeStruct((), jnp.int32))
        dx = jax.ShapeDtypeStruct((b, cfg.n_dense), jnp.float32)
        ids = jax.ShapeDtypeStruct((b * cfg.total_ids_per_sample,), jnp.int32)
        lb = jax.ShapeDtypeStruct((b,), jnp.float32)
        step = D.make_train_step(cfg, opt, axes)
        fn = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=((rep, shard, rep, tspec, rep), shard, shard, shard, rep),
            out_specs=((rep, shard, rep, tspec, rep), rep), check_vma=False))
        args = (state, dx, ids, lb, KEY_SDS)
    elif cell.step == "serve":
        b = cell.params["batch"]
        dx = jax.ShapeDtypeStruct((b, cfg.n_dense), jnp.float32)
        ids = jax.ShapeDtypeStruct((b * cfg.total_ids_per_sample,), jnp.int32)
        fn = jax.jit(jax.shard_map(
            D.make_serve_step(cfg, axes), mesh=mesh,
            in_specs=(rep, shard, shard, shard), out_specs=shard,
            check_vma=False))
        args = (dense, table, dx, ids)
    else:  # retrieval
        ncand = cell.params["n_candidates"]
        ncand = ((ncand + p_n - 1) // p_n) * p_n
        dx = jax.ShapeDtypeStruct((1, cfg.n_dense), jnp.float32)
        ids = jax.ShapeDtypeStruct((cfg.total_ids_per_sample,), jnp.int32)
        cand = jax.ShapeDtypeStruct((ncand,), jnp.int32)
        fn = jax.jit(jax.shard_map(
            D.make_retrieval_step(cfg, axes), mesh=mesh,
            in_specs=(rep, shard, rep, rep, shard), out_specs=(rep, rep),
            check_vma=False))
        args = (dense, table, dx, ids, cand)

    return Cell(spec.arch_id, cell.name, cell.step, fn, args, p_n,
                _dlrm_model_flops(cfg, cell),
                meta=dict(table_rows=cfg.total_rows, rows_per_device=rpd,
                          params=cfg.param_count()))


# ---------------------------------------------------------------------------


def build_cell(arch_id: str, shape_name: str, mesh, **kw) -> Cell:
    spec = configlib.get(arch_id)
    cell = spec.shape(shape_name)
    if spec.kind == "lm":
        return _lm_cell(spec, cell, mesh, **kw)
    if spec.kind == "gnn":
        return _gnn_cell(spec, cell, mesh, **kw)
    if spec.kind == "recsys":
        return _dlrm_cell(spec, cell, mesh, **kw)
    raise ValueError(spec.kind)


def all_cells() -> list[tuple[str, str]]:
    out = []
    for arch_id in configlib.ASSIGNED:
        for cell in configlib.get(arch_id).shapes:
            out.append((arch_id, cell.name))
    return out
