"""DLRM (MLPerf config) with model-parallel embedding tables in pure JAX.

JAX has no ``nn.EmbeddingBag`` and no CSR sparse — the embedding lookup layer
here IS part of the system (kernel_taxonomy §RecSys):

  * all 26 tables live concatenated in one ``(total_rows, d)`` array,
    **row-sharded over the whole mesh** (the tables dominate memory: the
    MLPerf Criteo sizes sum to ~188M rows -> ~96 GB fp32);
  * lookup is the classic model-parallel exchange, written explicitly under
    ``shard_map``: replicate the flat id vector (all_gather, ints are tiny),
    partial-gather each device's resident rows with ``jnp.take``, then
    ``psum_scatter`` the partial embeddings — summing the one non-zero
    contribution per row *and* landing the result batch-sharded for the
    data-parallel MLPs in a single fused collective. Backward is the mirrored
    all_gather (autodiff of the collective), which routes each row-gradient
    back to its owner — no parameter all-reduce ever touches the tables;
  * multi-hot bags reduce with ``jax.ops.segment_sum`` over static segment
    ids (sum mode), matching ``EmbeddingBag`` semantics.

The dense substrate (bottom/top MLP, dot interaction) is data-parallel over
the full flattened mesh with replicated weights + gradient ``psum``.

This file also hosts the *beyond-paper* Sylvie tie-in: the embedding exchange
is an activation collective with exactly the halo-exchange structure, so the
Low-bit Module can quantize it (``quantize_collective`` flag; off by
default).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..nn import mlp, mlp_init
from ...core import quantization as qlib

# MLPerf DLRM (Criteo Terabyte) per-field vocabulary sizes.
CRITEO_TABLE_SIZES = (
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771,
    25641295, 39664984, 585935, 12972, 108, 36)


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    n_dense: int = 13
    embed_dim: int = 128
    table_sizes: Sequence[int] = CRITEO_TABLE_SIZES
    bot_mlp: Sequence[int] = (512, 256, 128)
    top_mlp: Sequence[int] = (1024, 1024, 512, 256, 1)
    hot: Sequence[int] | int = 1          # per-field multi-hot bag size
    quantize_collective_bits: Optional[int] = None   # beyond-paper Sylvie

    @property
    def n_sparse(self) -> int:
        return len(self.table_sizes)

    @property
    def hots(self) -> tuple[int, ...]:
        if isinstance(self.hot, int):
            return (self.hot,) * self.n_sparse
        return tuple(self.hot)

    @property
    def total_ids_per_sample(self) -> int:
        return sum(self.hots)

    @property
    def total_rows(self) -> int:
        return int(sum(self.table_sizes))

    @property
    def row_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.table_sizes)]).astype(np.int64)

    @property
    def interaction_dim(self) -> int:
        f = self.n_sparse + 1
        return f * (f - 1) // 2 + self.embed_dim

    def param_count(self) -> int:
        n = self.total_rows * self.embed_dim
        dims = [self.n_dense, *self.bot_mlp]
        n += sum(dims[i] * dims[i + 1] + dims[i + 1] for i in range(len(dims) - 1))
        dims = [self.interaction_dim, *self.top_mlp]
        n += sum(dims[i] * dims[i + 1] + dims[i + 1] for i in range(len(dims) - 1))
        return n


def rows_per_device(cfg: DLRMConfig, n_dev: int) -> int:
    return (cfg.total_rows + n_dev - 1) // n_dev


def init_dense_params(key, cfg: DLRMConfig, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {"bot": mlp_init(k1, [cfg.n_dense, *cfg.bot_mlp], dtype=dtype),
            "top": mlp_init(k2, [cfg.interaction_dim, *cfg.top_mlp], dtype=dtype)}


def init_table(key, cfg: DLRMConfig, n_dev: int = 1, dtype=jnp.float32):
    """(n_dev * rows_per_device, d) — padded so the row shard is even."""
    rows = rows_per_device(cfg, n_dev) * n_dev
    return (jax.random.uniform(key, (rows, cfg.embed_dim), jnp.float32,
                               -0.05, 0.05)).astype(dtype)


# ---------------------------------------------------------------------------
# model-parallel embedding-bag
# ---------------------------------------------------------------------------


def _axis_index(axis_name):
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    idx = jax.lax.axis_index(names[0])
    for a in names[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _exchange_fwd_wire(x, axis_name, bits):
    """Forward wire of the embedding exchange. Every output row has exactly
    ONE non-zero contributor (its owner), so summing is lossless even in a
    narrower dtype: ``bits=16`` runs the psum_scatter itself in bf16
    (wire /2 vs f32; the single contributing value is bf16-rounded once)."""
    if bits is not None and bits <= 16:
        y = jax.lax.psum_scatter(x.astype(jnp.bfloat16), axis_name,
                                 scatter_dimension=0, tiled=True)
        return y.astype(x.dtype)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sylvie_embedding_exchange(part, axis_name, bits, key):
    """psum_scatter whose BACKWARD all-gather carries a b-bit packed payload
    (beyond-paper: the paper's Low-bit Module applied to DLRM's dominant
    collective). Forward: bf16 wire (lossless-in-expectation here — one
    contributor per row). Backward: the cotangent is quantized with
    stochastic rounding, the PACKED uint8 payload + bf16 scales cross the
    all-gather, and owners dequantize — unbiased, exactly Alg. 2's gradient
    communication."""
    del key
    return _exchange_fwd_wire(part, axis_name, bits)


def _see_fwd(part, axis_name, bits, key):
    return sylvie_embedding_exchange(part, axis_name, bits, key), key


def _see_bwd(axis_name, bits, key, g):
    if bits is None or bits > 16:
        gg = jax.lax.all_gather(g, axis_name, tiled=True)
        return (gg, None)
    if bits == 16:
        gg = jax.lax.all_gather(g.astype(jnp.bfloat16), axis_name,
                                tiled=True)
        return (gg.astype(g.dtype), None)
    qt = qlib.quantize(g, bits, key)
    data = jax.lax.all_gather(qt.data, axis_name, tiled=True)
    scale = jax.lax.all_gather(qt.scale, axis_name, tiled=True)
    zero = jax.lax.all_gather(qt.zero, axis_name, tiled=True)
    from ...core.quantization import QuantizedTensor
    gg = qlib.dequantize(QuantizedTensor(data, scale, zero, qt.bits,
                                         qt.feat_dim), g.dtype)
    return (gg, None)


sylvie_embedding_exchange.defvjp(_see_fwd, _see_bwd)


def _maybe_quantized_psum_scatter(x, axis_name, bits, key):
    """The embedding exchange; optionally Sylvie-quantized (beyond-paper)."""
    if bits is None:
        return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                    tiled=True)
    if key is None:
        key = jax.random.PRNGKey(0)
    return sylvie_embedding_exchange(x, axis_name, bits, key)


def embedding_bag(table, flat_ids_local, cfg: DLRMConfig, axis_name,
                  key=None):
    """flat_ids_local: (n_local,) int32 *global* row ids for this device's
    batch slice -> (n_local, d) bag-input rows, batch-sharded.

    Single-process (axis_name=None): plain take. Distributed: all_gather ids,
    partial local gather, psum_scatter partials (see module docstring)."""
    if axis_name is None:
        return jnp.take(table, flat_ids_local, axis=0)
    ids = jax.lax.all_gather(flat_ids_local, axis_name, tiled=True)  # (n_glob,)
    rpd = table.shape[0]
    lo = _axis_index(axis_name) * rpd
    loc = ids - lo
    ok = (loc >= 0) & (loc < rpd)
    part = jnp.where(ok[:, None], jnp.take(table, jnp.where(ok, loc, 0), axis=0),
                     0)
    return _maybe_quantized_psum_scatter(
        part, axis_name, cfg.quantize_collective_bits, key)


def bag_reduce(rows, cfg: DLRMConfig, batch: int):
    """(batch * total_ids, d) -> (batch, n_sparse, d) sum-bags via segment_sum."""
    seg_field = np.repeat(np.arange(cfg.n_sparse), cfg.hots)      # (ids/sample,)
    seg = (np.arange(batch)[:, None] * cfg.n_sparse + seg_field[None, :])
    seg = jnp.asarray(seg.reshape(-1), jnp.int32)
    out = jax.ops.segment_sum(rows, seg, num_segments=batch * cfg.n_sparse)
    return out.reshape(batch, cfg.n_sparse, cfg.embed_dim)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def dot_interaction(bot_out, emb):
    """bot_out (B, d); emb (B, F, d) -> (B, F+1 choose 2 + d)."""
    z = jnp.concatenate([bot_out[:, None, :], emb], axis=1)       # (B, F+1, d)
    g = jnp.einsum("bfd,bgd->bfg", z, z)
    f = z.shape[1]
    iu, ju = np.triu_indices(f, k=1)
    pairs = g[:, iu, ju]
    return jnp.concatenate([bot_out, pairs], axis=-1)


def dlrm_forward(dense_params, table, dense_x, flat_ids, cfg: DLRMConfig,
                 axis_name=None, key=None):
    """dense_x (B_local, n_dense); flat_ids (B_local * total_ids,) -> logits."""
    b = dense_x.shape[0]
    bot = mlp(dense_params["bot"], dense_x)                       # (B, d)
    rows = embedding_bag(table, flat_ids, cfg, axis_name, key)
    emb = bag_reduce(rows, cfg, b)
    feats = dot_interaction(bot, emb)
    return mlp(dense_params["top"], feats)[:, 0]                  # (B,)


def bce_loss(logits, labels):
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def make_train_step(cfg: DLRMConfig, optimizer, axis_name=None):
    """State: (dense_params, table, opt_dense, opt_table, step).

    The loss is sum-form normalized by the *global* batch, so per-device
    gradients are exact global-mean contributions; the replicated dense
    params' gradients are explicitly psummed (shard_map runs with replication
    checking off — see ShardMapBackend.shard), and the table grads stay
    local — each device owns its rows (the embedding collective's backward
    routes contributions to owners)."""
    def train_step(state, dense_x, flat_ids, labels, key):
        dense_params, table, opt_d, opt_t, step = state
        n_dev = 1
        if axis_name is not None:
            names = ((axis_name,) if isinstance(axis_name, str)
                     else tuple(axis_name))
            for a in names:
                n_dev *= jax.lax.axis_size(a)

        def loss_fn(dp, tb):
            logits = dlrm_forward(dp, tb, dense_x, flat_ids, cfg, axis_name,
                                  key)
            return bce_loss(logits, labels) / n_dev

        loss, (gd, gt) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            dense_params, table)
        if axis_name is not None:
            loss = jax.lax.psum(loss, axis_name)
            gd = jax.tree.map(lambda g: jax.lax.psum(g, axis_name), gd)
        upd_d, opt_d = optimizer.update(gd, opt_d, dense_params)
        upd_t, opt_t = optimizer.update(gt, opt_t, table)
        from ...train.optimizer import apply_updates
        dense_params = apply_updates(dense_params, upd_d)
        table = apply_updates(table, upd_t)
        return (dense_params, table, opt_d, opt_t, step + 1), loss

    return train_step


def make_serve_step(cfg: DLRMConfig, axis_name=None):
    def serve(dense_params, table, dense_x, flat_ids):
        logits = dlrm_forward(dense_params, table, dense_x, flat_ids, cfg,
                              axis_name)
        return jax.nn.sigmoid(logits)
    return serve


def make_retrieval_step(cfg: DLRMConfig, axis_name=None, top_k: int = 64,
                        cand_field: int = 0):
    """Score one query against n_cand candidates (batch of candidate ids for
    field ``cand_field``; the other 25 fields + dense features come from the
    query). Candidates stay sharded; per-shard top-k then a gathered merge."""
    def retrieval(dense_params, table, dense_x, flat_ids, cand_ids):
        # query embedding context: (1, F, d) + bottom output (1, d)
        bot = mlp(dense_params["bot"], dense_x)                   # (1, d)
        rows = embedding_bag(table, flat_ids, cfg, axis_name)
        emb = bag_reduce(rows, cfg, 1)                            # (1, F, d)
        # candidate rows (n_local, d): ids are already batch-sharded
        cand = embedding_bag(table, cand_ids, cfg, axis_name)
        n = cand.shape[0]
        embn = jnp.broadcast_to(emb, (n,) + emb.shape[1:])
        embn = embn.at[:, cand_field, :].set(cand)
        feats = dot_interaction(jnp.broadcast_to(bot, (n, bot.shape[-1])), embn)
        scores = mlp(dense_params["top"], feats)[:, 0]            # (n_local,)
        v, i = jax.lax.top_k(scores, min(top_k, n))
        ids = cand_ids[i]
        if axis_name is not None:
            v = jax.lax.all_gather(v, axis_name, tiled=True)
            ids = jax.lax.all_gather(ids, axis_name, tiled=True)
            # gathered copies are identical on every device; pmean/pmax make
            # that replication *provable* to shard_map's VMA checker so the
            # merged top-k can leave with out_specs=P()
            v = jax.lax.pmean(v, axis_name)
            ids = jax.lax.pmax(ids, axis_name)
            v, sel = jax.lax.top_k(v, top_k)
            ids = ids[sel]
        return v, ids
    return retrieval
