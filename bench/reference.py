"""Plain reference of Sylvie full-graph training, independent of the program.

It follows the semantics the program documents, on the benchmark's own graph
and weights, in float32, with matmuls at the precision the configuration
states (``matmul_precision``):

* partitions are contiguous blocks of node ids, ``part(v) = v * P // N``;
* a halo entry is a (receiving partition p, sending partition q != p, node u)
  for every edge u -> v with ``part(u) = q`` and ``part(v) = p``; within one
  (p, q) pair the entries are ranked by node id;
* the send buffer of partition q is laid out in ring buckets: bucket
  ``k = (p - q) % P`` holds what q sends to p, sized to the largest such
  count over q and rounded up to ``alignment`` rows; bucket 0 is empty. An
  entry's row is its bucket's start plus its rank;
* the Low-bit Module quantizes every halo row with its own min and max,
  rounds stochastically with uniform noise drawn per buffer element from the
  epoch key (``fold_in(PRNGKey(seed), epoch)``), site ``i`` forward with
  ``fold_in(key, 2i)`` and backward with ``fold_in(key, 2i + 1)``. With the
  whole partition stack on one chip the noise is one draw over the stacked
  ``(P, rows, d)`` buffer; with one partition per chip the key is first
  folded with the partition index and the draw is ``(1, rows, d)``. The
  forward noise row is the sender's, the backward one the receiver's. Scale
  and zero travel in ``scale_dtype``;
* ``sync`` (and ``vanilla``, at 32 bits) exchanges fresh halos in both
  passes; ``async`` consumes the previous step's halo features and boundary
  gradients and emits fresh ones for the next step. The first epoch is sync;
* the loss is the mean cross entropy over the training nodes, the optimiser
  Adam.

Aggregation runs over blocks of edges, so that the largest graph fits on one
chip. Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import graphgen

EDGE_BLOCK = 1 << 18


@dataclasses.dataclass
class Plan:
    """Host-side halo plan of the reference (global node ids)."""
    n: int
    parts: int
    rows: int                 # rows of one partition's send buffer
    src_ext: np.ndarray       # (E,) index into [h (N rows) ; halo entries]
    dst: np.ndarray           # (E,)
    w: np.ndarray             # (E,) GCN weights
    deg: np.ndarray           # (N,) in-degree over the self-looped edges
    halo_node: np.ndarray     # (M,) global node of each halo entry
    halo_recv: np.ndarray     # (M,) receiving partition
    halo_send: np.ndarray     # (M,) sending partition
    halo_row: np.ndarray      # (M,) row in the send (and receive) buffer


def build_plan(g: graphgen.Graph, parts: int, alignment: int) -> Plan:
    n = g.n_nodes
    src, dst = graphgen.with_self_loops(g)
    w = graphgen.gcn_weights(src, dst, n)
    part = (np.arange(n, dtype=np.int64) * parts) // n
    ps, pd = part[src], part[dst]
    halo = ps != pd
    combo = (pd[halo] * parts + ps[halo]) * n + src[halo]
    uniq, inv = np.unique(combo, return_inverse=True)
    pair, node = uniq // n, uniq % n
    recv, send = pair // parts, pair % parts
    first = np.searchsorted(pair, pair)
    rank = np.arange(uniq.size) - first
    counts = np.bincount(pair, minlength=parts * parts).reshape(parts, parts)
    sizes = np.zeros(parts, np.int64)
    q = np.arange(parts)
    for k in range(1, parts):
        c = int(counts[(q + k) % parts, q].max())
        sizes[k] = -(-c // alignment) * alignment if c else 0
    start = np.concatenate([[0], np.cumsum(sizes)])
    row = start[(recv - send) % parts] + rank
    src_ext = src.copy()
    src_ext[halo] = n + inv
    deg = np.bincount(dst, minlength=n).astype(np.float32)
    return Plan(n, parts, int(sizes.sum()), src_ext, dst, w, deg, node,
                recv, send, row)


# ---------------------------------------------------------------------------
# Low-bit Module
# ---------------------------------------------------------------------------
def quantize_roundtrip(h, u, bits: int, stochastic: bool, scale_dtype):
    """Per-row affine quantization to ``bits`` and back, in float32."""
    if bits >= 32:
        return h
    h = h.astype(jnp.float32)
    big = 2.0 ** bits - 1.0
    lo = jnp.min(h, axis=-1, keepdims=True)
    hi = jnp.max(h, axis=-1, keepdims=True)
    rng = hi - lo
    safe = jnp.where(rng > 0, rng, 1.0)
    hbar = (h - lo) / safe * big
    if stochastic:
        fl = jnp.floor(hbar)
        q = fl + (u < hbar - fl).astype(jnp.float32)
    else:
        q = jnp.round(hbar)
    q = jnp.clip(q, 0.0, big)
    return q * round_to(rng / big, scale_dtype) + round_to(lo, scale_dtype)


def round_to(x, dtype):
    """``x`` rounded to the nearest value of ``dtype``, held in float32.
    ``reduce_precision`` states the rounding itself: a compiler may drop a
    float32 -> bfloat16 -> float32 pair of casts as excess precision."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def noise(key, tag: int, owner, row, parts: int, rows: int, d: int,
          per_chip: bool):
    """Uniform noise of each halo entry: element ``[owner, row]`` of the
    draw over the stacked buffer (``per_chip`` False) or of partition
    ``owner``'s own draw (``per_chip`` True)."""
    if not per_chip:
        u = jax.random.uniform(jax.random.fold_in(key, tag), (parts, rows, d))
    else:
        u = jnp.stack([
            jax.random.uniform(jax.random.fold_in(jax.random.fold_in(key, p),
                                                  tag), (1, rows, d))[0]
            for p in range(parts)])
    return u[owner, row]


# ---------------------------------------------------------------------------
# Aggregation over blocks of edges
# ---------------------------------------------------------------------------
def _blocks(a: np.ndarray, fill) -> np.ndarray:
    e = a.shape[0]
    nb = max(1, -(-e // EDGE_BLOCK))
    out = np.full(nb * EDGE_BLOCK, fill, a.dtype)
    out[:e] = a
    return out.reshape(nb, EDGE_BLOCK)


def edge_blocks(plan: Plan, weighted: bool) -> dict:
    """Edge arrays of ``spmm`` in blocks of ``EDGE_BLOCK`` (padding edges
    carry weight 0)."""
    w = plan.w if weighted else np.ones_like(plan.w)
    return {"src": jnp.asarray(_blocks(plan.src_ext.astype(np.int32), 0)),
            "dst": jnp.asarray(_blocks(plan.dst.astype(np.int32), 0)),
            "w": jnp.asarray(_blocks(w, 0.0))}


def _scan_spmm(table, a_idx, b_idx, w, out_rows):
    def body(acc, blk):
        ia, ib, wk = blk
        return acc.at[ib].add(table[ia] * wk[:, None].astype(table.dtype)), None
    acc = jnp.zeros((out_rows, table.shape[-1]), table.dtype)
    return jax.lax.scan(body, acc, (a_idx, b_idx, w))[0]


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def spmm(table, edges, n):
    """``z[v] = sum over edges u -> v of w * table[src_ext]`` for ``v < n``:
    a scan over edge blocks; its transpose is the same scan with the ends
    swapped."""
    return _scan_spmm(table, edges["src"], edges["dst"], edges["w"], n)


def _spmm_fwd(table, edges, n):
    return spmm(table, edges, n), (edges, jnp.zeros((table.shape[0], 0)))


def _spmm_bwd(n, res, g):
    edges, rows = res
    return (_scan_spmm(g, edges["dst"], edges["src"], edges["w"],
                       rows.shape[0]), None)


spmm.defvjp(_spmm_fwd, _spmm_bwd)


# ---------------------------------------------------------------------------
# The training step
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Setting:
    """What the traffic mix and configuration fix for the reference."""
    mode: str                 # "vanilla" | "sync" | "async"
    bits: int
    stochastic: bool
    scale_dtype: str
    per_chip: bool            # one partition per chip (noise per partition)
    lr: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


class Reference:
    """Steps of plain Sylvie training. ``dtype`` is the compute precision
    (float32 for the reference, bfloat16 for the control); ``fault`` plants
    one of the faults the benchmark must catch: ``"half_batch"`` (half the
    training nodes left out of the mean) or ``"no_exchange"`` (halo rows
    replaced by zeros, in both passes). The graph rides into the compiled
    step as arguments, never as constants."""

    def __init__(self, arch: str, dims, plan: Plan, g: graphgen.Graph,
                 setting: Setting, dtype=jnp.float32, fault: str = "",
                 precision: str = "highest"):
        self.arch = importlib.import_module(f"bench.models.{arch}")
        self.precision = getattr(jax.lax.Precision, precision.upper())
        self.dims = tuple(dims)
        self.plan = plan
        self.s = setting
        self.dtype = jnp.dtype(dtype)
        self.fault = fault
        mask = g.train_mask.copy()
        if fault == "half_batch":
            idx = np.flatnonzero(mask)
            mask[idx[1::2]] = False
        self.data = {
            "x": jnp.asarray(g.x), "y": jnp.asarray(g.y),
            "mask": jnp.asarray(mask.astype(np.float32)),
            "halo_node": jnp.asarray(plan.halo_node.astype(np.int32)),
            "halo_send": jnp.asarray(plan.halo_send.astype(np.int32)),
            "halo_recv": jnp.asarray(plan.halo_recv.astype(np.int32)),
            "halo_row": jnp.asarray(plan.halo_row.astype(np.int32)),
            "deg": jnp.asarray(plan.deg),
            "edges_w": edge_blocks(plan, weighted=True),
            "edges_1": edge_blocks(plan, weighted=False)}
        self.n_sites = len(self.dims) - 1
        self._sync = jax.jit(partial(self._step, sync=True))
        self._async = jax.jit(partial(self._step, sync=False))

    # -- pieces the architecture's layer calls ---------------------------------
    def mm(self, a, b):
        if self.dtype == jnp.float32:
            return jnp.matmul(a, b, precision=self.precision)
        return jnp.matmul(a.astype(self.dtype), b.astype(self.dtype))

    def sum_w(self, table):
        return spmm(table, self._d["edges_w"], self.plan.n)

    def sum_1(self, table):
        return spmm(table, self._d["edges_1"], self.plan.n)

    @property
    def deg(self):
        return self._d["deg"]

    # -- the exchange ---------------------------------------------------------
    def _q(self, rows, u):
        out = quantize_roundtrip(rows, u, self.s.bits, self.s.stochastic,
                                 jnp.dtype(self.s.scale_dtype))
        if self.fault == "no_exchange":
            out = jnp.zeros_like(out)
        return out.astype(self.dtype)

    def _noise(self, key, i, direction, d):
        m = self._d["halo_node"].shape[0]
        if self.s.bits >= 32 or not self.s.stochastic:
            return jnp.zeros((m, d), jnp.float32)
        owner = self._d["halo_send" if direction == 0 else "halo_recv"]
        return noise(key, 2 * i + direction, owner, self._d["halo_row"],
                     self.plan.parts, self.plan.rows, d, self.s.per_chip)

    def _halo_fns(self):
        """(fresh, stale): the synchronous quantized exchange, and the stale
        one of the asynchronous mode, each with Sylvie's backward."""
        ref, n = self, self.plan.n

        def scatter(g, node):
            return jax.ops.segment_sum(g, node, num_segments=n)

        @jax.custom_vjp
        def fresh(h, node, uf, ub):
            return ref._q(h[node], uf)

        def fresh_fwd(h, node, uf, ub):
            return fresh(h, node, uf, ub), (node, ub)

        def fresh_bwd(res, g):
            node, ub = res
            return scatter(ref._q(g, ub).astype(g.dtype), node), None, None, None

        fresh.defvjp(fresh_fwd, fresh_bwd)

        @jax.custom_vjp
        def stale(h, node, cache, gin, gslot, ub):
            return cache

        def stale_fwd(h, node, cache, gin, gslot, ub):
            return cache, (node, gin, ub)

        def stale_bwd(res, g):
            node, gin, ub = res
            return (scatter(gin, node).astype(ref.dtype), None, None, None,
                    ref._q(g, ub).astype(jnp.float32), None)

        stale.defvjp(stale_fwd, stale_bwd)
        return fresh, stale

    def _loss(self, params, gslots, caches, gins, key, sync):
        d = self._d
        h = d["x"].astype(self.dtype)
        p = jax.tree.map(lambda a: a.astype(self.dtype), params)
        fresh, stale = self._halo_fns()
        node = d["halo_node"]
        new_caches = []
        for i in range(self.n_sites):
            uf = self._noise(key, i, 0, self.dims[i])
            ub = self._noise(key, i, 1, self.dims[i])
            if sync:
                halo = fresh(h, node, uf, ub)
                new_caches.append(
                    jax.lax.stop_gradient(halo).astype(jnp.float32))
            else:
                halo = stale(h, node, caches[i].astype(self.dtype), gins[i],
                             gslots[i], ub)
                new_caches.append(self._q(jax.lax.stop_gradient(h)[node],
                                          uf).astype(jnp.float32))
            table = jnp.concatenate([h, halo], axis=0)
            h = self.arch.layer(p[f"layer{i}"], h, table, self,
                                last=i == self.n_sites - 1)
        logits = h.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, d["y"][:, None], axis=-1)[:, 0]
        mask = d["mask"]
        loss = ((logz - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return loss, tuple(new_caches)

    def _step(self, data, params, opt, caches, gins, key, sync):
        self._d = data
        gslots = tuple(jnp.zeros_like(g) for g in gins)
        (loss, new_caches), (grads, ggrads) = jax.value_and_grad(
            self._loss, argnums=(0, 1), has_aux=True)(
                params, gslots, caches, gins, key, sync)
        grads = jax.tree.map(lambda a: a.astype(jnp.float32), grads)
        new_gins = (tuple(jnp.zeros_like(g) for g in gins) if sync
                    else tuple(g.astype(jnp.float32) for g in ggrads))
        s = self.s
        t = opt["t"] + 1
        m = jax.tree.map(lambda m_, g: s.b1 * m_ + (1 - s.b1) * g, opt["m"],
                         grads)
        v = jax.tree.map(lambda v_, g: s.b2 * v_ + (1 - s.b2) * g * g,
                         opt["v"], grads)
        bc1 = 1 - s.b1 ** t.astype(jnp.float32)
        bc2 = 1 - s.b2 ** t.astype(jnp.float32)
        params = jax.tree.map(
            lambda p_, m_, v_: p_ - s.lr * (m_ / bc1 / (jnp.sqrt(v_ / bc2)
                                                      + s.eps)),
            params, m, v)
        return params, {"m": m, "v": v, "t": t}, new_caches, new_gins, \
            loss, grads

    # -- the steps in order ----------------------------------------------------
    def run(self, params, seed_key, steps: int):
        """Train ``steps`` epochs from ``params``; returns (losses, first
        gradient, parameters after the last step, the halo features of each
        site as the first step delivered them, one row per halo entry)."""
        opt = {"m": jax.tree.map(jnp.zeros_like, params),
               "v": jax.tree.map(jnp.zeros_like, params),
               "t": jnp.zeros((), jnp.int32)}
        m = self.data["halo_node"].shape[0]
        caches = tuple(jnp.zeros((m, d), jnp.float32) for d in self.dims[:-1])
        gins = tuple(jnp.zeros((m, d), jnp.float32) for d in self.dims[:-1])
        losses, first_grad = [], None
        for epoch in range(steps):
            sync = self.s.mode != "async" or epoch == 0
            fn = self._sync if sync else self._async
            params, opt, caches, gins, loss, grads = fn(
                self.data, params, opt, caches, gins,
                jax.random.fold_in(seed_key, epoch))
            losses.append(float(loss))
            if first_grad is None:
                first_grad = jax.device_get(grads)
                first_halo = [np.asarray(c) for c in jax.device_get(caches)]
        return losses, first_grad, jax.device_get(params), first_halo
