"""Halo exchange primitives: boundary gather/scatter + the exchange entry points.

All GNN runtime code operates on *stacked* arrays with a leading partition axis
``P`` — e.g. node features ``(P, n_local, d)``. *Which* collective moves the
halo buffers is a :class:`repro.dist.backend.HaloBackend` decision — the
simulated stacked transpose/roll or the shard_map ``all_to_all``/``ppermute``
(or any future communicator) — and this module is the seam.

Two buffer layouts exist (see ``graph/partition.py``):

* dense pairwise blocks ``(P, P*h_pad, ...)`` — the exchange is a transpose
  (an involution), so forward and backward communication share one primitive;
* compact ring buckets ``(P, R, ...)`` with ``R = sum(bucket_sizes)`` — bucket
  ``k`` moves ``p -> (p+k) % P``. Reversing the rings undoes it, so the
  backward communication (Alg. 2) calls :func:`exchange_halo` with
  ``reverse=True``. The layout is carried statically on :class:`PlanArrays`
  (``bucket_sizes``), so one code path in ``core/sylvie.py`` serves both.

The boundary gather, the gradient scatter and the exchange entry points run
under ``jax.named_scope("exchange")``, which the compiled program keeps in its
``op_name`` metadata (the simulated roll and the shard_map collectives alike).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..dist.backend import as_backend
from .quantization import QuantizedTensor
from .scopes import scoped


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlanArrays:
    """Device-side halo plan (stacked, leading axis P). See graph/partition.py.

    ``bucket_sizes`` is ``None`` for the dense pairwise layout and a static
    per-ring-offset row-count tuple for the compact layout. ``wire_rows`` /
    ``real_rows`` are exchange-accounting constants (totals across partitions):
    rows the layout actually ships vs. true unpadded off-diagonal halo rows.
    """

    send_idx: jax.Array   # (P, rows) int32 — local rows to send, blocked/bucketed
    send_mask: jax.Array  # (P, rows) bool
    recv_mask: jax.Array  # (P, rows) bool
    n_local: int = dataclasses.field(metadata=dict(static=True))
    h_pad: int = dataclasses.field(metadata=dict(static=True))
    n_parts: int = dataclasses.field(metadata=dict(static=True))
    bucket_sizes: Optional[tuple[int, ...]] = dataclasses.field(
        default=None, metadata=dict(static=True))
    wire_rows: int = dataclasses.field(default=0, metadata=dict(static=True))
    real_rows: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def halo_rows(self) -> int:
        """Rows of one partition's halo buffer (dense: P*h_pad; compact: R)."""
        return int(self.send_idx.shape[1])

    @staticmethod
    def from_plan(plan) -> "PlanArrays":
        p = plan
        buckets = None
        if getattr(p, "layout", "dense") == "compact":
            buckets = tuple(int(b) for b in p.bucket_sizes)
        return PlanArrays(
            send_idx=jnp.asarray(p.send_idx.reshape(p.n_parts, -1), jnp.int32),
            send_mask=jnp.asarray(p.send_mask.reshape(p.n_parts, -1)),
            recv_mask=jnp.asarray(p.recv_mask),
            n_local=int(p.n_local), h_pad=int(p.h_pad), n_parts=int(p.n_parts),
            bucket_sizes=buckets, wire_rows=int(p.wire_rows()),
            real_rows=int(p.real_rows()))

    @staticmethod
    def from_spec(spec) -> "PlanArrays":
        """ShapeDtypeStruct stand-ins for the dry-run (no allocation). Analytic
        specs size the dense layout; wire/real rows fall back to the
        off-diagonal dense estimate (no masks exist to count real rows)."""
        s = spec
        rows = s.n_parts * s.h_pad
        wire = s.n_parts * (s.n_parts - 1) * s.h_pad
        return PlanArrays(
            send_idx=jax.ShapeDtypeStruct((s.n_parts, rows), jnp.int32),
            send_mask=jax.ShapeDtypeStruct((s.n_parts, rows), jnp.bool_),
            recv_mask=jax.ShapeDtypeStruct((s.n_parts, rows), jnp.bool_),
            n_local=int(s.n_local), h_pad=int(s.h_pad), n_parts=int(s.n_parts),
            bucket_sizes=None, wire_rows=wire, real_rows=wire)


@scoped("exchange")
def gather_boundary(h: jax.Array, plan: PlanArrays) -> jax.Array:
    """(P, n_local, d) -> (P, rows, d) packed send buffer (masked).

    ``plan.send_idx`` is the compaction permutation: for the compact layout the
    output has no dead pairwise blocks, only per-bucket alignment tails."""
    buf = jnp.take_along_axis(h, plan.send_idx[..., None], axis=1)
    return jnp.where(plan.send_mask[..., None], buf, 0)


@scoped("exchange")
def scatter_boundary_grad(g: jax.Array, plan: PlanArrays) -> jax.Array:
    """(P, rows, d) received grads -> (P, n_local, d) scatter-add onto owners.

    A node sent to multiple partitions accumulates all their gradients (sum) —
    Alg. 2 line 13."""
    g = jnp.where(plan.send_mask[..., None], g, 0)

    def one(gp, idx):
        return jnp.zeros((plan.n_local, g.shape[-1]), g.dtype).at[idx].add(gp)

    return jax.vmap(one)(g, plan.send_idx)


@scoped("exchange")
def exchange(x: jax.Array, backend=None) -> jax.Array:
    """The dense halo all-to-all. ``x``: (P_local, P*h_pad, ...) pairwise-blocked
    buffer.

    ``backend`` is a :class:`~repro.dist.backend.HaloBackend`; ``None`` (the
    simulated stacked transpose) and bare axis names are accepted for
    compatibility and normalized via ``as_backend``.
    """
    return as_backend(backend).exchange(x)


@scoped("exchange")
def exchange_quantized(qt: QuantizedTensor, backend=None) -> QuantizedTensor:
    """Exchange a dense quantized payload: data + error-compensation (scale,
    zero) move together (paper §3.2 Communicator)."""
    return as_backend(backend).exchange_quantized(qt)


@scoped("exchange")
def exchange_halo(x: jax.Array, plan: PlanArrays, backend=None,
                  reverse: bool = False) -> jax.Array:
    """Layout-dispatching halo exchange. Dense plans use the transpose
    (self-inverse, ``reverse`` ignored); compact plans run the ring buckets,
    reversed for the backward communication."""
    be = as_backend(backend)
    if plan.bucket_sizes is None:
        return be.exchange(x)
    return be.exchange_compact(x, plan.bucket_sizes, reverse=reverse)


@scoped("exchange")
def exchange_quantized_halo(qt: QuantizedTensor, plan: PlanArrays, backend=None,
                            reverse: bool = False) -> QuantizedTensor:
    """Layout-dispatching quantized exchange (payload + scale/zero together)."""
    be = as_backend(backend)
    if plan.bucket_sizes is None:
        return be.exchange_quantized(qt)
    return be.exchange_quantized_compact(qt, plan.bucket_sizes, reverse=reverse)


def exchange_bytes(plan: PlanArrays, d: int, bits: int,
                   scale_dtype=jnp.bfloat16) -> tuple[int, int]:
    """(payload, error-compensation) *true wire* bytes per exchange, totaled
    across partitions: diagonal self-blocks and padding rows are excluded —
    the Table-3 accounting and the roofline collective term."""
    from .quantization import comm_bytes
    return comm_bytes(plan.real_rows, d, bits, scale_dtype)


def wire_bytes(plan: PlanArrays, d: int, bits: int,
               scale_dtype=jnp.bfloat16) -> tuple[int, int]:
    """(payload, error-compensation) bytes this plan's layout actually ships per
    exchange, totaled across partitions — includes per-bucket alignment tails
    (compact) or pairwise padding to the global max (dense), but never the
    diagonal. ``wire_bytes - exchange_bytes`` is the padding overhead the
    compact layout exists to eliminate."""
    from .quantization import comm_bytes
    return comm_bytes(plan.wire_rows, d, bits, scale_dtype)
