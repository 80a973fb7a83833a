"""Low-bit Module: b-bit affine quantization with stochastic rounding (Sylvie §3.2).

Implements Equ. 3-5 of the paper:

    hbar = (h - min(h)) / (max(h) - min(h)) * B          with B = 2^b - 1
    q    = floor(hbar) + Bernoulli(hbar - floor(hbar))    (stochastic rounding, Equ. 4)
    h~   = q * (max - min) / B + min                      (dequantize, Equ. 5)

Per-*vector* (last axis) scale/zero-point — one (scale, zero) pair per node feature
vector, exactly as the paper's error-compensated information. Scale/zero are carried in
``scale_dtype`` (bf16 by default; the paper uses fp32 — see DESIGN.md §2).

Quantization is unbiased under stochastic rounding (Theorem 1):
    E[h~] = h,   Var(h~) = D * (max-min)^2 / (6 B^2).

Bit-widths:
  * b in {1, 2, 4}: values are packed 8//b per byte into uint8 (TPU-friendly payload).
  * b = 8: uint8, no packing.
  * b in {3, 5, 6, 7}: stored unpacked in uint8 (supported for the Fig.9 sweep).
  * b = 16: bf16 passthrough (no scale/zero).
  * b = 32: fp32 passthrough (identity — the "vanilla" baseline).

Implementation dispatch (the hot-path seam): :func:`quantize` / :func:`dequantize`
take an ``impl`` designator —

  * ``"jnp"``    — the pure-jnp reference path (always available, any bit-width);
  * ``"pallas"`` — the fused one-HBM-pass Pallas kernel (``repro.kernels.quant``:
    min/max reduce -> affine scale -> stochastic round -> bit-pack in one VMEM
    pass) for packable bit-widths {1, 2, 4, 8} with stochastic rounding; runs
    interpret mode off-TPU so tests/benchmarks can validate it anywhere;
  * ``"auto"`` / ``None`` — Pallas on a TPU backend, jnp elsewhere.

Both paths draw the same ``jax.random.uniform(key, h.shape)`` noise, so they are
bit-identical in interpret mode. Cases the kernel does not cover (passthrough or
odd bit-widths, deterministic rounding, scalar rows) silently fall back to jnp.

:func:`quantize` and :func:`dequantize` run under ``jax.named_scope("lowbit")``
on both paths: the noise draw, the pack and unpack, the kernels and the casts of
scale and zero all carry it in the compiled program's ``op_name`` metadata.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .scopes import scoped

PACKABLE_BITS = (1, 2, 4)
PASSTHROUGH_BITS = (16, 32)
PALLAS_BITS = (1, 2, 4, 8)        # widths the fused kernel implements
QUANT_IMPLS = ("auto", "jnp", "pallas")


def resolve_impl(impl: Optional[str] = None) -> str:
    """Resolve an ``impl`` designator to a concrete path ("jnp" | "pallas")."""
    if impl in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown quantize impl {impl!r}; pick from {QUANT_IMPLS}")
    return impl


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """Quantized payload + error-compensation info (scale, zero).

    ``data`` is uint8 (packed when bits in {1,2,4}) or bf16/fp32 for passthrough.
    ``scale``/``zero`` are per-leading-row (one per feature vector); empty arrays for
    passthrough bit-widths.
    """

    data: jax.Array
    scale: jax.Array
    zero: jax.Array
    bits: int = dataclasses.field(metadata=dict(static=True))
    feat_dim: int = dataclasses.field(metadata=dict(static=True))

    @property
    def payload_bits_per_value(self) -> float:
        return float(self.bits)


def _lanes_per_byte(bits: int) -> int:
    return 8 // bits if bits in PACKABLE_BITS else 1


def packed_width(feat_dim: int, bits: int) -> int:
    """Width of the uint8 payload row for a feat_dim-wide vector."""
    if bits in PASSTHROUGH_BITS:
        return feat_dim  # not bytes; dtype carries width
    k = _lanes_per_byte(bits)
    return (feat_dim + k - 1) // k


def comm_bytes(n_rows: int, feat_dim: int, bits: int,
               scale_dtype: jnp.dtype = jnp.bfloat16) -> tuple[int, int]:
    """(main payload bytes, error-compensation bytes) for one exchange buffer.

    Used by the Table-3 benchmark and the roofline collective-term accounting.
    """
    if bits == 32:
        return n_rows * feat_dim * 4, 0
    if bits == 16:
        return n_rows * feat_dim * 2, 0
    payload = n_rows * packed_width(feat_dim, bits)
    ec = 2 * n_rows * jnp.dtype(scale_dtype).itemsize  # scale + zero per row
    return payload, ec


def pack_bits(vals: jax.Array, bits: int) -> jax.Array:
    """Pack uint8 values in [0, 2^bits-1] along the last axis, 8//bits per byte.

    Strided lane groups: with ``w = packed_width(d, bits)``, byte ``j`` holds
    values ``j + i*w`` at bit offset ``i*bits`` for ``i < 8//bits`` (zero past
    ``d``) — the layout the Pallas kernel (``repro.kernels.quant``) emits.
    """
    if bits == 8 or bits not in PACKABLE_BITS:
        return vals.astype(jnp.uint8)
    k = _lanes_per_byte(bits)
    d = vals.shape[-1]
    w = packed_width(d, bits)
    vals = jnp.pad(vals.astype(jnp.uint8),
                   [(0, 0)] * (vals.ndim - 1) + [(0, k * w - d)])
    grouped = vals.reshape(*vals.shape[:-1], k, w)
    shifts = (jnp.arange(k, dtype=jnp.uint8) * np.uint8(bits))[:, None]
    return jnp.bitwise_or.reduce(grouped << shifts, axis=-2).astype(jnp.uint8)


def unpack_bits(packed: jax.Array, bits: int, feat_dim: int) -> jax.Array:
    """Inverse of :func:`pack_bits`; returns uint8 values of width ``feat_dim``."""
    if bits == 8 or bits not in PACKABLE_BITS:
        return packed[..., :feat_dim]
    k = _lanes_per_byte(bits)
    mask = np.uint8((1 << bits) - 1)
    shifts = (jnp.arange(k, dtype=jnp.uint8) * np.uint8(bits))[:, None]
    vals = (packed[..., None, :] >> shifts) & mask       # (..., k, w)
    return vals.reshape(*packed.shape[:-1], -1)[..., :feat_dim]


def theoretical_variance(h: jax.Array, bits: int) -> jax.Array:
    """Theorem 1 variance of the dequantized vector: D (max-min)^2 / (6 B^2)."""
    b = 2.0 ** bits - 1.0
    rng = jnp.max(h, -1) - jnp.min(h, -1)
    return h.shape[-1] * rng**2 / (6.0 * b**2)


def _rows(h: jax.Array) -> int:
    n = 1
    for s in h.shape[:-1]:
        n *= s
    return n


def _pallas_can_quantize(h, bits, key, stochastic) -> bool:
    return (bits in PALLAS_BITS and stochastic and key is not None
            and h.ndim >= 2 and h.shape[-1] > 0 and _rows(h) > 0)


def _quantize_pallas(h, bits, key, scale_dtype) -> QuantizedTensor:
    """Fused quantize+bitpack: one HBM read of the buffer, one packed write."""
    from ..kernels.quant import ops as kops
    d = h.shape[-1]
    lead = h.shape[:-1]
    # same noise stream as the jnp path (drawn at the unflattened shape) so the
    # two impls are bit-identical given one key
    u = jax.random.uniform(key, h.shape, dtype=jnp.float32)
    packed, scale, zero = kops.quantize_pack_rows(
        h.astype(jnp.float32).reshape(-1, d), u.reshape(-1, d), bits)
    return QuantizedTensor(packed.reshape(lead + (packed.shape[-1],)),
                           scale.reshape(lead).astype(scale_dtype),
                           zero.reshape(lead).astype(scale_dtype), bits, d)


def _dequantize_pallas(qt: QuantizedTensor, out_dtype) -> jax.Array:
    from ..kernels.quant import ops as kops
    w = qt.data.shape[-1]
    lead = qt.data.shape[:-1]
    out = kops.dequantize_rows(qt.data.reshape(-1, w),
                               qt.scale.reshape(-1).astype(jnp.float32),
                               qt.zero.reshape(-1).astype(jnp.float32),
                               qt.bits, qt.feat_dim)
    return out.reshape(lead + (qt.feat_dim,)).astype(out_dtype)


@scoped("lowbit")
def quantize(h: jax.Array, bits: int, key: Optional[jax.Array] = None,
             stochastic: bool = True,
             scale_dtype: jnp.dtype = jnp.bfloat16,
             impl: Optional[str] = None) -> QuantizedTensor:
    """Quantize ``h`` (..., D) to ``bits``-bit integers per Equ. 3-4.

    ``key`` is required when ``stochastic`` (training); deterministic
    round-to-nearest otherwise (eval / debugging). ``impl`` picks the
    implementation (see module docstring); unsupported cases fall back to jnp.
    """
    d = h.shape[-1]
    if bits == 32:
        return QuantizedTensor(h.astype(jnp.float32), jnp.zeros(h.shape[:-1] + (0,)),
                               jnp.zeros(h.shape[:-1] + (0,)), 32, d)
    if bits == 16:
        return QuantizedTensor(h.astype(jnp.bfloat16), jnp.zeros(h.shape[:-1] + (0,)),
                               jnp.zeros(h.shape[:-1] + (0,)), 16, d)
    if resolve_impl(impl) == "pallas" and _pallas_can_quantize(h, bits, key,
                                                               stochastic):
        return _quantize_pallas(h, bits, key, scale_dtype)

    big = 2.0 ** bits - 1.0
    h = h.astype(jnp.float32)
    lo = jnp.min(h, axis=-1, keepdims=True)
    hi = jnp.max(h, axis=-1, keepdims=True)
    rng = hi - lo
    safe = jnp.where(rng > 0, rng, 1.0)
    hbar = (h - lo) / safe * big                       # in [0, B]
    if stochastic:
        if key is None:
            raise ValueError("stochastic quantization requires a PRNG key")
        floor = jnp.floor(hbar)
        frac = hbar - floor
        u = jax.random.uniform(key, hbar.shape, dtype=jnp.float32)
        q = floor + (u < frac).astype(jnp.float32)     # Equ. 4
    else:
        q = jnp.round(hbar)
    q = jnp.clip(q, 0.0, big).astype(jnp.uint8)
    packed = pack_bits(q, bits)
    scale = (rng / big).astype(scale_dtype)[..., 0]
    zero = lo.astype(scale_dtype)[..., 0]
    return QuantizedTensor(packed, scale, zero, bits, d)


@scoped("lowbit")
def dequantize(qt: QuantizedTensor, out_dtype: jnp.dtype = jnp.float32,
               impl: Optional[str] = None) -> jax.Array:
    """Recover full-precision values per Equ. 5 (unbiased given Equ. 4)."""
    if qt.bits in PASSTHROUGH_BITS:
        return qt.data.astype(out_dtype)
    if (resolve_impl(impl) == "pallas" and qt.bits in PALLAS_BITS
            and qt.data.ndim >= 2 and _rows(qt.data) > 0 and qt.feat_dim > 0):
        return _dequantize_pallas(qt, out_dtype)
    vals = unpack_bits(qt.data, qt.bits, qt.feat_dim).astype(jnp.float32)
    out = vals * qt.scale[..., None].astype(jnp.float32) \
        + qt.zero[..., None].astype(jnp.float32)
    return out.astype(out_dtype)


def fake_quantize(h: jax.Array, bits: int, key: Optional[jax.Array] = None,
                  stochastic: bool = True) -> jax.Array:
    """dequantize(quantize(h)) in one call — the simulated-communication value."""
    return dequantize(quantize(h, bits, key, stochastic), h.dtype)


# ---------------------------------------------------------------------------
# Straight-through wrapper: the *computation* treats quant/dequant as identity in
# the backward pass; Sylvie quantizes the backward *communication* separately
# (Alg. 2 lines 10-12). Exposed for the non-exchange uses (EF21 grad compression,
# quantized MoE dispatch) that need gradients to flow through.
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(1, 3))
def straight_through_quantize(h, bits, key, stochastic=True):
    return fake_quantize(h, bits, key, stochastic)


def _stq_fwd(h, bits, key, stochastic=True):
    return fake_quantize(h, bits, key, stochastic), None


def _stq_bwd(bits, stochastic, _, g):
    return (g, None)


straight_through_quantize.defvjp(_stq_fwd, _stq_bwd)
