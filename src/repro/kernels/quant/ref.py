"""Pure-jnp oracle for the fused quantize+bitpack / unpack+dequantize kernels.

Semantically identical to ``repro.core.quantization`` but with the kernel's exact
I/O contract (flat 2-D buffers, uniform noise passed in explicitly) so the Pallas
kernel can be validated bit-exactly in interpret mode.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ...core.quantization import pack_bits, unpack_bits


def quantize_pack_ref(h: jnp.ndarray, u: jnp.ndarray, bits: int):
    """(rows, d) float32, (rows, d) uniform[0,1) -> (packed uint8, scale, zero).

    Per-row affine quantization (paper Equ. 3) with stochastic rounding (Equ. 4),
    packed 8//bits lanes per byte in strided lane groups (``pack_bits``).
    """
    big = np.float32(2.0**bits - 1.0)
    lo = jnp.min(h, axis=-1, keepdims=True)
    hi = jnp.max(h, axis=-1, keepdims=True)
    rng = hi - lo
    safe = jnp.where(rng > 0, rng, 1.0)
    hbar = (h - lo) / safe * big
    floor = jnp.floor(hbar)
    q = floor + (u < (hbar - floor)).astype(jnp.float32)
    q = jnp.clip(q, 0.0, big).astype(jnp.uint8)

    packed = pack_bits(q, bits)
    scale = (rng[:, 0] / big).astype(jnp.float32)
    zero = lo[:, 0].astype(jnp.float32)
    return packed, scale, zero


def unpack_dequantize_ref(packed: jnp.ndarray, scale: jnp.ndarray,
                          zero: jnp.ndarray, bits: int, d: int) -> jnp.ndarray:
    """(rows, packed_width) uint8 + per-row (scale, zero) -> (rows, d) float32."""
    vals = unpack_bits(packed, bits, d).astype(jnp.float32)
    return vals * scale[:, None] + zero[:, None]
