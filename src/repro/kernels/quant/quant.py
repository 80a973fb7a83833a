"""Pallas TPU kernels: fused b-bit quantize+bitpack and unpack+dequantize.

The paper's Low-bit Module sits on the critical path of *every* layer (its §4.4
overhead analysis shows it must stay far below the communication savings). On
GPU Sylvie uses a CUDA kernel; on TPU we fuse the whole pipeline —

    per-row min/max reduce -> affine scale -> stochastic round -> bit-pack

— into one VMEM pass so the boundary buffer is read from HBM exactly once and
the packed payload written once (arithmetic intensity is tiny; the kernel is
HBM-bandwidth-bound, so one pass is the roofline).

Tiling: grid over row blocks. Each invocation holds a ``(block_rows, d)`` tile
of the send buffer plus the same-shape uniform-noise tile in VMEM, and emits a
``(block_rows, w)`` uint8 tile, ``w = ceil(d / lanes)``, plus per-row
``(scale, zero)`` as ``(block_rows, 1)`` columns. ``d`` is the feature width of
one GNN layer (32-1433 here) so a tile is <= a few hundred KB — far under the
~16 MB VMEM budget; ``block_rows`` defaults to 256 rows to keep the sublane
dimension busy.

Packing uses strided lane groups: byte ``j`` holds values ``j, j + w, j + 2w,
...`` at bit offsets ``0, bits, 2 bits, ...``. Group ``i`` is the lane slice
``[i w, (i + 1) w)`` of the tile, so packing is shifts and ORs of lane slices
and unpacking is shifts and masks stored to lane slices. Mosaic lowers these;
it does not lower the lane-splitting reshape a contiguous layout needs
(``(br, d) -> (br, w, lanes)``), nor uint8 <-> float casts, which go through
int32 here.

Stochastic-rounding noise is passed in as a uniform tensor generated with
``jax.random.uniform`` outside the kernel (counter-based, reproducible across
restarts) rather than via ``pltpu.prng_random_bits`` — keeping the kernel a
pure function of its inputs lets interpret-mode CPU validation be bit-exact
against ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256


def _quantize_kernel(h_ref, u_ref, packed_ref, scale_ref, zero_ref, *,
                     bits: int, d: int):
    h = h_ref[...].astype(jnp.float32)              # (br, d)
    u = u_ref[...]
    big = np.float32(2.0**bits - 1.0)
    lo = jnp.min(h, axis=-1, keepdims=True)
    hi = jnp.max(h, axis=-1, keepdims=True)
    rng = hi - lo
    safe = jnp.where(rng > 0, rng, 1.0)
    hbar = (h - lo) / safe * big
    floor = jnp.floor(hbar)
    q = floor + (u < (hbar - floor)).astype(jnp.float32)
    q = jnp.clip(q, 0.0, big).astype(jnp.int32)

    # strided lane groups: byte j holds values j, j + w, j + 2w, ... (the
    # layout of core.quantization.pack_bits); lane slices only, no reshape
    w = packed_ref.shape[-1]
    packed = q[:, :w]
    for i in range(1, 8 // bits):
        n = min(w, d - i * w)                       # lanes of group i
        if n <= 0:
            break
        seg = q[:, i * w:i * w + n]
        if n < w:
            seg = jnp.concatenate(
                [seg, jnp.zeros((seg.shape[0], w - n), jnp.int32)], axis=-1)
        packed = packed | (seg << (i * bits))
    packed_ref[...] = packed.astype(jnp.uint8)
    scale_ref[...] = rng / big
    zero_ref[...] = lo


def _dequantize_kernel(packed_ref, scale_ref, zero_ref, out_ref, *,
                       bits: int, d: int):
    packed = packed_ref[...].astype(jnp.int32)      # (br, w)
    w = packed.shape[-1]
    mask = (1 << bits) - 1
    scale, zero = scale_ref[...], zero_ref[...]     # (br, 1)
    for i in range(8 // bits):
        n = min(w, d - i * w)                       # lanes of group i
        if n <= 0:
            break
        vals = ((packed >> (i * bits)) & mask)[:, :n].astype(jnp.float32)
        out_ref[:, i * w:i * w + n] = vals * scale + zero


def _grid(rows: int, block_rows: int) -> tuple[int, int]:
    br = min(block_rows, rows)
    return (rows + br - 1) // br, br


@functools.partial(jax.jit, static_argnames=("bits", "block_rows", "interpret"))
def quantize_pack(h: jax.Array, u: jax.Array, bits: int = 1,
                  block_rows: int = DEFAULT_BLOCK_ROWS, interpret: bool = False):
    """(rows, d) -> (packed (rows, d//lanes) uint8, scale (rows,), zero (rows,))."""
    rows, d = h.shape
    n_blocks, br = _grid(rows, block_rows)
    pad = n_blocks * br - rows
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        u = jnp.pad(u, ((0, pad), (0, 0)))
    w = (d + (8 // bits) - 1) // (8 // bits)
    out_shapes = (
        jax.ShapeDtypeStruct((n_blocks * br, w), jnp.uint8),
        jax.ShapeDtypeStruct((n_blocks * br, 1), jnp.float32),
        jax.ShapeDtypeStruct((n_blocks * br, 1), jnp.float32),
    )
    packed, scale, zero = pl.pallas_call(
        functools.partial(_quantize_kernel, bits=bits, d=d),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, w), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))),
        out_shape=out_shapes,
        interpret=interpret,
    )(h, u)
    return packed[:rows], scale[:rows, 0], zero[:rows, 0]


@functools.partial(jax.jit, static_argnames=("bits", "d", "block_rows", "interpret"))
def unpack_dequantize(packed: jax.Array, scale: jax.Array, zero: jax.Array,
                      bits: int, d: int, block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: bool = False) -> jax.Array:
    """(rows, d//lanes) uint8 + (rows,) scale/zero -> (rows, d) float32."""
    rows, w = packed.shape
    n_blocks, br = _grid(rows, block_rows)
    pad = n_blocks * br - rows
    scale, zero = scale[:, None], zero[:, None]
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0)))
        scale = jnp.pad(scale, ((0, pad), (0, 0)))
        zero = jnp.pad(zero, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, bits=bits, d=d),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((br, w), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * br, d), jnp.float32),
        interpret=interpret,
    )(packed, scale, zero)
    return out[:rows]
