"""Checksummed quantized exchange: corruption injection + detection.

The quantized wire payload gets a per-row integrity word: the sender computes
an int32 byte-sum checksum over each row of the (packed or passthrough)
payload *before* any injected corruption, ships it through the same exchange
as an int32 sidecar, and the receiver recomputes it over what actually
arrived. A mismatched row is *never dequantized into the model* — the halo
primitives of ``core/sylvie.py`` treat it exactly like a dropped row and fall
back to the site's cached halo.

Injected corruption is a single XOR of bit 0 of byte 0 of the row — the
smallest possible wire upset, and one a byte-sum checksum detects with
certainty (the sum changes by exactly ±1). Real multi-bit upsets could in
principle collide with a sum; the injection deliberately stays in the
guaranteed-detectable regime so the tests assert detection, not probability.

Everything here is traced (masks are data); byte views use
``lax.bitcast_convert_type`` so packed uint8, bf16 and f32 payloads all take
the same path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .exchange import PlanArrays, exchange_halo, exchange_quantized_halo
from .quantization import QuantizedTensor


def _byte_view(data: jax.Array) -> jax.Array:
    """(P, rows, w) any dtype -> (P, rows, bytes) uint8 view."""
    if data.dtype == np.uint8:
        return data
    b = jax.lax.bitcast_convert_type(data, jnp.uint8)
    return b.reshape(data.shape[:2] + (-1,))


def row_checksum(data: jax.Array) -> jax.Array:
    """(P, rows, w) payload -> (P, rows) int32 byte-sum checksum."""
    return _byte_view(data).astype(jnp.int32).sum(axis=-1)


def flip_rows(data: jax.Array, mask: jax.Array) -> jax.Array:
    """XOR bit 0 of byte 0 of every row where ``mask`` (P, rows) is set."""
    if data.dtype == np.uint8:
        bump = jnp.zeros_like(data).at[..., 0].set(mask.astype(jnp.uint8))
        return data ^ bump
    bv = jax.lax.bitcast_convert_type(data, jnp.uint8)
    bump = jnp.zeros_like(bv).at[..., 0, 0].set(mask.astype(jnp.uint8))
    return jax.lax.bitcast_convert_type(bv ^ bump, data.dtype)


def checked_exchange(qt: QuantizedTensor, plan: PlanArrays, backend,
                     corrupt_send: jax.Array, drop_recv: jax.Array,
                     reverse: bool = False
                     ) -> tuple[QuantizedTensor, jax.Array]:
    """Exchange ``qt`` with fault injection; -> (received qt, ok mask).

    ``corrupt_send`` (P, rows) flips payload bits on the send side;
    ``drop_recv`` (P, rows) marks rows whose message was lost (the data still
    moves — the stacked/sharded collective is all-or-nothing — but the row is
    condemned). ``ok`` is False exactly where the receiver must fall back to
    its cache: checksum mismatch or drop. Scale/zero sidecars travel
    untouched; corrupting them would also surface as a checksum-clean row
    with wrong values, which is out of this model's scope (documented in
    DESIGN.md §12).
    """
    sent_sum = row_checksum(qt.data)
    qt = QuantizedTensor(data=flip_rows(qt.data, corrupt_send),
                         scale=qt.scale, zero=qt.zero,
                         bits=qt.bits, feat_dim=qt.feat_dim)
    qr = exchange_quantized_halo(qt, plan, backend, reverse=reverse)
    recv_sum = exchange_halo(sent_sum[..., None], plan, backend,
                             reverse=reverse)[..., 0]
    ok = (row_checksum(qr.data) == recv_sum) & ~drop_recv
    return qr, ok
