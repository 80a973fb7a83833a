"""Error-feedback quantized weight-gradient all-reduce (beyond-paper).

Sylvie leaves the DP weight-gradient all-reduce in full precision because it
is negligible in the paper's 2-8 GPU setting (Fig. 2). At 256-512 chips the
all-reduce term grows with log(P) latency and byte volume, so we provide an
EF21-style compressed all-reduce that composes with Sylvie's Low-bit Module
(same quantizer) for the ``data`` axis:

    c_t   = Q_b(g_t - m_t + e_t)          per-device compress with memory
    e_t+1 = (g_t - m_t + e_t) - DQ(c_t)   local error feedback
    m_t+1 = m_t + psum(DQ(c_t)) / P       shared gradient estimate

``m`` (the running estimate) is replicated state; each step only the
*innovation* is quantized and reduced, so the estimate converges to the true
mean gradient while the wire carries b-bit payloads (Richtárik et al.,
EF21 [arXiv:2106.05203]; 1-bit Adam [arXiv:2102.02888]).

Off by default. The bit-width is part of the per-epoch communication
decision: any :class:`repro.policy.base.CommPolicy` whose ``EpochDecision``
sets ``ef_bits`` (e.g. ``Uniform(bits=1, ef_bits=2)``) routes the reduced
weight gradient through :func:`ef_allreduce` inside the step
(``train/gnn_step.py``); the EF error/estimate state lives in
``GNNTrainState.ef`` and :func:`ef_wire_bytes` joins the trainer's per-epoch
byte accounting.
"""
from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp

from ..core import quantization as qlib


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EFState:
    error: dict      # per-leaf local residual
    estimate: dict   # per-leaf shared gradient estimate (replicated)

    @staticmethod
    def zeros_like(params) -> "EFState":
        z = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        return EFState(error=z, estimate=jax.tree.map(jnp.zeros_like, z))


def _axis_size(axis_name) -> int:
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    n = 1
    for a in names:
        n *= jax.lax.axis_size(a)
    return n


def ef_allreduce(grads, state: EFState, bits: int = 1, axis_name=None):
    """-> (mean-gradient estimate tree, new EFState).

    Deterministic by construction: both compressors below are contractive
    *deterministic* maps (stochastic rounding breaks EF21 — see the inline
    note), so no PRNG key enters the signature.

    With ``axis_name=None`` (simulated / single-device) the wire is the
    identity and only the quantization noise path is exercised.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    e_leaves = jax.tree_util.tree_flatten(state.error)[0]
    m_leaves = jax.tree_util.tree_flatten(state.estimate)[0]
    new_e, new_m = [], []
    for g, e, m in zip(leaves, e_leaves, m_leaves):
        g = g.astype(jnp.float32)
        innov = g - m + e
        flat = innov.reshape(-1, innov.shape[-1]) if innov.ndim > 1 \
            else innov.reshape(1, -1)
        # Error feedback requires a CONTRACTIVE compressor. The Low-bit
        # Module's unbiased stochastic rounding has per-element variance
        # ~range^2/4 at 1 bit — above ||x||^2 for gaussian-ish vectors — and
        # the feedback loop diverges (measured: NaN within 60 rounds). At
        # 1 bit we therefore use scaled-sign (1-bit Adam's compressor,
        # delta = ||x||_1^2 / (D ||x||_2^2) > 0); >= 2 bits, deterministic
        # round-to-nearest affine is contractive enough. Same wire format:
        # packed bits + one bf16 scale per row.
        if bits == 1:
            scale = jnp.mean(jnp.abs(flat), axis=-1, keepdims=True)
            deq = (jnp.sign(flat) * scale).reshape(innov.shape)
        else:
            qt = qlib.quantize(flat, bits, stochastic=False)
            deq = qlib.dequantize(qt).reshape(innov.shape)
        new_e.append(innov - deq)
        if axis_name is not None:
            deq = jax.lax.psum(deq, axis_name) / _axis_size(axis_name)
        new_m.append(m + deq)
    est = jax.tree_util.tree_unflatten(treedef, new_m)
    return est, EFState(error=jax.tree_util.tree_unflatten(treedef, new_e),
                        estimate=est)


def ef_wire_bytes(params, bits: int) -> tuple[int, int]:
    """(payload, error-compensation) bytes one compressed all-reduce moves."""
    payload = ec = 0
    for p in jax.tree.leaves(params):
        rows = int(p.size // p.shape[-1]) if p.ndim > 1 else 1
        d = int(p.shape[-1]) if p.ndim > 1 else int(p.size)
        pb, eb = qlib.comm_bytes(rows, d, bits)
        payload += pb
        ec += eb
    return payload, ec
