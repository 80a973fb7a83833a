"""Overlapped (double-buffered) halo-exchange schedule.

The blocking schedule (``core/sylvie.py``) fuses issue and consumption of a
halo exchange into one dependency chain per site: ``gather -> quantize ->
exchange -> dequantize -> aggregate``. Nothing sits between the collective
and its consumer, so a scheduler has no room to hide the wire time — every
comm byte is *exposed*.

This module restructures each exchange site into the GNNPipe-style
issue/land protocol behind the exact same :class:`~repro.dist.backend
.HaloBackend` primitives:

* **issue** — the quantized send is emitted as early as the data allows
  (right after the boundary gather), exactly once per site per direction:
  the collective census is *identical* to the blocking schedule (contract
  RC209 — no duplicate sends, no extra collectives).
* **land** — the received buffer passes through ``backend.fence`` (an
  ``optimization_barrier``) before dequantize. The fence is the in-order
  consumption point: it keeps XLA from fusing the collective into its
  consumer, so the exchange stays a standalone op the latency-hiding
  scheduler can run concurrently with the site's *local* aggregation
  (intra-partition edges need no halo rows), while the halo-dependent
  boundary contribution consumes the landed values — the same values, in
  program order. The fence is the identity on data, which is why the
  sync/fresh overlap schedule is **bit-exact** to blocking (asserted by
  ``tests/test_overlap.py``).

Buffer lifetimes (the double buffer):

* sync/fresh (:func:`overlap_quantized_halo`) — ``inflight`` is issued and
  landed within the same layer step; the fence marks the land.
* async micro-step (:func:`overlap_stale_halo` + :func:`overlap_fresh_halo`)
  — the site consumes the *previous* layer-step's landed buffer
  (``feat_cache``, the Bounded Staleness contract) while this step's
  ``inflight`` is issued through the fence and becomes the next step's
  ``feat_cache``. Gradients ride the same ``gslot`` dataflow as the
  blocking async path.

The module also owns the DESIGN §8/§14 comm-time model extension: under the
overlap schedule each site's modeled comm time splits into an *overlapped*
share (hidden under that layer's local compute window) and an *exposed*
remainder; blocking exposes everything. Scenario reports and
``benchmarks/bench_overlap.py`` consume :func:`split_comm_time` /
:func:`site_comm_seconds`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core import quantization as qlib
from ..core.exchange import (PlanArrays, exchange_bytes,
                             exchange_quantized_halo, gather_boundary,
                             scatter_boundary_grad)
from ..core.sylvie import SCHEDULES


def fence(backend, tree):
    """The landing fence: identity on data, a scheduling barrier in the
    lowered program. Backends may override (``HaloBackend.fence``) — e.g. a
    real async transport would resolve its in-flight handle here; both
    shipped backends lower to ``lax.optimization_barrier``."""
    f = getattr(backend, "fence", None)
    return f(tree) if f is not None else jax.lax.optimization_barrier(tree)


def _issue(buf, key, bits, stochastic, scale_dtype, backend, plan,
           reverse=False, impl="auto"):
    """Issue one direction's quantized exchange (same ops as the blocking
    ``_q_roundtrip`` up to the collective — identical census)."""
    qt = qlib.quantize(buf, bits, key, stochastic, scale_dtype, impl=impl)
    return exchange_quantized_halo(qt, plan, backend, reverse=reverse)


def _land(inflight, backend, impl="auto"):
    """Land an in-flight exchange: fence, then dequantize the received
    payload. The fence pins consumption after the issue in program order
    without touching the values."""
    return qlib.dequantize(fence(backend, inflight), impl=impl)


# ---------------------------------------------------------------------------
# sync/fresh schedule: issue early, land in-order, bit-exact to blocking
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def overlap_quantized_halo(h, plan: PlanArrays, fwd_key, bwd_key,
                           fwd_bits: int, bwd_bits: int, stochastic: bool,
                           scale_dtype, backend, impl):
    """Overlapped twin of :func:`repro.core.sylvie.quantized_halo` — same
    signature, same values, fenced issue/land structure."""
    buf = gather_boundary(h, plan)
    inflight = _issue(buf, fwd_key, fwd_bits, stochastic, scale_dtype,
                      backend, plan, impl=impl)
    out = _land(inflight, backend, impl=impl)
    return jnp.where(plan.recv_mask[..., None], out, 0)


def _oqh_fwd(h, plan, fwd_key, bwd_key, fwd_bits, bwd_bits, stochastic,
             scale_dtype, backend, impl):
    out = overlap_quantized_halo(h, plan, fwd_key, bwd_key, fwd_bits,
                                 bwd_bits, stochastic, scale_dtype, backend,
                                 impl)
    return out, (plan, bwd_key)


def _oqh_bwd(fwd_bits, bwd_bits, stochastic, scale_dtype, backend, impl, res,
             g):
    plan, bwd_key = res
    g = jnp.where(plan.recv_mask[..., None], g, 0)
    inflight = _issue(g, bwd_key, bwd_bits, stochastic, scale_dtype, backend,
                      plan, reverse=True, impl=impl)
    back = _land(inflight, backend, impl=impl)
    grad_h = scatter_boundary_grad(back, plan)
    return (grad_h, None, None, None)


overlap_quantized_halo.defvjp(_oqh_fwd, _oqh_bwd)


# ---------------------------------------------------------------------------
# async micro-step: consume the previous layer-step's landed buffer
# ---------------------------------------------------------------------------
def overlap_fresh_halo(h, plan: PlanArrays, key, fwd_bits, stochastic,
                       scale_dtype, backend, impl="auto"):
    """Issue this layer-step's exchange through the fence; the landed result
    is the *next* step's ``feat_cache`` (the double buffer's inflight side).
    Detached like :func:`repro.core.sylvie.fresh_halo`."""
    buf = gather_boundary(jax.lax.stop_gradient(h), plan)
    inflight = _issue(buf, key, fwd_bits, stochastic, scale_dtype, backend,
                      plan, impl=impl)
    out = _land(inflight, backend, impl=impl)
    return jnp.where(plan.recv_mask[..., None], out, 0)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def overlap_stale_halo(h, feat_cache, grad_in, gslot, plan: PlanArrays,
                       bwd_key, bwd_bits: int, stochastic: bool, scale_dtype,
                       backend, impl):
    """Overlapped twin of :func:`repro.core.sylvie.stale_halo`: the primal
    consumes the previous layer-step's landed buffer under the Bounded
    Staleness contract; the backward issues this step's gradient exchange
    through the fence (it lands as the next step's ``grad_in``)."""
    del h, grad_in, gslot, plan, bwd_key
    return feat_cache


def _osh_fwd(h, feat_cache, grad_in, gslot, plan, bwd_key, bwd_bits,
             stochastic, scale_dtype, backend, impl):
    return feat_cache, (plan, grad_in, bwd_key)


def _osh_bwd(bwd_bits, stochastic, scale_dtype, backend, impl, res, g):
    plan, grad_in, bwd_key = res
    g = jnp.where(plan.recv_mask[..., None], g, 0)
    inflight = _issue(g, bwd_key, bwd_bits, stochastic, scale_dtype, backend,
                      plan, reverse=True, impl=impl)
    fresh_grad = _land(inflight, backend, impl=impl)
    fresh_grad = jnp.where(plan.send_mask[..., None], fresh_grad, 0)
    grad_h = scatter_boundary_grad(grad_in, plan)
    return (grad_h, None, None, fresh_grad, None, None)


overlap_stale_halo.defvjp(_osh_fwd, _osh_bwd)


# ---------------------------------------------------------------------------
# DESIGN §8/§14 comm-time model: exposed vs overlapped split
# ---------------------------------------------------------------------------
def site_comm_seconds(plan: PlanArrays, site_dims, decision, ici_bw: float,
                      scale_dtype=jnp.bfloat16) -> tuple[float, ...]:
    """Per-site modeled comm seconds (payload + error compensation, forward
    + backward, per device): ``bytes_i / n_parts / ici_bw`` — the per-site
    decomposition of the scenario reports' ``modeled_tpu_comm_s``."""
    out = []
    for d, sd in zip(site_dims, decision.sites):
        total = 0.0
        for bits in (sd.fwd_bits, sd.bwd_bits):
            pb, eb = exchange_bytes(plan, d, bits, scale_dtype)
            total += pb + eb
        out.append(total / plan.n_parts / ici_bw)
    return tuple(out)


def split_comm_time(site_comm_s, site_compute_s, schedule: str
                    ) -> tuple[float, float]:
    """(exposed_s, overlapped_s) per step under ``schedule``.

    Blocking exposes every comm second. Overlap hides, per site, up to that
    site's local-compute window (the intra-partition aggregation the issued
    exchange runs under): ``overlapped_i = min(comm_i, compute_i)``; the
    remainder stays exposed on the critical path. Modeled step time is then
    ``sum(compute) + exposed`` (== compute + comm for blocking).
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; known: {SCHEDULES}")
    total = float(sum(site_comm_s))
    if schedule != "overlap":
        return total, 0.0
    overlapped = float(sum(min(c, w) for c, w
                           in zip(site_comm_s, site_compute_s)))
    return total - overlapped, overlapped


def modeled_step_seconds(site_comm_s, site_compute_s, schedule: str) -> float:
    """Modeled per-step seconds: local compute plus the exposed comm share."""
    exposed, _ = split_comm_time(site_comm_s, site_compute_s, schedule)
    return float(sum(site_compute_s)) + exposed
