"""Sylvie: one-bit quantized halo communication, synchronous and asynchronous.

Three communication modes (paper §3):

* ``vanilla``  — full-precision synchronous exchange (the DGL baseline). Same code
  path as Sylvie-S with ``bits=32`` (quantize is then the identity).
* ``sync``     — **Sylvie-S**: quantize -> all-to-all -> dequantize each layer, both
  passes. The backward pass communicates *quantized feature gradients*
  (Alg. 2 lines 10-12) via the custom_vjp below.
* ``async``    — **Sylvie-A**: layer compute consumes the *previous step's* halo
  features (``feat_cache``); the fresh quantized exchange is emitted as a
  new cache for the next step, so XLA can overlap it with compute.
  Backward mirrors it: the cotangent on the stale halo is exchanged and
  surfaces as the gradient of a zero-valued ``gslot`` input, becoming the
  next step's ``grad_in`` (one-step-stale boundary gradients). A site whose
  input is the step's own node features sends no boundary gradient: the
  features are data, so nothing reads that gradient, and the site consumes
  its cache without ``stale_halo``, leaving autodiff no backward to run.

What each exchange site does in a given epoch — forward/backward bit-widths,
stochastic vs deterministic rounding, BNS boundary sampling — is a
:class:`repro.policy.base.SiteDecision`: ``SylvieComm`` consumes
``decision.sites[i]`` at the i-th ``halo`` call, so a
:class:`~repro.policy.base.CommPolicy` can vary precision per site and per
epoch without touching this module. Every decision field is static (it rides
the ``custom_vjp`` nondiff argnums), so jit compiles one executable per
distinct decision. Constructing ``SylvieComm`` without a decision falls back
to the one global ``SylvieConfig`` choice (the Uniform degenerate case).

Buffer layout and quantizer implementation are both plan/config decisions made
here once for every site:

* the exchange direction matters for compact (ring-bucket) plans — the forward
  exchange and the backward communication run opposite ring directions
  (``exchange_halo(..., reverse=True)``); dense plans are involutions and
  ignore the flag;
* ``SylvieConfig.quant_impl`` picks the Low-bit-Module implementation
  ("auto" = fused Pallas kernel on TPU, jnp elsewhere) — only the live rows of
  the compacted buffer are quantized, so Low-bit-Module FLOPs track the actual
  boundary set, not the padded worst case (paper §4.4 overhead budget).

Each of the three halo primitives (``quantized_halo``, ``stale_halo``,
``fresh_halo``) runs one Low-bit round trip per direction and takes two
more inputs: a static ``fenced`` flag, which lands the exchange through
``backend.fence`` (the ``"overlap"`` schedule, DESIGN §14), and an optional
fault block (:class:`HaloFaults`, data), which checksums the exchange and
lets a condemned row fall back to its stale value (DESIGN §12). With neither,
each traces the blocking program.

The *Bounded Staleness Adaptor* (paper §3.3) is the
``repro.policy.builtin.BoundedStaleness`` policy; the trainer runs the policy
loop (``train/trainer.py``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .. import obs
from ..dist.backend import as_backend
from ..policy.base import SiteDecision
from . import quantization as qlib
from .exchange import (PlanArrays, exchange_quantized_halo,
                       gather_boundary, scatter_boundary_grad)
from .wire import checked_exchange

Mode = str  # "vanilla" | "sync" | "async"

# Exchange schedules: "blocking" consumes each halo exchange where it is
# produced; "overlap" lands it through ``backend.fence`` (the primitives'
# ``fenced`` flag) so the collective stays a standalone op the scheduler can
# run under the layer's local aggregation. The fence is the identity on
# values, so the two schedules compute the same bits.
SCHEDULES = ("blocking", "overlap")


@dataclasses.dataclass(frozen=True)
class SylvieConfig:
    mode: Mode = "sync"
    bits: int = 1
    stochastic: bool = True
    scale_dtype: jnp.dtype = jnp.bfloat16
    # Low-bit Module implementation: "auto" (Pallas fused kernel on TPU, jnp
    # elsewhere) | "jnp" | "pallas" (interpret mode off-TPU).
    quant_impl: str = "auto"
    # BNS-GCN baseline (Wan et al. 2022a): random boundary-node sampling.
    # Each epoch keeps a (1-p) fraction of halo rows, scaled by 1/(1-p);
    # p=0 disables. Used by the Table-2 baseline comparison.
    boundary_sample_p: float = 0.0
    # Exchange schedule (see SCHEDULES above). An EpochDecision's schedule
    # overrides this when one is threaded into the step.
    schedule: str = "blocking"

    @property
    def effective_bits(self) -> int:
        return 32 if self.mode == "vanilla" else self.bits

    def replace(self, **kw) -> "SylvieConfig":
        return dataclasses.replace(self, **kw)


class HaloFaults(NamedTuple):
    """A fault-armed site's fault block, riding as data: ``masks`` is the
    site's :class:`~repro.faults.plan.SiteFaults` and ``fallback`` what a
    condemned received row (dropped, or failing its checksum) keeps instead —
    the previous halo cache for ``quantized_halo`` / ``fresh_halo``, the
    previous ``grad_in`` for ``stale_halo``. Both are zero outside their
    buffer's live rows (caches start zero and are only ever written by these
    masked blends), so one blend is all a condemned row needs."""

    fallback: jax.Array
    masks: Any


def _roundtrip(buf, key, bits, stochastic, scale_dtype, backend, plan, impl,
               fenced, masks=None, reverse=False):
    """quantize -> exchange -> dequantize: one direction of the Low-bit
    Module. -> (values, ok).

    ``reverse`` is the backward communication (compact plans run their rings
    the other way). ``masks`` (a site's ``SiteFaults``) checksums the
    exchange and injects that direction's faults; ``ok`` marks the rows the
    receiver may use, ``None`` on the clean path. ``fenced`` lands the
    exchange through ``backend.fence`` before dequantizing (the ``overlap``
    schedule): identity on the values, a scheduling barrier in the program."""
    qt = qlib.quantize(buf, bits, key, stochastic, scale_dtype, impl=impl)
    if masks is None:
        qr, ok = exchange_quantized_halo(qt, plan, backend,
                                         reverse=reverse), None
    else:
        corrupt, drop = ((masks.corrupt_bwd, masks.drop_bwd) if reverse
                         else (masks.corrupt_fwd, masks.drop_fwd))
        qr, ok = checked_exchange(qt, plan, backend, corrupt, drop,
                                  reverse=reverse)
    if fenced:
        qr = backend.fence(qr)
    return qlib.dequantize(qr, impl=impl), ok


def _blend(x, mask, ok, fallback):
    """Rows of ``x`` where ``mask`` holds and the checked exchange accepted
    the row (``ok``; ``None`` accepts every row), ``fallback`` elsewhere."""
    if ok is not None:
        mask = ok & mask
    return jnp.where(mask[..., None], x, fallback)


def _masks(faults):
    return None if faults is None else faults.masks


def _fallback(faults):
    return 0 if faults is None else faults.fallback


# ---------------------------------------------------------------------------
# Sylvie-S: synchronous quantized exchange with quantized backward communication
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def quantized_halo(h, faults, plan: PlanArrays, fwd_key, bwd_key,
                   fwd_bits: int, bwd_bits: int, stochastic: bool,
                   scale_dtype, backend, impl, fenced: bool):
    """(P, n_local, d) -> (P, halo_rows, d) dequantized halo features.

    ``fwd_bits`` quantizes the forward feature exchange, ``bwd_bits`` the
    backward gradient communication — per-site, per-direction decisions.
    ``faults`` (a :class:`HaloFaults` or ``None``) keeps the previous cache
    row wherever the forward wire failed; a lost returned-gradient row
    contributes zero, which is what a synchronous step's drained gradient
    cache holds."""
    buf = gather_boundary(h, plan)
    out, ok = _roundtrip(buf, fwd_key, fwd_bits, stochastic, scale_dtype,
                         backend, plan, impl, fenced, _masks(faults))
    return _blend(out, plan.recv_mask, ok, _fallback(faults))


def _qh_fwd(h, faults, plan, fwd_key, bwd_key, fwd_bits, bwd_bits,
            stochastic, scale_dtype, backend, impl, fenced):
    out = quantized_halo(h, faults, plan, fwd_key, bwd_key, fwd_bits,
                         bwd_bits, stochastic, scale_dtype, backend, impl,
                         fenced)
    return out, (plan, bwd_key, _masks(faults))


def _qh_bwd(fwd_bits, bwd_bits, stochastic, scale_dtype, backend, impl,
            fenced, res, g):
    plan, bwd_key, masks = res
    g = jnp.where(plan.recv_mask[..., None], g, 0)
    back, ok = _roundtrip(g, bwd_key, bwd_bits, stochastic, scale_dtype,
                          backend, plan, impl, fenced, masks, reverse=True)
    if ok is not None:
        # scatter_boundary_grad masks the send rows itself; only a checked
        # exchange has rows to condemn
        back = _blend(back, plan.send_mask, ok, 0)
    grad_h = scatter_boundary_grad(back, plan)
    return (grad_h, None, None, None, None)


quantized_halo.defvjp(_qh_fwd, _qh_bwd)


# ---------------------------------------------------------------------------
# Sylvie-A: stale halo consumption + fresh exchange emission
# ---------------------------------------------------------------------------
def fresh_halo(h, faults, plan: PlanArrays, key, fwd_bits, stochastic,
               scale_dtype, backend, impl, fenced):
    """The concurrent forward exchange: quantize this step's boundary features and
    deliver them as *next* step's cache. Detached — no gradient flows (staleness
    is handled by the grad_in path). Under ``faults`` a condemned row leaves
    the old cache row in place, one step staler."""
    buf = gather_boundary(jax.lax.stop_gradient(h), plan)
    out, ok = _roundtrip(buf, key, fwd_bits, stochastic, scale_dtype, backend,
                         plan, impl, fenced, _masks(faults))
    return _blend(out, plan.recv_mask, ok,
                  0 if faults is None else jax.lax.stop_gradient(faults.fallback))


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def stale_halo(h, feat_cache, grad_in, gslot, faults, plan: PlanArrays,
               bwd_key, bwd_bits: int, stochastic: bool, scale_dtype, backend,
               impl, fenced: bool):
    """Consume the stale halo; wire the staleness dataflow into autodiff.

    * primal output  = ``feat_cache`` (previous step's dequantized halo features)
    * grad wrt ``h``     = ``grad_in`` scattered onto boundary nodes (previous
      step's incoming boundary gradients — Alg. 2 line 13, one step stale)
    * grad wrt ``gslot`` = this step's outgoing quantized gradient exchange at
      ``bwd_bits`` (surfaces to the caller as the next step's ``grad_in``);
      under ``faults`` a condemned row keeps its ``grad_in`` row, one epoch
      staler
    """
    del h, grad_in, gslot, faults, plan, bwd_key
    return feat_cache


def _sh_fwd(h, feat_cache, grad_in, gslot, faults, plan, bwd_key,
            bwd_bits, stochastic, scale_dtype, backend, impl, fenced):
    return feat_cache, (plan, grad_in, bwd_key, faults)


def _sh_bwd(bwd_bits, stochastic, scale_dtype, backend, impl, fenced, res, g):
    plan, grad_in, bwd_key, faults = res
    g = jnp.where(plan.recv_mask[..., None], g, 0)
    fresh_grad, ok = _roundtrip(g, bwd_key, bwd_bits, stochastic, scale_dtype,
                                backend, plan, impl, fenced, _masks(faults),
                                reverse=True)
    fresh_grad = _blend(fresh_grad, plan.send_mask, ok, _fallback(faults))
    grad_h = scatter_boundary_grad(grad_in, plan)
    return (grad_h, None, None, fresh_grad, None, None, None)


stale_halo.defvjp(_sh_fwd, _sh_bwd)


# ---------------------------------------------------------------------------
# Per-step orchestrator handed to the model
# ---------------------------------------------------------------------------
class SylvieComm:
    """Created inside each traced step; models call ``comm.halo(h)`` once per
    layer-exchange site. All communication goes through ``backend`` (a
    :class:`repro.dist.backend.HaloBackend`; the simulated stack by default).

    ``decision`` is an :class:`~repro.policy.base.EpochDecision` whose
    ``sites[i]`` drives the i-th ``halo`` call; ``None`` falls back to the one
    global ``SylvieConfig`` choice for every site (the Uniform shim).
    Collects fresh caches (async mode) and — when ``collect_stats`` — per-site
    boundary range statistics as it goes.

    ``inputs`` is the step's own feature input (data, never differentiated).
    An async site handed exactly that array takes no gradient: it consumes
    its stale cache as plain data, so autodiff has no table transpose,
    backward Low-bit round trip or boundary scatter to run for it, and its
    ``gslot`` gradient (the next step's ``grad_in``) is zero."""

    def __init__(self, cfg: SylvieConfig, plan: PlanArrays, key,
                 backend=None, decision=None, collect_stats=False,
                 feat_caches=None, grad_ins=None, gslots=None,
                 fault_sites=None, inputs=None):
        self.cfg = cfg
        self.plan = plan
        self.key = key
        self.backend = as_backend(backend)
        self.decision = decision
        self.collect_stats = collect_stats
        self.feat_caches = feat_caches
        self.grad_ins = grad_ins
        self.gslots = gslots
        # per-site fault masks (repro.faults.plan.SiteFaults tuple) riding as
        # data; None = fault-free, traces the exact legacy program.
        self.fault_sites = fault_sites
        self.inputs = inputs
        self.new_feat_caches: list = []
        self.site_stats: list = []
        self._site = 0

    def _part_key(self):
        """Decorrelate stochastic-rounding noise across partitions: fold the
        partition index into the key under shard_map (the simulated mode's
        single batched uniform draw is already decorrelated)."""
        idx = self.backend.axis_index()
        if idx is None:
            return self.key
        return jax.random.fold_in(self.key, idx)

    def _bns_mask(self, key, p):
        """BNS-GCN-style boundary sampling: one Bernoulli keep-mask per halo
        row per epoch, shared by forward and backward (paper baseline)."""
        if p <= 0.0:
            return None
        rows = self.plan.recv_mask.shape
        return (jax.random.bernoulli(key, 1.0 - p, rows) / (1.0 - p))

    def _record_stats(self, h):
        """Per-site telemetry for adaptive policies: sum over live send rows
        of the squared per-row range, plus the live-row count (this
        partition's slice; the step psums across partitions)."""
        if not self.collect_stats:
            return
        buf = gather_boundary(jax.lax.stop_gradient(h), self.plan)
        rng = jnp.max(buf, axis=-1) - jnp.min(buf, axis=-1)
        live = self.plan.send_mask.astype(jnp.float32)
        self.site_stats.append(
            jnp.stack([(rng.astype(jnp.float32) ** 2 * live).sum(),
                       live.sum()]))

    def _site_decision(self, i) -> SiteDecision:
        if self.decision is not None:
            return self.decision.sites[i]
        return SiteDecision.from_config(self.cfg)

    @property
    def schedule(self) -> str:
        """Exchange schedule: the decision's choice when one is threaded in,
        else the config's (both default to ``"blocking"``)."""
        sched = (self.decision.schedule if self.decision is not None
                 else self.cfg.schedule)
        if sched not in SCHEDULES:
            raise ValueError(f"unknown schedule {sched!r}; known: {SCHEDULES}")
        return sched

    def halo(self, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        i = self._site
        self._site += 1
        sd = self._site_decision(i)
        key = self._part_key()
        kf = jax.random.fold_in(key, 2 * i)
        kb = jax.random.fold_in(key, 2 * i + 1)
        self._record_stats(h)
        sf = self.fault_sites[i] if self.fault_sites is not None else None
        # A fault-armed site never fences: its recovery blend needs the
        # landed exchange immediately (DESIGN §14).
        fenced = self.schedule == "overlap" and sf is None
        wire = (sd.stochastic, cfg.scale_dtype, self.backend, cfg.quant_impl,
                fenced)
        if cfg.mode in ("vanilla", "sync"):
            faults = None if sf is None else HaloFaults(self.feat_caches[i], sf)
            halo = quantized_halo(h, faults, self.plan, kf, kb, sd.fwd_bits,
                                  sd.bwd_bits, *wire)
            bns = self._bns_mask(jax.random.fold_in(key, 999),
                                 sd.boundary_sample_p)
            if bns is not None:
                halo = halo * bns[..., None]
            # a synchronous step doubles as a cache refresh for Sylvie-A
            # (Bounded Staleness Adaptor); caller stop-gradients these.
            self.new_feat_caches.append(halo)
            return halo
        # async: consume stale, emit fresh
        if h is self.inputs:
            # The node features take no gradient, so this site's boundary
            # gradients, outgoing and incoming, would feed nothing: consume
            # the cache as plain data and leave autodiff no backward to run.
            # (Stopping only the gslot gradient would still keep grad_in
            # as an operand, and so as a live argument of the step.)
            obs.count("halo.bwd_pruned")
            halo = self.feat_caches[i]
        else:
            faults = None if sf is None else HaloFaults(self.grad_ins[i], sf)
            halo = stale_halo(h, self.feat_caches[i], self.grad_ins[i],
                              self.gslots[i], faults, self.plan, kb,
                              sd.bwd_bits, *wire)
        faults = None if sf is None else HaloFaults(self.feat_caches[i], sf)
        fresh = fresh_halo(h, faults, self.plan, kf, sd.fwd_bits, *wire)
        self.new_feat_caches.append(fresh)
        return halo

    @property
    def n_sites(self) -> int:
        return self._site
