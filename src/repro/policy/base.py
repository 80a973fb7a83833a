"""CommPolicy vocabulary: decisions, telemetry, and the policy protocol.

Sylvie's original design fixes one static compression decision for the whole
run (``SylvieConfig.bits`` plus a lone ``eps_s`` staleness knob). The paper's
own Bounded Staleness Adaptor (§3.3) and the staged follow-ups — AdaQP's
variance-budgeted per-message bit-widths (Wan et al., arXiv:2306.01381) and
variable communication rates over training (Cerviño et al., arXiv:2406.17611)
— all show the *right* decision varies by exchange site and by epoch. This
module makes that decision a first-class object:

* :class:`SiteDecision` — what one halo-exchange site does this epoch
  (forward/backward bit-widths, stochastic vs deterministic rounding,
  BNS-style boundary sampling).
* :class:`EpochDecision` — one :class:`SiteDecision` per exchange site plus
  the epoch-level choices (synchronous vs pipelined step, EF21 weight-gradient
  compression bits). **Hashable and fully static**: the trainer threads it
  into the step as trace-level config, so jit caches one executable per
  distinct decision.
* :class:`Telemetry` / :class:`SiteStats` — what a policy may observe, all
  host-side floats gathered *outside the trace* (epoch index, per-site
  quantization range/variance statistics emitted by the previous step, the
  validation trajectory, partition count).
* :class:`CommPolicy` — the protocol: once per epoch, ``decide(telemetry) ->
  EpochDecision``. Policies are pure host-side objects; nothing they return
  ever becomes a traced value.

Trace-staticness rule: every field of an :class:`EpochDecision` selects *code*
(bit-widths pick pack/unpack shapes, ``sync`` picks the step function), never
data. To keep the number of compiled executables small the trainer snaps
decisions to the lattice below (:meth:`EpochDecision.snapped`) before using
them as cache keys.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, runtime_checkable

# The decision lattice: bit-widths a snapped decision may use (the widths the
# Low-bit Module packs / passes through) and the grid boundary-sampling rates
# are rounded to. Policies may compute anything; the trainer quantizes to this
# lattice so a drifting policy cannot trigger unbounded recompilation.
BIT_LATTICE = (1, 2, 4, 8, 16, 32)
SAMPLE_P_STEP = 0.05


def snap_bits(bits: int | float) -> int:
    """Round a requested bit-width *up* to the nearest lattice width::

        snap_bits(3)    # -> 4
        snap_bits(100)  # -> 32 (clamped to the widest lattice point)
    """
    for b in BIT_LATTICE:
        if bits <= b:
            return b
    return BIT_LATTICE[-1]


def snap_sample_p(p: float) -> float:
    """Round a boundary-sampling rate to the lattice grid, clamped to
    [0, 0.95] (p=1 would drop every halo row)::

        snap_sample_p(0.33)  # -> 0.35
        snap_sample_p(1.0)   # -> 0.95
    """
    q = round(float(p) / SAMPLE_P_STEP) * SAMPLE_P_STEP
    return min(max(q, 0.0), 0.95)


@dataclasses.dataclass(frozen=True)
class SiteDecision:
    """Per-exchange-site communication decision for one epoch.

    ``fwd_bits`` quantizes the forward halo features, ``bwd_bits`` the
    backward boundary-gradient communication (Alg. 2 lines 10-12) — the two
    directions are independent code paths through the custom_vjps in
    ``core/sylvie.py``. ``boundary_sample_p`` is the BNS-GCN keep-out rate
    (0 disables).

    Example — 1-bit features forward, 8-bit gradients backward::

        SiteDecision(fwd_bits=1, bwd_bits=8, stochastic=True)
    """

    fwd_bits: int = 1
    bwd_bits: int = 1
    stochastic: bool = True
    boundary_sample_p: float = 0.0

    @staticmethod
    def from_config(cfg) -> "SiteDecision":
        """The Uniform degenerate case: one global ``SylvieConfig`` decision.
        This is the only sanctioned place runtime code reads ``cfg.bits``."""
        b = int(cfg.effective_bits)
        return SiteDecision(fwd_bits=b, bwd_bits=b, stochastic=cfg.stochastic,
                            boundary_sample_p=cfg.boundary_sample_p)

    def snapped(self) -> "SiteDecision":
        return SiteDecision(fwd_bits=snap_bits(self.fwd_bits),
                            bwd_bits=snap_bits(self.bwd_bits),
                            stochastic=bool(self.stochastic),
                            boundary_sample_p=snap_sample_p(
                                self.boundary_sample_p))


@dataclasses.dataclass(frozen=True)
class EpochDecision:
    """One epoch's full communication schedule. Hashable; used as a jit/step
    cache key, so every field must stay static python data.

    * ``sites[i]`` drives the i-th ``comm.halo(h)`` call (``model.comm_dims()``
      order).
    * ``sync`` — run the synchronous step (Sylvie-S semantics, refreshes all
      staleness caches) instead of the pipelined Sylvie-A step. Only honored
      when the trainer's mode is ``"async"``; sync-mode trainers always run
      the synchronous step.
    * ``ef_bits`` — EF21-compressed weight-gradient all-reduce bit-width
      (``None`` = full-precision psum, the paper's setting).
    * ``schedule`` — ``"blocking"`` (each halo consumed as it is produced) or
      ``"overlap"`` (each exchange landed through ``backend.fence``, the
      ``fenced`` flag of the halo primitives in ``core/sylvie.py``). Part of
      :meth:`step_key`: the two schedules trace different programs.

    Example — Sylvie-S at 1 bit on a 2-site model::

        EpochDecision.uniform(n_sites=2, bits=1, sync=True)
    """

    sites: tuple[SiteDecision, ...]
    sync: bool = False
    ef_bits: Optional[int] = None
    schedule: str = "blocking"

    @staticmethod
    def uniform(n_sites: int, bits: int = 1, *, sync: bool = False,
                stochastic: bool = True, boundary_sample_p: float = 0.0,
                ef_bits: Optional[int] = None,
                schedule: str = "blocking") -> "EpochDecision":
        site = SiteDecision(fwd_bits=bits, bwd_bits=bits, stochastic=stochastic,
                            boundary_sample_p=boundary_sample_p)
        return EpochDecision(sites=(site,) * n_sites, sync=sync,
                             ef_bits=ef_bits, schedule=schedule)

    @staticmethod
    def from_config(cfg, n_sites: int, *, sync: bool = False) -> "EpochDecision":
        """The ``SylvieConfig(bits=...)`` shim: every site gets the config's
        one global decision (see :meth:`SiteDecision.from_config`)."""
        return EpochDecision(sites=(SiteDecision.from_config(cfg),) * n_sites,
                             sync=sync, schedule=cfg.schedule)

    def snapped(self) -> "EpochDecision":
        return EpochDecision(
            sites=tuple(s.snapped() for s in self.sites), sync=bool(self.sync),
            ef_bits=None if self.ef_bits is None else snap_bits(self.ef_bits),
            schedule=str(self.schedule))

    def with_bits(self, bits: int) -> "EpochDecision":
        """Every site forced to ``bits`` both directions (the trainer uses
        this to pin vanilla mode at 32)."""
        return EpochDecision(
            sites=tuple(dataclasses.replace(s, fwd_bits=bits, bwd_bits=bits)
                        for s in self.sites),
            sync=self.sync, ef_bits=self.ef_bits, schedule=self.schedule)

    def step_key(self):
        """Cache key for compiled step functions. ``sync`` is excluded — it
        selects *which* step runs, not how either is traced — so an adaptor
        toggling sync/async costs no extra compilation. ``schedule`` is
        included: blocking and overlap trace different programs."""
        return (self.sites, self.ef_bits, self.schedule)

    def bits_per_site(self) -> tuple[tuple[int, int], ...]:
        """((fwd_bits, bwd_bits), ...) — the EpochMetrics record."""
        return tuple((s.fwd_bits, s.bwd_bits) for s in self.sites)


@dataclasses.dataclass(frozen=True)
class SiteStats:
    """Observed per-site quantization statistics from the previous epoch.

    ``mean_range_sq`` is the mean over live boundary rows of the squared
    per-row range ``(max - min)^2`` — the quantity Theorem 1's variance bound
    is built from. ``rows`` is the live boundary-row count totaled across
    partitions; ``dim`` the feature width at this site.
    """

    dim: int
    rows: int
    mean_range_sq: float

    def variance(self, bits: int) -> float:
        """Theorem-1 quantization variance summed over this site's rows:
        ``rows * dim * E[range^2] / (6 * (2^bits - 1)^2)``. Passthrough
        widths (16/32) contribute zero."""
        if bits >= 16:
            return 0.0
        big = 2.0 ** bits - 1.0
        return self.rows * self.dim * self.mean_range_sq / (6.0 * big * big)


@dataclasses.dataclass(frozen=True)
class Telemetry:
    """Everything a policy may observe. Host-side, gathered once per epoch,
    outside any trace.

    ``site_stats`` is ``None`` until the first training epoch has run (the
    step emits the stats; see ``train/gnn_step.py``). ``prev`` is the previous
    epoch's (snapped) decision — policies can use it for hysteresis.
    ``needs_sync`` flags a trainer-level cache-coherence requirement (resume
    after an elastic repartition): policies must return ``sync=True`` when it
    is set, and the trainer enforces it regardless.
    """

    epoch: int
    n_parts: int
    n_sites: int
    site_dims: tuple[int, ...]
    site_stats: Optional[tuple[SiteStats, ...]] = None
    val_history: tuple[float, ...] = ()
    needs_sync: bool = False
    prev: Optional[EpochDecision] = None
    # Consecutive-faulty-epoch count per site under a chaos run (see
    # ``repro.faults``): a dropped/corrupted exchange degrades to the cached
    # halo, making that site's effective staleness grow — ``BoundedStaleness``
    # treats a counter at/over its ``eps_s`` exactly like a due refresh.
    # Empty when no fault plan is armed.
    site_staleness: tuple[int, ...] = ()


@runtime_checkable
class CommPolicy(Protocol):
    """Per-epoch communication schedules as a pluggable strategy.

    ``decide`` runs on the host once per epoch, before the step is chosen and
    compiled; it must be a pure function of the telemetry (the trainer may
    call it speculatively, e.g. for byte accounting). The returned decision is
    snapped to the lattice and used as the step-compilation cache key, so a
    well-behaved policy emits few distinct decisions over a run.

    A policy is any object with ``decide`` + ``name`` — e.g. one that widens
    bits whenever validation accuracy stalls::

        @dataclasses.dataclass(frozen=True)
        class WidenOnPlateau:
            name: str = "widen_on_plateau"
            def decide(self, tel):
                stalled = (len(tel.val_history) >= 2
                           and tel.val_history[-1] <= tel.val_history[-2])
                return EpochDecision.uniform(tel.n_sites,
                                             bits=4 if stalled else 1,
                                             sync=tel.needs_sync)

        GNNTrainer(model, pg, cfg, policy=WidenOnPlateau())
    """

    def decide(self, tel: Telemetry) -> EpochDecision: ...

    @property
    def name(self) -> str: ...


def validate_decision(decision: EpochDecision, n_sites: int) -> EpochDecision:
    """Shape-check a policy's output against the model's exchange sites."""
    if len(decision.sites) != n_sites:
        raise ValueError(
            f"EpochDecision has {len(decision.sites)} site decisions but the "
            f"model has {n_sites} halo-exchange sites (comm_dims order)")
    return decision
