"""Span/event tracer: zero-overhead when disabled, injectable clock.

One process-global :class:`Tracer` (armed via :func:`enable`, torn down via
:func:`disable`) collects **spans** (named intervals with a start and a
duration) and **instant events** into thread-local buffers. The taxonomy the
instrumented layers emit:

* training — ``epoch > decide``, ``epoch > build`` (a step-cache miss: the
  decision's step is traced and compiled in that epoch's ``dispatch``),
  ``epoch > step > dispatch`` (the jitted call until it returns) and
  ``epoch > step > readback.loss`` (the blocking ``float(loss)``), then
  ``epoch > readback.stats`` (the site-statistics ``device_get``); plus
  ``retrace`` events from the :class:`~repro.obs.metrics.TraceLog` shims;
* serving — ``request > lookup`` on the request path, ``admit`` on submit,
  ``refresh > plan > sweep`` on the update path.

**Profiler sink.** A span can also open a profiler annotation of the same
name, so that it lands in the profiler's trace on the clock of the device
operations. The JAX-importing layers install that sink once
(:func:`repro.obs.profiler.install`); it opens a
``jax.profiler.TraceAnnotation`` only while a profiler records. Spans go to
the sink whether or not a tracer is armed, and to the tracer's buffer (and
the Perfetto JSON) only when one is.

Design rules (DESIGN.md §15):

* **disabled = free.** :func:`span` with no tracer armed and no profiler
  recording returns one shared :class:`_NullSpan` singleton — no allocation,
  no clock read. ``args`` is a positional optional (never ``**kwargs``) so
  the disabled call builds no dict.
* **host-side only.** Instrumentation lives in host orchestration code or at
  trace time (the same seams as the ``TRACE_LOG`` appends); it must never
  lower into a traced program — contract RC210 holds training and serving
  jaxprs identical with tracing on and off.
* **injectable clock.** Every timestamp comes from the tracer's monotonic
  ``clock`` (default ``time.perf_counter``); :class:`FakeClock` substitutes a
  deterministic one for tests, with a ``sleep`` that advances fake time so
  load generators idle without real waits.

Thread safety: each thread appends to its own buffer (created under a lock,
appended to lock-free — list.append is atomic under the GIL); :func:`drain`
merges and time-sorts all buffers.

This module is pure stdlib — it imports neither jax nor any repro layer, so
every layer may import it without cycles.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, ContextManager, Optional

# A profiler sink maps a span name to a context manager that marks the span
# in the profiler's trace, or to None where nothing records.
Sink = Callable[[str], Optional[ContextManager]]


class _NullSpan:
    """The disabled-tracer span: a shared, stateless context manager."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """One live span: clocks itself on enter/exit, records on exit; opens and
    closes the profiler's annotation around that, where there is one."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_mark")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict],
                 mark: Optional[ContextManager] = None):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._mark = mark

    def __enter__(self) -> "_Span":
        if self._mark is not None:
            self._mark.__enter__()
        self._t0 = self._tracer.clock()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = self._tracer.clock()
        self._tracer._record(self.name, self._t0, t1 - self._t0, self.args)
        if self._mark is not None:
            self._mark.__exit__(*exc)
        return False


class FakeClock:
    """Deterministic injectable clock for tests.

    Calling it returns the current fake time; ``sleep`` advances it (so code
    that idles via ``clock.sleep`` makes progress without wall waits);
    ``advance`` moves it explicitly. ``tick`` (optional) auto-advances every
    read, guaranteeing strictly increasing stamps for code that polls."""

    def __init__(self, start: float = 0.0, tick: float = 0.0):
        self.t = float(start)
        self.tick = float(tick)

    def __call__(self) -> float:
        now = self.t
        self.t += self.tick
        return now

    def sleep(self, seconds: float) -> None:
        self.t += max(float(seconds), 0.0)

    def advance(self, seconds: float) -> None:
        self.t += float(seconds)


class Tracer:
    """Span/event collector with per-thread buffers and an injectable clock.

    Events are dicts in the Chrome ``trace_event`` shape (``ph``: ``"X"`` =
    complete span, ``"i"`` = instant), timestamps in *seconds* on the
    tracer's clock — ``repro.obs.export`` converts to the format's µs."""

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock: Callable[[], float] = \
            clock if clock is not None else time.perf_counter
        self._lock = threading.Lock()
        self._buffers: dict[int, list[dict]] = {}

    def _buf(self) -> list[dict]:
        tid = threading.get_ident()
        buf = self._buffers.get(tid)
        if buf is None:
            with self._lock:
                buf = self._buffers.setdefault(tid, [])
        return buf

    def _record(self, name: str, ts: float, dur: float,
                args: Optional[dict]) -> None:
        ev: dict[str, Any] = {"name": name, "ph": "X", "ts": ts, "dur": dur,
                              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._buf().append(ev)

    def span(self, name: str, args: Optional[dict] = None,
             mark: Optional[ContextManager] = None) -> _Span:
        return _Span(self, name, args, mark)

    def event(self, name: str, args: Optional[dict] = None) -> None:
        ev: dict[str, Any] = {"name": name, "ph": "i", "ts": self.clock(),
                              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._buf().append(ev)

    def drain(self) -> list[dict]:
        """All recorded events, merged across threads and time-sorted;
        buffers are cleared."""
        with self._lock:
            bufs = list(self._buffers.values())
            self._buffers = {}
        out = [ev for buf in bufs for ev in buf]
        out.sort(key=lambda e: e["ts"])
        return out


# ---------------------------------------------------------------------------
# the process-global tracer (module functions are the instrumentation API)
# ---------------------------------------------------------------------------
_TRACER: Optional[Tracer] = None
_SINK: Optional[Sink] = None


def enable(clock: Optional[Callable[[], float]] = None) -> Tracer:
    """Arm tracing (replacing any active tracer). Returns the new tracer."""
    global _TRACER
    _TRACER = Tracer(clock=clock)
    return _TRACER


def disable() -> None:
    global _TRACER
    _TRACER = None


def enabled() -> bool:
    return _TRACER is not None


def current() -> Optional[Tracer]:
    return _TRACER


def set_sink(sink: Optional[Sink]) -> Optional[Sink]:
    """Install the profiler sink every span also goes to (``None`` removes
    it). Returns the sink it replaces."""
    global _SINK
    prev, _SINK = _SINK, sink
    return prev


def span(name: str, args: Optional[dict] = None):
    """A span context manager: the tracer's span (with the sink's mark) when
    a tracer is armed, else the sink's mark, else :data:`NULL_SPAN` (the
    allocation-free hot path)."""
    t, sink = _TRACER, _SINK
    mark = sink(name) if sink is not None else None
    if t is not None:
        return t.span(name, args, mark)
    return mark if mark is not None else NULL_SPAN


def event(name: str, args: Optional[dict] = None) -> None:
    """Record an instant event; a no-op when tracing is off."""
    t = _TRACER
    if t is not None:
        t.event(name, args)


def clock() -> float:
    """The observability clock: the active tracer's (injectable,
    deterministic under :class:`FakeClock`) or ``time.perf_counter``.
    Instrumented modules read time through this — lint rule RA108 keeps raw
    ``time.time``/``time.perf_counter`` calls out of them."""
    t = _TRACER
    return t.clock() if t is not None else time.perf_counter()


def drain() -> list[dict]:
    """Drain the active tracer's events ([] when tracing is off)."""
    t = _TRACER
    return t.drain() if t is not None else []
