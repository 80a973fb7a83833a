"""repro.obs — unified tracing, metrics, and timeline export (DESIGN §15).

Three small pieces, all stdlib-only (no jax, no other repro imports — every
layer may depend on this one):

* :mod:`.spans` — the zero-overhead-when-disabled span/event tracer with an
  injectable monotonic clock (arm with :func:`enable`, read time through
  :func:`clock`). Training spans: ``epoch > decide``, ``epoch > build``,
  ``epoch > step > dispatch``, ``epoch > step > readback.loss``,
  ``epoch > readback.stats``; serving spans: ``request > lookup``,
  ``admit``, ``refresh > plan > sweep``;
* :mod:`.metrics` — the always-on typed counter/gauge/histogram registry,
  plus the :class:`TraceLog` list shims that superseded the two historical
  ``TRACE_LOG``s;
* :mod:`.export` — Chrome/Perfetto ``trace_event`` JSON + flat metrics JSON
  writers, rendered by ``python -m repro.obs summarize|timeline|diff``.

Every span also goes to a profiler sink where one is installed
(:func:`set_sink`): :mod:`.profiler`, which the JAX-importing layers install
on import, opens a ``jax.profiler.TraceAnnotation`` of the span's name while
a profiler records, so the spans share the device trace's clock.
"""
from .spans import (  # noqa: F401
    NULL_SPAN,
    FakeClock,
    Tracer,
    clock,
    current,
    disable,
    drain,
    enable,
    enabled,
    event,
    set_sink,
    span,
)
from .metrics import (  # noqa: F401
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    TraceLog,
    count,
    counter,
    gauge,
    histogram,
    observe,
    reset_metrics,
    snapshot,
)
from .export import (  # noqa: F401
    default_obs_dir,
    write_metrics,
    write_trace,
)

__all__ = [
    "NULL_SPAN", "FakeClock", "Tracer",
    "clock", "current", "disable", "drain", "enable", "enabled", "event",
    "set_sink", "span",
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "TraceLog", "count", "counter", "gauge", "histogram", "observe",
    "reset_metrics", "snapshot",
    "default_obs_dir", "write_metrics", "write_trace",
]
