"""Profiler sink for :mod:`repro.obs` spans: each span also opens a
``jax.profiler.TraceAnnotation`` of its name while a profiler records, so the
host spans land in the ``.xplane.pb`` on the clock of the device operations.

The JAX-importing layers (``train/trainer.py``, ``serve/engine.py``) call
:func:`install` on import; ``repro.obs.spans`` itself stays free of JAX.
With no profiler recording the sink returns None and :func:`repro.obs.span`
keeps its allocation-free null path.
"""
from __future__ import annotations

from . import spans


def install() -> None:
    """Route every span to the JAX profiler (idempotent)."""
    from jax.profiler import TraceAnnotation
    recording = TraceAnnotation.is_enabled

    def mark(name: str):
        return TraceAnnotation(name) if recording() else None

    spans.set_sink(mark)
