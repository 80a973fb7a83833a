"""Device time attributed to the program's own boundaries.

Two kinds of boundary, both opened by the program itself:

* named scopes of the compiled step: ``jax.named_scope`` names reach the
  optimised HLO's ``op_name`` metadata (``jvp(aggregation)``,
  ``transpose(jvp(exchange))`` in the backward). An operation belongs to the
  innermost of :data:`SCOPES` on its ``op_name`` path, once the ``jvp(`` and
  ``transpose(`` wrappers are stripped. An operation the compiler left without
  ``op_name`` belongs to none;
* host spans of the epoch loop (``repro.obs`` spans, which also open profiler
  annotations): device idle gaps are split by the host span open over them.
"""
from __future__ import annotations

import re

from bench.trace import _union

SCOPES = ("aggregation", "lowbit", "exchange")
_WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def scope_of(op_name: str) -> str | None:
    """The innermost of :data:`SCOPES` on an ``op_name`` path, or None."""
    found = None
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in SCOPES:
            found = part
    return found


def scope_ms(rec, scope: str) -> float | None:
    """Device milliseconds per epoch, mean over chips, of the operations
    whose instruction lies under ``scope``; None where none does."""
    s = rec.per_epoch_device_s(
        lambda op, ins: ins is not None and scope_of(ins.op_name) == scope)
    return None if s is None else 1e3 * s


def _overlap(gaps, spans) -> float:
    """Total length of the intersection of two sorted, disjoint interval
    lists."""
    tot, j = 0.0, 0
    for s, e in gaps:
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < e:
            tot += min(e, spans[k][1]) - max(s, spans[k][0])
            k += 1
    return tot


def idle_under_ms(rec, selects) -> float | None:
    """Device idle milliseconds per epoch, mean over chips, that lie under
    the host spans whose name ``selects(name)`` accepts; None where the trace
    holds no such span."""
    if rec.trace is None or not rec.trace.devices or not rec.epochs:
        return None
    spans = _union([(s, s + d) for name, s, d in rec.trace.host_spans
                    if selects(name)])
    if not spans:
        return None
    idle = [_overlap(sorted((s, s + d) for s, d in dev.gaps), spans)
            for dev in rec.trace.devices.values()]
    return 1e3 * sum(idle) / len(idle) / len(rec.epochs)
