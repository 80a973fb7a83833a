"""repro.faults — seeded fault injection and staleness-as-recovery.

Scheduling (`plan`) is host-side and deterministic in (seed, epoch);
injection is traced data (the checked exchange of ``core/wire.py``, driven by
the halo primitives of ``core/sylvie.py``) so chaos adds zero executables;
`backend.FaultyBackend` binds a plan to any HaloBackend.
"""
from .backend import FaultyBackend
from .plan import (BWD, FWD, FaultCtl, FaultEvents, FaultPlan, RowGeometry,
                   SiteFaults)

__all__ = [
    "BWD",
    "FWD",
    "FaultCtl",
    "FaultEvents",
    "FaultPlan",
    "FaultyBackend",
    "RowGeometry",
    "SiteFaults",
]
