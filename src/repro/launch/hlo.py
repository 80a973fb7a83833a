"""HLO analysis: collective-byte accounting + three-term roofline.

``collective_bytes`` parses the SPMD-partitioned (per-device) HLO from
``compiled.as_text()`` and sums, per collective opcode, the *wire bytes per
device* under the standard ring algorithms:

    all-gather          operand × (g-1)          (each shard forwarded g-1 times)
    reduce-scatter      operand × (g-1)/g
    all-reduce          operand × 2(g-1)/g       (RS + AG phases)
    all-to-all          operand × (g-1)/g
    collective-permute  operand × 1

``g`` is the replica-group size parsed per op. The roofline terms then follow
the assignment formulas with per-chip constants from ``mesh.py``:

    compute    = HLO_FLOPs_per_device / 197 TFLOP/s
    memory     = HLO_bytes_per_device / 819 GB/s
    collective = wire_bytes_per_device / 50 GB/s
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

from . import mesh as meshlib

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 0.5, "u4": 0.5,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %all-gather.7 = bf16[16,512]{1,0} all-gather(%p), ..., replica_groups=...
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def _wire_factor(op: str, g: int) -> float:
    if g <= 1:
        return 0.0
    if op == "all-gather":
        return g - 1
    if op == "reduce-scatter":
        return (g - 1) / g
    if op == "all-reduce":
        return 2 * (g - 1) / g
    if op == "all-to-all":
        return (g - 1) / g
    return 1.0   # collective-permute


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float = 0.0                 # per-device, algo-weighted
    payload_bytes: float = 0.0              # per-device, raw operand sizes
    by_op: dict = dataclasses.field(default_factory=dict)
    count: int = 0

    def add(self, op, payload, wire):
        self.count += 1
        self.payload_bytes += payload
        self.wire_bytes += wire
        ent = self.by_op.setdefault(op, dict(count=0, payload=0.0, wire=0.0))
        ent["count"] += 1
        ent["payload"] += payload
        ent["wire"] += wire


def collective_bytes(hlo_text: str, n_devices: int) -> CollectiveStats:
    stats = CollectiveStats()
    seen_starts = set()
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _OP_RE.search(line)
        shapes_bytes = None
        if m:
            dtype, dims, op = m.group(1), m.group(2), m.group(3)
            shapes_bytes = _shape_bytes(dtype, dims)
        else:
            mt = _TUPLE_RE.search(line)
            if not mt:
                continue
            op = mt.group(2)
            shapes_bytes = sum(_shape_bytes(d, s)
                               for d, s in _SHAPE_RE.findall(mt.group(1)))
        # async pairs appear as -start/-done; count the start only
        if "-done(" in line:
            continue
        name = line.split("=", 1)[0].strip()
        if name in seen_starts:
            continue
        seen_starts.add(name)
        g = _group_size(line, n_devices)
        # for all-gather the HLO result is the gathered buffer: operand
        # (per-shard) size = result / g
        payload = shapes_bytes / g if op == "all-gather" else shapes_bytes
        stats.add(op, payload, payload * _wire_factor(op, g))
    return stats


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    hbm_bytes_per_device: float
    wire_bytes_per_device: float
    n_devices: int
    model_flops_total: Optional[float] = None

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / meshlib.PEAK_FLOPS_BF16

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes_per_device / meshlib.HBM_BW

    @property
    def collective_s(self) -> float:
        return self.wire_bytes_per_device / meshlib.ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Modeled step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flop_ratio(self) -> Optional[float]:
        if not self.model_flops_total:
            return None
        return self.model_flops_total / (self.flops_per_device * self.n_devices)

    @property
    def roofline_fraction(self) -> Optional[float]:
        """MODEL_FLOPS-per-chip-second over peak — the MFU-style score: what
        fraction of peak the *useful* math achieves at the modeled step time."""
        if not self.model_flops_total:
            return None
        per_chip = self.model_flops_total / self.n_devices
        return per_chip / self.step_s / meshlib.PEAK_FLOPS_BF16

    def as_dict(self) -> dict:
        return dict(
            flops_per_device=self.flops_per_device,
            hbm_bytes_per_device=self.hbm_bytes_per_device,
            wire_bytes_per_device=self.wire_bytes_per_device,
            n_devices=self.n_devices,
            compute_s=self.compute_s, memory_s=self.memory_s,
            collective_s=self.collective_s, bottleneck=self.bottleneck,
            step_s=self.step_s,
            model_flops_total=self.model_flops_total,
            useful_flop_ratio=self.useful_flop_ratio,
            roofline_fraction=self.roofline_fraction)


def analyze(compiled, n_devices: int,
            model_flops_total: Optional[float] = None):
    """(compiled executable, mesh size) -> (Roofline, CollectiveStats, mem)."""
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    stats = collective_bytes(compiled.as_text(), n_devices)
    try:
        ma = compiled.memory_analysis()
        mem = dict(
            argument_bytes=getattr(ma, "argument_size_in_bytes", None),
            output_bytes=getattr(ma, "output_size_in_bytes", None),
            temp_bytes=getattr(ma, "temp_size_in_bytes", None),
            peak_bytes=(getattr(ma, "argument_size_in_bytes", 0) or 0)
            + (getattr(ma, "temp_size_in_bytes", 0) or 0))
    except Exception as e:                                    # pragma: no cover
        mem = dict(error=str(e))
    roof = Roofline(flops, hbm, stats.wire_bytes, n_devices,
                    model_flops_total)
    return roof, stats, mem
