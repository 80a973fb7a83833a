"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per HLO operation run, named after the HLO instruction. The ``XLA Modules``
line says which compiled program each operation belongs to. Host planes hold
the ``TraceAnnotation`` spans the harness opens around its own calls.

What comes out, per device and over the traced window:

* the busy time: the union of the operation intervals;
* the time of each operation by instruction name, and by what the compiled
  program says the instruction is (``hlo_index`` over the optimised HLO text:
  opcode, custom-call target, ``op_name`` metadata, opcodes of a fusion's
  body);
* the idle gaps, each named after the innermost host span open at its middle.

Run ``python -m bench.trace <dir>`` to print the planes, lines and a sample
of events of a trace, to look at one by hand.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


# ---------------------------------------------------------------------------
# compiled program: instruction name -> what it is
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    target: str = ""            # custom_call_target
    op_name: str = ""           # metadata op_name (the jax source path)
    calls: tuple = ()           # called computations
    body_opcodes: frozenset = frozenset()   # opcodes inside called bodies
    root_opcode: str = ""       # root opcode of the (first) called body


_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_EVENT = re.compile(r"^%?([\w.\-]+) = ")


def instruction_name(event_name: str) -> str:
    """TPU op events are named by the instruction's whole HLO text
    (``%fusion.11 = f32[...] fusion(...)``); the name is its first word."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def _opcode_and_rest(rhs: str) -> tuple[str, str]:
    """Skip the result shape (a token, or a parenthesised tuple) and return
    the opcode and what follows it."""
    i = 0
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        while i < len(rhs) and not rhs[i].isspace():
            i += 1
    m = re.match(r"\s*([a-z][\w\-]*)\(", rhs[i:])
    if not m:
        return "", ""
    return m.group(1), rhs[i + m.end():]


def hlo_index(texts) -> dict[str, dict[str, Instr]]:
    """``{module name: {instruction name: Instr}}`` from optimised HLO text."""
    out: dict[str, dict[str, Instr]] = {}
    for text in texts:
        module = ""
        comps: dict[str, list] = defaultdict(list)
        roots: dict[str, str] = {}
        root_names: dict[str, str] = {}
        comp = None
        for line in text.splitlines():
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                continue
            m = _COMP.match(line)
            if m:
                comp = m.group(1)
                continue
            m = _INSTR.match(line)
            if not m or comp is None:
                continue
            opcode, rest = _opcode_and_rest(m.group(3))
            if not opcode:
                continue
            target = re.search(r'custom_call_target="([^"]*)"', rest)
            op_name = re.search(r'op_name="([^"]*)"', rest)
            calls = re.findall(r"(?:calls|to_apply|body|condition)="
                               r"\{?%?([\w.\-]+)", rest)
            calls += re.findall(r"called_computations=\{([^}]*)\}", rest)
            ins = Instr(m.group(2), opcode,
                        target.group(1) if target else "",
                        op_name.group(1) if op_name else "",
                        tuple(c.strip().lstrip("%") for c in calls))
            comps[comp].append(ins)
            if m.group(1):
                roots[comp] = opcode
                root_names[comp] = m.group(2)
        def body(comp_name, seen):
            """Opcodes of a computation and of every one it calls."""
            if comp_name in seen:
                return set()
            seen.add(comp_name)
            ops = set()
            for i in comps.get(comp_name, ()):
                ops.add(i.opcode)
                for c in i.calls:
                    ops |= body(c, seen)
            return ops

        def root(comp_name, depth=0):
            """Root opcode, looking through a root that is itself a fusion."""
            op = roots.get(comp_name, "")
            if op == "fusion" and depth < 8:
                for i in comps.get(comp_name, ()):
                    if i.opcode == "fusion" and i.calls and i.name == \
                            root_names.get(comp_name):
                        return root(i.calls[0], depth + 1)
            return op

        table: dict[str, Instr] = {}
        for instrs in comps.values():
            for ins in instrs:
                if ins.calls:
                    ins.body_opcodes = frozenset(
                        set().union(*(body(c, set()) for c in ins.calls)))
                    ins.root_opcode = root(ins.calls[0])
                table[ins.name] = ins
        out[module] = table
    return out


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Op:
    name: str
    module: str
    start: float                # seconds
    dur: float


@dataclasses.dataclass
class Device:
    ops: list                   # [Op] inside the window
    busy_s: float
    gaps: list                  # [(start, dur)] idle gaps inside the window


@dataclasses.dataclass
class Trace:
    window: tuple               # (start, end) seconds
    devices: dict               # device index -> Device
    host_spans: list            # [(name, start, dur)]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def gap_cause(self, start: float, dur: float) -> str:
        """Innermost host span open at the middle of a gap."""
        mid = start + dur / 2
        best = None
        for name, s, d in self.host_spans:
            if s <= mid <= s + d and (best is None or d < best[1]):
                best = (name, d)
        return best[0] if best else "(no host span)"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(source, window_span: str, devices=None) -> Trace:
    """Read the trace under the directory ``source`` (or a ``ProfileData``).
    The window is the first host span named ``window_span``; ``devices``
    limits the device planes read."""
    from jax.profiler import ProfileData
    pd = (ProfileData.from_file(find_xplane(source))
          if isinstance(source, (str, os.PathLike)) else source)
    host_spans = []
    dev_planes = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            idx = int(m.group(1))
            if devices is None or idx in devices:
                dev_planes[idx] = plane
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host_spans.append((ev.name, ev.start_ns * 1e-9,
                                       ev.duration_ns * 1e-9))
    wins = [(s, s + d) for n, s, d in host_spans if n == window_span]
    if not wins:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, w1 = wins[0]
    out = {}
    for idx, plane in dev_planes.items():
        lines = {line.name: line for line in plane.lines}
        modules = []
        if MODULES_LINE in lines:
            modules = sorted((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns)
                              * 1e-9, ev.name)
                             for ev in lines[MODULES_LINE].events)
        ops = []
        if OPS_LINE in lines:
            for ev in lines[OPS_LINE].events:
                s = ev.start_ns * 1e-9
                d = ev.duration_ns * 1e-9
                if s + d < w0 or s > w1:
                    continue
                s0, e0 = max(s, w0), min(s + d, w1)
                ops.append(Op(instruction_name(ev.name), _module_at(modules, s),
                              s0, e0 - s0))
        busy = _union([(o.start, o.start + o.dur) for o in ops])
        busy_s = sum(e - s for s, e in busy)
        gaps, t = [], w0
        for s, e in busy:
            if s > t:
                gaps.append((t, s - t))
            t = max(t, e)
        if w1 > t:
            gaps.append((t, w1 - t))
        out[idx] = Device(ops, busy_s, gaps)
    return Trace((w0, w1), out, host_spans)


def _module_at(modules, t) -> str:
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][0] <= t <= modules[lo - 1][1]:
        return re.sub(r"\(\d+\)$", "", modules[lo - 1][2])
    return ""


def lookup(index: dict, op: Op):
    """The instruction an operation ran, or None. Module names in the trace
    may carry a suffix the HLO text lacks, so the match is by prefix."""
    for mod, table in index.items():
        if op.module and not (op.module.startswith(mod)
                              or mod.startswith(op.module)):
            continue
        ins = table.get(op.name)
        if ins is not None:
            return ins
    return None

