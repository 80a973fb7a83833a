"""Device time per epoch, mean over the cell's chips, in which the sharded
step waits on its collectives: a collective of the ``collective`` scope is in
flight and the chip runs no operation outside that scope. Layer: the halo
exchange over the inter-chip interconnect, ``dist/backend.py::
ShardMapBackend`` (the halo ``ppermute`` rings or ``all_to_all``, and the
gradient all-reduce), whose operations the compiled step keeps under
``jax.named_scope("collective")``.

An asynchronous collective is a ``*-start`` operation that issues the
transfer and a ``*-done`` operation that waits for it; the transfer runs
between them. So a collective is in flight from the start of its ``-start``
to the end of its ``-done``, and a synchronous one (an ``all-reduce``) for
its own duration. The compiler issues a ``-start`` early and places its
``-done`` late, so that span is the window the schedule leaves for the
transfer, not the transfer's own time; what other work covers of it is
hidden, and the rest is what the step waits on. It falls as the collectives
are hidden better or move fewer bytes.

The union of the spans is the same whichever start a done closes, provided
each done follows its start: an instant lies in it exactly when more starts
than dones of the scope precede it. So the starts and dones are counted in
time order on each chip, and no operand of the HLO is needed. None where the
trace holds no operation of the ``collective`` scope, as on one chip."""
from bench.scopes import _WRAPPED, _overlap
from bench.trace import _union

SCOPE = "collective"


def in_scope(ins) -> bool:
    """Whether the ``collective`` scope is on the instruction's ``op_name``
    path, once the ``jvp(`` and ``transpose(`` wrappers are stripped."""
    if ins is None:
        return False
    for part in ins.op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part == SCOPE:
            return True
    return False


def in_flight(rec) -> dict | None:
    """``{device: [(start, end)]}``: the union of the spans in which the
    ``collective`` scope's operations are in flight; None where there are
    none."""
    if rec.trace is None:
        return None
    found = False
    out = {dev: [] for dev in rec.trace.devices}
    marks = {dev: [] for dev in rec.trace.devices}
    for dev, op, ins in rec.ops():
        if not in_scope(ins):
            continue
        found = True
        if ins.opcode.endswith("-start"):
            marks[dev].append((op.start, 1))
        elif ins.opcode.endswith("-done"):
            marks[dev].append((op.start + op.dur, -1))
        else:
            out[dev].append((op.start, op.start + op.dur))
    if not found:
        return None
    w1 = rec.trace.window[1]
    for dev, ms in marks.items():
        depth, since = 0, None
        for t, step in sorted(ms, key=lambda m: (m[0], -m[1])):
            if step > 0:
                depth += 1
                if depth == 1:
                    since = t
            elif depth > 0:
                depth -= 1
                if depth == 0:
                    out[dev].append((since, t))
            # else: a done whose start fell before the window closes nothing
        if depth > 0:
            out[dev].append((since, w1))
        out[dev] = [tuple(iv) for iv in _union(out[dev])]
    return out


def read(rec):
    ivs = in_flight(rec)
    if ivs is None:
        return None
    other = {dev: [] for dev in ivs}
    for dev, op, ins in rec.ops():
        if not in_scope(ins):
            other[dev].append((op.start, op.start + op.dur))
    exposed = []
    for dev, iv in ivs.items():
        busy = [tuple(b) for b in _union(other[dev])]
        exposed.append(sum(e - s for s, e in iv) - _overlap(iv, busy))
    return 1e3 * sum(exposed) / max(len(exposed), 1) / max(len(rec.epochs), 1)
