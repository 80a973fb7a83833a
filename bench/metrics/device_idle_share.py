"""Share of the traced window in which no operation ran on the device, mean
over the cell's chips: 1 - (union of operation intervals) / window."""


def read(rec):
    if rec.trace is None or not rec.trace.devices:
        return None
    w = rec.trace.window_s
    busy = [d.busy_s for d in rec.trace.devices.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / w)
