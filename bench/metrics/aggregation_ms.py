"""Device time per epoch of the aggregation: HLO gathers and scatters, and
fusions with one in their body (the TPU compiler wraps the edge-list gather
in reshape-rooted fusions and the ``segment_sum`` scatter in nested ones),
matched by opcode in the compiled step. Mean over the cell's chips. Layer:
``models/gnn/blocks.py`` (gather over the edge list, ``segment_sum``). It
also holds the exchange's small boundary gather and gradient scatter, which
no opcode tells apart."""

OPCODES = ("gather", "scatter")


def is_aggregation(ins) -> bool:
    if ins is None:
        return False
    if ins.opcode in OPCODES:
        return True
    return ins.opcode == "fusion" and any(o in ins.body_opcodes
                                          for o in OPCODES)


def read(rec):
    ms = rec.per_epoch_device_s(lambda op, ins: is_aggregation(ins))
    return None if ms is None else 1e3 * ms
