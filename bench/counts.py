"""Work a cell needs per epoch, computed from the shapes of its graph and plan.

``flops_per_epoch``: the forward and backward passes of full-graph training,
three times the forward FLOPs of the architecture (``forward_flops`` in
``bench/models/<arch>.py``, one aggregation over every edge and the dense
update over every node, per layer).

``lowbit_bytes_per_epoch``: the bytes the Low-bit Module must move for the
exchanges an epoch needs, over the real halo rows only (no padding):
quantize reads float32 rows and writes the packed bits plus a scale and a
zero per row in ``scale_dtype``; dequantize reads those and writes float32
rows. Every site exchanges its features forward; every site but the first
exchanges its boundary gradients backward (the first site's input is the node
features, which take no gradient). The uniform noise the program draws for
stochastic rounding is an implementation choice and is not counted.
"""
from __future__ import annotations

import importlib
import math

SCALE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def flops_per_epoch(arch: str, n_nodes: int, n_edges: int, dims) -> float:
    mod = importlib.import_module(f"bench.models.{arch}")
    return 3.0 * mod.forward_flops(n_nodes, n_edges, tuple(dims))


def exchange_bytes(rows: int, d: int, bits: int, scale_dtype: str) -> float:
    """Necessary bytes of one quantize plus one dequantize over ``rows``
    rows of width ``d``."""
    packed = rows * math.ceil(d * bits / 8)
    ec = 2 * rows * SCALE_BYTES[scale_dtype]
    dense = rows * d * 4
    return 2.0 * (dense + packed + ec)


def lowbit_bytes_per_epoch(halo_rows: int, site_dims, bits: int,
                           scale_dtype: str) -> float:
    if bits >= 16:
        return 0.0
    total = 0.0
    for i, d in enumerate(site_dims):
        total += exchange_bytes(halo_rows, d, bits, scale_dtype)
        if i > 0:
            total += exchange_bytes(halo_rows, d, bits, scale_dtype)
    return total


def halo_rows(plan) -> int:
    """Real halo rows over all partitions (one per receiver and node)."""
    return len(plan.halo_node)
