"""The Low-bit Module kernels' share of their HBM roofline: the bytes an
epoch's exchanges need (``bench/counts.py``) over the kernels' device time
per epoch per chip, over the chips' HBM bandwidth. The kernels do almost no
arithmetic per byte, so bandwidth bounds them."""

from bench.metrics.lowbit_ms import is_lowbit


def read(rec):
    s = rec.per_epoch_device_s(lambda op, ins: is_lowbit(ins))
    need = rec.counts["lowbit_bytes_per_epoch"]
    if s is None or s <= 0 or need <= 0:
        return None
    return 100.0 * need / (rec.chips * s) / rec.peak["hbm_bytes_per_s"]
