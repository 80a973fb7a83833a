"""The whole train step's share of the chips' bf16 peak: the forward and
backward FLOPs of an epoch (``bench/counts.py``) over the traced epoch time,
over chips times peak."""


def read(rec):
    if not rec.epochs or rec.epoch_s <= 0:
        return None
    peak = rec.chips * rec.peak["bf16_flops_per_s"]
    return 100.0 * rec.counts["flops_per_epoch"] / rec.epoch_s / peak
