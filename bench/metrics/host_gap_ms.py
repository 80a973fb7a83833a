"""Host time per epoch outside the train step: the trainer's whole-epoch
wall time less the step call (``EpochMetrics.wall_s - seconds``), mean over
the traced window's epochs. Layer: the epoch loop, ``train/trainer.py``."""


def read(rec):
    if not rec.epochs:
        return None
    gaps = [m.wall_s - m.seconds for m in rec.epochs]
    return 1e3 * sum(gaps) / len(gaps)
