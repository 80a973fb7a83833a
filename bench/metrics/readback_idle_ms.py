"""Device idle time per epoch under the trainer's ``readback.*`` spans
(``readback.loss``: the blocking ``float(loss)``, which waits for the step;
``readback.stats``: the site-statistics ``device_get``), mean over the
cell's chips. Includes the gaps between the step's own operations, which
run while the host waits in ``readback.loss``."""

from bench.scopes import idle_under_ms


def read(rec):
    return idle_under_ms(rec, lambda name: name.startswith("readback."))
