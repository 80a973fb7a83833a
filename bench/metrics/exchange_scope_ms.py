"""Device time per epoch of the operations the compiled step keeps under
``jax.named_scope("exchange")``: the boundary gather, the halo exchange (on
one chip the stacked roll, on several the collectives) and the boundary
gradient scatter of ``core/exchange.py``. Mean over the cell's chips."""

from bench.scopes import scope_ms


def read(rec):
    return scope_ms(rec, "exchange")
