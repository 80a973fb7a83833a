"""Device time per epoch of the operations the compiled step keeps under
``jax.named_scope("aggregation")``: the edge-list gathers, segment
reductions, degrees and edge softmax of ``models/gnn/blocks.py``, forward
and backward. Mean over the cell's chips. Operations without ``op_name``
metadata are not counted (``bench/scopes.py``)."""

from bench.scopes import scope_ms


def read(rec):
    return scope_ms(rec, "aggregation")
