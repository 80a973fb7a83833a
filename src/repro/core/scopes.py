"""Named scopes that split the compiled step by layer: ``aggregation``
(``models/gnn/blocks.py``), ``lowbit`` (``core/quantization.py``),
``exchange`` (``core/exchange.py``) and ``collective`` (``dist/backend.py``:
the sharded path's collectives, inside ``exchange`` where they move halos).
They are metadata only: the names reach the optimised program's ``op_name``
and, through it, the profiler's device events; the program itself is the
same without them."""
from __future__ import annotations

import functools

import jax


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``.

    Each call enters a fresh scope. The object ``jax.named_scope`` returns
    keeps the name stack it restores in itself, so one object shared by nested
    calls (``agg_mean`` calling ``agg_sum``) would leave ``name`` on the stack
    for everything traced after them."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return run
    return wrap
