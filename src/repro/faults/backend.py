"""FaultyBackend: a HaloBackend wrapper that carries a chaos schedule.

The wrapper is deliberately *transparent on the wire*: every protocol method
delegates to the wrapped backend unchanged. Fault injection does not happen
here — the stacked/sharded collectives are all-or-nothing, so per-row drops
and bit-flips are applied as traced data by the halo primitives of
``core/sylvie.py`` (masks in ``GNNTrainState.faults``), never by mutating
the collective itself. What the wrapper *does* do is bind a
:class:`~repro.faults.plan.FaultPlan` to a runtime: ``GNNTrainer`` discovers
the plan on its ``Runtime``'s backend and arms the per-epoch schedule, so a
single constructor argument (``Runtime(FaultyBackend(base, plan))``) turns
any existing launch path into a chaos run.

Frozen and hashable (it keys jit caches and rides custom_vjp nondiff
argnums, exactly like the backends it wraps), and satisfies the runtime-
checkable ``HaloBackend`` protocol so ``as_backend``/``Runtime`` accept it.
"""
from __future__ import annotations

import dataclasses

from ..dist.backend import HaloBackend
from .plan import FaultPlan


@dataclasses.dataclass(frozen=True)
class FaultyBackend:
    """Delegating wrapper binding a :class:`FaultPlan` to a backend."""

    base: HaloBackend
    plan: FaultPlan = FaultPlan()

    # --- passthroughs Runtime introspects (mesh => sharded, n_parts) ---
    @property
    def mesh(self):
        return getattr(self.base, "mesh", None)

    @property
    def n_parts(self):
        return getattr(self.base, "n_parts", None)

    # --- HaloBackend protocol: pure delegation ---
    def exchange(self, send_bufs, h_pad):
        return self.base.exchange(send_bufs, h_pad)

    def exchange_compact(self, buf, bucket_sizes, reverse=False):
        return self.base.exchange_compact(buf, bucket_sizes, reverse=reverse)

    def exchange_quantized(self, qt, h_pad):
        return self.base.exchange_quantized(qt, h_pad)

    def exchange_quantized_compact(self, qt, bucket_sizes, reverse=False):
        return self.base.exchange_quantized_compact(qt, bucket_sizes,
                                                    reverse=reverse)

    def psum(self, x):
        return self.base.psum(x)

    def fence(self, tree):
        return self.base.fence(tree)

    def axis_index(self):
        return self.base.axis_index()

    def device_put(self, tree, sharded: bool):
        return self.base.device_put(tree, sharded)

    def shard(self, fn, state_specs, data_specs, out_specs):
        return self.base.shard(fn, state_specs, data_specs, out_specs)
