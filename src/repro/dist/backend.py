"""Pluggable halo-communicator backends (the paper's Communicator, §3.2).

Sylvie's core claim is that the *halo exchange* — not gradient sync — is the
bottleneck of distributed full-graph training, so the communicator is a
first-class, swappable subsystem: every piece of runtime code that moves
boundary data goes through the :class:`HaloBackend` protocol instead of
hard-coding a collective. Two concrete backends implement it:

* :class:`SimulatedBackend` — the whole partition stack ``(P, ...)`` lives in
  one program on one device; the exchange is the pure transpose
  ``out[p, q*h+s] = in[q, p*h+s]``, ``psum`` is the identity (the stacked-axis
  contraction is already global). Reference semantics; used by tests, CPU
  benchmarks, and laptop-scale training.
* :class:`ShardMapBackend` — one partition per mesh device (the production
  path). The leading axis is locally size 1 inside ``jax.shard_map``; the
  exchange is a single tiled ``jax.lax.all_to_all`` over the halo-buffer axis,
  which implements exactly the same transpose across devices.

Both backends speak two buffer layouts:

* dense pairwise blocks ``(P, P*h_pad, ...)`` — ``exchange`` is the transpose
  ``out[p, q*h+s] = in[q, p*h+s]`` (simulated: a stacked reshape/swap; shard_map:
  one tiled ``all_to_all``). It is an involution, so forward and backward
  communication share it.
* compact ring buckets ``(P, sum(bucket_sizes), ...)`` — ``exchange_compact``
  moves bucket ``k`` from ``p`` to ``(p+k) % P`` (simulated: a stacked
  ``jnp.roll`` per bucket; shard_map: one ``ppermute`` per bucket). Ragged
  bucket sizes break the involution; ``reverse=True`` runs the inverted rings
  for the backward communication (Alg. 2).

Backends are frozen dataclasses: hashable and comparable, so they can ride
through ``jax.custom_vjp`` nondiff argnums and key jit caches (see
``core/sylvie.py``). Later communication strategies (pairwise NCCL-style
sends, adaptive per-message bit-widths à la AdaQP) plug in as new
implementations of this protocol without touching model code.

See DESIGN.md §1 for the full contract.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import TYPE_CHECKING, Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

if TYPE_CHECKING:  # import-cycle guard: see _exchange_quantized
    from ..core.quantization import QuantizedTensor


@runtime_checkable
class HaloBackend(Protocol):
    """What the Sylvie runtime needs from a communicator.

    Traced (called inside jit / shard_map / custom_vjp):
      * ``exchange(buf)``            — the halo all-to-all on a pairwise-blocked
        buffer ``(P_local, P*h_pad, ...)``. An involution (a transpose), so the
        backward communication (Alg. 2) reuses the same primitive.
      * ``exchange_compact(buf, bucket_sizes, reverse)`` — the ragged ring
        exchange on a compacted buffer ``(P_local, sum(bucket_sizes), ...)``;
        ``reverse=True`` inverts the rings (backward communication).
      * ``exchange_quantized(qt)`` / ``exchange_quantized_compact(qt, ...)`` —
        exchange a quantized payload; data and error-compensation (scale,
        zero) move together.
      * ``psum(x)``                  — all-reduce across partitions (Alg. 2
        line 16); identity in the simulated stack.
      * ``fence(tree)``              — land an in-flight exchange: identity on
        the data, a scheduling barrier in the lowered program (the overlap
        schedule's in-order consumption point: the ``fenced`` flag of the
        halo primitives in ``core/sylvie.py``).
      * ``axis_index()``             — traced flat partition index, or ``None``
        when the whole stack is present (simulated).

    Untraced (host-side placement / compilation):
      * ``device_put(tree, spec)``   — place a pytree; ``spec`` is a single
        ``PartitionSpec`` applied to every leaf (ignored when unsharded).
      * ``shard(fn, in_specs, out_specs)`` — compile a step function for this
        backend (plain ``jax.jit`` or ``jit(shard_map(...))``).
    """

    def exchange(self, buf: jax.Array) -> jax.Array: ...

    def exchange_compact(self, buf: jax.Array, bucket_sizes: tuple[int, ...],
                         reverse: bool = False) -> jax.Array: ...

    def exchange_quantized(self, qt: QuantizedTensor) -> QuantizedTensor: ...

    def exchange_quantized_compact(self, qt: QuantizedTensor,
                                   bucket_sizes: tuple[int, ...],
                                   reverse: bool = False) -> QuantizedTensor: ...

    def psum(self, x: jax.Array) -> jax.Array: ...

    def fence(self, tree: Any) -> Any: ...

    def axis_index(self) -> Optional[jax.Array]: ...

    def device_put(self, tree: Any, spec: Optional[P] = None) -> Any: ...

    def shard(self, fn: Any, in_specs: Any = None,
              out_specs: Any = None) -> Any: ...


def _exchange_quantized(exch, qt: "QuantizedTensor") -> "QuantizedTensor":
    """Shared payload+error-compensation exchange (paper §3.2 Communicator).
    ``exch`` is the buffer-level exchange closure (dense or compact)."""
    # deferred import: this module must stay a leaf below repro.core so either
    # package can be imported first (core.exchange imports us at module level)
    from ..core.quantization import QuantizedTensor
    return QuantizedTensor(
        data=exch(qt.data),
        scale=exch(qt.scale) if qt.scale.size else qt.scale,
        zero=exch(qt.zero) if qt.zero.size else qt.zero,
        bits=qt.bits, feat_dim=qt.feat_dim)


def _collective(fn, *args):
    """``fn(*args)`` under ``jax.named_scope("collective")``: the cross-device
    collectives of the sharded path, nested inside the ``exchange`` scope
    where they move halos. Deferred import for the same cycle as
    ``_exchange_quantized``."""
    from ..core.scopes import scoped
    return scoped("collective")(fn)(*args)


def _bucket_slices(bucket_sizes: tuple[int, ...]):
    """(ring offset k, start, stop) for each non-empty bucket."""
    out, start = [], 0
    for k, b in enumerate(bucket_sizes):
        if b:
            out.append((k, start, start + b))
        start += b
    return out


@dataclasses.dataclass(frozen=True)
class SimulatedBackend:
    """Stacked single-program reference semantics (``P`` partitions, 1 device).

    ``n_parts`` is optional metadata for the :class:`~repro.dist.runtime.Runtime`
    facade (graph partitioning); the exchange itself reads ``P`` off the buffer.
    """

    n_parts: Optional[int] = None

    def exchange(self, buf: jax.Array) -> jax.Array:
        p = buf.shape[0]
        h = buf.shape[1] // p
        y = buf.reshape((p, p, h) + buf.shape[2:])
        y = jnp.swapaxes(y, 0, 1)
        return y.reshape((p, p * h) + buf.shape[2:])

    def exchange_compact(self, buf: jax.Array, bucket_sizes: tuple[int, ...],
                         reverse: bool = False) -> jax.Array:
        """Ring exchange on the stack: bucket k rolls k partitions forward
        (out[p] = in[(p-k) % P]), or backward when reversed."""
        parts = [jnp.roll(buf[:, s0:s1], -k if reverse else k, axis=0)
                 for k, s0, s1 in _bucket_slices(bucket_sizes)]
        return jnp.concatenate(parts, axis=1) if parts else buf

    def exchange_quantized(self, qt: QuantizedTensor) -> QuantizedTensor:
        return _exchange_quantized(self.exchange, qt)

    def exchange_quantized_compact(self, qt: QuantizedTensor,
                                   bucket_sizes: tuple[int, ...],
                                   reverse: bool = False) -> QuantizedTensor:
        return _exchange_quantized(
            lambda b: self.exchange_compact(b, bucket_sizes, reverse), qt)

    def psum(self, x: jax.Array) -> jax.Array:
        return x  # the stacked-axis contraction is already global

    def fence(self, tree: Any) -> Any:
        return jax.lax.optimization_barrier(tree)

    def axis_index(self) -> None:
        return None

    def device_put(self, tree: Any, spec: Optional[P] = None) -> Any:
        del spec  # single device — nothing to shard
        return tree

    def shard(self, fn: Any, in_specs: Any = None,
              out_specs: Any = None) -> Any:
        del in_specs, out_specs
        return jax.jit(fn)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rep_psum(x, axes):
    """All-reduce whose output is *replicated*: the cotangent of a replicated
    value is itself replicated, so the transpose is the identity (what
    check_vma replication tracking infers; under ``check_vma=False`` the raw
    ``lax.psum`` would transpose to another psum and over-count by P)."""
    return jax.lax.psum(x, axes)


def _rep_psum_fwd(x, axes):
    return jax.lax.psum(x, axes), None


def _rep_psum_bwd(axes, _, g):
    return (g,)


_rep_psum.defvjp(_rep_psum_fwd, _rep_psum_bwd)


@dataclasses.dataclass(frozen=True)
class ShardMapBackend:
    """One partition per mesh device; collectives over the flattened mesh.

    Construct from a mesh (``ShardMapBackend(mesh)``) for the full protocol, or
    from bare axis names (``ShardMapBackend(axes=("parts",))``) when only the
    traced collectives are needed inside an externally-managed ``shard_map``.
    """

    mesh: Any = None
    axes: Optional[tuple[str, ...]] = None

    def __post_init__(self):
        if self.mesh is None and self.axes is None:
            raise ValueError("ShardMapBackend needs a mesh or axis names")
        if self.axes is not None and not isinstance(self.axes, tuple):
            object.__setattr__(self, "axes", tuple(self.axes))

    @property
    def axis_names(self) -> tuple[str, ...]:
        return self.axes if self.axes is not None else tuple(self.mesh.axis_names)

    def exchange(self, buf: jax.Array) -> jax.Array:
        return _collective(partial(jax.lax.all_to_all,
                                   axis_name=self.axis_names, split_axis=1,
                                   concat_axis=1, tiled=True), buf)

    def exchange_compact(self, buf: jax.Array, bucket_sizes: tuple[int, ...],
                         reverse: bool = False) -> jax.Array:
        """Ring exchange across devices: one ``ppermute`` per non-empty bucket
        (bucket k: p -> (p+k) % P; inverted rings when reversed). Only the
        aligned bucket rows ever hit the interconnect — no global-max padding,
        no diagonal self-block."""
        names = self.axis_names
        axis = names[0] if len(names) == 1 else names  # tuple = flattened axes
        p = len(bucket_sizes)
        parts = []
        for k, s0, s1 in _bucket_slices(bucket_sizes):
            kk = (p - k) % p if reverse else k
            perm = [(src, (src + kk) % p) for src in range(p)]
            parts.append(_collective(partial(jax.lax.ppermute,
                                             axis_name=axis, perm=perm),
                                     buf[:, s0:s1]))
        return jnp.concatenate(parts, axis=1) if parts else buf

    def exchange_quantized(self, qt: QuantizedTensor) -> QuantizedTensor:
        return _exchange_quantized(self.exchange, qt)

    def exchange_quantized_compact(self, qt: QuantizedTensor,
                                   bucket_sizes: tuple[int, ...],
                                   reverse: bool = False) -> QuantizedTensor:
        return _exchange_quantized(
            lambda b: self.exchange_compact(b, bucket_sizes, reverse), qt)

    def psum(self, x: jax.Array) -> jax.Array:
        return _collective(_rep_psum, x, self.axis_names)

    def fence(self, tree: Any) -> Any:
        return jax.lax.optimization_barrier(tree)

    def axis_index(self) -> jax.Array:
        names = self.axis_names
        idx = jax.lax.axis_index(names[0])
        for a in names[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx

    def _require_mesh(self, what: str):
        if self.mesh is None:
            raise ValueError(f"{what} needs a mesh-backed ShardMapBackend")

    def device_put(self, tree: Any, spec: Optional[P] = None) -> Any:
        self._require_mesh("device_put")
        spec = P() if spec is None else spec
        return jax.device_put(tree, NamedSharding(self.mesh, spec))

    def shard(self, fn: Any, in_specs: Any = None,
              out_specs: Any = None) -> Any:
        # check=False: replication inference cannot see through the quantized
        # custom_vjp exchanges, so the steps reduce weight gradients with an
        # explicit self.psum (Alg. 2 line 16) instead of a boundary check.
        self._require_mesh("shard")
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))


def as_backend(b: Any) -> HaloBackend:
    """Normalize legacy communicator designators to a backend.

    ``None`` -> :class:`SimulatedBackend`; an axis name (or tuple of names) ->
    a mesh-less :class:`ShardMapBackend`; a backend passes through.
    """
    if b is None:
        return SimulatedBackend()
    if isinstance(b, str):
        return ShardMapBackend(axes=(b,))
    if isinstance(b, (tuple, list)):
        return ShardMapBackend(axes=tuple(b))
    if not isinstance(b, HaloBackend):
        raise TypeError(f"not a HaloBackend: {b!r} (pass a backend, an axis "
                        "name, or None for the simulated stack)")
    return b
