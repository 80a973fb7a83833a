"""Partitioned full-graph inference engine with per-layer embedding caches.

``InferenceEngine`` restores a trained checkpoint (or takes params directly)
and serves node queries off materialized caches:

* **one sweep executable** runs the model forward through the existing
  ``HaloBackend``/``SylvieComm`` quantized-halo machinery (simulated stack or
  shard_map — fixed by a :class:`~repro.dist.runtime.Runtime`, exactly like
  training). Per-site bit-widths come from an
  :class:`~repro.policy.base.EpochDecision` on the same lattice the training
  policies use;
* a **full sweep** and an incremental **delta refresh** are the *same traced
  function*: the sweep takes per-site "affected" send masks as data and blends
  freshly exchanged halo rows with the cached ones
  (``where(affected, fresh, cached)``). A full sweep is the all-rows mask; a
  delta refresh ships only the k-hop frontier of the changed nodes
  (``repro.serve.delta``). One executable means delta == full is a structural
  guarantee, not a numerical accident;
* after a sweep the engine holds, per exchange site, the embedding entering
  that site (``(P, n_local, d_i)``) and its dequantized halo buffer, plus the
  final logits — **node queries are O(lookup)**: global id -> (partition,
  slot) -> cached row, no graph compute on the request path.

Staleness bound: ``ServeConfig.max_staleness`` caps consecutive delta
refreshes; the next ``refresh()`` past the bound escalates to a forced full
sweep (the serving analogue of the Bounded Staleness Adaptor — see
``delta.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import quantization as qlib
from ..core.exchange import exchange_halo, exchange_quantized_halo, \
    gather_boundary
from ..core.staleness import HaloState
from ..core.sylvie import SylvieComm, SylvieConfig
from ..dist.runtime import Runtime
from ..graph.partition import PartitionedGraph, global_to_slot, khop_frontier
from ..models.gnn import blocks as B
from ..obs import profiler as obs_profiler
from ..policy.base import EpochDecision, validate_decision
from ..train import checkpoint as ckpt
from . import delta as deltalib

# host spans also land in the JAX profiler's trace, on the device clock
obs_profiler.install()

# Trace instrumentation, mirroring train.gnn_step.TRACE_LOG: the sweep body
# appends once per jit trace. repro.analysis (RC204/RC207) counts entries to
# verify the single-sweep-executable guarantee instead of trusting it; the
# TraceLog shim additionally counts ``retrace.serve`` in the metrics registry.
TRACE_LOG = obs.TraceLog("serve")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-time communication + refresh policy.

    ``bits`` quantizes every halo exchange of the serving forward pass
    (32 = full precision; per-site widths via an explicit ``decision``).
    ``stochastic=False`` (the default) uses deterministic round-to-nearest —
    required for the delta-refresh exactness guarantee; stochastic rounding is
    allowed but makes deltas unbiased rather than exact. ``max_staleness`` is
    the number of consecutive delta refreshes served before the next refresh
    is forced to a full sweep. ``schedule`` picks the exchange schedule of the
    sweep executable (``"overlap"`` lands each exchange through
    ``backend.fence``; bit-exact to blocking — the serving sweep is always
    synchronous/fresh)."""

    bits: int = 1
    stochastic: bool = False
    max_staleness: int = 8
    scale_dtype: jnp.dtype = jnp.bfloat16
    quant_impl: str = "auto"
    schedule: str = "blocking"


class ServeComm(SylvieComm):
    """Forward-only quantized halo with delta blending.

    At site ``i``: quantize the (full) send buffer, exchange, dequantize, then
    keep only the rows the refresh plan marked affected — every other row
    comes from ``cached_halos[i]``. The affected mask travels through the same
    exchange so each partition learns which *received* rows are fresh. Records
    the site-input embedding (the per-layer cache) and the blended halo (the
    next refresh's cache) as it goes. No custom_vjp: serving never
    differentiates."""

    def __init__(self, cfg, plan, key, backend, decision, cached_halos,
                 send_affected):
        super().__init__(cfg, plan, key, backend=backend, decision=decision)
        self.cached_halos = cached_halos
        self.send_affected = send_affected
        self.layer_inputs: list = []

    def halo(self, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        i = self._site
        self._site += 1
        sd = self._site_decision(i)
        kf = jax.random.fold_in(self._part_key(), 2 * i)
        self.layer_inputs.append(h)
        buf = gather_boundary(h, self.plan)
        qt = qlib.quantize(buf, sd.fwd_bits, kf, sd.stochastic,
                           cfg.scale_dtype, impl=cfg.quant_impl)
        inflight = exchange_quantized_halo(qt, self.plan, self.backend)
        # which received rows are fresh = the senders' affected masks, moved
        # through the same exchange as a uint8 bitmap (never fp32 on the
        # wire: the analysis wire-dtype audit, RC202, holds this path to the
        # same low-bit contract as the payload)
        aff = exchange_halo(
            self.send_affected[i][..., None].astype(jnp.uint8),
            self.plan, self.backend)
        if self.schedule == "overlap":
            # land both issued exchanges through one fence: the collectives
            # stay standalone ops the scheduler can overlap with the layer's
            # local aggregation; identity on data (bit-exact to blocking).
            inflight, aff = self.backend.fence((inflight, aff))
        fresh = qlib.dequantize(inflight, impl=cfg.quant_impl)
        fresh = jnp.where(self.plan.recv_mask[..., None], fresh, 0)
        halo = jnp.where(aff > 0, fresh, self.cached_halos[i])
        self.new_feat_caches.append(halo)
        return halo


@dataclasses.dataclass
class QueryResult:
    """One answered query batch.

    ``staleness[j]`` counts the sweeps served from cache for node ``j``'s
    partition since its rows were last recomputed — 0 everywhere while the
    engine is healthy, >0 for nodes on a partition marked down (degraded
    mode: answers come from the stale embedding cache, stamped, never
    refused)."""

    node_ids: np.ndarray
    logits: np.ndarray
    staleness: Optional[np.ndarray] = None

    @property
    def predictions(self) -> np.ndarray:
        return np.argmax(self.logits, axis=-1)


class InferenceEngine:
    """Quantized full-graph inference over a partitioned graph.

    Example::

        pg, _ = datasets.load_partitioned("yelp_like@small", n_parts=4)
        params, meta = checkpoint.restore_for_inference(ckpt_dir,
                                                        model.init(key))
        eng = InferenceEngine(model, pg, params,
                              config=ServeConfig(bits=1))
        eng.full_sweep()                        # materialize all caches
        out = eng.query([3, 17, 4242])          # O(lookup)
        rep = eng.refresh(changed_ids, new_rows)   # k-hop delta refresh
        print(rep.kind, rep.wire_bytes)
    """

    # store table names: cached logits + the deepest cached embedding layer
    # (what ``embeddings(site=-1)`` serves).
    STORE_TABLES = ("logits", "emb")

    def __init__(self, model, pg: PartitionedGraph, params,
                 config: Optional[ServeConfig] = None,
                 decision: Optional[EpochDecision] = None,
                 runtime: Optional[Runtime] = None, seed: int = 0,
                 store=None):
        self.model = model
        self.pg = pg
        self.config = cfg = config if config is not None else ServeConfig()
        p = pg.plan.n_parts
        if runtime is None:
            runtime = Runtime.simulated(p)
        if runtime.n_parts not in (None, p):
            raise ValueError(
                f"runtime is committed to {runtime.n_parts} partitions but "
                f"the graph was partitioned into {p}")
        self.runtime = runtime
        self.site_dims = tuple(int(d) for d in model.comm_dims())
        self.n_sites = len(self.site_dims)
        if decision is None:
            # the config owns the schedule for the default decision; an
            # explicit decision keeps its own (mirrors trainer semantics).
            decision = EpochDecision.uniform(self.n_sites, bits=cfg.bits,
                                             stochastic=cfg.stochastic,
                                             schedule=cfg.schedule)
        self.decision = validate_decision(decision.snapped(), self.n_sites)
        self._scfg = SylvieConfig(mode="sync", bits=cfg.bits,
                                  stochastic=cfg.stochastic,
                                  scale_dtype=cfg.scale_dtype,
                                  quant_impl=cfg.quant_impl,
                                  schedule=self.decision.schedule)
        self.block = B.build_block(pg, transposed=False)   # forward only
        self.key = jax.random.PRNGKey(seed)

        # global id -> (partition, local slot): the O(lookup) request path
        self._part_of, self._slot_of = global_to_slot(pg)

        self._sweep = self._build_sweep()
        # refresh planning amortizes the O(E) edge/ownership reconstruction
        self._frontier = deltalib.FrontierIndex.build(pg)
        self.params = runtime.device_put_replicated(params)
        self.block = runtime.device_put_stacked(self.block)
        self._x_host = np.asarray(pg.x, dtype=np.float32).copy()
        self.x = runtime.device_put_stacked(jnp.asarray(self._x_host))
        self._halos = runtime.device_put_stacked(
            HaloState.zeros(self.block.plan, self.site_dims,
                            stacked_parts=p).feats)
        self._layers: Optional[tuple] = None
        self._logits_host: Optional[np.ndarray] = None
        self._since_full = 0
        self._refresh_count = 0
        # degraded mode: partitions marked down contribute no fresh halo
        # rows (their send-affected masks are zeroed — data, same sweep
        # executable) and their cached logits are frozen; per-partition
        # staleness counts sweeps served from the frozen cache.
        self._down = np.zeros(p, dtype=bool)
        self._part_staleness = np.zeros(p, dtype=np.int64)
        # optional sharded embedding store (repro.store): node lookups read
        # through it, sweeps publish into it (see attach_store)
        self.store = None
        if store is not None:
            self.attach_store(store)

    # ------------------------------------------------------------------
    # the sweep executable (shared by full sweeps and delta refreshes)
    # ------------------------------------------------------------------
    def _build_sweep(self):
        model, scfg, decision = self.model, self._scfg, self.decision
        backend = self.runtime.backend

        def sweep_fn(params, block, x, halos, masks, key):
            TRACE_LOG.append("sweep")
            comm = ServeComm(scfg, block.plan, key, backend, decision,
                             cached_halos=halos, send_affected=masks)
            logits = model.apply(params, block, x, comm)
            return logits, tuple(comm.layer_inputs), \
                tuple(comm.new_feat_caches)

        return self.runtime.shard_serve_fn(sweep_fn)

    def _run(self, refresh: deltalib.RefreshPlan, *, kind: str, forced: bool,
             changed_ids: Optional[np.ndarray] = None
             ) -> deltalib.RefreshReport:
        t0 = obs.clock()
        key = jax.random.fold_in(self.key, self._refresh_count)
        self._refresh_count += 1
        masks = refresh.device_masks()
        if self._down.any():
            # down partitions publish nothing fresh: zero their send-affected
            # rows so every receiver keeps its cached rows from them. Masks
            # are data — the sweep executable is unchanged.
            up = (~self._down)[:, None].astype(np.float32)
            masks = tuple(m * up for m in masks)
        with obs.span("sweep", {"kind": kind}):
            logits, layers, halos = self._sweep(self.params, self.block,
                                                self.x, self._halos, masks,
                                                key)
        self._layers = layers
        self._halos = halos
        fresh_logits = np.asarray(jax.device_get(logits))
        if self._logits_host is not None and self._down.any():
            # a down partition computes nothing: its served rows stay frozen
            # at the last sweep before it went down. (device_get may hand
            # back a read-only view — copy before patching.)
            fresh_logits = fresh_logits.copy()
            fresh_logits[self._down] = self._logits_host[self._down]
        self._logits_host = fresh_logits
        self._part_staleness = np.where(self._down,
                                        self._part_staleness + 1, 0)
        if self.store is not None:
            # full sweeps republish every row; deltas only the rows the
            # sweep could have changed (the logits-depth frontier)
            self._publish(None if kind == "full" else changed_ids)
        pb, eb, mb = deltalib.refresh_wire_bytes(
            self.block.plan.real_rows, self.site_dims, self.decision, refresh,
            self.config.scale_dtype)
        return deltalib.RefreshReport(
            kind=kind, forced=forced, changed=refresh.changed,
            affected_rows=refresh.affected_rows, payload_bytes=pb,
            ec_bytes=eb, meta_bytes=mb, seconds=obs.clock() - t0)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    @staticmethod
    def from_checkpoint(ckpt_dir, model, pg: PartitionedGraph,
                        config: Optional[ServeConfig] = None,
                        decision: Optional[EpochDecision] = None,
                        runtime: Optional[Runtime] = None,
                        step: Optional[int] = None, seed: int = 0,
                        store=None) -> tuple["InferenceEngine", dict]:
        """Train -> save -> serve handoff: restore only the model parameters
        (``checkpoint.restore_for_inference``) and build an engine. Returns
        ``(engine, checkpoint_meta)``."""
        example = model.init(jax.random.PRNGKey(0))
        params, meta = ckpt.restore_for_inference(ckpt_dir, example, step=step)
        return InferenceEngine(model, pg, params, config=config,
                               decision=decision, runtime=runtime,
                               seed=seed, store=store), meta

    def full_sweep(self) -> deltalib.RefreshReport:
        """Recompute every cache from the current features (all boundary rows
        ship). Resets the staleness clock."""
        rep = self._run(deltalib.plan_full(self.pg, self.n_sites),
                        kind="full", forced=False)
        self._since_full = 0
        return rep

    def refresh(self, changed_global_ids, new_rows, *,
                full: bool = False) -> deltalib.RefreshReport:
        """Apply a feature update and refresh the caches incrementally.

        ``new_rows`` are the replacement feature rows for
        ``changed_global_ids`` (same order). Ships only the k-hop-affected
        boundary rows per layer; escalates to a full sweep when ``full=True``
        is requested, the staleness bound is reached, or no sweep has run yet
        (a delta against the zero-initialized caches would serve garbage)."""
        ids = self._check_ids(changed_global_ids)
        rows = np.asarray(new_rows, dtype=np.float32)
        if rows.shape != (ids.size, self._x_host.shape[-1]):
            raise ValueError(
                f"new_rows must be ({ids.size}, {self._x_host.shape[-1]}), "
                f"got {rows.shape}")
        # scatter the changed rows on device — O(changed), never a full
        # O(N*d) re-upload — and mirror them into the host copy
        self._x_host[self._part_of[ids], self._slot_of[ids]] = rows
        self.x = self.runtime.device_put_stacked(
            self.x.at[self._part_of[ids], self._slot_of[ids]].set(
                jnp.asarray(rows)))
        never_swept = self._logits_host is None
        with obs.span("refresh", {"changed": int(ids.size)}):
            if full or never_swept or \
                    self._since_full >= self.config.max_staleness:
                rep = self._run(deltalib.plan_full(self.pg, self.n_sites),
                                kind="full", forced=not full)
                rep = dataclasses.replace(rep, changed=int(ids.size))
                self._since_full = 0
                return rep
            with obs.span("plan"):
                plan = self._frontier.plan_refresh(ids, self.n_sites)
            rep = self._run(plan, kind="delta", forced=False, changed_ids=ids)
            self._since_full += 1
            return rep

    # ------------------------------------------------------------------
    # degraded mode (partition down/up)
    # ------------------------------------------------------------------
    def set_down(self, parts) -> None:
        """Mark partitions down. Their cached rows keep serving (stamped with
        growing staleness); sweeps stop consuming their halo contributions."""
        self._down[np.asarray(parts, dtype=np.int64).reshape(-1)] = True

    def set_up(self, parts) -> None:
        """Bring partitions back. Staleness resets on their next sweep (the
        caller should run ``full_sweep``/``refresh`` to recompute their rows)."""
        self._down[np.asarray(parts, dtype=np.int64).reshape(-1)] = False

    def down_partitions(self) -> np.ndarray:
        return np.nonzero(self._down)[0]

    @property
    def part_staleness(self) -> np.ndarray:
        """(P,) sweeps served from frozen cache per partition (0 = fresh)."""
        return self._part_staleness.copy()

    # ------------------------------------------------------------------
    # sharded embedding store (repro.store)
    # ------------------------------------------------------------------
    def attach_store(self, store) -> None:
        """Serve node lookups through a :class:`repro.store.StoreBackend`.

        The engine stays the single *writer*: every sweep publishes the rows
        it could have changed into the store's per-partition shards (tables
        ``"logits"`` and ``"emb"``); ``query``/``embeddings(site=-1)`` then
        *read* through the store's hot-node cache instead of the
        materialized tables — bit-exact by construction (``verify_store``
        asserts it, ``BENCH_store.json`` gates it). Attach before the first
        sweep, or re-publish with ``full_sweep()``."""
        self.store = store
        if self._logits_host is not None:
            self._publish(None)

    def _publish(self, changed_ids: Optional[np.ndarray]) -> None:
        """Write the rows the last sweep could have changed into the store.

        ``changed_ids=None`` republishes every real row (full sweep). For a
        delta, the superset of rows whose cached values may differ is the
        ``n_sites``-hop frontier of the changed set — one hop per layer plus
        the logits readout (unaffected rows are bit-stable under
        deterministic rounding, the delta==full guarantee)."""
        st = self.store
        p_count = self.pg.plan.n_parts
        tables = {"logits": self._logits_host,
                  "emb": np.asarray(jax.device_get(self._layers[-1]))}
        for name, arr in tables.items():
            if not st.has_table(name):
                st.create_table(name, part_rows=(arr.shape[1],) * p_count,
                                d=arr.shape[2], dtype=arr.dtype)
        if changed_ids is None:
            for p in range(p_count):
                slots = np.nonzero(self.pg.node_mask[p])[0]
                for name, arr in tables.items():
                    st.put_rows(name, p, slots, arr[p, slots])
            return
        fr = khop_frontier(self.pg, changed_ids, self.n_sites,
                           edges=self._frontier.edges)[-1]
        ids = np.nonzero(fr)[0]
        parts, slots = self._part_of[ids], self._slot_of[ids]
        for p in np.unique(parts):
            sl = slots[parts == p]
            for name, arr in tables.items():
                st.put_rows(name, int(p), sl, arr[int(p), sl])

    def _store_lookup(self, table: str, ids: np.ndarray) -> np.ndarray:
        """Batched store read in request order (one ``get_rows`` per
        partition the batch touches)."""
        parts, slots = self._part_of[ids], self._slot_of[ids]
        out: Optional[np.ndarray] = None
        for p in np.unique(parts):
            sel = parts == p
            rows = self.store.get_rows(table, int(p), slots[sel])
            if out is None:
                out = np.empty((ids.size,) + rows.shape[1:], rows.dtype)
            out[sel] = rows
        return out

    def pin_hot(self, node_ids, tables: Optional[tuple] = None) -> None:
        """Pin the hot nodes' rows into the store's pinned tier (they stay
        materialized and are write-through refreshed by every publish)."""
        if self.store is None:
            raise RuntimeError("no store attached")
        self._require_swept()
        ids = self._check_ids(node_ids)
        parts, slots = self._part_of[ids], self._slot_of[ids]
        for p in np.unique(parts):
            for table in tables or self.STORE_TABLES:
                self.store.pin(table, int(p), slots[parts == p])

    def verify_store(self) -> int:
        """Assert the store-backed read path is bit-exact vs the materialized
        tables: every shard row equals the engine's row, and every cached row
        equals its shard row. Returns the number of rows verified."""
        if self.store is None:
            raise RuntimeError("no store attached")
        self._require_swept()
        st = self.store
        peek = getattr(st, "peek_rows", st.get_rows)
        tables = {"logits": self._logits_host,
                  "emb": np.asarray(jax.device_get(self._layers[-1]))}
        checked = 0
        for p in range(self.pg.plan.n_parts):
            slots = np.nonzero(self.pg.node_mask[p])[0]
            for name, arr in tables.items():
                if not np.array_equal(peek(name, p, slots), arr[p, slots]):
                    raise AssertionError(
                        f"store table {name!r} shard {p} diverged from the "
                        f"materialized path")
                checked += slots.size
        coherent = getattr(st, "check_coherence", None)
        if coherent is not None:
            checked += coherent()
        return checked

    def reader(self) -> "InferenceEngine | StoreReader":
        """A query-only replica view: a :class:`StoreReader` over the
        attached store, or the engine itself when none is attached (the
        materialized tables are then the only copy)."""
        return StoreReader(self) if self.store is not None else self

    def feature_rows(self, node_ids) -> np.ndarray:
        """Current feature rows for a batch of global node ids (what a
        mutation-stream edge *touch* re-submits — see repro.store.stream)."""
        ids = self._check_ids(node_ids)
        return self._x_host[self._part_of[ids], self._slot_of[ids]].copy()

    def _require_swept(self):
        if self._logits_host is None:
            raise RuntimeError("no caches yet — call full_sweep() first")

    def _check_ids(self, node_ids) -> np.ndarray:
        """Normalize + bounds-check global node ids *before* any state is
        touched (numpy's negative indexing would otherwise silently address
        the wrong node)."""
        ids = np.asarray(node_ids, dtype=np.int64).reshape(-1)
        n = self._slot_of.shape[0]
        if ids.size and (ids.min() < 0 or ids.max() >= n):
            raise ValueError(f"node ids must be in [0, {n})")
        return ids

    def query(self, node_ids) -> QueryResult:
        """Logits for a batch of global node ids — a cache lookup, no graph
        compute. With a store attached the rows come through its hot-node
        cache (miss -> shard fetch); otherwise from the materialized table.
        Both paths are bit-identical (``verify_store``)."""
        self._require_swept()
        ids = self._check_ids(node_ids)
        if self.store is not None and ids.size:
            out = self._store_lookup("logits", ids)
        else:
            out = self._logits_host[self._part_of[ids], self._slot_of[ids]]
        return QueryResult(node_ids=ids, logits=out,
                           staleness=self._part_staleness[
                               self._part_of[ids]].copy())

    def embeddings(self, node_ids, site: int = -1) -> np.ndarray:
        """Cached embeddings entering exchange site ``site`` for a batch of
        global node ids (``-1`` = last site, the deepest cached layer).
        The deepest layer is store-served when a store is attached (the
        ``"emb"`` table); other sites gather the requested rows on device —
        only O(batch * d) crosses to the host, never the full layer table."""
        self._require_swept()
        ids = self._check_ids(node_ids)
        if self.store is not None and ids.size and \
                site in (-1, self.n_sites - 1):
            return self._store_lookup("emb", ids)
        rows = self._layers[site][self._part_of[ids], self._slot_of[ids]]
        return np.asarray(jax.device_get(rows))

    @property
    def logits(self) -> np.ndarray:
        """The full cached logits table, reassembled into global node order."""
        self._require_swept()
        return self.pg.unpartition(self._logits_host)

    def full_sweep_wire_bytes(self) -> int:
        """What one full sweep ships (payload + ec), for comparison against a
        delta's :attr:`RefreshReport.wire_bytes`."""
        pb, eb, mb = deltalib.refresh_wire_bytes(
            self.block.plan.real_rows, self.site_dims, self.decision,
            deltalib.plan_full(self.pg, self.n_sites),
            self.config.scale_dtype)
        return pb + eb + mb


class StoreReader:
    """Query-only replica view over an engine's published store tables.

    A serving replica needs exactly three things: the ``(part, slot)`` index,
    the store's read path, and the writer's health/staleness stamps. A
    ``StoreReader`` carries nothing else — it cannot sweep, refresh, or mark
    partitions down, so any number of them can front one store while the
    engine remains the single writer (``ReplicaSet`` in ``server.py`` builds
    one per replica via ``engine.reader()``)."""

    def __init__(self, engine: InferenceEngine):
        if engine.store is None:
            raise ValueError("engine has no store attached")
        self._engine = engine
        self.store = engine.store
        self.pg = engine.pg

    def query(self, node_ids) -> QueryResult:
        """Store-backed logits lookup — same contract as ``engine.query``."""
        eng = self._engine
        eng._require_swept()
        ids = eng._check_ids(node_ids)
        out = eng._store_lookup("logits", ids) if ids.size else \
            np.empty((0, eng._logits_host.shape[-1]), np.float32)
        return QueryResult(node_ids=ids, logits=out,
                           staleness=eng._part_staleness[
                               eng._part_of[ids]].copy())

    def embeddings(self, node_ids, site: int = -1) -> np.ndarray:
        return self._engine.embeddings(node_ids, site=site)

    def down_partitions(self) -> np.ndarray:
        """Health rides the writer's state machine (servers fronting a
        reader recompute DEGRADED/HEALTHY from the same source)."""
        return self._engine.down_partitions()

    @property
    def part_staleness(self) -> np.ndarray:
        return self._engine.part_staleness
