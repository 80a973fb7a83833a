"""Contract registry: trace the real entry points, apply the jaxpr checks.

Each contract builds a *representative* workload — a 96-node skewed-partition
synthetic graph on 4 partitions (skewed so ring buckets are ragged: a
symmetric graph would make the forward and inverted-backward shift censuses
identical and the ring-inversion check vacuous) — traces an entry point with
``jax.make_jaxpr`` (tracing only; nothing executes except the two
budget/serve contracts, which must run to count executables), and diffs the
lowered structure against its :class:`~.jaxpr_checks.ExchangeExpectation`.

Covered entry points (acceptance matrix):

* train_step_sync for GCN/GraphSAGE x dense/compact, simulated + shard_map;
* train_step_async + eval_step (GCN/compact, shard_map);
* the serve sweep (quantized forward + uint8 affected-mask rides);
* the quantize kernel's payload dtypes across the whole bit lattice (RC206);
* recompile budgets: train executables per lattice decision (RC204) and the
  serve single-sweep-executable guarantee from PR 6 (RC207);
* fault-injection transparency: with ``faults=None`` a FaultyBackend-built
  step traces the *identical* program as the plain backend, and two armed
  epochs with different fault masks share one jaxpr (RC208);
* overlap-schedule parity: the ``schedule="overlap"`` step lowers the *same*
  ppermute-per-bucket census and wire dtypes as blocking — the fence
  (``optimization_barrier``) reorders, it must never duplicate or widen an
  exchange — and overlap decisions stay inside the RC204 budget of two
  executables per lattice decision (RC209);
* observability transparency: enabling the span tracer (``repro.obs``)
  traces jaxpr-identical train/serve programs — spans and counters live at
  the host seams, never in the lowered program (RC210).

shard_map contracts need >= 4 devices; with fewer they are *reported as
skipped*, never silently passed (``python -m repro.analysis`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` itself, so the CLI
always runs them on CPU).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..api import partition
from ..core import quantization as qlib
from ..core.sylvie import SylvieConfig
from ..dist.runtime import Runtime
from ..graph import synthetic
from ..models.gnn import blocks as B
from ..models.gnn.models import GCN, GraphSAGE
from ..policy.base import BIT_LATTICE, EpochDecision
from ..train import gnn_step, optimizer as optlib
from ..train.gnn_step import GNNTrainState, make_gnn_steps
from .jaxpr_checks import (ExchangeExpectation, check_exchange_census,
                           check_no_callbacks, check_no_collectives,
                           check_wire_dtypes, summarize)
from .report import Finding

N_PARTS = 4
ARCHS: dict[str, Callable] = {
    "gcn": lambda d_in, d_out: GCN(d_in, 8, d_out, n_layers=2),
    "sage": lambda d_in, d_out: GraphSAGE(d_in, 8, d_out, n_layers=2),
}


def _mesh_ready() -> bool:
    return len(jax.devices()) >= N_PARTS


def _workload(arch: str, layout: str):
    """(model, pg, state, args) for one traced config — skewed partitions so
    every ring bucket has a distinct row count."""
    g = synthetic.planted_partition(n_nodes=96, d_feat=8, seed=0)
    pg = partition(g, N_PARTS, method="skewed", layout=layout, alignment=4)
    model = ARCHS[arch](8, g.n_classes)
    opt = optlib.sgd(1e-1)
    block = B.build_block(
        pg, transposed=getattr(model, "transposed_edges", False))
    state = GNNTrainState.create(model, opt, jax.random.PRNGKey(0),
                                 block.plan, stacked_parts=N_PARTS)
    args = (block, jnp.asarray(pg.x), jnp.asarray(pg.y),
            jnp.asarray(pg.train_mask), jax.random.PRNGKey(1))
    return model, pg, opt, state, args


def _buckets(pg, layout: str) -> Optional[tuple[int, ...]]:
    if layout != "compact":
        return None
    return tuple(int(b) for b in pg.plan.bucket_sizes)


def _train_exp(model, state, pg, layout: str,
               bits: int) -> ExchangeExpectation:
    """Declared comm structure of a train step.

    Forward: one exchange per site. Backward: the site-0 exchange ships raw
    input features for GCN/SAGE, which carry no gradient, so its backward
    exchange is dead-code-eliminated — ``n_sites - 1`` ops. Async steps
    exchange the *gradient caches* instead; the site fed by the features
    consumes its cache as plain data, so the same site drops out there too.
    psums: one per weight-grad leaf (Alg. 2 line 16) + 2 for the masked loss
    (sum, count) + 1 for the site telemetry.
    """
    n_sites = len(model.comm_dims())
    n_leaves = len(jax.tree.leaves(state.params))
    return ExchangeExpectation(
        fwd_ops=n_sites,
        bwd_ops=n_sites - 1,
        bits=bits, buckets=_buckets(pg, layout), psums=n_leaves + 3)


# ---------------------------------------------------------------------------
# contracts (each returns (findings, skipped-notes))
# ---------------------------------------------------------------------------
def contract_train_census(arch: str, layout: str
                          ) -> tuple[list[Finding], list[str]]:
    """RC201/202/203/205 on the shard_map sync train step."""
    where = f"contract:train_sync/{arch}/{layout}/shard_map"
    if not _mesh_ready():
        return [], [f"{where} (needs {N_PARTS} devices)"]
    model, pg, opt, state, args = _workload(arch, layout)
    rt = Runtime.sharded(N_PARTS)
    cfg = SylvieConfig(mode="sync", bits=1, stochastic=False)
    ts, ta, ev = make_gnn_steps(model, cfg, opt, backend=rt.backend)
    ts, _, _ = rt.shard_gnn_steps(ts, ta, ev, state, *args[:1])
    summary = summarize(jax.make_jaxpr(ts)(state, *args))
    exp = _train_exp(model, state, pg, layout, bits=1)
    return (check_exchange_census(summary, exp, where)
            + check_wire_dtypes(summary, exp, where)
            + check_no_callbacks(summary, where)), []


def contract_train_async_census() -> tuple[list[Finding], list[str]]:
    """The async (Sylvie-A) step: cached-halo consumption still lowers to one
    quantized exchange per site per direction, inverted rings in backward."""
    where = "contract:train_async/gcn/compact/shard_map"
    if not _mesh_ready():
        return [], [f"{where} (needs {N_PARTS} devices)"]
    model, pg, opt, state, args = _workload("gcn", "compact")
    rt = Runtime.sharded(N_PARTS)
    cfg = SylvieConfig(mode="async", bits=1, stochastic=False)
    ts, ta, ev = make_gnn_steps(model, cfg, opt, backend=rt.backend)
    _, ta, _ = rt.shard_gnn_steps(ts, ta, ev, state, *args[:1])
    summary = summarize(jax.make_jaxpr(ta)(state, *args))
    exp = _train_exp(model, state, pg, "compact", bits=1)
    return (check_exchange_census(summary, exp, where)
            + check_wire_dtypes(summary, exp, where)
            + check_no_callbacks(summary, where)), []


def contract_eval_census() -> tuple[list[Finding], list[str]]:
    """eval_step: full-precision forward exchange, exactly 2 psums
    (correct, count) — no telemetry, no weight-grad reduce."""
    where = "contract:eval/gcn/compact/shard_map"
    if not _mesh_ready():
        return [], [f"{where} (needs {N_PARTS} devices)"]
    model, pg, opt, state, args = _workload("gcn", "compact")
    rt = Runtime.sharded(N_PARTS)
    cfg = SylvieConfig(mode="sync", bits=1, stochastic=False)
    ts, ta, ev = make_gnn_steps(model, cfg, opt, backend=rt.backend)
    _, _, ev = rt.shard_gnn_steps(ts, ta, ev, state, *args[:1])
    summary = summarize(jax.make_jaxpr(ev)(state.params, *args))
    n_sites = len(model.comm_dims())
    exp = ExchangeExpectation(
        fwd_ops=n_sites, bwd_ops=0, bits=32, buckets=_buckets(pg, "compact"),
        psums=2, wire_dtypes=frozenset({"float32"}))
    return (check_exchange_census(summary, exp, where)
            + check_wire_dtypes(summary, exp, where)
            + check_no_callbacks(summary, where)), []


def contract_simulated_pure(arch: str, layout: str
                            ) -> tuple[list[Finding], list[str]]:
    """The simulated backend compiles the whole stack to one program: zero
    collective primitives, zero callbacks (RC201/RC205)."""
    where = f"contract:train_sync/{arch}/{layout}/simulated"
    model, pg, opt, state, args = _workload(arch, layout)
    rt = Runtime.simulated(N_PARTS)
    cfg = SylvieConfig(mode="sync", bits=1, stochastic=False)
    ts, ta, ev = make_gnn_steps(model, cfg, opt, backend=rt.backend)
    summary = summarize(jax.make_jaxpr(ts)(state, *args))
    return (check_no_collectives(summary, where)
            + check_no_callbacks(summary, where)), []


def contract_serve_census() -> tuple[list[Finding], list[str]]:
    """The serve sweep: per site one quantized forward exchange + one uint8
    affected-mask ride; no psum, no backward, nothing fp32 on the wire."""
    where = "contract:serve_sweep/gcn/compact/shard_map"
    if not _mesh_ready():
        return [], [f"{where} (needs {N_PARTS} devices)"]
    from ..serve.engine import InferenceEngine, ServeConfig
    from ..serve import delta as deltalib
    model, pg, opt, state, args = _workload("gcn", "compact")
    rt = Runtime.sharded(N_PARTS)
    eng = InferenceEngine(model, pg, model.init(jax.random.PRNGKey(0)),
                          config=ServeConfig(bits=1), runtime=rt)
    masks = deltalib.plan_full(pg, eng.n_sites).device_masks()
    summary = summarize(jax.make_jaxpr(eng._sweep)(
        eng.params, eng.block, eng.x, eng._halos, masks,
        jax.random.PRNGKey(2)))
    exp = ExchangeExpectation(
        fwd_ops=eng.n_sites, bwd_ops=0, bits=1,
        buckets=_buckets(pg, "compact"), mask_ops=eng.n_sites, psums=0)
    return (check_exchange_census(summary, exp, where)
            + check_wire_dtypes(summary, exp, where)
            + check_no_callbacks(summary, where)), []


def contract_quantize_payload() -> tuple[list[Finding], list[str]]:
    """RC206: across the whole bit lattice the quantize kernel's wire payload
    is uint8 (packed to ``packed_width`` bytes) with scale_dtype error
    compensation — passthrough widths keep bf16/f32 and ship no scale."""
    where = "contract:quantize_payload"
    findings = []
    h = jax.ShapeDtypeStruct((N_PARTS, 24, 16), jnp.float32)
    for bits in BIT_LATTICE:
        qt = jax.eval_shape(
            lambda x, b=bits: qlib.quantize(x, b, jax.random.PRNGKey(0),
                                            stochastic=False), h)
        if bits >= 16:
            want = "bfloat16" if bits == 16 else "float32"
            if qt.data.dtype.name != want or qt.scale.size:
                findings.append(Finding(
                    code="RC206", where=where,
                    message=f"bits={bits} passthrough must ship {want} with "
                    f"empty scale, got {qt.data.dtype.name} + scale shape "
                    f"{qt.scale.shape}"))
            continue
        want_w = qlib.packed_width(16, bits)
        if qt.data.dtype.name != "uint8" or qt.data.shape[-1] != want_w:
            findings.append(Finding(
                code="RC206", where=where,
                message=f"bits={bits} payload must be uint8 packed to "
                f"{want_w} bytes/row, got {qt.data.dtype.name} "
                f"shape {qt.data.shape}"))
        if qt.scale.dtype.name != "bfloat16":
            findings.append(Finding(
                code="RC206", where=where,
                message=f"bits={bits} scale must be bfloat16 (wire-cheap "
                f"error compensation), got {qt.scale.dtype.name}"))
    return findings, []


def contract_recompile_budget() -> tuple[list[Finding], list[str]]:
    """RC204: the executable budget. One compiled program per (step flavor,
    lattice decision) — re-invoking a built step must hit the jit cache, so
    K distinct decisions trace exactly K sync + K async executables. This is
    the static generalization of tests/test_policy's TRACE_LOG assertions to
    a *declared* budget."""
    where = "contract:recompile_budget/train"
    model, pg, opt, state, args = _workload("gcn", "compact")
    rt = Runtime.simulated(N_PARTS)
    cfg = SylvieConfig(mode="async", bits=1, stochastic=False)
    n_sites = len(model.comm_dims())
    decisions = [EpochDecision.uniform(n_sites, bits=b, stochastic=False)
                 for b in (1, 2)]
    budget = 2 * len(decisions)   # sync + async per lattice point
    base = len(gnn_step.TRACE_LOG)
    for d in decisions:
        ts, ta, ev = make_gnn_steps(model, cfg, opt, backend=rt.backend,
                                    decision=d)
        ts, ta, _ = rt.shard_gnn_steps(ts, ta, ev, state, *args[:1])
        for _ in range(2):        # second call must reuse the executable
            st2, _ = ts(state, *args)
            st2, _ = ta(st2, *args)
    traced = len(gnn_step.TRACE_LOG) - base
    if traced != budget:
        return [Finding(
            code="RC204", where=where,
            message=f"recompile budget exceeded: {len(decisions)} lattice "
            f"decisions x (sync+async) x 2 invocations must trace exactly "
            f"{budget} executables, traced {traced}")], []
    return [], []


def contract_serve_one_executable() -> tuple[list[Finding], list[str]]:
    """RC207: PR 6's claim, verified instead of trusted — a full sweep and a
    delta refresh are served by ONE traced sweep executable (the affected
    masks ride as data), and the jaxprs traced with full vs delta mask values
    are structurally identical."""
    where = "contract:serve_one_executable"
    import numpy as np
    from ..serve import delta as deltalib, engine as englib
    model, pg, opt, state, args = _workload("gcn", "compact")
    eng = englib.InferenceEngine(model, pg, model.init(jax.random.PRNGKey(0)),
                                 config=englib.ServeConfig(bits=1),
                                 runtime=Runtime.simulated(N_PARTS))
    findings = []
    base = len(englib.TRACE_LOG)
    eng.full_sweep()
    eng.refresh(np.array([0]), np.zeros((1, 8), np.float32))
    eng.full_sweep()
    traced = len(englib.TRACE_LOG) - base
    if traced != 1:
        findings.append(Finding(
            code="RC204", where=where,
            message=f"full sweep + delta refresh + full sweep must share one "
            f"traced executable, traced {traced}"))
    full = deltalib.plan_full(pg, eng.n_sites).device_masks()
    part = eng._frontier.plan_refresh(np.array([0]),
                                      eng.n_sites).device_masks()
    key = jax.random.PRNGKey(3)
    trace = jax.make_jaxpr(lambda m: eng._sweep(
        eng.params, eng.block, eng.x, eng._halos, m, key))
    if str(trace(full)) != str(trace(part)):
        findings.append(Finding(
            code="RC207", where=where,
            message="jaxpr traced with the all-rows mask differs from the "
            "delta-frontier mask trace — the masks are influencing program "
            "structure instead of riding as data"))
    return findings, []


def contract_fault_transparency() -> tuple[list[Finding], list[str]]:
    """RC208: fault injection must be invisible to the compiler. Two halves:

    (a) fault-free transparency — a train step built against a
        ``FaultyBackend`` wrapper, invoked with ``faults=None``, traces a
        jaxpr *string-identical* to the plain-backend step (zero extra traced
        executables when no chaos is armed);
    (b) masks-as-data — the armed step traces the same jaxpr for two epochs
        with *different* fault sets (the masks ride in
        ``GNNTrainState.faults``; fault values never shape the program).
    """
    import dataclasses
    import re

    from ..faults import FaultCtl, FaultPlan, FaultyBackend, RowGeometry

    def canon(fn, st):
        # jaxpr pretty-printing embeds repr()s of custom_vjp thunks, which
        # carry object addresses; strip them so only structure is compared.
        return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(st, *args)))

    where = "contract:fault_transparency"
    model, pg, opt, state, args = _workload("gcn", "compact")
    rt = Runtime.simulated(N_PARTS)
    plan = FaultPlan(seed=3, drop_rate=0.2, corrupt_rate=0.1)
    faulty = FaultyBackend(rt.backend, plan)
    findings: list[Finding] = []
    for mode in ("sync", "async"):
        cfg = SylvieConfig(mode=mode, bits=1, stochastic=False)
        ts_p, ta_p, _ = make_gnn_steps(model, cfg, opt, backend=rt.backend)
        ts_f, ta_f, _ = make_gnn_steps(model, cfg, opt, backend=faulty)
        step_p = ts_p if mode == "sync" else ta_p
        step_f = ts_f if mode == "sync" else ta_f
        if canon(step_p, state) != canon(step_f, state):
            findings.append(Finding(
                code="RC208", where=f"{where}/{mode}",
                message="FaultyBackend with faults=None traces a different "
                "program than the plain backend — the fault path leaks into "
                "the fault-free trace"))
        # (b) two different armed epochs must share one jaxpr
        geom = RowGeometry.from_plan(args[0].plan)
        n_sites = len(model.comm_dims())
        ctls = [FaultCtl.expand(plan.events(e, n_sites, N_PARTS), geom,
                                n_sites) for e in (1, 2)]
        traces = [canon(step_f, dataclasses.replace(state, faults=c))
                  for c in ctls]
        if traces[0] != traces[1]:
            findings.append(Finding(
                code="RC208", where=f"{where}/{mode}/armed",
                message="two epochs with different fault masks trace "
                "different jaxprs — fault events are shaping program "
                "structure instead of riding as data"))
    return findings, []


def contract_overlap_census() -> tuple[list[Finding], list[str]]:
    """RC209(a): the overlap schedule is *census-identical* to blocking. The
    issue/land split reorders work around the collective; it must not add,
    drop, widen, or re-route a single exchange. So the shard_map sync step
    traced with ``schedule="overlap"`` must pass the exact
    :class:`ExchangeExpectation` the blocking step is held to (same bucket
    multiset, same ring inversion, same wire dtypes, same psum count) — plus
    at least one ``optimization_barrier`` eqn, the fence that pins the land
    after the issue."""
    where = "contract:overlap_census/gcn/compact/shard_map"
    if not _mesh_ready():
        return [], [f"{where} (needs {N_PARTS} devices)"]
    model, pg, opt, state, args = _workload("gcn", "compact")
    rt = Runtime.sharded(N_PARTS)
    cfg = SylvieConfig(mode="sync", bits=1, stochastic=False,
                       schedule="overlap")
    ts, ta, ev = make_gnn_steps(model, cfg, opt, backend=rt.backend)
    ts, _, _ = rt.shard_gnn_steps(ts, ta, ev, state, *args[:1])
    summary = summarize(jax.make_jaxpr(ts)(state, *args))
    exp = _train_exp(model, state, pg, "compact", bits=1)
    findings = (check_exchange_census(summary, exp, where)
                + check_wire_dtypes(summary, exp, where)
                + check_no_callbacks(summary, where))
    if not summary.count("optimization_barrier"):
        findings.append(Finding(
            code="RC209", where=where,
            message="overlap-schedule step lowers no optimization_barrier — "
            "without the fence the land is free to fold back into the issue "
            "and the schedule silently degenerates to blocking"))
    return findings, []


def contract_overlap_budget() -> tuple[list[Finding], list[str]]:
    """RC209(b): overlap decisions obey the RC204 budget — one executable per
    (step flavor, decision), so a blocking + an overlap decision trace exactly
    2 sync + 2 async executables across repeated invocations (the schedule is
    part of ``EpochDecision.step_key()``; it must not retrace per call)."""
    where = "contract:overlap_budget/train"
    model, pg, opt, state, args = _workload("gcn", "compact")
    rt = Runtime.simulated(N_PARTS)
    cfg = SylvieConfig(mode="async", bits=1, stochastic=False)
    n_sites = len(model.comm_dims())
    decisions = [EpochDecision.uniform(n_sites, bits=1, stochastic=False,
                                       schedule=s)
                 for s in ("blocking", "overlap")]
    budget = 2 * len(decisions)
    base = len(gnn_step.TRACE_LOG)
    for d in decisions:
        ts, ta, ev = make_gnn_steps(model, cfg, opt, backend=rt.backend,
                                    decision=d)
        ts, ta, _ = rt.shard_gnn_steps(ts, ta, ev, state, *args[:1])
        for _ in range(2):        # second call must reuse the executable
            st2, _ = ts(state, *args)
            st2, _ = ta(st2, *args)
    traced = len(gnn_step.TRACE_LOG) - base
    if traced != budget:
        return [Finding(
            code="RC209", where=where,
            message=f"overlap recompile budget exceeded: blocking + overlap "
            f"decisions x (sync+async) x 2 invocations must trace exactly "
            f"{budget} executables, traced {traced}")], []
    return [], []


def contract_obs_transparency() -> tuple[list[Finding], list[str]]:
    """RC210: observability must be compiler-invisible. The span tracer and
    metrics counters live at the host seams (the same trace-time seams as the
    TRACE_LOG appends); enabling tracing must not add, drop, or reorder a
    single eqn. Checked by canon-comparing (hex addresses stripped) the
    jaxprs of the sync + async train steps (``schedule="overlap"``; their
    traced bodies hold the TRACE_LOG seams, which emit ``retrace`` events)
    and the serve sweep, traced with the tracer disabled vs enabled on a
    FakeClock."""
    import re

    from .. import obs
    from ..serve import delta as deltalib, engine as englib

    where = "contract:obs_transparency"
    model, pg, opt, state, args = _workload("gcn", "compact")
    rt = Runtime.simulated(N_PARTS)
    cfg = SylvieConfig(mode="sync", bits=1, stochastic=False,
                       schedule="overlap")
    acfg = SylvieConfig(mode="async", bits=1, stochastic=False,
                        schedule="overlap")
    key = jax.random.PRNGKey(2)

    def canon(jaxpr):
        # jaxpr pretty-printing embeds repr()s of custom_vjp thunks with
        # object addresses; strip them so only structure is compared.
        return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))

    def snapshot() -> dict[str, str]:
        # fresh step functions + a fresh engine per pass: the steps are
        # jitted, so reusing them would serve the second trace from the jit
        # cache without ever re-running the instrumented python bodies
        ts, _, _ = make_gnn_steps(model, cfg, opt, backend=rt.backend)
        _, ta, _ = make_gnn_steps(model, acfg, opt, backend=rt.backend)
        eng = englib.InferenceEngine(
            model, pg, model.init(jax.random.PRNGKey(0)),
            config=englib.ServeConfig(bits=1), runtime=rt)
        masks = deltalib.plan_full(pg, eng.n_sites).device_masks()
        return {
            "train_sync": canon(jax.make_jaxpr(ts)(state, *args)),
            "train_async": canon(jax.make_jaxpr(ta)(state, *args)),
            "serve_sweep": canon(jax.make_jaxpr(eng._sweep)(
                eng.params, eng.block, eng.x, eng._halos, masks, key)),
        }

    was_on = obs.enabled()
    try:
        obs.disable()
        off = snapshot()
        obs.enable(obs.FakeClock())
        on = snapshot()
        obs.drain()               # discard the trace-time events we provoked
    finally:
        if was_on:
            obs.enable()
        else:
            obs.disable()
    return [Finding(
        code="RC210", where=f"{where}/{k}",
        message="enabling the span tracer changes the traced program — "
        "instrumentation is leaking ops into the jaxpr instead of staying "
        "at the host seams")
        for k in off if off[k] != on[k]], []


# ---------------------------------------------------------------------------
# registry + driver
# ---------------------------------------------------------------------------
CONTRACTS: dict[str, Callable[[], tuple[list[Finding], list[str]]]] = {
    **{f"train_sync/{a}/{lay}/shard_map":
       (lambda a=a, lay=lay: contract_train_census(a, lay))
       for a in ARCHS for lay in ("compact", "dense")},
    **{f"train_sync/{a}/{lay}/simulated":
       (lambda a=a, lay=lay: contract_simulated_pure(a, lay))
       for a in ARCHS for lay in ("compact", "dense")},
    "train_async/gcn/compact/shard_map": contract_train_async_census,
    "eval/gcn/compact/shard_map": contract_eval_census,
    "serve_sweep/gcn/compact/shard_map": contract_serve_census,
    "quantize_payload": contract_quantize_payload,
    "recompile_budget/train": contract_recompile_budget,
    "serve_one_executable": contract_serve_one_executable,
    "fault_transparency": contract_fault_transparency,
    "overlap_census/gcn/compact/shard_map": contract_overlap_census,
    "overlap_budget/train": contract_overlap_budget,
    "obs_transparency": contract_obs_transparency,
}


def run_contracts(only: Optional[list[str]] = None
                  ) -> tuple[list[Finding], list[str]]:
    """Run every registered contract (or the named subset). Returns
    (findings, skipped-notes); a contract that *errors* is itself a finding
    (RC200) — a broken checker must fail CI, not pass it."""
    findings: list[Finding] = []
    skipped: list[str] = []
    for name, fn in CONTRACTS.items():
        if only is not None and name not in only:
            continue
        try:
            got, skip = fn()
        except Exception as e:  # noqa: BLE001 - surfaced as a finding
            findings.append(Finding(
                code="RC200", where=f"contract:{name}",
                message=f"contract raised {type(e).__name__}: {e}"))
            continue
        findings.extend(got)
        skipped.extend(skip)
    return findings, skipped
