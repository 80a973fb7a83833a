"""The benchmark harness: one cell, one seed, one run.

Everything is found by name. ``BENCHMARK.json`` names the cell's
configuration, traffic mix and chips; ``bench/configs/<config>.json`` holds
the graph, model and partitioning, ``bench/models/<arch>.py`` the weights and
the plain reference layer, ``bench/traffic/<mix>.json`` the communication
mode, ``bench/limits/<cell>.json`` the limits of the correctness check, and
``bench/metrics/<metric>.py`` one reader per per-layer metric.

A run builds the trainer the way the program's launcher does (generate,
``gcn_normalize``, ``partition_graph``, ``GNNTrainer``), hands it the
benchmark's weights, drives its first epochs (epoch 0 is synchronous, then one
of every step the window uses), times ``train_epoch`` in a loop for
``--seconds``, reads what a chip holds for the compiled steps, and then checks the first three
epochs against the plain reference (``bench/reference.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import check, counts, graphgen

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
CHECK_STEPS = 3
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict
    per_layer: list           # the per-layer metric entries this cell reports
    end_to_end: list
    bench_dir: Path = BENCH   # where traffic, limits and metrics are found


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, benchmark: dict, bench_dir: Path = BENCH) -> Cell:
    work = {w["name"]: w for w in benchmark["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in benchmark["configs"]}[w["config"]]
    config = _load(REPO / conf["file"])        # an absolute path stays as it is

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, config, _load(bench_dir / "traffic" / f"{w['traffic']}.json"),
                int(w["chips"]), _load(bench_dir / "limits" / f"{name}.json"),
                [m for m in benchmark["per_layer"] if applies(m)],
                [m for m in benchmark["end_to_end"] if applies(m)], bench_dir)


def seeds(seed: int) -> tuple[int, int]:
    """(weights seed, trainer seed) from the run's seed, each in 31 bits."""
    words = np.random.SeedSequence(seed % (1 << 64)).generate_state(2)
    return int(words[0]) & 0x7FFFFFFF, int(words[1]) & 0x7FFFFFFF


def dims_of(cell: Cell) -> tuple[int, ...]:
    c = cell.config
    return ((c["d_feat"],) + (c["d_hidden"],) * (c["n_layers"] - 1)
            + (c["n_classes"],))


# ---------------------------------------------------------------------------
# building the system under test
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Built:
    graph: graphgen.Graph
    trainer: object
    params0: object           # host copy of the benchmark's weights
    trainer_seed: int


def make_weights(arch: str, dims, weights_seed: int):
    import jax
    mod = importlib.import_module(f"bench.models.{arch}")
    return jax.jit(lambda k: mod.init(k, dims))(jax.random.PRNGKey(weights_seed))


def sylvie_config(traffic: dict):
    import jax.numpy as jnp
    from repro.core.sylvie import SylvieConfig
    return SylvieConfig(mode=traffic["mode"], bits=traffic["bits"],
                        stochastic=traffic["stochastic"],
                        scale_dtype=getattr(jnp, traffic["scale_dtype"]),
                        quant_impl=traffic["quant_impl"],
                        schedule=traffic["schedule"])


def make_policy(traffic: dict):
    """``{"class": "Uniform"}`` is the trainer's default; any other class of
    ``repro.policy`` is built from the remaining keys."""
    spec = dict(traffic["policy"])
    cls = spec.pop("class")
    if cls == "Uniform" and not spec:
        return None
    import repro.policy as P
    return getattr(P, cls)(**spec)


def build(cell: Cell, seed: int, graph: graphgen.Graph | None = None) -> Built:
    import dataclasses as dc

    import jax
    from repro.dist.runtime import Runtime
    from repro.graph import formats, partition
    from repro.train import optimizer as optlib
    from repro.train.trainer import GNNTrainer

    c = cell.config
    g = graph if graph is not None else graphgen.generate(c)
    pg_graph = formats.Graph(
        g.n_nodes, np.stack([g.src, g.dst]).astype(np.int32), g.x, g.y,
        g.train_mask, g.val_mask, g.test_mask, n_classes=g.n_classes)
    pg_graph, ew = formats.gcn_normalize(pg_graph)
    pg = partition.partition_graph(pg_graph, c["parts"], edge_weight=ew,
                                   layout=c["halo_layout"],
                                   alignment=c["halo_alignment"])
    arch = importlib.import_module(f"bench.models.{c['arch']}")
    model = arch.program_model(c["d_feat"], c["d_hidden"], c["n_classes"],
                               c["n_layers"])
    runtime = (Runtime.simulated(c["parts"]) if c["runtime"] == "simulated"
               else Runtime.sharded(c["parts"]))
    w_seed, t_seed = seeds(seed)
    tr = GNNTrainer(model, pg, sylvie_config(cell.traffic),
                    opt=optlib.adam(c["lr"]), policy=make_policy(cell.traffic),
                    runtime=runtime, seed=t_seed)
    params = make_weights(c["arch"], dims_of(cell), w_seed)
    tr.state = dc.replace(tr.state, params=params,
                          opt_state=tr.opt.init(params))
    tr.state, tr.block, _ = tr.runtime.device_put_gnn(tr.state, tr.block, ())
    return Built(g, tr, jax.device_get(params), t_seed)


def program_readings(tr, params0, steps: int = CHECK_STEPS) -> dict:
    """Drive the first ``steps`` epochs through ``train_epoch`` and keep what
    the check compares: each loss, the first gradient (Adam's first moment
    after one step, divided by ``1 - b1``), the parameter change, and the
    halo features each site received in the first step (the trainer's halo
    caches, ``(P, rows, d)`` receive buffers)."""
    import jax
    losses, grad, halo = [], None, None
    for _ in range(steps):
        losses.append(tr.train_epoch().loss)
        if grad is None:
            m = jax.device_get(tr.state.opt_state["m"])
            grad = jax.tree.map(lambda a: np.asarray(a, np.float64) / 0.1, m)
            halo = [np.asarray(c) for c in jax.device_get(tr.state.halo.feats)]
    after = jax.device_get(tr.state.params)
    return {"losses": losses, "grad": check.leaves(grad),
            "change": check.leaf_norms(check.change(after, params0)),
            "halo": halo}


def reference_readings(cell: Cell, g: graphgen.Graph, params0,
                       trainer_seed: int, dtype="float32", fault: str = "",
                       steps: int = CHECK_STEPS) -> dict:
    import jax
    import jax.numpy as jnp

    from . import reference as R
    c, t = cell.config, cell.traffic
    plan = R.build_plan(g, c["parts"], c["halo_alignment"])
    setting = R.Setting(mode=t["mode"], bits=t["bits"] if t["mode"] != "vanilla"
                        else 32, stochastic=t["stochastic"],
                        scale_dtype=t["scale_dtype"],
                        per_chip=c["runtime"] != "simulated", lr=c["lr"])
    ref = R.Reference(c["arch"], dims_of(cell), plan, g, setting,
                      dtype=getattr(jnp, dtype), fault=fault,
                      precision=c["matmul_precision"])
    losses, grad, after, halo = ref.run(jax.tree.map(jnp.asarray, params0),
                                        jax.random.PRNGKey(trainer_seed), steps)
    return {"losses": losses, "grad": check.leaves(grad),
            "change": check.leaf_norms(check.change(after, params0)),
            "halo": halo, "halo_at": (plan.halo_recv, plan.halo_row)}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts the programs JAX builds (``backend_compile_duration`` fires for
    a compile and for a load from the persistent cache alike), the cache hits
    among them, and traces."""

    def __init__(self):
        import jax
        self.programs = 0
        self.hits = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.programs - self.hits


def require_chips(chips: int):
    """The devices of the cell, or SystemExit where JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench/run.py needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def annotate_trainer(tr) -> None:
    """Open a host span around the trainer's per-epoch host work, so that
    device idle gaps can be named after what the host was doing."""
    import jax
    for name in ("_decide", "_steps_for", "_absorb_site_stats",
                 "comm_bytes_per_epoch", "_epoch_key"):
        fn = getattr(tr, name)

        def wrapped(*a, _fn=fn, _name=name, **k):
            with jax.profiler.TraceAnnotation(f"trainer.{_name}"):
                return _fn(*a, **k)
        setattr(tr, name, wrapped)


def window(tr, seconds: float):
    """``train_epoch`` in a loop; counts the epochs that end inside the
    window. Returns (epoch_s, counted epochs, all epochs run)."""
    import jax
    run = []
    counted = []
    t0 = time.perf_counter()
    last = t0
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while True:
            with jax.profiler.TraceAnnotation("bench.epoch"):
                m = tr.train_epoch()
            t = time.perf_counter()
            run.append(m)
            if t - t0 > seconds:
                break
            counted.append(m)
            last = t
    if not counted:
        counted, last = run[:1], t
    return (last - t0) / len(counted), counted, run


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader gets."""
    cell: Cell
    chips: int
    epochs: list              # EpochMetrics of every epoch the window ran
    epoch_s: float
    trace: object             # bench.trace.Trace or None
    index: dict               # bench.trace.hlo_index of the compiled steps
    counts: dict
    peak: dict

    def ops(self):
        """(device, op, instruction or None) for every traced operation."""
        from . import trace as T
        for dev, d in sorted(self.trace.devices.items()):
            for op in d.ops:
                yield dev, op, T.lookup(self.index, op)

    def per_epoch_device_s(self, pred) -> float | None:
        """Seconds per traced epoch, mean over devices, of the operations
        ``pred(op, instr)`` selects; None where it selects none."""
        if self.trace is None:
            return None
        tot, hit = 0.0, False
        for _, op, ins in self.ops():
            if pred(op, ins):
                tot += op.dur
                hit = True
        if not hit:
            return None
        return tot / max(len(self.trace.devices), 1) / max(len(self.epochs), 1)


def load_reader(bench_dir: Path, name: str):
    """The module ``<bench_dir>/metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name}", bench_dir / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(entries, record: RunRecord) -> dict:
    out = {}
    for m in entries:
        v = load_reader(record.cell.bench_dir, m["name"]).read(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(record: RunRecord) -> dict:
    from collections import defaultdict
    by = defaultdict(float)
    for _, op, ins in record.ops():
        label = op.name
        if ins is not None:
            kind = ins.target or ins.root_opcode or ins.opcode
            label = f"{op.name} [{ins.opcode}:{kind}] {ins.op_name[-60:]}"
        by[label] += op.dur
    n = max(len(record.trace.devices), 1)
    ops = sorted(((k, v / n) for k, v in by.items()), key=lambda kv: -kv[1])
    gaps = sorted(((dur, s, dev) for dev, d in record.trace.devices.items()
                   for s, dur in d.gaps), reverse=True)[:10]
    return {"device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": [[f"tpu{dev}: {record.trace.gap_cause(s, dur)}", dur]
                          for dur, s, dev in gaps]}


def compiled_steps(tr) -> list:
    """The compiled steps the window ran (sync and, if used, async), lowered
    as the trainer calls them; the programs come from the compile cache."""
    modes = {m.mode for m in tr.history[CHECK_STEPS:]} or {"sync"}
    ts, ta = tr._steps_for(tr._last_decision or tr._decide())
    args = (tr.state, tr.block, tr.x, tr.y, tr.train_mask, tr._epoch_key())
    return [(ts if mode == "sync" else ta).lower(*args).compile()
            for mode in sorted(modes)]


def footprint(compiled) -> int:
    """Bytes one chip holds while the compiled program runs: its arguments,
    its outputs that do not reuse an argument's buffer, and its temporaries,
    as the compiler's ``memory_analysis()`` gives them."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, devices) -> dict:
    import jax

    from . import trace as T

    counter = CompileCounter()
    built = build(cell, seed)
    tr = built.trainer
    prog = program_readings(tr, built.params0)
    setup_compiles = counter.compiled
    setup_s = time.perf_counter() - t_start
    print(f"setup: {setup_s:.3f} s, {counter.programs} programs, "
          f"{counter.hits} from the compile cache, {setup_compiles} compiled "
          f"({'cold' if setup_compiles else 'warm'})", flush=True)

    c0, t0 = counter.programs, counter.traces
    tdir = None
    if traced:
        annotate_trainer(tr)
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
    try:
        epoch_s, epochs, ran = window(tr, seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    w_compiles, w_traces = counter.programs - c0, counter.traces - t0
    print(f"window: {len(epochs)} epochs counted of {len(ran)} run, "
          f"{w_compiles} programs built and {w_traces} traces inside the "
          "window", flush=True)
    runtime_peak = memory_peak(devices)
    steps = compiled_steps(tr)
    step_bytes = max(footprint(c) for c in steps)
    print(f"memory: {step_bytes} bytes a chip for the largest step, "
          f"{runtime_peak} bytes runtime peak", flush=True)
    failed = sum(not math.isfinite(m.loss) for m in ran)

    metrics = {}
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(step_bytes, runtime_peak)}
    result_extra = {}
    if traced:
        try:
            tr_red = T.reduce(tdir, WINDOW_SPAN,
                              devices={d.id for d in devices})
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        rec = RunRecord(cell, len(devices), ran, epoch_s, tr_red,
                        T.hlo_index([c.as_text() for c in steps]),
                        cell_counts(cell, built.graph),
                        load_peaks(dev0.device_kind))
        metrics = read_metrics(cell.per_layer, rec)
        busy = [d.busy_s for d in tr_red.devices.values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = tr_red.window_s
        result_extra["breakdown"] = breakdown(rec)
    else:
        e2e = {"epoch_s": epoch_s, "peak_hbm_gb": step_bytes / 1e9,
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the program's state goes before the reference takes the chip
    g, params0, t_seed = built.graph, built.params0, built.trainer_seed
    del tr, built, steps
    gc.collect()
    ref = reference_readings(cell, g, params0, t_seed)
    values = check.readings(prog, ref)
    correct = failed == 0 and check.verdict(values, cell.limits)
    print(f"losses program {prog['losses']!r} reference {ref['losses']!r}",
          file=sys.stderr)
    for k in cell.limits:
        print(f"check {k}: {values[k]!r} (limit {cell.limits[k]!r})",
              file=sys.stderr)
    return {"correct": bool(correct), "attempted": len(ran), "failed": failed,
            "metrics": metrics, "device": device, **result_extra,
            "setup_compiles": setup_compiles,
            "window_compiles": w_compiles,
            "check": {k: {"value": values[k], "limit": cell.limits[k]}
                      for k in cell.limits}}


def cell_counts(cell: Cell, g: graphgen.Graph) -> dict:
    from . import reference as R
    c, t = cell.config, cell.traffic
    plan = R.build_plan(g, c["parts"], c["halo_alignment"])
    dims = dims_of(cell)
    bits = 32 if t["mode"] == "vanilla" else t["bits"]
    return {"flops_per_epoch": counts.flops_per_epoch(
                c["arch"], plan.n, int(plan.dst.shape[0]), dims),
            "lowbit_bytes_per_epoch": counts.lowbit_bytes_per_epoch(
                counts.halo_rows(plan), dims[:-1], bits, t["scale_dtype"]),
            "halo_rows": counts.halo_rows(plan)}


def load_peaks(kind: str) -> dict:
    table = _load(BENCH / "peaks.json")
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Sylvie on-chip benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = find_cell(args.workload, _load(REPO / "BENCHMARK.json"))
    devices = require_chips(cell.chips)
    print(f"compile cache: {use_compile_cache()}", flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devices)
    print(json.dumps(result), flush=True)
    return 0
