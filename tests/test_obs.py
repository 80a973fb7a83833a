"""repro.obs: span tracer, metrics registry, exporters, CLI, instrumentation.

Four layers of evidence:

* the tracer itself — disabled calls return the shared null span (no
  allocation, no clock read) with or without the profiler sink, enabled spans
  nest/thread/sort, FakeClock makes every timestamp deterministic;
* the metrics registry — typed instruments, in-place reset, and the TraceLog
  shim keeping full list semantics while counting ``retrace.<scope>``;
* the exporters — Perfetto trace JSON and metrics JSON round-trip, the CLI
  renders all three subcommands and exit-codes its failures;
* the instrumented layers — the trainer emits ``epoch > decide``,
  ``epoch > build``, ``epoch > step > dispatch | readback.loss`` and
  ``epoch > readback.stats`` spans and per-epoch ``wall_s``, on the obs
  tracer and in the JAX profiler's trace; its compiled steps keep the
  ``aggregation``, ``exchange`` and ``lowbit`` named scopes in their
  ``op_name`` metadata; the server emits request-path spans and rejection
  counters, the store counts hits/miss-bytes, and ``open_loop`` under a
  FakeClock is fully deterministic (identical reports, no wall waits).
"""
import glob
import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import export as ox

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
CLI_ENV = {**os.environ,
           "PYTHONPATH": os.path.join(ROOT, "src")
           + os.pathsep + os.environ.get("PYTHONPATH", "")}


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends untraced with zeroed metrics."""
    obs.disable()
    obs.reset_metrics()
    yield
    obs.disable()
    obs.reset_metrics()


# ---------------------------------------------------------------------------
# spans: null path, nesting, FakeClock, threads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sink", [False, True])
def test_disabled_tracer_is_allocation_free(sink):
    """With no tracer armed and no profiler recording, a span is the shared
    null singleton, whether the profiler sink is installed or not."""
    from repro.obs import profiler
    prev = obs.set_sink(None)
    try:
        if sink:
            profiler.install()
        assert not obs.enabled() and obs.current() is None
        # the hot-path contract: one shared singleton, never a fresh object
        assert obs.span("epoch") is obs.NULL_SPAN
        assert obs.span("epoch", {"k": 1}) is obs.NULL_SPAN
        obs.event("retrace", {"scope": "train"})    # no-op, no error
        assert obs.drain() == []
    finally:
        obs.set_sink(prev)


def test_fake_clock_semantics():
    c = obs.FakeClock(start=10.0, tick=0.5)
    assert c() == 10.0 and c() == 10.5          # tick auto-advances per read
    c.sleep(2.0)
    assert c() == 13.0
    c.sleep(-1.0)                               # negative sleep never rewinds
    assert c() == 13.5
    c.advance(0.25)
    assert c() == 14.25


def test_spans_nest_and_events_interleave():
    obs.enable(obs.FakeClock(tick=1.0))
    with obs.span("epoch", {"epoch": 0}):       # enter @0
        with obs.span("step"):                  # enter @1, exit @2
            pass
        obs.event("retrace", {"scope": "train"})  # @3
    ev = obs.drain()                            # epoch exit @4
    assert [(e["name"], e["ph"]) for e in ev] == \
        [("epoch", "X"), ("step", "X"), ("retrace", "i")]
    epoch, step, mark = ev
    assert epoch["ts"] == 0.0 and epoch["dur"] == 4.0
    assert step["ts"] == 1.0 and step["dur"] == 1.0
    assert mark["ts"] == 3.0
    assert epoch["args"] == {"epoch": 0} and "args" not in step
    assert obs.drain() == []                    # drain clears the buffers


def test_span_records_even_when_body_raises():
    obs.enable(obs.FakeClock(tick=1.0))
    with pytest.raises(RuntimeError):
        with obs.span("step"):
            raise RuntimeError("boom")
    ev = obs.drain()
    assert [e["name"] for e in ev] == ["step"]  # recorded, not swallowed


def test_thread_buffers_merge_time_sorted():
    clock = obs.FakeClock(tick=0.125)
    obs.enable(clock)

    barrier = threading.Barrier(3)              # all alive at once, so thread
                                                # idents cannot be reused
    def emit(tag):
        barrier.wait()
        for i in range(5):
            obs.event(tag, {"i": i})
        barrier.wait()

    threads = [threading.Thread(target=emit, args=(f"t{k}",))
               for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    obs.event("main")
    ev = obs.drain()
    assert len(ev) == 16
    assert [e["ts"] for e in ev] == sorted(e["ts"] for e in ev)
    assert len({e["tid"] for e in ev}) == 4     # one buffer per thread


# ---------------------------------------------------------------------------
# metrics registry + TraceLog shim
# ---------------------------------------------------------------------------
def test_registry_instruments_and_reset_in_place():
    obs.count("faults.injected", 3)
    obs.count("faults.injected")
    obs.gauge("queue.depth").set(7)
    obs.observe("step.seconds", 2.0)
    obs.observe("step.seconds", 4.0)
    snap = obs.snapshot()
    assert snap["counters"]["faults.injected"] == 4
    assert snap["gauges"]["queue.depth"] == 7
    h = snap["histograms"]["step.seconds"]
    assert h == {"count": 2, "sum": 6.0, "min": 2.0, "max": 4.0, "mean": 3.0}
    obs.reset_metrics()
    snap = obs.snapshot()
    # names survive a reset with zeroed values: a zero is evidence the seam
    # ran and saw nothing, absence is not
    assert snap["counters"]["faults.injected"] == 0
    assert snap["histograms"]["step.seconds"]["count"] == 0


def test_tracelog_keeps_list_semantics_and_counts_retraces():
    log = obs.TraceLog("train")
    assert log == [] and len(log) == 0
    log.append("sync")
    log.append("async")
    assert list(log) == ["sync", "async"] and log[-1] == "async"
    assert obs.snapshot()["counters"]["retrace.train"] == 2
    log.clear()
    assert len(log) == 0                        # clear() is plain list.clear
    assert obs.snapshot()["counters"]["retrace.train"] == 2
    obs.enable(obs.FakeClock())
    log.append("sync")
    ev = obs.drain()
    assert [e["name"] for e in ev] == ["retrace"]
    assert ev[0]["args"] == {"scope": "train", "tag": "sync"}


def test_production_trace_logs_are_shims():
    from repro.serve import engine as englib
    from repro.train import gnn_step
    assert isinstance(gnn_step.TRACE_LOG, obs.TraceLog)
    assert isinstance(englib.TRACE_LOG, obs.TraceLog)
    assert isinstance(gnn_step.TRACE_LOG, list)   # contracts count via len()


# ---------------------------------------------------------------------------
# exporters: trace JSON, metrics JSON, renderers
# ---------------------------------------------------------------------------
def _sample_events():
    obs.enable(obs.FakeClock(tick=0.001))
    with obs.span("epoch", {"epoch": 0}):
        with obs.span("step"):
            pass
        obs.event("retrace", {"scope": "train"})
    return obs.drain()


def test_trace_roundtrip_is_perfetto_shaped(tmp_path):
    path = ox.write_trace(tmp_path / "deep" / "run.trace.json",
                          _sample_events())
    body = json.loads(path.read_text())
    assert body["displayTimeUnit"] == "ms"
    events = body["traceEvents"]
    assert {e["ph"] for e in events} == {"X", "i"}
    for e in events:                # trace_event wants integer microseconds
        assert isinstance(e["ts"], int) and e["ts"] >= 0
        assert "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 0
    assert ox.load_trace(path) == events
    art = ox.render_timeline(path, width=32)
    assert "epoch" in art and "retrace" in art
    art = ox.render_timeline(path, width=32, limit=1)
    assert "more (raise --limit)" in art


def test_metrics_roundtrip_summary_and_diff(tmp_path):
    obs.count("retrace.train", 3)
    obs.count("store.hits", 10)
    a = ox.write_metrics(tmp_path / "a.metrics.json", metrics=obs.snapshot(),
                         run="smoke/cell_a")
    obs.count("retrace.train", 2)
    b = ox.write_metrics(tmp_path / "b.metrics.json", metrics=obs.snapshot(),
                         run="smoke/cell_b")
    assert ox.load_metrics(a)["run"] == "smoke/cell_a"
    assert ox.metrics_files(tmp_path) == [a, b]
    summary = ox.render_summary(tmp_path)
    assert "smoke/cell_a" in summary and "smoke/cell_b" in summary
    assert "retrace" in summary
    diff = ox.render_diff(a, b)
    assert "retrace.train" in diff and "+2" in diff
    # schema and emptiness are hard errors, not silent garbage
    (tmp_path / "junk.metrics.json").write_text('{"schema": "nope"}')
    with pytest.raises(ValueError):
        ox.load_metrics(tmp_path / "junk.metrics.json")
    with pytest.raises(FileNotFoundError):
        ox.render_summary(tmp_path / "empty")


# ---------------------------------------------------------------------------
# CLI: subcommands + exit codes
# ---------------------------------------------------------------------------
def _cli(*args):
    return subprocess.run([sys.executable, "-m", "repro.obs", *args],
                          capture_output=True, text=True, env=CLI_ENV,
                          cwd=ROOT, timeout=120)


def test_cli_summarize_timeline_diff(tmp_path):
    trace = ox.write_trace(tmp_path / "cell.trace.json", _sample_events())
    ox.write_metrics(tmp_path / "cell.metrics.json", metrics=obs.snapshot(),
                     run="smoke/cell", trace_path=str(trace))
    r = _cli("summarize", str(tmp_path))
    assert r.returncode == 0 and "smoke/cell" in r.stdout
    r = _cli("timeline", str(trace), "--width", "24")
    assert r.returncode == 0 and "epoch" in r.stdout
    r = _cli("diff", str(tmp_path / "cell.metrics.json"),
             str(tmp_path / "cell.metrics.json"))
    assert r.returncode == 0 and "retrace" in r.stdout


def test_cli_exit_codes_on_bad_input(tmp_path):
    r = _cli("summarize", str(tmp_path / "nowhere"))
    assert r.returncode == 2 and "error:" in r.stderr
    bad = tmp_path / "bad.trace.json"
    bad.write_text("{}")
    r = _cli("timeline", str(bad))
    assert r.returncode == 2 and "error:" in r.stderr


# ---------------------------------------------------------------------------
# instrumented layers: trainer, server, store, loadgen
# ---------------------------------------------------------------------------
def _tiny_trainer(epochs=2, arch="gcn", cfg=None, policy=None):
    from repro.core.sylvie import SylvieConfig
    from repro.graph import formats, partition, synthetic
    from repro.models.gnn.models import GCN, GraphSAGE
    from repro.train.trainer import GNNTrainer

    g0 = synthetic.planted_partition(n_nodes=120, d_feat=8, seed=0)
    ei = formats.add_self_loops(g0.edge_index, g0.n_nodes)
    ew = formats.gcn_edge_weights(ei, g0.n_nodes)
    g = formats.Graph(g0.n_nodes, ei, g0.x, g0.y, g0.train_mask, g0.val_mask,
                      g0.test_mask, n_classes=g0.n_classes)
    pg = partition.partition_graph(g, 4, edge_weight=ew, layout="compact")
    model = {"gcn": GCN, "sage": GraphSAGE}[arch](g.x.shape[1], 16,
                                                  g.n_classes, n_layers=2)
    tr = GNNTrainer(model, pg, cfg or SylvieConfig(mode="sync", bits=1),
                    policy=policy)
    tr.fit(epochs)
    return g, tr


def test_trainer_emits_epoch_spans_and_wall_s():
    obs.enable(obs.FakeClock(tick=0.01))
    _, tr = _tiny_trainer(epochs=2)
    ev = obs.drain()
    spans = [e["name"] for e in ev if e["ph"] == "X"]
    assert spans.count("epoch") == 2
    assert spans.count("decide") == 2 and spans.count("step") == 2
    assert spans.count("dispatch") == 2 and spans.count("readback.loss") == 2
    assert spans.count("readback.stats") == 2
    assert spans.count("build") == 1            # one decision, one build
    steps = [e for e in ev if e["name"] == "step"]
    assert steps[0]["args"]["mode"] in ("sync", "async")
    # wall_s is the whole-epoch clock (decide + step + host bookkeeping),
    # measured on the same deterministic clock as the spans
    for m in tr.history:
        assert m.wall_s > 0.0
    # wall_s brackets the epoch span (it opens one clock read earlier and
    # closes one later — 2 ticks of skew on the FakeClock)
    epochs = [e for e in ev if e["name"] == "epoch"]
    assert epochs[0]["dur"] <= tr.history[0].wall_s \
        <= epochs[0]["dur"] + 0.03


def test_trainer_wall_s_populated_untraced():
    _, tr = _tiny_trainer(epochs=1)
    assert tr.history[0].wall_s > 0.0           # obs.clock works untraced
    assert tr.history[0].wall_s >= tr.history[0].seconds


def _host_events(trace_dir):
    """{line: [(name, start_ns, end_ns)]} of the host planes of the one
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                       recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out[(plane.name, line.name)] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return out


def test_trainer_spans_reach_the_profiler(tmp_path):
    """Untraced by obs, the trainer's spans still land on a host plane of
    the JAX profiler's trace, nested as the taxonomy says; ``build`` opens
    only in the first epoch of each decision (here 32 bits, then 1)."""
    import jax

    from repro.core.sylvie import SylvieConfig
    from repro.policy import Warmup

    with jax.profiler.trace(str(tmp_path)):
        _tiny_trainer(epochs=3, cfg=SylvieConfig(mode="sync", bits=1),
                      policy=Warmup(epochs=1, bits=1))
    lines = [evs for evs in _host_events(tmp_path).values()
             if any(n == "epoch" for n, _, _ in evs)]
    assert len(lines) == 1
    evs = lines[0]
    epochs = sorted((s, e) for n, s, e in evs if n == "epoch")
    assert len(epochs) == 3

    def within(name, outer):
        """For each ``name`` event, the index of the ``outer`` interval
        that holds it."""
        out = []
        for n, s, e in evs:
            if n == name:
                [i] = [i for i, (os_, oe) in enumerate(outer)
                       if os_ <= s and e <= oe]
                out.append(i)
        return sorted(out)

    for name in ("decide", "step", "readback.stats"):
        assert within(name, epochs) == [0, 1, 2], name
    assert within("build", epochs) == [0, 1]
    steps = sorted((s, e) for n, s, e in evs if n == "step")
    assert within("dispatch", steps) == [0, 1, 2]
    assert within("readback.loss", steps) == [0, 1, 2]
    # the profiler's step marker holds each epoch span
    assert within("epoch", sorted((s, e) for n, s, e in evs
                                  if n == "train")) == [0, 1, 2]


_WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def _scope_path(op_name):
    """The ``op_name`` path with ``jvp(``/``transpose(`` wrappers stripped."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def _hlo_instructions(text):
    """(opcode, op_name) of every instruction of an optimised HLO text."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\([^=]*?\)|\S+)"
                     r"\s+([a-z][\w\-]*)\(", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), op.group(1) if op else ""))
    return out


@pytest.mark.parametrize("mode", ["sync", "async"])
@pytest.mark.parametrize("arch", ["gcn", "sage"])
def test_compiled_step_keeps_layer_scopes(arch, mode):
    """Every gather and scatter of the compiled 1-bit step (jnp Low-bit
    path, CPU) lies under the ``aggregation`` or ``exchange`` scope, but the
    loss's label pick, which ``take_along_axis`` makes straight from the
    step; the noise draw, rounding and bit packing lie under ``lowbit``; the
    matmuls lie under no scope (a scope left open by nested calls would
    claim them)."""
    from repro.core.sylvie import SylvieConfig
    _, tr = _tiny_trainer(epochs=0, arch=arch, cfg=SylvieConfig(
        mode=mode, bits=1, quant_impl="jnp"))
    seen = {"aggregation": 0, "exchange": 0, "lowbit": 0}
    for opcode, op_name in _hlo_instructions(
            tr.compiled_step_text(sync=mode == "sync")):
        path = _scope_path(op_name)
        scopes = [p for p in path if p in seen]
        if scopes:
            seen[scopes[-1]] += 1
        if opcode == "dot":                     # the layers' matmuls
            assert not scopes, op_name
        if opcode in ("gather", "scatter"):
            assert scopes[-1:] in (["aggregation"], ["exchange"]) or \
                path[1:-1] == ["jit(take_along_axis)"], op_name
        packs = (path[-1] in ("shift_left", "shift_right_logical")
                 and "threefry" not in op_name)   # not the key fold-ins
        if "jit(_uniform)" in path or opcode in ("floor", "clamp") or packs:
            assert scopes[-1:] == ["lowbit"], op_name
    assert all(seen.values()), seen


def _tiny_server(microbatch=8, max_queue=2, clock=None):
    from repro.serve import EmbeddingServer, InferenceEngine, ServeConfig

    g, tr = _tiny_trainer(epochs=1)
    eng = InferenceEngine(tr.model, tr.pg, tr.state.params,
                          config=ServeConfig(bits=1))
    eng.full_sweep()
    return g, EmbeddingServer(eng, microbatch=microbatch, max_queue=max_queue,
                              clock=clock)


def test_server_spans_and_rejection_counters():
    from repro.serve import Rejection

    g, srv = _tiny_server(max_queue=1)
    obs.enable(obs.FakeClock(tick=0.001))
    assert isinstance(srv.submit([1, 2]), int)
    rej = srv.submit([3])
    assert isinstance(rej, Rejection) and rej.reason == "queue_full"
    srv.step()
    ev = obs.drain()
    names = [e["name"] for e in ev if e["ph"] == "X"]
    assert names.count("admit") == 2            # accepted AND rejected submits
    assert "request" in names and "lookup" in names
    req = next(e for e in ev if e["name"] == "request")
    assert req["args"] == {"requests": 1, "nodes": 2}
    assert obs.snapshot()["counters"]["serve.rejected.queue_full"] == 1
    srv.start_draining()
    srv.submit([4])
    assert obs.snapshot()["counters"]["serve.rejected.draining"] == 1


def test_store_counts_hits_and_miss_bytes():
    from repro.store.backend import ShardedEmbeddingStore

    store = ShardedEmbeddingStore(cache_bytes=1 << 16)
    store.create_table("t", part_rows=(8,), d=4)
    rows = np.arange(32, dtype=np.float32).reshape(8, 4)
    store.put_rows("t", 0, np.arange(8), rows)
    store.get_rows("t", 0, np.array([0, 1]))    # cold: 2 misses
    store.get_rows("t", 0, np.array([0, 1]))    # warm: 2 hits
    c = obs.snapshot()["counters"]
    assert c["store.hits"] == 2
    assert c["store.miss_bytes"] == 2 * 4 * 4   # 2 rows x 4 feats x fp32


def test_open_loop_fake_clock_is_deterministic():
    """Satellite (a): open_loop on an injected FakeClock — the idle waits
    advance fake time (no wall sleeps), and two runs over the same seed
    produce *identical* reports, latencies included."""
    from repro.serve.loadgen import open_loop

    g, srv1 = _tiny_server(microbatch=8, max_queue=64)
    srv2 = type(srv1)(srv1.engine, microbatch=8, max_queue=64)

    def run(srv):
        return open_loop(srv, g.n_nodes, qps=500.0, requests=24, batch=2,
                         seed=7, clock=obs.FakeClock(tick=1e-5))

    rep1, rep2 = run(srv1), run(srv2)
    assert rep1 == rep2                         # bit-identical, floats and all
    assert rep1["completed"] == 24 and rep1["lost"] == 0
    assert rep1["seconds"] > 0.0
    # the run's duration is fake-clock time: it covers the Poisson schedule's
    # horizon even though no wall-clock waiting happened
    arrivals = np.cumsum(np.random.default_rng(7).exponential(1 / 500.0,
                                                              size=24))
    assert rep1["seconds"] >= arrivals[-1] - 1e-3


def test_server_inherits_fake_clock_from_obs(tmp_path):
    """server.clock defaults to obs.clock: arming a FakeClock tracer makes
    the whole request path deterministic with no constructor plumbing."""
    g, srv = _tiny_server()
    obs.enable(obs.FakeClock(start=100.0, tick=0.5))
    srv.submit([1])
    [resp] = srv.step()
    obs.disable()
    assert resp.latency_s > 0.0
    assert resp.latency_s == pytest.approx(round(resp.latency_s / 0.5) * 0.5)
