"""repro.faults: seeded chaos schedules, one-bit wire checksums, recovery.

Acceptance contracts under test (ISSUE: fault-tolerant runtime):
  * schedules are deterministic in (seed, epoch) alone, epoch 0 is clean
    under ``warmup_clean``, corruption/delay are disjoint from drops, and a
    preempted partition folds every one of its messages into drops;
  * event -> wire-row mask expansion respects both layouts' geometry and the
    forward/backward buffer flip;
  * a corrupted 1-bit payload is *detected* by the per-row checksum and
    handled exactly like a drop — never silently dequantized;
  * a rate-0 plan is bit-identical to no plan at all (sync + async), and a
    seeded schedule dropping >= 10% of exchanges on ``yelp_like@smoke``
    trains to within 2% test accuracy of the fault-free twin, with
    ``faults_injected == halos_reused + forced_syncs`` exact on every epoch;
  * staleness-as-recovery escalates: a site faulted ``escalate_after``
    consecutive epochs forces one clean full-precision synchronous retry, and
    ``BoundedStaleness`` treats fault staleness like scheduled staleness;
  * arming faults costs exactly one extra traced executable (masks are data);
  * checkpointing GCs ``.tmp_step_*`` crash orphans, and the kill-and-resume
    harness proves bit-exact resume under Uniform/sync (`slow`: subprocess);
  * serving keeps answering 100% of in-deadline requests while a partition
    is down, with correct per-partition staleness stamps, typed admission
    rejections, deadline expiry, and refresh-failure degradation.
"""
import dataclasses
import json
import os
import signal
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import datasets
from repro.core.exchange import PlanArrays, exchange_quantized_halo, gather_boundary
from repro.core.quantization import quantize
from repro.core.sylvie import SylvieConfig
from repro.core.wire import checked_exchange, flip_rows, row_checksum
from repro.dist.backend import SimulatedBackend
from repro.faults import BWD, FWD, FaultCtl, FaultPlan, RowGeometry
from repro.graph import formats, partition, synthetic
from repro.models.gnn.models import GCN, PAPER_ARCHS
from repro.policy import BoundedStaleness, Telemetry, Uniform
from repro.serve import EmbeddingServer, InferenceEngine, Rejection, ServeConfig
from repro.serve.loadgen import closed_loop
from repro.train import gnn_step
from repro.train.checkpoint import latest_step
from repro.train.trainer import GNNTrainer

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _graph(n=240, d=16, seed=0):
    g = synthetic.planted_partition(n_nodes=n, d_feat=d, seed=seed)
    ei = formats.add_self_loops(g.edge_index, g.n_nodes)
    ew = formats.gcn_edge_weights(ei, g.n_nodes)
    return formats.Graph(g.n_nodes, ei, g.x, g.y, g.train_mask, g.val_mask,
                         g.test_mask, n_classes=g.n_classes), ew


def _trainer(mode="sync", fault_plan=None, parts=4, seed=0, policy=None,
             layout="compact", **kw):
    g, ew = _graph(seed=seed)
    pg = partition.partition_graph(g, parts, edge_weight=ew, layout=layout)
    model = GCN(d_in=16, d_hidden=24, d_out=g.n_classes, n_layers=2)
    return GNNTrainer(model, pg, SylvieConfig(mode=mode),
                      policy=policy or Uniform(bits=1), seed=seed,
                      fault_plan=fault_plan, **kw)


# ---------------------------------------------------------------------------
# FaultPlan: seeded schedules
# ---------------------------------------------------------------------------
def test_plan_events_deterministic_and_warmup_clean():
    plan = FaultPlan(seed=5, drop_rate=0.3, corrupt_rate=0.1, delay_rate=0.1)
    a, b = plan.events(3, 2, 4), plan.events(3, 2, 4)
    assert (a.drop == b.drop).all() and (a.corrupt == b.corrupt).all()
    assert (a.delay == b.delay).all() and (a.preempted == b.preempted).all()
    assert (plan.events(4, 2, 4).drop != a.drop).any()          # epoch-keyed
    other = dataclasses.replace(plan, seed=6).events(3, 2, 4)
    assert (other.drop != a.drop).any()                         # seed-keyed
    e0 = plan.events(0, 2, 4)
    assert e0.n_injected == 0 and not e0.delay.any()            # warmup
    hot = dataclasses.replace(plan, warmup_clean=False).events(0, 2, 4)
    assert hot.n_injected > 0


def test_plan_faults_offdiagonal_and_disjoint():
    ev = FaultPlan(seed=1, drop_rate=0.5, corrupt_rate=0.5,
                   delay_rate=0.5).events(2, 2, 4)
    eye = np.eye(4, dtype=bool)
    for field in (ev.drop, ev.corrupt, ev.delay):
        assert not field[:, :, eye].any()       # no self-messages
    assert not (ev.corrupt & ev.drop).any()     # lost != corrupted
    assert not (ev.delay & ev.drop).any()       # lost != late
    assert ev.n_injected == int(ev.drop.sum() + ev.corrupt.sum())


def test_plan_preemption_folds_into_drop():
    ev = FaultPlan(seed=0, preempt_rate=1.0).events(1, 2, 4)
    assert ev.preempted.all()
    off = ~np.eye(4, dtype=bool)
    assert ev.drop[:, :, off].all()             # every real message lost
    assert not ev.corrupt.any()                 # folded, not double-counted
    assert ev.n_injected == 2 * 2 * 4 * 3 == FaultPlan.n_units(2, 4)


def test_plan_stall_is_critical_path_not_total():
    plan = FaultPlan(delay_s=0.25)
    shape = (1, 2, 4, 4)
    delay = np.zeros(shape, bool)
    delay[0, FWD, 0, 2] = delay[0, FWD, 1, 2] = True    # 2 pile up on dst 2
    delay[0, BWD, 0, 3] = True
    ev = dataclasses.replace(plan.events(0, 1, 4), delay=delay)
    assert ev.stall_s(plan.delay_s) == pytest.approx(0.5)   # 2 * 0.25, not 3
    assert plan.events(0, 1, 4).stall_s(plan.delay_s) == 0.0


# ---------------------------------------------------------------------------
# event -> wire-row mask geometry
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["dense", "compact"])
def test_fault_ctl_expand_geometry(layout):
    g, ew = _graph()
    pg = partition.partition_graph(g, 4, edge_weight=ew, layout=layout)
    geom = RowGeometry.from_plan(PlanArrays.from_plan(pg.plan))
    peer_recv, peer_send = geom.peers()
    src, dst = 1, 2
    ev = FaultPlan().events(0, 2, 4)    # all-false template
    drop = np.zeros_like(ev.drop)
    drop[0, FWD, src, dst] = True       # forward message src -> dst lost
    drop[1, BWD, src, dst] = True       # backward gradient src -> dst lost
    ctl = FaultCtl.expand(dataclasses.replace(ev, drop=drop), geom, 2)
    # forward drop masks the *recv* buffer of dst, exactly the rows fed by src
    df = np.asarray(ctl.sites[0].drop_fwd)
    assert df[dst].sum() == (peer_recv[dst] == src).sum() > 0
    assert (df[np.arange(4) != dst] == False).all()  # noqa: E712
    # backward drop masks the returned-grad (send-geometry) buffer of dst
    db = np.asarray(ctl.sites[1].drop_bwd)
    assert db[dst].sum() == (peer_send[dst] == src).sum() > 0
    assert (db[np.arange(4) != dst] == False).all()  # noqa: E712
    # untouched site/masks stay all-false
    assert not np.asarray(ctl.sites[1].drop_fwd).any()
    assert not np.asarray(ctl.sites[0].corrupt_fwd).any()
    # clean() shares the pytree structure (one executable for recovery epochs)
    clean = FaultCtl.clean(geom, 2)
    assert (jax.tree_util.tree_structure(clean)
            == jax.tree_util.tree_structure(ctl))
    assert not any(bool(leaf.any()) for leaf in jax.tree_util.tree_leaves(clean))


# ---------------------------------------------------------------------------
# wire: checksum detection of corrupted payloads
# ---------------------------------------------------------------------------
def test_flip_rows_checksum_detects_exactly_masked_rows():
    rng = np.random.default_rng(0)
    for data in (jnp.asarray(rng.integers(0, 255, (4, 6, 3), dtype=np.uint8)),
                 jnp.asarray(rng.normal(size=(4, 6, 3)).astype(np.float32))):
        mask = jnp.asarray(rng.random((4, 6)) < 0.4)
        flipped = flip_rows(data, mask)
        changed = np.asarray((row_checksum(flipped)
                              != row_checksum(data)))
        assert (changed == np.asarray(mask)).all()      # exact detection
        # the flip is an involution: re-flipping restores the payload
        assert (np.asarray(flip_rows(flipped, mask)) == np.asarray(data)).all()


@pytest.mark.parametrize("layout", ["dense", "compact"])
def test_checked_exchange_never_silently_dequantizes_corruption(layout):
    g, ew = _graph()
    pg = partition.partition_graph(g, 4, edge_weight=ew, layout=layout)
    plan = PlanArrays.from_plan(pg.plan)
    be = SimulatedBackend()
    qt = quantize(gather_boundary(jnp.asarray(pg.x), plan), bits=1,
                  stochastic=False)
    ref = exchange_quantized_halo(qt, plan, be)
    zeros = jnp.zeros((plan.n_parts, plan.halo_rows), bool)
    # fault-free: bitwise-identical wire payload, every row ok
    qr, ok = checked_exchange(qt, plan, be, zeros, zeros)
    assert (np.asarray(qr.data) == np.asarray(ref.data)).all()
    assert np.asarray(ok).all()
    # corrupted rows: each lands on exactly one receiver row, every one is
    # caught by the checksum, and the payload differs on exactly those rows
    rng = np.random.default_rng(1)
    corrupt = jnp.asarray(rng.random((plan.n_parts, plan.halo_rows)) < 0.3)
    qr, ok = checked_exchange(qt, plan, be, corrupt, zeros)
    bad = ~np.asarray(ok)
    assert bad.sum() == int(np.asarray(corrupt).sum()) > 0
    differs = (np.asarray(qr.data) != np.asarray(ref.data)).reshape(
        plan.n_parts, plan.halo_rows, -1).any(axis=-1)
    assert (differs == bad).all()
    # drops condemn their rows even though the payload is intact
    dropm = jnp.asarray(rng.random((plan.n_parts, plan.halo_rows)) < 0.3)
    _, ok = checked_exchange(qt, plan, be, zeros, dropm)
    assert (np.asarray(ok) == ~np.asarray(dropm)).all()


# ---------------------------------------------------------------------------
# trainer integration: transparency, accuracy, accounting
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_rate_zero_plan_bit_identical_to_no_plan(mode):
    a = _trainer(mode=mode)
    b = _trainer(mode=mode, fault_plan=FaultPlan())    # armed, all rates 0
    la = [a.train_epoch() for _ in range(3)]
    lb = [b.train_epoch() for _ in range(3)]
    assert [m.loss for m in la] == [m.loss for m in lb]          # exact
    assert all(m.faults_injected == m.halos_reused == m.forced_syncs == 0
               for m in lb)


def test_corruption_is_handled_as_drop_and_accounted():
    tr = _trainer(mode="async",
                  fault_plan=FaultPlan(seed=2, corrupt_rate=0.3))
    hist = [tr.train_epoch() for _ in range(3)]
    assert hist[0].faults_injected == 0                          # warmup
    assert sum(m.faults_injected for m in hist) > 0
    for m in hist:       # every corrupted unit recovered from the cache
        assert m.faults_injected == m.halos_reused + m.forced_syncs
        assert np.isfinite(m.loss)


def test_chaos_training_within_2pct_of_fault_free():
    """The headline acceptance run: >= 10% of exchanges dropped on
    ``yelp_like@smoke``, final test accuracy within 2% of the clean twin,
    accounting exact on every epoch."""
    epochs = 6
    plan = FaultPlan(seed=7, drop_rate=0.15, corrupt_rate=0.05)

    def run(fault_plan):
        pg, _ = datasets.load_partitioned("yelp_like@smoke", 4, seed=0)
        model = PAPER_ARCHS["gcn"](pg.x.shape[-1], pg.n_classes)
        tr = GNNTrainer(model, pg, SylvieConfig(mode="async"),
                        policy=Uniform(bits=1), seed=0, fault_plan=fault_plan)
        hist = [tr.train_epoch() for _ in range(epochs)]
        return tr, hist

    clean_tr, _ = run(None)
    tr, hist = run(plan)
    n_sites = tr.n_sites
    units = FaultPlan.n_units(n_sites, 4) * (epochs - 1)    # epoch 0 clean
    dropped = sum(int(plan.events(e, n_sites, 4).drop.sum())
                  for e in range(1, epochs))
    assert dropped / units >= 0.10, "schedule too mild for the claim"
    for m in hist:
        assert m.faults_injected == m.halos_reused + m.forced_syncs
    assert sum(m.faults_injected for m in hist) > 0
    acc_clean, acc_faulty = clean_tr.evaluate("test"), tr.evaluate("test")
    assert abs(acc_clean - acc_faulty) <= 0.02, \
        f"chaos run lost {acc_clean - acc_faulty:.3f} accuracy"


def test_escalation_forces_clean_sync_recovery_epoch():
    plan = FaultPlan(seed=0, drop_rate=1.0, escalate_after=2)
    tr = _trainer(mode="async", fault_plan=plan)
    hist = [tr.train_epoch() for _ in range(5)]
    # epoch 0 clean, 1-2 degrade (staleness 1, 2), 3 is the forced recovery,
    # 4 degrades again from a reset counter
    assert hist[0].faults_injected == 0
    for m in (hist[1], hist[2], hist[4]):
        assert m.mode == "async"
        assert m.faults_injected == m.halos_reused > 0
        assert m.forced_syncs == 0
    rec = hist[3]
    assert rec.mode == "sync"                        # forced synchronous
    assert all(b == (32, 32) for b in rec.bits_per_site)   # full precision
    assert rec.forced_syncs == rec.faults_injected > 0     # schedule suppressed
    assert rec.halos_reused == 0
    assert (tr._site_staleness == 1).all()           # reset at 3, rearmed at 4


def test_bounded_staleness_counts_fault_staleness():
    pol = BoundedStaleness(eps_s=3, bits=1)
    tel = Telemetry(epoch=5, n_parts=4, n_sites=2, site_dims=(16, 24))
    base = dataclasses.replace(tel, site_staleness=(0, 2))
    assert not pol.decide(base).sync                 # under the bound
    due = dataclasses.replace(tel, site_staleness=(3, 0))
    assert pol.decide(due).sync                      # fault staleness counts


def test_armed_faults_share_one_executable():
    """Masks ride as data: an armed trainer traces exactly ONE sync
    executable across the clean warmup epoch and every faulty epoch — the
    epoch's fault set only changes mask *values*, never program structure."""
    tr = _trainer(mode="sync", fault_plan=FaultPlan(seed=3, drop_rate=0.5))
    base = len(gnn_step.TRACE_LOG)
    for _ in range(4):
        tr.train_epoch()
    assert len(gnn_step.TRACE_LOG) - base == 1


# ---------------------------------------------------------------------------
# preemption-safe checkpointing
# ---------------------------------------------------------------------------
def test_latest_step_gcs_crash_orphans(tmp_path):
    (tmp_path / "step_00000003").mkdir()
    orphan = tmp_path / ".tmp_step_00000004"
    orphan.mkdir()
    (orphan / "arrays.npz").write_bytes(b"partial garbage")
    assert latest_step(tmp_path) == 3
    assert not orphan.exists()                       # GC'd, not trusted
    assert (tmp_path / "step_00000003").exists()


def _chaos(tmp_path, *extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "repro.launch.chaos", "--kill-resume",
           "--epochs", "4", "--out-dir", str(tmp_path), *extra]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout)


@pytest.mark.slow
def test_kill_resume_bit_exact_uniform_sync(tmp_path):
    out = _chaos(tmp_path, "--policy", "uniform:1", "--mode", "sync")
    assert out["bit_exact"] and out["max_deviation"] == 0.0


@pytest.mark.slow
def test_kill_resume_bit_exact_uniform_sync_shard_map(tmp_path):
    env_extra = ("--runtime", "sharded")
    os.environ.setdefault("XLA_FLAGS", "")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", "")).strip()
    cmd = [sys.executable, "-m", "repro.launch.chaos", "--kill-resume",
           "--epochs", "4", "--out-dir", str(tmp_path), "--policy",
           "uniform:1", "--mode", "sync", *env_extra]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert out["bit_exact"] and out["max_deviation"] == 0.0


@pytest.mark.slow
def test_kill_resume_within_tolerance_bounded_staleness(tmp_path):
    """BoundedStaleness/async under a live fault schedule: the resume path
    resets staleness counters (a deliberately conservative forced refresh),
    so bit-exactness is not guaranteed — final accuracy must still match the
    uninterrupted run within the chaos tolerance."""
    _chaos(tmp_path, "--policy", "bounded_staleness:4:1", "--mode", "async",
           "--fault", "drop=0.1,seed=3")
    ref = json.loads((tmp_path / "ref.json").read_text())
    res = json.loads((tmp_path / "resumed.json").read_text())
    assert res["epochs"] == ref["epochs"] == 4
    assert abs(ref["test_acc"] - res["test_acc"]) <= 0.02


# ---------------------------------------------------------------------------
# serving robustness: degraded mode, deadlines, typed rejections
# ---------------------------------------------------------------------------
def _engine(parts=4, n=240):
    g, ew = _graph(n=n)
    pg = partition.partition_graph(g, parts, edge_weight=ew, layout="compact")
    model = GCN(d_in=16, d_hidden=24, d_out=g.n_classes, n_layers=2)
    eng = InferenceEngine(model, pg, model.init(jax.random.PRNGKey(0)),
                          config=ServeConfig(bits=32))
    eng.full_sweep()
    return eng, pg


def test_degraded_serving_answers_all_in_deadline_with_stamps():
    eng, pg = _engine()
    srv = EmbeddingServer(eng, microbatch=64)
    before = eng.logits.copy()
    srv.mark_partition_down(1)
    assert srv.health == "degraded"
    eng.full_sweep()             # the sweep the partition missed
    part_of = np.asarray(pg.part_of)
    n = part_of.size
    answered = []
    for start in range(0, n, 64):
        ids = np.arange(start, min(start + 64, n))
        rid = srv.submit(ids, deadline_s=60.0)
        assert not isinstance(rid, Rejection)
        answered.extend(srv.step())
    assert srv.expired == 0
    assert sum(r.node_ids.size for r in answered) == n     # 100% answered
    for r in answered:
        # stamps: 1 sweep stale exactly for nodes on the downed partition
        assert (np.asarray(r.staleness)
                == (part_of[r.node_ids] == 1).astype(np.int64)).all()
        # downed partition serves its frozen (pre-sweep) cache rows
        frozen = part_of[r.node_ids] == 1
        assert np.array_equal(r.logits[frozen], before[r.node_ids][frozen])
    srv.mark_partition_up(1)
    assert srv.health == "healthy"
    eng.full_sweep()
    assert (eng.part_staleness == 0).all()


def test_deadline_expiry_with_injected_clock():
    eng, _ = _engine()
    now = [0.0]
    srv = EmbeddingServer(eng, microbatch=8, clock=lambda: now[0])
    rid = srv.submit([1, 2], deadline_s=0.5)
    assert not isinstance(rid, Rejection)
    now[0] = 1.0                                   # past the deadline
    assert srv.step() == []
    assert srv.expired == 1 and srv.depth == 0
    rid = srv.submit([3], deadline_s=5.0)          # in-deadline still serves
    [resp] = srv.step()
    assert resp.req_id == rid


def test_refresh_failure_degrades_and_recovers():
    eng, pg = _engine()
    srv = EmbeddingServer(eng)
    bad = np.zeros((2, 3), np.float32)             # wrong feature width
    assert srv.refresh(np.array([0, 1]), bad) is None
    assert srv.health == "degraded" and srv.refresh_failures == 1
    [resp] = (srv.submit([0]), srv.step())[1]      # still answering
    assert np.isfinite(resp.logits).all()
    good = np.zeros((1, pg.x.shape[-1]), np.float32)
    assert srv.refresh(np.array([0]), good) is not None
    assert srv.health == "healthy"


def test_loadgen_reports_rejections_backoff_and_draining():
    eng, _ = _engine()
    srv = EmbeddingServer(eng, microbatch=16, max_queue=1)
    rep = closed_loop(srv, n_nodes=200, clients=4, batch=8, requests=40,
                      seed=0)
    assert rep["requests"] == 40                   # retries win through
    assert rep["rejection_reasons"].get("queue_full", 0) > 0
    assert rep["backoff_s"] > 0.0
    drained = EmbeddingServer(eng)
    drained.start_draining()
    rep = closed_loop(drained, n_nodes=200, clients=2, batch=4, requests=10,
                      seed=0)
    assert rep["requests"] == 0
    assert rep["rejection_reasons"] == {"draining": 1}
