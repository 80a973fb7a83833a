"""Overlap-schedule parity: the fenced issue/land pipeline vs blocking.

Contract (DESIGN §14): ``schedule="overlap"`` reorders work around the halo
collective — it must never perturb a value. Under sync (fresh) exchange the
overlap step is **bit-exact** to blocking: identical loss trajectories and
bit-identical parameters, in the simulated stack and under shard_map. Under
async/BoundedStaleness the stale-halo micro-step variant holds the same
staleness contract, checked to a 2% accuracy band. The `slow` test forks a
subprocess with 4 forced host devices; ``test_shardmap_overlap_parity_inline``
runs the same check in-process when the session already has >= 4 devices (the
CI ``--overlap`` lane).
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.api as repro
from repro.core.sylvie import SCHEDULES, SylvieConfig
from repro.graph import formats, partition, synthetic
from repro.models.gnn.models import GCN
from repro.policy import BoundedStaleness
from repro.train import optimizer as opt

pytestmark = pytest.mark.overlap

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _skewed_graph(n=600, d=16, seed=0):
    g = synthetic.powerlaw(n_nodes=n, d_feat=d, avg_degree=10, seed=seed)
    ei = formats.add_self_loops(g.edge_index, g.n_nodes)
    ew = formats.gcn_edge_weights(ei, g.n_nodes)
    return formats.Graph(g.n_nodes, ei, g.x, g.y, g.train_mask, g.val_mask,
                         g.test_mask, n_classes=g.n_classes), ew


def _pg(layout="compact", n=600, parts=4):
    g, ew = _skewed_graph(n=n)
    return partition.partition_graph(g, parts, method="skewed",
                                     edge_weight=ew, layout=layout)


def _train(pg, schedule, mode="sync", epochs=3, policy=None,
           stochastic=False):
    model = GCN(d_in=pg.x.shape[-1], d_hidden=24, d_out=pg.n_classes,
                n_layers=2)
    cfg = SylvieConfig(mode=mode, bits=1, stochastic=stochastic,
                       schedule=schedule)
    return repro.train(model, pg, cfg, opt=opt.sgd(1e-1), epochs=epochs,
                       policy=policy, seed=0)


def _assert_bit_exact(tr_a, tr_b, what=""):
    la = [m.loss for m in tr_a.history]
    lb = [m.loss for m in tr_b.history]
    assert la == lb, f"{what}: loss trajectories diverged: {la} vs {lb}"
    for a, b in zip(jax.tree.leaves(jax.device_get(tr_a.state.params)),
                    jax.tree.leaves(jax.device_get(tr_b.state.params))):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"{what}: params are not bit-identical"


# ---------------------------------------------------------------------------
# simulated stack: bit-exactness under sync, both layouts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["dense", "compact"])
def test_sync_bitexact_simulated(layout):
    """Fresh (sync) overlap is value-transparent: same losses, same bits."""
    pg = _pg(layout)
    blocking = _train(pg, "blocking", mode="sync")
    overlap = _train(pg, "overlap", mode="sync")
    _assert_bit_exact(blocking, overlap, f"sync/{layout}")
    assert all(m.schedule == "overlap" for m in overlap.history)
    assert all(m.schedule == "blocking" for m in blocking.history)


def test_sync_bitexact_stochastic_rounding():
    """Bit-exactness holds under stochastic rounding too — the overlap path
    consumes the identical per-site PRNG keys as blocking."""
    pg = _pg("compact")
    _assert_bit_exact(_train(pg, "blocking", stochastic=True),
                      _train(pg, "overlap", stochastic=True),
                      "sync/stochastic")


def test_async_uniform_bitexact_simulated():
    """The stale-halo micro-step variant: cached features consumed, fresh
    exchange fenced into the next step's cache — values match blocking
    Sylvie-A exactly under the Uniform policy."""
    pg = _pg("compact")
    _assert_bit_exact(_train(pg, "blocking", mode="async", epochs=4),
                      _train(pg, "overlap", mode="async", epochs=4),
                      "async/uniform")


def test_async_bounded_staleness_accuracy_band():
    """Under BoundedStaleness (periodic sync refresh epochs interleaved with
    stale micro-steps) the overlap schedule must track blocking to within a
    2% accuracy band (DESIGN §14 acceptance)."""
    pg = _pg("compact")
    pol = lambda: BoundedStaleness(eps_s=2, bits=1, stochastic=False)  # noqa: E731
    blocking = _train(pg, "blocking", mode="async", epochs=8, policy=pol())
    overlap = _train(pg, "overlap", mode="async", epochs=8, policy=pol())
    acc_b, acc_o = blocking.evaluate("val"), overlap.evaluate("val")
    assert abs(acc_b - acc_o) <= 0.02, (acc_b, acc_o)
    lb, lo = blocking.history[-1].loss, overlap.history[-1].loss
    assert abs(lb - lo) <= 0.02 * max(abs(lb), 1e-8), (lb, lo)


def test_loss_trajectory_parity_dense_vs_compact_under_overlap():
    """The overlap schedule preserves the dense<->compact layout-parity
    contract of test_halo_compact: same trajectories to fp32 tolerance."""
    for mode, epochs in (("sync", 3), ("async", 4)):
        runs = {lay: _train(_pg(lay), "overlap", mode=mode, epochs=epochs)
                for lay in ("dense", "compact")}
        np.testing.assert_allclose(
            [m.loss for m in runs["dense"].history],
            [m.loss for m in runs["compact"].history], rtol=1e-5)
        for a, b in zip(jax.tree.leaves(runs["dense"].state.params),
                        jax.tree.leaves(runs["compact"].state.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# schedule knob plumbing
# ---------------------------------------------------------------------------
def test_unknown_schedule_rejected():
    pg = _pg("compact", n=200)
    with pytest.raises(ValueError, match="unknown schedule"):
        _train(pg, "eager")
    assert "blocking" in SCHEDULES and "overlap" in SCHEDULES


def test_fence_is_value_transparent():
    """The backend fence is optimization_barrier: identity on values, for
    arbitrary pytrees including empty passthrough scale/zero leaves."""
    from repro.core import quantization as qlib
    from repro.dist.backend import SimulatedBackend
    be = SimulatedBackend()
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 8))
    qt = qlib.quantize(x, 1, jax.random.PRNGKey(1), stochastic=False)
    out = be.fence(qt)
    for a, b in zip(jax.tree.leaves(qt), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    jaxpr = str(jax.make_jaxpr(be.fence)((x, x)))
    assert "optimization_barrier" in jaxpr


def test_serve_sweep_overlap_bitexact():
    """The serving sweep under schedule="overlap" (payload + affected-mask
    exchanges landed through one fence) is bit-exact to blocking."""
    from repro.dist.runtime import Runtime
    from repro.serve.engine import InferenceEngine, ServeConfig
    pg = _pg("compact", n=300)
    model = GCN(d_in=pg.x.shape[-1], d_hidden=24, d_out=pg.n_classes,
                n_layers=2)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.arange(0, 300, 7)
    out = {}
    for sched in SCHEDULES:
        eng = InferenceEngine(
            model, pg, params,
            config=ServeConfig(bits=1, stochastic=False, schedule=sched),
            runtime=Runtime.simulated(4))
        eng.full_sweep()
        out[sched] = (eng.query(ids).logits, eng.embeddings(ids))
    for a, b in zip(out["blocking"], out["overlap"]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# shard_map parity
# ---------------------------------------------------------------------------
OVERLAP_PARITY = """
import repro.api as repro
from repro.graph import synthetic
from repro.models.gnn.models import GCN
from repro.train import optimizer as opt

g = synthetic.powerlaw(n_nodes=500, d_feat=16, avg_degree=10, seed=0)
model = GCN(d_in=16, d_hidden=24, d_out=g.n_classes, n_layers=2)
rt = repro.Runtime.from_mesh(repro.make_gnn_mesh(4))
pg = repro.partition(g, n_parts=4, method="skewed", layout="compact")


def run(schedule, mode, epochs):
    cfg = repro.SylvieConfig(mode=mode, bits=1, stochastic=False,
                             schedule=schedule)
    return repro.train(model, pg, cfg, runtime=rt, opt=opt.sgd(1e-1),
                       epochs=epochs)


for mode, epochs in (("sync", 3), ("async", 4)):
    ref = run("blocking", mode, epochs)
    got = run("overlap", mode, epochs)
    assert ([m.loss for m in ref.history] == [m.loss for m in got.history]), (
        mode, [m.loss for m in ref.history], [m.loss for m in got.history])
    for pa, pb in zip(jax.tree.leaves(jax.device_get(ref.state.params)),
                      jax.tree.leaves(jax.device_get(got.state.params))):
        assert np.array_equal(np.asarray(pa), np.asarray(pb)), mode
print("OK")
"""


def test_shardmap_overlap_parity_inline():
    """Runs when the session already has >= 4 devices (the CI --overlap
    lane): overlap under shard_map is bit-exact to blocking, sync and
    async."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
    env = {"repro": repro, "jax": jax, "np": np}
    exec(textwrap.dedent(OVERLAP_PARITY), env)


@pytest.mark.slow
def test_shardmap_overlap_parity_subprocess():
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, {SRC!r})
        import jax, numpy as np
    """) + textwrap.dedent(OVERLAP_PARITY)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "OK" in r.stdout
