"""Compact (ragged ring-bucket) halo plan vs dense pairwise plan.

Parity contract: with deterministic rounding (quantization is per-row, so the
buffer layout cannot change its numerics) the two layouts must produce
identical forward activations, losses, and parameter trajectories — in the
simulated stack and under shard_map — while the compact plan ships a fraction
of the dense wire bytes on skewed partitions. The `slow` test forks a
subprocess with 4 forced host devices (jax locks the device count at first
init); `test_shardmap_*_inline` runs the same check in-process when the
current session already has >= 4 devices (the CI `--halo` lane).
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.exchange import (PlanArrays, exchange_bytes, exchange_halo,
                                 gather_boundary, wire_bytes)
from repro.core.sylvie import SylvieComm, SylvieConfig
from repro.dist.backend import SimulatedBackend
from repro.graph import formats, partition, synthetic
from repro.models.gnn import blocks as B
from repro.models.gnn.models import GCN
from repro.train import optimizer as opt
from repro.train.gnn_step import GNNTrainState, make_gnn_steps

SRC = str(Path(__file__).resolve().parents[1] / "src")
KEY = jax.random.PRNGKey(0)


def _skewed_graph(n=900, d=16, seed=0):
    """Power-law graph whose `skewed` partition has badly imbalanced pairs."""
    g = synthetic.powerlaw(n_nodes=n, d_feat=d, avg_degree=10, seed=seed)
    ei = formats.add_self_loops(g.edge_index, g.n_nodes)
    ew = formats.gcn_edge_weights(ei, g.n_nodes)
    return formats.Graph(g.n_nodes, ei, g.x, g.y, g.train_mask, g.val_mask,
                         g.test_mask, n_classes=g.n_classes), ew


def _both_layouts(g, ew, p=8):
    return {layout: partition.partition_graph(g, p, method="skewed",
                                              edge_weight=ew, layout=layout)
            for layout in ("dense", "compact")}


# ---------------------------------------------------------------------------
# plan structure + accounting
# ---------------------------------------------------------------------------
def test_compact_plan_structure_and_wire_reduction():
    g, ew = _skewed_graph()
    pgs = _both_layouts(g, ew)
    pd, pc = pgs["dense"].plan, pgs["compact"].plan

    # the stress partition really is skewed: per-pair counts differ by >10x
    off = pc.pair_counts[~np.eye(pc.n_parts, dtype=bool)]
    nz = off[off > 0]
    assert nz.max() > 10 * nz.min(), (nz.min(), nz.max())

    assert pc.bucket_sizes[0] == 0                  # diagonal dropped
    assert (pc.bucket_sizes % pc.alignment == 0).all()
    # both layouts carry the same true halo set
    assert pc.real_rows() == pd.real_rows()
    assert pc.pad_efficiency() > pd.pad_efficiency()
    # acceptance: compact wire <= 60% of the dense (P, P*h_pad) layout
    assert pc.wire_rows() <= 0.6 * pd.wire_rows(), \
        (pc.wire_rows(), pd.wire_rows())

    # device-side accounting mirrors the host plan; true bytes are
    # layout-invariant, shipped bytes are not
    ad, ac = PlanArrays.from_plan(pd), PlanArrays.from_plan(pc)
    d_feat = 64
    assert exchange_bytes(ac, d_feat, 1) == exchange_bytes(ad, d_feat, 1)
    assert wire_bytes(ac, d_feat, 1)[0] <= 0.6 * wire_bytes(ad, d_feat, 1)[0]
    # payload ratio between bit-widths is padding-invariant (Table 3)
    assert exchange_bytes(ac, d_feat, 32)[0] == 32 * exchange_bytes(ac, d_feat, 1)[0]


def test_compact_exchange_ring_semantics_and_reverse():
    """recv[p][bucket k] == send[(p-k)%P][bucket k]; reverse undoes forward."""
    g, ew = _skewed_graph(n=400)
    plan = PlanArrays.from_plan(
        partition.partition_graph(g, 4, method="skewed", edge_weight=ew,
                                  layout="compact").plan)
    p, rows = plan.n_parts, plan.halo_rows
    x = jax.random.normal(KEY, (p, rows, 3))
    be = SimulatedBackend()
    y = exchange_halo(x, plan, be)
    start = 0
    for k, b in enumerate(plan.bucket_sizes):
        for pi in range(p):
            np.testing.assert_allclose(
                np.asarray(y[pi, start:start + b]),
                np.asarray(x[(pi - k) % p, start:start + b]))
        start += b
    back = exchange_halo(y, plan, be, reverse=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x))


def test_compact_gather_packs_live_rows():
    """The compaction permutation leaves no dead pairwise blocks: every
    unmasked row of the send buffer is a real boundary node."""
    g, ew = _skewed_graph(n=500)
    pg = partition.partition_graph(g, 4, method="skewed", edge_weight=ew,
                                   layout="compact")
    plan = PlanArrays.from_plan(pg.plan)
    h = jnp.asarray(pg.x)
    buf = gather_boundary(h, plan)
    mask = np.asarray(plan.send_mask)
    # masked (alignment-tail) rows are zeroed; live rows match the features
    assert (np.asarray(buf)[~mask] == 0).all()
    idx = np.asarray(plan.send_idx)
    for p in range(plan.n_parts):
        live = np.where(mask[p])[0]
        np.testing.assert_allclose(np.asarray(buf)[p, live],
                                   np.asarray(h)[p, idx[p, live]])


# ---------------------------------------------------------------------------
# numerics parity, simulated stack
# ---------------------------------------------------------------------------
def test_forward_parity_dense_vs_compact():
    """Vanilla and 1-bit deterministic halo: identical layer inputs."""
    g, ew = _skewed_graph()
    pgs = _both_layouts(g, ew)
    for cfg in (SylvieConfig(mode="vanilla", stochastic=False),
                SylvieConfig(mode="sync", bits=1, stochastic=False)):
        aggs = {}
        for layout, pg in pgs.items():
            blk = B.build_block(pg)
            x = jnp.asarray(pg.x)
            halo = SylvieComm(cfg, blk.plan, KEY).halo(x)
            table = B.halo_table(x, halo)
            msgs = B.gather_src(blk, table) * blk.edge_weight[..., None]
            aggs[layout] = pg.unpartition(np.asarray(B.agg_sum(blk, msgs)))
        np.testing.assert_allclose(aggs["dense"], aggs["compact"],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_train_parity_dense_vs_compact(mode):
    """Same PRNG keys, deterministic rounding: losses and params match to
    fp32 tolerance through full forward/backward training steps."""
    g, ew = _skewed_graph(n=600)
    pgs = _both_layouts(g, ew)
    out = {}
    for layout, pg in pgs.items():
        blk = B.build_block(pg)
        model = GCN(d_in=g.x.shape[1], d_hidden=24, d_out=g.n_classes,
                    n_layers=2)
        o = opt.adam(1e-2)
        cfg = SylvieConfig(mode=mode, bits=1, stochastic=False)
        ts, ta, _ = make_gnn_steps(model, cfg, o)
        st = GNNTrainState.create(model, o, KEY, blk.plan, stacked_parts=8)
        x, y, m = jnp.asarray(pg.x), jnp.asarray(pg.y), jnp.asarray(pg.train_mask)
        losses = []
        st, loss = jax.jit(ts)(st, blk, x, y, m, KEY)   # warmup / sync step
        losses.append(float(loss))
        step = jax.jit(ta if mode == "async" else ts)
        for i in range(3):
            st, loss = step(st, blk, x, y, m, jax.random.fold_in(KEY, i))
            losses.append(float(loss))
        out[layout] = (losses, jax.tree.leaves(st.params))
    np.testing.assert_allclose(out["dense"][0], out["compact"][0], rtol=1e-5)
    for a, b in zip(out["dense"][1], out["compact"][1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_quantized_backward_scatter_compact():
    """Gradient scatter through the reversed rings equals the analytic sum
    over receivers (Alg. 2 line 13) on a compact plan."""
    from repro.core.sylvie import quantized_halo
    g, ew = _skewed_graph(n=300)
    pg = partition.partition_graph(g, 4, method="skewed", edge_weight=ew,
                                   layout="compact")
    plan = PlanArrays.from_plan(pg.plan)
    x = jnp.asarray(pg.x)

    def f(h):
        halo = quantized_halo(h, None, plan, KEY, KEY, 32, 32, False,
                              jnp.bfloat16, None, "jnp", False)
        return (halo ** 2).sum() / 2

    grad = jax.grad(f)(x)
    sends = np.asarray(plan.send_mask)
    idx = np.asarray(plan.send_idx)
    expected = np.zeros_like(np.asarray(x))
    for p in range(plan.n_parts):
        for slot in range(idx.shape[1]):
            if sends[p, slot]:
                expected[p, idx[p, slot]] += np.asarray(x)[p, idx[p, slot]]
    np.testing.assert_allclose(np.asarray(grad), expected, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# shard_map parity
# ---------------------------------------------------------------------------
PARITY = """
import repro.api as repro
from repro.graph import synthetic
from repro.models.gnn.models import GCN
from repro.train import optimizer as opt

g = synthetic.powerlaw(n_nodes=500, d_feat=16, avg_degree=10, seed=0)
model = GCN(d_in=16, d_hidden=24, d_out=g.n_classes, n_layers=2)
rt_sim = repro.Runtime.simulated(4)
rt_sm = repro.Runtime.from_mesh(repro.make_gnn_mesh(4))
pgs = {lay: repro.partition(g, n_parts=4, method="skewed", layout=lay)
       for lay in ("dense", "compact")}


def run(runtime, pg, mode, epochs):
    cfg = repro.SylvieConfig(mode=mode, bits=1, stochastic=False)
    return repro.train(model, pg, cfg, runtime=runtime, opt=opt.sgd(1e-1),
                       epochs=epochs)


for mode, epochs in (("sync", 3), ("async", 4)):
    ref = run(rt_sim, pgs["compact"], mode, epochs)
    for lay in ("dense", "compact"):
        b = run(rt_sm, pgs[lay], mode, epochs)
        np.testing.assert_allclose([m.loss for m in ref.history],
                                   [m.loss for m in b.history], rtol=1e-5)
        for pa, pb in zip(jax.tree.leaves(ref.state.params),
                          jax.tree.leaves(jax.device_get(b.state.params))):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                       rtol=1e-4, atol=1e-6)
print("OK")
"""


def test_shardmap_compact_parity_inline():
    """Runs when the session already has >= 4 devices (the CI --halo lane)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
    env = {"repro": __import__("repro.api", fromlist=["api"]),
           "jax": jax, "np": np}
    exec(textwrap.dedent(PARITY), env)


@pytest.mark.slow
def test_shardmap_compact_parity_subprocess():
    """Dense and compact plans under shard_map both match the simulated
    compact reference — losses and params, sync and async."""
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, {SRC!r})
        import jax, numpy as np
    """) + textwrap.dedent(PARITY)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "OK" in r.stdout
