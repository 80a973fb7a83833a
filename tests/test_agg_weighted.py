"""Destination-sorted edges and ``agg_weighted``: GCN's and GraphSAGE-mean's
weighted neighbour sum as one gather-weight-scatter over the sorted edges,
transposed over the edges sorted by source.

``agg_weighted`` and its VJP must equal the per-edge path (``gather_src`` x
weight -> ``agg_sum``, or ``agg_mean``, and autodiff's transpose) bit for bit:
each row's sum runs in the per-edge path's edge order. That holds in the
simulated stack and under ``shard_map`` on four virtual CPU devices (one
subprocess serves every ``shard_map`` case: jax fixes the device count when
it first starts). The models whose message is a per-edge function keep the
per-edge scatter.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.sylvie import SylvieConfig
from repro.graph import formats, partition
from repro.models.gnn import blocks as B
from repro.models.gnn.models import GAT, GCN, PNA, GraphSAGE
from repro.train import optimizer as opt
from repro.train.gnn_step import GNNTrainState, make_gnn_steps
from repro.train.trainer import GNNTrainer

SRC = str(Path(__file__).resolve().parents[1] / "src")
KEY = jax.random.PRNGKey(0)
PARTS = 4
D = 6
HUB = 150          # a destination with 24 extra in-edges from other partitions


def graph():
    """Random edges plus a hub destination; 301 nodes, so three of four
    partitions end in a padded row with no edges."""
    n = 301
    rng = np.random.default_rng(0)
    src = rng.integers(0, n, 900)
    dst = rng.integers(0, n, 900)
    hub = np.arange(10, 10 + 24) * 12 % n
    src = np.concatenate([src, hub])
    dst = np.concatenate([dst, np.full(hub.size, HUB)])
    keep = src != dst
    ei = np.stack([src[keep], dst[keep]]).astype(np.int32)
    x = rng.standard_normal((n, D)).astype(np.float32)
    g = formats.Graph(n, ei, x, rng.integers(0, 3, n).astype(np.int32),
                      np.ones(n, bool), np.ones(n, bool), np.ones(n, bool),
                      n_classes=3)
    return formats.gcn_normalize(g)


def partitioned(layout):
    g, ew = graph()
    return partition.partition_graph(g, PARTS, edge_weight=ew, layout=layout)


def old_path(block, table, weights):
    msgs = B.gather_src(block, table)
    if weights == "unit":
        return B.agg_mean(block, msgs)
    return B.agg_sum(block, msgs * block.edge_weight[..., None])


def new_path(block, table, weights):
    return B.agg_weighted(block, table, mean=weights == "unit")


def readings(pg, weights, run=lambda f: f):
    """(new, old) outputs and (new, old) table cotangents."""
    block = B.build_block(pg)
    rows = block.n_local + block.plan.halo_rows
    table = jax.random.normal(KEY, (PARTS, rows, D))
    ct = jax.random.normal(jax.random.fold_in(KEY, 1),
                           (PARTS, block.n_local, D))

    def both(blk, t, c):
        outs = []
        for path in (new_path, old_path):
            y, vjp = jax.vjp(lambda tt: path(blk, tt, weights), t)
            outs += [y, vjp(c)[0]]
        return tuple(outs)

    return [np.asarray(a) for a in run(both)(block, table, ct)]


def _runs(keys):
    """Lengths of the runs of equal values in a sorted 1-D array."""
    return np.diff(np.flatnonzero(np.diff(keys, prepend=-1, append=-1)))


def test_graph_exercises_the_edge_cases():
    """Padded node rows, padded edges, halo rows no edge reads, and a
    destination with many in-edges."""
    for layout in ("compact", "dense"):
        pg = partitioned(layout)
        assert (~pg.node_mask).any()
        assert (~pg.edge_mask).any()
        read = np.zeros((PARTS, pg.plan.n_local + pg.plan.halo_rows), bool)
        for p in range(PARTS):
            read[p, pg.edges[p, pg.edge_mask[p], 0]] = True
        assert (~read[:, pg.plan.n_local:]).any()
        assert _runs(pg.edges[pg.edge_mask][:, 1]).max() >= 24


@pytest.mark.parametrize("layout", ["compact", "dense"])
def test_edges_sorted_by_destination_in_edge_order(layout):
    """Each partition's real edges come first, sorted by destination, each
    destination's sources in the graph's edge order; the padding edges
    address the last local row and the last table row."""
    g, ew = graph()
    pg = partitioned(layout)
    src_g, dst_g = partition.global_edges(pg)
    n_local, rows = pg.plan.n_local, pg.plan.n_local + pg.plan.halo_rows
    start = 0
    for p in range(PARTS):
        k = int(pg.edge_mask[p].sum())
        assert pg.edge_mask[p, :k].all() and not pg.edge_mask[p, k:].any()
        assert (np.diff(pg.edges[p, :k, 1]) >= 0).all()
        assert (pg.edges[p, k:] == (rows - 1, n_local - 1)).all()
        assert not pg.edge_weight[p, k:].any()
        for v in np.unique(pg.edges[p, :k, 1]):
            sel = pg.edges[p, :k, 1] == v
            gid = pg.global_ids[p, v]
            got = src_g[start:start + k][sel]
            want_sel = g.edge_index[1] == gid
            np.testing.assert_array_equal(got, g.edge_index[0][want_sel])
            np.testing.assert_array_equal(pg.edge_weight[p, :k][sel],
                                          ew[want_sel])
        assert (dst_g[start:start + k] == pg.global_ids[p, pg.edges[p, :k, 1]]).all()
        start += k


@pytest.mark.parametrize("layout", ["compact", "dense"])
def test_transposed_edges_hold_every_edge_once(layout):
    """``edges_t`` holds each partition's edges, each once with its weight,
    sorted by source, each source's destinations in edge order; the padding
    stays last, so ``edge_mask`` masks both orders."""
    pg = partitioned(layout)
    blk = B.build_block(pg)
    et, wt = np.asarray(blk.edges_t), np.asarray(blk.edge_weight_t)
    for p in range(PARTS):
        k = int(pg.edge_mask[p].sum())
        assert (np.diff(et[p, :, 1]) >= 0).all()
        want = sorted(zip(pg.edges[p, :, 0].tolist(), pg.edges[p, :, 1].tolist(),
                          pg.edge_weight[p].tolist()))
        got = sorted(zip(et[p, :, 1].tolist(), et[p, :, 0].tolist(),
                         wt[p].tolist()))
        assert got == want
        assert not wt[p, k:].any()
        for u in np.unique(et[p, :k, 1]):
            fwd = pg.edges[p, :k, 1][pg.edges[p, :k, 0] == u]
            np.testing.assert_array_equal(et[p, :k, 0][et[p, :k, 1] == u], fwd)


@pytest.mark.parametrize("layout", ["compact", "dense"])
def test_degrees_count_the_real_in_edges(layout):
    pg = partitioned(layout)
    want = np.stack([np.bincount(pg.edges[p, pg.edge_mask[p], 1],
                                 minlength=pg.plan.n_local)
                     for p in range(PARTS)]).astype(np.float32)
    got = np.asarray(B.degrees(B.build_block(pg)))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


SHARDED = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{src!r}, {tests!r}]
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import test_agg_weighted as T

    mesh = jax.make_mesh((4,), ("parts",))
    out = {{}}
    for layout in ("compact", "dense"):
        pg = T.partitioned(layout)
        for weights in ("gcn", "unit"):
            def run(f):
                return jax.jit(jax.shard_map(
                    f, mesh=mesh, in_specs=P("parts"), out_specs=P("parts"),
                    check_vma=False))
            r = T.readings(pg, weights, run)
            out[f"{{layout}}-{{weights}}"] = [a.tolist() for a in r]
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_readings():
    code = SHARDED.format(src=SRC, tests=str(Path(__file__).parent))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("runtime", ["simulated", "shard_map"])
@pytest.mark.parametrize("layout", ["compact", "dense"])
@pytest.mark.parametrize("weights", ["gcn", "unit"])
def test_agg_weighted_equals_per_edge_path(weights, layout, runtime, request):
    if runtime == "simulated":
        r = readings(partitioned(layout), weights, jax.jit)
    else:
        r = [np.asarray(a, np.float32) for a in
             request.getfixturevalue("sharded_readings")[f"{layout}-{weights}"]]
    y_new, g_new, y_old, g_old = r
    assert np.abs(y_old).max() > 0 and np.abs(g_old).max() > 0
    np.testing.assert_array_equal(y_new, y_old)
    np.testing.assert_array_equal(g_new, g_old)


def test_gcn_aggregation_without_weights_raises():
    g, _ = graph()
    pg = partition.partition_graph(g, PARTS)
    blk = B.build_block(pg)
    table = jnp.zeros((PARTS, blk.n_local + blk.plan.halo_rows, D))
    with pytest.raises(ValueError, match="edge weights"):
        B.agg_weighted(blk, table)
    assert B.agg_weighted(blk, table, mean=True).shape == (PARTS, blk.n_local, D)


def test_forward_only_block_differentiates_with_a_clear_error():
    """A block built without the transposed edges serves the forward (as
    the serving engine does) and refuses the backward by name."""
    pg = partitioned("compact")
    blk = B.build_block(pg, transposed=False)
    assert blk.edges_t is None and blk.edge_weight_t is None
    table = jax.random.normal(KEY, (PARTS, blk.n_local + blk.plan.halo_rows, D))
    np.testing.assert_array_equal(B.agg_weighted(blk, table),
                                  B.agg_weighted(B.build_block(pg), table))
    with pytest.raises(ValueError, match="transposed=True"):
        jax.grad(lambda t: B.agg_weighted(blk, t).sum())(table)


@pytest.mark.parametrize("arch,reads", [("gcn", True), ("sage", True),
                                        ("gat", False)])
def test_trainer_builds_transposed_edges_for_models_that_read_them(arch, reads):
    pg = partitioned("compact")
    model = {"gcn": GCN(D, 8, 3), "sage": GraphSAGE(D, 8, 3),
             "gat": GAT(D, 4, 3, heads=2)}[arch]
    tr = GNNTrainer(model, pg, SylvieConfig(mode="vanilla"))
    assert (tr.block.edges_t is not None) == reads
    assert np.isfinite(tr.train_epoch().loss)


def _scatter_adds(jaxpr):
    """``(updates shape, indices_are_sorted)`` of every scatter-add in
    ``jaxpr`` and in the jaxprs nested in its equations."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            out.append((eqn.invars[2].aval.shape,
                        eqn.params["indices_are_sorted"]))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _scatter_adds(sub)
    return out


def _traced_step(model):
    pg = partitioned("compact")
    blk = B.build_block(pg)
    o = opt.adam(1e-2)
    ts, _, _ = make_gnn_steps(model, SylvieConfig(mode="vanilla"), o)
    st = GNNTrainState.create(model, o, KEY, blk.plan, stacked_parts=PARTS)
    args = (st, blk, jnp.asarray(pg.x), jnp.asarray(pg.y),
            jnp.asarray(pg.train_mask), KEY)
    obs.reset_metrics()
    jaxpr = jax.make_jaxpr(ts)(*args)
    return (obs.counter("aggregation.slotted").value,
            _scatter_adds(jaxpr.jaxpr), pg.edges.shape[1])


@pytest.mark.parametrize("arch", ["gcn", "sage", "gat", "pna"])
def test_slotted_counter_and_per_edge_bypass(arch):
    """A traced GCN or SAGE step counts two ``agg_weighted`` calls (one a
    layer), and each of its scatter-adds over the edges, backward included,
    runs over the partitions' edges folded into one sorted axis; GAT and PNA
    count none and keep their per-edge scatter-add over each partition's
    edge axis, whose autodiff transpose scatters unsorted."""
    model = {"gcn": GCN(D, 8, 3), "sage": GraphSAGE(D, 8, 3),
             "gat": GAT(D, 4, 3, heads=2),
             "pna": PNA(D, d_hidden=8, d_out=3, n_layers=2)}[arch]
    n, scatters, e_pad = _traced_step(model)
    folded = [s for shape, s in scatters if shape[0] == PARTS * e_pad]
    per_edge = [s for shape, s in scatters if shape[:2] == (PARTS, e_pad)]
    if arch in ("gcn", "sage"):
        assert n == 2 and len(folded) == 3 and all(folded) and not per_edge
    else:
        assert n == 0 and not folded
        assert any(per_edge) and not all(per_edge)
