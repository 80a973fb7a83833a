"""Sylvie core: halo exchange semantics, quantized custom_vjp, staleness."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import quantization as q
from repro.core.exchange import (PlanArrays, exchange, gather_boundary,
                                 scatter_boundary_grad)
from repro.core.staleness import HaloState, use_sync_step
from repro.core.sylvie import SylvieComm, SylvieConfig, quantized_halo
from repro.graph import formats, partition, synthetic
from repro.models.gnn import blocks as B
from repro.models.gnn.models import GAT, GCN
from repro.train import optimizer as opt
from repro.train.gnn_step import GNNTrainState, make_gnn_steps

KEY = jax.random.PRNGKey(0)


def _setup(n=300, p=4, d=16, seed=0):
    g = synthetic.planted_partition(n_nodes=n, d_feat=d, seed=seed)
    ei = formats.add_self_loops(g.edge_index, g.n_nodes)
    ew = formats.gcn_edge_weights(ei, g.n_nodes)
    g = formats.Graph(g.n_nodes, ei, g.x, g.y, g.train_mask, g.val_mask,
                      g.test_mask, n_classes=g.n_classes)
    pg = partition.partition_graph(g, p, edge_weight=ew)
    return g, pg, B.build_block(pg)


def test_exchange_is_transpose_involution():
    p, h, d = 4, 3, 5
    x = jax.random.normal(KEY, (p, p * h, d))
    y = exchange(x, None)
    # transpose: out[p, q*h+s] = in[q, p*h+s]
    for pi in range(p):
        for qi in range(p):
            np.testing.assert_allclose(
                np.asarray(y[pi, qi * h:(qi + 1) * h]),
                np.asarray(x[qi, pi * h:(pi + 1) * h]))
    np.testing.assert_allclose(np.asarray(exchange(y, None)), np.asarray(x))


def test_vanilla_halo_matches_global_gather():
    """bits=32 halo exchange delivers exactly the neighbors' features."""
    g, pg, block = _setup()
    x = jnp.asarray(pg.x)
    comm = SylvieComm(SylvieConfig(mode="vanilla"), block.plan, KEY)
    halo = comm.halo(x)
    table = B.halo_table(x, halo)
    src_feats = B.gather_src(block, table)
    # compare against a global gather
    glob_x = g.x
    for pi in range(pg.n_parts):
        for k in range(0, int(pg.edge_mask[pi].sum()), 7):
            s_gid_feat = np.asarray(src_feats[pi, k])
            # find edge endpoints in global terms
            d_loc = pg.edges[pi, k, 1]
            # recompute src gid from reconstruction logic
    # spot-check sums: aggregated features equal the global aggregation
    agg = B.agg_sum(block, src_feats * block.edge_weight[..., None])
    glob_agg = np.zeros_like(glob_x)
    ew = formats.gcn_edge_weights(g.edge_index, g.n_nodes)
    np.add.at(glob_agg, g.edge_index[1], glob_x[g.edge_index[0]] * ew[:, None])
    back = pg.unpartition(np.asarray(agg))
    np.testing.assert_allclose(back, glob_agg, rtol=1e-4, atol=1e-5)


def test_quantized_halo_unbiased():
    _, pg, block = _setup(n=120, p=3, d=8)
    x = jnp.asarray(pg.x)
    cfgv = SylvieConfig(mode="vanilla")
    ref = SylvieComm(cfgv, block.plan, KEY).halo(x)
    acc = jnp.zeros_like(ref)
    n = 300
    for i in range(n):
        comm = SylvieComm(SylvieConfig(mode="sync", bits=1), block.plan,
                          jax.random.fold_in(KEY, i))
        acc = acc + comm.halo(x)
    err = np.abs(np.asarray(acc / n) - np.asarray(ref))
    mask = np.asarray(block.plan.recv_mask)
    # 1-bit stochastic rounding: per-element SE of the mean <= range/(2 sqrt n)
    rng_rows = (np.asarray(x).max(-1) - np.asarray(x).min(-1)).max()
    se = rng_rows / (2 * np.sqrt(n))
    mean_err = err[mask].mean()
    assert mean_err < 3 * se * np.sqrt(2 / np.pi), (mean_err, se)


def test_backward_scatter_adds_duplicate_sends():
    """A node sent to multiple partitions accumulates all their gradients."""
    _, pg, block = _setup(n=80, p=4, d=4)
    plan = block.plan
    x = jnp.asarray(pg.x)

    def f(h):
        halo = quantized_halo(h, plan, KEY, KEY, 32, 32, False, jnp.bfloat16,
                              None, "jnp")
        return (halo ** 2).sum() / 2

    g = jax.grad(f)(x)
    # expected: each sent node's grad = sum over receivers of its value
    sends = np.asarray(plan.send_mask).reshape(plan.n_parts, -1)
    idx = np.asarray(plan.send_idx)
    expected = np.zeros_like(np.asarray(x))
    for p in range(plan.n_parts):
        for slot in range(idx.shape[1]):
            if sends[p, slot]:
                expected[p, idx[p, slot]] += np.asarray(x)[p, idx[p, slot]]
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-4, atol=1e-5)


def test_async_one_step_staleness_dataflow():
    """Async step consumes exactly the previous step's halo features."""
    _, pg, block = _setup(n=100, p=4, d=8)
    model = GCN(d_in=8, d_hidden=16, d_out=4, n_layers=2)
    o = opt.sgd(0.0)   # freeze params: isolates the cache dataflow
    cfg = SylvieConfig(mode="async", bits=32, stochastic=False)
    ts, ta, _ = make_gnn_steps(model, cfg, o)
    st = GNNTrainState.create(model, o, KEY, block.plan, stacked_parts=4)
    x = jnp.asarray(pg.x); y = jnp.asarray(pg.y); m = jnp.asarray(pg.train_mask)
    st1, _ = jax.jit(ts)(st, block, x, y, m, KEY)     # warmup: fills caches
    # with frozen params, the async step's fresh caches equal the sync ones
    st2, _ = jax.jit(ta)(st1, block, x, y, m, KEY)
    for a, b in zip(st1.halo.feats, st2.halo.feats):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_async_converges_on_planted_graph():
    _, pg, block = _setup(n=400, p=4, d=24, seed=3)
    model = GCN(d_in=24, d_hidden=32, d_out=7, n_layers=2)
    o = opt.adam(1e-2)
    cfg = SylvieConfig(mode="async", bits=1)
    ts, ta, ev = make_gnn_steps(model, cfg, o)
    st = GNNTrainState.create(model, o, KEY, block.plan, stacked_parts=4)
    x = jnp.asarray(pg.x); y = jnp.asarray(pg.y); m = jnp.asarray(pg.train_mask)
    ts = jax.jit(ts); ta = jax.jit(ta)
    st, _ = ts(st, block, x, y, m, KEY)
    for i in range(40):
        st, loss = ta(st, block, x, y, m, jax.random.fold_in(KEY, i))
    c, n = jax.jit(ev)(st.params, block, x, y, jnp.asarray(pg.test_mask), KEY)
    assert float(c) / float(n) > 0.8


def test_bounded_staleness_schedule():
    assert use_sync_step(0, None) is True           # warmup
    assert use_sync_step(3, None) is False          # pure async
    assert [use_sync_step(e, 3) for e in range(7)] == \
        [True, False, False, True, False, False, True]
    assert all(use_sync_step(e, 1) for e in range(5))


def test_halo_state_pytree():
    _, pg, block = _setup(n=60, p=2, d=4)
    hs = HaloState.zeros(block.plan, [4, 8], stacked_parts=2)
    leaves = jax.tree.leaves(hs)
    assert len(leaves) == 4
    assert all(l.shape[0] == 2 for l in leaves)


@dataclasses.dataclass(frozen=True)
class _FeatureCopy:
    """Hands the wrapped model ``x + 0.0``: equal values, but a new array, so
    the first exchange no longer sees the step's own feature input and keeps
    its backward (the pre-pruning program)."""
    inner: object

    def comm_dims(self):
        return self.inner.comm_dims()

    def init(self, key):
        return self.inner.init(key)

    def apply(self, params, block, x, comm):
        return self.inner.apply(params, block, x + 0.0, comm)


def _async_run(model, schedule="blocking", epochs=4):
    """One sync warm-up epoch, then ``epochs`` async 1-bit stochastic epochs;
    returns (state, losses, jitted async step, data args)."""
    _, pg, block = _setup(n=400, p=4, d=24, seed=3)
    o = opt.adam(1e-2)
    cfg = SylvieConfig(mode="async", bits=1, schedule=schedule)
    ts, ta, _ = make_gnn_steps(model, cfg, o)
    st = GNNTrainState.create(model, o, KEY, block.plan, stacked_parts=4)
    args = (block, jnp.asarray(pg.x), jnp.asarray(pg.y),
            jnp.asarray(pg.train_mask))
    st, _ = jax.jit(ts)(st, *args, KEY)
    ta = jax.jit(ta)
    losses = []
    for i in range(epochs):
        st, loss = ta(st, *args, jax.random.fold_in(KEY, i))
        losses.append(float(loss))
    return st, losses, ta, args


@pytest.mark.parametrize("schedule", ["blocking", "overlap"])
def test_async_feature_site_pruning_is_exact(schedule):
    """Pruning the backward of the site fed by the node features changes no
    loss and no parameter, to the bit; only that site's gradient cache
    differs: it reads zeros, where the unpruned program fills it with
    gradients nothing reads."""
    model = GCN(d_in=24, d_hidden=32, d_out=7)
    st, losses, _, _ = _async_run(model, schedule)
    st_ref, losses_ref, _, _ = _async_run(_FeatureCopy(model), schedule)
    assert losses == losses_ref
    for a, b in zip(jax.tree.leaves(st.params), jax.tree.leaves(st_ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(st.halo.grads[1]),
                                  np.asarray(st_ref.halo.grads[1]))
    assert not np.any(np.asarray(st.halo.grads[0]))
    assert np.any(np.asarray(st_ref.halo.grads[0]))


def _scatter_widths(hlo: str) -> list[int]:
    return [int(w) for w in re.findall(
        r"f32\[(?:\d+,)*(\d+)\]\{[\d,]*\} scatter\(", hlo)]


def test_async_feature_site_drops_its_input_width_scatter():
    """The compiled async GCN step holds one scatter at the input width fewer
    than the unpruned program (the transpose into the [x ; halo] table) and
    no more argument bytes (the pruned site's incoming gradient cache is not
    read), and each trace counts one pruned site."""
    model = GCN(d_in=24, d_hidden=32, d_out=7)
    widths, arg_bytes = {}, {}
    for name, m in (("pruned", model), ("unpruned", _FeatureCopy(model))):
        obs.reset_metrics()
        st, _, ta, args = _async_run(m, epochs=1)
        assert obs.counter("halo.bwd_pruned").value == (
            1 if name == "pruned" else 0)
        compiled = ta.lower(st, *args, KEY).compile()
        widths[name] = _scatter_widths(compiled.as_text())
        arg_bytes[name] = compiled.memory_analysis().argument_size_in_bytes
    assert widths["pruned"].count(24) == widths["unpruned"].count(24) - 1
    assert widths["pruned"].count(32) == widths["unpruned"].count(32)
    assert arg_bytes["pruned"] <= arg_bytes["unpruned"]


def test_async_gat_keeps_feature_site_backward():
    """GAT exchanges x·W, which does take a gradient: its first site is not
    pruned and still sends boundary gradients."""
    obs.reset_metrics()
    st, _, _, _ = _async_run(GAT(d_in=24, d_hidden=8, d_out=7, heads=2),
                             epochs=1)
    assert obs.counter("halo.bwd_pruned").value == 0
    assert np.any(np.asarray(st.halo.grads[0]))
