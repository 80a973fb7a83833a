"""Multi-device equivalence tests. Each test forks a subprocess that sets
--xla_force_host_platform_device_count (jax locks device count at first init,
and the rest of the suite must see the real single device)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(body: str, devices: int = 8):
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys; sys.path.insert(0, {SRC!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout


GNN_EQUIV = """
from repro.graph import synthetic, partition, formats
from repro.models.gnn import models as M, blocks as B
from repro.core.sylvie import SylvieConfig
from repro.train.gnn_step import GNNTrainState, make_gnn_steps
from repro.train import optimizer as opt
from repro.dist import api as dist

P_ = 8
g = synthetic.planted_partition(n_nodes=800, d_feat=32)
ei = formats.add_self_loops(g.edge_index, g.n_nodes)
ew = formats.gcn_edge_weights(ei, g.n_nodes)
g2 = formats.Graph(g.n_nodes, ei, g.x, g.y, g.train_mask, g.val_mask,
                   g.test_mask, n_classes=g.n_classes)
pg = partition.partition_graph(g2, P_, edge_weight=ew)
block = B.build_block(pg)
model = M.GCN(d_in=32, d_hidden=64, d_out=g.n_classes, n_layers=2)
o = opt.sgd(1e-1)   # scale-sensitive: catches any grad-scaling bug
key = jax.random.PRNGKey(0)
x = jnp.asarray(pg.x); y = jnp.asarray(pg.y); m = jnp.asarray(pg.train_mask)

cfg = SylvieConfig(mode="sync", bits=1, stochastic=False)
ts_sim, ta_sim, _ = make_gnn_steps(model, cfg, o)
st_sim = GNNTrainState.create(model, o, key, block.plan, stacked_parts=P_)
st_sim, _ = jax.jit(ts_sim)(st_sim, block, x, y, m, key)
st_sim, loss_sim = jax.jit(ta_sim)(st_sim, block, x, y, m, key)

from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((4, 2), ("data", "model"))
ts_sm, ta_sm, ev_sm = make_gnn_steps(model, cfg, o,
                                     backend=dist.ShardMapBackend(mesh))
st = GNNTrainState.create(model, o, key, block.plan, stacked_parts=P_)
ts_w, ta_w, ev_w = dist.shard_gnn_steps(ts_sm, ta_sm, ev_sm, mesh, st, block)
st_d, block_d, arrs = dist.device_put_gnn(mesh, st, block, (x, y, m))
st_d, _ = ts_w(st_d, block_d, *arrs, key)
st_d, loss_sm = ta_w(st_d, block_d, *arrs, key)
np.testing.assert_allclose(float(loss_sim), float(loss_sm), rtol=1e-5)
for a, b in zip(jax.tree.leaves(st_sim.params),
                jax.tree.leaves(jax.device_get(st_d.params))):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-6)
c, n = ev_w(st_d.params, block_d, *arrs[:2], arrs[2], key)
print("OK", float(loss_sm))
"""


DLRM_EQUIV = """
from repro.models.recsys import dlrm as D
from repro.train import optimizer as opt

cfg = D.DLRMConfig(n_dense=13, embed_dim=16, table_sizes=(50, 30, 20, 40),
                   bot_mlp=(32, 16), top_mlp=(64, 32, 1), hot=(2, 1, 1, 3))
key = jax.random.PRNGKey(0)
dp = D.init_dense_params(key, cfg)
B = 32
offs = cfg.row_offsets
rng = np.random.default_rng(0)
ids = np.concatenate([rng.integers(offs[f], offs[f+1], (B, h))
                      for f, h in enumerate(cfg.hots)],
                     axis=1).reshape(-1).astype(np.int32)
dx = jnp.asarray(rng.normal(0, 1, (B, 13)), jnp.float32)
labels = jnp.asarray(rng.integers(0, 2, B), jnp.float32)
tb1 = D.init_table(jax.random.fold_in(key, 1), cfg, n_dev=1)
o = opt.sgd(0.5)
step1 = jax.jit(D.make_train_step(cfg, o, None))
st = (dp, tb1, o.init(dp), o.init(tb1), jnp.zeros((), jnp.int32))
for i in range(8):
    st, loss1 = step1(st, dx, jnp.asarray(ids), labels, key)
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((4, 2), ("data", "model"))
ax = ("data", "model")
rpd = D.rows_per_device(cfg, 8)
tb8 = jnp.pad(tb1, ((0, rpd*8 - tb1.shape[0]), (0, 0)))
shard = P(ax); rep = P()
sm = jax.jit(jax.shard_map(D.make_train_step(cfg, o, ax), mesh=mesh,
    in_specs=((rep, shard, rep, (), rep), shard, shard, shard, rep),
    out_specs=((rep, shard, rep, (), rep), rep), check_vma=False))
st8 = (dp, tb8, o.init(dp), o.init(tb8), jnp.zeros((), jnp.int32))
for i in range(8):
    st8, loss8 = sm(st8, dx, jnp.asarray(ids), labels, key)
np.testing.assert_allclose(float(loss1), float(loss8), rtol=1e-4)
np.testing.assert_allclose(np.asarray(st[1])[:cfg.total_rows],
    np.asarray(jax.device_get(st8[1]))[:cfg.total_rows], rtol=1e-3, atol=1e-5)
# quantized embedding exchange (beyond-paper) trains too
cfgq = D.DLRMConfig(n_dense=13, embed_dim=16, table_sizes=(50, 30, 20, 40),
                    bot_mlp=(32, 16), top_mlp=(64, 32, 1), hot=(2, 1, 1, 3),
                    quantize_collective_bits=8)
smq = jax.jit(jax.shard_map(D.make_train_step(cfgq, o, ax), mesh=mesh,
    in_specs=((rep, shard, rep, (), rep), shard, shard, shard, rep),
    out_specs=((rep, shard, rep, (), rep), rep), check_vma=False))
stq = (dp, tb8, o.init(dp), o.init(tb8), jnp.zeros((), jnp.int32))
for i in range(8):
    stq, lossq = smq(stq, dx, jnp.asarray(ids), labels,
                     jax.random.fold_in(key, i))
assert abs(float(lossq) - float(loss8)) < 0.1
print("OK", float(loss8), float(lossq))
"""


LM_GSPMD = """
import sys; sys.path.insert(0, {src!r})
from repro import configs as configlib
from repro.models.lm import model as LM
from repro.models.lm import sharding as lm_sharding
from repro.train import optimizer as optlib
from jax.sharding import NamedSharding

cfg = configlib.get("olmoe-1b-7b").reduced()
key = jax.random.PRNGKey(0)
params = LM.init_params(key, cfg, dtype=jnp.float32)
tokens = jax.random.randint(key, (8, 16), 0, cfg.vocab)
labels = jax.random.randint(jax.random.fold_in(key, 1), (8, 16), 0, cfg.vocab)
o = optlib.adam(1e-3)
state = (params, o.init(params), jnp.zeros((), jnp.int32))
ts = jax.jit(LM.make_train_step(cfg, o))
state1, loss1 = ts(state, tokens, labels)

from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 2), ("data", "model"))
p_specs = lm_sharding.param_specs(params, cfg, mesh)
pp = jax.device_put(params, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                         p_specs))
state_d = (pp, o.init(pp), jnp.zeros((), jnp.int32))
LM.set_shard_ctx(LM.shard_ctx_from_mesh(mesh))
with jax.set_mesh(mesh):
    ts_d = jax.jit(LM.make_train_step(cfg, o))
    state2, loss2 = ts_d(state_d, tokens, labels)
LM.set_shard_ctx(None)
np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-4)
for a, b in zip(jax.tree.leaves(state1[0]),
                jax.tree.leaves(jax.device_get(state2[0]))):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-4)
print("OK", float(loss2))
"""


@pytest.mark.slow
def test_gnn_shard_map_equals_simulated():
    assert "OK" in _run(GNN_EQUIV)


@pytest.mark.slow
def test_dlrm_shard_map_equals_single_device():
    assert "OK" in _run(DLRM_EQUIV)


@pytest.mark.slow
def test_lm_gspmd_sharded_equals_single_device():
    assert "OK" in _run(LM_GSPMD.format(src=SRC), devices=4)
