"""GNN model zoo.

Paper models (evaluated in Sylvie): GCN, GraphSAGE, GAT.
Assigned architectures:  PNA, MeshGraphNet, SchNet (NequIP lives in nequip.py).

Uniform contract::

    model.comm_dims()                 -> feature width at each halo-exchange site
    model.init(key, d_in)             -> params pytree
    model.apply(params, block, x, comm) -> (P, n_local, d_out)

``comm`` is a :class:`repro.core.sylvie.SylvieComm`; every layer calls
``comm.halo(h)`` exactly once per site, in ``comm_dims`` order. Models never see
the communication mode — vanilla / Sylvie-S / Sylvie-A / bit-width are runtime
config, which is what makes the Low-bit Module a first-class framework feature.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import nn
from . import blocks as B


def _exchange_and_table(comm, block, h):
    halo = comm.halo(h)
    return B.halo_table(h, halo)


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GCN:
    """Kipf-Welling GCN, Alg. 1 form: H^{l} = sigma(A_hat^T H~^{l-1} W^{l})."""
    d_in: int
    d_hidden: int
    d_out: int
    n_layers: int = 2
    # agg_weighted's backward reads the block's edges sorted by source
    transposed_edges = True

    def comm_dims(self):
        return [self.d_in] + [self.d_hidden] * (self.n_layers - 1)

    def init(self, key):
        dims = [self.d_in] + [self.d_hidden] * (self.n_layers - 1) + [self.d_out]
        keys = jax.random.split(key, self.n_layers)
        return {f"layer{i}": nn.linear_init(keys[i], dims[i], dims[i + 1])
                for i in range(self.n_layers)}

    def apply(self, params, block, x, comm):
        h = x
        for i in range(self.n_layers):
            table = _exchange_and_table(comm, block, h)
            z = B.agg_weighted(block, table)
            h = nn.linear(params[f"layer{i}"], z)
            if i < self.n_layers - 1:
                h = jax.nn.relu(h)
        return h


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GraphSAGE:
    """SAGE-mean: h' = sigma(W_self h + W_nb mean_{u in N(v)} h_u)."""
    d_in: int
    d_hidden: int
    d_out: int
    n_layers: int = 2
    # agg_weighted's backward reads the block's edges sorted by source
    transposed_edges = True

    def comm_dims(self):
        return [self.d_in] + [self.d_hidden] * (self.n_layers - 1)

    def init(self, key):
        dims = [self.d_in] + [self.d_hidden] * (self.n_layers - 1) + [self.d_out]
        keys = jax.random.split(key, 2 * self.n_layers)
        return {f"layer{i}": {"self": nn.linear_init(keys[2 * i], dims[i], dims[i + 1]),
                              "nb": nn.linear_init(keys[2 * i + 1], dims[i], dims[i + 1],
                                                   bias=False)}
                for i in range(self.n_layers)}

    def apply(self, params, block, x, comm):
        h = x
        for i in range(self.n_layers):
            table = _exchange_and_table(comm, block, h)
            agg = B.agg_weighted(block, table, mean=True)
            h = nn.linear(params[f"layer{i}"]["self"], h) \
                + nn.linear(params[f"layer{i}"]["nb"], agg)
            if i < self.n_layers - 1:
                h = jax.nn.relu(h)
        return h


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GAT:
    """Multi-head GAT. We exchange the *projected* features Wh (width H*dh),
    halving comm vs raw features when d_in is wide; scores use the split-form
    a = [a_src ; a_dst] so each side is a local dot product."""
    d_in: int
    d_hidden: int          # per-head
    d_out: int
    n_layers: int = 2
    heads: int = 4

    def comm_dims(self):
        return [self.d_hidden * self.heads] * self.n_layers

    def init(self, key):
        p = {}
        d = self.d_in
        for i in range(self.n_layers):
            k1, k2, k3, key = jax.random.split(key, 4)
            p[f"layer{i}"] = {
                "w": nn.linear_init(k1, d, self.heads * self.d_hidden, bias=False),
                "a_src": jax.random.normal(k2, (self.heads, self.d_hidden)) * 0.1,
                "a_dst": jax.random.normal(k3, (self.heads, self.d_hidden)) * 0.1,
            }
            d = self.heads * self.d_hidden
        p["out"] = nn.linear_init(key, d, self.d_out)
        return p

    def apply(self, params, block, x, comm):
        h = x
        for i in range(self.n_layers):
            lp = params[f"layer{i}"]
            hw = nn.linear(lp["w"], h)                       # (P, n, H*dh) local
            table = _exchange_and_table(comm, block, hw)
            nh, dh = self.heads, self.d_hidden
            t4 = table.reshape(table.shape[:-1] + (nh, dh))
            s_all = jnp.einsum("...hd,hd->...h", t4, lp["a_src"])
            hw4 = hw.reshape(hw.shape[:-1] + (nh, dh))
            s_dst = jnp.einsum("...hd,hd->...h", hw4, lp["a_dst"])
            e_src = B.gather_src(block, s_all)               # (P, E, H)
            e_dst = B.gather_dst(block, s_dst)
            score = jax.nn.leaky_relu(e_src + e_dst, 0.2)
            alpha = B.edge_softmax(block, score)             # (P, E, H)
            v = B.gather_src(block, table).reshape(alpha.shape[:2] + (nh, dh))
            msg = (alpha[..., None] * v).reshape(alpha.shape[:2] + (nh * dh,))
            h = B.agg_sum(block, msg)
            if i < self.n_layers - 1:
                h = jax.nn.elu(h)
        return nn.linear(params["out"], h)


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PNA:
    """Principal Neighbourhood Aggregation: 4 aggregators x 3 degree scalers.
    [arXiv:2004.05718] — assigned config: 4 layers, d=75."""
    d_in: int
    d_hidden: int = 75
    d_out: int = 0
    n_layers: int = 4
    delta: float = 2.5     # E[log(deg+1)] normalizer (dataset statistic)

    def comm_dims(self):
        return [self.d_hidden] * self.n_layers

    def init(self, key):
        ke, key = jax.random.split(key)
        p = {"encoder": nn.linear_init(ke, self.d_in, self.d_hidden)}
        d = self.d_hidden
        for i in range(self.n_layers):
            k1, k2, key = jax.random.split(key, 3)
            p[f"layer{i}"] = {"pre": nn.linear_init(k1, 2 * d, d),
                              "post": nn.linear_init(k2, 12 * d, d)}
        p["out"] = nn.linear_init(key, d, self.d_out)
        return p

    def apply(self, params, block, x, comm):
        h = jax.nn.relu(nn.linear(params["encoder"], x))
        deg = B.degrees(block)
        logd = jnp.log1p(deg)[..., None]
        for i in range(self.n_layers):
            lp = params[f"layer{i}"]
            table = _exchange_and_table(comm, block, h)
            src = B.gather_src(block, table)
            dst = B.gather_dst(block, h)
            msg = jax.nn.relu(nn.linear(lp["pre"], jnp.concatenate([src, dst], -1)))
            aggs = [B.agg_mean(block, msg), B.agg_max(block, msg),
                    B.agg_min(block, msg), B.agg_std(block, msg)]
            a = jnp.concatenate(aggs, axis=-1)               # (P, n, 4d)
            amp = logd / self.delta
            att = self.delta / jnp.maximum(logd, 1e-6)
            scaled = jnp.concatenate([a, a * amp, a * att], axis=-1)  # (P, n, 12d)
            h = jax.nn.relu(h + nn.linear(lp["post"], scaled))
        return nn.linear(params["out"], h)


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MeshGraphNet:
    """Encode-process-decode with edge+node MLPs and residuals
    [arXiv:2010.03409] — assigned config: 15 layers, d=128, sum aggregator.
    ``edge_attr`` carries [dist, unit_vec] (4) computed host-side."""
    d_in: int
    d_hidden: int = 128
    d_out: int = 0
    n_layers: int = 15
    mlp_layers: int = 2
    d_edge_in: int = 4

    def comm_dims(self):
        return [self.d_hidden] * self.n_layers

    def _mlp_dims(self, d_in):
        return [d_in] + [self.d_hidden] * self.mlp_layers

    def init(self, key):
        kn, ke, ko, key = jax.random.split(key, 4)
        d = self.d_hidden
        p = {"enc_node": nn.mlp_init(kn, self._mlp_dims(self.d_in)),
             "enc_edge": nn.mlp_init(ke, self._mlp_dims(self.d_edge_in)),
             "decoder": nn.mlp_init(ko, [d, d, self.d_out])}
        for i in range(self.n_layers):
            k1, k2, key = jax.random.split(key, 3)
            p[f"proc{i}"] = {"edge": nn.mlp_init(k1, self._mlp_dims(3 * d)),
                             "node": nn.mlp_init(k2, self._mlp_dims(2 * d))}
        return p

    def apply(self, params, block, x, comm):
        h = nn.mlp(params["enc_node"], x)
        e = nn.mlp(params["enc_edge"], block.edge_attr[..., :self.d_edge_in])
        for i in range(self.n_layers):
            lp = params[f"proc{i}"]
            table = _exchange_and_table(comm, block, h)
            src = B.gather_src(block, table)
            dst = B.gather_dst(block, h)
            e = e + nn.mlp(lp["edge"], jnp.concatenate([e, src, dst], -1))
            agg = B.agg_sum(block, e)
            h = h + nn.mlp(lp["node"], jnp.concatenate([h, agg], -1))
        return nn.mlp(params["decoder"], h)


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SchNet:
    """SchNet continuous-filter convolutions [arXiv:1706.08566] — assigned
    config: 3 interactions, d=64, 300 RBFs, cutoff 10. ``edge_attr[..., 0]`` is
    the edge distance (host-side geometry)."""
    d_in: int
    d_hidden: int = 64
    d_out: int = 0
    n_interactions: int = 3
    n_rbf: int = 300
    cutoff: float = 10.0

    def comm_dims(self):
        return [self.d_hidden] * self.n_interactions

    def init(self, key):
        ke, ko, key = jax.random.split(key, 3)
        d = self.d_hidden
        p = {"embed": nn.linear_init(ke, self.d_in, d),
             "out": nn.mlp_init(ko, [d, d, self.d_out])}
        for i in range(self.n_interactions):
            k1, k2, k3, k4, key = jax.random.split(key, 5)
            p[f"int{i}"] = {
                "filter": nn.mlp_init(k1, [self.n_rbf, d, d]),
                "in": nn.linear_init(k2, d, d, bias=False),
                "dense1": nn.linear_init(k3, d, d),
                "dense2": nn.linear_init(k4, d, d),
            }
        return p

    def _rbf(self, dist):
        centers = jnp.linspace(0.0, self.cutoff, self.n_rbf)
        gamma = 0.5 * (self.n_rbf / self.cutoff) ** 2
        return jnp.exp(-gamma * (dist[..., None] - centers) ** 2)

    def apply(self, params, block, x, comm):
        h = nn.linear(params["embed"], x)
        rbf = self._rbf(block.edge_attr[..., 0])
        act = jax.nn.softplus
        for i in range(self.n_interactions):
            lp = params[f"int{i}"]
            w = nn.mlp(lp["filter"], rbf, act=act)           # (P, E, d)
            table = _exchange_and_table(comm, block, nn.linear(lp["in"], h))
            src = B.gather_src(block, table)
            agg = B.agg_sum(block, src * w)
            v = nn.linear(lp["dense2"], act(nn.linear(lp["dense1"], agg)))
            h = h + v
        return nn.mlp(params["out"], h, act=act)


# Canonical benchmark-scale factories for the paper's three architectures —
# the single definition the benchmark harness (benchmarks/common.MODELS) and
# the scenario runner (launch/scenarios.ARCHS) both resolve "gcn" /
# "graphsage" / "gat" through, so a scenario report and a fig/table row with
# the same arch name are always the same model.
PAPER_ARCHS = {
    "gcn": lambda d_in, d_out: GCN(d_in, 64, d_out, n_layers=2),
    "graphsage": lambda d_in, d_out: GraphSAGE(d_in, 64, d_out, n_layers=2),
    "gat": lambda d_in, d_out: GAT(d_in, 16, d_out, n_layers=2, heads=4),
}
