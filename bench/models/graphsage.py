"""GraphSAGE-mean: ``h' = act(h W_self + b + mean_{u -> v} h_u W_nb)``, the
mean over the in-edges of A + I; ReLU between layers."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _glorot(key, d_in, d_out):
    lim = math.sqrt(6.0 / (d_in + d_out))
    return jax.random.uniform(key, (d_in, d_out), jnp.float32, -lim, lim)


def init(key, dims):
    """``{"layer<i>": {"self": {"w", "b"}, "nb": {"w"}}}``."""
    keys = jax.random.split(key, 2 * (len(dims) - 1))
    return {f"layer{i}": {
        "self": {"w": _glorot(keys[2 * i], dims[i], dims[i + 1]),
                 "b": jnp.zeros((dims[i + 1],), jnp.float32)},
        "nb": {"w": _glorot(keys[2 * i + 1], dims[i], dims[i + 1])}}
        for i in range(len(dims) - 1)}


def layer(p, h, table, ctx, last):
    deg = jnp.maximum(ctx.deg, 1.0)[:, None].astype(table.dtype)
    agg = ctx.sum_1(table) / deg
    out = (ctx.mm(h, p["self"]["w"]) + p["self"]["b"].astype(h.dtype)
           + ctx.mm(agg, p["nb"]["w"]))
    return out if last else jax.nn.relu(out)


def forward_flops(n, e, dims):
    """The sum aggregation over ``e`` edges and two dense updates over ``n``
    nodes, per layer."""
    return sum(2 * e * dims[i] + 4 * n * dims[i] * dims[i + 1]
               for i in range(len(dims) - 1))


def program_model(d_in, d_hidden, d_out, n_layers):
    from repro.models.gnn.models import GraphSAGE
    return GraphSAGE(d_in=d_in, d_hidden=d_hidden, d_out=d_out,
                     n_layers=n_layers)
