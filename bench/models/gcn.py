"""GCN (Kipf and Welling): ``h' = act(A_hat (h) W + b)`` with the
symmetric-normalised adjacency of A + I; ReLU between layers."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init(key, dims):
    """Glorot-uniform weights, zero biases, as ``{"layer<i>": {"w", "b"}}``."""
    keys = jax.random.split(key, len(dims) - 1)
    out = {}
    for i in range(len(dims) - 1):
        lim = math.sqrt(6.0 / (dims[i] + dims[i + 1]))
        out[f"layer{i}"] = {
            "w": jax.random.uniform(keys[i], (dims[i], dims[i + 1]),
                                    jnp.float32, -lim, lim),
            "b": jnp.zeros((dims[i + 1],), jnp.float32)}
    return out


def layer(p, h, table, ctx, last):
    out = ctx.mm(ctx.sum_w(table), p["w"]) + p["b"].astype(table.dtype)
    return out if last else jax.nn.relu(out)


def forward_flops(n, e, dims):
    """FLOPs of one forward pass: the weighted aggregation over ``e`` edges
    and the dense update over ``n`` nodes, per layer."""
    return sum(2 * e * dims[i] + 2 * n * dims[i] * dims[i + 1]
               for i in range(len(dims) - 1))


def program_model(d_in, d_hidden, d_out, n_layers):
    from repro.models.gnn.models import GCN
    return GCN(d_in=d_in, d_hidden=d_hidden, d_out=d_out, n_layers=n_layers)
