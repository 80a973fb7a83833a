"""Traffic generator: the benchmark's own copy of the power-law community graph.

A full-graph training cell's "traffic" is the graph it trains on. This is a
copy of the program's ``powerlaw_community`` generator, kept here so that the
yardstick does not move when the program's generator is rewritten: the same
keyword arguments and graph seed give the same arrays forever.

Each node attaches ``avg_degree // 2`` edges; with probability ``p_in`` the
target is drawn popularity-weighted inside the node's own class, otherwise
popularity-weighted over all nodes. Popularity is Zipf-like with exponent
``gamma`` over a random permutation of the nodes. Features are Gaussian class
means plus ``noise``. Both edge directions are stored.

:func:`with_self_loops` and :func:`gcn_weights` are the benchmark's own
normalisation, used by the plain reference (``bench/reference.py``).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    n_nodes: int
    src: np.ndarray          # (E,) int64, messages flow src -> dst
    dst: np.ndarray          # (E,) int64
    x: np.ndarray            # (N, d) float32
    y: np.ndarray            # (N,) int32
    train_mask: np.ndarray   # (N,) bool
    val_mask: np.ndarray
    test_mask: np.ndarray
    n_classes: int


def _split_masks(rng, n, frac=(0.6, 0.2, 0.2)):
    perm = rng.permutation(n)
    a = int(frac[0] * n)
    b = int((frac[0] + frac[1]) * n)
    tr = np.zeros(n, bool)
    va = np.zeros(n, bool)
    te = np.zeros(n, bool)
    tr[perm[:a]] = True
    va[perm[a:b]] = True
    te[perm[b:]] = True
    return tr, va, te


def powerlaw_community(n_nodes: int, n_classes: int, d_feat: int,
                       avg_degree: int, p_in: float, gamma: float,
                       noise: float, seed: int) -> Graph:
    rng = np.random.default_rng(seed)
    y = rng.integers(0, n_classes, n_nodes).astype(np.int32)
    pop = 1.0 / (np.arange(1, n_nodes + 1) ** gamma)
    pop = pop[rng.permutation(n_nodes)]
    m = max(1, avg_degree // 2)
    src = np.repeat(np.arange(n_nodes), m)
    intra = rng.random(src.size) < p_in
    dst = rng.choice(n_nodes, size=src.size, p=pop / pop.sum())
    for c in range(n_classes):
        nodes_c = np.where(y == c)[0]
        sel = intra & (y[src] == c)
        if nodes_c.size and sel.any():
            pc = pop[nodes_c] / pop[nodes_c].sum()
            dst[sel] = nodes_c[rng.choice(nodes_c.size, size=int(sel.sum()),
                                          p=pc)]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    means = rng.normal(0, 1, (n_classes, d_feat))
    x = (means[y] + noise * rng.normal(0, 1, (n_nodes, d_feat))).astype(
        np.float32)
    tr, va, te = _split_masks(rng, n_nodes)
    return Graph(n_nodes, src.astype(np.int64), dst.astype(np.int64), x, y,
                 tr, va, te, n_classes)


GENERATORS = {"powerlaw_community": powerlaw_community}


def generate(cfg: dict) -> Graph:
    """The graph a configuration file describes (``generator`` plus its
    keyword arguments at the top level, ``graph_seed`` fixed)."""
    gen = GENERATORS[cfg["generator"]]
    return gen(n_nodes=cfg["n_nodes"], n_classes=cfg["n_classes"],
               d_feat=cfg["d_feat"], avg_degree=cfg["avg_degree"],
               p_in=cfg["p_in"], gamma=cfg["gamma"], noise=cfg["noise"],
               seed=cfg["graph_seed"])


def with_self_loops(g: Graph) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) with one self loop per node appended (A + I)."""
    loop = np.arange(g.n_nodes, dtype=np.int64)
    return np.concatenate([g.src, loop]), np.concatenate([g.dst, loop])


def gcn_weights(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """Symmetric normalisation 1/sqrt(deg(src) deg(dst)) of A + I, with the
    in-degree counted over the given (self-looped) edges."""
    deg = np.bincount(dst, minlength=n).astype(np.float64)
    inv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    return (inv[src] * inv[dst]).astype(np.float32)
