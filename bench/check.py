"""The comparison that decides ``correct`` for a training cell.

The numbers, each against its own limit (``bench/limits/<cell>.json``):

* ``loss_gap``: the largest relative gap between the program's loss and the
  reference's, over the first steps;
* ``grad_gap``: for each parameter leaf, the gap between the norm of the
  program's first gradient (as its optimiser holds it after one step) and the
  reference's, over the larger of the reference leaf's norm and the median
  leaf norm; the worst leaf;
* ``grad_diff``: for each parameter leaf, the norm of the difference
  between the program's first gradient and the reference's, element by
  element, over the same denominator; the worst leaf. A gap of norms cannot
  see a gradient that points elsewhere with about the same length, as one
  taken over half the training nodes does;
* ``change_gap``: the same measure as ``grad_gap`` for the change of the
  parameters over the first steps;
* ``halo_gap``: for each exchange site, the relative gap (norm of the
  difference over the reference's norm) between the halo features the
  program received in the first step and the reference's; the worst site.
  It holds the Low-bit Module and which rows land in which partition to the
  reference row by row. It reads the first step because Adam's first update
  moves every weight by about the learning rate whatever its gradient's
  size, so round-off differences in near-zero gradients change the later
  steps' hidden features, and 1-bit stochastic rounding turns those into
  whole-range differences;
* ``feature_halo_gap``: the same for the first site alone, whose input is
  the node features themselves, so that nothing upstream of its Low-bit
  Module differs between program and reference.

A cell compares the numbers its limits file names.

Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of both leaf measures: Adam moves them by round-off alone.
"""
from __future__ import annotations

import math

import jax
import numpy as np

NEGLIGIBLE = 1e-3


def leaves(tree) -> dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64).ravel()
            for k, v in flat}


def norms(arrays: dict) -> dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in arrays.items()}


def leaf_norms(tree) -> dict[str, float]:
    return norms(leaves(tree))


def leaf_gap(prog: dict, ref: dict, ref_grad: dict) -> float:
    med = float(np.median(list(ref.values())))
    med_g = float(np.median(list(ref_grad.values())))
    worst = 0.0
    for k, r in ref.items():
        if ref_grad[k] < NEGLIGIBLE * med_g:
            continue
        p = prog.get(k, math.nan)
        if not math.isfinite(p):
            return math.inf
        worst = max(worst, abs(p - r) / max(r, med, 1e-30))
    return worst


def leaf_diff(prog: dict, ref: dict) -> float:
    """Worst leaf's norm of ``prog - ref`` (leaves as arrays) over the larger
    of the reference leaf's norm and the median leaf norm; negligible leaves
    of ``ref`` left out."""
    size = norms(ref)
    med = float(np.median(list(size.values())))
    worst = 0.0
    for k, r in ref.items():
        if size[k] < NEGLIGIBLE * med:
            continue
        p = prog.get(k)
        if p is None or p.shape != r.shape or not np.all(np.isfinite(p)):
            return math.inf
        worst = max(worst, float(np.linalg.norm(p - r)) / max(size[k], med,
                                                               1e-30))
    return worst


def loss_gap(prog, ref) -> float:
    gaps = [abs(p - r) / max(abs(r), 1e-30) for p, r in zip(prog, ref)]
    if len(prog) != len(ref) or not all(map(math.isfinite, gaps)):
        return math.inf
    return max(gaps)


def halo_gap(prog, ref, at) -> float:
    """Worst site's relative gap between the halo features the program
    received and the reference's. ``ref`` has one row per
    halo entry; ``prog`` is either that too, or the program's ``(P, rows, d)``
    receive buffers, read at ``at`` = (receiving partition, row)."""
    worst = 0.0
    for p, r in zip(prog, ref):
        p = np.asarray(p, np.float64)
        if p.ndim == 3:
            p = p[at[0], at[1]]
        r = np.asarray(r, np.float64)
        if p.shape != r.shape:
            return math.inf
        gap = np.linalg.norm(p - r) / max(np.linalg.norm(r), 1e-30)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, float(gap))
    return worst if len(prog) == len(ref) else math.inf


def readings(prog: dict, ref: dict) -> dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses``, ``grad`` (the first
    gradient's leaves, by ``leaves``), ``change`` (leaf norms of the
    parameter change) and
    ``halo`` (each site's received halo features); ``ref`` also holds
    ``halo_at``, where its halo entries lie in the program's buffers."""
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_gap": leaf_gap(norms(prog["grad"]), norms(ref["grad"]),
                                 norms(ref["grad"])),
            "grad_diff": leaf_diff(prog["grad"], ref["grad"]),
            "change_gap": leaf_gap(prog["change"], ref["change"],
                                   norms(ref["grad"])),
            "halo_gap": halo_gap(prog["halo"], ref["halo"], ref["halo_at"]),
            "feature_halo_gap": halo_gap(prog["halo"][:1], ref["halo"][:1],
                                         ref["halo_at"])}


def change(params_after, params_before):
    return jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                        - np.asarray(b, np.float64),
                        params_after, params_before)


def verdict(values: dict, limits: dict) -> bool:
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)
