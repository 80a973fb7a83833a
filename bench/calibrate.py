"""Readings the correctness limits are set from (not run by the benchmark).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control]
        [--faults]

For each seed, in one process (the graph is generated once):

* ``program``: the program's first three epochs against the float32
  reference: the lower readings;
* ``control`` (``--control``): the reference computed in bfloat16, put in the
  program's place, against the float32 reference: the upper readings;
* ``half_batch`` and ``no_exchange`` (``--faults``): the reference with half
  the training nodes left out of the mean, and with the halo exchange left
  out, against the float32 reference.

A step that returns its state unchanged reads 1 on ``change_gap`` by
construction and needs no run. Prints one JSON line per seed and kind, then
the largest reading of each kind.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import check, graphgen  # noqa: E402
from bench import harness as H  # noqa: E402

KEYS = ("loss_gap", "grad_gap", "grad_diff", "change_gap", "halo_gap",
        "feature_halo_gap")


def readings(cell, seeds, control: bool, faults: bool, g=None, log=print):
    g = g if g is not None else graphgen.generate(cell.config)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        built = H.build(cell, seed, graph=g)
        prog = H.program_readings(built.trainer, built.params0)
        params0, t_seed = built.params0, built.trainer_seed
        del built
        gc.collect()
        ref = H.reference_readings(cell, g, params0, t_seed)
        kinds = {"program": prog}
        if control:
            kinds["control"] = H.reference_readings(cell, g, params0, t_seed,
                                                    dtype="bfloat16")
        if faults:
            for f in ("half_batch", "no_exchange"):
                kinds[f] = H.reference_readings(cell, g, params0, t_seed,
                                                fault=f)
        for kind, got in kinds.items():
            row = {"seed": seed, "kind": kind, **check.readings(got, ref),
                   "losses": got["losses"], "ref_losses": ref["losses"],
                   "s": time.perf_counter() - t0}
            rows.append(row)
            log(json.dumps(row))
    return rows


def summary(rows) -> dict:
    out = {}
    for r in rows:
        s = out.setdefault(r["kind"], {k: [] for k in KEYS})
        for k in KEYS:
            s[k].append(r[k])
    return {kind: {k: {"max": max(v), "min": min(v)} for k, v in s.items()}
            for kind, s in out.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args()
    cell = H.find_cell(args.workload, H._load(REPO / "BENCHMARK.json"))
    H.require_chips(cell.chips)
    H.use_compile_cache()
    rows = readings(cell, [int(s) for s in args.seeds.split(",")],
                    args.control, args.faults)
    print(json.dumps({"summary": summary(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
