"""Shared smoke-size helpers: the cells' shapes cut to what a CPU test holds."""
import json
import time
from pathlib import Path

from bench import harness as H

ROOT = Path(__file__).resolve().parents[2]
SMOKE = dict(n_nodes=600, avg_degree=16, d_feat=32, n_classes=8, d_hidden=16)
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_names() -> list[str]:
    return [w["name"] for w in benchmark()["workloads"]]


def smoke_cell(name: str, bench=None, bench_dir=H.BENCH, impl="pallas"):
    cell = H.find_cell(name, bench or benchmark(), bench_dir)
    cell.config.update(SMOKE)
    if cell.traffic["mode"] != "vanilla":
        cell.traffic["quant_impl"] = impl     # Pallas in interpret mode
    return cell


def run(cell, seed=4294967311, seconds=0.5, traced=False):
    """A whole run of the harness on this process's CPU devices."""
    import jax
    return H.run_cell(cell, seed, seconds, traced, time.perf_counter(),
                      jax.devices()[:cell.chips])
