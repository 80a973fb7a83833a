"""Halo-exchange microbenchmark: dense vs compacted plan, jnp vs fused Pallas
quantize. Tracks the perf trajectory of the system's hottest path from this PR
onward by writing ``BENCH_halo.json`` at the repo root.

Measures, on a skewed power-law partition (8 parts, geometric sizes):

* rows/bytes: buffer rows, wire rows/bytes the layout ships, true halo rows —
  the compact plan's reduction factor vs the dense ``(P, P*h_pad)`` layout;
* ms: jit wall time of the full quantized halo round trip (gather -> quantize
  -> exchange -> dequantize), forward and forward+backward, per layout;
* quantize impls: jnp vs the fused Pallas kernel on the compacted send-buffer
  shape (off-TPU the kernel runs *interpret mode* — correctness-path timing
  only; the one-HBM-pass claim is a TPU number).

``--smoke`` shrinks everything so CI can run it in seconds.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core import quantization as qlib
from repro.core.exchange import PlanArrays, exchange_bytes, wire_bytes
from repro.core.sylvie import quantized_halo
from repro.graph import formats, partition, synthetic

ROOT = Path(__file__).resolve().parents[1]
def _key():
    # built lazily: no device work at import time (lint RA104)
    return jax.random.PRNGKey(0)


def _timed(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))             # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e3


def _bench_layout(pg, d_feat, bits, reps):
    plan = PlanArrays.from_plan(pg.plan)
    p = plan.n_parts
    h = jax.random.normal(_key(), (p, plan.n_local, d_feat), jnp.float32)
    k1, k2 = jax.random.split(_key())

    @jax.jit
    def fwd(x):
        return quantized_halo(x, None, plan, k1, k2, bits, bits, True,
                              jnp.bfloat16, None, "jnp", False)

    @jax.jit
    def fwdbwd(x):
        # quadratic loss: the backward cotangent depends on x, so XLA cannot
        # constant-fold the quantized backward communication away
        return jax.grad(lambda v: (quantized_halo(
            v, None, plan, k1, k2, bits, bits, True, jnp.bfloat16, None,
            "jnp", False) ** 2).sum() / 2)(x)

    pb, eb = wire_bytes(plan, d_feat, bits)
    tb, _ = exchange_bytes(plan, d_feat, bits)
    return dict(
        layout=pg.plan.layout,
        halo_rows_per_part=plan.halo_rows,
        buffer_rows_total=p * plan.halo_rows,
        wire_rows=plan.wire_rows,
        real_rows=plan.real_rows,
        wire_payload_bytes=pb,
        wire_ec_bytes=eb,
        true_payload_bytes=tb,
        pad_efficiency=pg.plan.pad_efficiency(),
        fwd_ms=_timed(fwd, h, reps=reps),
        fwd_bwd_ms=_timed(fwdbwd, h, reps=reps),
    )


def _bench_quantize(rows, d_feat, bits, reps):
    h = jax.random.normal(_key(), (rows, d_feat), jnp.float32)
    out = {}
    for impl in ("jnp", "pallas"):
        qfn = jax.jit(lambda x, impl=impl: qlib.dequantize(
            qlib.quantize(x, bits, _key(), True, impl=impl), impl=impl))
        out[impl] = _timed(qfn, h, reps=reps)
    out["pallas_mode"] = ("compiled" if jax.default_backend() == "tpu"
                          else "interpret")
    return out


def run(smoke: bool = False) -> dict:
    # full config sized for XLA-CPU wall clocks (DESIGN.md §8); the byte/row
    # columns — the acceptance metric — are exact at any size
    n, d_feat, parts, reps = (2000, 32, 8, 2) if smoke else (8000, 64, 8, 3)
    bits = 1
    g = synthetic.powerlaw(n_nodes=n, d_feat=d_feat, avg_degree=16, seed=0)
    ei = formats.add_self_loops(g.edge_index, g.n_nodes)
    ew = formats.gcn_edge_weights(ei, g.n_nodes)
    g = formats.Graph(g.n_nodes, ei, g.x, g.y, g.train_mask, g.val_mask,
                      g.test_mask, n_classes=g.n_classes)

    layouts = {}
    for layout in ("dense", "compact"):
        pg = partition.partition_graph(g, parts, method="skewed",
                                       edge_weight=ew, layout=layout)
        layouts[layout] = _bench_layout(pg, d_feat, bits, reps)

    # cap the impl-comparison rows: off-TPU the Pallas kernel runs interpret
    # mode, whose wall clock is meaningless beyond a correctness-path signal
    q_rows = min(layouts["compact"]["buffer_rows_total"], 16384)
    rec = dict(
        config=dict(n_nodes=n, d_feat=d_feat, parts=parts, bits=bits,
                    method="skewed", smoke=smoke,
                    backend=jax.default_backend()),
        dense=layouts["dense"],
        compact=layouts["compact"],
        wire_reduction=layouts["compact"]["wire_payload_bytes"]
        / max(layouts["dense"]["wire_payload_bytes"], 1),
        quantize=_bench_quantize(max(q_rows, 8), d_feat, bits,
                                 reps=1 if smoke else reps),
    )

    print(f"== bench_halo (P={parts}, n={n}, d={d_feat}, {bits}-bit, skewed) ==")
    for lay in ("dense", "compact"):
        r = layouts[lay]
        print(f"{lay:8s} rows/part={r['halo_rows_per_part']:6d} "
              f"wire={r['wire_payload_bytes'] / 1e3:9.1f} kB "
              f"pad_eff={r['pad_efficiency']:.3f} "
              f"fwd={r['fwd_ms']:7.2f} ms fwd+bwd={r['fwd_bwd_ms']:7.2f} ms")
    q = rec["quantize"]
    print(f"wire reduction (compact/dense): {rec['wire_reduction']:.3f}")
    print(f"quantize {q_rows}x{d_feat}: jnp={q['jnp']:.2f} ms  "
          f"pallas[{q['pallas_mode']}]={q['pallas']:.2f} ms")

    # --smoke is a CI freshness/regression check; only full runs update the
    # tracked perf-trajectory record
    out = ROOT / ("BENCH_halo.smoke.json" if smoke else "BENCH_halo.json")
    out.write_text(json.dumps(rec, indent=1, default=float))
    assert rec["wire_reduction"] <= 0.6, \
        f"compact layout regressed: wire ratio {rec['wire_reduction']:.3f} > 0.6"
    return rec


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + 1 rep (CI freshness check)")
    run(**vars(ap.parse_args()))
