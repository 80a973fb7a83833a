"""Bring-up check: Sylvie GNN training on TPU chips, through the trainer.

One chip (default): GCN at its configured width (hidden 256, 2 layers) on
``reddit_like@paper`` (25,000 nodes, 602 features, 41 classes, generated from
the seed), partitioned 4 ways in the simulated runtime, trained as Sylvie-A
(async, 1-bit, Uniform policy) for 5 epochs. It trains once with
``quant_impl="auto"`` — the compiled Pallas quantize kernels — and checks the
compiled sync and async steps call them (``tpu_custom_call``), then again with
the jnp Low-bit Module. Both runs share the seed, so the epoch losses must agree
within ``PALLAS_RTOL``.

Four chips (``--chips 4``): the same graph and model with one partition per chip
(``Runtime.sharded(4)``) against the whole stack on one chip
(``Runtime.simulated(4)``), deterministic rounding, sync and async for 3 epochs
each. The losses must agree within ``PARITY_RTOL`` and the halo state must span
four devices.

    python chip_smoke.py
    python chip_smoke.py --chips 4

The last line of output is one JSON object naming the device. Where JAX finds
no TPU the script exits non-zero before any work and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GRAPH = "reddit_like@paper"
ARCH = "gcn"
PARTS = 4
SEED = 0
EPOCHS = 5
PARITY_EPOCHS = 3
# Pallas vs jnp Low-bit Module: same noise, same bytes up to the rounding of
# the affine scale, which the two compilers may evaluate differently.
PALLAS_RTOL = 1e-3
# one partition per chip vs the stacked partitions on one chip: the same
# program up to the order of cross-partition reductions.
PARITY_RTOL = 1e-3


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found {dev.platform!r}")
    return dev


def train(graph: str = GRAPH, reduced: bool = False, *, impl: str = "auto",
          mode: str = "async", epochs: int = EPOCHS, stochastic: bool = True,
          runtime=None):
    """Train 1-bit Sylvie GCN on ``graph``; returns (trainer, epoch losses).
    Raises if a loss is not finite."""
    from repro import configs
    from repro.core.sylvie import SylvieConfig
    from repro.launch.train import build_gnn_trainer

    spec = configs.get(ARCH)
    arch = spec.reduced() if reduced else spec.config()
    cfg = SylvieConfig(mode=mode, bits=1, stochastic=stochastic,
                       quant_impl=impl)
    tr = build_gnn_trainer(arch, graph, PARTS, cfg, seed=SEED, runtime=runtime)
    losses = [tr.train_epoch().loss for _ in range(epochs)]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss ({impl}, {mode}): {losses}")
    return tr, losses


def max_rel_dev(a, b) -> float:
    return max(abs(x - y) / abs(y) for x, y in zip(a, b))


def one_chip() -> None:
    import jax

    from repro.core.quantization import resolve_impl

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    tr, pallas = train(impl="auto")
    for sync in (True, False):
        if "tpu_custom_call" not in tr.compiled_step_text(sync):
            raise AssertionError(f"{'sync' if sync else 'async'} step runs no "
                                 "Pallas kernel")
    tr_ref, ref = train(impl="jnp")
    dev = max_rel_dev(pallas, ref)
    print(f"impl: auto -> {resolve_impl('auto')} "
          "(tpu_custom_call in the sync and async steps)")
    print(f"losses pallas: {pallas}")
    print(f"losses jnp:    {ref}")
    print(f"max rel deviation pallas vs jnp: {dev!r} (limit {PALLAS_RTOL})")
    print(f"compile seconds: {sum(compile_s)!r} over {len(compile_s)} programs")
    # the first sync and async epochs include their step's compile
    for name, t in (("pallas", tr), ("jnp", tr_ref)):
        print(f"step seconds {name}: {[m.seconds for m in t.history]}")
    if dev > PALLAS_RTOL:
        raise AssertionError("Pallas and jnp losses disagree")


def four_chips(graph: str = GRAPH, reduced: bool = False) -> None:
    import jax

    from repro.dist.runtime import Runtime

    worst = 0.0
    for mode in ("sync", "async"):
        tr, sharded = train(graph, reduced, mode=mode, epochs=PARITY_EPOCHS,
                            stochastic=False, runtime=Runtime.sharded(PARTS))
        halo_devices = {len(a.sharding.device_set)
                        for a in jax.tree.leaves(tr.state.halo)}
        if halo_devices != {PARTS}:
            raise AssertionError(f"halo state spans {halo_devices} devices")
        _, simulated = train(graph, reduced, mode=mode, epochs=PARITY_EPOCHS,
                             stochastic=False, runtime=Runtime.simulated(PARTS))
        dev = max_rel_dev(sharded, simulated)
        worst = max(worst, dev)
        print(f"{mode} losses sharded:   {sharded}")
        print(f"{mode} losses simulated: {simulated}")
        print(f"{mode} max rel deviation: {dev!r} (limit {PARITY_RTOL}); "
              f"halo state spans {PARTS} devices")
    if worst > PARITY_RTOL:
        raise AssertionError("sharded and simulated losses disagree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: sharded-vs-simulated parity across four chips")
    args = ap.parse_args()

    import jax
    dev = require_tpu()
    if len(jax.devices()) < args.chips:
        raise SystemExit(f"--chips {args.chips}: JAX found "
                         f"{len(jax.devices())} devices")
    from repro.launch.cache import use_compile_cache
    print(f"device: {dev.device_kind} x{len(jax.devices())}; compile cache "
          f"{use_compile_cache()}")
    one_chip() if args.chips == 1 else four_chips()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
