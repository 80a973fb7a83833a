"""The benchmark's four-chip cell (``gcn-reddit100k-x4.sylvie-a1``) on four
CPU devices, at smoke size: the sharded program against the benchmark's
plain reference (``bench/reference.py``, per-chip noise), and the
``collective`` scope that ``collective_exposed_ms`` reads on every
collective of the compiled steps. JAX fixes its device count when it starts,
so one subprocess with four forced host devices makes every reading and the
tests assert on them."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
CELL = "gcn-reddit100k-x4.sylvie-a1"
TRAFFICS = ("sylvie-a1", "vanilla32")
COLLECTIVES = ("collective-permute", "all-to-all", "all-reduce")

PROGRAM = textwrap.dedent(f"""
    import dataclasses, json, sys
    sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
    from bench import check, trace as T
    from bench import harness as H
    from bench.metrics.collective_exposed_ms import in_scope
    from bench.tests import smoke

    def collectives(tr):
        \"\"\"{{opcode: [in the collective scope?]}} over the collective
        operations of the sync and async steps, and whether any operation
        carries the scope.\"\"\"
        ts, ta = tr._steps_for(tr._last_decision or tr._decide())
        args = (tr.state, tr.block, tr.x, tr.y, tr.train_mask,
                tr._epoch_key())
        found, scoped = {{}}, False
        for step in (ts, ta):
            text = step.lower(*args).compile().as_text()
            for instrs in T.hlo_index([text]).values():
                for ins in instrs.values():
                    scoped = scoped or in_scope(ins)
                    for op in {COLLECTIVES!r}:
                        if ins.opcode.startswith(op):
                            found.setdefault(op, []).append(in_scope(ins))
        return {{"found": found, "scoped": scoped}}

    def cell_of(mix, **config):
        cell = smoke.smoke_cell({CELL!r})
        traffic = H._load(H.BENCH / "traffic" / f"{{mix}}.json")
        if traffic["mode"] != "vanilla":
            traffic["quant_impl"] = "pallas"
        return dataclasses.replace(cell, traffic=traffic,
                                   config=dict(cell.config, **config))

    out = {{"simulated": collectives(
        H.build(cell_of("sylvie-a1", runtime="simulated"), 4294967311).trainer)}}
    for mix in {TRAFFICS!r}:
        cell = cell_of(mix)
        built = H.build(cell, 4294967311)
        tr = built.trainer
        prog = H.program_readings(tr, built.params0)
        row = {{"chips": len(tr.runtime.mesh.devices.flat),
               "limits": cell.limits, "collectives": collectives(tr)}}
        for per_chip, runtime in ((True, "sharded"), (False, "simulated")):
            c = dataclasses.replace(cell, config=dict(cell.config,
                                                      runtime=runtime))
            ref = H.reference_readings(c, built.graph, built.params0,
                                       built.trainer_seed)
            values = check.readings(prog, ref)
            row[f"per_chip={{per_chip}}"] = values
            row[f"correct per_chip={{per_chip}}"] = check.verdict(
                values, cell.limits)
        out[mix] = row
    out["dense"] = collectives(
        H.build(cell_of("sylvie-a1", halo_layout="dense"), 4294967311).trainer)

    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", PROGRAM], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mix", TRAFFICS)
def test_sharded_program_matches_the_per_chip_reference(readings, mix):
    """Under the cell's own limits (``bench/limits/<cell>.json``)."""
    row = readings[mix]
    assert row["chips"] == 4
    assert row["correct per_chip=True"], row["per_chip=True"]


@pytest.mark.parametrize("mix", TRAFFICS)
def test_stacked_noise_reference_fails_the_sharded_program(readings, mix):
    """Read against the one-draw-over-the-stack noise of the simulated
    runtime, the sharded program's stochastic halos differ: the check sees
    the per-chip noise key. At 32 bits there is no noise to see."""
    limits = readings[mix]["limits"]
    values = readings[mix]["per_chip=False"]
    if mix == "vanilla32":
        assert values["halo_gap"] <= limits["halo_gap"], values
    else:
        assert values["halo_gap"] > limits["halo_gap"], values


@pytest.mark.parametrize("layout, halo_op", [
    ("sylvie-a1", "collective-permute"), ("vanilla32", "collective-permute"),
    ("dense", "all-to-all")])
def test_every_collective_is_in_the_collective_scope(readings, layout,
                                                     halo_op):
    """The compact layout's ``ppermute`` rings, the dense layout's
    ``all_to_all`` and the gradient all-reduce all keep the scope in the
    optimised program, so ``collective_exposed_ms`` sees each of them."""
    row = readings[layout]
    found = (row["collectives"] if "collectives" in row else row)["found"]
    assert found.get(halo_op) and found.get("all-reduce"), found
    assert all(all(v) for v in found.values()), found


def test_no_collective_on_the_stacked_runtime(readings):
    """The simulated runtime, which the one-chip cells run, has neither a
    collective nor the scope."""
    assert readings["simulated"] == {"found": {}, "scoped": False}
