"""A configuration, a traffic mix and a per-layer metric added as new files
are found by name, with no edit to the harness."""
import json
import shutil

import pytest

from bench import harness as H
from bench.tests import smoke

READER = '''
def read(rec):
    return float(len(rec.epochs)) if rec.epochs else None
'''


@pytest.fixture
def bench_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(H, "load_peaks", lambda kind: smoke.CPU_PEAKS)
    d = tmp_path / "bench"
    for sub in ("traffic", "limits", "metrics"):
        shutil.copytree(H.BENCH / sub, d / sub)
    cfg = json.loads((H.BENCH / "configs" / "gcn-reddit25k.json").read_text())
    cfg.update(smoke.SMOKE, name="tiny-gcn", n_nodes=500)
    (d / "configs").mkdir()
    (d / "configs" / "tiny-gcn.json").write_text(json.dumps(cfg))
    mix = json.loads((H.BENCH / "traffic" / "sylvie-s1.json").read_text())
    mix.update(bits=2, quant_impl="pallas")
    (d / "traffic" / "sylvie-s2.json").write_text(json.dumps(mix))
    limits = json.loads(
        (H.BENCH / "limits" / "gcn-reddit25k.sylvie-a1.json").read_text())
    (d / "limits" / "tiny-gcn.sylvie-s2.json").write_text(json.dumps(limits))
    (d / "metrics" / "epochs_in_window.py").write_text(READER)
    return d


def test_new_files_are_found_by_name(bench_dir):
    bench = smoke.benchmark()
    bench["configs"].append({"name": "tiny-gcn", "source": "test",
                             "file": str(bench_dir / "configs" / "tiny-gcn.json"),
                             "reduced": [], "why": "test"})
    bench["workloads"] = [{"name": "tiny-gcn.sylvie-s2", "config": "tiny-gcn",
                           "traffic": "sylvie-s2", "chips": 1, "why": "test"}]
    bench["per_layer"].append({"name": "epochs_in_window", "unit": "epochs",
                               "better": "higher", "source": "host_clock",
                               "layer": "epoch loop", "moves": "epoch_s",
                               "workloads": ["tiny-gcn.sylvie-s2"]})
    cell = H.find_cell("tiny-gcn.sylvie-s2", bench, bench_dir)
    assert cell.traffic["bits"] == 2 and cell.config["n_nodes"] == 500
    res = smoke.run(cell, traced=True)
    assert res["correct"], res["check"]
    assert res["metrics"]["epochs_in_window"]["value"] >= 1
    # readers with nothing to read on the CPU leave their metric out
    assert "device_idle_share" not in res["metrics"]
    assert "lowbit_ms" not in res["metrics"]
