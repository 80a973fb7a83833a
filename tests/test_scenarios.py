"""Scenario-matrix runner: expansion, reports, schema, plan-cache reuse."""
import json

import pytest

from repro.launch import scenarios as S

# The v1 report keys, pinned independently of the source: v2 must stay a
# strict superset (schema versioning means old consumers keep working).
V1_REPORT_KEYS = {
    "scenario", "cell", "arch", "dataset", "policy", "policy_spec", "mode",
    "runtime", "n_parts", "epochs", "seed", "plan_cache_hit", "final_loss",
    "val_acc", "test_acc", "comm_payload_bytes_per_epoch",
    "comm_ec_bytes_per_epoch", "wire_payload_bytes_per_epoch",
    "wire_ec_bytes_per_epoch", "modeled_tpu_comm_s", "schedule",
    "modeled_tpu_comm_exposed_s", "modeled_tpu_comm_overlapped_s",
    "bits_per_site", "seconds", "fault", "faults_injected", "halos_reused",
    "forced_syncs", "stall_s",
}


def test_report_schema_is_versioned_superset():
    assert S.REPORT_SCHEMA_VERSION == 2
    assert V1_REPORT_KEYS < S.REPORT_KEYS
    assert S.REPORT_KEYS - V1_REPORT_KEYS == \
        {"schema_version", "obs", "trace_path"}


def test_smoke_scenario_matrix_shape():
    """The acceptance matrix: >= 2 archs x 2 datasets x 2 policies."""
    scn = S.resolve("smoke")
    assert len(scn.archs) >= 2 and len(scn.datasets) >= 2
    assert len(scn.policies) >= 2
    cells = scn.cells()
    assert len(cells) == (len(scn.archs) * len(scn.datasets)
                          * len(scn.policies))
    assert len({c.cell_id for c in cells}) == len(cells)     # ids unique


def test_parse_policy_specs():
    from repro import policy as P
    assert isinstance(S.parse_policy("uniform:32"), P.Uniform)
    assert S.parse_policy("uniform:32").bits == 32
    w = S.parse_policy("warmup:3:2")
    assert (w.epochs, w.bits) == (3, 2)
    b = S.parse_policy("bounded_staleness:4:1")
    assert (b.eps_s, b.bits) == (4, 1)
    assert S.parse_policy("adaqp:4").budget_bits == 4
    with pytest.raises(KeyError, match="unknown policy"):
        S.parse_policy("nope:1")


def test_unknown_scenario_and_empty_filter():
    with pytest.raises(KeyError, match="unknown scenario"):
        S.resolve("nope")
    with pytest.raises(ValueError, match="matched no cell"):
        S.run_scenario("smoke", only="no_such_cell")


@pytest.mark.slow
def test_run_scenario_writes_reports_and_reuses_plan_cache(tmp_path):
    """End-to-end on a 2x2x2-shaped tiny matrix; the second invocation must
    hit the partition-plan cache in every cell (the acceptance criterion).
    Trains 16 cells end-to-end (~30s) — slow suite."""
    scn = S.Scenario(
        name="tiny",
        archs=("gcn", "graphsage"),
        datasets=("yelp_like@smoke", "mesh_like@smoke"),
        policies=("uniform:1", "uniform:32"),
        parts=2, epochs=1)
    out, cache = tmp_path / "scenarios", tmp_path / "plans"
    reports = S.run_scenario(scn, out_dir=out, cache_dir=cache)
    assert len(reports) == 8
    # one JSON per cell + summary, all parseable, full schema
    files = sorted((out / "tiny").glob("*.json"))
    assert len(files) == 9
    summary = json.loads((out / "tiny" / "summary.json").read_text())
    assert summary["n_cells"] == 8
    for rep in reports:
        on_disk = json.loads((out / "tiny" / f"{rep['cell']}.json")
                             .read_text())
        # the exact pinned key set: keys cannot silently drop OR appear
        assert set(on_disk) == S.REPORT_KEYS
        assert on_disk["schema_version"] == S.REPORT_SCHEMA_VERSION
        assert on_disk["obs"]["enabled"] is False
        assert on_disk["obs"]["n_epochs"] == 1
        assert on_disk["trace_path"] is None
        assert on_disk["epochs"] == 1 and on_disk["n_parts"] == 2
        assert on_disk["comm_payload_bytes_per_epoch"] > 0
        assert on_disk["modeled_tpu_comm_s"] > 0
    # first run: each dataset is partitioned from scratch exactly once and
    # memoized across its cells, so every cell reports that disk miss...
    assert not any(r["plan_cache_hit"] for r in reports)
    assert len(list(cache.glob("*.npz"))) == 2        # one entry per dataset
    # ...and a second full invocation is served by the on-disk cache
    reports2 = S.run_scenario(scn, out_dir=out, cache_dir=cache)
    assert all(r["plan_cache_hit"] for r in reports2)
    # 32-bit cells ship 32x the payload of 1-bit cells, same everything else
    by_cell = {r["cell"]: r for r in reports2}
    for cell, r in by_cell.items():
        if "uniform-1__" in cell:
            r32 = by_cell[cell.replace("uniform-1__", "uniform-32__")]
            ratio = (r32["comm_payload_bytes_per_epoch"]
                     / r["comm_payload_bytes_per_epoch"])
            assert ratio == 32.0


def test_only_filter_selects_a_slice_and_summary_merges(tmp_path):
    scn = S.Scenario(name="slice", archs=("gcn", "graphsage"),
                     datasets=("mesh_like@smoke",),
                     policies=("uniform:1",), parts=2, epochs=1)
    reports = S.run_scenario(scn, out_dir=tmp_path / "s",
                             cache_dir=tmp_path / "p", only="graphsage")
    assert len(reports) == 1 and reports[0]["arch"] == "graphsage"
    # running the complementary slice must extend — not clobber — the summary
    S.run_scenario(scn, out_dir=tmp_path / "s", cache_dir=tmp_path / "p",
                   only="gcn")
    summary = json.loads((tmp_path / "s" / "slice" / "summary.json")
                         .read_text())
    assert summary["n_cells"] == 2
    assert {c["arch"] for c in summary["cells"]} == {"gcn", "graphsage"}
    # a full (unfiltered) run of a shrunk matrix prunes orphaned cell files
    shrunk = S.Scenario(name="slice", archs=("gcn",),
                        datasets=("mesh_like@smoke",),
                        policies=("uniform:1",), parts=2, epochs=1)
    S.run_scenario(shrunk, out_dir=tmp_path / "s", cache_dir=tmp_path / "p")
    summary = json.loads((tmp_path / "s" / "slice" / "summary.json")
                         .read_text())
    assert summary["n_cells"] == 1
    assert {c["arch"] for c in summary["cells"]} == {"gcn"}


def test_traced_cell_writes_obs_artifacts_with_full_schema(tmp_path):
    """One traced cell end-to-end: the report carries the exact v2 key set
    with a populated obs block, and the obs artifacts are a valid Perfetto
    trace + a summarizable metrics file (the --obs acceptance path)."""
    from repro.obs import export as ox

    scn = S.Scenario(name="one", archs=("gcn",),
                     datasets=("mesh_like@smoke",),
                     policies=("uniform:1",), parts=2, epochs=2)
    [cell] = scn.cells()
    obs_dir = tmp_path / "obs" / "one"
    rep = S.run_cell(scn, cell, cache_dir=tmp_path / "p", obs_dir=obs_dir)
    assert set(rep) == S.REPORT_KEYS
    assert rep["schema_version"] == S.REPORT_SCHEMA_VERSION
    assert rep["obs"]["enabled"] is True
    assert rep["obs"]["n_epochs"] == 2
    trace = obs_dir / f"{cell.cell_id}.trace.json"
    metrics = obs_dir / f"{cell.cell_id}.metrics.json"
    assert rep["trace_path"] == str(trace)
    names = {e["name"] for e in ox.load_trace(trace)}
    assert {"epoch", "decide", "step"} <= names
    body = ox.load_metrics(metrics)
    assert body["run"] == f"one/{cell.cell_id}"
    assert body["trace_path"] == str(trace)
    assert body["metrics"]["counters"]["retrace.train"] >= 1
    summary = ox.render_summary(obs_dir)
    assert f"one/{cell.cell_id}" in summary
    # and the tracer is torn down again: later cells run untraced
    from repro import obs
    assert not obs.enabled()
