"""repro.analysis: planted-violation fixtures, clean-repo runs, CLI gating.

Three layers of evidence that the analysis pass *can* catch what it claims:

* every AST lint rule fires on its planted fixture
  (``tests/fixtures/analysis/``) and the real repo is clean;
* every jaxpr contract check fires on a fabricated or monkeypatched
  violation (fp32 leak on a quantized exchange, a second psum, un-inverted
  backward rings, an all_gather, a host callback, a busted quantize payload,
  a retracing serve sweep) and the contract suite is clean on the repo;
* the ``python -m repro.analysis`` CLI exits non-zero on a fixture and zero
  once the finding is baselined.

shard_map contracts need 4 devices and are exercised by ``tools/ci.sh
--analysis`` (which forces 4 host devices); here they report as skipped.
"""
import collections
import os
import subprocess
import sys

import jax
import pytest

from repro.analysis import contracts
from repro.analysis.jaxpr_checks import (CollectiveOp, ExchangeExpectation,
                                         JaxprSummary, check_exchange_census,
                                         check_no_callbacks,
                                         check_no_collectives,
                                         check_wire_dtypes, cyclic_shift,
                                         expected_shift_census, summarize)
from repro.analysis.lint import run_lint
from repro.analysis.report import (Finding, load_baseline,
                                   split_by_baseline, stale_baseline_entries,
                                   write_report)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "analysis")
CLI_ENV = {**os.environ,
           "PYTHONPATH": os.path.join(ROOT, "src")
           + os.pathsep + os.environ.get("PYTHONPATH", "")}


def _fixture(code: str) -> str:
    # RA101-107 fire in traced modules (core/); RA108 fires in
    # obs-instrumented modules (serve/) — each fixture lives where its rule
    # is scoped so it trips exactly one rule.
    rel = {"RA101": "core/ra101_traced_branch",
           "RA102": "core/ra102_unhashable_static",
           "RA103": "core/ra103_vjp_arity",
           "RA104": "core/ra104_import_time",
           "RA105": "core/ra105_nondeterminism",
           "RA106": "core/ra106_host_sync",
           "RA107": "core/ra107_unused_import",
           "RA108": "serve/ra108_wallclock"}[code]
    return os.path.join(FIXTURES, "src", "repro", *rel.split("/")) + ".py"


# ---------------------------------------------------------------------------
# AST lint: planted fixtures + clean repo
# ---------------------------------------------------------------------------
ALL_LINT_CODES = ["RA101", "RA102", "RA103", "RA104", "RA105", "RA106",
                  "RA107", "RA108"]


@pytest.mark.parametrize("code", ALL_LINT_CODES)
def test_planted_lint_fixture_fires(code):
    findings = run_lint([_fixture(code)], root=FIXTURES)
    assert any(f.code == code for f in findings), \
        f"{code} did not fire on its planted fixture"


@pytest.mark.parametrize("code", ALL_LINT_CODES)
def test_planted_lint_fixture_fires_exactly_one_rule(code):
    findings = run_lint([_fixture(code)], root=FIXTURES)
    assert {f.code for f in findings} == {code}, \
        f"fixture for {code} trips other rules too: {findings}"


def test_ra108_fires_exactly_once():
    # the ISSUE-level guarantee: one offending read, one finding — sleep and
    # the module-level import don't count
    findings = run_lint([_fixture("RA108")], root=FIXTURES)
    assert [f.code for f in findings] == ["RA108"]


def test_ra108_scoped_to_instrumented_paths(tmp_path):
    # the same wall-clock read outside INSTRUMENTED_MODULES must stay silent
    src = open(_fixture("RA108")).read()
    elsewhere = tmp_path / "src" / "repro" / "launch"
    elsewhere.mkdir(parents=True)
    (elsewhere / "wallclock.py").write_text(src)
    findings = run_lint([str(elsewhere / "wallclock.py")],
                        root=str(tmp_path), only=["RA108"])
    assert findings == []


def test_ra108_catches_aliased_import(tmp_path):
    # `from time import perf_counter as pc` must not smuggle the read past
    # the attribute-chain check
    mod = tmp_path / "src" / "repro" / "store" / "timing.py"
    mod.parent.mkdir(parents=True)
    mod.write_text("from time import perf_counter as pc\n\n\n"
                   "def read():\n    return pc()\n")
    findings = run_lint([str(mod)], root=str(tmp_path), only=["RA108"])
    assert [f.code for f in findings] == ["RA108"]


def test_traced_module_rules_scoped_to_traced_paths(tmp_path):
    # the same offending source outside TRACED_MODULES must NOT fire RA105
    src = open(_fixture("RA105")).read()
    host_side = tmp_path / "src" / "repro" / "serve"
    host_side.mkdir(parents=True)
    (host_side / "host_timing.py").write_text(src)
    findings = run_lint([str(host_side / "host_timing.py")],
                        root=str(tmp_path), only=["RA105"])
    assert findings == []


def test_clean_repo_lint():
    findings = run_lint([os.path.join(ROOT, "src", "repro"),
                         os.path.join(ROOT, "benchmarks")], root=ROOT)
    assert findings == [], "repo lint must be clean (fix or baseline):\n" + \
        "\n".join(f.render() for f in findings)


def test_noqa_suppresses(tmp_path):
    mod = tmp_path / "src" / "repro" / "core" / "m.py"
    mod.parent.mkdir(parents=True)
    mod.write_text("import time  # noqa: RA105 - trace-time timestamp ok\n")
    assert run_lint([str(mod)], root=str(tmp_path), only=["RA105"]) == []


def test_syntax_error_is_a_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    findings = run_lint([str(bad)], root=str(tmp_path))
    assert [f.code for f in findings] == ["RA100"]


# ---------------------------------------------------------------------------
# jaxpr checks: fabricated-summary planted violations (no devices needed)
# ---------------------------------------------------------------------------
BUCKETS = (0, 44, 28, 24)   # ragged, as produced by the skewed partitioner


def _exp(**kw):
    base = dict(fwd_ops=2, bwd_ops=1, bits=1, buckets=BUCKETS, psums=7)
    base.update(kw)
    return ExchangeExpectation(**base)


def _pp(shift, rows, dtype="uint8", n=1):
    return [CollectiveOp(prim="ppermute", dtype=dtype, shape=(1, rows, 4),
                         shift=shift)] * n


def _summary(collectives=(), counts=None, callbacks=()):
    counter = collections.Counter(counts or {})
    for op in collectives:
        counter[op.prim] += 1
    return JaxprSummary(prim_counts=counter, collectives=list(collectives),
                        callbacks=list(callbacks))


def _clean_compact_ops(exp):
    ops = []
    for (shift, rows), n in expected_shift_census(exp).items():
        ops += _pp(shift, rows, n=n)
    return ops


def test_clean_census_passes():
    exp = _exp()
    s = _summary(_clean_compact_ops(exp), counts={"psum": 7})
    assert check_exchange_census(s, exp, "t") == []


def test_second_psum_fires():
    exp = _exp()
    s = _summary(_clean_compact_ops(exp), counts={"psum": 8})
    codes = [f.code for f in check_exchange_census(s, exp, "t")]
    assert codes == ["RC201"]


def test_missing_bucket_fires():
    exp = _exp()
    ops = _clean_compact_ops(exp)[:-1]          # drop one bucket's ppermute
    s = _summary(ops, counts={"psum": 7})
    assert any(f.code == "RC201"
               for f in check_exchange_census(s, exp, "t"))


def test_uninverted_backward_rings_fire_rc203():
    # backward ran the FORWARD rings: same totals per rows-class, wrong shifts
    exp = _exp()
    ops = []
    p = len(BUCKETS)
    for k, b in enumerate(BUCKETS):
        if k == 0 or not b:
            continue
        ops += _pp(k, b, n=exp.fwd_ops * exp.comps)     # fwd: correct
        ops += _pp(k, b, n=exp.bwd_ops * exp.comps)     # bwd: NOT p-k
    s = _summary(ops, counts={"psum": 7})
    codes = {f.code for f in check_exchange_census(s, exp, "t")}
    assert codes == {"RC203"}


def test_fp32_leak_on_quantized_exchange_fires_rc202():
    exp = _exp()
    ops = _clean_compact_ops(exp)[:-1] + _pp(3, 24, dtype="float32")
    s = _summary(ops, counts={"psum": 7})
    codes = {f.code for f in check_wire_dtypes(s, exp, "t")}
    assert codes == {"RC202"}


def test_psum_exempt_from_wire_audit():
    s = _summary([CollectiveOp(prim="psum", dtype="float32",
                               shape=(4, 4), shift=None)])
    assert check_wire_dtypes(s, _exp(), "t") == []


def test_all_gather_fires():
    exp = _exp()
    s = _summary(_clean_compact_ops(exp),
                 counts={"psum": 7, "all_gather": 1})
    assert any("all_gather" in f.message
               for f in check_exchange_census(s, exp, "t"))


def test_callback_fires_rc205():
    s = _summary(callbacks=["pure_callback"])
    assert [f.code for f in check_no_callbacks(s, "t")] == ["RC205"]
    assert check_no_callbacks(_summary(), "t") == []


def test_simulated_collective_leak_fires():
    s = _summary(counts={"ppermute": 1})
    assert [f.code for f in check_no_collectives(s, "t")] == ["RC201"]


def test_cyclic_shift_extraction():
    assert cyclic_shift([(0, 1), (1, 2), (2, 3), (3, 0)]) == 1
    assert cyclic_shift([(0, 3), (1, 0), (2, 1), (3, 2)]) == 3
    assert cyclic_shift([(0, 1), (1, 0)]) == 1
    assert cyclic_shift([(0, 2), (1, 2)]) is None     # not a permutation
    assert cyclic_shift([]) is None


def test_summarize_recurses_into_jit():
    def f(x):
        return jax.jit(lambda y: y * 2)(x) + 1

    s = summarize(jax.make_jaxpr(f)(1.0))
    assert s.count("mul") == 1        # found inside the pjit sub-jaxpr


# ---------------------------------------------------------------------------
# contracts: monkeypatched planted violations + clean run
# ---------------------------------------------------------------------------
def test_quantize_payload_contract_clean():
    findings, skipped = contracts.contract_quantize_payload()
    assert findings == [] and skipped == []


def test_quantize_payload_contract_fires_on_fp32_payload(monkeypatch):
    from repro.core import quantization as qlib

    real = qlib.quantize

    def leaky(h, bits, *a, **kw):
        qt = real(h, bits, *a, **kw)
        if bits <= 8:       # ship dequantized fp32 instead of the payload
            return qlib.QuantizedTensor(qt.data.astype("float32"), qt.scale,
                                        qt.zero, qt.bits, qt.feat_dim)
        return qt

    monkeypatch.setattr(qlib, "quantize", leaky)
    findings, _ = contracts.contract_quantize_payload()
    assert findings and all(f.code == "RC206" for f in findings)


def test_recompile_budget_contract_clean():
    findings, skipped = contracts.contract_recompile_budget()
    assert findings == [] and skipped == []


def test_serve_one_executable_contract_clean():
    findings, skipped = contracts.contract_serve_one_executable()
    assert findings == [] and skipped == []


def test_serve_one_executable_fires_on_retracing_sweep(monkeypatch):
    from repro.dist.runtime import Runtime

    def leaky_shard_serve_fn(self, sweep_fn):
        # a sweep that builds a FRESH executable per invocation — the exact
        # failure mode the one-executable contract exists to catch (the fresh
        # lambda defeats jax's function-identity trace cache)
        def call(*args):
            return jax.jit(lambda *a: sweep_fn(*a))(*args)
        return call

    monkeypatch.setattr(Runtime, "shard_serve_fn", leaky_shard_serve_fn)
    findings, _ = contracts.contract_serve_one_executable()
    assert any(f.code == "RC204" for f in findings)


def test_overlap_budget_contract_clean():
    findings, skipped = contracts.contract_overlap_budget()
    assert findings == [] and skipped == []


def test_overlap_budget_fires_on_uncached_steps(monkeypatch):
    from repro.dist.runtime import Runtime

    # a runtime that hands back the raw eager steps: every invocation re-runs
    # the python body, so the TRACE_LOG grows per call instead of per decision
    monkeypatch.setattr(Runtime, "shard_gnn_steps",
                        lambda self, ts, ta, ev, *a: (ts, ta, ev))
    findings, _ = contracts.contract_overlap_budget()
    assert any(f.code == "RC209" for f in findings)


def test_overlap_census_fires_without_fence(monkeypatch):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
    from repro.dist.backend import ShardMapBackend

    # strip the fence: values are unchanged (identity), but the land can fold
    # back into the issue — exactly what RC209's barrier census must catch
    monkeypatch.setattr(ShardMapBackend, "fence", lambda self, tree: tree)
    findings, _ = contracts.contract_overlap_census()
    assert any(f.code == "RC209" for f in findings)


def test_obs_transparency_contract_clean():
    findings, skipped = contracts.contract_obs_transparency()
    assert findings == [] and skipped == []


def test_obs_transparency_fires_on_leaky_instrumentation(monkeypatch):
    from repro import obs
    from repro.train import gnn_step

    class LeakyLog(obs.TraceLog):
        # the planted violation: instrumentation that emits a *traced op*
        # (a debug callback) when the tracer is armed — exactly what RC210
        # exists to catch at the TRACE_LOG seam
        def append(self, tag):
            super().append(tag)
            if obs.enabled():
                jax.debug.print("retraced {}", 0)

    monkeypatch.setattr(gnn_step, "TRACE_LOG", LeakyLog("train"))
    findings, _ = contracts.contract_obs_transparency()
    assert any(f.code == "RC210" for f in findings)
    assert all("train" in f.where for f in findings if f.code == "RC210")


def test_contract_error_reported_not_swallowed(monkeypatch):
    monkeypatch.setitem(contracts.CONTRACTS, "boom",
                        lambda: (_ for _ in ()).throw(RuntimeError("nope")))
    findings, _ = contracts.run_contracts(only=["boom"])
    assert [f.code for f in findings] == ["RC200"]
    assert "nope" in findings[0].message


def test_full_contract_suite_clean():
    """The acceptance gate: zero findings on the repo. On a 1-device pytest
    run the shard_map entry points report as skipped (never as passes); under
    tools/ci.sh --analysis all of them run."""
    findings, skipped = contracts.run_contracts()
    assert findings == [], "\n".join(f.render() for f in findings)
    if len(jax.devices()) < 4:
        assert any("shard_map" in s for s in skipped)


def test_shard_map_contracts_run_with_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (tools/ci.sh --analysis lane)")
    for name in ("train_sync/gcn/compact/shard_map",
                 "serve_sweep/gcn/compact/shard_map",
                 "overlap_census/gcn/compact/shard_map"):
        findings, skipped = contracts.run_contracts(only=[name])
        assert findings == [] and skipped == []


# ---------------------------------------------------------------------------
# baseline + report plumbing
# ---------------------------------------------------------------------------
def test_baseline_round_trip(tmp_path):
    f1 = Finding(code="RA107", where="src/x.py", message="unused import 'os'",
                 line=3)
    f2 = Finding(code="RC202", where="contract:t", message="fp32 leak")
    base = tmp_path / "baseline.txt"
    base.write_text(f"# accepted: legacy debt\n{f1.fingerprint}\n")
    baseline = load_baseline(str(base))
    fresh, known = split_by_baseline([f1, f2], baseline)
    assert fresh == [f2] and known == [f1]
    # line numbers are not part of the fingerprint
    moved = Finding(code="RA107", where="src/x.py",
                    message="unused import 'os'", line=99)
    assert moved.fingerprint in baseline
    # paid-off debt is reported as stale
    assert stale_baseline_entries([f2], baseline) == [f1.fingerprint]


def test_report_schema(tmp_path):
    import json
    f1 = Finding(code="RA101", where="src/a.py", message="m", line=1)
    path = write_report(str(tmp_path / "report.json"), [f1], set(),
                        skipped=["contract:x (needs 4 devices)"],
                        meta={"lanes": ["lint"]})
    body = json.load(open(path))
    assert body["counts"] == {"fresh": 1, "baselined": 0}
    assert body["findings"][0]["code"] == "RA101"
    assert body["findings"][0]["baselined"] is False
    assert body["skipped"] == ["contract:x (needs 4 devices)"]
    assert body["stale_baseline"] == []


# ---------------------------------------------------------------------------
# CLI: exit codes gate
# ---------------------------------------------------------------------------
def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=CLI_ENV, cwd=ROOT, timeout=120)


def test_cli_exits_nonzero_on_planted_fixture():
    r = _cli("--lint-only", "--root", FIXTURES,
             os.path.join(FIXTURES, "src", "repro"))
    assert r.returncode == 1, r.stdout + r.stderr
    for code in ALL_LINT_CODES:
        assert code in r.stdout


def test_cli_exits_zero_with_baseline(tmp_path):
    fixture_dir = os.path.join(FIXTURES, "src", "repro", "core")
    findings = run_lint([fixture_dir], root=FIXTURES)
    base = tmp_path / "baseline.txt"
    base.write_text("# test baseline: every planted fixture accepted\n" +
                    "".join(f.fingerprint + "\n" for f in findings))
    r = _cli("--lint-only", "--root", FIXTURES, "--baseline", str(base),
             fixture_dir)
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_lint_only_clean_repo():
    r = _cli("--lint-only")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout


def test_repo_baseline_is_empty_or_justified():
    baseline = load_baseline(os.path.join(ROOT, "tools",
                                          "analysis_baseline.txt"))
    assert baseline == set(), \
        "repo baseline must stay empty unless debt is justified in-file"
