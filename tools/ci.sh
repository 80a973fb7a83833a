#!/usr/bin/env bash
# Tier-1 verify: the exact command the roadmap pins. Extra args pass through
# (e.g. `tools/ci.sh -m "not slow"` for the fast lane).
#
# Extra lanes (used by .github/workflows/ci.yml):
#   tools/ci.sh --halo         halo-exchange parity tests with 4 forced host
#                              devices (runs the shard_map compact/dense parity
#                              checks in-process instead of skipping them)
#   tools/ci.sh --bench-smoke  fast benchmark regression checks: bench_halo
#                              fails if the compact layout's wire-byte
#                              reduction regresses past 60%; bench_serve fails
#                              if the quantized delta refresh ships more than
#                              10% of the full 32-bit sweep bytes; bench_chaos
#                              fails if the armed fault path's epoch overhead
#                              regresses; bench_store fails if store-backed
#                              reads diverge, the cache hit rate drops below
#                              0.9, or open-loop p99 breaks the SLO (all write
#                              untracked *.smoke.json; only full runs update
#                              the tracked BENCH_*.json records)
#   tools/ci.sh --overlap      overlap-schedule parity suite with 4 forced
#                              host devices (runs the shard_map blocking-vs-
#                              overlap bit-exactness check in-process instead
#                              of skipping it; the hypothesis property tests
#                              ride along when the dev extra is installed)
#   tools/ci.sh --policy       CommPolicy suite with 4 forced host devices
#                              (runs the shard_map Uniform-parity check
#                              in-process instead of skipping it)
#   tools/ci.sh --serve        repro.serve suite with 4 forced host devices
#                              (runs the shard_map serving-parity + delta
#                              refresh checks in-process instead of skipping)
#   tools/ci.sh --store        repro.store suite (sharded embedding store,
#                              hot-node cache, mutation stream, multi-replica
#                              serving) with 4 forced host devices, then the
#                              bench_store smoke gate (bit-exact store-backed
#                              reads, >= 0.9 cache hit rate on the skewed
#                              workload, open-loop p99 within SLO under the
#                              streaming feed)
#   tools/ci.sh --chaos        fault-tolerance suite with 4 forced host
#                              devices (seeded injection, staleness recovery,
#                              kill-and-resume), then the chaos launcher's
#                              own self-check (repro.launch.chaos --ci)
#   tools/ci.sh --obs          observability lane: repro.obs suite (span
#                              tracer, profiler sink, metrics registry,
#                              exporters, CLI, instrumented layers and named
#                              scopes), then a traced smoke scenario slice
#                              (--obs writes Perfetto trace + metrics JSON
#                              under artifacts/obs/smoke/) rendered by
#                              `python -m repro.obs summarize` (exit-code
#                              gated)
#   tools/ci.sh --docs         documentation lane: markdown link check over
#                              README/DESIGN/CHANGES + execution of every
#                              README ```bash block (quickstart, scenario
#                              smoke, fast verify) via tools/check_docs.py.
#                              `--docs --links-only` skips the executions.
#   tools/ci.sh --analysis     static-analysis gate: `python -m repro.analysis`
#                              (trace-discipline AST lint + jaxpr contract
#                              suite, baseline-gated, JSON report to
#                              artifacts/analysis/), then ruff + mypy when
#                              installed (CI installs them; locally they are
#                              skipped with a notice, never silently passed
#                              as success of the repro.analysis gate).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
case "${1:-}" in
  --policy)
    shift
    XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
      exec python -m pytest -x -q tests/test_policy.py -m "not slow" "$@"
    ;;
  --halo)
    shift
    XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
      exec python -m pytest -x -q tests/test_halo_compact.py \
      tests/test_kernels.py -m "not slow" "$@"
    ;;
  --serve)
    shift
    XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
      exec python -m pytest -x -q tests/test_serve.py -m "not slow" "$@"
    ;;
  --overlap)
    shift
    XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
      exec python -m pytest -x -q tests/test_overlap.py \
      tests/test_overlap_properties.py -m "not slow" "$@"
    ;;
  --store)
    shift
    XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
      python -m pytest -x -q tests/test_store.py -m "not slow" "$@"
    exec python -m benchmarks.bench_store --smoke
    ;;
  --chaos)
    shift
    XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
      python -m pytest -x -q tests/test_faults.py "$@"
    exec python -m repro.launch.chaos --ci
    ;;
  --bench-smoke)
    shift
    python -m benchmarks.bench_halo --smoke "$@"
    python -m benchmarks.bench_serve --smoke "$@"
    python -m benchmarks.bench_chaos --smoke "$@"
    exec python -m benchmarks.bench_store --smoke "$@"
    ;;
  --obs)
    shift
    XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
      python -m pytest -x -q tests/test_obs.py -m "not slow" "$@"
    python -m repro.launch.train --scenario smoke --only gcn__yelp_like --obs
    exec python -m repro.obs summarize artifacts/obs/smoke
    ;;
  --docs)
    shift
    exec python tools/check_docs.py "$@"
    ;;
  --analysis)
    shift
    python -m repro.analysis --json "$@"
    if command -v ruff >/dev/null 2>&1; then
      ruff check src tests benchmarks tools
    else
      echo "ruff not installed - skipping (CI installs it; pip install ruff)"
    fi
    if command -v mypy >/dev/null 2>&1; then
      mypy --config-file pyproject.toml
    else
      echo "mypy not installed - skipping (CI installs it; pip install mypy)"
    fi
    exit 0
    ;;
esac
exec python -m pytest -x -q "$@"
