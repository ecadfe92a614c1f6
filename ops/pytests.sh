#!/usr/bin/env bash
# Test runner — role of the reference's scripts/pytests.sh (which had to
# reset Mongo/Redis containers and docker-load the KB first).  Here the
# store is in-process: the suite builds its KBs itself, and multi-chip
# behavior runs on a virtual 8-device CPU mesh (tests/conftest.py sets
# XLA_FLAGS=--xla_force_host_platform_device_count=8).
set -euo pipefail
cd "$(dirname "$0")/.."
# `ops/pytests.sh pipeline` runs the serving-pipeline + result-cache
# suite standalone (coalescer pipelining, cache invalidation pins).
if [[ "${1:-}" == "pipeline" ]]; then
  shift
  exec python -m pytest tests/ -q -m pipeline "$@"
fi
# `ops/pytests.sh sharded` runs the sharded serving-parity suite
# standalone (mesh dispatch/settle pipeline, tree-composite +
# count-batch cache scope); any further args pass through to pytest.
if [[ "${1:-}" == "sharded" ]]; then
  shift
  exec python -m pytest tests/ -q -m sharded "$@"
fi
# `ops/pytests.sh lint` runs the daslint static-analysis suite standalone
# (analyzer clean-run pin + per-rule fixture corpus); ops/lint.sh is the
# non-pytest wrapper for CI/pre-commit.
if [[ "${1:-}" == "lint" ]]; then
  shift
  exec python -m pytest tests/ -q -m lint "$@"
fi
# `ops/pytests.sh planner` runs the cost-based planner suite standalone
# (planner-vs-greedy bit-parity on the bio suite, retry-round-0 pins,
# estimator invalidation on commit, explain surface).
if [[ "${1:-}" == "planner" ]]; then
  shift
  exec python -m pytest tests/ -q -m planner "$@"
fi
# `ops/pytests.sh treefuse` runs the whole-tree fused execution suite
# standalone (fused-tree vs tree-executor bit-parity on the bio
# Or/negation suite, the one-program acceptance pin, fallback on
# composite shapes, fused-tree cache scope, sig distinctness).
if [[ "${1:-}" == "treefuse" ]]; then
  shift
  exec python -m pytest tests/ -q -m treefuse "$@"
fi
# `ops/pytests.sh obs` runs the observability suite standalone (trace
# span coverage for a coalesced query, cache/commit events, histogram
# percentile math, Perfetto/Prometheus exporter shapes, the
# disabled-mode no-op recorder pin, and the DL014 clean-tree pin).
if [[ "${1:-}" == "obs" ]]; then
  shift
  exec python -m pytest tests/ -q -m obs "$@"
fi
# `ops/pytests.sh fault` runs the dasfault robustness suite standalone
# (seeded chaos-parity sweep over FAULT_SITES on both backends, deadline
# expiry in queue/grouped/in-flight states, breaker trip/half-open/
# restore, RetryPolicy determinism, commit atomicity under injection,
# DL015 fixtures).
if [[ "${1:-}" == "fault" ]]; then
  shift
  exec python -m pytest tests/ -q -m fault "$@"
fi
# `ops/pytests.sh prof` runs the dasprof program-ledger suite standalone
# (ledger lifecycle on both backends, disabled-path identity pin,
# explain(compile=True) shape, byte-model calibration sanity, DL016
# fixtures).
if [[ "${1:-}" == "prof" ]]; then
  shift
  exec python -m pytest tests/ -q -m prof "$@"
fi
# `ops/pytests.sh dur` runs the dasdur durability suite standalone
# (crash-point matrix over the five persist fault sites on both
# backends, torn-tail WAL truncation, corrupt-generation fallback,
# warm-bundle staleness + zero-retry warm restore, disabled-path
# identity, DL017 fixtures).
if [[ "${1:-}" == "dur" ]]; then
  shift
  exec python -m pytest tests/ -q -m dur "$@"
fi
python -m pytest tests/ -q "$@"
