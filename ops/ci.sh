#!/usr/bin/env bash
# One pre-commit/CI gate (ISSUE 12 satellite): the static analyzer with
# machine-readable SARIF output, then the lint + obs pytest markers —
# the two suites that pin the analyzer's registries (counters, env,
# FETCH_SITES, the DL014 span/metric names) and the observability
# layer's contracts (disabled-path no-op, exporter shapes).
#
#   ops/ci.sh [--changed-only]
#
# --changed-only passes through to ops/lint.sh (pre-commit fast path:
# changed das_tpu files + registry anchors under --allow-partial); the
# full run stays the CI authority.  SARIF lands in
# ${DASLINT_SARIF:-/tmp/daslint.sarif} for CI annotation upload; the
# human-readable text pass is what fails the gate (exit 1 on findings
# or stale baseline entries, exit 2 on usage errors).
set -euo pipefail
cd "$(dirname "$0")/.."

SARIF_OUT="${DASLINT_SARIF:-/tmp/daslint.sarif}"
CHANGED=()
if [ "${1:-}" = "--changed-only" ]; then
  CHANGED=(--changed-only)
  shift
fi

# 1. analyzer — the lint.sh text pass gates (compileall + analyzer +
#    doc-gen check); a direct analyzer invocation then records SARIF
#    (stdout must be PURE JSON — lint.sh's doc-gen check line would
#    corrupt it; the re-run is near-free on the analyzer's parse cache)
ops/lint.sh "${CHANGED[@]}" "$@"
python -m das_tpu.analysis das_tpu --format sarif > "$SARIF_OUT"
echo "daslint SARIF: $SARIF_OUT"

# 2. the registry-pinning + observability + robustness + profiling +
#    durability suites as one pytest run (lint: analyzer clean-tree pin
#    + per-rule fixture corpus; obs: span coverage, percentile math,
#    exporters, DL014; fault: chaos-parity sweep, deadlines, breaker
#    lifecycle, commit atomicity, DL015; prof: program-ledger
#    lifecycle, explain(compile=True), byte-model calibration,
#    DL016; dur: crash-point matrix over the persist
#    fault sites, torn-tail WAL truncation, corrupt-generation
#    fallback, warm-restore pins, DL017)
python -m pytest tests/ -q -m "lint or obs or fault or prof or dur"
