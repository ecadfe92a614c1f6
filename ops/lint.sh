#!/usr/bin/env bash
# Static gate: daslint (the AST invariant analyzer, ARCHITECTURE.md §11)
# + a bytecode compile of the whole package + the generated-docs check.
# Run from anywhere; pass extra args through to the analyzer
# (e.g. ops/lint.sh --select DL003 --format json).
#
# --changed-only (first arg): pre-commit fast path — analyze only the
# das_tpu/*.py files changed vs HEAD (staged, unstaged, untracked),
# plus the registry-bearing modules every cross-file rule anchors on
# (counters, ENV_REGISTRY, COLLECTIVE_SITES,
# FETCH_SITES, LOCK_DISCIPLINE), under --allow-partial so staleness
# legs that need the full tree don't fire on the subset.  The full run
# stays the authority; CI runs it.
set -euo pipefail
cd "$(dirname "$0")/.."

if [ "${1:-}" = "--changed-only" ]; then
  shift
  mapfile -t changed < <(
    {
      git diff --name-only HEAD -- 'das_tpu/*.py' 'das_tpu/**/*.py'
      git ls-files --others --exclude-standard -- 'das_tpu/*.py' 'das_tpu/**/*.py'
    } | sort -u
  )
  if [ "${#changed[@]}" -eq 0 ]; then
    echo "daslint: no changed das_tpu/*.py files — skipping analyzer"
    exit 0
  fi
  # registry anchors: cross-file rules resolve their declared sets here
  anchors=(
    das_tpu/ops/counters.py
    das_tpu/core/config.py
    das_tpu/parallel/mesh.py
    das_tpu/service/coalesce.py
    das_tpu/query/fused.py
  )
  files=()
  for f in "${changed[@]}" "${anchors[@]}"; do
    [ -f "$f" ] || continue
    case " ${files[*]-} " in *" $f "*) ;; *) files+=("$f") ;; esac
  done
  python -m compileall -q "${files[@]}"
  python -m das_tpu.analysis "${files[@]}" --allow-partial "$@"
  exit 0
fi

python -m compileall -q das_tpu
python -m das_tpu.analysis das_tpu "$@"
python scripts/gen_env_table.py --check
