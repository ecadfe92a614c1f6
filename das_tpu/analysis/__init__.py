"""daslint — AST invariant analyzer for the das_tpu contracts.

Four PRs of perf work (fused programs, dispatch/settle pipelining,
sharded parity) piled up invariants that existed only by convention
and reviewer memory: dispatch paths must be transfer-free, every field
that changes a traced program must live in the plan signature, every
DAS_TPU_* env read must be declared, counter keys must be registered
and test-pinned, and the coalescer's worker-thread state must honor
its locks.  Query-on-tensor-runtime systems live or
die on exactly these silent-recompile / cache-poisoning hazards (a
plan/signature mismatch surfaces as a wrong answer, not a crash), so
this package checks them mechanically, on every run of `ops/lint.sh`
and in the tier-1 suite (tests/test_zlint.py).

Since ISSUE 11 the analyzer is project-wide, not per-file: a
call-graph + dataflow core (analysis/callgraph.py — module symbol
tables, intra-repo call resolution, transitive reachability over
function summaries) backs the rules that follow helper calls, and a
(path, mtime, size) parse cache keeps the growing rule count fast.

Usage:  python -m das_tpu.analysis [paths...]   (wrapper: ops/lint.sh;
        --select/--ignore for subsets, --format sarif for CI,
        ops/lint.sh --changed-only for the pre-commit fast path)

Rules (one module each under rules/; contracts in ARCHITECTURE.md §11):

  DL001 host-sync-in-dispatch   dispatch halves are transfer-free
  DL002 plan-sig completeness   routing fields live in the frozen sig
  DL003 env registry            DAS_TPU_* reads <-> ENV_REGISTRY
  DL004 counter discipline      DISPATCH/ROUTE keys <-> ops/counters.py
  DL006 lock discipline         coalescer mutations <-> LOCK_DISCIPLINE
  DL007 cache-insert guard      delta_version captured before dispatch
  DL008 planner vocabularies    routes/counter keys <-> ops/counters.py
  DL009 collective discipline   collectives <-> COLLECTIVE_SITES
  DL010 transitive host sync    DL001 through the whole call graph
  DL012 retrace hygiene         jit closures derive from *Sig/constants
  DL013 fetch-site registry     jax.device_get <-> FETCH_SITES + tally
  DL014 obs name discipline     span/metric names <-> obs/registry.py
  DL015 fault-site registry     maybe_fail <-> FAULT_SITES, ban in
                                dispatch halves
  DL016 program-site registry   jax.jit <-> PROGRAM_SITES + the
                                instrument tally
  DL017 durability discipline   persist writes via atomic helpers,
                                fsync-before-rename, PERSIST_SITES

Per-file suppression: a comment line `# daslint: disable=DL001[,DL002]`
anywhere in a file disables those rules for that file.  Deliberate keeps
are grandfathered in daslint.baseline.json (repo root) with a one-line
justification; stale baseline entries fail the run so the file cannot
rot.  Everything here is stdlib-`ast` only — the analyzer never imports
the modules it checks.
"""

from das_tpu.analysis.core import (  # noqa: F401
    Finding,
    iter_rules,
    load_baseline,
    run_analysis,
)
