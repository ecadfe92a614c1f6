"""Cost-based whole-plan query planner (ISSUE 8 / ROADMAP item).

Turns a conjunction into a COSTED whole-plan program before anything is
dispatched: join order from a Selinger-style DP over the wildcard-index
degree statistics (search.py / stats.py), per-step pricing in bytes
moved (cost.py), and an estimated initial capacity per intermediate — replacing the greedy smallest-first
`order_plans` and the blind `initial_result_capacity` seed, so most
queries settle in retry round 0 (every avoided retry tier is a fresh
XLA compile saved).

Consumers: `query/fused.py FusedExecutor._exec_job` and
`parallel/fused_sharded.py ShardedFusedExecutor._exec_job` call
`plan_conjunction` behind `DasConfig.use_planner` (env DAS_TPU_PLANNER;
"auto" = on — the planner is pure host arithmetic).  The tree executor's
ordered-conjunction leaves (query/tree.py conj) ride the same executor
hook.  Count batches keep their structural ordering (`_count_order`
exists to SHARE compiles across miner lanes; per-lane planning would
fragment them).

Observability: `PLANNER_COUNTS` (keys declared in ops/counters.py
PLANNER_KEYS, daslint DL008) tracks planned-vs-greedy traffic, retry
rounds, and summed estimated-vs-actual join rows;
`DistributedAtomSpace.explain(query)` renders one query's costed plan
(and, with execute=True, the actual per-stage rows next to the
estimates); the service facade folds `snapshot()` into
`coalescer_stats()["planner"]` so estimator drift is visible in
production.

Correctness envelope: the planner chooses among orders the executors
already accept — answers are bit-identical to the legacy path for every
order (the reseed quirk re-answers on the exact variant exactly as
before), and capacity seeds only move the STARTING rung of the existing
overflow-retry ladder.  A planner bug can cost time, never answers.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from das_tpu.ops.counters import PLANNER_KEYS

#: planner telemetry; keys DECLARED in ops/counters.py (PLANNER_KEYS)
#: and pinned by daslint rule DL008 — the dict is built from the
#: registry so the two cannot drift (the DL004 idiom).
PLANNER_COUNTS: Dict[str, int] = {k: 0 for k in PLANNER_KEYS}


def reset_planner_counts() -> None:
    for k in PLANNER_COUNTS:
        PLANNER_COUNTS[k] = 0


def enabled(config=None) -> bool:
    """Resolve planner routing.  Env DAS_TPU_PLANNER beats the config so
    a deployment (or the bench A/B) can flip the path without code
    changes."""
    mode = os.environ.get("DAS_TPU_PLANNER")
    if mode is None and config is not None:
        mode = getattr(config, "use_planner", "auto")
    mode = str("auto" if mode is None else mode).lower()
    if mode in ("off", "0", "false"):
        return False
    return True  # "auto"/"on": pure host arithmetic, on everywhere


def snapshot() -> Dict[str, float]:
    """Counter snapshot plus the estimator-error ratio operators watch:
    actual/estimated summed join rows of settled planned programs (1.0 =
    the statistics still describe the data; >>1 = skew has outgrown the
    uniformity assumption and capacity seeds are starting to retry)."""
    out = dict(PLANNER_COUNTS)
    est = out.get("est_rows", 0)
    out["actual_vs_est_ratio"] = (
        round(out.get("actual_rows", 0) / est, 4) if est else None
    )
    return out


def record_planned(planned) -> None:
    """Executor-hook accounting: one planner-driven conjunction plus the
    search method that produced its order.  Lives HERE, not in
    plan_conjunction, so explain() (which also plans) never inflates the
    planned/method decomposition — dp + greedy_tail + ref_order always
    sums to `planned`.  The explicit literal dispatch (instead of
    `PLANNER_COUNTS[planned.method]`) keeps every counting site a
    declared-key literal daslint DL008 can pin."""
    PLANNER_COUNTS["planned"] += 1
    method = planned.method
    if method == "dp":
        PLANNER_COUNTS["dp"] += 1
    elif method == "greedy_tail":
        PLANNER_COUNTS["greedy_tail"] += 1
    else:
        PLANNER_COUNTS["ref_order"] += 1


def observe_settle(planned, actual_join_rows, rounds: int,
                   shards: int = 1) -> None:
    """Fold one settled planner-driven job into the telemetry: retry
    rounds actually paid and estimated-vs-actual join output rows (the
    estimator-error signal).  Called from the executors' settle halves
    (inside span `exec.verdict`).  Totals only: the worker pays for
    every event it records, and nobody read one job's.
    The sharded executor's per-join actuals are WORST-SHARD totals, so
    its estimates are scaled to the even-split per-shard expectation —
    a ratio drifting past the 2x skew headroom is exactly the signal
    that hub keys are concentrating on one shard."""
    if rounds <= 1:
        PLANNER_COUNTS["round0"] += 1
    else:
        PLANNER_COUNTS["retries"] += rounds - 1
    est = sum(-(-int(r) // max(shards, 1)) for r in planned.est_join_rows)
    act = sum(int(r) for r in actual_join_rows)
    PLANNER_COUNTS["est_rows"] += est
    PLANNER_COUNTS["actual_rows"] += act


# re-exports: the public planner surface
from das_tpu.planner.search import (  # noqa: E402
    PlannedProgram,
    PlannedTree,
    plan_conjunction,
    plan_tree,
)
from das_tpu.planner.stats import (  # noqa: E402
    CardinalityEstimator,
    estimator_for,
)


def _term_brief(plan) -> Dict:
    """Human-readable one-liner for explain output."""
    return {
        "arity": plan.arity,
        "type_id": plan.type_id,
        "ctype": plan.ctype,
        "fixed": list(plan.fixed),
        "vars": list(plan.var_names),
        "negated": plan.negated,
    }


#: sentinel: "no precomputed plan — run plan_conjunction here" (None is
#: a legitimate computed outcome, the planner's decline)
_UNPLANNED = object()


def _compile_report(digest: str, site_hint: Optional[str] = None) -> Dict:
    """The explain(compile=True) block: ledger rows for the executed
    program's signature digest (compile wall, cost/memory analysis,
    calibration ratio), falling back to the site's rows when the digest
    has no entry (e.g. the program compiled before the ledger was
    enabled).  `enabled` False with empty rows tells the operator WHY
    nothing is there."""
    from das_tpu.obs import proflog

    rows = proflog.rows(digest=digest)
    if not rows and site_hint is not None:
        rows = proflog.rows(site=site_hint)
    return {
        "enabled": proflog.enabled(),
        "digest": digest,
        "rows": rows,
    }


def _explain_plans(db, plans, execute: bool, sharded: bool,
                   planned=_UNPLANNED, compile_report: bool = False) -> Dict:
    if planned is _UNPLANNED:
        PLANNER_COUNTS["explain"] += 1
        n_shards = 1
        if sharded:
            n_shards = int(db.mesh.devices.size)
        planned = plan_conjunction(db, list(plans), n_shards=n_shards)
    out: Dict = {
        "route": (
            planned.route if planned is not None
            else ("sharded" if sharded else "fused")
        ),
        "planner_enabled": enabled(getattr(db, "config", None)),
        "planned": planned is not None,
    }
    if planned is not None:
        out.update(
            method=planned.method,
            cost_bytes=planned.cost,
            order=[_term_brief(plans[i]) for i in planned.order],
            est_term_rows=list(planned.est_term_rows),
            est_join_rows=list(planned.est_join_rows),
            join_cap_seeds=list(planned.join_cap_seeds),
        )
    if not execute:
        return out
    # run the job through the executor's real dispatch/settle halves so
    # "actual" reflects the exact program production would run (route,
    # caps, learned-capacity merge included)
    if sharded:
        from das_tpu.parallel.fused_sharded import get_sharded_executor

        ex = get_sharded_executor(db)
    else:
        from das_tpu.query.fused import get_executor

        ex = get_executor(db)
    job = ex._exec_job(list(plans), False)
    if job is None:
        out["actual"] = None  # executor declined: staged/host path answers
        if compile_report:
            out["compile"] = None
        return out
    import jax

    from das_tpu.query.fused import FETCH_COUNTS

    while True:
        dev = job.dispatch()
        FETCH_COUNTS["n"] += 1  # one settle transfer per round (DL013)
        if job.settle(jax.device_get(dev), dev):
            break
    result = job.result
    out["actual"] = {
        "count": None if result is None else result.count,
        "term_rows": list(getattr(job, "last_ranges", ()) or ()),
        "join_rows": list(getattr(job, "last_join_rows", ()) or ()),
        "retry_rounds": max(0, getattr(job, "rounds", 1) - 1),
        "reseed_fallback": bool(getattr(result, "reseed_needed", False)),
    }
    if compile_report:
        # the dispatched program's ledger record (ISSUE 14): the final
        # plan_sig is the signature the settled round compiled under —
        # the same digest the builders keyed instrument() with
        from das_tpu.obs import proflog

        out["compile"] = _compile_report(
            proflog.sig_digest(job.plan_sig(), False),
            site_hint="sharded" if sharded else "fused",
        )
    return out


def _explain_tree_fused(db, fusable, execute: bool, sharded: bool,
                        compile_report: bool = False) -> Dict:
    """Render the whole-tree fused plan (ISSUE 10): per-site costed
    conjunction plans, the union/anti placement the one program
    hard-codes, and per-branch estimated rows — with execute=True, the
    actual per-site rows, retry rounds and the final count out of the
    SINGLE dispatched program."""
    PLANNER_COUNTS["explain"] += 1
    pos_sites, neg_plans, _const = fusable
    n_shards = int(db.mesh.devices.size) if sharded else 1
    pt = plan_tree(db, pos_sites, neg_plans, n_shards=n_shards)
    # render per-site detail from the plans plan_tree ALREADY computed —
    # one explain call plans each site exactly once and bumps the
    # explain counter exactly once
    site_plans = (
        pt.site_plans if pt is not None else tuple(None for _ in pos_sites)
    )
    out: Dict = {
        "route": (
            pt.route if pt is not None
            else ("sharded_tree_fused" if sharded else "fused_tree")
        ),
        "planned": pt is not None,
        "tree_fused": True,
        "planner_enabled": enabled(getattr(db, "config", None)),
        "sites": [
            _explain_plans(db, site, False, sharded, planned=sp)
            for site, sp in zip(pos_sites, site_plans)
        ],
        "neg_site": (
            _explain_plans(
                db, neg_plans, False, sharded,
                planned=pt.neg_plan if pt is not None else None,
            )
            if neg_plans else None
        ),
    }
    if pt is not None:
        out.update(
            cost_bytes=pt.cost,
            est_site_rows=list(pt.est_site_rows),
            est_union_rows=pt.est_union_rows,
            # placement: the union (concat + dedup) runs after ALL
            # positive sites; the anti (difference) after the union
            union_after=pt.union_after,
            anti_after_union=pt.anti_after_union,
        )
    if not execute:
        return out
    if sharded:
        from das_tpu.parallel.fused_sharded import get_sharded_executor

        ex = get_sharded_executor(db)
    else:
        from das_tpu.query.fused import get_executor

        ex = get_executor(db)
    job = ex.execute_tree(pos_sites, neg_plans)
    if job is None or job.result is None:
        out["actual"] = None  # declined: the tree executor answers
        if compile_report:
            out["compile"] = None
        return out
    if compile_report:
        from das_tpu.obs import proflog

        out["compile"] = _compile_report(
            proflog.sig_digest(job.tree_sig(), False),
            site_hint="sharded_tree" if sharded else "fused_tree",
        )
    out["actual"] = {
        "count": job.result.count,
        # the mesh union dedups SHARD-LOCALLY (cross-shard duplicate
        # answers die in the host set at materialization — the
        # ShardedTreeOps rule), so the replicated count UPPER-BOUNDS
        # the distinct answer count on the sharded route; single-device
        # counts are exact post-dedup
        "count_is_upper_bound": sharded,
        "matched_any": job.matched_any,
        "retry_rounds": max(0, job.rounds - 1),
        "programs": job.rounds,
        "sites": [
            {
                "count": j.result.count,
                "term_rows": list(j.last_ranges or ()),
                "join_rows": list(j.last_join_rows or ()),
            }
            for j in job.site_jobs
        ],
        "neg_site": (
            {
                "count": job.neg_job.result.count,
                "term_rows": list(job.neg_job.last_ranges or ()),
                "join_rows": list(job.neg_job.last_join_rows or ()),
            }
            if job.neg_job is not None else None
        ),
    }
    return out


def explain(db, query, execute: bool = False,
            compile: bool = False) -> Dict:
    """The observability surface behind `DistributedAtomSpace.explain`:
    what the planner decided for `query` — chosen order, route,
    estimated rows, capacity seeds — and, with execute=True, the actual
    per-stage rows and retry rounds next to the estimates.  An
    Or/negation tree in the fusable subset reports the WHOLE-TREE fused
    plan (site order, union/anti placement, per-branch est rows —
    _explain_tree_fused); other tree composites report one entry per
    ordered-conjunction site (query/tree.py conj_sites); queries
    outside the compiled language report route "host".

    With compile=True (ISSUE 14; implies execute — the rows describe
    the program the executor actually dispatched) each entry gains a
    `compile` block: the program ledger's record for the executed
    signature — compile wall seconds, cost_analysis flops /
    bytes-accessed, memory_analysis byte columns and the byte-model
    calibration ratio (das_tpu/obs/proflog.py; empty rows with
    enabled=False when DAS_TPU_PROFLOG is off)."""
    from das_tpu.query import compiler as qc

    execute = execute or compile
    plans = qc.plan_query(db, query)
    if plans is qc.EMPTY_PLAN:
        return {"route": "fused", "planned": False, "empty": True}
    sharded = hasattr(db, "query_sharded")
    if plans is not None:
        return _explain_plans(
            db, plans, execute, sharded, compile_report=compile
        )
    from das_tpu.query.plan import NotCompilable, build_plan
    from das_tpu.query.tree import (
        conj_sites,
        tree_fusion_enabled,
        tree_fusion_sites,
    )

    try:
        node = build_plan(db, query)
    except NotCompilable:
        return {"route": "host", "planned": False}
    fusable = tree_fusion_sites(node)
    if fusable is not None and tree_fusion_enabled(
        getattr(db, "config", None)
    ):
        return _explain_tree_fused(
            db, fusable, execute, sharded, compile_report=compile
        )
    sites = conj_sites(node)
    return {
        "route": "tree",
        "planned": bool(sites),
        "sites": [
            _explain_plans(
                db, site, execute, sharded, compile_report=compile
            )
            for site in sites
        ],
    }
