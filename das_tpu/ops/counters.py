"""Central registry of telemetry counter keys (daslint DL004).

Until round 8 the DISPATCH_COUNTS/ROUTE_COUNTS key strings were
scattered literals across seven modules — a typo'd key would count into
a fresh dict slot while the pinned key stayed zero, and the dispatch-
count regression pins only catch that for paths someone thought to pin.
These tuples are now the ONE declared set: the dicts are built from
them (DISPATCH_COUNTS below, `das_tpu/query/compiler.py` ROUTE_COUNTS),
the analyzer (das_tpu/analysis, rule DL004) pins every counting literal
against them in both directions, and tests/test_zlint.py pins the
tuples themselves so a key rename cannot slip through unreviewed.

This module imports nothing at load — every counter owner and user
(ops/, query/, parallel/, the analyzer's fixtures) can depend on it
without cycles.
"""

#: host-side launches of compiled device programs, by path.  "lowered" =
#: one generic jitted op of the staged pipeline (ops/posting.py,
#: ops/join.py wrappers), "fused" = one whole-plan single-dispatch
#: program, alone or a group's (query/fused.py), "fused_tree" = ONE
#: whole-tree program for an Or/negation plan tree (query/fused.py
#: _TreeExecJob.dispatch), "sharded" / "sharded_tree_fused" = their
#: shard_map mesh twins (parallel/fused_sharded.py), "count" = one
#: vmapped count-batch group program (query/fused.py count_batch).
#: The dispatch-count regression tests pin the per-query totals so a
#: refactor can't silently re-fragment the pipeline.
DISPATCH_KEYS = (
    "lowered",
    "fused",
    "fused_tree",
    "sharded",
    "sharded_tree_fused",
    "count",
)

#: the counts themselves, built from the registry so dict and registry
#: cannot drift
DISPATCH_COUNTS = {k: 0 for k in DISPATCH_KEYS}


def record_dispatch(kind: str, n: int = 1) -> None:
    DISPATCH_COUNTS[kind] = DISPATCH_COUNTS.get(kind, 0) + n
    from das_tpu import obs

    if obs.enabled():
        # the obs metric layer's one aggregate dispatch tick — every
        # device-program enqueue funnels through here, so the Prometheus
        # surface gets a total without a counter per DISPATCH_KEYS route
        obs.counter("exec.dispatches").inc(n)


def reset_dispatch_counts() -> None:
    for k in DISPATCH_COUNTS:
        DISPATCH_COUNTS[k] = 0


#: per-query answer routes — the dict lives in query/compiler.py;
#: counting sites: query/compiler.py (the per-query router),
#: api/atomspace.py (batched settle), query/fused.py (tree-job settle),
#: mining/miner.py (star lanes).  The cost-based planner
#: (das_tpu/planner) PREDICTS one of these per plan — daslint rule
#: DL008 pins every planner route literal against this tuple, so a
#: planner emitting a route no counter tracks fails lint.
ROUTE_KEYS = (
    "fused",
    #: the whole Or/negation plan tree settled as ONE fused program
    #: (in-program union + anti; counted at tree-job settle in
    #: query/fused.py — a fused-tree answer also counts "tree", its
    #: route family); the planner's plan_tree emits these two keys
    "fused_tree",
    "sharded_tree_fused",
    "staged",
    "tree",
    "sharded",
    "host",
    "star",
)

#: cost-based planner telemetry — the dict (PLANNER_COUNTS) lives in
#: das_tpu/planner/__init__.py and is BUILT from this tuple; counting
#: sites: planner/__init__.py (record_planned, explain, settle
#: observation), query/fused.py and parallel/fused_sharded.py (the
#: _exec_job planner hooks + per-program dispatch accounting).
#: plan_conjunction itself counts NOTHING — explain() plans too, and
#: the planned/method decomposition must cover executor traffic only
#: (dp + greedy_tail + ref_order always sums to planned).
#: daslint rule DL008 pins every
#: PLANNER_COUNTS[...] literal against this tuple in both directions,
#: exactly like DL004 does for the two sets above.
#:   planned / greedy   — conjunctions ordered+seeded by the planner vs
#:                        the legacy heuristics (off, declined, count
#:                        paths)
#:   dp / greedy_tail / ref_order — which search produced the plan
#:   programs           — device programs dispatched for planned jobs
#:   round0 / retries   — planned jobs settled with no capacity retry /
#:                        total retry rounds planned jobs still paid
#:   est_rows / actual_rows — summed estimated vs actual join output
#:                        rows of settled planned jobs (estimator-error
#:                        observability: a drifting ratio means the
#:                        degree statistics no longer describe the data)
#:   explain            — explain() invocations
PLANNER_KEYS = (
    "planned",
    "greedy",
    "dp",
    "greedy_tail",
    "ref_order",
    "programs",
    "round0",
    "retries",
    "est_rows",
    "actual_rows",
    "explain",
)
