"""Compiled conjunctive queries: the device fast path.

Reference behavior being replaced: `And.matched` retrieves each term's
candidate links, builds one Python Assignment object per candidate, and
joins assignment *sets* with an O(|A|×|B|) nested loop
(pattern_matcher.py:705-748).  Here a conjunctive query over ordered link
patterns compiles to a pipeline of device kernels:

    per term:  searchsorted range probe  → binding table (int32 matrix)
               + intra-term equality + lexsort dedup
    fold:      sort-merge equi-joins over shared variable columns
    negation:  anti-joins for each forbidden table whose variable set is
               covered by the output (exact reference semantics — tabu
               assignments with extra variables never exclude anything)
    output:    one padded (vals, valid) table + exact count

Join/anti-join/dedup kernels: das_tpu/ops/join.py.  The host orchestrates
stage boundaries (exact counts drive capacity-doubling retries and the
reference's empty-accumulator-reseed quirk) but touches no per-candidate
data until final materialization.

Compilable subset: `And`/bare patterns of *ordered* `Link`s (targets:
Node | grounded | Variable) and *ordered* `LinkTemplate`s, plus `Not` of
those; everything else (unordered multiset semantics, Or, nesting) falls
back to the host algebra, which is answer-identical.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from das_tpu.core.hashing import hex_to_i64
from das_tpu.ops.counters import ROUTE_KEYS
from das_tpu.ops.join import anti_join, build_term_table, dedup_table, join_tables
from das_tpu.query import assignment as asn_mod
from das_tpu.query.ast import (
    And,
    AnswerBlock,
    Link,
    LinkTemplate,
    LogicalExpression,
    Node,
    Not,
    PatternMatchingAnswer,
    TypedVariable,
    Variable,
)
from das_tpu.storage.tensor_db import TensorDB


@dataclass
class TermPlan:
    arity: int
    type_id: Optional[int]          # None only for template probes
    fixed: Tuple[Tuple[int, int], ...]   # (position, global_row)
    var_names: Tuple[str, ...]           # one per output column
    var_cols: Tuple[int, ...]            # first position of each var
    eq_pairs: Tuple[Tuple[int, int], ...]  # same-var repeated positions
    ctype: Optional[int] = None          # template probe key (int64)
    negated: bool = False


@dataclass
class BindingTable:
    var_names: Tuple[str, ...]
    vals: Optional[jax.Array]   # [cap, k] int32; None on the serving
    valid: Optional[jax.Array]  # [cap]     path, which reads the copies
    count: int
    host_vals: Optional[np.ndarray] = None   # prefetched host copies (one
    host_valid: Optional[np.ndarray] = None  # transfer with the stats)


class NotCompilable(Exception):
    pass


class UnknownAtom(NotCompilable):
    """A grounded node or link type that doesn't exist in the KB: the
    reference answers no-match for these, not an error — planners convert
    this (and only this) into a static False, never a host fallback."""


#: How queries were executed, for benchmark reporting and tests.  "fused" =
#: single-dispatch jitted program, "staged" = per-stage device kernels,
#: "tree" = generalized device tree executor, "host" = Python algebra
#: fallback (incremented by the API dispatcher, not here).  Keys are
#: DECLARED in ops/counters.py —
#: the one registry daslint rule DL004 pins every counting literal
#: against — and the dict is built from it so the two cannot drift.
ROUTE_COUNTS = {k: 0 for k in ROUTE_KEYS}


def reset_route_counts() -> None:
    for k in ROUTE_COUNTS:
        ROUTE_COUNTS[k] = 0


def _plan_term(db: TensorDB, term, negated: bool) -> TermPlan:
    if isinstance(term, LinkTemplate):
        if not term.ordered:
            raise NotCompilable("unordered template")
        arity = len(term.targets)
        names, cols, eq = [], [], []
        for p, tv in enumerate(term.targets):
            if not isinstance(tv, TypedVariable):
                raise NotCompilable("template target")
            if tv.name in names:
                eq.append((cols[names.index(tv.name)], p))
            else:
                names.append(tv.name)
                cols.append(p)
        from das_tpu.core.hashing import ExpressionHasher

        type_hashes = [
            db.data.table.get_named_type_hash(t)
            for t in [term.link_type, *[tv.type for tv in term.targets]]
        ]
        ctype_hex = ExpressionHasher.composite_hash(type_hashes)
        return TermPlan(
            arity=arity,
            type_id=None,
            fixed=(),
            var_names=tuple(names),
            var_cols=tuple(cols),
            eq_pairs=tuple(eq),
            ctype=int(hex_to_i64(ctype_hex)),
            negated=negated,
        )
    if not isinstance(term, Link) or not term.ordered:
        raise NotCompilable("not an ordered link")
    if term.atom_type in db.data.pattern_black_list:
        # no pattern index exists for blacklisted types; the host algebra
        # (whose get_matched_links consults the same blacklist) answers
        raise NotCompilable("blacklisted link type")
    arity = len(term.targets)
    fixed, names, cols, eq = [], [], [], []
    for p, target in enumerate(term.targets):
        if isinstance(target, TypedVariable):
            raise NotCompilable("typed variable in link")
        if isinstance(target, Variable):
            if target.name in names:
                eq.append((cols[names.index(target.name)], p))
            else:
                names.append(target.name)
                cols.append(p)
        elif isinstance(target, Node):
            handle = target.get_handle(db)
            row = db.fin.row_of_hex.get(handle)
            if row is None:
                raise UnknownAtom("unknown grounded node")  # term can't match
            fixed.append((p, row))
        else:
            raise NotCompilable("unsupported target kind")
    if not names:
        raise NotCompilable("fully grounded term")
    type_id = db._type_id(term.atom_type)
    if type_id is None:
        raise UnknownAtom("unknown link type")
    return TermPlan(
        arity=arity,
        type_id=type_id,
        fixed=tuple(fixed),
        var_names=tuple(names),
        var_cols=tuple(cols),
        eq_pairs=tuple(eq),
        negated=negated,
    )


#: sentinel for a statically-empty plan (a positive grounded atom that
#: doesn't exist): the reference answers no-match, not an error.  Opaque
#: (neither truthy-iterable nor None) so a caller that forgets the
#: `plans is EMPTY_PLAN` identity check fails fast instead of iterating it.
EMPTY_PLAN = object()


def plan_query(
    db: TensorDB, query: LogicalExpression, unknown_atom_empty: bool = False
) -> "Union[List[TermPlan], None, object]":
    """Return term plans, or None when the query isn't compilable.  With
    unknown_atom_empty, a POSITIVE term grounded on an atom absent from
    the store returns EMPTY_PLAN instead of None — callers composing plans
    (the sharded Or decomposition) can then skip the branch as a static
    no-match instead of abandoning device execution."""
    if asn_mod.CONFIG.get("no_overload"):
        return None
    if isinstance(query, (Link, LinkTemplate)):
        terms = [query]
    elif isinstance(query, And):
        terms = query.terms
    else:
        return None
    if not terms:
        return None
    plans = []
    try:
        for term in terms:
            if isinstance(term, Not):
                try:
                    plans.append(_plan_term(db, term.term, True))
                except UnknownAtom:
                    continue  # tabu on a nonexistent atom never excludes
            else:
                plans.append(_plan_term(db, term, False))
    except UnknownAtom:
        return EMPTY_PLAN if unknown_atom_empty else None
    except NotCompilable:
        return None
    if not plans or all(p.negated for p in plans):
        return None
    return plans


def _run_term(db: TensorDB, plan: TermPlan) -> Optional[BindingTable]:
    if plan.ctype is not None:
        padded = db.probe_ctype_padded(plan.arity, plan.ctype)
    else:
        padded = db.probe_ordered_padded(plan.arity, plan.type_id, plan.fixed)
    if padded is None:
        return None
    local, mask = padded
    bucket = db.dev.buckets[plan.arity]
    vals, mask = build_term_table(
        bucket.targets, local, mask, plan.var_cols, plan.eq_pairs
    )
    vals, keep, count = dedup_table(vals, mask)
    n = int(count)
    if n == 0:
        return None
    return BindingTable(plan.var_names, vals, keep, n)


def _join(db: TensorDB, left: BindingTable, right: BindingTable) -> BindingTable:
    shared = [
        (left.var_names.index(v), right.var_names.index(v))
        for v in left.var_names
        if v in right.var_names
    ]
    extra = tuple(
        i for i, v in enumerate(right.var_names) if v not in left.var_names
    )
    out_names = left.var_names + tuple(
        v for v in right.var_names if v not in left.var_names
    )
    cap = max(64, min(left.count * right.count, db.config.initial_result_capacity))
    while True:
        vals, valid, total = join_tables(
            left.vals, left.valid, right.vals, right.valid,
            tuple(shared), extra, cap,
        )
        t = int(total)
        if t <= cap:
            break
        if cap >= db.config.max_result_capacity:
            from das_tpu.core.exceptions import CapacityOverflowError

            raise CapacityOverflowError(
                f"join needs {t} rows > max_result_capacity "
                f"{db.config.max_result_capacity}"
            )
        cap = min(max(cap * 2, t), db.config.max_result_capacity)
    vals, keep, count = dedup_table(vals, valid)
    return BindingTable(out_names, vals, keep, int(count))


def _execute_fused(
    db: TensorDB, plans: List[TermPlan], count_only: bool = False
) -> Optional[BindingTable]:
    """Single-dispatch fast path (query/fused.py): the whole plan runs as
    one jitted program, cached per plan shape on the device tables so every
    re-grounding of the same query skips tracing entirely.  When the
    greedy-order program detects the empty-accumulator reseed condition,
    the exact reference-order variant (in-program reseed automaton) runs
    instead — still one dispatch.  Returns None only when a term's bucket
    is absent or a capacity ceiling is hit — caller runs the staged path,
    which is answer-identical."""
    from das_tpu.query.fused import get_executor

    ex = get_executor(db)
    res = ex.execute(plans, count_only=count_only)
    if res is not None and res.reseed_needed:
        res = ex.execute_exact(plans, count_only=count_only)
    if res is None or res.reseed_needed:
        return None
    return BindingTable(
        res.var_names, res.vals, res.valid, res.count,
        host_vals=res.host_vals, host_valid=res.host_valid,
    )


def execute_fused_many_dispatch(db: TensorDB, plans_lists: List[List[TermPlan]],
                                cache_only: bool = False):
    """Pipeline phase 1 for the serving coalescer: resolve result-cache
    hits and ENQUEUE the batch's fused programs on the device — purely
    asynchronous, no host transfer.  Returns the pending handle for
    execute_fused_many_settle; between the two calls the device executes
    this batch while the host settles/materializes the previous one.
    cache_only (degraded-mode serving, ISSUE 13 breaker) answers from
    the delta-versioned cache only — no device program is enqueued."""
    from das_tpu.query.fused import get_executor

    return get_executor(db).dispatch_many(plans_lists, cache_only=cache_only)


def execute_fused_many_settle_iter(
    db: TensorDB, plans_lists: List[List[TermPlan]], pending
):
    """Streaming pipeline phase 2 (ISSUE 6 early-settle): yields
    `(index, BindingTable-or-None)` as each query's verdict becomes
    final.  Settled entries stream in retry-round order — a query whose
    first round fit arrives one RTT after its own dispatch, while its
    batch-mates' capacity retries are still re-dispatching.
    Reseed-flagged entries resolve on the exact reference-order variant
    in place.  Declines yield None for the caller to replay on the
    staged/host path: a settle-time decline (capacity ceiling,
    unresolved reseed) yields IN VERDICT ORDER as its round lands,
    while dispatch-time declines (no job, no cache hit) are never seen
    by the settle stream and yield last."""
    from das_tpu.query.fused import get_executor

    ex = get_executor(db)
    seen = [False] * len(plans_lists)
    for i, res in ex.settle_many_iter(pending):
        seen[i] = True
        if res is not None and res.reseed_needed:
            res = ex.execute_exact(plans_lists[i])
        if res is None or res.reseed_needed:
            yield i, None
            continue
        # the serving path materializes from the prefetched host copies:
        # the device references stay unread (an answer that rode in a
        # group program would slice its lane out on the read)
        prefetched = res.host_vals is not None
        yield i, BindingTable(
            res.var_names,
            None if prefetched else res.vals,
            None if prefetched else res.valid,
            res.count,
            host_vals=res.host_vals, host_valid=res.host_valid,
        )
    for i, done in enumerate(seen):
        if not done:
            yield i, None


def execute_fused_many_settle(
    db: TensorDB, plans_lists: List[List[TermPlan]], pending
) -> List[Optional[BindingTable]]:
    """Pipeline phase 2: pay the host transfer, run per-query settle
    verdicts (capacity retries re-dispatch serially inside — the graceful
    fallback), and resolve reseed-flagged entries on the exact
    reference-order variant.  Queries the fused path declines come back
    None — the caller falls through to the staged/host path, exactly like
    the single-query route.  (The non-streaming form of
    execute_fused_many_settle_iter.)"""
    out: List[Optional[BindingTable]] = [None] * len(plans_lists)
    for i, table in execute_fused_many_settle_iter(db, plans_lists, pending):
        out[i] = table
    return out


def execute_sharded_many_dispatch(db, plans_lists: List[List[TermPlan]],
                                  cache_only: bool = False):
    """Mesh pendant of execute_fused_many_dispatch: resolve result-cache
    hits and ENQUEUE the batch's shard_map programs on the mesh — purely
    asynchronous.  The sharded serving path always opts into the
    delta-versioned result cache (same contract as _run_conjunctive);
    cache_only answers from it alone (degraded-mode serving)."""
    from das_tpu.parallel.fused_sharded import get_sharded_executor

    return get_sharded_executor(db).dispatch_many(
        plans_lists, cache_only=cache_only
    )


def execute_sharded_many_settle_iter(db, plans_lists, pending):
    """Mesh pendant of execute_fused_many_settle_iter: yields
    `(index, ShardedFusedResult-or-None)` as each query's verdict lands.
    Declines yield None for the caller to replay on the staged mesh
    pipeline (db.sharded_execute, answer-identical) — settle-time
    declines (capacity ceiling, reseed) in verdict order, dispatch-time
    declines last."""
    from das_tpu.parallel.fused_sharded import get_sharded_executor

    seen = [False] * len(plans_lists)
    for i, res in get_sharded_executor(db).settle_many_iter(pending):
        seen[i] = True
        yield i, (None if res is None or res.reseed_needed else res)
    for i, done in enumerate(seen):
        if not done:
            yield i, None


def execute_sharded_many_settle(db, plans_lists, pending) -> List:
    """Mesh pendant of execute_fused_many_settle: pay the host transfer,
    run per-query verdicts (capacity retries re-dispatch serially inside).
    Entries the fused mesh program declines — capacity ceiling or the
    reseed condition — come back None; the caller replays them on the
    staged mesh pipeline (db.sharded_execute), which is answer-identical."""
    out = [None] * len(plans_lists)
    for i, res in execute_sharded_many_settle_iter(db, plans_lists, pending):
        out[i] = res
    return out


def execute_fused_many(
    db: TensorDB, plans_lists: List[List[TermPlan]]
) -> List[Optional[BindingTable]]:
    """Batched `_execute_fused` for the serving coalescer: every query
    dispatches before ONE host transfer fetches all results (per retry
    round).  Queries the fused path declines (None) or that need the
    reseed fallback are resolved individually, exactly like the single
    path would."""
    pending = execute_fused_many_dispatch(db, plans_lists)
    return execute_fused_many_settle(db, plans_lists, pending)


def execute_plan(db: TensorDB, plans: List[TermPlan]) -> Optional[BindingTable]:
    """Run the pipeline; returns the final table or None for no match."""
    tabu_tables: List[BindingTable] = []
    accumulated: Optional[BindingTable] = None
    for plan in plans:
        table = _run_term(db, plan)
        if plan.negated:
            if table is not None:
                tabu_tables.append(table)
            continue
        if table is None:
            return None  # positive term unmatched -> whole And fails
        if accumulated is None or accumulated.count == 0:
            # reference quirk: an empty accumulator is re-seeded by the
            # next positive term (see das_tpu/query/ast.py And.matched)
            accumulated = table
        else:
            accumulated = _join(db, accumulated, table)
    if accumulated is None:
        return None
    valid = accumulated.valid
    for tabu in tabu_tables:
        if not set(tabu.var_names) <= set(accumulated.var_names):
            continue  # tabu with extra vars never excludes (NO_COVERING)
        pairs = tuple(
            (accumulated.var_names.index(v), tabu.var_names.index(v))
            for v in tabu.var_names
        )
        valid = anti_join(
            accumulated.vals, valid, tabu.vals, tabu.valid, pairs
        )
    count = int(valid.sum())
    return BindingTable(accumulated.var_names, accumulated.vals, valid, count)


def distinct_rows(rows: np.ndarray) -> np.ndarray:
    """Equal binding tuples once — what adding frozen assignments to a
    Python set gave.  A row sorts as ONE scalar, its bytes seen as an
    int64 where they are eight (two int32 columns) and as a fixed-width
    byte string otherwise: 10 x / 3 x faster at an answer's size than
    `np.unique(rows, axis=0)`, whose order the result does not keep
    (an answer is a set)."""
    n, k = rows.shape
    if n < 2:
        return rows
    width = rows.dtype.itemsize * k
    keys = np.sort(np.ascontiguousarray(rows).view(
        np.int64 if width == 8 else f"S{width}").ravel())
    first = np.ones(n, dtype=bool)
    first[1:] = keys[1:] != keys[:-1]
    return keys[first].view(rows.dtype).reshape(-1, k)


def _valid_rows(vals, valid) -> np.ndarray:
    return distinct_rows(np.asarray(vals)[np.asarray(valid)])


def materialize(db, table, answer: PatternMatchingAnswer,
                valid_rows=_valid_rows) -> bool:
    """A settled binding table into the answer, as a block: the
    distinct valid rows plus `var_names` (query/ast.py AnswerBlock).
    Frozen OrderedAssignments are built from it only when a consumer
    touches `answer.assignments`.  Shared by both backends: the mesh
    (parallel/sharded_db.py) passes its own `valid_rows`, the reshape
    over shards."""
    from das_tpu import obs

    if table is None or table.count == 0:
        return False
    with obs.span("exec.materialize", rows=table.count,
                  prefetched=table.host_vals is not None):
        if table.host_vals is not None:
            vals, valid = table.host_vals, table.host_valid
        else:
            # one transfer for both arrays (each separate fetch is a
            # host sync)
            from das_tpu.query.fused import FETCH_COUNTS

            FETCH_COUNTS["n"] += 1
            vals, valid = jax.device_get((table.vals, table.valid))
        answer.add_block(AnswerBlock(
            valid_rows(vals, valid), table.var_names,
            db.fin.hex_of_row))
    return answer.row_count() > 0


def query_on_device(db: TensorDB, query: LogicalExpression, answer: PatternMatchingAnswer) -> Optional[bool]:
    """Full compiled execution; returns None when not compilable (caller
    falls back to the host algebra).  Pure ordered conjunctions take the
    fused single-dispatch path; everything else in the logical language
    (Or, unordered links, nested And/Or, negation trees) runs through the
    generalized tree executor (query/tree.py)."""
    plans = plan_query(db, query)
    if plans is not None:
        table = _execute_fused(db, plans)
        if table is None:
            table = execute_plan(db, plans)
            ROUTE_COUNTS["staged"] += 1
        else:
            ROUTE_COUNTS["fused"] += 1
        return materialize(db, table, answer)
    from das_tpu.query.tree import query_tree

    matched = query_tree(db, query, answer)
    if matched is not None:
        ROUTE_COUNTS["tree"] += 1
    return matched


def dispatch(db, query: LogicalExpression, answer: PatternMatchingAnswer, host=None) -> bool:
    """Route one query against any backend: sharded mesh program →
    single-device compiled path → host algebra, with an overflow fallback.
    This is the single routing point used by the API facade
    (das_tpu/api/atomspace.py) and the reference-compat shim (compat/das),
    so `expr.matched(db, answer)`-style call sites get the same device
    execution as `DistributedAtomSpace.query`.

    `host` overrides the host-algebra fallback callable (db, answer) ->
    bool.  A query object may also advertise `host_matched` (the compat
    shim's routing wrappers do) so that ANY dispatch call site — not just
    the wrapper itself — falls back to the pure host evaluator instead of
    re-entering the wrapper's `matched` and paying the device attempt
    twice."""
    from das_tpu.core.exceptions import CapacityOverflowError
    from das_tpu.utils.logger import logger

    matched = None
    try:
        if hasattr(db, "query_sharded"):
            matched = db.query_sharded(query, answer)
            if matched is not None:
                ROUTE_COUNTS["sharded"] += 1
        elif isinstance(db, TensorDB):
            matched = query_on_device(db, query, answer)
    except CapacityOverflowError as exc:
        logger().warning(f"device query overflowed, host fallback: {exc}")
        answer.assignments.clear()
        answer.negation = False
        matched = None
    if matched is None:
        ROUTE_COUNTS["host"] += 1
        fallback = host or getattr(query, "host_matched", None) or query.matched
        matched = fallback(db, answer)
    return matched


def explain(db, query: LogicalExpression, execute: bool = False,
            compile: bool = False) -> dict:
    """Costed-plan explain surface (das_tpu/planner): what the planner
    decided for `query` — join order, expected route, estimated rows,
    capacity seeds — and with execute=True the actual per-stage rows and
    retry rounds next to the estimates (compile=True adds the program
    ledger's compile/cost/memory record, ISSUE 14).  Lives here so the
    API facade and the reference-compat shim share one entry point,
    mirroring `dispatch`."""
    from das_tpu import planner

    return planner.explain(db, query, execute=execute, compile=compile)


def count_matches_staged(db: TensorDB, plans: List[TermPlan]) -> int:
    """Staged-pipeline count for plans the fused path already declined —
    skips re-trying the fused executor (it would just rediscover the same
    reseed/overflow verdict at the cost of an extra device dispatch)."""
    table = execute_plan(db, plans)
    return 0 if table is None else table.count


def count_matches(db: TensorDB, query: LogicalExpression) -> Optional[int]:
    """Benchmark surface: exact match count without host materialization."""
    plans = plan_query(db, query)
    if plans is not None:
        from das_tpu.query.fused import trivial_plan_count

        n = trivial_plan_count(db, plans)
        if n is not None:
            # single unconstrained term: the host-side range size is exact
            # (no device dispatch, no whole-table materialization)
            return n
        from das_tpu.query import starcount

        n = starcount.try_star_count(db, plans)
        if n is not None:
            # star conjunction (one shared variable, the miner's joint
            # shape): closed-form Σ_v Π deg_t(v), no join materialization
            ROUTE_COUNTS["star"] += 1
            return n
        table = _execute_fused(db, plans, count_only=True)
        if table is None:
            table = execute_plan(db, plans)
        return 0 if table is None else table.count
    # generalized tree: counts are exact only after host-set identity
    # (constraint-permutation and hash-XOR quirks), so materialize
    from das_tpu.query.tree import query_tree

    answer = PatternMatchingAnswer()
    matched = query_tree(db, query, answer)
    if matched is None:
        return None
    return len(answer.assignments) if matched else 0
