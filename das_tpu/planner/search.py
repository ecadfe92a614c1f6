"""Join-order search: costed whole-plan programs (`PlannedProgram`).

Selinger-style dynamic programming over CONNECTED subsets of the
positive terms, left-deep chains only (the executors fold left-deep),
up to ``DAS_TPU_PLANNER_DP_MAX`` clauses (default 8: 2^8 subsets × 8
extensions is microseconds of host arithmetic); wider conjunctions fall
back to greedy smallest-ESTIMATED-OUTPUT-first — still a strict upgrade
over the legacy smallest-term-first, which ignores join selectivity
entirely.

One ordering rule is inherited unchanged from `order_plans`
(query/fused.py): when the positive terms are CONNECTED in reference
order and at least one is grounded, the reference order is kept — the
compiled program is then the reference fold itself, its in-program
reseed flag is authoritative, and a zero-count answer needs no
exact-variant re-run.  The planner still prices that order and seeds
its capacities; it just refuses to trade the reseed authority away for
an estimated win on queries whose intermediates are small by
construction (they are grounded).  Reordering stays bit-identical
either way — the executors' reseed fallback re-answers any order the
quirk could bite — this rule is about not PAYING that fallback.

Negated terms filter at the end regardless of order, exactly like the
legacy ordering.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from das_tpu.planner import cost as pcost
from das_tpu.planner.stats import RelEstimate, estimator_for
from das_tpu.query.fused import reference_order_authoritative

#: exact-DP clause ceiling (env DAS_TPU_PLANNER_DP_MAX); beyond it the
#: greedy-by-estimated-output tail orders the conjunction
DEFAULT_DP_MAX = 8

def dp_max() -> int:
    raw = os.environ.get("DAS_TPU_PLANNER_DP_MAX")
    if not raw:
        return DEFAULT_DP_MAX
    try:
        return max(int(raw), 2)
    except ValueError:
        return DEFAULT_DP_MAX


@dataclass(frozen=True)
class PlannedProgram:
    """One costed whole-plan decision, fixed BEFORE anything dispatches.

    order          — permutation into the caller's plan list (positives
                     in chosen join order, then negatives)
    est_term_rows  — exact per-term candidate rows, in `order`
    est_join_rows  — estimated output rows per join (the executors'
                     stats report the same layout, so est-vs-actual
                     compares like with like)
    join_cap_seeds — initial capacity per join buffer (margin + pow2),
                     replacing the blind initial_result_capacity seed;
                     same layout as est_join_rows
    route          — the answer route this plan expects to take; always
                     a member of ops/counters.py ROUTE_KEYS (daslint
                     DL008 pins this)
    method         — "dp" / "greedy_tail" / "ref_order" (PLANNER_KEYS)
    cost           — the model's bytes-moved figure for the whole chain
    """

    order: Tuple[int, ...]
    est_term_rows: Tuple[int, ...]
    est_join_rows: Tuple[int, ...]
    join_cap_seeds: Tuple[int, ...]
    route: str
    method: str
    cost: float


def _shares_var(a, b) -> bool:
    return bool(set(a.var_names) & set(b.var_names))


def _connected(plans: List) -> bool:
    """All positive terms form one variable-connected component."""
    if len(plans) <= 1:
        return True
    seen = {0}
    grew = True
    while grew:
        grew = False
        for i, p in enumerate(plans):
            if i in seen:
                continue
            if any(_shares_var(p, plans[j]) for j in seen):
                seen.add(i)
                grew = True
    return len(seen) == len(plans)


def _index_join_eligible(plan) -> bool:
    """Mirror of query/fused.py plan_index_joins' right-side test: an
    ordered whole-type probe (no grounding, no template key, no repeated
    variables, positive) — the executor will probe the posting index
    instead of materializing the table, and the join CAPACITY then
    scales with the FIRST shared variable's candidate count."""
    return (
        not plan.negated
        and not plan.eq_pairs
        and not plan.fixed
        and plan.ctype is None
        and plan.type_id is not None
    )


def _join_step(est, acc, right, right_plan):
    """One left-deep join step: (folded RelEstimate, capacity-relevant
    rows, shared-var count, exact?).  For an index-join-eligible right
    side the capacity model is stats.pair_join_rows: on ONE shared
    variable the posting index's candidate count, never below the
    final match estimate; on two or more the rows that agree on all of
    them (the join verifies a pair before it counts it), never above
    the first variable's candidates.  The step is PRICED on the same
    rows (cost.join_step_cost: both tables whole, which is what the
    verified join sorts, plus the window it writes).  `exact` marks a
    capacity figure derived from the degree dot product — a hard bound
    on what the overflow stats can report, so the seed needs no
    estimate-error margin."""
    shared = [v for v in acc.dv if v in right.dv]
    out = est.join_estimate(acc, right)
    cap_rows = out.rows
    exact = (
        len(shared) == 1
        and acc.plan is not None and right.plan is not None
        and est.exact_join_rows(acc.plan, right.plan, shared[0]) is not None
    )
    if shared and _index_join_eligible(right_plan):
        pr, p_exact = est.pair_join_rows(acc, right, shared)
        if len(shared) > 1 or pr >= cap_rows:
            cap_rows, exact = pr, p_exact
    return out, cap_rows, len(shared), exact


def _chain_estimates(est, terms: List, order: Tuple[int, ...]):
    """(est_join_rows, join_cap_seeds, cost) of one left-deep order.
    est_join_rows are the CAPACITY-relevant per-join rows — the number
    the executors' overflow stats report (the rows of the join, for an
    index join and a materialized one alike) — so est-vs-actual
    telemetry compares like with like."""
    rels = [est.term_estimate(terms[i]) for i in order]
    acc = rels[0]
    widths = [len(terms[i].var_names) for i in order]
    width = widths[0]
    total = pcost.term_cost(int(acc.rows), width)
    join_rows: List[int] = []
    max_cap = _max_capacity(est.db)
    caps: List[int] = []
    for n in range(1, len(order)):
        right = rels[n]
        out, cap_rows, n_pairs, exact = _join_step(
            est, acc, right, terms[order[n]]
        )
        out_width = width + sum(
            1 for v in terms[order[n]].var_names if v not in acc.dv
        )
        total += pcost.term_cost(int(right.rows), widths[n])
        total += pcost.join_step_cost(
            acc.rows, width, right.rows, widths[n],
            n_pairs, cap_rows, out_width, max_cap,
        )
        join_rows.append(int(cap_rows))
        caps.append(pcost.cap_for(cap_rows, max_cap, exact=exact))
        acc = out
        width = out_width
    return tuple(join_rows), tuple(caps), total


def _star_prefix(terms: List, order: Tuple[int, ...]):
    """(m, v): the longest prefix of the ordered positives forming a
    STAR on one shared variable — every clause after the first shares
    EXACTLY {v} with the variables accumulated so far (its remaining
    variables are fresh), so each prefix intermediate is a k-way star
    join whose exact size `stats.star_rows` computes.  m == 0 when
    even the first join is not a single-variable step."""
    if len(order) < 2:
        return 0, None
    seen = set(terms[order[0]].var_names)
    shared0 = set(terms[order[1]].var_names) & seen
    if len(shared0) != 1:
        return 0, None
    v = next(iter(shared0))
    m = 1
    for idx in order[1:]:
        t = terms[idx]
        if (set(t.var_names) & seen) != {v}:
            break
        seen |= set(t.var_names)
        m += 1
    return (m if m >= 2 else 0), v


def _max_capacity(db) -> int:
    return int(getattr(
        getattr(db, "config", None), "max_result_capacity", 1 << 24
    ))


def _star_chain_seeds(est, terms, order, join_rows, caps, max_cap):
    """Seeds of a star prefix from the EXACT k-way statistic: the
    chain's DEEPER star-prefix intermediates would otherwise ride the
    independence model, which errs low exactly on skew (a guaranteed
    retry tier).  But the intermediate after folding prefix clauses
    0..t+1 IS the (t+2)-way star join, whose exact size
    `stats.star_rows` computes: use it for the capacity seed,
    margin-free, so the chain settles in round 0 on skew shapes.

    The statistic covers INDEX-JOIN steps too: a star step shares
    exactly ONE variable, so the posting-index candidate count — Σ over
    accumulator rows of the right term's degree at the probed position
    — telescopes to Σ_v Π_j deg_j(v) over the intersected supports,
    which is star_rows verbatim (no remaining shared columns exist to
    verify candidates away).  The capacity model and the match count
    coincide on stars, so the seed is exact for index joins too."""
    m, v = _star_prefix(terms, order)
    if m < 3:
        return join_rows, caps  # the first join is already exact (dot)
    join_rows, caps = list(join_rows), list(caps)
    for t in range(1, m - 1):
        prefix = [terms[order[j]] for j in range(t + 2)]
        rows, exact = est.star_rows(prefix, v)
        if exact:
            join_rows[t] = int(rows)
            caps[t] = pcost.cap_for(rows, max_cap, exact=True)
    return tuple(join_rows), tuple(caps)


def _dp_order(est, terms: List) -> Tuple[int, ...]:
    """Best left-deep order over connected subsets (exact within the
    model).  States key on frozensets of term indices; transitions only
    extend by variable-connected terms, so cross products never enter a
    plan for a connected conjunction."""
    n = len(terms)
    rels = [est.term_estimate(t) for t in terms]
    widths = [len(t.var_names) for t in terms]
    max_cap = _max_capacity(est.db)
    # state -> (cost, order, RelEstimate, width)
    best: Dict[frozenset, Tuple[float, Tuple[int, ...], RelEstimate, int]] = {}
    for i in range(n):
        best[frozenset((i,))] = (
            pcost.term_cost(int(rels[i].rows), widths[i]),
            (i,), rels[i], widths[i],
        )
    for size in range(1, n):
        for state, (c, order, acc, width) in list(best.items()):
            if len(state) != size:
                continue
            for j in range(n):
                if j in state:
                    continue
                if not any(_shares_var(terms[j], terms[i]) for i in state):
                    continue
                out, cap_rows, n_pairs, _exact = _join_step(
                    est, acc, rels[j], terms[j]
                )
                out_width = width + sum(
                    1 for v in terms[j].var_names if v not in acc.dv
                )
                c2 = c + pcost.term_cost(int(rels[j].rows), widths[j])
                c2 += pcost.join_step_cost(
                    acc.rows, width, rels[j].rows, widths[j],
                    n_pairs, cap_rows, out_width, max_cap,
                )
                key = state | {j}
                cur = best.get(key)
                if cur is None or c2 < cur[0]:
                    best[key] = (c2, order + (j,), out, out_width)
    return best[frozenset(range(n))][1]


def _greedy_order(est, terms: List) -> Tuple[int, ...]:
    """Greedy tail for conjunctions past the DP ceiling: start from the
    smallest term, always extend with the connected term minimizing the
    estimated join OUTPUT (selectivity-aware, unlike the legacy
    smallest-term-first)."""
    n = len(terms)
    rels = [est.term_estimate(t) for t in terms]
    start = min(range(n), key=lambda i: rels[i].rows)
    order = [start]
    acc = rels[start]
    remaining = set(range(n)) - {start}
    while remaining:
        connected = [
            j for j in remaining
            if any(_shares_var(terms[j], terms[i]) for i in order)
        ] or list(remaining)
        j = min(
            connected,
            key=lambda j: _join_step(est, acc, rels[j], terms[j])[1],
        )
        acc = _join_step(est, acc, rels[j], terms[j])[0]
        order.append(j)
        remaining.remove(j)
    return tuple(order)


def conjunction_rule(plans):
    """What of a plan the SHAPE of the conjunction decides, whatever
    its grounded values: `(pos_idx, neg_idx, method)`, or None when the
    planner declines (no positive term, disconnected positives).
    `method` is "ref_order" where the reference-order rule fixes the
    order, else None: the order is then searched per query from its
    own counts.  The executor's job builder keeps it per shape
    (query/fused.py _JobTemplate)."""
    if not plans or not isinstance(plans, (list, tuple)):
        return None
    pos_idx = [i for i, p in enumerate(plans) if not p.negated]
    neg_idx = [i for i, p in enumerate(plans) if p.negated]
    if not pos_idx:
        return None
    positives = [plans[i] for i in pos_idx]
    if not _connected(positives):
        return None  # cross products: legacy ordering owns the rare case
    # reference-order authority rule — ONE shared predicate with
    # order_plans (see module docstring)
    method = "ref_order" if reference_order_authoritative(positives) else None
    return pos_idx, neg_idx, method


#: per-shard share of a join's estimated rows from which
#: `shard_cap_seed` sizes the buffer near the share
LARGE_SHARE_ROWS = 1 << 20


def shard_cap_seed(cap: int, est_rows: int, n_shards: int) -> int:
    """Per-shard capacity seed of one join buffer on `n_shards` shards,
    from its one-chip seed `cap` (cost.cap_for: the estimate, its
    margin unless exact, rounded up to a power of two) and the
    estimate itself.

    A short table: the even split of `cap` with 2x skew headroom, then
    the power of two (slabs are round-robin, so ranges spread evenly;
    the headroom plus the overflow retry covers a hub) — every grounded
    shape, unchanged.

    A LONG one (a share of LARGE_SHARE_ROWS estimated rows or more a
    shard): the share of the ESTIMATE with the one-chip seed's own
    margin (`cap / est_rows` below 2 says the figure was exact and
    carries none) and an eighth on top, then the power of two, and
    never above the rule for short tables.  `cap` has already rounded
    the estimate up by as much as 2x, so splitting it and doubling
    again stacks three headrooms: 2.25 M rows a shard (the whole-store
    conjunction's first join at FlyBase scale 0.3 on 4 shards) seeded
    8.4 M slots, and every table-long pass and every sort operand of
    the program after it doubled with it, on a store whose shards
    differ by a fraction of a per cent: rows dealt round-robin put a
    share of millions of rows within a per mille of even, where a
    16-row probe can land whole on one slab.  The power of two still
    leaves 0 to 100 % of room, and an overflow is a counted retry that
    grows the buffer exactly as before."""
    legacy = pcost.pow2_at_least(max(64, 2 * (-(-cap // n_shards))))
    share = -(-int(est_rows) // n_shards)
    if share < LARGE_SHARE_ROWS:
        return legacy
    if cap >= pcost.CAP_MARGIN * int(est_rows):
        share *= pcost.CAP_MARGIN
    return min(legacy, pcost.pow2_at_least(share + share // 8))


def plan_conjunction(
    db, plans, *, n_shards: int = 1, est=None, rule=None,
) -> Optional[PlannedProgram]:
    """Turn a conjunction into a costed whole-plan program, or None when
    the planner declines (no estimator surface, disconnected positives)
    — the caller falls back to the legacy heuristics, answer-identical.

    `n_shards > 1` scales the capacity seeds to PER-SHARD buffers (the
    sharded executor's join_caps unit: `shard_cap_seed`, the 2x skew
    headroom its probe capacities use for short tables, the estimate's
    share for long ones).  `est`: the estimator to read (default:
    the backend's live one; the job builder passes its batch's
    `BatchEstimator`), `rule`: `conjunction_rule(plans)` where the
    caller kept it.

    Pure planning — no counters here: explain() calls this too, and the
    planned/method telemetry must decompose EXECUTOR traffic only (the
    hooks count via planner.record_planned)."""
    if est is None:
        est = estimator_for(db)
        if est is None:
            return None
    if rule is None:
        rule = conjunction_rule(plans)
        if rule is None:
            return None
    pos_idx, neg_idx, method = rule
    positives = [plans[i] for i in pos_idx]
    if method is not None:
        order_pos: Tuple[int, ...] = tuple(range(len(positives)))
    elif len(positives) <= dp_max():
        order_pos = _dp_order(est, positives)
        method = "dp"
    else:
        order_pos = _greedy_order(est, positives)
        method = "greedy_tail"

    join_rows, caps, total = _chain_estimates(est, positives, order_pos)
    # the deeper intermediates of a star prefix seed from the exact
    # k-way statistic instead of the independence model
    join_rows, caps = _star_chain_seeds(
        est, positives, order_pos, join_rows, caps, _max_capacity(db)
    )

    if n_shards > 1:
        caps = tuple(
            shard_cap_seed(c, rows, n_shards)
            for c, rows in zip(caps, join_rows)
        )
    order = tuple(pos_idx[i] for i in order_pos) + tuple(neg_idx)
    term_rows = tuple(
        est.rows(plans[i]) for i in order
    )
    return PlannedProgram(
        order=order,
        est_term_rows=term_rows,
        est_join_rows=join_rows,
        join_cap_seeds=caps,
        route="sharded" if n_shards > 1 else "fused",
        method=method,
        cost=float(total),
    )


# ---------------------------------------------------------------------------
# whole-tree planning (ISSUE 10): one costed program for an Or/Not tree
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PlannedTree:
    """One costed whole-TREE decision (fused Or/negation execution,
    query/tree.py tree_fusion_sites): per-site conjunction plans plus
    the union/anti placement the fused program hard-codes.

    site_plans     — one Optional[PlannedProgram] per positive Or branch
                     (None = the per-site planner declined; the executor
                     falls back to its legacy ordering for that site —
                     the tree still fuses)
    neg_plan       — plan of the joint negative conjunction, when the Or
                     carries syntactic Not children (de-Morgan branch)
    est_site_rows  — estimated final rows per positive site, in site
                     order (the union's concat inputs)
    est_union_rows — estimated union size (sum of sites — the dedup can
                     only shrink it, so this bounds the union buffer)
    union_after    — index into the site list after which the in-program
                     union (concat + dedup) runs; always len(site_plans)
                     (every positive site feeds it) — recorded so
                     explain() renders the placement explicitly
    anti_after_union — the anti-join (negation difference) runs AFTER
                     the union dedup, against the joint-negative table
    route          — "fused_tree" / "sharded_tree_fused" (ROUTE_KEYS,
                     daslint DL008)
    cost           — summed site costs + the union's modeled bytes
    """

    site_plans: Tuple[Optional[PlannedProgram], ...]
    neg_plan: Optional[PlannedProgram]
    est_site_rows: Tuple[int, ...]
    est_union_rows: int
    union_after: int
    anti_after_union: bool
    route: str
    cost: float


def _site_out_rows(db, plans, planned) -> int:
    """Estimated FINAL rows of one conjunction site: the last join's
    estimate when planned, else the largest positive term's exact count
    (the fallback executor's capacity logic never sees an estimate)."""
    if planned is not None and planned.est_join_rows:
        return int(planned.est_join_rows[-1])
    if planned is not None:
        return int(planned.est_term_rows[0])
    est = estimator_for(db)
    pos = [p for p in plans if not p.negated]
    if est is None or not pos:
        return 0
    return max(est.rows(p) for p in pos)


def plan_tree(db, pos_sites, neg_plans=None, *, n_shards: int = 1):
    """Cost and order a whole Or/negation plan tree (ISSUE 10): one
    PlannedProgram per conjunction site (plan_conjunction — Selinger
    order + capacity seeds, counts nothing), the union buffer estimate,
    and the union/anti placement.  Returns None when there is nothing
    to plan (no sites) — the caller keeps the tree executor.

    Pure planning, like plan_conjunction: explain() calls this too, so
    no counters fire here (the executors' tree jobs count per site via
    the ordinary record_planned hook)."""
    if not pos_sites and not neg_plans:
        return None
    site_plans = tuple(
        plan_conjunction(db, list(site), n_shards=n_shards)
        for site in pos_sites
    )
    neg_plan = (
        plan_conjunction(db, list(neg_plans), n_shards=n_shards)
        if neg_plans else None
    )
    site_rows = tuple(
        _site_out_rows(db, site, planned)
        for site, planned in zip(pos_sites, site_plans)
    )
    union_rows = int(sum(site_rows))
    out_width = max(
        (len({v for p in site if not p.negated for v in p.var_names})
         for site in pos_sites),
        default=1,
    )
    cost = sum(p.cost for p in site_plans if p is not None)
    if neg_plan is not None:
        cost += neg_plan.cost
    # the union's modeled bytes: one concat + dedup pass over the
    # summed site windows (sort-dominated, priced as materialization)
    cost += float(union_rows) * max(out_width, 1) * pcost.ROW_BYTES
    route = "sharded_tree_fused" if n_shards > 1 else "fused_tree"
    return PlannedTree(
        site_plans=site_plans,
        neg_plan=neg_plan,
        est_site_rows=site_rows,
        est_union_rows=union_rows,
        union_after=len(site_plans),
        anti_after_union=neg_plans is not None and bool(neg_plans),
        route=route,
        cost=float(cost),
    )
