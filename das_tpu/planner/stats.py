"""Cardinality estimation from the wildcard-index degree statistics.

The storage layer already holds everything a textbook System-R style
estimator needs, in host memory, sorted:

  * exact per-term candidate counts — the same binary searches the
    device probes run (`query/fused.py estimate_plan_rows` over
    `host_segments`, base bucket + incremental-delta overlays);
  * exact distinct-value counts per (arity, type, position) — the
    number of run-length boundaries in the contiguous
    ``(type_id << 32 | target)`` slice of the sorted `key_type_pos`
    index (the same extraction `query/starcount.py _table_sparse`
    uses for its closed-form degree products, reduced to a count).

From those two, joins estimate with the standard independence model:

    |L ⋈ R|  ≈  |L| · |R| · Π_{v ∈ shared}  1 / max(dv_L(v), dv_R(v))

with per-variable distinct counts folded through the chain
(``dv_out(v) = min(dv_L, dv_R)`` on shared variables, clamped by the
estimated row count).  On uniform data this is exact for the star/FK
shapes the serving workload is made of; on skew it errs low — which the
planner's capacity margin (cost.py CAP_MARGIN) plus the existing
overflow-retry ladder absorb, and which the est-vs-actual planner
counters (`ops/counters.py PLANNER_KEYS`) make observable.

Invalidation rides the SAME commit counter as the result caches
(`storage/delta.py delta_version`): `estimator_for` rebuilds the
estimator whenever the backend's version moved, so estimates can never
describe pre-commit tables — exactly the ResultCache contract, for
exactly the same reason.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from das_tpu import obs
from das_tpu.query.fused import estimate_plan_rows


def _probe_degrees(ia, ib, cb):
    """Align two sorted degree supports: for every atom row in `ia`,
    its multiplicity in (ib, cb) — 0 where absent.  The asymmetric probe
    idiom both the pairwise dot and the k-way intersection fold use:
    the (smaller) probe side binary-searches the (larger) key side, so
    grounded-vs-FlyBase-scale supports stay O(small · log big)."""
    if ia.size == 0 or ib.size == 0:
        return np.zeros(ia.shape, np.int64)
    pos = np.searchsorted(ib, ia)
    pos_safe = np.minimum(pos, ib.size - 1)
    match = ib[pos_safe] == ia
    return np.where(match, cb[pos_safe], 0).astype(np.int64)


class RelEstimate:
    """Estimated shape of one relation mid-plan: row count plus the
    per-variable distinct-value counts the join model folds.  `plan` is
    set while the relation is still a BASE TERM — leaf-leaf joins then
    take the exact degree-product path instead of the independence
    model."""

    __slots__ = ("rows", "dv", "plan")

    def __init__(self, rows: float, dv: Dict[str, float], plan=None):
        self.rows = rows
        self.dv = dv
        self.plan = plan


class CardinalityEstimator:
    """Per-backend cardinality estimates, valid for ONE delta version.

    All statistics are memoized: the per-term counts and distinct-value
    extractions are host searchsorted/diff passes over index arrays the
    store already keeps resident, so a planner call on a warm estimator
    is dictionary lookups plus float arithmetic."""

    def __init__(self, db):
        self.db = db
        self.version = getattr(db, "delta_version", None)
        self._rows: Dict[Tuple, int] = {}
        self._distinct: Dict[Tuple[int, int, int], int] = {}

    # -- raw statistics ----------------------------------------------------

    @staticmethod
    def _plan_key(plan) -> Tuple:
        return (
            plan.arity, plan.type_id, plan.ctype, plan.fixed, plan.negated,
        )

    def rows(self, plan) -> int:
        """EXACT candidate count of one term (host searchsorted, zero
        device work) — shared with the executors' capacity sizing."""
        key = self._plan_key(plan)
        hit = self._rows.get(key)
        if hit is None:
            hit = self._rows[key] = int(estimate_plan_rows(self.db, plan))
        return hit

    def distinct_at(self, arity: int, type_id: int, pos: int) -> int:
        """Distinct REAL targets at `pos` among links of `type_id`: the
        run-length boundary count of the contiguous slice of the sorted
        (type<<32|target) key — dangling (-1) targets OR to negative
        keys and fall outside the slice, mirroring starcount's
        `_table_sparse` extraction.  Summed over overlay segments (a
        value present in two segments counts twice — an overcount of at
        most the small delta overlay, fine for an estimate)."""
        from das_tpu.storage.atom_table import host_segments

        key = (arity, type_id, pos)
        hit = self._distinct.get(key)
        if hit is not None:
            return hit
        base = np.int64(type_id) << 32
        total = rows = 0
        # an uncached whole-table pass: what a commit makes the planner
        # pay again (the estimator is rebuilt per delta_version)
        with obs.span("planner.stats", what="distinct_at") as sp:
            for b in host_segments(self.db, arity):
                keys = b.key_type_pos[pos]
                lo = int(np.searchsorted(keys, base, side="left"))
                hi = int(np.searchsorted(
                    keys, base + (np.int64(1) << 31), side="left"
                ))
                if hi > lo:
                    rows += hi - lo
                    total += 1 + int(
                        np.count_nonzero(np.diff(keys[lo:hi]))
                    )
            sp.set(version=self.version, rows=rows)
        self._distinct[key] = total
        return total

    # -- relation-level estimates ------------------------------------------

    def term_estimate(self, plan) -> RelEstimate:
        """Estimate for one materialized term table."""
        rows = self.rows(plan)
        dv: Dict[str, float] = {}
        for name, col in zip(plan.var_names, plan.var_cols):
            if plan.ctype is not None or plan.type_id is None:
                # template probes carry no per-position degree index
                # entry worth scanning — all-distinct is the safe bound
                d = rows
            else:
                d = self.distinct_at(plan.arity, plan.type_id, col)
                if plan.fixed:
                    # a grounded term's column can't exceed its own rows
                    d = min(d, rows)
            dv[name] = float(max(min(d, rows), 1 if rows else 0))
        return RelEstimate(float(rows), dv, plan=plan)

    def _support(self, plan, var: str):
        """Sparse degree support ((sorted atom rows, multiplicities),
        total) of a base term over `var` — straight from the star-count
        degree fast path (query/starcount.py), whose host caches are
        segment-identity-validated so commits invalidate naturally.
        None when the shape has no support extraction (templates,
        repeated variables)."""
        if plan.ctype is not None or plan.type_id is None or plan.eq_pairs:
            return None
        from das_tpu.query import starcount

        pos = plan.var_cols[plan.var_names.index(var)]
        spec = (plan.arity, plan.type_id, pos, tuple(plan.fixed))
        if plan.fixed:
            return starcount._host_sparse_deg(self.db, spec)
        return starcount._table_sparse(self.db, spec)

    def exact_join_rows(self, pa, pb, var: str) -> Optional[int]:
        """EXACT output rows of a leaf ⋈ leaf join on ONE shared
        variable: the sparse degree dot product Σ_v deg_a(v)·deg_b(v) —
        the miner's closed-form degree-product count (mining/miner.py,
        query/starcount.py), which is exact because every non-shared
        position is a distinct free variable and links are
        content-addressed (no two rows of a term bind identical
        tuples).  This is what catches the skew-heavy self-join blow-up
        (Σ deg² ≫ |L|·|R|/dv) that the independence model misses.

        The dot is asymmetric on purpose: the smaller support binary-
        searches the larger (both are sorted by construction), so a
        serving-shaped grounded term (a handful of rows) against a
        FlyBase-scale whole-type support costs O(small · log big), not
        a sort of the big side per query."""
        # the memo key must carry each side's PROBED POSITION, not just
        # the term shape: two same-shaped leaves sharing `var` at
        # different positions have different supports (Member(B, P) vs
        # Member(G, B)) and must not serve each other's dot product
        pos_a = pa.var_cols[pa.var_names.index(var)]
        pos_b = pb.var_cols[pb.var_names.index(var)]
        key = ("dot", self._plan_key(pa), pos_a, self._plan_key(pb), pos_b)
        hit = self._rows.get(key)
        if hit is not None:
            return hit if hit >= 0 else None
        ea = self._support(pa, var)
        eb = self._support(pb, var)
        if ea is None or eb is None:
            self._rows[key] = -1
            return None
        (ia, ca), _ta = ea
        (ib, cb), _tb = eb
        if ia.size > ib.size:
            (ia, ca), (ib, cb) = (ib, cb), (ia, ca)
        out = int((ca * _probe_degrees(ia, ib, cb)).sum())
        self._rows[key] = out
        return out

    def star_rows(self, plans, var: str) -> Tuple[float, bool]:
        """(rows, exact) of the k-way STAR join of base terms on ONE
        shared variable, the size of a chain's star-prefix intermediate
        (search.py _star_chain_seeds): Σ_v Π_j deg_j(v) over the INTERSECTION
        of the per-clause supports.  Exact whenever every clause has a
        support extraction — the k-way generalization of
        `exact_join_rows`, realizing the min-degree intersection bound
        (the surviving v set can never exceed the SMALLEST clause's
        distinct count, which is why the intersection deletes exactly
        the intermediates the chain's independence model over-admits);
        margin-free seeds follow.  Estimated by folding the pairwise
        model otherwise.

        Same asymmetric-searchsorted discipline as the pairwise dot:
        the smallest support probes the others, so a serving-shaped
        grounded clause against FlyBase-scale whole-type supports costs
        O(small · k · log big)."""
        key = ("mdot",) + tuple(
            (self._plan_key(p), p.var_cols[p.var_names.index(var)])
            for p in plans
        )
        hit = self._rows.get(key)
        if hit is not None and hit >= 0:
            return float(hit), True
        if hit is None:
            sups = [self._support(p, var) for p in plans]
            if all(s is not None for s in sups):
                arrs = sorted(
                    ((ia, ca) for (ia, ca), _t in sups),
                    key=lambda t: t[0].size,
                )
                base_i, prod = arrs[0][0], arrs[0][1].astype(np.int64)
                for ia, ca in arrs[1:]:
                    prod = prod * _probe_degrees(base_i, ia, ca)
                out = int(prod.sum()) if prod.size else 0
                self._rows[key] = out
                return float(out), True
            self._rows[key] = -1
        # no support for some clause (template/repeated-var shapes):
        # fold the pairwise model — the chain's estimate, same error bar
        rels = [self.term_estimate(p) for p in plans]
        acc = rels[0]
        for r in rels[1:]:
            acc = self.join_estimate(acc, r)
        return acc.rows, False

    def pair_join_rows(
        self, left: RelEstimate, right: RelEstimate, var: str
    ) -> Tuple[float, bool]:
        """(rows, exact) of the join restricted to ONE shared variable
        — the CAPACITY model of an INDEX JOIN (query/fused.py
        plan_index_joins): the join probes the posting index at the
        first shared variable's position and materializes every
        candidate BEFORE the remaining shared columns verify, so the
        buffer (and the overflow stats the retry ladder reads) scale
        with the single-variable candidate count, not the final match
        count.  Exact (degree dot product) while both sides are base
        terms; independence otherwise."""
        if left.plan is not None and right.plan is not None:
            exact = self.exact_join_rows(left.plan, right.plan, var)
            if exact is not None:
                return float(exact), True
        return left.rows * right.rows / max(
            left.dv.get(var, 1.0), right.dv.get(var, 1.0), 1.0
        ), False

    def join_estimate(
        self, left: RelEstimate, right: RelEstimate
    ) -> RelEstimate:
        """Fold one equi-join into the running relation estimate.  A
        leaf ⋈ leaf step on exactly one shared variable is EXACT (degree
        products); everything else uses the independence model."""
        shared = [v for v in left.dv if v in right.dv]
        rows = None
        if len(shared) == 1 and left.plan is not None and right.plan is not None:
            exact = self.exact_join_rows(left.plan, right.plan, shared[0])
            if exact is not None:
                rows = float(exact)
        if rows is None:
            rows = left.rows * right.rows
            for v in shared:
                rows /= max(left.dv[v], right.dv[v], 1.0)
        dv: Dict[str, float] = {}
        for v, d in left.dv.items():
            dv[v] = min(d, right.dv[v]) if v in right.dv else d
        for v, d in right.dv.items():
            dv.setdefault(v, d)
        rows = max(rows, 0.0)
        for v in dv:
            dv[v] = max(min(dv[v], rows), 1.0 if rows else 0.0)
        return RelEstimate(rows, dv)


def estimator_for(db) -> Optional[CardinalityEstimator]:
    """The backend's live estimator, rebuilt whenever `delta_version`
    moved — statistics invalidate exactly like result caches.  None for
    backends without host index segments (the pure host algebra needs
    no planning)."""
    if (
        getattr(db, "fin", None) is None
        and getattr(db, "host_bucket_segments", None) is None
    ):
        return None
    est = getattr(db, "_planner_estimator", None)
    version = getattr(db, "delta_version", None)
    if est is None or est.version != version or est.db is not db:
        # the rebuild itself is cheap (empty memo dicts); the span marks
        # WHEN the statistics went cold — the uncached extractions that
        # follow record their own planner.stats spans
        with obs.span("planner.stats", what="rebuild", version=version,
                      rows=0):
            est = CardinalityEstimator(db)
            db._planner_estimator = est
    return est
