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
        if not self._has_support(plan):
            return None
        from das_tpu.query import starcount

        pos = plan.var_cols[plan.var_names.index(var)]
        spec = (plan.arity, plan.type_id, pos, tuple(plan.fixed))
        if plan.fixed:
            return starcount._host_sparse_deg(self.db, spec)
        return starcount._table_sparse(self.db, spec)

    @staticmethod
    def _has_support(plan) -> bool:
        return (
            plan.ctype is None and plan.type_id is not None
            and not plan.eq_pairs
        )

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
        if hit is None:
            out = self._dot(pa, pb, var)
            hit = self._rows[key] = -1 if out is None else out
        return hit if hit >= 0 else None

    def _dot(self, pa, pb, var: str) -> Optional[int]:
        """`exact_join_rows` without its memo: the sparse dot of the two
        supports, None where a side has none."""
        ea = self._support(pa, var)
        eb = self._support(pb, var)
        if ea is None or eb is None:
            return None
        (ia, ca), _ta = ea
        (ib, cb), _tb = eb
        if ia.size > ib.size:
            (ia, ca), (ib, cb) = (ib, cb), (ia, ca)
        return int((ca * _probe_degrees(ia, ib, cb)).sum())

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
        if hit is None:
            out = self._star_exact(plans, var)
            hit = self._rows[key] = -1 if out is None else out
        if hit >= 0:
            return float(hit), True
        return self._star_model(plans), False

    def _star_exact(self, plans, var: str) -> Optional[int]:
        """`star_rows`' exact statistic without its memo; None where a
        clause has no support extraction."""
        sups = [self._support(p, var) for p in plans]
        if any(s is None for s in sups):
            return None
        arrs = sorted(
            ((ia, ca) for (ia, ca), _t in sups), key=lambda t: t[0].size
        )
        base_i, prod = arrs[0][0], arrs[0][1].astype(np.int64)
        for ia, ca in arrs[1:]:
            prod = prod * _probe_degrees(base_i, ia, ca)
        return int(prod.sum()) if prod.size else 0

    def _star_model(self, plans) -> float:
        """No support for some clause (template / repeated-variable
        shapes): fold the pairwise model, the chain's estimate with the
        same error bar."""
        rels = [self.term_estimate(p) for p in plans]
        acc = rels[0]
        for r in rels[1:]:
            acc = self.join_estimate(acc, r)
        return acc.rows

    def pair_join_rows(
        self, left: RelEstimate, right: RelEstimate, shared
    ) -> Tuple[float, bool]:
        """(rows, exact) of a join INTO a whole-type term (query/fused.py
        plan_index_joins; ops/join.py whole_type_join) on the variables
        `shared`: what the join counts, writes and reports to the retry
        ladder, so the CAPACITY model and what the step is priced on.
        One shared variable: every candidate of the posting index is a
        match: the degree dot product, exact while both sides are base
        terms, independence otherwise.  Two or more: the join verifies
        a pair on every shared column BEFORE it counts it, so its rows
        are those of the whole equi-join: the independence estimate
        over ALL shared variables, never above the candidates of the
        first, and never exact (no composite-key statistic is kept)."""
        var = shared[0]
        rows, exact = None, False
        if left.plan is not None and right.plan is not None:
            dot = self.exact_join_rows(left.plan, right.plan, var)
            if dot is not None:
                rows, exact = float(dot), True
        if rows is None:
            rows = left.rows * right.rows / max(
                left.dv.get(var, 1.0), right.dv.get(var, 1.0), 1.0
            )
        if len(shared) > 1:
            return min(self.join_estimate(left, right).rows, rows), False
        return rows, exact

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


class BatchEstimator(CardinalityEstimator):
    """The estimator over N conjunctions of ONE shape (the same terms,
    other grounded values: query/fused.py `shape_key`), for the
    executor's job builder: the statistics that differ between two
    queries of a shape are read for all N at once, the first time a
    fold asks for one, and every later fold of the batch finds its
    number in a list.

      * a grounded term's exact rows: ONE searchsorted per (term, side,
        host segment) over the vector of probe keys;
      * a grounded leaf x table leaves on one variable (the pairwise
        dot, the k-way star): sum_v deg_g(v) * prod_j deg_j(v) is the
        sum of prod_j deg_j over the ROWS of the grounded term, so the
        N ranges are gathered once, probed into the tables' kept
        supports (`_probe_degrees`) and summed per query: no unique,
        no `_host_sparse_deg` entry per grounded value;
      * what no grounded value enters (a table's rows, its distinct
        counts, table x table) is the live estimator's, memo and all;
      * two or more grounded leaves in one statistic: per query,
        through `_dot` / `_star_exact`, unmemoized.

    The formulas (`term_estimate`, `join_estimate`, `pair_join_rows`,
    the planner's chain fold) are the base class's, run per query on
    plain numbers after `at(i)`: what is batched is the statistics,
    never a second copy of a rule.  Nothing per grounded value is
    written to the live estimator's memo (ROADMAP D17).  Valid for the
    one batch it was built for."""

    def __init__(self, base: CardinalityEstimator, plans_lists):
        self.db = base.db
        self.version = base.version
        self._base = base
        self._distinct = base._distinct
        self._lists = plans_lists
        self._n = len(plans_lists)
        self._cols: Dict[Tuple, object] = {}
        self._i = 0
        self._term: Dict[int, int] = {}

    def at(self, i: int) -> "BatchEstimator":
        """Answer for query `i` of the batch from here on."""
        self._i = i
        self._term = {id(p): t for t, p in enumerate(self._lists[i])}
        return self

    def term_of(self, plan) -> int:
        """Index of `plan` in the current query's plan list."""
        return self._term[id(plan)]

    def _col(self, key, make, *args):
        """The batch's column under `key`, made on first demand (None is
        a column: "no such statistic").  `rows`, `term_estimate` and
        `exact_join_rows`, asked several times per query, look theirs
        up in place."""
        col = self._cols.get(key, self)
        if col is self:
            col = self._cols[key] = make(*args)
        return col

    # -- the grounded values, as vectors -------------------------------------

    def fixed_col(self, t: int, k: int) -> np.ndarray:
        """int64[N]: the k-th grounded value of term `t`, per query."""
        return self._col(("fixed", t, k), self._fixed_col, t, k)

    def _fixed_col(self, t, k):
        return np.fromiter(
            (pl[t].fixed[k][1] for pl in self._lists), np.int64, self._n
        )

    def key_col(self, t: int) -> np.ndarray:
        """int64[N]: term `t`'s probe key (type_id << 32 | v0), the key
        the device program searches and estimate_plan_rows counts."""
        return self._col(("key", t), self._key_col, t)

    def _key_col(self, t):
        return (np.int64(self._lists[0][t].type_id) << 32) | self.fixed_col(t, 0)

    # -- raw statistics ------------------------------------------------------

    def rows(self, plan) -> int:
        t = self._term[id(plan)]
        col = self._cols.get(t)
        if col is None:
            col = self._cols[t] = self._rows_col(t)
        return col[self._i]

    def term_estimate(self, plan) -> RelEstimate:
        """The base formula; of a term no grounded value enters, ONE
        estimate for the batch (a fold reads a RelEstimate, it never
        writes one)."""
        if plan.fixed:
            return super().term_estimate(plan)
        key = ("term", self._term[id(plan)])
        rel = self._cols.get(key)
        if rel is None:
            rel = self._cols[key] = super().term_estimate(plan)
        return RelEstimate(rel.rows, rel.dv, plan=plan)

    def _ranges(self, t):
        """Per host segment `(segment, lo, count)`: grounded term `t`'s
        key range for all N queries, ONE searchsorted per side."""
        from das_tpu.storage.atom_table import host_segments

        plan = self._lists[0][t]
        p0 = plan.fixed[0][0]
        keys = self.key_col(t)
        out = []
        for b in host_segments(self.db, plan.arity):
            sk = b.key_type_pos[p0]
            lo = np.searchsorted(sk, keys, side="left")
            out.append((b, lo, np.searchsorted(sk, keys, side="right") - lo))
        return out

    def _rows_col(self, t):
        plan = self._lists[0][t]
        if not plan.fixed:
            return [self._base.rows(plan)] * self._n
        ranges = self._col(("ranges", t), self._ranges, t)
        if len(ranges) == 1:
            return ranges[0][2].tolist()
        return sum(cnt for _b, _lo, cnt in ranges).tolist()

    def exact_join_rows(self, pa, pb, var: str) -> Optional[int]:
        key = (self._term[id(pa)], self._term[id(pb)], var)
        col = self._cols.get(key, self)
        if col is self:
            col = self._cols[key] = self._star_col(key[:2], var)
        return None if col is None else col[self._i]

    def star_rows(self, plans, var: str) -> Tuple[float, bool]:
        ts = tuple(self._term[id(p)] for p in plans)
        col = self._col(("mdot", ts, var), self._star_col, ts, var)
        if col is not None:
            return float(col[self._i]), True
        return self._star_model(plans), False

    def _star_col(self, ts, var):
        """Per query, sum_v prod_j deg_j(v) over the terms `ts` (two:
        the pairwise dot); None where a term has no support."""
        first = [self._lists[0][t] for t in ts]
        sups = {}
        for t, p in zip(ts, first):
            if not self._has_support(p):
                return None
            if not p.fixed:
                sups[t] = self._support(p, var)
                if sups[t] is None:
                    return None
        grounded = [t for t in ts if t not in sups]
        if len(grounded) == 1:
            return self._grounded_star(
                grounded[0], var, [sups[t][0] for t in ts if t in sups]
            )
        if grounded:
            out = [
                self._base._star_exact([pl[t] for t in ts], var)
                for pl in self._lists
            ]
            return None if any(o is None for o in out) else out
        # tables alone: the live estimator's memo keeps it
        if len(ts) == 2:
            one = self._base.exact_join_rows(first[0], first[1], var)
        else:
            rows, exact = self._base.star_rows(first, var)
            one = int(rows) if exact else None
        return None if one is None else [one] * self._n

    def _grounded_star(self, t, var, tables):
        """sum over the rows of grounded term `t` of prod deg_table at
        the row's value of `var`, for all N queries: the rows of every
        query's key range gathered in one pass per host segment."""
        plan = self._lists[0][t]
        ranges = self._col(("ranges", t), self._ranges, t)
        if not ranges:
            return None
        p0 = plan.fixed[0][0]
        pos = plan.var_cols[plan.var_names.index(var)]
        n = self._n
        out = np.zeros(n, np.int64)
        for b, lo, cnt in ranges:
            total = int(cnt.sum())
            if total == 0:
                continue
            qid = np.repeat(np.arange(n), cnt)
            at = np.arange(total) + np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
            local = b.order_by_type_pos[p0][at]
            vals = b.targets[local, pos]
            ok = vals >= 0  # device parity: dangling rows never scatter
            for k, (q, _v) in enumerate(plan.fixed[1:], 1):
                ok &= b.targets[local, q] == self.fixed_col(t, k)[qid]
            if not ok.all():
                vals, qid = vals[ok], qid[ok]
            vals = vals.astype(np.int64)
            w = None
            for ib, cb in tables:
                deg = _probe_degrees(vals, ib, cb)
                w = deg if w is None else w * deg
            # the sums are whole numbers far below 2^53 (a join past
            # max_result_capacity is declined), where float64 adds them
            # exactly
            out += np.bincount(qid, weights=w, minlength=n).astype(np.int64)
        return out.tolist()


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
