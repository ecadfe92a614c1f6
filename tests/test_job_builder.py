"""A batch's jobs are built per shape, not per query (ISSUE 41).

`FusedExecutor._build` tells the queries of a batch apart by SHAPE
(`shape_key`), keeps per shape a `_JobTemplate`, and fills the jobs of
a shape's queries from the batch's statistics (planner/stats.py
BatchEstimator).  Pinned here: every job equals, FIELD FOR FIELD, the
one the parent's per-query `_exec_job` builds (kept below as the
oracle: PR 37's body, word for word, on the scalar planner and the
live estimator), the `PLANNER_COUNTS` move alike, the jobs of a batch
that end with equal capacities hold ONE `FusedPlanSig` object, the
served answers are `execute`'s; after a commit (`delta_version` moved,
a second host segment) the templates are rebuilt and the jobs are
equal again; the live estimator's memo does not grow with the number
of distinct grounded values served (ROADMAP D17).

Stores: the bio test store, the benchmark's `generator.Store` at scale
0.002, and a small one with a ternary link (a probe with a verified
second fixed position)."""

import os
import random
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from das_tpu import planner as _planner  # noqa: E402
from das_tpu.api.atomspace import DistributedAtomSpace  # noqa: E402
from das_tpu.core.config import DasConfig  # noqa: E402
from das_tpu.models.bio import build_bio_atomspace  # noqa: E402
from das_tpu.planner.stats import estimator_for  # noqa: E402
from das_tpu.query import compiler, fused  # noqa: E402
from das_tpu.query.ast import And, Link, Node, Not, Variable  # noqa: E402
from das_tpu.query.fused import _ExecJob, _pow2_at_least  # noqa: E402
from das_tpu.storage.atom_table import host_segments, load_metta_text  # noqa: E402
from das_tpu.storage.tensor_db import TensorDB  # noqa: E402


@pytest.fixture(autouse=True)
def _cold_cap_store(monkeypatch):
    # capacities an earlier process learned must not merge into the
    # seeds under test; no exported knob may pick another plan
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    for name in ("DAS_TPU_PLANNER", "DAS_TPU_PLANNER_DP_MAX"):
        monkeypatch.delenv(name, raising=False)


# -- the oracle: the parent's per-query builder ----------------------------


def oracle_exec_job(self, plans, count_only):
    """PR 37's `FusedExecutor._exec_job`, body unchanged: one query, the
    scalar planner on the live estimator, `_term_args` per term."""
    planned = (
        _planner.plan_conjunction(self.db, plans)
        if _planner.enabled(self.db.config) else None
    )
    if planned is not None:
        ordered = [plans[i] for i in planned.order]
    else:
        ordered = self._order(plans)
    same_order = self._same_positive_order(ordered, plans)
    plans = ordered
    mapped = []
    for plan in plans:
        m = self._term_args(plan)
        if m is None:
            return None
        mapped.append(m)
    sigs = tuple(m[0] for m in mapped)
    arrays = tuple(m[1] for m in mapped)
    keys = tuple(m[2] for m in mapped)
    fvals = tuple(m[3] for m in mapped)

    cfg = self.db.config
    term_caps = tuple(_pow2_at_least(self._estimate(plan)) for plan in plans)
    index_joins, index_right, arrays, term_caps = self._apply_index_joins(
        sigs, arrays, term_caps
    )
    n_joins = max(0, sum(1 for s in sigs if not s.negated) - 1)
    if planned is not None and len(planned.join_cap_seeds) == n_joins:
        join_caps = planned.join_cap_seeds
    else:
        join_caps = tuple(
            [self._join_cap_seed(plans, term_caps)] * n_joins
        )
    learned = self._learned_caps(
        self._caps, self._cap_store, sigs,
        (len(term_caps), len(join_caps)),
    )
    if learned is not None:
        term_caps = self._clamp_index_terms(
            tuple(max(a, b) for a, b in zip(term_caps, learned[0])),
            index_right,
        )
        join_caps = tuple(max(a, b) for a, b in zip(join_caps, learned[1]))
    if max(term_caps + join_caps, default=0) > cfg.max_result_capacity:
        return None
    if planned is not None:
        _planner.record_planned(planned)
    else:
        _planner.PLANNER_COUNTS["greedy"] += 1
    return _ExecJob(
        self, count_only, same_order, sigs, arrays, keys, fvals,
        term_caps, join_caps, index_joins, planned=planned,
    )


def assert_same_job(got, want, where):
    assert (got is None) == (want is None), where
    if want is None:
        return
    for field in ("count_only", "same_order", "sigs", "term_caps",
                  "join_caps", "index_joins", "planned"):
        assert getattr(got, field) == getattr(want, field), (where, field)
    assert got.plan_sig() == want.plan_sig(), where
    assert all(type(c) is int for c in got.term_caps + got.join_caps), where
    if want.planned is not None:
        for field in ("est_term_rows", "est_join_rows", "join_cap_seeds"):
            assert all(
                type(v) is int for v in getattr(got.planned, field)
            ), (where, field)
    # the bucket arrays are the store's own objects, term by term
    assert len(got.arrays) == len(want.arrays), where
    for a, b in zip(got.arrays, want.arrays):
        assert len(a) == len(b) == 4 and all(
            x is y for x, y in zip(a, b)), where
    for a, b in zip(got.keys, want.keys):
        assert type(a) is type(b) and a == b, (where, "keys")
    for a, b in zip(got.fvals, want.fvals):
        assert a.dtype == b.dtype and a.shape == b.shape, (where, "fvals")
        assert (a == b).all(), (where, "fvals")


def check_batch(ex, plans_lists, count_only=False, where=""):
    """`_build` against the oracle on one batch; returns the jobs."""
    _planner.reset_planner_counts()
    want = [oracle_exec_job(ex, list(p), count_only) for p in plans_lists]
    want_counts = dict(_planner.PLANNER_COUNTS)
    _planner.reset_planner_counts()
    got, shapes, _built = ex._build([list(p) for p in plans_lists], count_only)
    assert dict(_planner.PLANNER_COUNTS) == want_counts, where
    assert shapes == len({fused.shape_key(p) for p in plans_lists})
    for i, (g, w) in enumerate(zip(got, want)):
        assert_same_job(g, w, f"{where}[{i}]")
    # ONE signature object per group of equal signatures
    by_sig = {}
    for job in got:
        if job is not None:
            first = by_sig.setdefault(job.plan_sig(), job.plan_sig())
            assert job.plan_sig() is first, where
            assert job.term_caps is first.term_caps
            assert job.join_caps is first.join_caps
    return got


# -- stores ---------------------------------------------------------------

CELL_SCALE, CELL_SEED = 0.002, 11


def _tern_text() -> str:
    """10 genes, 4 processes, and a ternary link `Annot gene process
    gene`: a probe with a second, verified fixed position."""
    lines = ["(: Gene Type)", "(: Process Type)", "(: Member Type)",
             "(: Annot Type)"]
    lines += [f'(: "g{i}" Gene)' for i in range(10)]
    lines += [f'(: "p{j}" Process)' for j in range(4)]
    for i in range(10):
        lines.append(f'(Member "g{i}" "p{i % 4}")')
        lines.append(f'(Member "g{i}" "p{(i + 1) % 4}")')
        for k in (1, 2, 3):
            lines.append(
                f'(Annot "g{i}" "p{(i * k) % 4}" "g{(i + k) % 10}")')
    return "\n".join(lines)


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    built = {}

    def bio(**fields):
        data, _g, _p = build_bio_atomspace(
            n_genes=60, n_processes=15, members_per_gene=4,
            n_interactions=80, seed=7)
        return TensorDB(data, DasConfig(**fields))

    def cell():
        from benchmark.reference import generator

        path = os.path.join(
            str(tmp_path_factory.mktemp("job_builder")), "kb.metta")
        generator.write_canonical(
            generator.Store(CELL_SCALE, CELL_SEED), path)
        das = DistributedAtomSpace(
            database_name="jb_cell", backend="tensor", config=DasConfig())
        das.load_canonical_knowledge_base(path)
        return das.db

    makers = {
        "bio": bio,
        "bio_greedy": lambda: bio(use_planner="off"),
        "cell": cell,
        "tern": lambda: TensorDB(load_metta_text(_tern_text()), DasConfig()),
    }

    def get(name):
        if name not in built:
            os.environ["DAS_TPU_XLA_CACHE"] = "0"
            built[name] = makers[name]()
        return built[name]

    return get


def _genes(db):
    return [Node("Gene", g) for g in sorted(db.get_all_nodes("Gene", names=True))]


# -- shapes ---------------------------------------------------------------


def _v(name):
    return Variable(name)


def _member(a, b):
    return Link("Member", [a, b], True)


def _interacts(a, b):
    return Link("Interacts", [a, b], True)


def grounded3(g, h):
    return And([_member(g, _v("V3")), _member(_v("V2"), _v("V3")),
                _interacts(g, _v("V2"))])


def shared2(g, h):
    return And([_member(g, _v("V3")), _member(_v("V2"), _v("V3"))])


def star3(g, h):
    """A star on V3 whose first clause is grounded: the reference order
    stands and its deeper seed is the exact 3-way statistic."""
    return And([_member(g, _v("V3")), _member(_v("V2"), _v("V3")),
                _member(_v("V4"), _v("V3"))])


def star4(g, h):
    return And([_member(_v("V1"), _v("V3")), _member(g, _v("V3")),
                _member(_v("V4"), _v("V3")), _member(_v("V5"), _v("V3"))])


def negated(g, h):
    return And([_member(_v("V2"), _v("V3")), _member(g, _v("V3")),
                Not(_interacts(g, _v("V2")))])


def two_grounded(g, h):
    """Both leaves of the dot carry a grounded value."""
    return And([_member(g, _v("V3")), _member(h, _v("V3"))])


def dp_shape(g, h):
    """Connected, but not in reference order: no rule fixes the order,
    every query is ordered from its own counts ("dp")."""
    return And([_member(g, _v("V3")), _interacts(_v("V4"), _v("V2")),
                _member(_v("V2"), _v("V3"))])


def allvar3(g, h):
    return And([_member(_v("V1"), _v("V3")), _member(_v("V2"), _v("V3")),
                _interacts(_v("V1"), _v("V2"))])


def disconnected(g, h):
    """A cross product: the planner declines, the greedy order applies."""
    return And([_member(g, _v("V3")), _interacts(h, _v("V5"))])


def tern2(g, h):
    p = Node("Process", "p1")
    return And([Link("Annot", [g, p, _v("X")], True),
                Link("Annot", [_v("X"), _v("Y"), _v("Z")], True)])


def tern_star(g, h):
    p = Node("Process", "p2")
    return And([Link("Annot", [g, p, _v("X")], True),
                _member(_v("X"), _v("P")),
                Link("Annot", [_v("X"), _v("Y"), h], True)])


BIO_SHAPES = (grounded3, shared2, star3, star4, negated, two_grounded,
              dp_shape, allvar3, disconnected)
CASES = (
    [("bio", s) for s in BIO_SHAPES]
    + [("cell", s) for s in (grounded3, shared2, star3, dp_shape)]
    + [("bio_greedy", s) for s in (grounded3, shared2, dp_shape)]
    + [("tern", s) for s in (tern2, tern_star)]
)


def _plans(db, queries):
    out = []
    for q in queries:
        plans = compiler.plan_query(db, q)
        assert plans is not None and plans is not compiler.EMPTY_PLAN
        out.append(plans)
    return out


def _batch(db, shape, rng, n):
    genes = _genes(db)
    return _plans(db, [shape(rng.choice(genes), rng.choice(genes))
                       for _ in range(n)])


# -- the builder against the oracle ---------------------------------------


@pytest.mark.parametrize(
    "store,shape", CASES, ids=[f"{s}.{f.__name__}" for s, f in CASES])
def test_batch_jobs_equal_per_query_jobs(stores, store, shape):
    db = stores(store)
    ex = fused.get_executor(db)
    rng = random.Random(f"{store}.{shape.__name__}")
    methods = set()
    for n in (1, 2, 7, 19):     # random keys: duplicates happen
        for count_only in (False, True):
            jobs = check_batch(
                ex, _batch(db, shape, rng, n), count_only,
                f"{store}.{shape.__name__} n={n}")
            methods |= {
                j.planned.method if j.planned is not None else "greedy"
                for j in jobs if j is not None
            }
    want = {
        "dp_shape": {"dp"}, "allvar3": {"dp"}, "disconnected": {"greedy"},
    }.get(shape.__name__, {"ref_order"})
    assert methods == ({"greedy"} if store == "bio_greedy" else want)


def test_mixed_batch_is_told_apart_by_shape(stores):
    db = stores("bio")
    ex = fused.get_executor(db)
    rng = random.Random(41)
    genes = _genes(db)
    shapes = (grounded3, shared2, negated, star3, dp_shape)
    queries = [rng.choice(shapes)(rng.choice(genes), rng.choice(genes))
               for _ in range(40)]
    jobs = check_batch(ex, _plans(db, queries), False, "mixed")
    assert len({fused.shape_key(p) for p in _plans(db, queries)}) == 5
    # templates: one per shape, kept across batches of one version
    ex._templates.clear()
    _jobs, n_shapes, built = ex._build(_plans(db, queries), False)
    assert (n_shapes, built) == (5, 5)
    _jobs, n_shapes, built = ex._build(_plans(db, queries[:9]), False)
    assert built == 0
    assert all(j is not None for j in jobs)


def test_learned_capacities_merge_like_the_oracle(stores):
    db = stores("bio")
    ex = fused.get_executor(db)
    rng = random.Random(5)
    plans = _batch(db, shared2, rng, 6)
    job = ex._exec_job(list(plans[0]), False)
    saved = dict(ex._caps)
    try:
        # a learned tier above every seed, and one above the ceiling
        big = tuple(c * 8 for c in job.join_caps)
        ex._caps[job.sigs] = (job.term_caps, big)
        jobs = check_batch(ex, plans, False, "learned")
        assert all(j.join_caps == big for j in jobs)
        ex._caps[job.sigs] = (
            job.term_caps, (db.config.max_result_capacity * 2,))
        assert check_batch(ex, plans, False, "ceiling") == [None] * 6
    finally:
        ex._caps.clear()
        ex._caps.update(saved)


def test_exec_job_is_the_batch_of_one(stores):
    db = stores("bio")
    ex = fused.get_executor(db)
    plans = _batch(db, grounded3, random.Random(3), 3)
    for p in plans:
        assert_same_job(ex._exec_job(list(p), False),
                        oracle_exec_job(ex, list(p), False), "lone")
    # a missing bucket declines the whole shape, counting nothing
    saved = dict(db.dev.buckets)
    _planner.reset_planner_counts()
    try:
        db.dev.buckets.clear()
        assert ex._build([list(p) for p in plans], False)[0] == [None] * 3
        assert oracle_exec_job(ex, list(plans[0]), False) is None
    finally:
        db.dev.buckets.update(saved)
    assert _planner.PLANNER_COUNTS["planned"] == 0
    assert _planner.PLANNER_COUNTS["greedy"] == 0


# -- through dispatch_pending: duplicates, cache hits, answers ------------


def _rows(result):
    return sorted(map(tuple, np.asarray(result.host_vals)[
        np.asarray(result.host_valid)]))


def test_served_batch_builds_only_distinct_misses_and_answers_like_execute():
    data, _g, _p = build_bio_atomspace(
        n_genes=60, n_processes=15, members_per_gene=4,
        n_interactions=80, seed=7)
    db = TensorDB(data, DasConfig(result_cache_size=64))
    ex = fused.get_executor(db)
    genes = _genes(db)
    queries = [(shared2 if i % 4 == 3 else grounded3)(genes[i], None)
               for i in range(12)]
    plans = _plans(db, queries)
    want = [ex.execute(list(p)) for p in plans]
    ex.execute_many(plans[:3])              # three answers are cached
    batch = plans + [plans[5], plans[7], plans[5]]   # in-batch duplicates
    seen = []
    build = ex._build_jobs

    def spy(plans_lists, count_only):
        seen.append(len(plans_lists))
        return build(plans_lists, count_only)

    ex._build_jobs = spy
    try:
        misses = ex.results.stats["misses"]
        pending = ex.dispatch_many(batch)
        assert seen == [9]       # 12 - 3 hits; a duplicate builds nothing
        assert ex.results.stats["misses"] == misses + 9
        # one program per shape: the jobs of a shape share a signature
        assert len(pending.programs) == 2
        got = ex.settle_many(pending)
        assert ex.dispatch_many(plans[:3], cache_only=True).programs == []
        assert seen == [9]       # a cache_only round builds nothing
    finally:
        del ex._build_jobs
    for g, w in zip(got, want + [want[5], want[7], want[5]]):
        assert (g.count, g.reseed_needed) == (w.count, w.reseed_needed)
        assert _rows(g) == _rows(w)
    assert got[12] is got[5] and got[14] is got[5] and got[13] is got[7]


# -- a commit: templates rebuilt, jobs equal again ------------------------


def _group_store_text():
    from tests.test_group_dispatch import _store_text

    return _store_text()


def test_commit_rebuilds_the_templates():
    das = DistributedAtomSpace(
        database_name="jb_commit", backend="tensor", config=DasConfig())
    das.load_metta_text(_group_store_text())
    db = das.db
    ex = fused.get_executor(db)

    def queries():
        return _plans(db, [
            (shared2 if i % 3 == 2 else grounded3)(Node("Gene", f"g{i}"), None)
            for i in range(9)])

    check_batch(ex, queries(), False, "before")
    assert len(ex._templates) == 2
    before = dict(ex._templates)
    version = db.delta_version
    das.load_metta_text(
        '(: "g3" Gene)\\n(: "g4" Gene)\\n(: "p0" Process)\\n(: "p5" Process)\\n'
        '(Member "g3" "p0")\\n(Member "g4" "p5")\\n(Interacts "g3" "g4")\\n'
        .replace("\\n", "\n"))
    assert db.delta_version != version
    assert len(host_segments(db, 2)) == 2     # base + one overlay
    ex = fused.get_executor(db)
    jobs = check_batch(ex, queries(), False, "after")
    assert ex._templates_version == db.delta_version
    assert len(ex._templates) == 2
    assert all(t is not before[k] for k, t in ex._templates.items())
    # the overlay's rows are counted: g3 has three memberships now
    g3 = jobs[3]
    assert g3.planned.est_term_rows[0] == 3
    # and the answers are the lone execute's
    plans = queries()
    got = ex.execute_many(plans)
    for g, p in zip(got, plans):
        w = ex.execute(list(p))
        assert g.count == w.count and _rows(g) == _rows(w)


# -- the planner's memo (ROADMAP D17) -------------------------------------


def test_served_path_writes_no_key_per_grounded_value(stores):
    db = stores("cell")
    ex = fused.get_executor(db)
    from benchmark.reference import generator

    def serve(lo, hi):
        qs = []
        for i in range(lo, hi):
            g = Node("Gene", generator.gene_name(i))
            qs.append((shared2 if i % 10 == 9 else grounded3)(g, None))
        jobs = ex._build_jobs(_plans(db, qs), False)
        assert all(j is not None for j in jobs)

    db._planner_estimator = None   # the oracle's runs wrote their keys
    serve(0, 3)
    serve(9, 10)
    est = estimator_for(db)
    memo, cache = len(est._rows), len(getattr(db, "_star_host_cache", {}))
    serve(10, 130)
    assert estimator_for(db) is est
    assert len(est._rows) == memo
    assert not [
        key for key in est._rows if isinstance(key[0], int) and key[3]
    ], "a per-term key with grounded values"
    # nor an entry per grounded value in starcount's FIFO of supports
    assert len(getattr(db, "_star_host_cache", {})) == cache
