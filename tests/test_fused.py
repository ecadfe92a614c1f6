"""Fused single-dispatch executor: answer parity with the host algebra,
batched counting, capacity learning, and the staged-path fallbacks that
keep the reference reseed quirk exact."""

import numpy as np
import pytest

import das_tpu.query.compiler as compiler
from das_tpu.query.ast import (
    And,
    Link,
    Node,
    Not,
    PatternMatchingAnswer,
    Variable,
)
from das_tpu.query.fused import FusedExecutor, _pow2_at_least
from das_tpu.storage.tensor_db import TensorDB


@pytest.fixture(scope="module")
def tdb(animals_data):
    return TensorDB(animals_data)


@pytest.fixture(scope="module")
def ex(tdb):
    return FusedExecutor(tdb)


def _answers(db, query):
    host = PatternMatchingAnswer()
    query.matched(db, host)
    dev = PatternMatchingAnswer()
    compiler.query_on_device(db, query, dev)
    return host, dev


def test_pow2():
    assert _pow2_at_least(0) == 16
    assert _pow2_at_least(16) == 16
    assert _pow2_at_least(17) == 32
    assert _pow2_at_least(100000) == 131072


def test_estimates_are_exact(tdb, ex):
    plans = compiler.plan_query(
        tdb, Link("Inheritance", [Variable("V1"), Variable("V2")], True)
    )
    assert ex._estimate(plans[0]) == 12  # 12 Inheritance edges in animals
    plans = compiler.plan_query(
        tdb,
        Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True),
    )
    # 4 links end at mammal: human/monkey/chimp/rhino
    assert ex._estimate(plans[0]) == 4


def test_order_policy(tdb, ex):
    # connected-in-reference-order plans KEEP reference order (the program
    # is then the reference fold; zero counts are definitive)
    q = And([
        Link("Inheritance", [Variable("V1"), Variable("V2")], True),      # 12
        Link("Inheritance", [Variable("V2"), Node("Concept", "animal")], True),  # 2
    ])
    plans = compiler.plan_query(tdb, q)
    ordered = ex._order(plans)
    assert [p is q for p, q in zip(ordered, plans)] == [True, True]
    # disconnected plans fall back to greedy smallest-first
    q2 = And([
        Link("Inheritance", [Variable("V1"), Variable("V2")], True),      # 12
        Link("Similarity", [Variable("V3"), Variable("V4")], True),       # 14
        Link("Inheritance", [Variable("V3"), Node("Concept", "animal")], True),  # 2
    ])
    plans2 = compiler.plan_query(tdb, q2)
    ordered2 = ex._order(plans2)
    assert ex._estimate(ordered2[0]) == min(ex._estimate(p) for p in plans2)
    # negated terms always run last
    q3 = And([
        Not(Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True)),
        Link("Inheritance", [Variable("V1"), Variable("V2")], True),
    ])
    plans3 = compiler.plan_query(tdb, q3)
    assert ex._order(plans3)[-1].negated


def test_fused_execute_matches_host(tdb, ex):
    q = And([
        Link("Inheritance", [Variable("V1"), Variable("V3")], True),
        Link("Inheritance", [Variable("V2"), Variable("V3")], True),
    ])
    host, dev = _answers(tdb, q)
    assert host.assignments == dev.assignments
    res = ex.execute(compiler.plan_query(tdb, q))
    assert res is not None
    assert res.count == len(host.assignments)


def test_count_only_matches_full(tdb, ex):
    q = And([
        Link("Inheritance", [Variable("V1"), Variable("V3")], True),
        Link("Inheritance", [Variable("V2"), Variable("V3")], True),
    ])
    plans = compiler.plan_query(tdb, q)
    full = ex.execute(plans)
    counted = ex.execute(plans, count_only=True)
    assert counted.vals is None and counted.valid is None
    assert counted.count == full.count


def test_empty_positive_term_is_definitive_no_match(tdb, ex):
    # plant has no outgoing Inheritance: an empty POSITIVE TERM fails the
    # whole And in the reference (term.matched False -> return False), so
    # the fused path answers count=0 WITHOUT a reseed fallback — zero-answer
    # queries stay on the single-dispatch path (critical for batch counting)
    q = And([
        Link("Inheritance", [Node("Concept", "plant"), Variable("V1")], True),
        Link("Inheritance", [Variable("V1"), Variable("V2")], True),
    ])
    plans = compiler.plan_query(tdb, q)
    res = ex.execute(plans)
    assert res is not None and not res.reseed_needed and res.count == 0
    # and the public path still agrees with the host algebra
    host, dev = _answers(tdb, q)
    assert host.assignments == dev.assignments


def test_join_emptied_accumulator_still_defers(tdb, ex):
    # both terms non-empty but the join is empty AND a positive term
    # remains -> the reference reseed quirk can fire; fused must defer
    q = And([
        Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True),
        Link("Inheritance", [Node("Concept", "earthworm"), Variable("V1")], True),
        Link("Similarity", [Variable("V2"), Variable("V3")], True),
    ])
    plans = compiler.plan_query(tdb, q)
    if plans is None:
        return  # shape outside the fused subset on this KB — nothing to check
    res = ex.execute(plans)
    assert res is None or res.reseed_needed or res.count > 0
    host, dev = _answers(tdb, q)
    assert host.assignments == dev.assignments


def test_caps_learned_and_reused(tdb):
    ex2 = FusedExecutor(tdb)
    q = And([
        Link("Inheritance", [Variable("V1"), Variable("V3")], True),
        Link("Inheritance", [Variable("V2"), Variable("V3")], True),
    ])
    plans = compiler.plan_query(tdb, q)
    ex2.execute(plans)
    assert len(ex2._caps) == 1
    (tc, jc), = ex2._caps.values()
    ex2.execute(plans)  # second run seeds from memo — still correct
    assert ex2._caps[next(iter(ex2._caps))] == (tc, jc)


def test_count_batch_matches_individual(tdb, ex):
    queries = [
        Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True),
        Link("Inheritance", [Variable("V1"), Node("Concept", "animal")], True),
        Link("Inheritance", [Variable("V1"), Node("Concept", "plant")], True),
        Link("Similarity", [Variable("V1"), Variable("V2")], False),  # unordered
        And([
            Link("Inheritance", [Variable("V1"), Variable("V3")], True),
            Link("Inheritance", [Variable("V2"), Variable("V3")], True),
        ]),
    ]
    plans_list = [compiler.plan_query(tdb, q) for q in queries]
    fusable = [p for p in plans_list if p is not None]
    batch = ex.count_batch(fusable)
    # single-term queries can never need the reseed fallback, so the batch
    # path must actually answer them — guards against a vacuous pass where
    # count_batch declines everything
    assert sum(g is not None for g in batch) >= 3
    it = iter(batch)
    for q, plans in zip(queries, plans_list):
        if plans is None:
            continue
        got = next(it)
        expected = compiler.count_matches(tdb, q)
        if got is not None:
            assert got == expected, repr(q)


def test_count_batch_groups_same_shape(tdb, ex):
    # three same-shape queries must produce exactly one batch group
    queries = [
        Link("Inheritance", [Variable("V1"), Node("Concept", c)], True)
        for c in ("mammal", "animal", "reptile")
    ]
    plans_list = [compiler.plan_query(tdb, q) for q in queries]
    counts = ex.count_batch(plans_list)
    # mammal ← human/monkey/chimp/rhino; animal ← mammal/reptile/earthworm;
    # reptile ← snake/dinosaur
    assert counts == [4, 3, 2]


# -- exact (reference-order, in-program reseed) variant ---------------------

RESEED_SHAPES = [
    # join empties mid-way, later term reseeds (suffix answer)
    And([
        Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True),
        Link("Inheritance", [Node("Concept", "earthworm"), Variable("V1")], True),
        Link("Inheritance", [Variable("V2"), Node("Concept", "animal")], True),
    ]),
    # reseeds twice: two disjoint empty joins
    And([
        Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True),
        Link("Inheritance", [Node("Concept", "earthworm"), Variable("V1")], True),
        Link("Inheritance", [Variable("V2"), Node("Concept", "reptile")], True),
        Link("Inheritance", [Node("Concept", "vine"), Variable("V2")], True),
        Link("Inheritance", [Variable("V3"), Node("Concept", "plant")], True),
    ]),
    # empties at the FINAL join: definitive empty answer, no reseed
    And([
        Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True),
        Link("Inheritance", [Node("Concept", "earthworm"), Variable("V1")], True),
    ]),
    # reseed + negation: tabu covers only the suffix variable set
    And([
        Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True),
        Link("Inheritance", [Node("Concept", "earthworm"), Variable("V1")], True),
        Link("Inheritance", [Variable("V2"), Node("Concept", "animal")], True),
        Not(Link("Inheritance", [Variable("V2"), Node("Concept", "animal")], True)),
    ]),
]


@pytest.mark.parametrize("qi", range(len(RESEED_SHAPES)))
def test_exact_variant_matches_host_on_reseed_shapes(tdb, ex, qi):
    q = RESEED_SHAPES[qi]
    host, dev = _answers(tdb, q)
    assert dev.assignments == host.assignments
    # the exact program itself (not the staged fallback) must answer it
    plans = compiler.plan_query(tdb, q)
    assert plans is not None
    res = ex.execute_exact(plans)
    assert res is not None and not res.reseed_needed
    host_count = len(host.assignments)
    assert res.count == host_count


def test_count_batch_exact_pass_answers_reseed_queries(tdb, ex):
    queries = RESEED_SHAPES[:3]
    plans_list = [compiler.plan_query(tdb, q) for q in queries]
    assert all(p is not None for p in plans_list)
    batch = ex.count_batch(plans_list)
    for got, q in zip(batch, queries):
        assert got is not None, f"exact pass declined {q}"
        host = __import__("das_tpu.query.ast", fromlist=["PatternMatchingAnswer"]).PatternMatchingAnswer()
        q.matched(tdb, host)
        assert got == len(host.assignments)


def test_index_join_routing_and_parity(tdb, ex):
    """A whole-type ungrounded right term routes through the posting-index
    join (never materialized: its term cap stays at the 16-row token) and
    answers stay host-identical."""
    from das_tpu.query.fused import plan_index_joins

    q = And([
        Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True),
        Link("Inheritance", [Variable("V1"), Variable("V2")], True),  # whole-type
    ])
    plans = compiler.plan_query(tdb, q)
    ordered = ex._order(plans)
    mapped = [ex._term_args(p) for p in ordered]
    sigs = tuple(m[0] for m in mapped)
    index_joins, index_right = plan_index_joins(sigs)
    assert any(p >= 0 for p in index_joins), "index join did not activate"
    host, dev = _answers(tdb, q)
    assert dev.assignments == host.assignments
    res = ex.execute(plans)
    assert res is not None and res.count == len(host.assignments)


# -- host single-term counting (the miner's candidate shape) ----------------


TRI_METTA = """(: Rel Type)
(: Concept Type)
(: "a" Concept)
(: "b" Concept)
(: "c" Concept)
(: "d" Concept)
(: "e" Concept)
(: "x" Concept)
(Rel "a" "b" "c")
(Rel "a" "b" "d")
(Rel "a" "e" "c")
(Rel "x" "b" "c")
(Rel "x" "e" "d")
"""


@pytest.fixture(scope="module")
def tri_db():
    from das_tpu.storage.atom_table import load_metta_text

    return TensorDB(load_metta_text(TRI_METTA))


def _grounded_cases(db):
    yield Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True), db
    yield Link("Inheritance", [Node("Concept", "human"), Variable("V1")], True), db
    yield Link("Inheritance", [Node("Concept", "plant"), Variable("V1")], True), db


def test_host_single_term_count_matches_device_and_host(tdb, tri_db, ex, monkeypatch):
    """The host-side exact count for grounded single-term patterns (the
    miner's wildcard-variant shape) agrees with BOTH the device path and
    the host algebra, across one- and multi-fixed shapes."""
    from das_tpu.query.fused import trivial_plan_count

    cases = [
        (q, db) for q, db in _grounded_cases(tdb)
    ] + [
        # multi-fixed arity-3 variants: narrowest-position probe + verify
        (Link("Rel", [Node("Concept", "a"), Node("Concept", "b"), Variable("V1")], True), tri_db),
        (Link("Rel", [Node("Concept", "a"), Variable("V1"), Node("Concept", "c")], True), tri_db),
        (Link("Rel", [Variable("V1"), Node("Concept", "b"), Node("Concept", "c")], True), tri_db),
        (Link("Rel", [Node("Concept", "x"), Variable("V1"), Variable("V2")], True), tri_db),
        (Link("Rel", [Variable("V1"), Variable("V2"), Node("Concept", "d")], True), tri_db),
    ]
    for q, db in cases:
        plans = compiler.plan_query(db, q)
        assert plans is not None
        n = trivial_plan_count(db, plans)
        assert n is not None, repr(q)
        # host algebra
        host = PatternMatchingAnswer()
        matched = q.matched(db, host)
        assert n == (len(host.assignments) if matched else 0), repr(q)
        # device (staged pipeline — shortcut-independent)
        assert n == compiler.count_matches_staged(db, plans), repr(q)
        # and the device BATCH path with the shortcut disabled
        monkeypatch.setenv("DAS_TPU_HOST_COUNT", "0")
        try:
            from das_tpu.query.fused import FusedExecutor

            dev = FusedExecutor(db).count_batch([plans])[0]
        finally:
            monkeypatch.delenv("DAS_TPU_HOST_COUNT")
        if dev is not None:
            assert n == dev, repr(q)


def test_host_single_term_count_sees_commit():
    """Counts must include incremental-delta overlay segments: the host
    route sums over host_bucket_segments, exactly mirroring the merged
    device index."""
    from das_tpu.api.atomspace import DistributedAtomSpace
    from das_tpu.models.animals import animals_metta
    from das_tpu.query.fused import trivial_plan_count

    das = DistributedAtomSpace(backend="tensor")
    das.load_metta_text(animals_metta())
    q = Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")], True)
    assert trivial_plan_count(das.db, compiler.plan_query(das.db, q)) == 4

    tx = das.open_transaction()
    tx.add('(: "lion" Concept)')
    tx.add('(Inheritance "lion" "mammal")')
    das.commit_transaction(tx)
    plans = compiler.plan_query(das.db, q)
    assert trivial_plan_count(das.db, plans) == 5
    host = PatternMatchingAnswer()
    q.matched(das.db, host)
    assert len(host.assignments) == 5


def test_host_single_term_count_dangling_defers():
    """A dangling (-1) element in a variable position could make two
    distinct links bind identical tuples — the host route must defer to
    the device path (None) instead of answering without dedup."""
    from das_tpu.query.fused import trivial_plan_count
    from das_tpu.storage.atom_table import load_metta_text

    data = load_metta_text(
        '(: Rel Type)(: Concept Type)(: "a" Concept)(: "b" Concept)\n'
        '(Rel "a" "b")'
    )
    # forge a link whose second element resolves to no row
    rec = next(iter(data.links.values()))
    from das_tpu.storage.atom_table import LinkRec

    data.links["f" * 32] = LinkRec(
        named_type=rec.named_type,
        named_type_hash=rec.named_type_hash,
        composite_type=rec.composite_type,
        composite_type_hash=rec.composite_type_hash,
        elements=(rec.elements[0], "e" * 32),  # unknown handle -> dangling
        is_toplevel=True,
    )
    db = TensorDB(data)
    assert db.fin.dangling_hexes  # the forged ghost element
    q = Link("Rel", [Node("Concept", "a"), Variable("V1")], True)
    plans = compiler.plan_query(db, q)
    assert trivial_plan_count(db, plans) is None
