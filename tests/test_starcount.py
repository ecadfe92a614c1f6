"""Star-count route (query/starcount.py): closed-form degree-product
counts must equal the general join path and the host algebra on every
star-shaped conjunction the miner emits."""

import pytest

from das_tpu.core.config import DasConfig
from das_tpu.models.bio import build_bio_atomspace
from das_tpu.query import compiler, starcount
from das_tpu.query.ast import And, Link, Node, PatternMatchingAnswer, Variable
from das_tpu.storage.memory_db import MemoryDB
from das_tpu.storage.tensor_db import TensorDB


@pytest.fixture(params=["host", "device"], autouse=True)
def star_fold_edition(request, monkeypatch):
    """Every case runs under BOTH fold editions: the host fold (sparse
    supports + symbolic whole-table terms) and the device degree-vector
    fold — they must be count-identical everywhere, including the
    reseed/empty-term quirks."""
    monkeypatch.setenv("DAS_TPU_STAR_FOLD", request.param)
    return request.param


@pytest.fixture(scope="module")
def bio_db():
    data, _, _ = build_bio_atomspace(
        n_genes=120, n_processes=10, members_per_gene=4,
        n_interactions=150, n_evaluations=30,
    )
    return TensorDB(data, DasConfig())


def _star(terms):
    return And(terms)


def _general_count(db, q, monkeypatch_env=None):
    """The same query through the general executors (star disabled)."""
    import os

    old = os.environ.get("DAS_TPU_STAR")
    os.environ["DAS_TPU_STAR"] = "0"
    try:
        return compiler.count_matches(db, q)
    finally:
        if old is None:
            del os.environ["DAS_TPU_STAR"]
        else:
            os.environ["DAS_TPU_STAR"] = old


def _host_count(db, q):
    host = MemoryDB(db.data)
    a = PatternMatchingAnswer()
    matched = q.matched(host, a)
    return len(a.assignments) if matched else 0


CASES = []


def _case(fn):
    CASES.append(fn)
    return fn


@_case
def _all_whole_table(db):
    return _star([
        Link("Member", [Variable("V0"), Variable("T0_V1")], True),
        Link("Interacts", [Variable("V0"), Variable("T1_V1")], True),
    ])


@_case
def _three_way(db):
    return _star([
        Link("Member", [Variable("V0"), Variable("T0_V1")], True),
        Link("Member", [Variable("V0"), Variable("T1_V1")], True),
        Link("Interacts", [Variable("V0"), Variable("T2_V1")], True),
    ])


@_case
def _structurally_identical_terms(db):
    # the diagonal counts too: ordered pairs of Member links per gene
    return _star([
        Link("Member", [Variable("V0"), Variable("A")], True),
        Link("Member", [Variable("V0"), Variable("B")], True),
    ])


@_case
def _with_grounded(db):
    procs = db.get_all_nodes("BiologicalProcess", names=True)
    return _star([
        Link("Member", [Variable("V0"), Node("BiologicalProcess", procs[0])], True),
        Link("Interacts", [Variable("V0"), Variable("T1_V1")], True),
    ])


@_case
def _two_probed(db):
    procs = db.get_all_nodes("BiologicalProcess", names=True)
    return _star([
        Link("Member", [Variable("V0"), Node("BiologicalProcess", procs[0])], True),
        Link("Member", [Variable("V0"), Node("BiologicalProcess", procs[1])], True),
        Link("Member", [Variable("V0"), Variable("T2_V1")], True),
    ])


@_case
def _shared_in_second_position(db):
    return _star([
        Link("Member", [Variable("T0_V1"), Variable("V0")], True),
        Link("Member", [Variable("T1_V1"), Variable("V0")], True),
    ])


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__)
def test_star_matches_general_and_host(bio_db, case):
    q = case(bio_db)
    plans = compiler.plan_query(bio_db, q)
    lane = starcount.plan_star(bio_db, plans)
    assert lane is not None, "case must be star-shaped"
    n_star = starcount.star_count_many(bio_db, [lane])[0]
    assert n_star == _general_count(bio_db, q)
    assert n_star == _host_count(bio_db, q)
    assert n_star > 0  # vacuous parity would prove nothing


def test_non_star_shapes_fall_through(bio_db):
    # path shape (two shared variables) must NOT take the star route
    q = And([
        Link("Member", [Variable("V1"), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Interacts", [Variable("V1"), Variable("V2")], True),
    ])
    plans = compiler.plan_query(bio_db, q)
    assert starcount.plan_star(bio_db, plans) is None
    # single term is not a star either
    q1 = Link("Member", [Variable("V0"), Variable("V1")], True)
    assert starcount.plan_star(bio_db, compiler.plan_query(bio_db, q1)) is None


def test_count_matches_routes_star(bio_db):
    q = _three_way(bio_db)
    compiler.reset_route_counts()
    n = compiler.count_matches(bio_db, q)
    assert compiler.ROUTE_COUNTS["star"] == 1
    assert n == _host_count(bio_db, q)


@pytest.mark.full
def test_miner_equivalence_with_star_disabled(bio_db, monkeypatch):
    """mine() must produce identical results with and without the route."""
    from das_tpu.mining.miner import PatternMiner

    def run():
        miner = PatternMiner(bio_db, halo_length=2, link_rate=0.5, seed=11)
        genes = bio_db.get_all_nodes("Gene", names=True)[:2]
        seeds = [bio_db.get_node_handle("Gene", g) for g in genes]
        miner.expand_halo(seeds)
        miner.build_patterns()
        best = miner.mine(ngram=3, epochs=12)
        return (best.count, best.isurprisingness, best.term_handles) if best else None

    with_star = run()
    monkeypatch.setenv("DAS_TPU_STAR", "0")
    without = run()
    assert with_star == without and with_star is not None


def test_midfold_reseed_computed_in_program(bio_db):
    """A DISJOINT join in the middle of the fold fires the reference's
    reseed quirk — the in-program fold must reproduce the reseeded answer
    exactly (no general-path fallback)."""
    procs = bio_db.get_all_nodes("BiologicalProcess", names=True)
    genes = bio_db.get_all_nodes("Gene", names=True)
    q = _star([
        # V0 = genes in procs[0]
        Link("Member", [Variable("V0"), Node("BiologicalProcess", procs[0])], True),
        # V0 = processes of genes[0] — disjoint domain; join 2 empties
        Link("Member", [Node("Gene", genes[0]), Variable("V0")], True),
        Link("Member", [Variable("T2_V1"), Variable("V0")], True),
    ])
    plans = compiler.plan_query(bio_db, q)
    lane = starcount.plan_star(bio_db, plans)
    assert lane is not None
    n_host = _host_count(bio_db, q)
    assert n_host > 0  # the quirk actually fired here
    assert starcount.star_count_many(bio_db, [lane]) == [n_host]
    assert compiler.count_matches(bio_db, q) == n_host


def test_final_join_zero_is_certified(bio_db):
    """The FINAL join emptying leaves no term to reseed from — the
    reference answers 0 too, and the cascade certifies it without the
    general path (prefixes nonempty, last total zero)."""
    procs = bio_db.get_all_nodes("BiologicalProcess", names=True)
    genes = bio_db.get_all_nodes("Gene", names=True)
    q = _star([
        Link("Member", [Variable("V0"), Node("BiologicalProcess", procs[0])], True),
        Link("Member", [Variable("V0"), Variable("T1_V1")], True),
        # disjoint only at the LAST fold step
        Link("Member", [Node("Gene", genes[0]), Variable("V0")], True),
    ])
    plans = compiler.plan_query(bio_db, q)
    lane = starcount.plan_star(bio_db, plans)
    assert lane is not None
    assert starcount.star_count_many(bio_db, [lane]) == [0]
    assert _host_count(bio_db, q) == 0


def test_two_term_disjoint_is_exact_zero(bio_db):
    """With n=2 a disjoint join IS the final join: exact 0, no decline."""
    procs = bio_db.get_all_nodes("BiologicalProcess", names=True)
    genes = bio_db.get_all_nodes("Gene", names=True)
    q = _star([
        Link("Member", [Variable("V0"), Node("BiologicalProcess", procs[0])], True),
        Link("Member", [Node("Gene", genes[0]), Variable("V0")], True),
    ])
    lane = starcount.plan_star(bio_db, compiler.plan_query(bio_db, q))
    assert starcount.star_count_many(bio_db, [lane]) == [0]
    assert _host_count(bio_db, q) == 0


def test_empty_positive_term_is_exact_zero(bio_db):
    """A term with ZERO matching rows makes the reference And fail
    outright (Link.matched is False before any join/reseed) — the guard
    must answer 0 even though the fold would reseed past it."""
    genes = bio_db.get_all_nodes("Gene", names=True)
    # find a gene with no outgoing Interacts: its grounded term is empty
    for g in genes:
        probe = _star([
            Link("Interacts", [Node("Gene", g), Variable("V0")], True),
            Link("Member", [Variable("V0"), Variable("T1_V1")], True),
        ])
        plans = compiler.plan_query(bio_db, probe)
        lane = starcount.plan_star(bio_db, plans)
        host = _host_count(bio_db, probe)
        assert starcount.star_count_many(bio_db, [lane]) == [host]
        if host == 0:
            # found the empty-term case and the guard answered it
            a = compiler.count_matches(bio_db, probe)
            assert a == 0
            return
    pytest.skip("every gene interacts; KB too dense for the empty case")


def test_missing_bucket_term_is_exact_zero(bio_db):
    """A term whose (arity, type) bucket does not exist at all (unknown
    arity) short-circuits to 0 before any dispatch."""
    q = _star([
        Link("Member", [Variable("V0"), Variable("A"), Variable("B"),
                        Variable("C"), Variable("D"), Variable("E")], True),
        Link("Member", [Variable("V0"), Variable("F")], True),
    ])
    plans = compiler.plan_query(bio_db, q)
    if plans is None:
        pytest.skip("6-ary plan declined upstream")
    lane = starcount.plan_star(bio_db, plans)
    assert lane is not None
    assert starcount.star_count_many(bio_db, [lane]) == [0]
    assert _host_count(bio_db, q) == 0


def test_deg_cache_stale_length_after_mixed_arity_commit():
    """A commit that grows atom_count while leaving one arity's bucket
    untouched must not serve that arity's cached degree vector at the old
    length (the fold would shape-mismatch or undercount)."""
    from das_tpu.storage.atom_table import AtomSpaceData, load_metta_text

    text = "\n".join(
        ["(: Concept Type)", "(: List Type)", "(: Pair Type)"]
        + [f'(: "c{i}" Concept)' for i in range(6)]
        + [f'(List "c{i}")' for i in range(6)]
        + [f'(Pair "c{i}" "c{(i + 1) % 6}")' for i in range(6)]
    )
    db = TensorDB(load_metta_text(text), DasConfig())
    q = _star([
        Link("List", [Variable("V0")], True),
        Link("Pair", [Variable("V0"), Variable("A")], True),
    ])
    lane = starcount.plan_star(db, compiler.plan_query(db, q))
    assert lane is not None
    before = starcount.star_count_many(db, [lane])[0]
    assert before == _host_count(db, q) > 0
    # commit: new node + arity-2 link ONLY — the arity-1 bucket object
    # survives while atom_count grows
    load_metta_text(
        '(: "c_new" Concept)\n(Pair "c_new" "c0")', db.data
    )
    db.refresh()
    lane2 = starcount.plan_star(db, compiler.plan_query(db, q))
    after = starcount.star_count_many(db, [lane2])[0]
    assert after == _host_count(db, q)


def test_deg_cache_invalidates_on_commit(bio_db, star_fold_edition):
    """An incremental commit swaps buckets; the cached degree vectors must
    not serve stale counts.  (bio_db is module-scoped and both fold
    editions run against it — the commit names carry the edition so the
    second run's delta is not a dedup no-op.)"""
    from das_tpu.storage.atom_table import load_metta_text

    q = _star([
        Link("Interacts", [Variable("V0"), Variable("A")], True),
        Link("Interacts", [Variable("V0"), Variable("B")], True),
    ])
    before = compiler.count_matches(bio_db, q)
    tag = star_fold_edition
    commit = "\n".join(
        [f'(: "SGX_{tag}_{i}" Gene)' for i in range(3)]
        + [f'(Interacts "SGX_{tag}_0" "SGX_{tag}_1")',
           f'(Interacts "SGX_{tag}_0" "SGX_{tag}_2")']
    )
    load_metta_text(commit, bio_db.data)
    bio_db.refresh()
    after = compiler.count_matches(bio_db, q)
    assert after == _host_count(bio_db, q)
    assert after > before


def test_dangling_whole_table_term_matches_dense_edition(monkeypatch):
    """A whole-table term whose rows dangle at the shared position must
    contribute the DENSE degree sum (danglings excluded), not the raw row
    count: the symbolic total feeds the empty-positive-term guard and any
    reseed landing on the term.  Both fold editions must agree."""
    import numpy as np

    from das_tpu.storage.atom_table import LinkRec, load_metta_text

    data = load_metta_text(
        "\n".join(
            ["(: Rel Type)", "(: Tab Type)", "(: Concept Type)"]
            + [f'(: "c{i}" Concept)' for i in range(4)]
            + ['(Rel "c0" "c1")', '(Rel "c0" "c2")', '(Tab "c3" "c0")']
        )
    )
    # forge Tab links dangling at position 0 (the shared-variable side)
    tab = next(rec for rec in data.links.values() if rec.named_type == "Tab")
    for i in range(2):
        data.links[f"{i:x}" * 32] = LinkRec(
            named_type=tab.named_type,
            named_type_hash=tab.named_type_hash,
            composite_type=tab.composite_type,
            composite_type_hash=tab.composite_type_hash,
            elements=("e" * 31 + str(i), tab.elements[1]),  # ghost col 0
            is_toplevel=True,
        )
    db = TensorDB(data)
    assert db.fin.dangling_hexes
    # star lane: two probed terms with an empty product, then the Tab
    # whole-table term sharing V0 at its DANGLING position — the reseed
    # lands on the symbolic table term
    q = _star([
        Link("Rel", [Node("Concept", "c0"), Variable("V0")], True),
        Link("Rel", [Variable("V0"), Node("Concept", "c1")], True),
        Link("Tab", [Variable("V0"), Variable("T2_V1")], True),
    ])
    plans = compiler.plan_query(db, q)
    lane = starcount.plan_star(db, plans)
    assert lane is not None
    monkeypatch.setenv("DAS_TPU_STAR_FOLD", "host")
    n_host = starcount.star_count_many(db, [lane])[0]
    monkeypatch.setenv("DAS_TPU_STAR_FOLD", "device")
    db._star_deg_cache = {}
    n_dev = starcount.star_count_many(db, [lane])[0]
    assert n_host == n_dev, (n_host, n_dev)
    # the dense degree sum of Tab at position 0 is 1 (only the real link);
    # the raw row count is 3 — a reseed returning the raw count would
    # answer 3 here
    assert n_host == 1


def test_skewed_kb_star_counts_match_host(monkeypatch):
    """Power-law (hub-heavy) degree profile — the shape of real
    annotation data (VERDICT r03 weak #7): the star fold and the device
    paths stay exact when one process hub dominates Member and one gene
    hub dominates Interacts."""
    import numpy as np

    from das_tpu.models.bio import build_bio_atomspace

    data, genes, procs = build_bio_atomspace(
        n_genes=400, n_processes=60, members_per_gene=4,
        n_interactions=500, n_evaluations=0, seed=5, skew=1.5,
    )
    db = TensorDB(data, DasConfig())
    # the profile is actually skewed: top process degree >> median
    b = db.fin.buckets[2]
    member_tid = None
    for h, tid in db.fin.type_id_of_hash.items():
        if db.fin.type_names[tid] == "Member":
            member_tid = tid
    col = b.targets[b.type_id == member_tid, 1]
    degs = np.bincount(col, minlength=db.fin.atom_count)
    assert degs.max() >= 8 * max(1, int(np.median(degs[degs > 0])))

    q = _star([
        Link("Member", [Variable("V0"), Node("BiologicalProcess", "GO:0000000")], True),
        Link("Member", [Variable("V0"), Variable("T1_V1")], True),
        Link("Interacts", [Variable("V0"), Variable("T2_V1")], True),
    ])
    plans = compiler.plan_query(db, q)
    lane = starcount.plan_star(db, plans)
    assert lane is not None
    n = starcount.star_count_many(db, [lane])[0]
    assert n == _host_count(db, q) > 0


def test_evict_oldest_is_fifo_and_partial():
    """Round-4 review: cache eviction keeps the newest entries of the matching
    class (FIFO over dict insertion order) instead of wiping the class,
    and never touches non-matching keys."""
    cache = {}
    for i in range(300):
        cache[("sparse", i)] = i
    cache[("dense", 0)] = "keep"
    starcount._evict_oldest(cache, lambda k: k[0] == "sparse", 192)
    sparse_left = [k for k in cache if k[0] == "sparse"]
    assert len(sparse_left) == 192
    # the SURVIVORS are the newest 192, in original order
    assert sparse_left == [("sparse", i) for i in range(108, 300)]
    assert cache[("dense", 0)] == "keep"


# -- the two host classes: kept whole-table supports, the grounded FIFO ----


@pytest.fixture
def table_counters():
    """`() -> (planner.table_extractions, planner.table_hits)`, live
    (the counters move only under tracing, like every obs counter)."""
    from das_tpu import obs

    was = obs.enabled()
    obs.configure(enabled=True)
    yield lambda: (
        obs.counter("planner.table_extractions").value,
        obs.counter("planner.table_hits").value,
    )
    obs.configure(enabled=was)


def _small_store():
    data, _, _ = build_bio_atomspace(
        n_genes=40, n_processes=6, members_per_gene=3,
        n_interactions=50, n_evaluations=0, seed=3,
    )
    return TensorDB(data, DasConfig())


def _grounded_specs(db, n):
    """`n` DISTINCT grounded Member specs (fixed position 0 at atom row
    r; most supports are empty, every one is an entry of the FIFO)."""
    tid = db._type_id("Member")
    return [(2, tid, 1, ((0, r),)) for r in range(n)]


def _by_handle(db, ent):
    """A support as {atom handle: multiplicity} + its total: what two
    stores with different row numberings (a cold rebuild renumbers the
    rows a commit appended) can be compared on."""
    (idx, cnt), total = ent
    hexes = db.fin.hex_of_row
    return {hexes[int(r)]: int(c) for r, c in zip(idx, cnt)}, total


def test_table_support_outlives_the_grounded_fifo(table_counters):
    """A whole-table support is not in the FIFO of grounded supports:
    600 distinct grounded supports later (six evictions of that FIFO;
    it used to hold the table's entry too and swept it out within
    three, so a table read between them was extracted every ~195
    inserts) the SAME entry object is served, nothing extracted again."""
    db = _small_store()
    spec = (2, db._type_id("Member"), 0, ())
    first = starcount._table_sparse(db, spec)
    assert first[1] == 120  # 40 genes x 3 memberships
    built, served = table_counters()
    for g in _grounded_specs(db, 600):
        starcount._host_sparse_deg(db, g)
    assert starcount._table_sparse(db, spec) is first
    assert table_counters() == (built, served + 1)
    assert list(starcount._table_cache(db)) == [spec[:3]]
    # and the FIFO holds grounded supports only
    assert {k[0] for k in starcount._host_cache(db)} == {"sparse"}


def test_grounded_fifo_keeps_its_bounds():
    """256 -> 192, counting only its own entries: 257 grounded supports
    all stay; the next insert keeps the newest 192 of them, whatever
    the whole-table class holds."""
    db = _small_store()
    tid = db._type_id("Member")
    for pos in (0, 1):
        starcount._table_sparse(db, (2, tid, pos, ()))
    specs = _grounded_specs(db, 258)
    for g in specs[:257]:
        starcount._host_sparse_deg(db, g)
    assert len(starcount._host_cache(db)) == 257
    starcount._host_sparse_deg(db, specs[257])
    assert list(starcount._host_cache(db)) == [
        ("sparse",) + g for g in specs[65:258]
    ]
    assert len(starcount._table_cache(db)) == 2


def test_commit_replaces_a_table_support_in_place(table_counters):
    """Segment identity is the one validity rule: a commit that extends
    the arity's segment list costs exactly ONE extraction per joined
    table, equal to a cold store's, and the class holds one entry for
    the key; an arity whose segment objects survived keeps its entry."""
    from das_tpu.storage.atom_table import load_metta_text

    text = "\n".join(
        ["(: Concept Type)", "(: List Type)", "(: Pair Type)"]
        + [f'(: "c{i}" Concept)' for i in range(6)]
        + [f'(List "c{i}")' for i in range(6)]
        + [f'(Pair "c{i}" "c{(i + 1) % 6}")' for i in range(6)]
    )
    db = TensorDB(load_metta_text(text), DasConfig())
    pair = (2, db._type_id("Pair"), 0, ())
    lst = (1, db._type_id("List"), 0, ())
    pair_before = starcount._table_sparse(db, pair)
    list_before = starcount._table_sparse(db, lst)
    built, served = table_counters()
    # new node + arity-2 link ONLY: the arity-1 segments survive
    load_metta_text('(: "c_new" Concept)\n(Pair "c_new" "c0")', db.data)
    db.refresh()
    pair_after = starcount._table_sparse(db, pair)
    assert starcount._table_sparse(db, pair) is pair_after
    assert starcount._table_sparse(db, lst) is list_before
    assert table_counters() == (built + 1, served + 2)
    assert pair_after is not pair_before
    assert pair_after[1] == pair_before[1] + 1
    cold = TensorDB(db.data, DasConfig())
    assert _by_handle(db, pair_after) == _by_handle(
        cold, starcount._table_sparse(cold, pair)
    )
    assert sorted(starcount._table_cache(db)) == sorted([pair[:3], lst[:3]])
    kept_segments = starcount._table_cache(db)[pair[:3]][0]
    assert len(kept_segments) == 2  # base + the commit's overlay


def test_commit_drops_the_arity_s_overtaken_siblings():
    """A support read from segments that are gone pins them: the next
    extraction at that arity drops such siblings (they could only miss)
    and leaves another arity's live entry alone."""
    from das_tpu.storage.atom_table import load_metta_text

    db = _small_store()
    member, interacts = db._type_id("Member"), db._type_id("Interacts")
    for tid in (member, interacts):
        starcount._table_sparse(db, (2, tid, 0, ()))
    load_metta_text(
        '(: "GENE:NEW" Gene)\n(Interacts "GENE:NEW" "GENE:NEW")', db.data
    )
    db.refresh()
    starcount._table_sparse(db, (2, member, 1, ()))
    assert list(starcount._table_cache(db)) == [(2, member, 1)]
