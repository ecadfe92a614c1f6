"""Cost-based whole-plan query planner (ISSUE 8; marker `planner`,
standalone via `ops/pytests.sh planner`).

Pins, in order of load-bearing-ness:

  * BIT-IDENTICAL answers planner-vs-greedy on the bio query suite —
    analytic 3-var, grounded conjunctions, Or/negation trees, and a
    sharded mesh tenant (the planner chooses among orders the executors
    already accept; a planner bug may cost time, never answers);
  * the acceptance case: the costed initial capacity settles a query in
    retry round 0 where greedy pays a capacity retry — STRICTLY fewer
    compiled programs than greedy on the same query (every avoided
    retry tier is an XLA compile saved);
  * the `_join_cap_seed` clamp fix: an operator-shrunk
    initial_result_capacity can no longer clamp the join seed below the
    exact grounded row counts (the guaranteed-retry bug), planner OFF;
  * estimator invalidation on commit: statistics rebuild under
    delta_version exactly like the result caches;
  * DL002 sig-completeness for the new `planned` signature field, and
    the explain/telemetry surface.

Compile-budget note: KBs are small, each arm compiles a handful of
fused shapes at serving-scale capacities.
"""

import dataclasses

import pytest

from das_tpu import planner
from das_tpu.api.atomspace import DistributedAtomSpace
from das_tpu.core.config import DasConfig
from das_tpu.models.bio import build_bio_atomspace
from das_tpu.ops import counters
from das_tpu.planner.stats import estimator_for
from das_tpu.query import compiler, fused
from das_tpu.query.ast import And, Link, Node, Not, Or, Variable
from das_tpu.storage.tensor_db import TensorDB

pytestmark = pytest.mark.planner


def _bio_data(**kw):
    data, genes, procs = build_bio_atomspace(**kw)
    return data, genes, procs


def _tensor_das(data, config, monkeypatch):
    # CapStore off: learned capacities persisted by an earlier run (or
    # the other arm) would pre-seed the retry ladder and blind the pins
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    db = TensorDB(data, config)
    return DistributedAtomSpace(database_name="zplan", db=db), db


def _sharded_das(data, config, monkeypatch):
    from das_tpu.parallel.sharded_db import ShardedDB

    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    db = ShardedDB(data, config)
    return DistributedAtomSpace(database_name="zplans", db=db), db


def _three_var():
    return And([
        Link("Member", [Variable("V1"), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Interacts", [Variable("V1"), Variable("V2")], True),
    ])


def _grounded(gene):
    return And([
        Link("Member", [Node("Gene", gene), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Interacts", [Node("Gene", gene), Variable("V2")], True),
    ])


def _negated(gene):
    return And([
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Member", [Node("Gene", gene), Variable("V3")], True),
        Not(Link("Interacts", [Node("Gene", gene), Variable("V2")], True)),
    ])


def _or_tree(g1, g2):
    return Or([
        And([
            Link("Member", [Node("Gene", g1), Variable("V3")], True),
            Link("Member", [Variable("V2"), Variable("V3")], True),
        ]),
        And([
            Link("Member", [Node("Gene", g2), Variable("V3")], True),
            Link("Member", [Variable("V2"), Variable("V3")], True),
        ]),
    ])


# -- bit-identical answers planner-vs-greedy -----------------------------


def _suite(names):
    return [
        _three_var(),
        _grounded(names[0]),
        _negated(names[1]),
        _or_tree(names[0], names[2]),
    ]


def _gene_names(db, n):
    return db.get_all_nodes("Gene", names=True)[:n]


def test_planner_vs_greedy_bit_identical_tensor(monkeypatch):
    data, _, _ = _bio_data(
        n_genes=60, n_processes=15, members_per_gene=4, n_interactions=80,
        seed=7,
    )
    das_on, db_on = _tensor_das(
        data, DasConfig(use_planner="on"), monkeypatch
    )
    das_off, db_off = _tensor_das(
        data, DasConfig(use_planner="off"), monkeypatch
    )
    names = _gene_names(db_on, 3)
    for q in _suite(names):
        m_on, a_on = das_on.query_answer(q)
        m_off, a_off = das_off.query_answer(q)
        assert m_on == m_off
        assert a_on.assignments == a_off.assignments, q
        assert a_on.negation == a_off.negation
    # the conjunctions actually took the planner (trees plan per site)
    assert planner.PLANNER_COUNTS["planned"] >= 1


def test_planner_vs_greedy_bit_identical_sharded(monkeypatch):
    data, _, _ = _bio_data(
        n_genes=60, n_processes=15, members_per_gene=4, n_interactions=80,
        seed=7,
    )
    das_on, db_on = _sharded_das(
        data, DasConfig(use_planner="on"), monkeypatch
    )
    das_off, _db_off = _sharded_das(
        data, DasConfig(use_planner="off"), monkeypatch
    )
    names = _gene_names(db_on, 3)
    for q in _suite(names):
        m_on, a_on = das_on.query_answer(q)
        m_off, a_off = das_off.query_answer(q)
        assert m_on == m_off
        assert a_on.assignments == a_off.assignments, q
        assert a_on.negation == a_off.negation


def test_planner_count_parity(monkeypatch):
    """count_matches rides the same executors; counts must agree."""
    data, _, _ = _bio_data(
        n_genes=60, n_processes=15, members_per_gene=4, n_interactions=80,
        seed=7,
    )
    _das_on, db_on = _tensor_das(
        data, DasConfig(use_planner="on"), monkeypatch
    )
    _das_off, db_off = _tensor_das(
        data, DasConfig(use_planner="off"), monkeypatch
    )
    q = _three_var()
    assert compiler.count_matches(db_on, q) == compiler.count_matches(
        db_off, q
    )


# -- the acceptance pin: costed capacity kills a retry round -------------


def _fanout_kb():
    """32 genes x 50 memberships over 100 processes: a grounded probe of
    one process holds ~16 rows, but joining back through Member fans out
    to ~16*50 = ~800 rows — an order of magnitude past greedy's
    max(64, min(init, 4*mg), mg) seed, and almost exactly the
    independence estimate rows_L * |Member| / max(dv) = 16 * 1600 / 32."""
    return _bio_data(
        n_genes=32, n_processes=100, members_per_gene=50,
        n_interactions=0, seed=3,
    )


def _fanout_query(db):
    proc = db.get_all_nodes("BiologicalProcess", names=True)[0]
    return And([
        Link("Member", [Variable("G"), Node("BiologicalProcess", proc)], True),
        Link("Member", [Variable("G"), Variable("P2")], True),
    ])


def test_costed_capacity_settles_round0_greedy_retries(monkeypatch):
    data, _, _ = _fanout_kb()
    das_off, db_off = _tensor_das(
        data, DasConfig(use_planner="off"), monkeypatch
    )
    q = _fanout_query(db_off)
    counters.reset_dispatch_counts()
    off_answer = das_off.query(q)
    greedy_programs = counters.DISPATCH_COUNTS["fused"]
    assert greedy_programs >= 2, (
        "greedy was expected to pay a capacity retry on this shape; "
        f"dispatches={counters.DISPATCH_COUNTS}"
    )

    das_on, db_on = _tensor_das(
        data, DasConfig(use_planner="on"), monkeypatch
    )
    planner.reset_planner_counts()
    counters.reset_dispatch_counts()
    on_answer = das_on.query(q)
    planner_programs = counters.DISPATCH_COUNTS["fused"]
    assert planner_programs == 1, counters.DISPATCH_COUNTS
    assert planner_programs < greedy_programs  # the acceptance criterion
    assert planner.PLANNER_COUNTS["round0"] >= 1
    assert planner.PLANNER_COUNTS["retries"] == 0
    assert on_answer == off_answer  # same bindings, fewer programs


# -- the _join_cap_seed clamp fix (planner OFF) --------------------------


def test_shrunk_capacity_config_no_guaranteed_retry(monkeypatch):
    """ISSUE 8 satellite: `max(64, min(initial_result_capacity, 4*mg))`
    clamped the join seed to 64 when an operator shrank the configured
    capacity — below the EXACT grounded row count mg, a guaranteed
    retry round.  The seed now folds the per-term estimate's bound in:
    seed >= mg, so this query settles in ONE program."""
    data, _, _ = _bio_data(
        n_genes=100, n_processes=1, members_per_gene=1,
        n_interactions=40, seed=5,
    )
    cfg = DasConfig(use_planner="off", initial_result_capacity=64)
    das, db = _tensor_das(data, cfg, monkeypatch)
    proc = db.get_all_nodes("BiologicalProcess", names=True)[0]
    q = And([
        Link("Member", [Variable("G"), Node("BiologicalProcess", proc)], True),
        Link("Interacts", [Variable("G"), Variable("H")], True),
    ])
    plans = compiler.plan_query(db, q)
    ex = fused.get_executor(db)
    grounded_rows = ex._estimate(plans[0])
    assert grounded_rows > cfg.initial_result_capacity  # the bug setup
    term_caps = tuple(fused._pow2_at_least(ex._estimate(p)) for p in plans)
    seed = ex._join_cap_seed(plans, term_caps)
    assert seed >= grounded_rows, (
        "the configured clamp must not force a seed below the exact "
        f"grounded rows: seed={seed} rows={grounded_rows}"
    )
    counters.reset_dispatch_counts()
    das.query(q)
    assert counters.DISPATCH_COUNTS["fused"] == 1, counters.DISPATCH_COUNTS


# -- estimator invalidation on commit ------------------------------------


def test_estimator_invalidates_on_commit(monkeypatch):
    data, _, _ = _bio_data(
        n_genes=20, n_processes=5, members_per_gene=2, n_interactions=10,
        seed=11,
    )
    das, db = _tensor_das(data, DasConfig(), monkeypatch)
    q = _three_var()
    plans = compiler.plan_query(db, q)
    est = estimator_for(db)
    member_rows = est.rows(plans[0])
    assert member_rows == 40  # 20 genes x 2 memberships
    dv = est.distinct_at(plans[0].arity, plans[0].type_id,
                         plans[0].var_cols[0])
    assert 0 < dv <= 20

    # commit two new memberships for a brand-new gene: delta_version
    # bumps, the estimator rebuilds, and both statistics move
    procs = db.get_all_nodes("BiologicalProcess", names=True)[:2]
    das.load_metta_text(
        '(: "GENE:NEW" Gene)\n'
        # re-declaring existing terminals is idempotent (content-
        # addressed); the parser needs them in scope for the new links
        + "".join(f'(: "{p}" BiologicalProcess)\n' for p in procs)
        + "".join(f'(Member "GENE:NEW" "{p}")\n' for p in procs)
    )
    est2 = estimator_for(db)
    assert est2 is not est, "estimator must rebuild on commit"
    assert est2.rows(compiler.plan_query(db, q)[0]) == member_rows + 2
    assert est2.distinct_at(
        plans[0].arity, plans[0].type_id, plans[0].var_cols[0]
    ) == dv + 1
    # same version -> same estimator object (statistics are memoized)
    assert estimator_for(db) is est2


# -- DL002 sig-completeness for the planner fields -----------------------


def test_planned_field_in_plan_signatures():
    from das_tpu.parallel.fused_sharded import ShardedPlanSig

    f_names = [f.name for f in dataclasses.fields(fused.FusedPlanSig)]
    s_names = [f.name for f in dataclasses.fields(ShardedPlanSig)]
    assert "planned" in f_names
    assert "planned" in s_names
    # a costed choice is part of the cache key: planner and greedy
    # executables for the same order/caps must cache side by side
    a = fused.FusedPlanSig((), (), (), planned=True)
    b = fused.FusedPlanSig((), (), (), planned=False)
    assert a != b and hash(a) != hash(b)


def test_planner_sig_fields_pass_dl002_and_dl008():
    from pathlib import Path

    from das_tpu.analysis import run_analysis

    repo = Path(__file__).resolve().parent.parent
    findings = run_analysis(
        [repo / "das_tpu"], rules=["DL002", "DL008"],
        tests_dir=repo / "tests",
    )
    assert not findings, "\n".join(f.render() for f in findings)


# -- explain + telemetry surface -----------------------------------------


def test_explain_estimates_vs_actuals(monkeypatch):
    data, _, _ = _fanout_kb()
    das, db = _tensor_das(data, DasConfig(), monkeypatch)
    q = _fanout_query(db)
    out = das.explain(q, execute=True)
    assert out["planned"] is True
    assert out["route"] == "fused"
    assert out["method"] in ("ref_order", "dp", "greedy_tail")
    assert len(out["order"]) == 2
    assert len(out["est_join_rows"]) == 1
    assert out["join_cap_seeds"][0] >= out["est_join_rows"][0]
    actual = out["actual"]
    assert actual["retry_rounds"] == 0
    assert actual["count"] == actual["join_rows"][0] > 0
    # the independence estimate is exact on this uniform KB shape
    est, act = out["est_join_rows"][0], actual["join_rows"][0]
    assert act / 2 <= est <= act * 2, (est, act)


def test_explain_tree_reports_sites(monkeypatch):
    data, _, _ = _bio_data(
        n_genes=20, n_processes=5, members_per_gene=2, n_interactions=10,
        seed=11,
    )
    das, db = _tensor_das(data, DasConfig(), monkeypatch)
    names = _gene_names(db, 3)
    # the homogeneous Or now renders the WHOLE-TREE fused plan (ISSUE
    # 10): site order, union/anti placement, per-branch est rows
    out = das.explain(_or_tree(names[0], names[2]))
    assert out["route"] == "fused_tree"
    assert out["tree_fused"] is True
    assert len(out["sites"]) == 2
    assert out["union_after"] == 2
    assert out["anti_after_union"] is False
    assert len(out["est_site_rows"]) == 2
    for s in out["sites"]:
        assert s["route"] == "fused"
        if s["planned"]:
            assert "est_term_rows" in s
    # with fusion off the per-site tree rendering survives unchanged
    das_off, db_off = _tensor_das(
        data, DasConfig(use_tree_fusion="off"), monkeypatch
    )
    out_off = das_off.explain(_or_tree(names[0], names[2]))
    assert out_off["route"] == "tree"
    assert len(out_off["sites"]) == 2


def test_planner_snapshot_in_service_stats(monkeypatch):
    from das_tpu.service.server import DasService

    data, _, _ = _bio_data(
        n_genes=20, n_processes=5, members_per_gene=2, n_interactions=10,
        seed=11,
    )
    das, _db = _tensor_das(data, DasConfig(), monkeypatch)
    planner.reset_planner_counts()
    das.query(_three_var())
    service = DasService()
    service.attach_tenant("zplan", das)
    stats = service.coalescer_stats()
    assert "planner" in stats
    assert stats["planner"]["planned"] >= 1
    assert "actual_vs_est_ratio" in stats["planner"]


def test_exact_dot_keys_on_probed_position(monkeypatch):
    """Review regression: two same-shaped leaves sharing a variable at
    DIFFERENT positions have different supports — the degree-dot memo
    must not serve one term's product for the other (a falsely-'exact'
    figure would seed a margin-free capacity, i.e. a guaranteed retry,
    or corrupt the est-vs-actual telemetry)."""
    data, _, _ = _bio_data(
        n_genes=20, n_processes=5, members_per_gene=2, n_interactions=30,
        seed=11,
    )
    _das, db = _tensor_das(data, DasConfig(), monkeypatch)
    q = And([
        # B at position 0 of one Member leaf, position 1 of the other
        Link("Member", [Variable("B"), Variable("P")], True),
        Link("Member", [Variable("G"), Variable("B")], True),
        Link("Interacts", [Variable("B"), Variable("X")], True),
    ])
    plans = compiler.plan_query(db, q)
    est = estimator_for(db)
    first = est.exact_join_rows(plans[0], plans[2], "B")
    second = est.exact_join_rows(plans[1], plans[2], "B")
    fresh = estimator_for(db.__class__(data, DasConfig()))
    assert first == fresh.exact_join_rows(plans[0], plans[2], "B")
    assert second == fresh.exact_join_rows(plans[1], plans[2], "B")
    # Member targets genes at pos 0 and processes at pos 1; Interacts
    # targets genes — the two dots MUST differ (pos-1 support is
    # process rows, disjoint from gene rows)
    assert first != second
    assert second == 0


def test_method_counters_decompose_planned_traffic(monkeypatch):
    """Review regression: explain() plans too, but the planned/method
    decomposition must cover EXECUTOR traffic only — after any mix of
    queries and explains, dp + greedy_tail + ref_order == planned."""
    data, _, _ = _bio_data(
        n_genes=20, n_processes=5, members_per_gene=2, n_interactions=10,
        seed=11,
    )
    das, _db = _tensor_das(data, DasConfig(), monkeypatch)
    planner.reset_planner_counts()
    das.explain(_three_var())
    c = planner.PLANNER_COUNTS
    assert c["planned"] == 0
    assert c["dp"] + c["greedy_tail"] + c["ref_order"] == 0
    assert c["explain"] == 1
    das.query(_three_var())
    das.query(_grounded(_gene_names(_db, 1)[0]))
    c = planner.PLANNER_COUNTS
    assert c["planned"] == 2
    assert c["dp"] + c["greedy_tail"] + c["ref_order"] == c["planned"]


def test_declined_jobs_not_counted_as_planned(monkeypatch):
    """Review regression: _exec_job can still decline AFTER planning
    (capacity ceiling, missing bucket) — the legacy fallback answers,
    and the planned/greedy counters must not credit a job that never
    existed (observe_settle would never complete the decomposition)."""
    data, _, _ = _bio_data(
        n_genes=20, n_processes=5, members_per_gene=2, n_interactions=10,
        seed=11,
    )
    # ceiling below every term capacity: the fused executor must decline
    _das, db = _tensor_das(
        data, DasConfig(max_result_capacity=32), monkeypatch
    )
    plans = compiler.plan_query(db, _three_var())
    planner.reset_planner_counts()
    ex = fused.get_executor(db)
    assert ex._exec_job(list(plans), False) is None
    c = planner.PLANNER_COUNTS
    assert c["planned"] == 0 and c["greedy"] == 0
    assert c["dp"] + c["greedy_tail"] + c["ref_order"] == 0


def test_planner_dp_orders_disconnected_declines(monkeypatch):
    """Disconnected conjunctions (cross products) stay with the legacy
    ordering — the planner declines rather than price cross products."""
    data, _, _ = _bio_data(
        n_genes=20, n_processes=5, members_per_gene=2, n_interactions=10,
        seed=11,
    )
    _das, db = _tensor_das(data, DasConfig(), monkeypatch)
    q = And([
        Link("Member", [Variable("A"), Variable("B")], True),
        Link("Interacts", [Variable("C"), Variable("D")], True),
    ])
    plans = compiler.plan_query(db, q)
    assert planner.plan_conjunction(db, plans) is None


def test_dp_max_env_clamps_search(monkeypatch):
    from das_tpu.planner import search

    monkeypatch.setenv("DAS_TPU_PLANNER_DP_MAX", "2")
    assert search.dp_max() == 2
    monkeypatch.setenv("DAS_TPU_PLANNER_DP_MAX", "bogus")
    assert search.dp_max() == search.DEFAULT_DP_MAX
    monkeypatch.delenv("DAS_TPU_PLANNER_DP_MAX")
    assert search.dp_max() == search.DEFAULT_DP_MAX


# -- star prefixes: the chain's deeper seeds are the exact k-way figure --


def _star3():
    return And([
        Link("Member", [Variable("V1"), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Member", [Variable("V4"), Variable("V3")], True),
    ])


def _skew_kb():
    """120 genes x 3 memberships over 40 processes at skew 1.1: hub
    processes own degrees far above the median.  The chain's FIRST
    intermediate seeds exactly (pairwise degree dot), but under the
    independence model its SECOND would not — Σ deg³ concentrates on
    the hubs far past est × CAP_MARGIN, a guaranteed retry tier."""
    data, _g, _p = build_bio_atomspace(
        n_genes=120, n_processes=40, members_per_gene=3,
        n_interactions=0, seed=17, skew=1.1,
    )
    return data


def _star_env(monkeypatch):
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    monkeypatch.delenv("DAS_TPU_PLANNER", raising=False)


def test_chain_star_seeds_settle_round0(monkeypatch):
    """The deeper star-prefix intermediates of a chain seed from the
    exact `stats.star_rows` k-way statistic instead of the independence
    model: the skew-heavy star settles in ONE program, where the blind
    legacy seeds (planner off) pay a retry tier."""
    _star_env(monkeypatch)
    # off the closed-form star counter: the executors' capacities (the
    # thing under test) only engage on the fused count path
    monkeypatch.setenv("DAS_TPU_STAR", "0")
    data = _skew_kb()
    q = _star3()

    db_blind = TensorDB(data, DasConfig(use_planner="off"))
    counters.reset_dispatch_counts()
    n_blind = compiler.count_matches(db_blind, q)
    assert counters.DISPATCH_COUNTS["fused"] >= 2, (
        "the blind seeds were expected to pay a capacity-retry tier on "
        f"this skew shape; dispatches={counters.DISPATCH_COUNTS}"
    )

    db = TensorDB(data, DasConfig())
    plans = compiler.plan_query(db, q)
    exact_rows, exact = estimator_for(db).star_rows(plans, "V3")
    assert exact
    planned = planner.plan_conjunction(db, plans)
    assert planned is not None and planned.route == "fused"
    # the DEEPER seed (second intermediate) bounds the exact k-way
    # figure — the independence model sat far under it on this skew
    assert planned.join_cap_seeds[1] >= exact_rows
    assert planned.est_join_rows[1] == int(exact_rows)

    planner.reset_planner_counts()
    counters.reset_dispatch_counts()
    assert compiler.count_matches(db, q) == n_blind
    assert counters.DISPATCH_COUNTS["fused"] == 1, counters.DISPATCH_COUNTS
    assert planner.PLANNER_COUNTS["round0"] >= 1
    assert planner.PLANNER_COUNTS["retries"] == 0
    assert planner.snapshot()["actual_vs_est_ratio"] == 1.0


def test_shrunk_capacity_cannot_clamp_star_seed(monkeypatch):
    """An operator-shrunk initial_result_capacity must not clamp a star
    prefix's seeds below the exact k-way intersection bound
    (stats.star_rows) — that would be a GUARANTEED retry round, the
    bug class the PR-8 `_join_cap_seed` fix closed for binary joins."""
    _star_env(monkeypatch)
    data, _g, _p = _bio_data(
        n_genes=50, n_processes=10, members_per_gene=3, n_interactions=0,
        seed=5,
    )
    cfg = DasConfig(initial_result_capacity=64)
    db = TensorDB(data, cfg)
    das = DistributedAtomSpace(database_name="zstar_seed", db=db)
    q = _star3()
    plans = compiler.plan_query(db, q)
    exact_rows, exact = estimator_for(db).star_rows(plans, "V3")
    assert exact and exact_rows > cfg.initial_result_capacity  # bug setup
    planned = planner.plan_conjunction(db, plans)
    assert planned is not None
    assert planned.join_cap_seeds[-1] >= exact_rows, (
        "the configured clamp must not force the star seed under the "
        f"exact bound: seed={planned.join_cap_seeds[-1]} rows={exact_rows}"
    )
    counters.reset_dispatch_counts()
    das.query(q)
    assert counters.DISPATCH_COUNTS["fused"] == 1, counters.DISPATCH_COUNTS


def test_star_rows_exact_vs_brute_force(monkeypatch):
    """stats.star_rows == the brute-force Σ_v Π_j deg_j(v) over the
    support intersection, memoized on the second call."""
    from collections import Counter

    import numpy as np

    from das_tpu.storage.atom_table import host_segments

    _star_env(monkeypatch)
    data, _g, _p = _bio_data(
        n_genes=40, n_processes=12, members_per_gene=3, n_interactions=0,
        seed=9,
    )
    db = TensorDB(data, DasConfig())
    plans = compiler.plan_query(db, _star3())
    est = estimator_for(db)
    rows, exact = est.star_rows(plans, "V3")
    assert exact
    deg = Counter()
    p0 = plans[0]
    vcol = p0.var_cols[p0.var_names.index("V3")]
    for b in host_segments(db, p0.arity):
        lo = int(np.searchsorted(b.key_type, np.int32(p0.type_id), "left"))
        hi = int(np.searchsorted(b.key_type, np.int32(p0.type_id), "right"))
        for r in np.asarray(b.order_by_type[lo:hi]):
            deg[int(b.targets[r, vcol])] += 1
    assert int(rows) == sum(d ** 3 for d in deg.values())
    assert est.star_rows(plans, "V3") == (rows, True)


def test_exact_join_rows_reads_each_table_once(monkeypatch):
    """400 distinct grounded terms joined against the whole-table side
    of their link type (the serving shapes: one new grounded support a
    query, enough to turn the FIFO of grounded supports over twice):
    every value equals a COLD store's, and whole-table supports were
    extracted once per distinct (type, position), two in all."""
    from das_tpu import obs
    from das_tpu.query import starcount

    data, _g, _p = _bio_data(
        n_genes=400, n_processes=12, members_per_gene=3, n_interactions=300,
        seed=13,
    )
    db = TensorDB(data, DasConfig())
    cold = TensorDB(data, DasConfig())
    genes = _gene_names(db, 400)
    assert len(set(genes)) == 400

    def shape(gene, link):
        return And([
            Link(link, [Node("Gene", gene), Variable("V3")], True),
            Link(link, [Variable("V2"), Variable("V3")], True),
        ])

    was = obs.enabled()
    obs.configure(enabled=True)
    try:
        built = obs.counter("planner.table_extractions")
        served = obs.counter("planner.table_hits")
        built0, served0 = built.value, served.value
        values = []
        for i, gene in enumerate(genes):
            q = shape(gene, ("Member", "Interacts")[i % 2])
            pa, pb = compiler.plan_query(db, q)
            values.append(estimator_for(db).exact_join_rows(pa, pb, "V3"))
        assert (built.value - built0, served.value - served0) == (2, 398)
        assert len(starcount._table_cache(db)) == 2
        assert len(starcount._host_cache(db)) <= 257
    finally:
        obs.configure(enabled=was)
    assert sum(v > 0 for v in values) >= 200
    for i, gene in enumerate(genes):
        # a cold store: no estimator, no support of either class
        for kept in ("_planner_estimator", "_star_table_cache",
                     "_star_host_cache"):
            cold.__dict__.pop(kept, None)
        q = shape(gene, ("Member", "Interacts")[i % 2])
        pa, pb = compiler.plan_query(cold, q)
        assert values[i] == estimator_for(cold).exact_join_rows(pa, pb, "V3")
