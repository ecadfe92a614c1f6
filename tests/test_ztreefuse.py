"""Whole-tree fused execution (ISSUE 10; marker `treefuse`, standalone
via `ops/pytests.sh treefuse`).

Pins, in order of load-bearing-ness:

  * BIT-IDENTICAL assignment sets fused-tree vs the tree executor on
    the bio Or/negation suite — positive unions, 3-branch Ors, the
    de-Morgan difference branch, nested positive Ors — on the
    single-device executor AND the sharded mesh (the host-set dedup
    semantics contract: a fused-tree bug may cost a fallback, never
    answers);
  * the acceptance pin: an eligible 3-branch Or executes in ONE device
    program on the fused-tree route where the tree executor dispatches
    one fused program per site (DISPATCH_COUNTS asserted both arms);
  * fallback-to-tree-executor on shapes outside the homogeneous subset
    (unordered links, heterogeneous variable universes) — answered
    correctly with ZERO fused_tree dispatches;
  * cache-hit 0-dispatch on the fused-tree `tree_results` entry and
    exact invalidation on commit (the delta_version guard);
  * FusedTreeSig / ShardedTreeSig field distinctness (cache-key
    honesty, the DL002 contract).

Compile-budget note: KBs are small; each arm compiles a handful of
fused shapes at serving-scale capacities.
"""

import dataclasses

import pytest

from das_tpu.ops import counters
from das_tpu.api.atomspace import DistributedAtomSpace
from das_tpu.core.config import DasConfig
from das_tpu.models.bio import build_bio_atomspace
from das_tpu.query.ast import And, Link, Node, Not, Or, Variable
from das_tpu.storage.tensor_db import TensorDB

pytestmark = pytest.mark.treefuse


def _bio_data(**kw):
    data, _genes, _procs = build_bio_atomspace(**kw)
    return data


def _tensor_das(data, config, monkeypatch, tag="ztf"):
    # CapStore off: learned capacities persisted by an earlier run (or
    # the other arm) would pre-seed the retry ladder and blind the pins
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    monkeypatch.delenv("DAS_TPU_TREE_FUSION", raising=False)
    db = TensorDB(data, config)
    return DistributedAtomSpace(database_name=tag, db=db), db


def _sharded_das(data, config, monkeypatch, tag="ztfs"):
    from das_tpu.parallel.sharded_db import ShardedDB

    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    monkeypatch.delenv("DAS_TPU_TREE_FUSION", raising=False)
    db = ShardedDB(data, config)
    return DistributedAtomSpace(database_name=tag, db=db), db


def _branch(gene):
    return And([
        Link("Member", [Node("Gene", gene), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
    ])


def _suite(names):
    return [
        # plain 2-branch union
        Or([_branch(names[0]), _branch(names[2])]),
        # 3-branch union (the acceptance shape)
        Or([_branch(g) for g in names]),
        # single-term branches sharing the universe with a conjunction
        Or([
            _branch(names[0]),
            And([
                Link("Member", [Node("Gene", names[1]), Variable("V3")], True),
                Link("Member", [Variable("V2"), Variable("V3")], True),
            ]),
        ]),
        # the de-Morgan difference branch (joint negative minus union)
        Or([_branch(names[0]), Not(_branch(names[1]))]),
        Or([_branch(names[0]), _branch(names[2]), Not(_branch(names[1]))]),
        # nested positive Or flattens into the same union
        Or([_branch(names[0]), Or([_branch(names[1]), _branch(names[2])])]),
        # in-branch negated term (anti-join inside one site)
        Or([
            _branch(names[0]),
            And([
                Link("Member", [Node("Gene", names[1]), Variable("V3")], True),
                Link("Member", [Variable("V2"), Variable("V3")], True),
                Not(Link("Interacts",
                         [Node("Gene", names[1]), Variable("V2")], True)),
            ]),
        ]),
    ]


def _kb():
    return _bio_data(
        n_genes=60, n_processes=15, members_per_gene=4, n_interactions=80,
        seed=7,
    )


# -- bit-identical answers fused-tree vs the tree executor ---------------


def test_tree_fused_bit_identical_tensor(monkeypatch):
    data = _kb()
    das_on, db_on = _tensor_das(
        data, DasConfig(use_tree_fusion="on"), monkeypatch, "ztf_on"
    )
    das_off, _db = _tensor_das(
        data, DasConfig(use_tree_fusion="off"), monkeypatch, "ztf_off"
    )
    names = db_on.get_all_nodes("Gene", names=True)[:3]
    fused_answers = 0
    for q in _suite(names):
        counters.reset_dispatch_counts()
        m_on, a_on = das_on.query_answer(q)
        fused_answers += counters.DISPATCH_COUNTS["fused_tree"]
        m_off, a_off = das_off.query_answer(q)
        assert m_on == m_off
        assert a_on.assignments == a_off.assignments, q
        assert a_on.negation == a_off.negation
    # no silent fallback across the suite: every shape above is in the
    # homogeneous subset and must actually ride the fused route
    assert fused_answers >= len(_suite(names))


def test_tree_fused_bit_identical_sharded(monkeypatch):
    data = _kb()
    das_on, db_on = _sharded_das(
        data, DasConfig(use_tree_fusion="on"), monkeypatch, "ztfs_on"
    )
    das_off, _db = _sharded_das(
        data, DasConfig(use_tree_fusion="off"), monkeypatch, "ztfs_off"
    )
    names = db_on.get_all_nodes("Gene", names=True)[:3]
    fused_answers = 0
    for q in _suite(names):
        counters.reset_dispatch_counts()
        m_on, a_on = das_on.query_answer(q)
        fused_answers += counters.DISPATCH_COUNTS["sharded_tree_fused"]
        m_off, a_off = das_off.query_answer(q)
        assert m_on == m_off
        assert a_on.assignments == a_off.assignments, q
        assert a_on.negation == a_off.negation
    assert fused_answers >= len(_suite(names))


# -- the acceptance pin: one program where the tree executor pays >= N ---


def test_three_branch_or_one_program(monkeypatch):
    data = _kb()
    das_off, db_off = _tensor_das(
        data, DasConfig(use_tree_fusion="off"), monkeypatch, "ztf3_off"
    )
    names = db_off.get_all_nodes("Gene", names=True)[:3]
    q = Or([_branch(g) for g in names])
    counters.reset_dispatch_counts()
    m_off, a_off = das_off.query_answer(q)
    tree_programs = counters.DISPATCH_COUNTS["fused"]
    assert tree_programs >= 3, (
        "the tree executor pays one fused program per Or branch; "
        f"dispatches={counters.DISPATCH_COUNTS}"
    )

    das_on, _db = _tensor_das(
        data, DasConfig(use_tree_fusion="on"), monkeypatch, "ztf3_on"
    )
    from das_tpu.query import compiler as qc

    qc.reset_route_counts()
    counters.reset_dispatch_counts()
    m_on, a_on = das_on.query_answer(q)
    assert counters.DISPATCH_COUNTS["fused_tree"] == 1, (
        counters.DISPATCH_COUNTS
    )
    assert counters.DISPATCH_COUNTS["fused"] == 0  # no per-site programs
    assert 1 < tree_programs  # the acceptance criterion
    assert m_on == m_off and a_on.assignments == a_off.assignments
    # per-ANSWER route telemetry: ONE fused_tree answer, and the site
    # jobs count no route of their own
    assert qc.ROUTE_COUNTS["fused_tree"] == 1
    assert qc.ROUTE_COUNTS["fused"] == 0


# -- fallback on shapes outside the homogeneous subset -------------------


def test_unordered_shapes_fall_back(monkeypatch, animals_data):
    """An Or carrying an unordered (Similarity) branch is outside the
    homogeneous subset: the tree executor must answer (zero fused_tree
    dispatches), identically to the fusion-off arm."""
    das_on, _db = _tensor_das(
        animals_data, DasConfig(use_tree_fusion="on"), monkeypatch,
        "ztf_u_on",
    )
    das_off, _db2 = _tensor_das(
        animals_data, DasConfig(use_tree_fusion="off"), monkeypatch,
        "ztf_u_off",
    )
    q = Or([
        And([
            Link("Inheritance", [Node("Concept", "human"), Variable("V1")],
                 True),
            Link("Inheritance", [Variable("V2"), Variable("V1")], True),
        ]),
        Link("Similarity", [Node("Concept", "human"), Variable("V1")],
             False),
    ])
    counters.reset_dispatch_counts()
    m_on, a_on = das_on.query_answer(q)
    assert counters.DISPATCH_COUNTS["fused_tree"] == 0
    m_off, a_off = das_off.query_answer(q)
    assert m_on == m_off
    assert a_on.assignments == a_off.assignments


def test_heterogeneous_universe_falls_back(monkeypatch):
    """Branches binding DIFFERENT variable sets keep separate CTable
    groups in the tree executor — outside the shared-universe subset."""
    data = _kb()
    das_on, db_on = _tensor_das(
        data, DasConfig(use_tree_fusion="on"), monkeypatch, "ztf_h_on"
    )
    das_off, _db = _tensor_das(
        data, DasConfig(use_tree_fusion="off"), monkeypatch, "ztf_h_off"
    )
    names = db_on.get_all_nodes("Gene", names=True)[:2]
    q = Or([
        _branch(names[0]),  # binds {V2, V3}
        Link("Interacts", [Node("Gene", names[1]), Variable("V5")], True),
    ])
    counters.reset_dispatch_counts()
    m_on, a_on = das_on.query_answer(q)
    assert counters.DISPATCH_COUNTS["fused_tree"] == 0
    m_off, a_off = das_off.query_answer(q)
    assert m_on == m_off
    assert a_on.assignments == a_off.assignments


def test_sharded_tree_fallback_mode_gates_fusion(monkeypatch):
    """Review fix: sharded_tree_fallback="host" promises NO device tree
    programs — the fused-tree intercept must honor it (and "tensor"
    keeps the single-chip replica path, where the single-device fused
    tree applies instead)."""
    data = _kb()
    das, db = _sharded_das(
        data,
        DasConfig(use_tree_fusion="on", sharded_tree_fallback="host"),
        monkeypatch, "ztfs_host",
    )
    names = db.get_all_nodes("Gene", names=True)[:2]
    # a negated Or dodges the per-branch decomposition: in "host" mode
    # it must reach the host algebra with zero mesh tree programs
    q = Or([_branch(names[0]), Not(_branch(names[1]))])
    counters.reset_dispatch_counts()
    m, a = das.query_answer(q)
    assert counters.DISPATCH_COUNTS["sharded_tree_fused"] == 0, (
        counters.DISPATCH_COUNTS
    )
    das_mesh, _db2 = _sharded_das(
        data, DasConfig(use_tree_fusion="on"), monkeypatch, "ztfs_mesh"
    )
    m2, a2 = das_mesh.query_answer(q)
    assert m == m2 and a.assignments == a2.assignments


# -- cache: 0-dispatch hits, exact invalidation on commit ----------------


def test_tree_fused_cache_hit_and_commit_invalidation(monkeypatch):
    data = _kb()
    das, db = _tensor_das(
        data, DasConfig(use_tree_fusion="on"), monkeypatch, "ztf_cache"
    )
    names = db.get_all_nodes("Gene", names=True)[:3]
    q = Or([_branch(names[0]), Not(_branch(names[1]))])
    _m1, a1 = das.query_answer(q)
    counters.reset_dispatch_counts()
    _m2, a2 = das.query_answer(q)
    assert sum(counters.DISPATCH_COUNTS.values()) == 0, (
        "a fused-tree cache hit must issue ZERO device programs"
    )
    assert a2.assignments == a1.assignments
    assert a2.negation == a1.negation

    # commit: delta_version bumps, the entry is stale, the next query
    # re-dispatches and sees the new row
    procs = db.get_all_nodes("BiologicalProcess", names=True)[:1]
    das.load_metta_text(
        '(: "GENE:ZTF" Gene)\n'
        + f'(: "{procs[0]}" BiologicalProcess)\n'
        + f'(Member "GENE:ZTF" "{procs[0]}")\n'
    )
    counters.reset_dispatch_counts()
    _m3, a3 = das.query_answer(q)
    assert counters.DISPATCH_COUNTS["fused_tree"] >= 1, (
        "a commit must invalidate the fused-tree entry"
    )
    # parity against the tree executor on the post-commit store
    das_off, _db = _tensor_das(
        data, DasConfig(use_tree_fusion="off"), monkeypatch, "ztf_c_off"
    )
    das_off.load_metta_text(
        '(: "GENE:ZTF" Gene)\n'
        + f'(: "{procs[0]}" BiologicalProcess)\n'
        + f'(Member "GENE:ZTF" "{procs[0]}")\n'
    )
    _m4, a4 = das_off.query_answer(q)
    assert a3.assignments == a4.assignments


def test_declined_fused_tree_memoized(monkeypatch):
    """Review fix: a declined fused attempt (per-site reseed verdict or
    capacity ceiling) is memoized in `tree_results` for the current
    delta version — repeat queries skip straight to the staged tree
    executor (whose own cache answers with zero dispatches) instead of
    re-executing and discarding the whole fused program every time."""
    from das_tpu.query import fused as fused_mod

    data = _kb()
    das, db = _tensor_das(
        data, DasConfig(use_tree_fusion="on"), monkeypatch, "ztf_dec"
    )
    names = db.get_all_nodes("Gene", names=True)[:3]
    q = Or([_branch(g) for g in names])
    ex = fused_mod.get_executor(db)
    calls = {"n": 0}

    def declining(pos_sites, neg_plans=None):
        calls["n"] += 1
        return None

    monkeypatch.setattr(ex, "execute_tree", declining)
    m1, a1 = das.query_answer(q)  # fused declines -> tree executor answers
    m2, a2 = das.query_answer(q)  # memoized decline + staged cache hit
    assert calls["n"] == 1, "the decline must be memoized per delta version"
    assert m1 == m2 and a1.assignments == a2.assignments
    das_off, _db2 = _tensor_das(
        data, DasConfig(use_tree_fusion="off"), monkeypatch, "ztf_dec_off"
    )
    _m3, a3 = das_off.query_answer(q)
    assert a1.assignments == a3.assignments


def test_sharded_tree_fused_cache_hit(monkeypatch):
    data = _kb()
    das, db = _sharded_das(
        data, DasConfig(use_tree_fusion="on"), monkeypatch, "ztfs_cache"
    )
    names = db.get_all_nodes("Gene", names=True)[:3]
    q = Or([_branch(g) for g in names])
    das.query_answer(q)
    counters.reset_dispatch_counts()
    das.query_answer(q)
    assert sum(counters.DISPATCH_COUNTS.values()) == 0


# -- sig-field distinctness (cache-key honesty, DL002) -------------------


def test_tree_sig_field_distinctness():
    from das_tpu.parallel.fused_sharded import ShardedPlanSig, ShardedTreeSig
    from das_tpu.query.fused import FusedPlanSig, FusedTreeSig

    site_a = FusedPlanSig((), (16,), ())
    site_b = FusedPlanSig((), (32,), ())
    assert FusedTreeSig((site_a,)) != FusedTreeSig((site_b,))
    # a negative site is part of the key: union-only and difference
    # programs for the same positive sites must cache side by side
    assert FusedTreeSig((site_a,), None) != FusedTreeSig((site_a,), site_b)
    assert hash(FusedTreeSig((site_a,), None)) != hash(
        FusedTreeSig((site_a,), site_b)
    )
    s_site = ShardedPlanSig((), (16,), (), (), 8)
    s_site2 = ShardedPlanSig((), (32,), (), (), 8)
    assert ShardedTreeSig((s_site,)) != ShardedTreeSig((s_site2,))
    assert ShardedTreeSig((s_site,), None) != ShardedTreeSig(
        (s_site,), s_site2
    )
    # frozen: tree sigs are cache keys and must hash by value (DL002
    # pins the dataclass mechanics; this pins the field semantics)
    assert dataclasses.fields(FusedTreeSig)[0].name == "sites"
    assert dataclasses.fields(ShardedTreeSig)[0].name == "sites"
