"""One way to run a query (PR 31): the plan a CPU test checks is the plan
the chip executes.

Until PR 31 the planner resolved a routing option by PLATFORM: off a
TPU every star-shaped conjunction went to a k-way "multiway" program
that no chip ever ran.  These cases hold what replaced that:

  * planner and executors give one `PlannedProgram` and one plan
    signature whatever `jax.devices()[0].platform` says, and no module
    of the planning and executing layers reads the platform at all;
  * the star-shaped bio queries of the old multiway suite, now on the
    chain every platform runs, answer exactly as `MemoryDB` does — on
    `tensor` and on the mesh;
  * the dispatch-count pins: a fused conjunction is ONE program a
    query, the staged pipeline its 13 single-op launches.
"""

import ast
import types
from pathlib import Path

import jax
import pytest

from das_tpu import planner
from das_tpu.api.atomspace import DistributedAtomSpace
from das_tpu.core.config import DasConfig
from das_tpu.models.bio import build_bio_atomspace
from das_tpu.ops import counters
from das_tpu.query import compiler
from das_tpu.query.ast import And, Link, Node, Not, Variable
from das_tpu.storage.memory_db import MemoryDB
from das_tpu.storage.tensor_db import TensorDB
from tests.test_plan_identity import _executor

REPO = Path(__file__).resolve().parent.parent


def _bio_data(**kw):
    data, _g, _p = build_bio_atomspace(**kw)
    return data


@pytest.fixture(scope="module")
def star_data():
    return _bio_data(
        n_genes=60, n_processes=15, members_per_gene=4, n_interactions=80,
        seed=7,
    )


def _db(backend, data):
    if backend == "tensor":
        return TensorDB(data, DasConfig())
    from das_tpu.parallel.sharded_db import ShardedDB

    return ShardedDB(data, DasConfig())


def _member(a, b):
    return Link("Member", [a, b], True)


def _star_suite(db):
    """The old multiway suite's queries: a 3-clause star, the triangle
    whose first two clauses are a star on V3, and its grounded and
    negated variants."""
    v1, v2, v3, v4 = (Variable(n) for n in ("V1", "V2", "V3", "V4"))
    g0, g1 = (Node("Gene", g) for g in db.get_all_nodes("Gene", names=True)[:2])
    return [
        And([_member(v1, v3), _member(v2, v3), _member(v4, v3)]),
        And([_member(v1, v3), _member(v2, v3),
             Link("Interacts", [v1, v2], True)]),
        And([_member(g0, v3), _member(v2, v3),
             Link("Interacts", [g0, v2], True)]),
        And([_member(v2, v3), _member(g1, v3),
             Not(Link("Interacts", [g1, v2], True))]),
    ]


# -- one platform, one plan ----------------------------------------------


@pytest.mark.parametrize("backend", ["tensor", "sharded"])
def test_one_platform_one_plan(backend, star_data, monkeypatch):
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    db = _db(backend, star_data)
    ex, n_shards = _executor(db)
    seen = {}
    for platform in ("tpu", "cpu"):
        monkeypatch.setattr(
            jax, "devices",
            lambda *a, _p=platform: [types.SimpleNamespace(platform=_p)],
        )
        out = []
        for q in _star_suite(db):
            plans = compiler.plan_query(db, q)
            out.append((
                planner.plan_conjunction(db, list(plans), n_shards=n_shards),
                ex._exec_job(list(plans), False).plan_sig(),
            ))
        seen[platform] = out
    assert seen["tpu"] == seen["cpu"]
    for planned, sig in seen["tpu"]:
        assert planned.route == ("sharded" if n_shards > 1 else "fused")
        assert sig.planned and len(sig.join_caps) == len(
            [t for t in sig.terms if not t.negated]) - 1


def test_planning_and_executing_layers_read_no_platform():
    """No module that plans or executes may ask which platform it is on:
    a plan chosen by platform is a plan tier-1 cannot check."""
    offenders = []
    for layer in ("planner", "query", "parallel", "ops"):
        for path in sorted((REPO / "das_tpu" / layer).rglob("*.py")):
            for node in ast.walk(ast.parse(path.read_text())):
                name = getattr(node, "attr", getattr(node, "id", None))
                if name in ("platform", "default_backend", "device_kind"):
                    offenders.append(f"{path.relative_to(REPO)}:{node.lineno}")
    assert not offenders, offenders


# -- the star queries answer as the reference store does -----------------


@pytest.mark.parametrize("backend", ["tensor", "sharded"])
def test_star_queries_match_memory_db(backend, star_data, monkeypatch):
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    db = _db(backend, star_data)
    das = DistributedAtomSpace(database_name=f"one_{backend}", db=db)
    ref = DistributedAtomSpace(
        database_name="one_ref", db=MemoryDB(star_data))
    kind = "sharded" if backend == "sharded" else "fused"
    want = [ref.query_answer(q) for q in _star_suite(ref.db)]
    counters.reset_dispatch_counts()
    compiler.reset_route_counts()
    suite = _star_suite(db)
    for q_dev, (m_ref, a_ref) in zip(suite, want):
        m_dev, a_dev = das.query_answer(q_dev)
        assert m_dev == m_ref
        assert a_dev.assignments == a_ref.assignments, q_dev
        assert a_dev.negation == a_ref.negation
    # every one was answered by the whole-plan program, none fell back
    assert counters.DISPATCH_COUNTS[kind] >= len(suite)
    assert compiler.ROUTE_COUNTS[kind] == len(suite)
    assert compiler.ROUTE_COUNTS["staged"] == 0
    assert compiler.ROUTE_COUNTS["host"] == 0
    ex = planner.explain(db, suite[0])
    assert ex["route"] == kind and len(ex["join_cap_seeds"]) == 2


# -- dispatch-count pins -------------------------------------------------


@pytest.fixture(scope="module")
def pin_db():
    # sized so no capacity tier retries at initial_result_capacity=1024
    data = _bio_data(
        n_genes=30, n_processes=10, members_per_gene=3,
        n_interactions=40, n_evaluations=10,
    )
    return TensorDB(data, DasConfig(initial_result_capacity=1024))


def _three_var():
    v1, v2, v3 = (Variable(n) for n in ("V1", "V2", "V3"))
    return And([_member(v1, v3), _member(v2, v3),
                Link("Interacts", [v1, v2], True)])


def test_fused_is_one_program_a_query(pin_db):
    from das_tpu.query.fused import get_executor

    plans = compiler.plan_query(pin_db, _three_var())
    ex = get_executor(pin_db)
    # warm (compile + capacity learning), then count one execution
    assert ex.execute(plans, count_only=True) is not None
    counters.reset_dispatch_counts()
    res = ex.execute(plans, count_only=True)
    assert res is not None and not res.overflow
    assert counters.DISPATCH_COUNTS == {
        **dict.fromkeys(counters.DISPATCH_KEYS, 0), "fused": 1}


def test_staged_pipeline_is_thirteen_launches(pin_db):
    from das_tpu.query.fused import get_executor

    plans = compiler.plan_query(pin_db, _three_var())
    fused = get_executor(pin_db).execute(plans, count_only=True)
    # 3 terms x (probe + term table + dedup) + 2 joins x (join + dedup)
    counters.reset_dispatch_counts()
    table = compiler.execute_plan(pin_db, plans)
    assert counters.DISPATCH_COUNTS == {
        **dict.fromkeys(counters.DISPATCH_KEYS, 0), "lowered": 13}
    assert table.count == fused.count
