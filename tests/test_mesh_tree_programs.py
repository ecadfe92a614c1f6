"""Every mesh table operation is ONE compiled program (PR 50).

The tree evaluator's mesh op layer (`parallel/sharded_tree.py
ShardedTreeOps`) and the staged route (`parallel/sharded_db.py
ShardedDB._term_table` / `_join` / `_anti_join`) build their
`shard_map` bodies through one door each (`_smap`, `_staged_program`;
both call `parallel/mesh.py table_program`), which jits the program
whole and keeps it under its statics.  A bare
`shard_map` dispatches its body primitive by primitive, on every call:
these cases hold each builder to handing out a jitted executable
(`.lower`), one per key, that a second call of a shape finds compiled
(its own cache holds one entry).  Answers are held elsewhere
(tests/test_fused_sharded.py, test_ztreefuse.py, test_fuzz.py).
"""

import jax.numpy as jnp
import pytest

from das_tpu.parallel.sharded_db import ShardedDB
from das_tpu.query import compiler as qc
from das_tpu.query.ast import Link, Node, PatternMatchingAnswer, Variable
from das_tpu.query.tree import CTable
from das_tpu.storage.atom_table import load_metta_text
from das_tpu.storage.memory_db import MemoryDB
from tests.test_fused import TRI_METTA

CAP = 16


@pytest.fixture
def db(animals_data):
    return ShardedDB(animals_data)


def _table(ops, cols, first=0):
    """A row-sharded `[S*CAP, cols]` table with a few valid rows."""
    rows = ops.S * CAP
    vals = (jnp.arange(rows * cols, dtype=jnp.int32).reshape(rows, cols)
            + first) % 7
    return vals, jnp.arange(rows) % 3 == 0


def _grounded(concept):
    return Link("Inheritance", [Node("Concept", concept), Variable("V1")], True)


def _similar(concept):
    return Link("Similarity", [Node("Concept", concept), Variable("V1")], False)


def _flatten(db, ops, i):
    ops._flatten(jnp.zeros((ops.S, CAP, 2), jnp.int32) + i,
                 jnp.ones((ops.S, CAP), bool))


def _uterm(db, ops, i):
    # two atoms, one shape: the probed key rides as an operand
    q = _similar(("human", "monkey")[i])
    assert db.query_sharded(q, PatternMatchingAnswer())


def _join(db, ops, i):
    av, am = _table(ops, 2, i)
    bv, bm = _table(ops, 2, i + 1)
    ops.join_tables(av, am, bv, bm, ((0, 0),), (1,), 64)


def _swapped_join(db, ops, i):
    av, am = _table(ops, 2, i)
    bv, bm = _table(ops, 2, i + 1)
    ops.join_tables(av, am, bv, bm, ((0, 0),), (1,), 64, counts=(1, 5))


def _dedup(db, ops, i):
    ops.dedup(*_table(ops, 2, i))


def _anti(db, ops, i):
    lv, lm = _table(ops, 2, i)
    t = CTable("O", ("a", "b"), (0, 1), (), *_table(ops, 2, i + 1), 1)
    tabu = ops.replicate(t)
    ops.anti_join(lv, lm, tabu.vals, tabu.valid, ((0, 0), (1, 1)))


def _concat(db, ops, i):
    ops.concat([_table(ops, 2, i), _table(ops, 2, i + 1)])


def _replicate(db, ops, i):
    t = CTable("O", ("a", "b"), (0, 1), (), *_table(ops, 2, i), 1)
    ops.replicate(t)


TREE_BUILDERS = {
    "flatten": (_flatten, "flatten"),
    "uterm": (_uterm, "uterm"),
    "join": (_join, "join"),
    "swapped_join": (_swapped_join, "join"),
    "dedup": (_dedup, "dedup"),
    "anti": (_anti, "anti"),
    "concat": (_concat, "concat"),
    "replicate": (_replicate, "replicate"),
}


def _the_one_program(cache, kind):
    """The cache's ONE entry of `kind`, held to being a jitted program
    that two calls of a shape compiled once (an op may need programs of
    other kinds too: every entry is held to being jitted)."""
    for key, fn in cache.items():
        assert hasattr(fn, "lower"), f"{key}: not a jitted program"
    (key,) = [k for k in cache if k[0] == kind]
    assert cache[key]._cache_size() == 1, (key, cache[key]._cache_size())
    return key


@pytest.mark.parametrize("builder", sorted(TREE_BUILDERS))
def test_a_tree_op_is_one_compiled_program(db, builder):
    """Two calls of one shape through a `ShardedTreeOps` builder: one
    cache entry of its kind, a jitted executable compiled once."""
    call, kind = TREE_BUILDERS[builder]
    ops = db.tree_ops
    assert not ops._fn_cache
    call(db, ops, 0)
    call(db, ops, 1)
    key = _the_one_program(ops._fn_cache, kind)
    if kind == "join":
        assert key[4] == (builder == "swapped_join"), "wrong side gathered"


def _staged_term(db, i):
    plans = qc.plan_query(db, _grounded(("human", "monkey")[i]))
    table = db._term_table(plans[0])
    assert table is not None and table.count > 0
    return table


def _staged_join(db, i):
    left = _staged_term(db, i)
    (plan,) = qc.plan_query(
        db, Link("Inheritance", [Variable("V1"), Variable("V2")], True))
    joined = db._join(left, db._term_table(plan))
    assert joined.var_names == ("V1", "V2")
    return joined


def _staged_anti(db, i):
    left = _staged_term(db, i)
    out = db._anti_join(left, _staged_term(db, 1 - i))
    assert out.count <= left.count


STAGED_SITES = {"term": _staged_term, "join": _staged_join,
                "anti": _staged_anti}


@pytest.mark.parametrize("site", sorted(STAGED_SITES))
def test_a_staged_site_is_one_compiled_program(db, site):
    """The staged route's three sites: the grounded atom and the
    capacity retry reuse the program a first call of the shape
    compiled (the atom's key and values ride as replicated operands)."""
    call = STAGED_SITES[site]
    call(db, 0)
    call(db, 1)
    _the_one_program(db._staged_programs, site)


def test_the_staged_probe_filters_by_its_operands():
    """The operands that replaced `_probe_kernel`'s closed-over key and
    values select the same rows as the host engine: links with TWO
    grounded positions (one probes, one filters), two atoms a shape
    through ONE program."""
    data = load_metta_text(TRI_METTA)
    db, host = ShardedDB(data), MemoryDB(data)

    def rel(*targets):
        return Link("Rel", [Variable(t[1:]) if t[0] == "$"
                            else Node("Concept", t) for t in targets], True)

    for query in (rel("a", "b", "$V1"), rel("x", "e", "$V1"),
                  rel("a", "$V1", "c"), rel("$V1", "b", "c")):
        (plan,) = qc.plan_query(db, query)
        assert len(plan.fixed) == 2
        want = PatternMatchingAnswer()
        assert query.matched(host, want)
        got = PatternMatchingAnswer()
        assert db.materialize(db._term_table(plan), got)
        assert got.assignments == want.assignments
    # four queries, three shapes: the two (atom, atom, $V1) share one
    assert len(db._staged_programs) == 3
    for fn in db._staged_programs.values():
        assert fn._cache_size() == 1
