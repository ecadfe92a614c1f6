"""An answer leaves the worker as one block (ISSUE 32).

`materialize` (query/compiler.py, shared with parallel/sharded_db.py)
stops at the distinct valid rows of a settled binding table and hands
them to the answer as an `AnswerBlock` (query/ast.py); HANDLE text is
printed from the block in numpy (`LazyHexRows.hex_block`), and frozen
`OrderedAssignment`s exist only for a consumer that touches
`answer.assignments`.  Pinned here, against the row-by-row loop the
parent ran (kept below as the reference):

  * the block's text equals the object path's on randomised tables —
    1 to 4 variables, duplicate rows, rows in `LazyHexRows._tail`, one
    row, no row, a plain-list registry — as `canonical_answer` rows AND
    by `ast.literal_eval`;
  * on a FlyBase-shape store: HANDLE through the served path and
    `das.query`, `query_answer()`, `ATOM_INFO` and `JSON` give what the
    reference loop gives, before and after a commit whose new nodes
    live in the registry's tail;
  * the harness's `approximate_answers` control still loses its row;
  * a mesh answer whose row is valid on two shards appears once;
  * `exec.answers_block` / `exec.answers_objects` read n / 0 after n
    served HANDLE answers, and `exec.format`'s `rows` is the block's.
"""

import ast as pyast
import hashlib
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.reference import generator
from benchmark.reference.plain import canonical_answer
from das_tpu import obs
from das_tpu.api.atomspace import DistributedAtomSpace, QueryOutputFormat
from das_tpu.core.config import DasConfig
from das_tpu.query import compiler
from das_tpu.query.assignment import OrderedAssignment
from das_tpu.query.ast import (
    And,
    AnswerBlock,
    Link,
    Node,
    Or,
    PatternMatchingAnswer,
    Variable,
)
from das_tpu.storage.columnar import LazyHexRows

SCALE = 0.002
SEED = 2**31 + 32


# -- the reference: the loop the parent's `materialize` ran ---------------


def loop_assignments(var_names, vals, valid, hexes) -> set:
    out = set()
    for row in np.asarray(vals).reshape(-1, len(var_names))[
            np.asarray(valid).reshape(-1)]:
        a = OrderedAssignment()
        ok = True
        for name, val in zip(var_names, row):
            if not a.assign(name, hexes[int(val)]):
                ok = False
                break
        if ok and a.freeze():
            out.add(a)
    return out


def rows_of_text(text: str) -> list:
    """`{{..}, {..}}` -> its mappings, in a sorted order."""
    if text == "":
        return []
    assert text[0] == "{" and text[-1] == "}"
    return sorted(pyast.literal_eval("[" + text[1:-1] + "]"),
                  key=lambda m: sorted(m.items()))


def same_reply(got: str, want: str) -> None:
    assert len(got) == len(want)
    assert canonical_answer(got) == canonical_answer(want)
    assert rows_of_text(got) == rows_of_text(want)


# -- randomised tables ------------------------------------------------------


def _md5(i: int) -> str:
    return hashlib.md5(str(i).encode()).hexdigest()


N_BASE, N_TAIL = 500, 40


def _registry(kind: str):
    if kind == "list":
        return [_md5(i) for i in range(N_BASE + N_TAIL)]
    base = np.frombuffer(
        b"".join(bytes.fromhex(_md5(i)) for i in range(N_BASE)),
        dtype=np.uint8).reshape(N_BASE, 16)
    reg = LazyHexRows(base)
    for i in range(N_BASE, N_BASE + N_TAIL):
        reg.append(_md5(i))
    return reg


#: (name, valid rows, whether rows may point into the tail)
SHAPES = [("many", 300, False), ("tail", 300, True), ("one", 1, True),
          ("none", 0, False)]


def _table(k: int, n: int, tail: bool, seed: int):
    """A padded [cap, k] table of `n` valid rows among invalid ones,
    every third valid row a copy of an earlier one."""
    rng = np.random.default_rng(seed)
    cap = 512
    hi = N_BASE + N_TAIL if tail else N_BASE
    # few distinct values a column: duplicate TUPLES arise by chance too
    vals = rng.integers(max(0, hi - 60), hi, size=(cap, k)).astype(np.int32)
    valid = np.zeros(cap, dtype=bool)
    at = rng.permutation(cap)[:n]
    valid[at] = True
    for i in range(2, n, 3):
        vals[at[i]] = vals[at[i - 2]]
    if tail and n:
        vals[at[0], 0] = N_BASE + N_TAIL - 1
    names = tuple(f"${j + 1}" for j in range(k))
    return SimpleNamespace(var_names=names, vals=None, valid=None, count=n,
                           host_vals=vals, host_valid=valid)


@pytest.mark.parametrize("registry", ["lazy", "list"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: s[0])
@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_block_text_is_the_object_paths_text(k, shape, registry):
    _name, n, tail = shape
    hexes = _registry(registry)
    db = SimpleNamespace(fin=SimpleNamespace(hex_of_row=hexes))
    for seed in range(3):
        table = _table(k, n, tail, seed)
        want = loop_assignments(table.var_names, table.host_vals,
                                table.host_valid, hexes)
        answer = PatternMatchingAnswer()
        matched = compiler.materialize(db, table, answer)
        assert matched == bool(want)
        if not want:
            assert answer.block is None and answer.assignments == set()
            continue
        block = answer.block
        assert block is not None and len(block) == len(want)
        assert answer.row_count() == len(want)
        same_reply(block.handle_text(), str(want))
        # and the objects, for a consumer that asks for them
        assert answer.assignments == want
        assert answer.block is None
        assert all(a.frozen for a in answer.assignments)


@pytest.mark.parametrize("rows", [
    [0, N_BASE - 1], [N_BASE, N_BASE + N_TAIL - 1],
    [3, N_BASE + 1, 7, N_BASE, 3], []], ids=["base", "tail", "mixed", "none"])
def test_hex_block_is_the_per_row_read(rows):
    reg = _registry("lazy")
    got = reg.hex_block(np.asarray(rows, dtype=np.int32))
    assert got.shape == (len(rows), 32) and got.dtype == np.uint8
    assert [bytes(r).decode() for r in got] == [reg[i] for i in rows]


def test_a_quoted_variable_name_prints_as_repr_prints_it():
    hexes = _registry("lazy")
    block = AnswerBlock(np.array([[1, 2]], dtype=np.int32),
                        ("it's", "$é"), hexes)
    assert block.handle_text() == str(block.assignments())


def test_a_second_block_meets_the_first_as_objects():
    hexes = _registry("lazy")
    answer = PatternMatchingAnswer()
    answer.add_block(AnswerBlock(np.array([[1, 2], [3, 4]]), ("$1", "$2"),
                                 hexes))
    answer.add_block(AnswerBlock(np.array([[4, 3], [2, 1]]), ("$2", "$1"),
                                 hexes))
    assert answer.block is None and answer.row_count() == 2
    answer.add_block(AnswerBlock(np.array([[9, 9]]), ("$1", "$2"), hexes))
    assert len(answer.assignments) == 3
    answer.assignments = set()
    assert answer.row_count() == 0


# -- a FlyBase-shape store, before and after a commit ---------------------


def _das(tmp_path, backend: str, **config) -> DistributedAtomSpace:
    store = generator.Store(SCALE, SEED)
    path = os.path.join(str(tmp_path), "kb.metta")
    generator.write_canonical(store, path)
    das = DistributedAtomSpace(
        database_name="block", backend=backend,
        config=DasConfig.from_env(**config))
    das.load_canonical_knowledge_base(path)
    os.remove(path)
    return das


def shared2(gene: str):
    return And([
        Link("Member", [Node("Gene", gene), Variable("$3")], True),
        Link("Member", [Variable("$2"), Variable("$3")], True),
    ])


def grounded3(gene: str):
    g = Node("Gene", gene)
    return And([
        Link("Member", [g, Variable("$3")], True),
        Link("Member", [Variable("$2"), Variable("$3")], True),
        Link("Interacts", [g, Variable("$2")], True),
    ])


NEW_GENE = "gene-of-the-tail"


GENE = generator.gene_name(3)   # its shared2 answer is large


@pytest.fixture(scope="module")
def base_store(tmp_path_factory):
    return _das(tmp_path_factory.mktemp("kb"), "tensor")


@pytest.fixture(scope="module")
def tail_store(tmp_path_factory):
    """The store after a commit of a new gene into two of GENE's
    processes: the new node's row lives past the registry's base."""
    das = _das(tmp_path_factory.mktemp("kb"), "tensor")
    procs = sorted({m["$3"] for m in rows_of_text(das.query(shared2(GENE)))})
    tx = das.open_transaction()
    tx.add(f'(: "{NEW_GENE}" Gene)')
    for h in procs[:2]:
        tx.add(f'(Member "{NEW_GENE}" "{das.get_node_name(h)}")')
    das.commit_transaction(tx)
    hexes = das.db.fin.hex_of_row
    if isinstance(hexes, LazyHexRows):
        assert das.get_node("Gene", NEW_GENE) in hexes._tail
    return das


@pytest.fixture(scope="module", params=["base", "tail"])
def store(request):
    return request.getfixturevalue(request.param + "_store"), GENE


def _loop_answer(das, query) -> set:
    """The reference loop over the table the compiled path settles."""
    plans = compiler.plan_query(das.db, query)
    table = compiler._execute_fused(das.db, plans)
    if table is None or table.count == 0:
        return set()
    import jax

    vals, valid = jax.device_get((table.vals, table.valid))
    return loop_assignments(table.var_names, vals, valid,
                            das.db.fin.hex_of_row)


def _queries(store):
    das, gene = store
    out = [shared2(gene), grounded3(gene)]
    out += [shared2(generator.gene_name(i)) for i in (5, 8)]
    out += [grounded3(generator.gene_name(i)) for i in range(10, 16)]
    return out


def test_served_handle_replies_are_the_loops(store):
    das, _gene = store
    queries = _queries(store)
    wants = [_loop_answer(das, q) for q in queries]
    assert max(len(w) for w in wants) > 100
    served = das.query_many_dispatch(queries).settle()
    for q, want, got in zip(queries, wants, served):
        assert rows_of_text(got) == rows_of_text(das.query(q))
        if want:
            same_reply(got, str(want))
        else:
            assert got == ""


def test_the_tail_gene_is_in_its_neighbours_answer(tail_store):
    new = tail_store.get_node("Gene", NEW_GENE)
    rows = rows_of_text(tail_store.query(shared2(GENE)))
    assert sum(1 for m in rows if m["$2"] == new) == 2


def test_query_answer_gives_the_loops_objects(store):
    das, gene = store
    for q in (shared2(gene), grounded3(gene)):
        want = _loop_answer(das, q)
        matched, answer = das.query_answer(q)
        assert matched == bool(want)
        assert answer.assignments == want
        assert {frozenset(a.mapping.items()) for a in answer.assignments} \
            == {frozenset(a.mapping.items()) for a in want}


@pytest.mark.parametrize("fmt", [QueryOutputFormat.ATOM_INFO,
                                 QueryOutputFormat.JSON],
                         ids=["atom_info", "json"])
def test_rendered_replies_are_the_loops(store, fmt):
    das, gene = store
    q = shared2(gene)
    want = _loop_answer(das, q)
    deep = fmt == QueryOutputFormat.JSON
    rendered = [das._render_assignment(a, deep=deep) for a in want]
    got = das.query(q, fmt)
    if deep:
        assert got.startswith("[\n    {\n")       # indent=4, as it was
        parsed = json.loads(got)
    else:
        parsed = pyast.literal_eval(got)
    key = lambda m: json.dumps(m, sort_keys=True)  # noqa: E731
    assert sorted(parsed, key=key) == sorted(rendered, key=key)
    assert len(got) == len(
        json.dumps(rendered, sort_keys=False, indent=4) if deep
        else str(rendered))


def test_the_harness_control_still_loses_its_row(store):
    """benchmark/harness/cell.py `approximate_answers`, copied."""
    das, gene = store
    q = shared2(gene)
    exact_rows = canonical_answer(das.query_many_dispatch([q, q]).settle()[0])
    assert len(exact_rows) > 100
    exact = das._format_answer

    def lossy(matched, answer, output_format):
        if matched and len(answer.assignments) > 100:
            answer.assignments.pop()
        return exact(matched, answer, output_format)

    das._format_answer = lossy
    try:
        served = das.query_many_dispatch([q, q]).settle()
        lone = das.query(q)
    finally:
        del das._format_answer
    for got in served + [lone]:
        rows = canonical_answer(got)
        assert len(rows) == len(exact_rows) - 1
        assert set(rows) < set(exact_rows)


@pytest.fixture
def tracing():
    obs.configure(enabled=True, capacity=8192)
    obs.reset()
    yield
    obs.configure(enabled=False)
    obs.reset()


def test_served_handle_answers_count_as_blocks_and_build_no_object(
        store, tracing):
    das, _gene = store
    queries = _queries(store)
    served = das.query_many_dispatch(queries).settle()
    n = sum(1 for s in served if s)
    assert 0 < n < len(queries)
    assert obs.counter("exec.answers_block").value == n
    assert obs.counter("exec.answers_objects").value == 0
    spans = [e[8] for e in obs.events() if e[0] == "exec.format"]
    assert sorted(a["rows"] for a in spans) == sorted(
        len(canonical_answer(s)) for s in served)
    assert sorted(a["bytes"] for a in spans) == sorted(
        len(s) for s in served)
    # a consumer of objects is counted as one
    das.query_answer(queries[0])[1].assignments
    das.query(queries[0], QueryOutputFormat.ATOM_INFO)
    assert obs.counter("exec.answers_objects").value == 2
    assert obs.counter("exec.answers_block").value == n


# -- the mesh ----------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh_das(tmp_path_factory):
    return _das(tmp_path_factory.mktemp("mesh"), "sharded", mesh_shape=(4,))


def test_a_row_valid_on_two_shards_appears_once(mesh_das, tracing):
    from das_tpu.parallel.sharded_db import ShardedTable

    db = mesh_das.db
    assert db.tables.n_shards == 4
    vals = np.zeros((4, 8, 2), dtype=np.int32)
    valid = np.zeros((4, 8), dtype=bool)
    vals[0, 0] = vals[2, 5] = vals[2, 6] = [11, 12]   # one row, three times
    vals[1, 3] = [11, 13]
    vals[3, 7] = [12, 11]
    for at in ((0, 0), (2, 5), (2, 6), (1, 3), (3, 7)):
        valid[at] = True
    table = ShardedTable(("$2", "$3"), None, None, 5,
                         host_vals=vals, host_valid=valid)
    answer = PatternMatchingAnswer()
    assert db.materialize(table, answer)
    assert answer.row_count() == 3
    text = answer.block.handle_text()
    want = loop_assignments(table.var_names, vals, valid,
                            db.fin.hex_of_row)
    assert len(want) == 3
    same_reply(text, str(want))
    dedup = [e[8] for e in obs.events() if e[0] == "mesh.dedup"]
    assert [(a["rows"], a["distinct"]) for a in dedup] == [(5, 3)]


@pytest.mark.parametrize("shape", ["shared2", "grounded3", "or"])
def test_mesh_answers_are_one_chips(mesh_das, base_store, shape):
    das, gene = base_store, GENE
    other = generator.gene_name(5)
    query = {"shared2": shared2(gene), "grounded3": grounded3(gene),
             # two branches add into one answer: the union of their rows
             "or": Or([shared2(gene), shared2(other)])}[shape]
    got = mesh_das.query(query)
    want = das.query(query)
    assert rows_of_text(got) == rows_of_text(want)
    if shape == "or":
        both = rows_of_text(das.query(shared2(gene))) + rows_of_text(
            das.query(shared2(other)))
        assert len(rows_of_text(got)) == len(
            {tuple(sorted(m.items())) for m in both})
    served = mesh_das.query_many_dispatch([query, query]).settle()
    assert rows_of_text(served[0]) == rows_of_text(want)
