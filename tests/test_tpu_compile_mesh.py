"""AOT compiles for a DESCRIBED TPU v5e:2x2: the mesh programs.

The fused shard_map programs of the sharded backend at the shapes
`chip_smoke.py --chips 4` and cells `sharded4-uniform-closed` /
`sharded4-analytic` run, compiled against a Mesh built from the
described devices.  One of three files (tests/test_tpu_compile.py says
why, and what a run under several workers needs).
"""

import dataclasses

import pytest

from das_tpu.core.config import DasConfig
from das_tpu.storage.delta import capacity_class
from tests.described_v5e import (  # noqa: F401  (fixtures by name)
    SMOKE_ARITY2_ROWS,
    _assert_the_first_join_searches_by_rows,
    _compile_on_described_mesh,
    _three_var_plans,
    _tiny_store_and_query,
    _trace_on_described_mesh,
    no_persistent_cache,
    topo,
)

def test_sharded_grounded3_on_described_2x2_mesh(topo, no_persistent_cache):
    """`chip_smoke.py --chips 4`: the fused shard_map program of the
    grounded conjunction, compiled against a Mesh built from the
    described v5e:2x2 devices with the row-sharded bucket arrays at the
    smoke store's per-shard size.  The collectives must be there."""
    from das_tpu.parallel.fused_sharded import get_sharded_executor
    from das_tpu.parallel.mesh import make_mesh
    from das_tpu.parallel.sharded_db import ShardedDB

    db, plans = _tiny_store_and_query(
        lambda data: ShardedDB(data, DasConfig(), mesh=make_mesh(4))
    )
    job = get_sharded_executor(db)._exec_job(plans, False)
    assert job is not None
    sig = job.plan_sig()
    assert sig.n_shards == 4
    text = _compile_on_described_mesh(
        topo, job, sig, capacity_class(-(-SMOKE_ARITY2_ROWS // 4))
    ).as_text()
    assert "all-gather" in text or "all-reduce" in text or "all-to-all" in text


#: cell 3 of the benchmark (`sharded4-uniform-closed`, FlyBase shape x
#: 0.3 on 4 shards): 8,361,000 links of arity 2 dealt round-robin, and the
#: capacities the mesh executor holds after the cell's warm-up (recorded
#: from a CPU run of the served path at scale 0.3 on 4 virtual devices)
CELL3_ARITY2_ROWS = 8_361_000
CELL3_PROGRAMS = {
    "grounded3": dict(term_caps=(16, 16, 16), join_caps=(1024, 64),
                      exch_caps=(0, 0), index_joins=(1, -1)),
    "shared2": dict(term_caps=(16, 16), join_caps=(1024,),
                    exch_caps=(0,), index_joins=(1,)),
}


def _cell3_job(shape):
    """The mesh executor's own job for one of cell 3's shapes (tiny
    store) and its signature at the cell's capacities."""
    from das_tpu.parallel.fused_sharded import get_sharded_executor
    from das_tpu.parallel.mesh import make_mesh
    from das_tpu.parallel.sharded_db import ShardedDB

    db, plans = _tiny_store_and_query(
        lambda data: ShardedDB(data, DasConfig(), mesh=make_mesh(4)),
        n_clauses=3 if shape == "grounded3" else 2,
    )
    job = get_sharded_executor(db)._exec_job(plans, False)
    assert job is not None
    want = CELL3_PROGRAMS[shape]
    assert job.plan_sig().index_joins == want["index_joins"]
    sig = dataclasses.replace(job.plan_sig(), **want)
    assert sig.n_shards == 4
    per_shard = capacity_class(-(-CELL3_ARITY2_ROWS // 4))
    assert per_shard == 2_220_890
    return job, sig, per_shard


@pytest.mark.parametrize("shape", sorted(CELL3_PROGRAMS))
def test_cell3_mesh_programs_on_described_2x2_mesh(topo, no_persistent_cache,
                                                   shape):
    """The two mesh programs the warm-up of `sharded4-uniform-closed`
    builds for a job alone in its signature, at the cell's per-shard
    table size and capacities, compiled for the described v5e:2x2: the
    gathers of the index joins and the stats reductions (int32 `pmax`,
    `psum`) must lower."""
    job, sig, per_shard = _cell3_job(shape)
    text = _compile_on_described_mesh(topo, job, sig, per_shard).as_text()
    assert "all-gather" in text and "all-reduce" in text


@pytest.mark.parametrize("count_only", [True, False],
                         ids=["count_program", "result_program"])
@pytest.mark.parametrize("shape", sorted(CELL3_PROGRAMS))
def test_cell3_mesh_group_programs_on_described_2x2_mesh(
        topo, no_persistent_cache, shape, count_only):
    """`das_sharded_group` (ISSUE 43), the program a batch's
    same-signature mesh jobs ride, at the served path's lanes and cell
    3's shapes: the collectives lower with the lanes axis on them, and
    the lanes add no table-sized temporary (the bucket arrays ride
    unbatched inside the shard_map: no `[lanes, slab]` intermediate)."""
    from das_tpu.query import fused

    job, sig, per_shard = _cell3_job(shape)
    compiled = _compile_on_described_mesh(
        topo, job, sig, per_shard, group=(count_only, fused.GROUP_LANES))
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
    assert "tpu_custom_call" not in text
    lone = _compile_on_described_mesh(topo, job, sig, per_shard)
    slab_bytes = per_shard * 8                  # one int64 key array
    assert (compiled.memory_analysis().temp_size_in_bytes
            < lone.memory_analysis().temp_size_in_bytes + slab_bytes)


# -- cell 6: the whole-store conjunction on the mesh ----------------------

#: cell `sharded4-analytic` (`flybase-sharded4-analytic`, FlyBase shape
#: x 0.3 on 4 shards, cell 3's store): the capacities the mesh executor
#: seeds for the all-variable conjunction there (Interacts rows a shard
#: near their share; Interacts x Member a shard; the verified join's
#: rows; the exchange slots of the second join, which PARTITIONS), read
#: from the executor's own job on a CPU build of the store at 0.3 (PR
#: 47; tests/test_mesh_analytic.py holds the rules' arithmetic)
CELL6_CAPS = dict(term_caps=(262_144, 16, 16), join_caps=(4_194_304, 2048),
                  exch_caps=(0, 1_048_576))


def test_mesh_three_var_at_cell6_shapes(topo, no_persistent_cache):
    """`das_sharded` of the all-variable conjunction at cell 6's
    per-shard shapes, compiled for the described v5e:2x2.  What keeps
    its FIRST compile inside the statement deadline, beyond "it
    compiles": the verified join sorts ONCE (its two shared columns and
    one payload), each exchange once (ONE 32-bit operand), nothing is
    stable, no operand is 64-bit; the only 64-bit all-reduce is a Sum
    (the chip's compiler lowers no other); and no shard holds the
    gathered left side of the second join (4 x 4.2 M slots: 0.6 GB of
    temporaries where the partition needs under 0.2).  And the first
    join of a shard searches as cell 5's does: by rows, with no loop."""
    import re

    from das_tpu.parallel.fused_sharded import get_sharded_executor
    from das_tpu.parallel.mesh import make_mesh
    from das_tpu.parallel.sharded_db import ShardedDB

    db, plans = _three_var_plans(
        lambda data: ShardedDB(data, DasConfig(), mesh=make_mesh(4)))
    job = get_sharded_executor(db)._exec_job(plans, False)
    assert job.index_joins == (0, 0)
    # at any size the first join gathers, the second partitions
    assert job.exch_caps[0] == 0 and job.exch_caps[1] > 0
    sig = dataclasses.replace(job.plan_sig(), **CELL6_CAPS)
    per_shard = capacity_class(-(-CELL3_ARITY2_ROWS // 4))
    traced = _trace_on_described_mesh(topo, job, sig, per_shard)
    # every shard probes its slab's index with the gathered left side
    _assert_the_first_join_searches_by_rows(
        traced.jaxpr.jaxpr, per_shard, 4 * CELL6_CAPS["term_caps"][0])
    lowered = traced.lower()
    text = lowered.as_text()
    sorts = re.findall(
        r'"stablehlo\.sort"\(([^)]*)\) <\{([^}]*)\}>.*?\}\) : \(([^)]*)\) ->',
        text, flags=re.S)
    assert sorted(ops.count("%") for ops, _a, _t in sorts) == [1, 1, 3]
    for _operands, attrs, types in sorts:
        assert "is_stable = false" in attrs and "i64" not in types
    assert text.count('"stablehlo.all_to_all"') == 2
    reduces = re.findall(
        r'"stablehlo\.all_reduce"\(.*?\^bb0\((.*?)\):\s*(.*?)stablehlo\.return',
        text, flags=re.S)
    assert reduces
    for args, body in reduces:
        if "i64" in args:
            assert "stablehlo.add" in body
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "all-to-all" in hlo and "all-gather" in hlo
    assert "tpu_custom_call" not in hlo
    # beside ONE level of the first join's search: a row of
    # SEARCH_FANOUT words a gathered left slot, live a level at a time
    # (0.54 GB at 128; the second join's gathered left side would be
    # 0.6 GB MORE)
    from das_tpu.ops.join import SEARCH_FANOUT

    search_rows = 4 * CELL6_CAPS["term_caps"][0] * SEARCH_FANOUT * 4
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 300e6 + search_rows)


# -- the mesh tree's and the staged route's table programs ----------------


def _record_table_programs(monkeypatch, drive):
    """Every table program `drive(db)` builds on a 4-device CPU mesh
    (tiny store), as `(body, n_in, n_out, specs, argument shapes)`:
    what `parallel/mesh.py table_program` was handed and what the
    program was first called with."""
    import numpy as np

    from das_tpu.models.animals import animals_metta
    from das_tpu.parallel import mesh as mesh_mod
    from das_tpu.parallel import sharded_db, sharded_tree
    from das_tpu.storage.atom_table import load_metta_text

    records = []

    def spy(mesh, fn, n_in, n_out, replicated_in=(), **specs):
        program = mesh_mod.table_program(
            mesh, fn, n_in, n_out, replicated_in, **specs)
        seen = set()

        def call(*args):
            shapes = tuple(
                (tuple(np.shape(a)), np.asarray(a).dtype.name) for a in args)
            if shapes not in seen:
                seen.add(shapes)
                records.append(
                    (fn, n_in, n_out, tuple(replicated_in), specs, shapes))
            return program(*args)

        return call

    monkeypatch.setattr(sharded_tree, "table_program", spy)
    monkeypatch.setattr(sharded_db, "table_program", spy)
    db = sharded_db.ShardedDB(
        load_metta_text(animals_metta()), DasConfig(),
        mesh=mesh_mod.make_mesh(4))
    drive(db)
    return records


def _ask_the_tree(*queries):
    from das_tpu.query.ast import PatternMatchingAnswer

    def drive(db):
        for query in queries:
            assert db.query_sharded(query, PatternMatchingAnswer()) is not None

    return drive


def _staged_conjunction(db):
    from das_tpu.query import compiler
    from das_tpu.query.ast import And, Link, Node, Not, Variable

    table = db.sharded_execute(compiler.plan_query(db, And([
        Link("Inheritance", [Variable("V1"), Variable("V2")], True),
        Link("Inheritance", [Variable("V2"), Node("Concept", "animal")], True),
        Not(Link("Inheritance", [Variable("V1"), Node("Concept", "mammal")],
                 True)),
    ])))
    assert table is not None and table.count > 0


def _tree_drives():
    from tests.test_fused_sharded import MESH_TREE_QUERIES as q

    return {
        "unordered_probes": _ask_the_tree(q[0], q[1]),
        "ordered_x_unordered_join": _ask_the_tree(q[2]),
        "negation": _ask_the_tree(q[3], q[4]),
        "nested_or": _ask_the_tree(q[5]),
        "staged_conjunction": _staged_conjunction,
    }


@pytest.mark.parametrize("drive", ["unordered_probes",
                                   "ordered_x_unordered_join", "negation",
                                   "nested_or", "staged_conjunction"])
def test_mesh_table_programs_on_described_2x2_mesh(
        topo, no_persistent_cache, monkeypatch, drive):
    """Since PR 50 every table operation of the mesh tree evaluator and
    of the staged route is ONE jitted `shard_map` program where its
    primitives were dispatched one by one: the chip's compiler now sees
    each WHOLE.  No cell asks the mesh an Or / Not / unordered query, so
    this is where the chip's compiler is asked: every program the drive
    builds (leaf probes, joins either way round, dedup, anti-join,
    concat, replicate; the staged term, join, anti-join), at the shapes
    a tiny store gives them (what is at stake is what the compiler
    REFUSES: a 64-bit collective, an unpartitionable op; not size),
    compiled against a Mesh of the described v5e:2x2 devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from das_tpu.parallel.mesh import SHARD_AXIS, table_program

    records = _record_table_programs(monkeypatch, _tree_drives()[drive])
    assert records
    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))
    for body, n_in, n_out, replicated_in, specs, shapes in records:
        args = [
            jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=NamedSharding(
                mesh, P() if i in replicated_in else P(SHARD_AXIS)))
            for i, (shape, dtype) in enumerate(shapes)
        ]
        program = table_program(mesh, body, n_in, n_out, replicated_in, **specs)
        text = program.lower(*args).compile().as_text()
        assert "tpu_custom_call" not in text
