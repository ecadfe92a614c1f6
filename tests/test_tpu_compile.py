"""AOT compiles for a DESCRIBED TPU v5e: the one-chip query programs.

The CPU suite cannot see what the chip's compiler refuses: r03's
fori_loop count body died on the chip with "reduce-window ... exceeded
scoped vmem limit" while the identical program ran everywhere else, and
every Pallas kernel the repo once had passed its interpret-mode tests
while Mosaic refused all of them (they were deleted in PR 31; the
verdict test is in this file's history).  These cases hand the chip's
compiler the programs of the served path at the shapes `chip_smoke.py`
and the benchmark's cells run: shapes only, nothing executes, no chip
needed.

Three files by what they compile, so that no one file is the suite's
wall under `--dist loadfile` (PR 50): this one (the one-chip query
programs, the first join's expansion alone, and the accepted cells'
programs letter for letter), `test_tpu_compile_mesh.py` (the mesh
programs) and `test_tpu_compile_ops.py` (the staged join at the largest
capacity, the commit's programs).  `tests/described_v5e.py` holds the
fixtures and the shape helpers they share, and the rules they keep.
Only one process at a time may load the TPU's library: under several
xdist workers the files can land on different ones, and then only the
first describes the topology and the others SKIP, unless the run sets
`ALLOW_MULTIPLE_LIBTPU_LOAD=1` as the driver's tier-1 command does.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from das_tpu.core.config import DasConfig
from das_tpu.storage.delta import capacity_class
from tests.described_v5e import (  # noqa: F401  (fixtures by name)
    SMOKE_ARITY2_CAPACITY,
    _as_shape,
    _assert_the_first_join_searches_by_rows,
    _lower_on_described_mesh,
    _shape,
    _table,
    _three_var_plans,
    _tiny_store_and_query,
    compile_for_chip,
    no_persistent_cache,
    one_chip,
    topo,
)
from tests.test_tpu_compile_mesh import _cell3_job

#: capacities the executor settles on there for the grounded 3-clause
#: conjunction (recorded from a CPU run of chip_smoke's phases at 0.1)
SMOKE_TERM_CAPS = (16, 16, 16)
SMOKE_JOIN_CAPS = (2048, 64)


@pytest.fixture(scope="module")
def grounded_job():
    """The executor's own job for the grounded conjunction."""
    from das_tpu.query.fused import get_executor
    from das_tpu.storage.tensor_db import TensorDB

    db, plans = _tiny_store_and_query(lambda data: TensorDB(data, DasConfig()))
    job = get_executor(db)._exec_job(plans, False)
    assert job is not None
    return job


def _smoke_shapes(job):
    """The job's argument shapes with every bucket array stretched to
    the smoke store's capacity class."""
    cap = SMOKE_ARITY2_CAPACITY

    def stretch(a):
        a = np.asarray(a) if not hasattr(a, "shape") else a
        shape = tuple(a.shape)
        return _shape((cap, *shape[1:]) if shape else shape, a.dtype)

    arrays = jax.tree.map(stretch, job.arrays)
    keys = jax.tree.map(lambda k: _shape((), np.asarray(k).dtype), job.keys)
    fvals = jax.tree.map(
        lambda f: _shape(np.shape(f), np.asarray(f).dtype), job.fvals
    )
    return arrays, keys, fvals


@pytest.mark.parametrize("count_only", [True, False],
                         ids=["count_program", "result_program"])
def test_fused_grounded3_at_smoke_shapes(compile_for_chip, grounded_job,
                                         count_only):
    """The fused 3-clause count and result programs `auto` dispatches on
    the chip (the lowered route), at the smoke's bucket shapes."""
    from das_tpu.query.fused import build_fused

    sig = dataclasses.replace(
        grounded_job.plan_sig(),
        term_caps=SMOKE_TERM_CAPS, join_caps=SMOKE_JOIN_CAPS,
    )
    assert len(sig.terms) == 3
    fn, _names = build_fused(sig, count_only)
    compiled = compile_for_chip(fn, *_smoke_shapes(grounded_job))
    assert "tpu_custom_call" not in compiled.as_text()


#: cell 1 of the benchmark (`mem-uniform-closed`, FlyBase shape x 0.3 on
#: one chip): the arity-2 bucket's capacity class, and the capacities the
#: executor holds for the cell's two shapes after its warm-up (recorded
#: from the chip run of _archive/group_timing.py, PR 30)
CELL1_ARITY2_CAPACITY = 8_883_562
CELL1_PROGRAMS = {
    "grounded3": dict(term_caps=(16, 16, 16), join_caps=(2048, 64),
                      index_joins=(1, -1)),
    "shared2": dict(term_caps=(16, 16), join_caps=(2048,),
                    index_joins=(1,)),
}


@pytest.fixture(scope="module")
def cell1_jobs():
    """The executor's own jobs for cell 1's two shapes (tiny store)."""
    from das_tpu.query.fused import get_executor
    from das_tpu.storage.tensor_db import TensorDB

    jobs = {}
    for shape, n in (("grounded3", 3), ("shared2", 2)):
        db, plans = _tiny_store_and_query(
            lambda data: TensorDB(data, DasConfig()), n_clauses=n)
        jobs[shape] = get_executor(db)._exec_job(plans, False)
        assert jobs[shape] is not None
    return jobs


def _cell1_program(job, shape, count_only, group):
    """(jitted fn, its argument shapes) of `das_fused` (`group` false)
    or `das_fused_group` at the served path's lanes, for one of cell
    1's shapes at its bucket size and capacities.  The lanes' inputs as
    dispatch_group stacks them: every lane its own gene (the probe key
    of the grounded terms), the whole-type term's key hoisted."""
    from das_tpu.query import fused

    sig = dataclasses.replace(job.plan_sig(), **CELL1_PROGRAMS[shape])
    keys, fvals = job.keys, job.fvals
    if group:
        lanes = fused.GROUP_LANES
        keys, key_axes, fvals, fval_axes = fused.stack_lanes(
            [tuple(np.asarray(k)
                   + (i if t != sig.index_joins.index(1) + 1 else 0)
                   for t, k in enumerate(job.keys)) for i in range(lanes)],
            [job.fvals] * lanes, lanes,
        )
        assert None in key_axes and 0 in key_axes
        fn, _names = fused.build_fused_group(
            sig, count_only, key_axes, fval_axes)
    else:
        fn, _names = fused.build_fused(sig, count_only)

    def stretch(a):
        shape_ = tuple(a.shape)
        return _shape(
            (CELL1_ARITY2_CAPACITY, *shape_[1:]) if shape_ else shape_,
            a.dtype)

    return fn, (jax.tree.map(stretch, job.arrays),
                jax.tree.map(_as_shape, keys), jax.tree.map(_as_shape, fvals))


@pytest.mark.parametrize("count_only", [True, False],
                         ids=["count_program", "result_program"])
@pytest.mark.parametrize("shape", sorted(CELL1_PROGRAMS))
def test_fused_group_at_cell1_shapes(compile_for_chip, cell1_jobs, shape,
                                     count_only):
    """`das_fused_group` (ISSUE 30) at the served path's lanes (its
    ladder has the one rung), for both shapes of cell 1 at its bucket
    size and capacities:
    the lowered route, and a batched lowering that keeps its
    temporaries far under ONE table (no `[lanes, table]` intermediate:
    the bucket arrays ride unbatched)."""
    from das_tpu.query import fused

    lanes = fused.GROUP_LANES
    assert CELL1_ARITY2_CAPACITY == capacity_class(8_361_000)
    job = cell1_jobs[shape]
    want = CELL1_PROGRAMS[shape]
    assert job.plan_sig().index_joins == want["index_joins"]
    fn, shapes = _cell1_program(job, shape, count_only, group=True)
    keys, fvals = shapes[1:]
    compiled = compile_for_chip(fn, *shapes)
    assert "tpu_custom_call" not in compiled.as_text()
    # das_fused alone holds 38.7 MB of temporaries there (the u32 halves
    # of a key array); 32 lanes add 1 MB.  One lane-batched copy of a
    # key array's half would be 4 x 35.5 MB at the lowest rung
    table_bytes = CELL1_ARITY2_CAPACITY * 8      # one int64 key array
    assert compiled.memory_analysis().temp_size_in_bytes < table_bytes
    # no lane-batched sort of a join's 2,048 left rows: that sort was
    # 23 s of the 26 s this program took to compile for the chip, inside
    # a serving window when a capacity step built it there; under lanes
    # the searches into the 16-row term tables are compares
    # (ops/join.py lane_batched) and the sorts left are those tables' own
    import re

    lowered = fn.lower(job.arrays, keys, fvals).as_text()
    sorted_rows = [int(n) for n in re.findall(
        r"stablehlo\.sort.*?\}\) : \(tensor<%dx(\d+)xi64>" % lanes,
        lowered, flags=re.S)]
    assert lowered.count("stablehlo.sort") == len(sorted_rows)
    assert all(n <= max(want["term_caps"]) for n in sorted_rows), sorted_rows


#: the benchmark's cell `mem-analytic` (`flybase-analytic`, FlyBase
#: shape x ANALYTIC_SCALE on one chip): the arity-2 bucket there, and
#: the capacities the planner seeds for the whole-store 3-clause
#: conjunction (Interacts rows; Interacts x Member; the verified join's
#: ~1,667 rows with the ladder's margin)
ANALYTIC_SCALE = 0.1
ANALYTIC_ARITY2_ROWS = int(27_870_000 * ANALYTIC_SCALE)
ANALYTIC_CAPS = dict(term_caps=(1 << 19, 16, 16), join_caps=(1 << 22, 4096))


def _analytic_program():
    """(jitted `das_fused`, its argument shapes, the bucket's capacity)
    of the all-variable conjunction at cell `mem-analytic`'s shapes."""
    from das_tpu.query import fused
    from das_tpu.storage.tensor_db import TensorDB

    db, plans = _three_var_plans(lambda data: TensorDB(data, DasConfig()))
    job = fused.get_executor(db)._exec_job(plans, False)
    assert job.index_joins == (0, 0)
    assert fused.whole_type_join_steps(job.sigs, job.index_joins)[:2] == (
        (1,), ((0, 1),))
    sig = dataclasses.replace(job.plan_sig(), **ANALYTIC_CAPS)
    fn, _names = fused.build_fused(sig, False)
    cap = capacity_class(ANALYTIC_ARITY2_ROWS)

    def stretch(a):
        shape = tuple(a.shape)
        return _shape((cap, *shape[1:]) if shape else shape, a.dtype)

    return fn, (jax.tree.map(stretch, job.arrays),
                jax.tree.map(_as_shape, job.keys),
                jax.tree.map(_as_shape, job.fvals)), cap


def test_fused_three_var_at_the_analytic_cells_shapes(compile_for_chip):
    """The lone `das_fused` program of the all-variable 3-clause
    conjunction (PR 44): the posting-index join on one variable, then
    the verified join on two.  Beyond "it compiles": what keeps its
    FIRST compile inside the cell's statement deadline.  On the chip a
    sort's compile time grows with its operands and keys and a 64-bit
    co-sort of a million queries takes two minutes, so the program
    holds ONE sort a join at most, none of 64-bit keys, none stable,
    and the verified join's is its shared columns plus one payload."""
    import re

    fn, shapes, cap = _analytic_program()
    compiled = compile_for_chip(fn, *shapes)
    assert "tpu_custom_call" not in compiled.as_text()
    # the 3 M x 10 x 10 candidates never exist: everything the program
    # holds beside the store is a few arrays of left + right rows
    rows = ANALYTIC_CAPS["join_caps"][0] + cap
    assert compiled.memory_analysis().temp_size_in_bytes < rows * 4 * 12
    lowered = fn.lower(*shapes).as_text()
    sorts = re.findall(
        r'"stablehlo\.sort"\(([^)]*)\) <\{([^}]*)\}>.*?\}\) : \(([^)]*)\) ->',
        lowered, flags=re.S)
    assert len(sorts) == 1, "the verified join sorts once; nothing else does"
    operands, attrs, types = sorts[0]
    assert operands.count("%") == 3 and "is_stable = false" in attrs
    assert "i64" not in types
    _assert_the_first_join_searches_by_rows(
        jax.make_jaxpr(fn)(*shapes).jaxpr, cap, ANALYTIC_CAPS["term_caps"][0])


# -- the first join's expansion, alone ------------------------------------

#: (left slots, index keys) of the whole-store conjunction's FIRST join:
#: cell 5's, and a shard's of cell 6; 4,194,304 output slots in both
EXPANSION_SHAPES = {"cell5": (524_288, 2_961_251),
                    "cell6_shard": (1_048_576, 2_220_890)}
EXPANSION_SLOTS = 1 << 22
#: the parent's expansion (tree ed23f55) compiled for the described v5e
#: holds four `reduce-window`s (the running maximum over the slots) and
#: no sort, at both shapes
EXPANSION_PARENT_SCANS = 4


@pytest.mark.parametrize("shape", sorted(EXPANSION_SHAPES))
def test_the_expansion_reads_a_left_row_once(compile_for_chip, shape):
    """`_expand_index_ranges` alone at the analytic cells' shapes (PR
    48): over the 4.2 M slots the optimized text holds the owner's
    scatter, ONE packed row gather of the left side, `perm` and
    `targets`: three slot-long gather fusions where the parent's held
    six.  And no scan or sort the parent's lacks: the cells' first
    request compiles the program under a statement deadline, one more
    running maximum over the slots is 7-11 s of compile there and a
    sort operand 15-40 s (PERF.md section 6, PR 47)."""
    import re

    from das_tpu.obs.registry import INDEX_EXPAND_SCOPE
    from das_tpu.ops.join import _expand_index_ranges

    n_left, n_keys = EXPANSION_SHAPES[shape]

    def expansion(lv, lm, lo, cnt, offsets, perm, targets):
        return _expand_index_ranges(
            lv, lm, lo, cnt, offsets, offsets[-1], perm, targets,
            (0, 1), (1,), EXPANSION_SLOTS)

    lv, lm = _table(n_left, 2)
    text = compile_for_chip(
        expansion, lv, lm, _shape((n_left,), jnp.int32),
        _shape((n_left,), jnp.int64), _shape((n_left,), jnp.int64),
        _shape((n_keys,), jnp.int32), _shape((n_keys, 2), jnp.int32),
    ).as_text()
    slot_long = [
        line for line in text.splitlines()
        if "kind=kCustom" in line
        and re.match(rf"\s*(ROOT )?%\S+ = \w+\[{EXPANSION_SLOTS}[\],]", line)
    ]
    assert all(INDEX_EXPAND_SCOPE in line for line in slot_long)
    gathers = [line for line in slot_long if '/gather"' in line]
    assert len(gathers) == 3
    assert sum(f"s32[{EXPANSION_SLOTS},3]" in line for line in gathers) == 1
    # the rest is the owner's scatter: a loop and the fusion that holds it
    scatters = [line for line in slot_long if line not in gathers]
    assert len(scatters) <= 2
    assert all('/scatter-max"' in line for line in scatters)
    assert len(re.findall(r"= \S+ reduce-window\(", text)) <= EXPANSION_PARENT_SCANS
    assert not re.findall(r"= \S+ sort\(", text)


# -- the accepted cells' programs, letter for letter ----------------------

#: sha256 of the text LOWERED FOR THE DESCRIBED v5e (StableHLO, before
#: the chip's compiler; a lowering rule may differ by platform, and the
#: chip's is the one the cells run): `das_fused` / `das_fused_group` at
#: cell 1's shapes (cells 2 and 4 run the same two), `das_sharded` /
#: `das_sharded_group` at cell 3's, each `count_only` and not, for
#: `grounded3` and `shared2`, and the lone `das_fused` of `three_var`
#: at cell 5's.  The sixteen grounded programs are those of tree
#: bf5006f (PR 47's parent) and no PR since has moved them; `three_var`
#: is PR 49's, which MEANT to change it: its first join takes the slice
#: search, whose 22-step binary search became a descent of two gathered
#: rows of 128 separators (tree 6fda653, PR 48's, held the loop:
#: 0a2e31e7...; PR 48 had changed it too: from `ops/join.py
#: PACKED_EXPAND_MIN_SLOTS` output slots on the expansion reads a left
#: row once).  A lane of the grounded shapes holds 16-2,048 left rows
#: and 64-2,048 slots and stays under both rules, so none of the
#: sixteen may move.  Regenerate only when a PR means to change a
#: program, and says so: LOWERED_PRINT=1 prints the dict.
PARENT_LOWERED = {
    "das_fused.grounded3.count":
        "42fe0ed913056455928201ab3595aaf238623dc24bf227003efaecddd9851db4",
    "das_fused.grounded3.result":
        "b8bd64feeb949194266f7b174c96b77fd740009522864416a9638cc88ad952ee",
    "das_fused.shared2.count":
        "99732c7bae77871bfcc257803fb53db732cdc3fd627ae02a875c7b471bf697d6",
    "das_fused.shared2.result":
        "5e44be30cb8e857c7afb963b03db822e4363e8d04de4648bfe2a7fd9f4e33b46",
    "das_fused_group.grounded3.count":
        "91142e96d21491465928d797d8fbd5732cafa9fc288130228b1ef684ad2b67d0",
    "das_fused_group.grounded3.result":
        "f722d9961834851e40a1fc0c586a21f4b1c231786677eaed37bb8695f0a45745",
    "das_fused_group.shared2.count":
        "098c56ae5e4e42fedbd3ede55abc2af4c8e0ef2f7eef46799081d7bfe40553ae",
    "das_fused_group.shared2.result":
        "f8466868f71cd99f4a814586c0f470f66f7e445234c20fd54dbd94827e23bd25",
    "das_sharded.grounded3.count":
        "1ef455b0682fbdd774e0d16454aa1bcc1580b38071b4335450797e239b8f7cae",
    "das_sharded.grounded3.result":
        "0a4eda97b34ea32d77de080762e1bc6b5d7d58f662d0bba3f6e5d4758b2c49ff",
    "das_sharded.shared2.count":
        "3e524fd9887b5f24f07836b6c1cc8d457c3e8e6a81fe638e36ff4d20fa19291f",
    "das_sharded.shared2.result":
        "41673e19b8e0722d5b902a105a3a83d5d6d7e946c3201b87cd3f29287882c7c4",
    "das_sharded_group.grounded3.count":
        "c9d253537dff0da961a604b6d64037947f1def381e1a0567c5daea32a1344180",
    "das_sharded_group.grounded3.result":
        "068236d0ecd31010380e87601a095c2ec3779859e67a519630f4e58081acea56",
    "das_sharded_group.shared2.count":
        "e9d37e2ade0bcb16c092a0c6b73c425c710293e1215b0ac66315edf485884f52",
    "das_sharded_group.shared2.result":
        "5452857022626f68fe5058e04c6ca34174f2ccc67ba5336274e3809089b66730",
    "das_fused.three_var.result":
        "03b0e41c5045b38374d16c0e0db988bc948d3335d40d0c29ea5de61783275a8e",
}


def _lowered_for_chip(topo, one_chip, name):
    from das_tpu.query import fused

    program, shape, what = name.split(".")
    count_only = what == "count"
    if program == "das_fused" and shape == "three_var":
        fn, shapes, _cap = _analytic_program()
    elif program.startswith("das_fused"):
        from das_tpu.query.fused import get_executor
        from das_tpu.storage.tensor_db import TensorDB

        db, plans = _tiny_store_and_query(
            lambda data: TensorDB(data, DasConfig()),
            n_clauses=3 if shape == "grounded3" else 2)
        fn, shapes = _cell1_program(
            get_executor(db)._exec_job(plans, False), shape, count_only,
            group=program.endswith("_group"))
    else:
        job, sig, per_shard = _cell3_job(shape)
        group = ((count_only, fused.GROUP_LANES)
                 if program.endswith("_group") else None)
        return _lower_on_described_mesh(
            topo, job, sig, per_shard, group, count_only).as_text()
    placed = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    return fn.lower(*placed).as_text()


LOWERED_CASES = [
    f"{program}.{shape}.{what}"
    for program in ("das_fused", "das_fused_group", "das_sharded",
                    "das_sharded_group")
    for shape in ("grounded3", "shared2")
    for what in ("count", "result")
] + ["das_fused.three_var.result"]


@pytest.mark.parametrize("name", LOWERED_CASES)
def test_the_accepted_cells_programs_are_the_parents(
        topo, one_chip, no_persistent_cache, name):
    import hashlib

    digest = hashlib.sha256(
        _lowered_for_chip(topo, one_chip, name).encode()).hexdigest()
    if os.environ.get("LOWERED_PRINT"):
        print(f'\n    "{name}":\n        "{digest}",')
        return
    assert digest == PARENT_LOWERED[name]
