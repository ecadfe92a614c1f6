"""The described TPU v5e, shared by the `tests/test_tpu_compile*.py` files.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached.  This module holds what those files
share: the fixtures that describe the topology and the helpers that
build the programs' shapes.  It is no test file and is imported by name;
a fixture takes effect in the test module that imports it, at that
module's scope.

Rules these keep (on-chip-measurement guide, section 2): the topology is
described inside a module-scoped fixture that skips when it cannot be,
never while a module is imported, not autouse, not in conftest.py; no
child process; the persistent compilation cache is off around the
compiles (an entry written for a described device cannot be read back).
"""

import collections
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from das_tpu.obs.registry import INDEX_JOIN_SCOPE, INDEX_SEARCH_SCOPE
from das_tpu.storage.delta import capacity_class

#: chip_smoke.py's default store: links of arity 2 at --scale 0.1
#: (2.4 M Member + ~0.3 M Interacts + 43.5 k List + 43.5 k Evaluation)
SMOKE_ARITY2_ROWS = 2_786_998
SMOKE_ARITY2_CAPACITY = capacity_class(SMOKE_ARITY2_ROWS)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip — keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(one_chip, no_persistent_cache):
    def compile_(fn, *shapes):
        placed = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            shapes,
        )
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        return jitted.lower(*placed).compile()

    return compile_


def _shape(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _table(rows, cols):
    return _shape((rows, cols), jnp.int32), _shape((rows,), jnp.bool_)


def _tiny_store_and_query(make_db, n_clauses=3):
    """A tiny CPU store and the smoke's grounded 3-clause conjunction on
    it (or its first `n_clauses`: two are the benchmark's `shared2`): the
    plan signature is scale-free, only capacities and bucket lengths
    grow with the KB."""
    from das_tpu.models.bio import build_bio_atomspace
    from das_tpu.query import compiler
    from das_tpu.query.ast import And, Link, Node, Variable

    data, _, _ = build_bio_atomspace(
        n_genes=400, n_processes=40, members_per_gene=10,
        n_interactions=300, n_evaluations=60, seed=0,
    )
    db = make_db(data)
    g = db.get_all_nodes("Gene", names=True)[0]
    query = And([
        Link("Member", [Node("Gene", g), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Interacts", [Node("Gene", g), Variable("V2")], True),
    ][:n_clauses])
    return db, compiler.plan_query(db, query)


def _lower_on_described_mesh(topo, job, sig, per_shard, group=None,
                             count_only=False):
    return _trace_on_described_mesh(
        topo, job, sig, per_shard, group, count_only).lower()


def _trace_on_described_mesh(topo, job, sig, per_shard, group=None,
                             count_only=False):
    """The fused shard_map program of `sig`, traced against a Mesh
    built from the described v5e:2x2 devices, the job's row-sharded
    bucket arrays stretched to `per_shard` rows a shard.
    `group`: `(count_only, lanes)` for the GROUP program over `lanes`
    lanes of the job's inputs, every lane its own gene; else the lone
    program, `count_only` or not."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from das_tpu.parallel import fused_sharded as fs
    from das_tpu.parallel.mesh import SHARD_AXIS
    from das_tpu.query import fused

    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))
    sharded, replicated = NamedSharding(mesh, P(SHARD_AXIS)), NamedSharding(mesh, P())

    def slab(a):  # [S, m(, a)] -> the store's per-shard rows
        return jax.ShapeDtypeStruct(
            (4, per_shard, *a.shape[2:]), a.dtype, sharding=sharded
        )

    def scalar_or_vec(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated)

    keys, fvals = job.keys, job.fvals
    if group is None:
        fn, _names = fs.build_fused_sharded(sig, mesh, count_only)
    else:
        count_only, lanes = group
        # the lanes' inputs as dispatch_group stacks them: the grounded
        # terms' probe keys differ a lane, the whole-type term's key is
        # hoisted
        hoisted = sig.index_joins.index(1) + 1
        keys, key_axes, fvals, fval_axes = fused.stack_lanes(
            [tuple(np.asarray(k) + (i if t != hoisted else 0)
                   for t, k in enumerate(job.keys)) for i in range(lanes)],
            [job.fvals] * lanes, lanes,
        )
        assert None in key_axes and 0 in key_axes
        fn, _names = fs.build_fused_sharded_group(
            sig, mesh, count_only, key_axes, fval_axes)
    return jax.jit(fn).trace(
        jax.tree.map(slab, job.arrays),
        jax.tree.map(scalar_or_vec, keys),
        jax.tree.map(scalar_or_vec, fvals),
    )


def _compile_on_described_mesh(topo, job, sig, per_shard, group=None):
    return _lower_on_described_mesh(topo, job, sig, per_shard, group).compile()


def _as_shape(x):
    x = np.asarray(x)
    return _shape(x.shape, x.dtype)


def _three_var_plans(make_db):
    """A tiny store and the all-variable 3-clause conjunction's plans
    on it (the benchmark's `three_var`)."""
    from das_tpu.models.bio import build_bio_atomspace
    from das_tpu.query import compiler
    from das_tpu.query.ast import And, Link, Variable

    data, _, _ = build_bio_atomspace(
        n_genes=60, n_processes=12, members_per_gene=3, n_interactions=40,
        seed=5)
    db = make_db(data)
    v = Variable
    return db, list(compiler.plan_query(db, And([
        Link("Interacts", [v("V1"), v("V2")], True),
        Link("Member", [v("V1"), v("V3")], True),
        Link("Member", [v("V2"), v("V3")], True),
    ])))


#: the running sums, maxima and minima the parent's first join holds
#: (tree 6fda653, the one-chip program and a shard's alike): the
#: reverse minimum behind `run_end`, the prefix sum's two 32-bit sums,
#: the slot owner's maximum.  One more over 0.5-4 M elements is 7-50 s
#: of a first request's compile (ops/join.py SLOW_SCAN_ROWS)
PARENT_FIRST_JOIN_SCANS = {"cummin": 1, "cumsum": 2, "cummax": 1}


def _assert_the_first_join_searches_by_rows(jaxpr, n_keys, n_left):
    """The FIRST join (524,288 left rows into the 2.96 M-key index; a
    shard's 1,048,576 into 2.22 M) holds NO loop: the 22 dependent
    one-word gathers of its binary search were 39-44 % of the program
    (PERF.md section 6, PR 49).  Under `join.index_search` it holds ONE
    gather of a row of `SEARCH_FANOUT` int32 words a level below the
    root and nothing else that reads by index, and the join as a whole
    no sort and no running sum, maximum or minimum the parent's
    lacks."""
    from das_tpu.ops.join import SEARCH_FANOUT, _search_levels

    joined = _primitives_under(jaxpr, INDEX_JOIN_SCOPE)
    counts = collections.Counter(eqn.primitive.name for eqn in joined)
    assert not {"while", "scan", "sort"} & set(counts)
    assert {name: counts[name] for name in counts
            if name.startswith("cum")} == PARENT_FIRST_JOIN_SCANS
    searched = _primitives_under(jaxpr, INDEX_SEARCH_SCOPE)
    assert {id(eqn) for eqn in searched} <= {id(eqn) for eqn in joined}
    gathers = [eqn for eqn in searched if eqn.primitive.name == "gather"]
    levels = _search_levels(n_keys)
    assert len(gathers) == len(levels) - 1 and len(levels) <= 6
    for eqn, rows in zip(gathers, levels[-2::-1]):      # from the top down
        assert eqn.invars[0].aval.shape == (rows, SEARCH_FANOUT)
        assert eqn.invars[0].aval.dtype == jnp.int32
        assert eqn.outvars[0].aval.shape == (n_left, SEARCH_FANOUT)
    assert not [eqn for eqn in searched
                if eqn.primitive.name in ("scatter", "dynamic_slice")]
    # no 64-bit element is read by index anywhere in the join: on the
    # chip an int64 gather is two u32 gathers (PERF.md section 6, PR 45)
    for eqn in joined:
        if eqn.primitive.name == "gather":
            assert eqn.invars[0].aval.dtype != jnp.int64


def _inner_jaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _primitives_under(jaxpr, scope, stack=""):
    """The equations whose name stack, from the program's root down,
    holds `scope`; a call's own equation (`pjit`, `shard_map`, a loop)
    and what its bodies hold both count where they lie under it."""
    found = []
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if scope in here:
            found.append(eqn)
        for sub in _inner_jaxprs(eqn):
            found += _primitives_under(sub, scope, here)
    return found
