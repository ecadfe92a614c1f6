"""`ops.index_search_ms_per_query` (PR 49): its entry, and its reader
on a hand-made profiler file of device planes whose first join holds
the search's scope NESTED in `join.index_probe`, beside the expansion's."""

import pytest

from benchmark.harness import spec
from test_mesh_analytic_cell import (MODULES, ROOT_SCOPE, _answers, _plane,
                                     _trace, _window)
from test_analytic_cell import _field

NAME = "ops.index_search_ms_per_query"
CELLS = ["mem-analytic", "sharded4-analytic"]
MS = 1_000_000
PROBE = ROOT_SCOPE + "join/join.index_probe/"
SEARCH = PROBE + "join.index_search/"


def _ops(scale: float):
    return [
        # the passes that make `words` and `run_end`: the join's, not
        # the search's
        ("%fusion.2 = s32[64]{0} fusion()", PROBE + "select_n:", 2 * MS, 9 * MS),
        ("%fusion.11 = s32[64,128]{1,0} fusion()", SEARCH + "gather:",
         20 * MS, int(10 * MS * scale)),
        # overlapping the first by 2 ms: the union counts once
        ("%fusion.12 = s32[64]{0} fusion()", SEARCH + "reduce_sum:",
         28 * MS, int(5 * MS * scale)),
        ("%fusion.13 = s32[64,128]{1,0} fusion()", SEARCH + "gather:",
         40 * MS, int(15 * MS * scale)),
        ("%fusion.6 = s32[64,3]{0,1} fusion()",
         PROBE + "join.index_expand/gather:", 100 * MS, 30 * MS),
        ("%sort.4 = (s32[64]{0}) sort()",
         ROOT_SCOPE + "join/join.pair_verify/sort:", 300 * MS, 20 * MS),
    ]


PLANES = [_ops(1.0), _ops(1.2), _ops(0.8), _ops(1.0)]


def _write(path, planes):
    with open(path, "wb") as fh:
        fh.write(b"".join(_plane(d, ops, MODULES)
                          for d, ops in enumerate(planes))
                 + _field(1, _field(2, "/host:CPU")))
    return str(path)


@pytest.fixture()
def xplane(tmp_path):
    return _write(tmp_path / "search.xplane.pb", PLANES)


def test_it_is_the_last_entry_and_lists_the_two_analytic_cells():
    for cell_name in CELLS:
        cell = spec.Cell(cell_name)
        (entry,) = [m for m in cell.per_layer if m["name"] == NAME]
        assert entry == {
            "name": NAME, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "ops",
            "moves": "query_p50_ms", "workloads": CELLS}
        assert callable(cell.layer_reader(NAME))
    for cell_name in ("mem-uniform-closed", "wal-mixed95-closed",
                      "sharded4-uniform-closed", "mem-zipf-open"):
        assert NAME not in {m["name"] for m in spec.Cell(cell_name).per_layer}


def test_the_scope_is_the_programs_and_nested_in_the_joins():
    """The harness spells the name itself (it never imports the
    program): the two spellings agree, and the program opens the scope
    INSIDE `join.index_probe`, beside `join.index_expand`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from das_tpu.obs import registry
    from das_tpu.ops import join

    read = spec.Cell(CELLS[0]).layer_reader(NAME)
    assert read.__globals__["INDEX_SEARCH_SCOPE"] == registry.INDEX_SEARCH_SCOPE

    def first_join(lv, lm, keys, perm, targets):
        return join.whole_type_join(
            lv, lm, (keys, perm, targets, None), np.int32(4),
            ((0, 0),), (0, 1), (1,), 64)

    jaxpr = jax.make_jaxpr(first_join)
    # the rule answers "slice" whatever the shapes
    real = join.index_search_method
    join.index_search_method = lambda n_left, n_keys: join.SLICE_SEARCH
    try:
        eqns = jaxpr(
            jnp.zeros((8, 2), jnp.int32), jnp.ones((8,), bool),
            jnp.zeros((300,), jnp.int64), jnp.zeros((300,), jnp.int32),
            jnp.zeros((300, 2), jnp.int32)).jaxpr.eqns
    finally:
        join.index_search_method = real
    stacks = {str(e.source_info.name_stack) for e in eqns}
    searched = {s for s in stacks if registry.INDEX_SEARCH_SCOPE in s}
    assert searched
    assert all(s.startswith(registry.INDEX_JOIN_SCOPE) for s in searched)
    assert not any(registry.INDEX_EXPAND_SCOPE in s for s in searched)


def test_a_four_plane_trace_reads_the_planes_mean_per_answer(xplane):
    read = spec.Cell(CELLS[1]).layer_reader(NAME)
    spans = _answers(4) + _answers(3, t=20.0)       # three outside the slice
    # a plane at scale s: [20, 20 + 10 s] u [28, 28 + 5 s] u [40, 40 + 15 s]
    want = [(13.0 + 15.0), (14.0 + 18.0), (8.0 + 4.0 + 12.0), (13.0 + 15.0)]
    assert read(spans, {}, _trace(PLANES), _window(xplane)) == pytest.approx(
        sum(want) / 4 / 4)
    assert read(spans, {}, None, _window(xplane)) is None
    assert read([], {}, _trace(PLANES), _window(xplane)) is None


def test_one_plane_reads_that_plane(tmp_path):
    read = spec.Cell(CELLS[0]).layer_reader(NAME)
    path = _write(tmp_path / "one.xplane.pb", PLANES[:1])
    assert read(_answers(2), {}, _trace(PLANES[:1]), _window(path)) == (
        pytest.approx(28.0 / 2))


def test_a_program_without_the_scope_reads_nothing(xplane, tmp_path):
    """The parent's program: the search is a loop straight under
    `join.index_probe`."""
    read = spec.Cell(CELLS[1]).layer_reader(NAME)
    bare = [[(n, p.replace("join.index_search/", ""), s, t)
             for n, p, s, t in ops] for ops in PLANES]
    path = _write(tmp_path / "bare.xplane.pb", bare)
    assert read(_answers(4), {}, _trace(bare), _window(path)) is None
