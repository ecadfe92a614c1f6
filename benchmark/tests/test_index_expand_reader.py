"""`ops.index_expand_ms_per_query` (PR 48): its entry, and its reader
on a hand-made profiler file of two device planes whose first join
holds the expansion's scope NESTED in `join.index_probe`."""

import pytest

from benchmark.harness import spec
from test_mesh_analytic_cell import (MODULES, ROOT_SCOPE, _answers, _plane,
                                     _trace, _window)
from test_analytic_cell import _field

NAME = "ops.index_expand_ms_per_query"
CELLS = ["mem-analytic", "sharded4-analytic"]
MS = 1_000_000
PROBE = ROOT_SCOPE + "join/join.index_probe/"


def _ops(scale: float):
    return [
        ("%while.29 = (s32[64]{0}) while()", PROBE + "while:", 2 * MS, 80 * MS),
        ("%fusion.6 = s32[64,3]{0,1} fusion()",
         PROBE + "join.index_expand/gather:", 100 * MS, int(30 * MS * scale)),
        # overlapping the first by 5 ms: the union counts once
        ("%fusion.7 = s32[64]{0} fusion()",
         PROBE + "join.index_expand/gather:", 125 * MS, int(25 * MS * scale)),
        ("%sort.4 = (s32[64]{0}) sort()",
         ROOT_SCOPE + "join/join.pair_verify/sort:", 300 * MS, 20 * MS),
    ]


PLANES = [_ops(1.0), _ops(1.2)]


@pytest.fixture()
def xplane(tmp_path):
    path = tmp_path / "expand.xplane.pb"
    path.write_bytes(b"".join(_plane(d, ops, MODULES)
                              for d, ops in enumerate(PLANES))
                     + _field(1, _field(2, "/host:CPU")))
    return str(path)


def test_it_is_listed_for_the_two_analytic_cells():
    for cell_name in CELLS:
        cell = spec.Cell(cell_name)
        (entry,) = [m for m in cell.per_layer if m["name"] == NAME]
        assert entry == {
            "name": NAME, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "ops",
            "moves": "query_p50_ms", "workloads": CELLS}
        assert callable(cell.layer_reader(NAME))
    assert NAME not in {m["name"] for m in spec.Cell("mem-uniform-closed").per_layer}


def test_chip_time_under_the_nested_scope_per_answer(xplane):
    read = spec.Cell(CELLS[0]).layer_reader(NAME)
    spans = _answers(4) + _answers(3, t=20.0)       # three outside the slice
    # a plane: [100, 150] ms; the second a fifth longer: [100, 155]
    assert read(spans, {}, _trace(PLANES), _window(xplane)) == pytest.approx(
        (50.0 + 55.0) / 2 / 4)
    assert read(spans, {}, None, _window(xplane)) is None
    assert read([], {}, _trace(PLANES), _window(xplane)) is None


def test_a_program_without_the_scope_reads_nothing(xplane):
    """The parent's program: the same operations straight under
    `join.index_probe`."""
    read = spec.Cell(CELLS[1]).layer_reader(NAME)
    bare = [[(n, p.replace("join.index_expand/", ""), s, t)
             for n, p, s, t in ops] for ops in PLANES]
    path = xplane + ".bare"
    with open(path, "wb") as fh:
        fh.write(b"".join(_plane(d, ops, MODULES)
                          for d, ops in enumerate(bare)))
    assert read(_answers(4), {}, _trace(bare), _window(path)) is None
