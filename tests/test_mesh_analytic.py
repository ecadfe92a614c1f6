"""The deployment `flybase-sharded4-analytic` on the CPU's virtual devices
(PR 47): the whole-store 3-clause conjunction
`And(Interacts($1,$2), Member($1,$3), Member($2,$3))` asked of a 4-shard
tenant, whose second join shares two variables with a LARGE left side
and therefore PARTITIONS both sides over the shards instead of gathering
the left onto every one (parallel/fused_sharded.py pair_join_partitions,
_partitioned_pair_join).

  * served over gRPC against the benchmark's plain reference rule, on
    two seeds, once through the partition and once through the gather;
  * the partitioned join alone against `ops/join.py whole_type_join` on
    one device and against the gathered join on the mesh: rows as sets,
    `total`, invalid left rows, a left value of -1, every left row bound
    for ONE destination, an output buffer one row short; both ways of
    filling the send buffer (scatter, sort);
  * an overflowing exchange slot and an overflowing output buffer are
    counted retries that end in the exact answer;
  * the static rule and the capacity seeds on the shapes of cells 3 and
    6 at scales 0.3 / 0.2 / 0.1;
  * the new counters and span attrs in a traced run, declared and read;
  * the cell `sharded4-analytic` rehearsed end to end.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmark.harness import spec
from benchmark.reference import generator, plain
from das_tpu import obs
from das_tpu.api.atomspace import DistributedAtomSpace, QueryOutputFormat
from das_tpu.core.config import DasConfig
from das_tpu.ops import join as join_ops
from das_tpu.parallel import fused_sharded as fs
from das_tpu.parallel.mesh import SHARD_AXIS, make_mesh
from das_tpu.planner.search import shard_cap_seed
from das_tpu.query import compiler
from das_tpu.service.query_dsl import parse_query
from das_tpu.storage.delta import capacity_class

pytestmark = [pytest.mark.sharded]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sharded4-analytic"
SCALE = 0.004
SEEDS = (2**31 + 47, 470047)
RULE = spec.load_rule("three_var")
DSL = spec.Cell(CELL).queries["three_var"]["dsl"]
S = 4
NEW_COUNTERS = ("mesh.partitioned_joins", "mesh.left_gathered_rows",
                "mesh.exchange_rows_max", "mesh.exchange_slots")


def _sharded_das(store, tmp_path) -> DistributedAtomSpace:
    """The store on a 4-shard mesh, configured as the cell configures
    it (cache off, the statement deadline)."""
    path = os.path.join(str(tmp_path), "kb.metta")
    generator.write_canonical(store, path)
    das = DistributedAtomSpace(
        database_name="mesh", backend="sharded",
        config=DasConfig.from_env(
            mesh_shape=(S,), **spec.Cell(CELL).config["das_config"]))
    das.load_canonical_knowledge_base(path)
    os.remove(path)
    return das


def _want(kb) -> list:
    return kb.canonical_rows(RULE.rows(kb, None), columns=RULE.COLUMNS)


def _job(das):
    plans = compiler.plan_query(das.db, parse_query(DSL))
    return fs.get_sharded_executor(das.db)._exec_job(list(plans), False)


def _run(job):
    while True:
        out = job.dispatch()
        if job.settle(jax.device_get(out), out):
            return job.result


def _rows(result) -> set:
    vals = np.asarray(result.host_vals if result.host_vals is not None
                      else result.vals)
    valid = np.asarray(result.host_valid if result.host_valid is not None
                       else result.valid)
    return set(map(tuple, vals.reshape(-1, vals.shape[-1])[
        valid.reshape(-1)].tolist()))


# -- the served path ---------------------------------------------------------


@pytest.fixture(scope="module", params=SEEDS, ids=lambda s: f"seed{s}")
def served(request, tmp_path_factory):
    from das_tpu.service.client import DasClient
    from das_tpu.service.server import serve

    store = generator.Store(SCALE, request.param)
    kb = plain.PlainKB(store)
    das = _sharded_das(store, tmp_path_factory.mktemp("kb"))
    server, service = serve(port=0, backend="sharded", block=False,
                            max_workers=8)
    token = service.attach_tenant("mesh", das)
    client = DasClient(port=server.bound_port)
    yield client, token, kb, das
    client.close()
    server.stop(0).wait()


@pytest.mark.parametrize("path", ["partition", "gather"])
def test_served_three_var_equals_the_plain_reference(served, path,
                                                     monkeypatch):
    client, token, kb, das = served
    if path == "gather":
        monkeypatch.setattr(fs, "pair_join_partitions",
                            lambda *shapes: False)
    want = _want(kb)
    assert len(want) > 1000
    ex = fs.get_sharded_executor(das.db)
    programs = set(ex._cache)
    before = dict(compiler.ROUTE_COUNTS)
    reply = client.call("query", key=token, output_format="HANDLE",
                        query=DSL)
    assert reply["success"], reply["msg"]
    assert plain.canonical_answer(reply["msg"]) == want
    moved = {k: compiler.ROUTE_COUNTS[k] - v for k, v in before.items()}
    assert moved["sharded"] == 1 and moved["staged"] == moved["host"] == 0
    # the program the request built says which way its second join went
    (sig, _count_only), = set(ex._cache) - programs
    assert sig.index_joins == (0, 0) and sig.exch_caps[0] == 0
    assert (sig.exch_caps[1] > 0) == (path == "partition")


# -- the join alone ----------------------------------------------------------


def _mesh_join(kind, left, lvalid, targets, type_ids, key, pairs, var_cols,
               extra, capacity, q):
    """The verified join on the 4-device mesh, `left` [S, n, k] and the
    store's slabs `targets` [S, m, a] / `type_ids` [S, m] row-sharded:
    per shard (vals, valid, total, occupancy), by the partition or by
    the gather of the left side."""
    mesh = make_mesh(S)
    moved = fs._Moved(S)

    def body(lv, lm, tg, ty):
        lv, lm, tg, ty = lv[0], lm[0], tg[0], ty[0]
        arrays = (None, None, tg, ty)
        if kind == "partition":
            v, m, total, occ = fs._partitioned_pair_join(
                lv, lm, arrays, key, pairs, var_cols, extra, capacity, S, q,
                moved)
        else:
            lv_full, lm_full = fs._gather_packed(lv, lm, moved)
            v, m, total = join_ops.whole_type_join(
                lv_full, lm_full, arrays, key, pairs, var_cols, extra,
                capacity)
            occ = jnp.int32(0)
        return v[None], m[None], total[None], occ[None]

    spec_ = P(SHARD_AXIS)
    out = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec_,) * 4,
                            out_specs=(spec_,) * 4))(
        jnp.asarray(left), jnp.asarray(lvalid), jnp.asarray(targets),
        jnp.asarray(type_ids))
    return tuple(np.asarray(o) for o in out)


def _one_device(left, lvalid, targets, type_ids, key, pairs, var_cols, extra,
                capacity):
    k, a = left.shape[-1], targets.shape[-1]
    vals, valid, total = join_ops.whole_type_join(
        jnp.asarray(left.reshape(-1, k)), jnp.asarray(lvalid.reshape(-1)),
        (None, None, jnp.asarray(targets.reshape(-1, a)),
         jnp.asarray(type_ids.reshape(-1))),
        key, pairs, var_cols, extra, capacity)
    return np.asarray(vals), np.asarray(valid), int(total)


def _as_set(vals, valid) -> set:
    return set(map(tuple, vals.reshape(-1, vals.shape[-1])[
        valid.reshape(-1)].tolist()))


def _tables(seed, n=96, m=160, values=12, k=3):
    """Random left rows [S, n, k] and slabs [S, m, 2] of two link types
    over a few values, so that keys repeat on both sides and across
    shards."""
    rng = np.random.default_rng(seed)
    left = rng.integers(0, values, size=(S, n, k), dtype=np.int32)
    lvalid = rng.random((S, n)) < 0.8
    targets = rng.integers(0, values, size=(S, m, 2), dtype=np.int32)
    type_ids = rng.integers(7, 9, size=(S, m), dtype=np.int32)
    return left, lvalid, targets, type_ids


PAIRS = ((1, 0), (2, 1))      # left columns 1, 2 = right variables 0, 1
TYPE = np.int64(7)


@pytest.mark.parametrize("place", ["scatter", "sort"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_partitioned_join_is_the_join(seed, place, monkeypatch):
    """Rows as a set and `total` against the one-device join and the
    gathered join; invalid left rows (whose values would match) take no
    part; the sort-filled send buffer holds the scatter-filled one's
    rows."""
    if place == "sort":
        monkeypatch.setattr(fs, "SCATTER_PLACE_MAX_ROWS", 8)
    left, lvalid, targets, type_ids = _tables(seed)
    args = (TYPE, PAIRS, (0, 1), (), 4096)
    vals1, valid1, total1 = _one_device(left, lvalid, targets, type_ids, *args)
    want = _as_set(vals1, valid1)
    assert total1 == int(valid1.sum()) > len(want) > 0    # duplicates too
    for kind in ("partition", "gather"):
        v, m, total, occ = _mesh_join(kind, left, lvalid, targets, type_ids,
                                      *args, q=256)
        assert _as_set(v, m) == want
        assert int(total.sum()) == total1 == int(m.sum())
        assert (0 < int(occ.max()) <= 256) == (kind == "partition")
    # every invalid left row is out, though its values match right rows
    dead = left[~lvalid]
    live_keys = {tuple(r[1:]) for r in left[lvalid]}
    assert any(tuple(r[1:]) not in live_keys and
               any((targets[type_ids == 7] == r[1:]).all(axis=1))
               for r in dead)


def test_a_right_extra_column_rides_through_the_exchange():
    """Three right variables, two shared: the third comes out as the
    output's last column, from the RECEIVED table."""
    rng = np.random.default_rng(5)
    left = rng.integers(0, 6, size=(S, 40, 2), dtype=np.int32)
    lvalid = np.ones((S, 40), dtype=bool)
    targets = rng.integers(0, 6, size=(S, 60, 3), dtype=np.int32)
    type_ids = np.full((S, 60), 7, dtype=np.int32)
    args = (TYPE, ((0, 0), (1, 2)), (0, 1, 2), (1,), 8192)
    vals1, valid1, total1 = _one_device(left, lvalid, targets, type_ids, *args)
    v, m, total, _occ = _mesh_join("partition", left, lvalid, targets,
                                   type_ids, *args, q=128)
    assert v.shape[-1] == 3 and _as_set(v, m) == _as_set(vals1, valid1)
    assert int(total.sum()) == total1 > 0


def test_a_left_value_of_minus_one_pairs_with_dangling_targets_alone():
    """-1 is a dangling target's value in the store's rows; a left row
    that carries it pairs with exactly the right rows that do, of the
    probed type, as on one device."""
    left, lvalid, targets, type_ids = _tables(3)
    left[:, :5, 1] = -1
    lvalid[:, :5] = True
    targets[0, :3, 0] = -1
    type_ids[0, :3] = (7, 7, 8)
    args = (TYPE, PAIRS, (0, 1), (), 4096)
    vals1, valid1, total1 = _one_device(left, lvalid, targets, type_ids, *args)
    want = _as_set(vals1, valid1)
    for kind in ("partition", "gather"):
        v, m, total, _occ = _mesh_join(kind, left, lvalid, targets, type_ids,
                                       *args, q=256)
        assert _as_set(v, m) == want and int(total.sum()) == total1
    dangling = {r for r in want if r[1] == -1}
    assert all(any((targets[0, :2] == r[1:]).all(axis=1)) for r in dangling)


def test_every_left_row_bound_for_one_destination():
    """All left rows share one key, so one shard owns them all: the
    occupancy says what did not fit, and slots that hold them give the
    exact join."""
    left, lvalid, targets, type_ids = _tables(4, n=64)
    left[..., 1:] = (3, 5)
    lvalid[:] = True
    targets[:, :4] = (3, 5)
    type_ids[:, :4] = 7
    args = (TYPE, PAIRS, (0, 1), (), 1 << 14)
    vals1, valid1, total1 = _one_device(left, lvalid, targets, type_ids, *args)
    v, m, total, occ = _mesh_join("partition", left, lvalid, targets,
                                  type_ids, *args, q=16)
    assert int(occ.max()) == 64 > 16       # a shard's rows for ONE owner
    assert int(total.sum()) < total1       # rows were dropped: retry
    v, m, total, occ = _mesh_join("partition", left, lvalid, targets,
                                  type_ids, *args, q=64)
    assert int(occ.max()) == 64
    assert _as_set(v, m) == _as_set(vals1, valid1)
    assert int(total.sum()) == total1 == 64 * S * int(
        ((targets == (3, 5)).all(axis=-1) & (type_ids == 7)).sum())
    # ONE shard holds the whole join
    assert sorted(int(t) for t in total)[:-1] == [0, 0, 0]


def test_an_output_buffer_one_row_short():
    left, lvalid, targets, type_ids = _tables(6)
    args = (TYPE, PAIRS, (0, 1), ())
    _v, _m, total, _occ = _mesh_join("partition", left, lvalid, targets,
                                     type_ids, *args, 4096, q=256)
    worst = int(total.max())
    v, m, short, _occ = _mesh_join("partition", left, lvalid, targets,
                                   type_ids, *args, worst - 1, q=256)
    # the exact totals, whatever fitted: the host grows THAT buffer
    assert [int(t) for t in short] == [int(t) for t in total]
    assert int(m.sum()) == int(total.sum()) - 1


# -- retries end in the exact answer ----------------------------------------


@pytest.fixture(scope="module")
def local(tmp_path_factory):
    store = generator.Store(SCALE, SEEDS[0])
    kb = plain.PlainKB(store)
    return _sharded_das(store, tmp_path_factory.mktemp("kb")), kb


@pytest.fixture()
def traced():
    was = obs.enabled()
    obs.configure(enabled=True)
    obs.reset()
    yield
    obs.reset()
    obs.configure(enabled=was)


@pytest.mark.parametrize("short", ["exchange_slots", "output_buffer"])
def test_an_overflow_is_a_counted_retry_then_the_exact_answer(local, traced,
                                                              short):
    das, kb = local
    exact = _job(das)
    want = _rows(_run(exact))
    assert len(want) == len(RULE.rows(kb, None)) and exact.rounds == 1
    obs.reset()
    job = _job(das)
    assert job.exch_caps[1] > 0
    if short == "exchange_slots":
        job.exch_caps = (0, 16)
    else:
        job.join_caps = (job.join_caps[0], 64)
    result = _run(job)
    assert job.rounds >= 2 and _rows(result) == want
    assert result.count == len(want)
    assert obs.counter("mesh.retries").value == job.rounds - 1
    if short == "exchange_slots":
        assert job.exch_caps[1] >= max(exact.last_exch_rows) > 16
    else:
        assert job.join_caps[1] >= max(exact.last_join_rows[1], 65)


# -- the static rule and the seeds ------------------------------------------

#: FlyBase shape x scale: (Interacts rows, Member rows, links of arity 2)
CELL_STORES = {
    0.3: (900_000, 7_200_000, 8_361_000),
    0.2: (600_000, 4_800_000, 5_574_000),
    0.1: (300_000, 2_400_000, 2_787_000),
}
#: the mesh job's capacities for the whole-store conjunction there:
#: (Interacts term, first join, exchange slots of the second)
CELL6_CAPS = {
    0.3: (262_144, 4_194_304, 1_048_576),
    0.2: (262_144, 2_097_152, 524_288),
    0.1: (131_072, 2_097_152, 262_144),
}


@pytest.mark.parametrize("scale", sorted(CELL_STORES))
def test_the_rule_on_the_cells_shapes(local, scale):
    """Cell 6's second join partitions at every scale of its rule, its
    first never; no join of cell 3 does (`tests/test_tpu_compile.py`
    pins their lowered text).  The seeds are the rules' arithmetic."""
    das, _kb = local
    ex = fs.get_sharded_executor(das.db)
    interacts, member, links = CELL_STORES[scale]
    slab = capacity_class(-(-links // S))
    term, first, slots = CELL6_CAPS[scale]
    assert ex._shard_cap(interacts) == term
    rows = interacts * 10                       # Interacts x Member
    # cost.cap_for(exact=True) under the default max_result_capacity
    one_chip = min(1 << (rows.bit_length()), 1 << 24)
    assert shard_cap_seed(one_chip, rows, S) == first
    assert ex._exchange_slots(rows, member) == slots
    # the first join: ONE shared variable, gathers whatever the sizes
    assert not fs.pair_join_partitions(1, term, S, slab)
    assert not fs.pair_join_partitions(1, 1 << 30, S, slab)
    # the second: two shared variables, the gathered left outweighs
    assert fs.pair_join_partitions(2, first, S, slab)
    assert S * first > slab
    # cell 3 (the same store at 0.3): 1,024-row left sides
    assert not fs.pair_join_partitions(2, 1024, S, slab)
    assert not fs.pair_join_partitions(1, 1024, S, slab)


def test_short_tables_keep_their_seeds():
    """`shard_cap_seed` below LARGE_SHARE_ROWS a shard and `_shard_cap`
    below LARGE_RANGE_ROWS are the rules cell 3's signatures were built
    by: the even split of the one-chip seed, doubled, then the power of
    two."""
    for cap, rows in ((2048, 1300), (64, 16), (1 << 20, 700_000),
                      (1 << 22, 3_000_000)):
        legacy = 64
        while legacy < 2 * -(-cap // S):
            legacy *= 2
        assert shard_cap_seed(cap, rows, S) == legacy
    # a long table whose figure was an ESTIMATE keeps the estimate's
    # margin (cost.CAP_MARGIN): 2 x the share, an eighth on top
    assert shard_cap_seed(1 << 25, 9_000_000, S) == 1 << 23
    assert shard_cap_seed(1 << 24, 9_000_000, S) == 1 << 22


# -- the names ---------------------------------------------------------------


def test_counters_and_attrs_in_a_traced_run(local, traced, monkeypatch):
    das, kb = local
    job = _job(das)
    out = das.query_many_dispatch([parse_query(DSL)],
                                  QueryOutputFormat.HANDLE).settle()
    assert plain.canonical_answer(out[0]) == _want(kb)
    term, first = job.term_caps[0], job.join_caps[0]
    assert obs.counter("mesh.partitioned_joins").value == 1
    # the first join's left side alone was gathered
    assert obs.counter("mesh.left_gathered_rows").value == S * term
    slots = obs.counter("mesh.exchange_slots").value
    rows = obs.counter("mesh.exchange_rows_max").value
    assert slots == job.exch_caps[1] and 0 < rows <= slots
    assert obs.counter("mesh.retries").value == 0
    verdicts = [attrs for name, _ph, _t, _d, _tr, _g, _lane, _th, attrs
                in obs.events() if name == "exec.verdict"]
    (attrs,) = verdicts
    assert attrs["done"] and attrs["partitioned"] == 1
    assert attrs["exchange_fill"] == pytest.approx(rows / slots)
    # through the gather, the SECOND join's left side is gathered too
    obs.reset()
    monkeypatch.setattr(fs, "pair_join_partitions", lambda *shapes: False)
    out = das.query_many_dispatch([parse_query(DSL)],
                                  QueryOutputFormat.HANDLE).settle()
    assert plain.canonical_answer(out[0]) == _want(kb)
    assert obs.counter("mesh.left_gathered_rows").value == S * (term + first)
    assert obs.counter("mesh.partitioned_joins").value == 0
    assert obs.counter("mesh.exchange_slots").value == 0


@pytest.mark.parametrize("name", NEW_COUNTERS + ("mesh.pair_partition",))
def test_every_new_name_is_declared_and_has_a_reader(name):
    """Declared in obs/registry.py and read by a per-layer metric of
    the benchmark (`mesh.partitioned_joins`: by the Prometheus
    exposition and the `partitioned` attr's test above)."""
    from das_tpu.obs import registry

    readers = os.path.join(ROOT, "benchmark", "layer_metrics")
    helper = os.path.join(ROOT, "benchmark", "harness", "mesh_scope.py")
    texts = [open(os.path.join(readers, f)).read()
             for f in sorted(os.listdir(readers)) if f.startswith("mesh.")]
    if name == "mesh.pair_partition":
        assert registry.PAIR_PARTITION_SCOPE == name
        assert f'"{name}"' in open(helper).read()
        assert sum("PAIR_PARTITION_SCOPE" in t for t in texts) == 2
        return
    assert name in obs.COUNTER_NAMES
    assert name.replace(".", "_") in obs.prometheus_text()
    if name != "mesh.partitioned_joins":
        assert any(name in t for t in texts), f"no reader for {name}"


# -- the cell ----------------------------------------------------------------


def test_the_cell_rehearsed_end_to_end():
    """`benchmark/run.py --rehearse`: every phase of the cell at scale
    0.004 with the look for a chip skipped, in a process of its own on
    FOUR virtual devices (tests/test_mesh_cell.py says why)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 47), "--seconds", "3",
         "--trace", "0", "--rehearse", str(SCALE)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stderr[-2000:]   # rehearsed, no chip
    result = next(json.loads(line) for line in proc.stderr.splitlines()
                  if line.startswith('{"correct"'))
    logs = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert {"query_p50_ms", "query_p95_ms", "setup_s"} <= set(result["metrics"])
    window = next(c for c in logs if c.get("log") == "window")
    counters = window["counters"]
    assert counters["route.sharded"] == window["requests"] > 0
    assert counters.get("route.staged", 0) == counters.get("route.host", 0) == 0
    assert counters["coalescer.deadline_expired"] == 0
    assert counters["planner.retries"] == 0
    assert window["nonempty_answers_compared"] == window["requests"]
