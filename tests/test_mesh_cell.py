"""The deployment `flybase-sharded4` on the CPU's virtual devices (PR 29):
the FlyBase-shape store on `backend="sharded"`, served through the
normal path, against the benchmark's plain reference.

  * the cell `sharded4-uniform-closed` rehearsed end to end at scale
    0.002 (the harness's look for a chip skipped);
  * served `grounded3` / `shared2` answers of a sharded tenant over gRPC
    equal `PlainKB`'s canonical rows, on two seeds, for a key whose
    answer is empty and keys whose answers are not;
  * an answer the STAGED mesh pipeline gave because the fused mesh
    program declined counts as `staged` (and `mesh.staged_fallbacks`),
    not as `sharded`: the harness's limit
    `route.staged_delta == 0` holds the mesh as it holds one chip;
  * what the mesh adds to the tracing (`mesh.fetch`, `mesh.dedup`,
    `mesh.collective_bytes`, `mesh.retries`, `mesh.staged_fallbacks`)
    appears in a traced run, is declared, and has a reader.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark.reference import generator, plain
from das_tpu import obs
from das_tpu.api.atomspace import DistributedAtomSpace, QueryOutputFormat
from das_tpu.core.config import DasConfig
from das_tpu.query import compiler
from das_tpu.service.query_dsl import parse_query

pytestmark = [pytest.mark.sharded]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sharded4-uniform-closed"
SCALE = 0.002
SEEDS = (2**31 + 29, 1234567)
MESH_SPANS = ("mesh.fetch", "mesh.dedup")
MESH_COUNTERS = ("mesh.collective_bytes", "mesh.retries",
                 "mesh.staged_fallbacks")


def _queries() -> dict:
    out = {}
    for shape in ("grounded3", "shared2"):
        with open(os.path.join(ROOT, "benchmark", "queries",
                               shape + ".json")) as fh:
            out[shape] = json.load(fh)
    return out


QUERIES = _queries()


def _dsl(shape: str, gene: int) -> str:
    return QUERIES[shape]["dsl"].format(key=generator.gene_name(gene))


def _want(kb, shape: str, gene: int) -> list:
    return kb.canonical_rows(kb.rows(QUERIES[shape]["reference_rule"], gene))


def _sharded_das(store, tmp_path) -> DistributedAtomSpace:
    """The store on a 4-shard mesh, loaded as the cell loads it."""
    path = os.path.join(str(tmp_path), "kb.metta")
    generator.write_canonical(store, path)
    das = DistributedAtomSpace(
        database_name="mesh", backend="sharded",
        config=DasConfig.from_env(mesh_shape=(4,)))
    das.load_canonical_knowledge_base(path)
    os.remove(path)
    return das


def _keys(kb) -> dict:
    """One gene per case: grounded3 with rows, grounded3 without, and
    shared2 (never empty: a gene shares its processes with itself)."""
    full = next(g for g in range(kb.store.n_genes)
                if kb.rows("grounded3", g))
    empty = next(g for g in range(kb.store.n_genes)
                 if not kb.rows("grounded3", g))
    return {"grounded3-rows": ("grounded3", full),
            "grounded3-empty": ("grounded3", empty),
            "shared2-rows": ("shared2", empty)}


@pytest.fixture(scope="module", params=SEEDS, ids=lambda s: f"seed{s}")
def served(request, tmp_path_factory):
    """(client, token, kb, das) of one seed's store behind the gRPC
    service, the tenant attached as `cell.py` attaches it."""
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


def test_every_shards_rows_add_up_to_the_stores_links(served):
    _client, _token, kb, das = served
    assert das.db.tables.n_shards == 4
    held = sum(int(b.slab_sizes.sum()) for b in das.db.tables.buckets.values())
    assert held == kb.counts()[1] == das.count_atoms()[1]
    for b in das.db.tables.buckets.values():
        assert int(b.slab_sizes.max()) - int(b.slab_sizes.min()) <= 1


@pytest.mark.parametrize("case", ["grounded3-rows", "grounded3-empty",
                                  "shared2-rows"])
def test_served_sharded_answers_equal_the_plain_reference(served, case):
    client, token, kb, _das = served
    shape, gene = _keys(kb)[case]
    want = _want(kb, shape, gene)
    assert bool(want) == case.endswith("rows")
    before = dict(compiler.ROUTE_COUNTS)
    reply = client.call("query", key=token, output_format="HANDLE",
                        query=_dsl(shape, gene))
    assert reply["success"], reply["msg"]
    assert plain.canonical_answer(reply["msg"]) == want
    assert compiler.ROUTE_COUNTS["host"] == before["host"]
    assert compiler.ROUTE_COUNTS["staged"] == before["staged"]


def test_the_cell_rehearsed_end_to_end():
    """`benchmark/run.py --rehearse`: every phase of the cell at scale
    0.002 with the look for a chip skipped.  In a process of its own on
    FOUR virtual devices, the cell's mesh: on this suite's eight, the
    CPU backend's collectives share a pool of as many threads as the
    machine has cores, and the 64 clients' programs in flight can fill
    it with parts of different programs, each waiting for peers that
    never get a thread (XLA ends the process after 40 s).  A chip has
    no such pool."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "3",
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
    compared = {c["name"]: c for c in logs if c.get("log") == "compare"}
    for name in ("route.host_delta", "route.staged_delta",
                 "per_query_dispatcher_calls", "wrong_answers"):
        assert compared[name]["value"] == 0 and compared[name]["ok"]
    window = next(c for c in logs if c.get("log") == "window")
    assert window["counters"]["route.sharded"] > 0
    assert window["counters"]["route.sharded"] >= window["latency_samples"]


@pytest.fixture()
def traced():
    was = obs.enabled()
    obs.configure(enabled=True)
    obs.reset()
    yield
    obs.reset()
    obs.configure(enabled=was)


@pytest.fixture(scope="module")
def local(tmp_path_factory):
    store = generator.Store(SCALE, SEEDS[0])
    kb = plain.PlainKB(store)
    return _sharded_das(store, tmp_path_factory.mktemp("kb")), kb


def _many(das, kb, genes):
    qs = [parse_query(_dsl(s, g)) for s, g in genes]
    out = das.query_many_dispatch(qs, QueryOutputFormat.HANDLE).settle()
    for (s, g), answer in zip(genes, out):
        assert plain.canonical_answer(answer) == _want(kb, s, g)


def test_a_declined_mesh_program_counts_as_staged(local, traced, monkeypatch):
    from das_tpu.parallel.fused_sharded import get_sharded_executor

    das, kb = local
    keys = _keys(kb)
    genes = [keys["grounded3-rows"], keys["shared2-rows"]]
    # the fused mesh program declines every plan, as at a capacity ceiling
    monkeypatch.setattr(get_sharded_executor(das.db), "_exec_job",
                        lambda plans, count_only: None)
    before = dict(compiler.ROUTE_COUNTS)
    _many(das, kb, genes)
    moved = {k: compiler.ROUTE_COUNTS[k] - v for k, v in before.items()}
    assert moved["staged"] == len(genes)
    assert moved["sharded"] == moved["host"] == 0
    assert obs.counter("mesh.staged_fallbacks").value == len(genes)
    assert obs.counter("mesh.collective_bytes").value == 0


def test_mesh_spans_and_counters_in_a_traced_run(local, traced):
    das, kb = local
    keys = _keys(kb)
    # distinct from the other tests' keys, so nothing is a cache hit
    genes = [("shared2", keys["grounded3-rows"][1]),
             ("grounded3", keys["shared2-rows"][1] + 1)]
    before = dict(compiler.ROUTE_COUNTS)
    _many(das, kb, genes)
    assert compiler.ROUTE_COUNTS["sharded"] - before["sharded"] == len(genes)
    spans = {}
    for name, _ph, _t, dur, _tr, _g, _lane, _th, attrs in obs.events():
        spans.setdefault(name, []).append((dur, attrs))
    assert len(spans["mesh.fetch"]) == 1          # one settle round
    _dur, attrs = spans["mesh.fetch"][0]
    assert attrs["jobs"] == 2 and attrs["shards"] == 4 and attrs["bytes"] > 0
    assert len(spans["exec.settle_fetch"]) == 1
    # only the shared2 answer has rows to bring together
    (_d, dedup), = spans["mesh.dedup"]
    assert dedup["distinct"] == dedup["rows"] == len(
        _want(kb, *genes[0]))
    assert len(spans["exec.materialize"]) == 1
    assert obs.counter("mesh.collective_bytes").value > 0
    assert obs.counter("mesh.retries").value == 0
    assert obs.counter("mesh.staged_fallbacks").value == 0


def test_a_traced_mesh_batch_rides_group_programs(local, traced):
    """ISSUE 43's mechanism, observable in the cell's own traffic (one
    large and one small signature group a batch): programs <
    jobs on the shared loop's counters, `exec.dispatch` spans of the
    sharded route carry their `lanes`, and the round is ONE fetch."""
    das, kb = local
    keys = _keys(kb)
    base = keys["shared2-rows"][1] + 10
    genes = ([("grounded3", base + i) for i in range(6)]
             + [("shared2", base + 6 + i) for i in range(2)])
    before = dict(compiler.ROUTE_COUNTS)
    _many(das, kb, genes)
    assert compiler.ROUTE_COUNTS["sharded"] - before["sharded"] == len(genes)
    assert compiler.ROUTE_COUNTS["staged"] == before["staged"]
    programs = obs.counter("exec.group_programs").value
    assert obs.counter("exec.group_lanes").value == len(genes) > programs
    spans = {}
    for name, _ph, _t, _dur, _tr, _g, _lane, _th, attrs in obs.events():
        spans.setdefault(name, []).append(attrs)
    enqueued = spans["exec.dispatch"]
    assert len(enqueued) == programs
    assert {a["route"] for a in enqueued} == {"sharded"}
    assert sum(a.get("lanes", 1) for a in enqueued) == len(genes)
    assert max(a.get("lanes", 1) for a in enqueued) > 1
    (fetch,) = spans["mesh.fetch"]
    assert fetch["jobs"] == len(genes)
    assert len(spans["exec.verdict"]) == len(genes)
    assert obs.counter("mesh.collective_bytes").value > 0
    assert obs.counter("mesh.retries").value == 0


def test_the_group_program_is_declared_and_read_as_a_mesh_program():
    """`das_sharded_group` (and its `_count` variant) is a declared
    program name, and the benchmark's mesh readers, which match
    `das_sharded*`, take it for a mesh query program."""
    from benchmark.harness import mesh_trace, readers

    assert "das_sharded_group" in obs.PROGRAM_NAMES
    for module in ("jit_das_sharded_group(123)",
                   "jit_das_sharded_group_count(7)"):
        name = readers.program_name(module)
        assert name.startswith(mesh_trace.MESH_PROGRAMS)
        assert readers.kind(module) == readers.QUERY


def test_a_shard_overflow_is_a_counted_retry(local, traced):
    from das_tpu.parallel.fused_sharded import get_sharded_executor

    das, kb = local
    gene = _keys(kb)["shared2-rows"][1] + 2
    plans = compiler.plan_query(das.db, parse_query(_dsl("shared2", gene)))
    job = get_sharded_executor(das.db)._exec_job(plans, False)
    job.join_caps = tuple(16 for _ in job.join_caps)   # under the answer
    while True:
        out = job.dispatch()
        if job.settle(jax.device_get(out), out):
            break
    assert job.rounds >= 2 and job.result.count == len(
        _want(kb, "shared2", gene))
    assert obs.counter("mesh.retries").value == job.rounds - 1
    # every round's program moved its own bytes
    assert obs.counter("mesh.collective_bytes").value > 0


@pytest.mark.parametrize("name", MESH_SPANS + MESH_COUNTERS)
def test_every_mesh_name_is_declared_and_has_a_reader(name):
    """Declared in obs/registry.py; read by a per-layer metric of the
    benchmark, or (`mesh.staged_fallbacks`, the twin of the route count
    the harness limits) by the Prometheus exposition alone."""
    declared = obs.SPAN_NAMES if name in MESH_SPANS else obs.COUNTER_NAMES
    assert name in declared
    readers = os.path.join(ROOT, "benchmark", "layer_metrics")
    read_by = [f for f in sorted(os.listdir(readers))
               if f.startswith("mesh.")
               and name in open(os.path.join(readers, f)).read()]
    if name in MESH_COUNTERS:
        assert name.replace(".", "_") in obs.prometheus_text()
    if name != "mesh.staged_fallbacks":
        assert read_by, f"no reader under benchmark/layer_metrics for {name}"
