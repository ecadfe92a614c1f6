"""A deployment is data: a query shape's plain reference is a rule file
found by name, a store's profile is its configuration's `shape` block.

The served answers of the tensor backend at scale 0.002 carry each
rule's digest, before and after two commits, for the two rules the cells
use and for a test-only third (`data/three_var.py`: three columns, no
key); the store's draws at `skew` 0 are the parent's, byte for byte; a
skewed store keeps its exact counts."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.harness.cell import verify_records
from benchmark.reference import generator, plain

HERE = os.path.dirname(os.path.abspath(__file__))
SCALE, SEED = 0.002, 2**31 + 41

#: rule -> (where its file is, the dsl that asks it)
RULES = {
    "grounded3": (None, None),
    "shared2": (None, None),
    "three_var": (os.path.join(HERE, "data"),
                  "Link Interacts $1 $2, Link Member $1 $3, "
                  "Link Member $2 $3, AND"),
}


def shape_of(config: str = "flybase-mem") -> dict:
    with open(os.path.join(spec.BENCH_DIR, "configs", config + ".json")) as fh:
        return json.load(fh)["shape"]


def rule_and_dsl(name: str):
    rules_dir, dsl = RULES[name]
    if dsl is None:
        with open(os.path.join(spec.BENCH_DIR, "queries",
                               name + ".json")) as fh:
            dsl = json.load(fh)["dsl"]
    return spec.load_rule(name, rules_dir), dsl


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(ask, commit, kb): one seed's store behind the gRPC service on the
    tensor backend, the tenant attached as `cell.py` attaches it."""
    from das_tpu.api.atomspace import DistributedAtomSpace
    from das_tpu.core.config import DasConfig
    from das_tpu.service.client import DasClient
    from das_tpu.service.server import serve

    store = generator.Store(SCALE, SEED, shape_of())
    kb = plain.PlainKB(store)
    path = os.path.join(str(tmp_path_factory.mktemp("kb")), "kb.metta")
    generator.write_canonical(store, path)
    das = DistributedAtomSpace(database_name="rules", backend="tensor",
                               config=DasConfig.from_env())
    das.load_canonical_knowledge_base(path)
    os.remove(path)
    server, service = serve(port=0, backend="tensor", block=False,
                            max_workers=4)
    token = service.attach_tenant("rules", das)
    client = DasClient(port=server.bound_port)

    def ask(dsl: str, gene: int):
        reply = client.call("query", key=token, output_format="HANDLE",
                            query=dsl.format(key=generator.gene_name(gene)))
        assert reply["success"], reply["msg"]
        return plain.canonical_answer(reply["msg"])

    def commit(g: int, v: int, n: int = 2):
        """n new (Interacts g x) + (Member x p), p a process of g that x
        lacks: `grounded3(g)` and `three_var` gain a row for each x,
        `shared2(g)` too."""
        tx = das.open_transaction()
        mine = sorted(kb.procs_of(g))
        added, x = 0, 0
        while added < n:
            x += 1
            if x == g or x in kb.out_of(g):
                continue
            p = next((q for q in mine if q not in kb.procs_of(x)), None)
            if p is None:
                continue
            tx.add(f'(Interacts "{generator.gene_name(g)}" '
                   f'"{generator.gene_name(x)}")')
            tx.add(f'(Member "{generator.gene_name(x)}" '
                   f'"{generator.proc_name(p)}")')
            assert kb.add_interacts(g, x, v) and kb.add_member(x, p, v)
            added += 1
        with service.tenants[token].lock:
            das.commit_transaction(tx)

    yield ask, commit, kb
    client.close()
    server.stop(0).wait()


@pytest.fixture(scope="module")
def states(served):
    """Every rule's served answer at commit 0, then after commits 1 and
    2 on one gene: {rule: [(canonical rows, gene) at v = 0, 2]}."""
    ask, commit, kb = served
    g3, _ = rule_and_dsl("grounded3")
    gene = next(g for g in range(kb.store.n_genes) if g3.rows(kb, g))
    out = {name: [] for name in RULES}
    for v in (0, 2):
        if v:
            commit(gene, 1)
            commit(gene, 2)
        for name in RULES:
            _rule, dsl = rule_and_dsl(name)
            out[name].append(ask(dsl, gene))
    return gene, out


@pytest.mark.parametrize("name", sorted(RULES))
def test_the_served_answer_has_the_rules_digest(served, states, name):
    _ask, _commit, kb = served
    gene, answers = states
    rule, _dsl = rule_and_dsl(name)
    assert len(rule.COLUMNS) == (3 if name == "three_var" else 2)
    rows = rule.rows(kb, gene if rule.KEY else None)
    before, after = (kb.canonical_rows(rows, v, rule.COLUMNS) for v in (0, 2))
    assert before and set(before) < set(after) and len(after) >= len(before) + 2
    assert plain.digest(answers[name][0]) == plain.digest(before)
    assert plain.digest(answers[name][1]) == plain.digest(after)
    # and through the verifier, as a run's records: the answer sent
    # before commit 1 was issued, the one sent after commit 2's ack
    acked, issued = [10.1, 20.1], [10.0, 20.0]
    records = [
        {"c": 0, "i": i, "shape": name, "key": gene, "ok": True,
         "sent": sent, "recv": sent + 1.0, "n": len(got),
         "d": plain.digest(got)}
        for i, (got, sent) in enumerate(zip(answers[name], (1.0, 25.0)))]
    # a whole-store shape is sent with whatever key the mix drew: ignored
    records.append(dict(records[0], i=2, key=gene + 1 if rule.KEY is None
                        else gene))
    out = verify_records(records, kb, {name: rule}, acked, issued)
    assert out["wrong"] == [] and out["nonempty"] == 3
    stale = dict(records[0], sent=25.0, recv=26.0)
    assert len(verify_records([stale], kb, {name: rule}, acked,
                              issued)["wrong"]) == 1


def test_a_rule_name_with_no_file_is_refused(tmp_path):
    with pytest.raises(spec.SpecError, match="no such file"):
        spec.load_rule("four_var")
    with pytest.raises(spec.SpecError, match="not a name"):
        spec.load_rule("../plain")
    (tmp_path / "half.py").write_text("COLUMNS = ((\"$1\", \"Gene\"),)\n")
    with pytest.raises(spec.SpecError, match="states no KEY"):
        spec.load_rule("half", str(tmp_path))
    # a shape file that names it is refused when the cell is loaded
    root = tmp_path / "root"
    bench = json.loads(json.dumps(spec.load_benchmark()))
    os.makedirs(root / "benchmark")
    for sub in ("configs", "traffic", "queries", "reference", "layer_metrics"):
        os.symlink(os.path.join(spec.BENCH_DIR, sub),
                   root / "benchmark" / sub)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert spec.Cell("mem-uniform-closed", str(root)).rules
    os.unlink(root / "benchmark" / "queries")
    os.makedirs(root / "benchmark" / "queries")
    for shape in ("grounded3", "shared2"):
        with open(os.path.join(spec.BENCH_DIR, "queries",
                               shape + ".json")) as fh:
            q = json.load(fh)
        if shape == "shared2":
            q["reference_rule"] = "four_var"
        (root / "benchmark" / "queries" / (shape + ".json")).write_text(
            json.dumps(q))
    with pytest.raises(spec.SpecError, match="four_var"):
        spec.Cell("mem-uniform-closed", str(root))


def test_rule_files_import_nothing_of_the_program():
    rules_dir = os.path.join(spec.BENCH_DIR, "reference", "rules")
    names = sorted(f for f in os.listdir(rules_dir) if f.endswith(".py"))
    assert names == ["grounded3.py", "shared2.py"]
    for path in [os.path.join(rules_dir, n) for n in names] + [
            os.path.join(HERE, "data", "three_var.py")]:
        with open(path) as fh:
            text = fh.read()
        assert "import" not in text.replace("imports nothing", ""), path


#: md5 of kb.metta as the PARENT (fa35407) writes it for (scale, seed):
#: at skew 0 with today's counts the draws are the ones made before the
#: profile became the configuration's, call for call
PARENT_MD5 = {
    (0.002, 11): "9dfe512914247c9572903ccd5bb998e2",
    (0.002, 12): "f8a64cbab3120452c8deb5a66b4b1a0d",
    (0.01, 2**31 + 5): "c3c1921571ad5a12149de1a7c1823b38",
}


@pytest.mark.parametrize("config", ["flybase-mem", "flybase-wal",
                                    "flybase-sharded4"])
@pytest.mark.parametrize("scale,seed", sorted(PARENT_MD5))
def test_the_store_file_is_the_parents_byte_for_byte(tmp_path, config,
                                                     scale, seed):
    path = str(tmp_path / "kb.metta")
    generator.write_canonical(generator.Store(scale, seed, shape_of(config)),
                              path)
    with open(path, "rb") as fh:
        assert hashlib.md5(fh.read()).hexdigest() == PARENT_MD5[scale, seed]


@pytest.mark.parametrize("config", ["flybase-mem", "flybase-wal",
                                    "flybase-sharded4"])
def test_every_configurations_shape_is_the_flybase_profile(config):
    shape = shape_of(config)
    for key, value in generator.FLYBASE.items():
        assert shape[key] == value, (config, key)


def test_a_shape_that_lacks_a_count_or_names_another_link_is_refused():
    shape = shape_of()
    for key in ("n_genes", "skew", "link_types"):
        with pytest.raises(ValueError, match=key):
            generator.Store(SCALE, 1, {k: v for k, v in shape.items()
                                       if k != key})
    with pytest.raises(ValueError, match="Regulates"):
        generator.Store(SCALE, 1, dict(shape, link_types=["Member",
                                                          "Regulates"]))


@pytest.mark.parametrize("seed", [11, 2**31 + 7])
def test_a_skewed_store_keeps_exact_counts_and_has_hubs(seed):
    flat = generator.Store(0.01, seed, shape_of())
    hub = generator.Store(0.01, seed, dict(shape_of(), skew=1.1))
    assert hub.skew == 1.1 and hub.params == flat.params
    assert hub.counts() == flat.counts()
    srt = np.sort(hub.members, axis=1)
    assert not (srt[:, 1:] == srt[:, :-1]).any()          # distinct per gene
    pairs = hub.interactions
    assert len(pairs) == hub.params["n_interactions"]
    assert (pairs[:, 0] != pairs[:, 1]).all()
    lo, hi = pairs.min(axis=1).astype(np.int64), pairs.max(axis=1)
    assert len(np.unique(lo * hub.n_genes + hi)) == len(pairs)
    ev = hub.evaluations
    assert len(ev) == hub.params["n_evaluations"]
    assert len(np.unique(ev[:, 0].astype(np.int64) * hub.n_processes
                         + ev[:, 1])) == len(ev)
    # hubs: the hottest gene holds more than 10 x the mean degree, the
    # uniform store's hottest does not; and the mass sits on LOW indices
    degree = np.bincount(pairs.reshape(-1), minlength=hub.n_genes)
    flat_degree = np.bincount(flat.interactions.reshape(-1),
                              minlength=flat.n_genes)
    assert degree.max() > 10 * degree.mean() > flat_degree.max() / 10
    assert flat_degree.max() < 10 * flat_degree.mean()
    assert int(degree.argmax()) < hub.n_genes // 100
    sizes = np.bincount(hub.members.reshape(-1), minlength=hub.n_processes)
    assert sizes.max() > 10 * sizes.mean()
    # the reference answers on it as on any store
    kb = plain.PlainKB(hub)
    rule, _ = rule_and_dsl("grounded3")
    assert rule.rows(kb, int(degree.argmax()))


def test_the_new_cell_rehearses_every_phase_on_the_cpu():
    """`benchmark/run.py --workload mem-zipf-open --rehearse 0.002`: the
    open loop, the Zipf keys, the result cache, every reader; then the
    refusal (exit 3), because a CPU run is no measurement."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DAS_TPU_TRACE", None)
    for trace in (0, 1):
        proc = subprocess.run(
            [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
             "--workload", "mem-zipf-open", "--seed", str(2**31 + 43),
             "--seconds", "3", "--trace", str(trace), "--rehearse", "0.002"],
            capture_output=True, text=True, env=env, cwd=spec.ROOT,
            timeout=600)
        assert proc.returncode == 3, proc.stderr[-2000:]
        result = json.loads(next(
            line for line in proc.stderr.splitlines()
            if line.startswith('{"correct"')))
        assert result["correct"] is True and result["failed"] == 0
        assert result["attempted"] > 100
        want = ({"query_p50_ms", "query_p95_ms", "setup_s"} if not trace else
                {"wire.generator_lateness_p95_ms", "exec.cache_hit_share",
                 "planner.table_extractions_per_k", "exec.answers_objects"})
        assert want <= set(result["metrics"]), sorted(result["metrics"])
        if not trace:
            assert "query_rate" not in result["metrics"]
