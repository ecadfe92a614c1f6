"""dasprof program ledger (ISSUE 14): compile/cost/memory telemetry,
byte-model calibration, and the DL016 program-site registry
discipline.

Pins, in one place (marker `prof`, standalone via `ops/pytests.sh
prof`):

  * DISABLED path is the identity: `instrument(...)` returns the jitted
    fn ITSELF (no wrapper objects), a served workload records nothing,
    and the analyzer's DL001/DL010 clean-tree run (test_zlint) covers
    the sync-free dispatch halves either way;
  * ledger lifecycle on both backends: one compile entry per program
    signature carrying wall seconds + cost_analysis (flops, bytes
    accessed) + memory_analysis byte columns, repeat calls of the same
    shape counted as ledger hits, answers bit-identical to the
    un-instrumented path;
  * the acceptance pin: the bio 3-var query under the coalescer yields
    a ledger entry with compile wall time + cost/memory analysis, and
    `explain(compile=True)` renders it by digest;
  * cold-start accounting: a persistent-XLA-cache-served compile is
    classified as a hit and excluded from cold_start_s;
  * daslint DL016 — clean tree, bad/good fixtures, and a mutated-copy
    regression deleting the real build_fused instrument hook.

Compile-budget note: every query here reuses small animals-KB plan
shapes (the test_zpipeline idiom); the bio acceptance case runs ONE
3-var shape.
"""

from pathlib import Path

import pytest

from das_tpu import obs
from das_tpu.api.atomspace import DistributedAtomSpace, QueryOutputFormat
from das_tpu.core.config import DasConfig
from das_tpu.models.animals import animals_metta
from das_tpu.obs import proflog
from das_tpu.query.ast import And, Link, Node, Or, Variable
from das_tpu.storage.atom_table import load_metta_text
from das_tpu.storage.tensor_db import TensorDB

pytestmark = pytest.mark.prof

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"


def _inherit_query(anchor="animal"):
    return And([
        Link("Inheritance", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Node("Concept", anchor)], True),
    ])


def _tensor_das(config=None):
    data = load_metta_text(animals_metta())
    db = TensorDB(data, config or DasConfig())
    return DistributedAtomSpace(database_name="zprof", db=db), db


@pytest.fixture
def ledger():
    """Ledger ON for the test body, clean before and after, OFF again
    on exit — the rest of the suite must keep running the identity
    fast path."""
    proflog.configure(enabled=True)
    proflog.reset()
    yield
    proflog.reset()
    proflog.configure(enabled=False)


# -- disabled path ---------------------------------------------------------


def test_disabled_instrument_is_identity():
    """The no-allocation contract: with the ledger off, instrument()
    hands back the very callable it was given — the serving path is
    structurally the pre-ledger path."""
    assert not proflog.enabled()

    def fn(x):
        return x

    assert proflog.instrument("fused", "deadbeef", fn) is fn


def test_disabled_workload_records_nothing():
    das, _db = _tensor_das()
    ok, ans = das.query_answer(_inherit_query())
    assert ok and ans.assignments
    snap = proflog.snapshot()
    assert snap["enabled"] is False
    assert snap["compiles"] == 0 and snap["entries"] == 0
    assert snap["calls"] == 0


# -- ledger lifecycle ------------------------------------------------------


def test_tensor_lifecycle_compile_then_hits(ledger):
    das, _db = _tensor_das()
    ok1, ans1 = das.query_answer(_inherit_query("animal"))
    # a DIFFERENT grounding of the same plan shape: same signature,
    # same compiled program — must be a ledger hit, not a compile
    ok2, _ans2 = das.query_answer(_inherit_query("mammal"))
    assert ok1 and ans1.assignments
    assert ok2 is not None  # empty answer is fine — the program still ran
    snap = proflog.snapshot()
    assert snap["compiles"] == 1, snap
    assert snap["calls"] >= 2 and snap["ledger_hits"] >= 1
    assert snap["hit_rate"] > 0
    (row,) = proflog.rows(site="fused")
    assert row["compiles"] == 1
    assert row["compile_s"] > 0
    assert row["first_compile_s"] == pytest.approx(row["compile_s"])
    # cost_analysis + memory_analysis columns (CPU backend provides
    # both; where a backend doesn't, the columns stay None — "where the
    # backend provides them")
    assert row["flops"] is not None and row["flops"] > 0
    assert row["bytes_accessed"] is not None
    assert row["peak_bytes"] is not None and row["peak_bytes"] > 0
    assert row["error"] is None


def test_answers_bit_identical_on_vs_off(ledger):
    das_on, _ = _tensor_das()
    _ok, on = das_on.query_answer(_inherit_query())
    proflog.configure(enabled=False)
    das_off, _ = _tensor_das()
    _ok, off = das_off.query_answer(_inherit_query())
    assert on.assignments == off.assignments


def test_sharded_lifecycle(ledger):
    from das_tpu.parallel.sharded_db import ShardedDB

    db = ShardedDB(
        load_metta_text(animals_metta()), DasConfig(backend="sharded")
    )
    das = DistributedAtomSpace(database_name="zprof-mesh", db=db)
    ok, ans = das.query_answer(_inherit_query())
    assert ok and ans.assignments
    rows = proflog.rows(site="sharded")
    assert rows and rows[0]["compiles"] == 1
    assert rows[0]["compile_s"] > 0 and rows[0]["flops"] is not None


def test_tree_site_records(ledger):
    das, _db = _tensor_das()
    q = Or([_inherit_query("animal"), _inherit_query("mammal")])
    ok, ans = das.query_answer(q)
    assert ok and ans.assignments
    rows = proflog.rows(site="fused_tree")
    assert rows and rows[0]["compiles"] >= 1
    assert rows[0]["peak_bytes"] is not None


def test_count_batch_site_records(ledger):
    from das_tpu.query import compiler
    from das_tpu.query.fused import get_executor

    das, db = _tensor_das()
    plans = [
        compiler.plan_query(db, _inherit_query(a))
        for a in ("animal", "mammal")
    ]
    counts = get_executor(db).count_batch(plans)
    assert all(c is not None for c in counts)
    rows = proflog.rows(site="count_batch")
    assert rows and rows[0]["compiles"] >= 1


# -- acceptance: bio 3-var under the coalescer + explain(compile=True) -----


def test_bio_three_var_coalescer_and_explain_compile(ledger):
    from das_tpu.models.bio import build_bio_atomspace
    from das_tpu.service.coalesce import QueryCoalescer
    from das_tpu.service.server import _Tenant

    data, _genes, _procs = build_bio_atomspace(
        n_genes=64, n_processes=16, members_per_gene=5, n_interactions=128
    )
    db = TensorDB(data, DasConfig())
    das = DistributedAtomSpace(database_name="zprof-bio", db=db)
    q = And([
        Link("Member", [Variable("V1"), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Interacts", [Variable("V1"), Variable("V2")], True),
    ])
    coal = QueryCoalescer(max_batch=16)
    fut = coal.submit(_Tenant("zprof-bio", das), q, QueryOutputFormat.HANDLE)
    assert fut.result(timeout=300) is not None
    rows = proflog.rows(site="fused")
    assert rows, "the served 3-var query must land a ledger entry"
    row = rows[0]
    assert row["compile_s"] > 0 and row["flops"] is not None
    assert row["peak_bytes"] is not None
    # explain(compile=True) renders the SAME entry by digest
    out = das.explain(q, compile=True)
    comp = out["compile"]
    assert comp is not None and comp["enabled"] is True
    assert comp["rows"], out
    assert comp["rows"][0]["digest"] == comp["digest"]
    for col in ("site", "compiles", "compile_s", "flops",
                "bytes_accessed", "arg_bytes", "out_bytes", "temp_bytes",
                "peak_bytes"):
        assert col in comp["rows"][0]
    # compile=True implies execute: the actual block rides along
    assert out["actual"]["count"] is not None


def test_explain_compile_disabled_reports_enabled_false():
    das, _db = _tensor_das()
    das.query(_inherit_query())
    out = das.explain(_inherit_query(), compile=True)
    assert out["compile"]["enabled"] is False
    assert out["compile"]["rows"] == []


# -- cold-start / persistent XLA cache ------------------------------------


def test_persistent_cache_hit_excluded_from_cold_start(ledger, tmp_path):
    import jax

    try:
        from jax._src.compilation_cache import reset_cache
    except Exception:
        pytest.skip("jax compilation-cache reset API unavailable")

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min_t = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_min_b = jax.config.jax_persistent_cache_min_entry_size_bytes
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the persistent cache binds its directory at first use; earlier
    # tests in the process may have initialized it already
    reset_cache()
    try:
        das, _db = _tensor_das()
        ok, _ = das.query_answer(_inherit_query())
        assert ok
        first = proflog.snapshot()
        assert first["compiles"] == 1
        assert first["persistent_cache_hits"] == 0
        assert first["cold_start_s"] == pytest.approx(first["compile_s"])
        # a fresh process would reuse the persistent cache; simulate it
        # by dropping jax's in-memory caches and recompiling the same
        # program shape
        jax.clear_caches()
        proflog.reset()
        das2, _db2 = _tensor_das()
        ok2, _ = das2.query_answer(_inherit_query())
        assert ok2
        warm = proflog.snapshot()
        assert warm["compiles"] == 1
        assert warm["persistent_cache_hits"] == 1, warm
        # the cache-served compile's wall time stays OUT of cold_start_s
        assert warm["cold_start_s"] == 0.0
        assert warm["compile_s"] > 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min_t
        )
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", prev_min_b
        )
        reset_cache()


# -- serving surfaces ------------------------------------------------------


def test_programs_in_service_stats_and_prometheus(ledger):
    from das_tpu.service.server import DasService

    svc = DasService(backend="tensor")
    stats = svc.coalescer_stats()
    progs = stats["programs"]
    for key in ("enabled", "compiles", "compile_s", "hit_rate",
                "cold_start_s", "persistent_cache_hits"):
        assert key in progs
    text = svc.metrics_text()
    assert "das_tpu_obs_programs_compiles" in text
    assert "das_tpu_obs_programs_compile_s" in text
    assert "das_tpu_obs_programs_cold_start_s" in text
    assert "das_tpu_obs_prof_compile_ms" in text


def test_compile_span_lands_in_trace_ring(ledger):
    obs.configure(enabled=True)
    obs.reset()
    try:
        das, _db = _tensor_das()
        ok, _ = das.query_answer(_inherit_query())
        assert ok
        comp = [e for e in obs.events() if e[0] == "prof.compile"]
        assert comp, "compile span must land when dastrace is on too"
        # the dedicated compile lane (scripts/dump_trace.py renders it
        # as its own Perfetto process row)
        assert comp[0][6] == "compile"
    finally:
        obs.reset()
        obs.configure(enabled=False)


# -- DL016 -----------------------------------------------------------------


def test_dl016_clean_tree():
    from das_tpu.analysis import run_analysis

    findings = run_analysis([REPO / "das_tpu"], rules=["DL016"])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_dl016_fixture_corpus():
    from das_tpu.analysis import run_analysis

    bad = run_analysis([FIXTURES / "dl016_bad.py"], rules=["DL016"])
    msgs = "\n".join(f.message for f in bad)
    assert "build_uninstrumented" in msgs, msgs  # missing ledger hook
    assert "surprise_builder" in msgs, msgs      # undeclared scope
    assert "bare_name_builder" in msgs, msgs     # `from jax import jit`
    assert "typo_site" in msgs, msgs             # undeclared hook label
    assert "retired_builder" in msgs, msgs       # stale registry entry
    assert "outside any function" in msgs, msgs  # import-time compile
    assert len(bad) == 6, msgs
    good = run_analysis([FIXTURES / "dl016_good.py"], rules=["DL016"])
    assert good == [], "\n".join(f.render() for f in good)


def test_dl016_partial_suppresses_stale_only():
    from das_tpu.analysis import run_analysis

    partial = run_analysis(
        [FIXTURES / "dl016_bad.py"], rules=["DL016"], partial=True
    )
    msgs = "\n".join(f.message for f in partial)
    assert "surprise_builder" in msgs and "build_uninstrumented" in msgs
    assert "retired_builder" not in msgs, (
        "--changed-only runs must skip the stale-entry leg"
    )


def test_dl016_catches_deleted_hook_on_real_builder(tmp_path):
    """Mutated-copy regression: strip build_fused's instrument() call —
    re-introducing an unledgered program builder must fail lint."""
    from das_tpu.analysis import run_analysis

    src = (REPO / "das_tpu/query/fused.py").read_text()
    needle = (
        "    return obs.proflog.instrument(\n"
        '        "fused", obs.proflog.sig_digest(sig, count_only),\n'
        '        jax.jit(obs.named_program("das_fused", fn, count_only)),\n'
        "    ), names"
    )
    assert src.count(needle) == 1, "fused.py build_fused layout changed"
    mutated = tmp_path / "fused.py"
    mutated.write_text(src.replace(needle, "    return jax.jit(fn), names"))
    findings = run_analysis(
        [mutated, REPO / "das_tpu/obs/proflog.py"],
        rules=["DL016"], partial=True,
    )
    assert any(
        "fused.build_fused" in f.message and "no" in f.message
        for f in findings
    ), "\n".join(f.render() for f in findings)
    # the committed module next to the registry stays clean
    clean = run_analysis(
        [REPO / "das_tpu/query/fused.py", REPO / "das_tpu/obs/proflog.py"],
        rules=["DL016"], partial=True,
    )
    assert clean == [], "\n".join(f.render() for f in clean)
    # the program-name leg: next to a PROGRAM_NAMES registry, a builder
    # that keeps its ledger hook but drops its declared module name
    # would show as jit_fn in the device trace
    named = 'jax.jit(obs.named_program("das_fused", fn, count_only))'
    assert src.count(named) == 1
    mutated.write_text(src.replace(named, "jax.jit(fn)"))
    findings = run_analysis(
        [mutated, REPO / "das_tpu/obs/proflog.py",
         REPO / "das_tpu/obs/registry.py"],
        rules=["DL016"], partial=True,
    )
    assert [f for f in findings if "das_fused" in f.message], "\n".join(
        f.render() for f in findings
    )


def test_program_sites_registry_pinned():
    """The DL004-idiom test leg: instrumenting or exempting a program
    site is a reviewed change HERE, not silent drift."""
    instrumented = {
        scope: label
        for scope, label in proflog.PROGRAM_SITES.items()
        if label is not None
    }
    assert instrumented == {
        "fused.build_fused": "fused",
        "fused.build_fused_group": "fused_group",
        "fused.build_fused_tree": "fused_tree",
        "fused.build_fused_exact": "fused_exact",
        "fused.FusedExecutor._run_batch_group": "count_batch",
        "fused_sharded._ShardedExecJob.dispatch": "sharded",
        "fused_sharded._ShardedExecJob._build_group": "sharded_group",
        "fused_sharded._ShardedTreeExecJob._build": "sharded_tree",
    }
