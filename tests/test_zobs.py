"""Observability layer (ISSUE 12): dastrace spans, metric histograms,
exporters, and the DL014 name-registry discipline.

Pins, in one place (marker `obs`, standalone via `ops/pytests.sh obs`):

  * end-to-end span coverage for a coalesced query: every lifecycle
    stage (submit → drain → group → plan → dispatch → settle → answer)
    lands in the ring, spans nest/order correctly, and the trace id
    born at submit is the one closed at answer;
  * cache-hit and commit-invalidation events, with the commit path's
    delta_version bump visible;
  * histogram percentile math vs exact quantiles on known samples
    (the fixed log-bucket error bound);
  * the DISABLED mode is structurally a no-op: `span()` returns THE
    shared no-op singleton (no span objects allocated), `mark()` is
    None, the ring stays empty through a served workload;
  * Perfetto (Chrome trace-event) and Prometheus exporter golden
    shapes;
  * daslint DL014 — clean tree, bad/good fixtures, and a mutated-copy
    regression on a real instrumentation site;
  * the coalescer's last-K (rtt, dispatch, depth) window-history ring
    (the ARCHITECTURE §10 window-formula evidence).

Compile-budget note: every served query here reuses ONE fused plan
shape on the small animals KB (the test_zpipeline idiom).
"""

import json
import re
import time
from pathlib import Path

import pytest

from das_tpu import obs
from das_tpu.api.atomspace import DistributedAtomSpace, QueryOutputFormat
from das_tpu.core.config import DasConfig
from das_tpu.models.animals import animals_metta
from das_tpu.obs.metrics import Histogram
from das_tpu.query.ast import And, Link, Node, Variable
from das_tpu.query.fused import FETCH_COUNTS
from das_tpu.service.coalesce import QueryCoalescer
from das_tpu.service.server import _Tenant
from das_tpu.storage.atom_table import load_metta_text
from das_tpu.storage.tensor_db import TensorDB

pytestmark = pytest.mark.obs

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"

COMMIT = '(: "platypus" Concept)\n(Inheritance "platypus" "chimp")'


def _pair_query():
    """Empty on the seed KB; gains its first answer after COMMIT (the
    test_zpipeline idiom)."""
    return And([
        Link("Inheritance", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Node("Concept", "mammal")], True),
    ])


def _matching_query():
    """Non-empty on the seed KB: ($1 inherits $2, $2 inherits animal) —
    e.g. (human, mammal), (snake, reptile) — so materialization runs."""
    return And([
        Link("Inheritance", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Node("Concept", "animal")], True),
    ])


def _tensor_das(config=None):
    data = load_metta_text(animals_metta())
    db = TensorDB(data, config or DasConfig())
    return DistributedAtomSpace(database_name="zobs", db=db), db


@pytest.fixture
def traced():
    """Tracing ON for the test body, clean ring before and after, OFF
    again on exit — the suite's other files must keep running against
    the no-op fast path."""
    obs.configure(enabled=True, capacity=8192)
    obs.reset()
    yield
    obs.reset()
    obs.configure(enabled=False)


def _serve(das, queries, coal=None, tenant=None):
    """Run queries through a real coalescer worker and wait for the new
    settle span(s) to land: futures resolve INSIDE the serve.settle
    span (and before the window-history append), so the ring/history
    writes race a thread that only waited on the futures."""
    tenant = tenant or _Tenant("zobs", das)
    coal = coal or QueryCoalescer(max_batch=64, pipeline_depth=2)
    before = sum(1 for e in obs.events() if e[0] == "serve.settle")
    futs = [
        coal.submit(tenant, q, QueryOutputFormat.HANDLE) for q in queries
    ]
    for f in futs:
        f.result(timeout=120)
    deadline = time.time() + 10
    while time.time() < deadline:
        now = sum(1 for e in obs.events() if e[0] == "serve.settle")
        if now > before:
            break
        time.sleep(0.01)
    return coal, tenant, [f.result() for f in futs]


# -- end-to-end span coverage ---------------------------------------------


def test_coalesced_query_full_lifecycle(traced):
    das, _db = _tensor_das()
    q = _matching_query()
    coal, _tenant, answers = _serve(das, [q, q, q])
    assert all(a == answers[0] for a in answers) and answers[0]
    names = {e[0] for e in obs.events()}
    for stage in ("serve.submit", "serve.drain", "serve.group",
                  "serve.plan", "serve.dispatch", "serve.settle",
                  "serve.answer", "exec.dispatch", "exec.settle_fetch",
                  "exec.verdict", "exec.materialize"):
        assert stage in names, f"lifecycle stage {stage} missing: {names}"
    # a miss is a counter and no instant (PR 42): the worker pays for
    # every event it records, and nothing read one miss
    assert "cache.miss" not in names
    assert obs.counter("cache.misses").value >= 1
    # every span/event name the ring holds is a declared registry member
    assert names <= set(obs.SPAN_NAMES)


def test_trace_id_threads_submit_to_answer(traced):
    das, _db = _tensor_das()
    _coal, _tenant, _ = _serve(das, [_pair_query()])
    evs = obs.events()
    submits = {e[4] for e in evs if e[0] == "serve.submit"}
    answers = {e[4] for e in evs if e[0] == "serve.answer"}
    assert submits and submits == answers, (submits, answers)


def test_spans_nest_and_order(traced):
    """The group id links the worker's dispatch span to the executor
    spans recorded under it; timestamps order submit < dispatch <=
    settle, and the exec.dispatch span nests inside serve.dispatch."""
    das, _db = _tensor_das()
    _coal, _tenant, _ = _serve(das, [_pair_query()])
    evs = obs.events()

    def spans(name):
        return [e for e in evs if e[0] == name]

    disp = spans("serve.dispatch")[0]
    settle = spans("serve.settle")[0]
    submit = spans("serve.submit")[0]
    gid = disp[4]  # serve.dispatch records trace=group id
    assert settle[4] == gid, "settle span must carry its group id"
    assert submit[2] <= disp[2] <= settle[2]
    # executor spans recorded on the worker thread inherit the group
    ex_disp = [e for e in spans("exec.dispatch") if e[5] == gid]
    assert ex_disp, "exec.dispatch must link to its serving group"
    e = ex_disp[0]
    assert disp[2] <= e[2] and e[2] + e[3] <= disp[2] + disp[3] + 1e-6, (
        "exec.dispatch must nest inside serve.dispatch"
    )
    # dispatch attributes: the window state the §10 decision reads
    for key in ("effective_depth", "rtt_ewma_ms", "dispatch_ewma_ms",
                "delta_version", "speculative", "traces"):
        assert key in disp[8], disp[8]
    # executor attributes: route + planner estimates
    assert e[8]["route"] == "fused"
    assert "est_join_rows" in e[8]


def test_planner_observe_totals_and_no_event_per_job(traced):
    """A planned job's settle folds est-vs-actual into PLANNER_COUNTS,
    inside its exec.verdict span; the per-job `planner.observe` instant
    is gone (PR 42: nothing read it), the dispatch span keeps the
    per-step estimates."""
    from das_tpu.planner import PLANNER_COUNTS

    das, _db = _tensor_das()
    before = dict(PLANNER_COUNTS)
    _coal, _tenant, _ = _serve(das, [_pair_query()])
    assert (PLANNER_COUNTS["round0"] + PLANNER_COUNTS["retries"]
            > before["round0"] + before["retries"])
    assert PLANNER_COUNTS["est_rows"] >= before["est_rows"]
    evs = obs.events()
    assert not [e for e in evs if e[0] == "planner.observe"]
    verdicts = [e for e in evs if e[0] == "exec.verdict"]
    assert verdicts and verdicts[-1][8]["done"] is True
    disp = [e for e in evs if e[0] == "exec.dispatch"]
    assert disp and disp[0][8]["est_join_rows"]


# -- cache + commit events ------------------------------------------------


def test_cache_hit_and_commit_invalidation_events(traced):
    das, db = _tensor_das()
    q = _pair_query()
    coal, tenant, _ = _serve(das, [q])
    obs.reset()
    _serve(das, [q], coal=coal, tenant=tenant)  # repeat: pure cache hit
    names = [e[0] for e in obs.events()]
    assert "cache.hit" in names
    assert "exec.dispatch" not in names, "a cache hit dispatched a program"
    assert obs.counter("cache.hits").value >= 1

    obs.reset()
    before = db.delta_version
    das.load_metta_text(COMMIT)  # incremental commit
    evs = obs.events()
    deltas = [e for e in evs if e[0] == "commit.delta"]
    assert deltas and deltas[0][8]["version"] == db.delta_version
    assert db.delta_version > before
    # the post-commit repeat must invalidate, then miss, then dispatch
    misses = obs.counter("cache.misses").value
    _serve(das, [q], coal=coal, tenant=tenant)
    names = [e[0] for e in obs.events()]
    assert "cache.invalidate" in names
    assert obs.counter("cache.misses").value == misses + 1
    assert "exec.dispatch" in names


# -- histogram percentile math --------------------------------------------


def test_histogram_percentiles_vs_exact_quantiles():
    import random

    rng = random.Random(7)
    for dist in (
        [rng.lognormvariate(1.0, 1.0) for _ in range(4000)],
        [rng.uniform(0.5, 500.0) for _ in range(4000)],
    ):
        h = Histogram("t")
        for v in dist:
            h.observe(v)
        s = sorted(dist)
        for q in (0.5, 0.95, 0.99):
            exact = s[max(0, int(q * len(s)) - 1)]
            approx = h.percentile(q)
            # fixed log buckets at ratio 2^(1/4): ~19% worst-case
            # relative error by construction
            assert abs(approx - exact) / exact < 0.2, (q, exact, approx)
        assert h.total == len(dist)
        assert abs(h.sum_ms - sum(dist)) < 1e-6 * sum(dist)


def test_histogram_edges():
    h = Histogram("t")
    assert h.percentile(0.5) is None  # empty
    h.observe(3.0)
    # single sample: min/max tighten the bucket to the sample itself
    assert abs(h.percentile(0.5) - 3.0) < 0.7
    assert h.percentile(0.99) <= h.max_ms + 1e-9
    h2 = Histogram("t2")
    h2.observe(0.0)      # below the lowest edge: clamps, never throws
    h2.observe(1e12)     # above the highest edge: clamps, never throws
    assert h2.total == 2


def test_histogram_percentiles_monotone():
    import random

    rng = random.Random(3)
    h = Histogram("t")
    for _ in range(1000):
        h.observe(rng.expovariate(0.1))
    ps = [h.percentile(q) for q in (0.1, 0.5, 0.9, 0.95, 0.99)]
    assert ps == sorted(ps)


# -- disabled mode: structurally a no-op ----------------------------------


def test_disabled_mode_allocates_no_span_objects():
    """THE acceptance pin: with DAS_TPU_TRACE off, span() hands back the
    one shared no-op singleton (identity — no per-call span objects),
    mark() is None, new_trace() is 0, and a full served workload leaves
    the ring empty and the metric layer untouched."""
    assert not obs.enabled()
    assert obs.span("serve.drain", width=4) is obs.NOOP_SPAN
    assert obs.span("exec.dispatch") is obs.NOOP_SPAN
    assert obs.mark() is None
    assert obs.new_trace() == 0
    counters_before = {k: c.value for k, c in obs.metrics.COUNTERS.items()}
    das, _db = _tensor_das()
    coal = QueryCoalescer(max_batch=8, pipeline_depth=2)
    tenant = _Tenant("zobs-off", das)
    futs = [
        coal.submit(tenant, _pair_query(), QueryOutputFormat.HANDLE)
        for _ in range(3)
    ]
    for f in futs:
        f.result(timeout=120)
    assert obs.events() == []
    assert {
        k: c.value for k, c in obs.metrics.COUNTERS.items()
    } == counters_before
    # the queue tuple carries None instead of a mark: no trace state
    snap = coal.snapshot()
    assert snap["items"] == 3


# -- exporters -------------------------------------------------------------


def test_chrome_trace_golden_shape(traced):
    das, _db = _tensor_das()
    _serve(das, [_pair_query()])
    doc = obs.chrome_trace(obs.events())
    # must round-trip as JSON (the Perfetto contract is plain JSON)
    doc = json.loads(json.dumps(doc))
    evs = doc["traceEvents"]
    assert evs, "empty trace"
    phases = {e["ph"] for e in evs}
    assert phases <= {"X", "i", "M"}
    for e in evs:
        assert isinstance(e["name"], str)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # one lane per tenant: the tenant name appears as a process_name
    lanes = {
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert "zobs" in lanes


def test_prometheus_text_golden_shape(traced):
    das, _db = _tensor_das()
    _serve(das, [_pair_query()])
    text = obs.prometheus_text(extra_gauges={"serving.effective_depth": 2})
    line_re = re.compile(
        r'^(# (TYPE|HELP) .*|[a-zA-Z_][a-zA-Z0-9_]*(\{[^}]*\})? '
        r'[-+0-9.eE]+)$'
    )
    for line in text.strip().splitlines():
        assert line_re.match(line), f"bad exposition line: {line!r}"
    assert "das_tpu_obs_serve_submitted_total 1" in text
    assert "das_tpu_obs_serving_effective_depth 2" in text
    # histogram triple: cumulative buckets, +Inf == count, sum present
    h = obs.histogram("serve.answer_ms")
    assert f'das_tpu_obs_serve_answer_ms_bucket{{le="+Inf"}} {h.total}' \
        in text
    assert "das_tpu_obs_serve_answer_ms_count" in text
    assert "das_tpu_obs_serve_answer_ms_sum" in text
    cums = [
        int(m.group(1)) for m in re.finditer(
            r'das_tpu_obs_serve_answer_ms_bucket\{le="[^+][^"]*"\} (\d+)',
            text,
        )
    ]
    assert cums == sorted(cums), "bucket counts must be cumulative"


def test_server_metrics_text_surface(traced):
    from das_tpu.service.server import DasService

    das, _db = _tensor_das()
    service = DasService()
    service.attach_tenant("zobs-metrics", das)
    text = service.metrics_text()
    assert "das_tpu_obs_serving_batches" in text
    assert "das_tpu_obs_exec_dispatches_total" in text


# -- the window-history ring (satellite) -----------------------------------


def test_window_history_ring(traced):
    das, _db = _tensor_das()
    q = _pair_query()
    cfg = DasConfig(result_cache_size=0)  # every round pays the wire
    das.config = cfg
    _db.config = cfg
    coal, tenant, _ = _serve(das, [q, q])
    for _ in range(3):
        _serve(das, [q], coal=coal, tenant=tenant)
    snap = coal.snapshot()
    hist = snap["window_history"]
    assert hist, "wire-fed settles must append history samples"
    for rtt, disp, depth in hist:
        assert rtt >= 0.0 and disp >= 0.0 and depth >= 1
    # the last sample mirrors the current EWMAs/depth surface
    assert hist[-1][0] == snap["rtt_ewma_ms"]
    from das_tpu.service.coalesce import _HISTORY_K

    assert len(hist) <= _HISTORY_K


def test_window_history_in_service_stats(traced):
    from das_tpu.service.server import DasService

    das, _db = _tensor_das()
    service = DasService()
    service.attach_tenant("zobs-hist", das)
    tenant = next(iter(service.tenants.values()))
    _serve(das, [_pair_query()], tenant=tenant,
           coal=tenant.get_coalescer())
    stats = service.coalescer_stats()
    per = stats["tenants"]["zobs-hist"]
    assert "window_history" in per
    assert all(len(s) == 3 for s in per["window_history"])


# -- DL014 ----------------------------------------------------------------


def test_dl014_clean_tree():
    from das_tpu.analysis import run_analysis

    findings = run_analysis([REPO / "das_tpu"], rules=["DL014"])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_dl014_fixture_corpus():
    from das_tpu.analysis import run_analysis

    bad = run_analysis([FIXTURES / "dl014_bad.py"], rules=["DL014"])
    msgs = "\n".join(f.message for f in bad)
    assert "serve.fetchh" in msgs, msgs          # undeclared span literal
    assert "serve.rows_ms" in msgs, msgs         # undeclared histogram
    assert "serve.retired" in msgs, msgs         # stale registry entry
    assert len(bad) == 3, msgs
    good = run_analysis([FIXTURES / "dl014_good.py"], rules=["DL014"])
    assert good == [], "\n".join(f.render() for f in good)


def test_dl014_partial_suppresses_stale_only():
    from das_tpu.analysis import run_analysis

    partial = run_analysis(
        [FIXTURES / "dl014_bad.py"], rules=["DL014"], partial=True
    )
    msgs = "\n".join(f.message for f in partial)
    assert "serve.fetchh" in msgs and "serve.rows_ms" in msgs
    assert "serve.retired" not in msgs, (
        "--changed-only runs must skip the stale-entry leg"
    )


def test_dl014_catches_typo_on_real_instrumentation_site(tmp_path):
    """Mutated-copy regression: typo ONE span literal in the real
    coalescer next to the real registry — DL014 must fire on exactly
    that literal."""
    from das_tpu.analysis import run_analysis

    src = (REPO / "das_tpu/service/coalesce.py").read_text()
    needle = 'obs.span("serve.drain", width=width)'
    assert src.count(needle) == 1, "coalesce.py layout changed"
    mutated = tmp_path / "coalesce.py"
    mutated.write_text(src.replace(
        needle, 'obs.span("serve.drian", width=width)', 1
    ))
    findings = run_analysis(
        [mutated, REPO / "das_tpu/obs/registry.py"],
        rules=["DL014"], partial=True,
    )
    assert any("serve.drian" in f.message for f in findings), "\n".join(
        f.render() for f in findings
    )
    # the committed module next to the registry stays clean
    clean = run_analysis(
        [REPO / "das_tpu/service/coalesce.py",
         REPO / "das_tpu/obs/registry.py"],
        rules=["DL014"], partial=True,
    )
    assert clean == [], "\n".join(f.render() for f in clean)


def test_obs_registries_pinned():
    """The declared name sets themselves (the DL004-idiom test leg): a
    rename or deletion must be a reviewed change here, not a silent
    drift of the dashboard vocabulary."""
    assert set(obs.SPAN_NAMES) >= {
        "serve.submit", "serve.drain", "serve.group", "serve.plan",
        "serve.dispatch", "serve.settle", "serve.answer",
        "exec.dispatch", "exec.settle_fetch", "exec.materialize",
        "cache.hit", "cache.invalidate",
        "commit.delta", "commit.rebuild",
        "serve.deadline", "serve.breaker", "fault.inject",
        # ISSUE 26: the commit path, the answer path, the planner's
        # statistics and the wire
        "commit.apply", "commit.stage", "commit.swap", "dur.wal_append",
        "exec.format", "planner.stats", "wire.query", "wire.parse",
        # ISSUE 41: the build of a batch's jobs
        "exec.build",
        # ISSUE 42: inside the settle loop
        "exec.verdict", "serve.rerun",
    }
    # ISSUE 42: the instants nobody read are gone, their counters stay
    assert not {"cache.miss", "planner.observe"} & set(obs.SPAN_NAMES)
    assert set(obs.COUNTER_NAMES) >= {
        "serve.submitted", "serve.answers", "serve.rejections",
        "cache.hits", "cache.misses", "cache.invalidations",
        "commit.deltas", "exec.dispatches", "exec.fetches",
        "serve.deadline_misses", "serve.breaker_trips",
        "serve.breaker_recoveries", "fault.injected", "fault.retries",
        "exec.stale_reruns", "exec.per_query_fallbacks",
        "exec.group_programs", "exec.group_lanes",
        "planner.table_extractions", "planner.table_hits",
        "exec.template_builds", "exec.template_hits",
    }
    assert set(obs.HISTOGRAM_NAMES) >= {
        "serve.queue_ms", "serve.dispatch_ms", "serve.settle_ms",
        "serve.answer_ms", "exec.settle_fetch_ms", "serve.lock_wait_ms",
    }
    # the module names a device trace shows: a rename moves the
    # benchmark's device-time readers (benchmark/harness/readers.py)
    assert set(obs.PROGRAM_NAMES) == {
        "das_fused", "das_fused_group", "das_fused_tree",
        "das_fused_exact", "das_count_batch",
        "das_sharded", "das_sharded_group",
        "das_sharded_tree", "das_merge_padded", "das_insert_rows",
        "das_merge_sharded",
    }
    # the metric dicts are BUILT from the registry
    assert set(obs.metrics.COUNTERS) == set(obs.COUNTER_NAMES)
    assert set(obs.metrics.HISTOGRAMS) == set(obs.HISTOGRAM_NAMES)


# -- jax.profiler integration gate ----------------------------------------


def test_jax_annotation_gate(monkeypatch):
    """DAS_TPU_TRACE_JAX off (default): the shared no-op, no jax
    import; on: a real jax.profiler.TraceAnnotation (enterable even
    with no device trace running)."""
    from das_tpu.obs import jaxprof

    monkeypatch.delenv("DAS_TPU_TRACE_JAX", raising=False)
    assert jaxprof.annotation("exec.dispatch") is obs.NOOP_SPAN
    monkeypatch.setenv("DAS_TPU_TRACE_JAX", "1")
    ann = jaxprof.annotation("exec.dispatch")
    assert ann is not obs.NOOP_SPAN
    with ann:
        pass


def test_profiler_trace_dir_plumbed():
    """DasConfig.profiler_trace_dir rides env DAS_TPU_TRACE_DIR
    (obs.maybe_start_trace consumes it); no dir configured = no trace
    started."""
    assert obs.maybe_start_trace(DasConfig()) is False
    import os

    os.environ["DAS_TPU_TRACE_DIR"] = "/tmp/zobs-trace-dir"
    try:
        cfg = DasConfig.from_env()
        assert cfg.profiler_trace_dir == "/tmp/zobs-trace-dir"
    finally:
        del os.environ["DAS_TPU_TRACE_DIR"]


# -- backpressure + rejection event ---------------------------------------


def test_reject_event_and_counter(traced):
    das, _db = _tensor_das()
    coal = QueryCoalescer(max_batch=4, pipeline_depth=1, queue_max=1)
    tenant = _Tenant("zobs-reject", das)
    # saturate: the queue bound is 1 and no worker is draining yet —
    # fill it, then the next submit must reject
    import queue as _q

    coal._queue.put_nowait((tenant, _pair_query(),
                            QueryOutputFormat.HANDLE, None, None))
    before = obs.counter("serve.rejections").value
    fut = coal.submit(tenant, _pair_query(), QueryOutputFormat.HANDLE)
    with pytest.raises(Exception):
        fut.result(timeout=5)
    assert obs.counter("serve.rejections").value == before + 1
    assert any(e[0] == "serve.reject" for e in obs.events())
    # unblock the stuffed queue entry so the worker (spawned by the
    # rejected submit path? no — rejects never spawn) stays idle
    coal._queue.get_nowait()


# -- ISSUE 26: clocks, the commit path, the answer path, the wire ----------


def test_span_clock_starts_at_enter_not_construction(traced):
    """A span built ahead of `with lock, span:` must not time the wait
    for the lock: both clocks start at __enter__."""
    sp = obs.span("serve.dispatch")
    time.sleep(0.05)                    # "the wait for the lock"
    t_enter = time.perf_counter()
    with sp:
        pass
    (ev,) = [e for e in obs.events() if e[0] == "serve.dispatch"]
    assert ev[3] < 0.04, f"the span timed the wait: {ev[3]:.3f} s"
    assert ev[2] + obs.origin() >= t_enter - 1e-6
    assert obs.origin() == obs.REC._t_origin


def test_span_cpu_ms_against_wall(traced):
    """cpu_ms is the thread's CPU time inside the span: far under the
    wall time of a sleeping span, the wall time (give or take the
    scheduler) of a spinning one."""
    with obs.span("exec.format"):
        time.sleep(0.08)
    with obs.span("exec.materialize"):
        t_cpu = time.thread_time()
        while time.thread_time() - t_cpu < 0.03:   # burn 30 ms of CPU
            pass
    evs = {e[0]: e for e in obs.events()}
    slept, spun = evs["exec.format"], evs["exec.materialize"]
    assert slept[3] >= 0.08 and slept[8]["cpu_ms"] < 0.5 * slept[3] * 1e3
    assert spun[8]["cpu_ms"] >= 29.0
    assert spun[8]["cpu_ms"] <= spun[3] * 1e3 + 1.0   # never over wall


class _NoClock:
    """Stands in for the `time` module of the obs layer: any clock read
    on the disabled path fails the test."""

    def __getattr__(self, name):
        raise AssertionError(f"time.{name} read with tracing off")


def test_new_sites_disabled_path_allocates_nothing(monkeypatch, tmp_path):
    """With DAS_TPU_TRACE off every site ISSUE 26 added (wire.query /
    wire.parse, exec.format, planner.stats, commit.apply / stage / swap,
    dur.wal_append, the lock-wait histogram, the two counters) builds
    no span object and reads no clock of the obs layer."""
    from das_tpu.obs import recorder
    from das_tpu.service import coalesce
    from das_tpu.service.server import DasService
    from das_tpu.storage import durable

    assert not obs.enabled()

    def no_span(*_a, **_k):
        raise AssertionError("a span object was built with tracing off")

    monkeypatch.setattr(recorder._Span, "__init__", no_span)
    monkeypatch.setattr(recorder, "time", _NoClock())
    monkeypatch.setattr(obs, "time", _NoClock())
    das, db = _tensor_das()
    durable.attach(db, str(tmp_path / "snap"))          # WAL armed
    service = DasService()
    token = service.attach_tenant("zobs-off26", das)
    before = {k: c.value for k, c in obs.metrics.COUNTERS.items()}
    hist_before = obs.histogram("serve.lock_wait_ms").total
    dsl = "Node n1 Concept mammal, Link Inheritance $1 n1"
    reply = service.query({"key": token, "query": dsl})
    assert reply["success"], reply
    das.load_metta_text(COMMIT)                         # commit + WAL
    assert service.query({"key": token, "query": dsl})["success"]
    # the lock helper: a bare acquire, no clock, nothing observed
    lock = service.tenants[token].lock
    monkeypatch.setattr(coalesce, "time", _NoClock())
    assert coalesce._acquire(lock) == 0.0
    lock.release()
    assert obs.events() == []
    assert {k: c.value for k, c in obs.metrics.COUNTERS.items()} == before
    assert obs.histogram("serve.lock_wait_ms").total == hist_before


def _children(evs, parent):
    """Spans lying inside `parent`'s interval, by start time."""
    lo, hi = parent[2], parent[2] + parent[3]
    inside = [e for e in evs if e is not parent and e[1] == "X"
              and lo <= e[2] and e[2] + e[3] <= hi + 1e-9]
    return sorted(inside, key=lambda e: e[2])


def test_commit_path_spans_nest_in_order(traced, tmp_path):
    from das_tpu.storage import durable

    das, db = _tensor_das()
    durable.attach(db, str(tmp_path / "snap"))
    obs.reset()
    das.load_metta_text(COMMIT)
    evs = obs.events()
    (apply_,) = [e for e in evs if e[0] == "commit.apply"]
    assert [e[0] for e in _children(evs, apply_)] == [
        "commit.stage", "dur.wal_append", "commit.swap"]
    assert apply_[8]["version"] == db.delta_version
    assert apply_[8]["nodes"] == 1 and apply_[8]["links"] == 1
    (wal,) = [e for e in evs if e[0] == "dur.wal_append"]
    # a span with a duration now, its old attrs kept
    assert wal[1] == "X" and wal[3] > 0
    assert wal[8]["version"] == db.delta_version
    assert wal[8]["kind"] == "delta" and wal[8]["bytes"] > 0
    assert all("cpu_ms" in e[8] for e in evs if e[1] == "X")
    # the instant that closes the commit comes after the swap
    (delta,) = [e for e in evs if e[0] == "commit.delta"]
    assert delta[2] >= apply_[2] + apply_[3] - 1e-9


def test_failed_stage_records_no_commit_swap(traced):
    from das_tpu import fault

    das, db = _tensor_das()
    before = db.delta_version
    obs.reset()
    fault.configure("seed=1;sites=commit_apply;every=1;max=10")
    try:
        with pytest.raises(Exception):
            das.load_metta_text(COMMIT)
    finally:
        fault.configure(None)
    names = [e[0] for e in obs.events()]
    assert "commit.stage" in names and "commit.apply" in names
    assert "commit.swap" not in names and "commit.delta" not in names
    assert db.delta_version == before


def _mammal_queries():
    """Three distinct queries, so a round holds three jobs: who inherits
    from mammal / reptile / animal through one step."""
    return [And([
        Link("Inheritance", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Node("Concept", c)], True),
    ]) for c in ("mammal", "reptile", "animal")]


def test_an_overtaken_round_goes_again_as_one_round(traced):
    """A commit overtakes a round of three: its queries are answered on
    the post-commit store by ONE new round (one `serve.plan`, programs
    by signature, one fetch), none by the per-query dispatcher."""
    das, _db = _tensor_das()
    queries = _mammal_queries()
    before = das.query(queries[0])
    job = das.query_many_dispatch(queries)
    das.load_metta_text(COMMIT)
    expected = [das.query(q) for q in queries]
    assert before == "" and expected[0] != ""    # the commit's row is in
    obs.reset()
    fetches = FETCH_COUNTS["n"]
    assert job.settle() == expected
    assert job.stale_round
    assert obs.counter("exec.stale_reruns").value == 3
    assert obs.counter("exec.per_query_fallbacks").value == 0
    assert FETCH_COUNTS["n"] - fetches == 1
    assert sum(1 for e in obs.events() if e[0] == "serve.plan") == 1


def test_a_commit_that_overtakes_the_second_round_too_ends_one_by_one(traced):
    """The re-run round is itself overtaken mid-stream: what it left goes
    through the per-query dispatcher (no third round), every query is
    answered once, on the last store."""
    das, _db = _tensor_das()
    queries = _mammal_queries()
    job = das.query_many_dispatch(queries)
    das.load_metta_text(COMMIT)
    obs.reset()
    it = job.settle_iter()
    first = next(it)                         # the second round's first answer
    das.load_metta_text('(: "echidna" Concept)\n'
                        '(Inheritance "echidna" "chimp")')
    rest = dict(it)
    assert len(rest) == 2 and first[0] not in rest
    for i, got in rest.items():
        assert got == das.query(queries[i])
    # three for the first round, two more for the second's remainder
    assert obs.counter("exec.stale_reruns").value == 5
    assert obs.counter("exec.per_query_fallbacks").value == 2


def test_stale_reruns_count_the_rerun_queries(traced):
    """The commit race of test_zpipeline under speculation: two groups
    dispatched, a commit overtakes both, every one of their three
    queries is re-run — counted once each: the two of the first group
    as ONE new round (PR 32), the lone one of the second through the
    per-query dispatcher; a mid-stream commit counts only the queries
    not yet answered."""
    das, _db = _tensor_das()
    q = _pair_query()
    job1 = das.query_many_dispatch([q, q])
    job2 = das.query_many_dispatch([q])      # speculative second group
    das.load_metta_text(COMMIT)
    obs.reset()
    expected = das.query(q)                  # a direct call: not counted
    assert job1.settle() == [expected, expected]
    assert job2.settle() == [expected]
    assert obs.counter("exec.stale_reruns").value == 3
    assert obs.counter("exec.per_query_fallbacks").value == 1
    # an undisturbed round re-runs nothing
    assert das.query_many_dispatch([q, q]).settle() == [expected] * 2
    assert obs.counter("exec.stale_reruns").value == 3
    assert obs.counter("exec.per_query_fallbacks").value == 1
    # mid-stream: the first answer was delivered before the commit
    job = das.query_many_dispatch([q, q])
    it = job.settle_iter()
    next(it)
    das.load_metta_text('(: "echidna" Concept)\n'
                        '(Inheritance "echidna" "chimp")')
    assert len(dict(it)) == 1
    assert obs.counter("exec.stale_reruns").value == 4
    assert obs.counter("exec.per_query_fallbacks").value == 2
    # every answer path above recorded its exec.format span
    assert sum(1 for e in obs.events() if e[0] == "exec.format") >= 8


def test_wire_span_shares_the_request_id(traced):
    """One id from the gRPC thread to the answer: wire.query, its child
    wire.parse, serve.submit and serve.answer."""
    from das_tpu.service.server import DasService

    das, _db = _tensor_das()
    service = DasService()
    token = service.attach_tenant("zobs-wire", das)
    reply = service.query({
        "key": token,
        "query": "Node n1 Concept mammal, Link Inheritance $1 n1"})
    assert reply["success"], reply
    # the future resolves INSIDE serve.settle: wait for the span to land
    deadline = time.time() + 10
    while time.time() < deadline and not any(
            e[0] == "serve.settle" for e in obs.events()):
        time.sleep(0.01)
    evs = obs.events()
    ids = {name: {e[4] for e in evs if e[0] == name}
           for name in ("wire.query", "wire.parse", "serve.submit",
                        "serve.answer")}
    assert len(ids["wire.query"]) == 1 and 0 not in ids["wire.query"]
    assert all(v == ids["wire.query"] for v in ids.values()), ids
    (wire,) = [e for e in evs if e[0] == "wire.query"]
    assert [e[0] for e in _children(evs, wire)][:1] == ["wire.parse"]
    # the worker's spans of that request: lock wait on the enclosing
    # spans, the histogram fed once per acquire
    disp = [e for e in evs if e[0] == "serve.dispatch"][0]
    settle = [e for e in evs if e[0] == "serve.settle"][0]
    assert disp[8]["lock_wait_ms"] >= 0 and settle[8]["lock_wait_ms"] >= 0
    assert obs.histogram("serve.lock_wait_ms").total >= 2
    assert any(e[0] == "planner.stats" for e in evs)
    # an invalid query still closes its wire span
    obs.reset()
    assert not service.query({"key": token, "query": "(("})["success"]
    assert [e[0] for e in obs.events() if e[0].startswith("wire.")] == [
        "wire.parse", "wire.query"]


def test_sync_annotation_and_origin_in_chrome_trace(traced, tmp_path):
    """One clock for an operator's two files: the obs trace carries the
    recorder's origin, the device trace the `obs.sync` annotation."""
    from das_tpu.obs import jaxprof

    obs.event("serve.submit", trace=1)
    doc = obs.chrome_trace(obs.events())
    assert doc["metadata"]["perf_counter_origin_s"] == obs.origin()
    cfg = DasConfig(profiler_trace_dir=str(tmp_path / "tb"))
    assert obs.maybe_start_trace(cfg) is True
    assert obs.maybe_stop_trace() is True
    from jax.profiler import ProfileData

    (xplane,) = (tmp_path / "tb").glob("plugins/profile/*/*.xplane.pb")
    sync = [
        {key: value for key, value in ev.stats}
        for plane in ProfileData.from_file(str(xplane)).planes
        for line in plane.lines for ev in line.events
        if ev.name == jaxprof.SYNC_NAME
    ]
    assert len(sync) == 1 and int(sync[0]["t_ns"]) > 0


def test_program_names_in_lowered_modules(monkeypatch):
    """On CPU the lowered module of every PROGRAM_NAMES builder carries
    its declared name: `jit_<name>` is what the device trace's modules
    line shows, and what the benchmark's readers key on."""
    import jax.numpy as jnp

    from das_tpu.query import compiler
    from das_tpu.query.ast import Or
    from das_tpu.query.fused import FusedExecutor
    from das_tpu.parallel.sharded_db import ShardedDB
    from das_tpu.storage import tensor_db

    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    seen = {}

    def spy(site, _digest, fn, **_kw):
        def call(*args, **kwargs):
            text = fn.lower(*args, **kwargs).as_text()
            seen.setdefault(site, set()).add(
                text.split("module @", 1)[1].split(" ", 1)[0])
            return fn(*args, **kwargs)
        return call

    monkeypatch.setattr(obs.proflog, "instrument", spy)
    das, db = _tensor_das()
    q, tree = _matching_query(), Or([_pair_query(), _matching_query()])
    das.query(q)
    das.query(tree)
    ex = FusedExecutor(db)
    plans = compiler.plan_query(db, q)
    ex.execute_exact(plans)
    # two groundings of one shape: ONE group program (ISSUE 30)
    ex.execute_many([plans, compiler.plan_query(db, _pair_query())])
    ex.count_batch([plans, plans])
    sdb = ShardedDB(load_metta_text(animals_metta()), DasConfig())
    sdas = DistributedAtomSpace(database_name="zobs-s", db=sdb)
    sdas.query(q)
    sdas.query(tree)
    # two groundings of one shape on the mesh: ONE group program (ISSUE 43)
    from das_tpu.parallel.fused_sharded import get_sharded_executor

    get_sharded_executor(sdb).execute_many([
        compiler.plan_query(sdb, And([
            Link("Inheritance", [Variable("$1"), Variable("$2")], True),
            Link("Inheritance", [Variable("$2"), Node("Concept", c)], True),
        ])) for c in ("mammal", "reptile")   # `q` itself is a cache hit
    ])
    sdas.load_metta_text(COMMIT)
    want = {
        "fused": "jit_das_fused", "fused_tree": "jit_das_fused_tree",
        "fused_group": "jit_das_fused_group",
        "fused_exact": "jit_das_fused_exact",
        "count_batch": "jit_das_count_batch",
        "sharded": "jit_das_sharded",
        "sharded_group": "jit_das_sharded_group",
        "sharded_tree": "jit_das_sharded_tree",
    }
    for site, name in want.items():
        assert site in seen, (site, sorted(seen))
        assert any(n.startswith(name) for n in seen[site]), (site, seen)
    # the commit-path programs, lowered directly
    k = jnp.arange(8, dtype=jnp.int64)
    o = jnp.arange(8, dtype=jnp.int32)
    merge = tensor_db._merge_padded.lower(k, o, k[:2], o[:2])
    assert "module @jit_das_merge_padded" in merge.as_text()
    # the named scopes of the merge's two stages ride the debug info
    dbg = merge.as_text(debug_info=True)
    assert "searchsorted" in dbg and "shift_network" in dbg
    text = tensor_db._insert_rows.lower(k, k[:2], jnp.int32(1)).as_text()
    assert "module @jit_das_insert_rows" in text
    merges = list(sdb.tables._merge_cache.values())
    assert merges and all(
        m.__name__ == "das_merge_sharded" for m in merges)
    # every declared name was met
    met = {n[len("jit_"):] for names in seen.values() for n in names}
    met |= {"das_merge_padded", "das_insert_rows", "das_merge_sharded"}
    for name in obs.PROGRAM_NAMES:
        assert any(m.startswith(name) for m in met), name
    assert "das_fused_group" in met


def test_traced_group_ticks_one_program_five_lanes(traced):
    """ISSUE 30's mechanism, observable: five same-shape queries of one
    batch are ONE enqueue — one `exec.dispatch` span with `lanes=5`, one
    program and five lanes on the counters, one settle fetch."""
    from das_tpu.query import compiler
    from das_tpu.query.fused import get_executor

    das, db = _tensor_das(DasConfig(result_cache_size=0))
    ex = get_executor(db)
    concepts = ["animal", "mammal", "reptile", "plant", "dinosaur"]
    plans = [
        compiler.plan_query(db, And([
            Link("Inheritance", [Variable("$1"), Variable("$2")], True),
            Link("Inheritance", [Variable("$2"), Node("Concept", c)], True),
        ]))
        for c in concepts
    ]
    ex.execute_many(plans)      # learn the capacities: no retry below
    want = [ex.execute(p).count for p in plans]
    obs.reset()
    got = ex.execute_many(plans)
    assert [r.count for r in got] == want
    assert obs.counter("exec.group_programs").value == 1
    assert obs.counter("exec.group_lanes").value == 5
    spans = [e for e in obs.events() if e[0] == "exec.dispatch"]
    assert len(spans) == 1 and spans[0][8]["lanes"] == 5
    fetches = [e for e in obs.events() if e[0] == "exec.settle_fetch"]
    assert len(fetches) == 1
    attrs = fetches[0][8]
    assert (attrs["jobs"], attrs["programs"]) == (5, 1)
    # ISSUE 42: the wait for the device inside the fetch, and the
    # device's queue as it began (this round's one program in it)
    assert 0 <= attrs["wait_ms"] <= fetches[0][3] * 1e3
    assert attrs["inflight"] == 1 and spans[0][8]["inflight"] == 0
    verdicts = [e for e in obs.events() if e[0] == "exec.verdict"]
    assert [v[8] for v in verdicts] == [
        {"lanes": 5, "done": True, "cpu_ms": v[8]["cpu_ms"]}
        for v in verdicts] and len(verdicts) == 5
    # a lone job's span carries no lanes attr, and counts 1 / 1
    obs.reset()
    ex.execute_many(plans[:1])
    assert obs.counter("exec.group_programs").value == 1
    assert obs.counter("exec.group_lanes").value == 1
    (lone,) = [e for e in obs.events() if e[0] == "exec.dispatch"]
    assert "lanes" not in lone[8]


def test_dl014_pins_program_names(tmp_path):
    """DL014's new registry: an undeclared `named_program` literal
    fires, and so does a declared name no builder uses."""
    from das_tpu.analysis import run_analysis

    src = tmp_path / "progs.py"
    src.write_text(
        'from das_tpu import obs\n'
        'PROGRAM_NAMES = ("das_a", "das_stale")\n'
        'def build(fn):\n'
        '    obs.named_program("das_a", fn)\n'
        '    return obs.named_program("das_typo", fn)\n'
    )
    msgs = "\n".join(
        f.message for f in run_analysis([src], rules=["DL014"]))
    assert "das_typo" in msgs and "das_stale" in msgs
    with pytest.raises(KeyError):
        obs.named_program("das_typo", lambda: None)


# -- the job builder's span and counters (ISSUE 41) -----------------------


def _concept_queries(names):
    return [And([
        Link("Inheritance", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Node("Concept", c)], True),
    ]) for c in names]


def _builds():
    return [e for e in obs.events() if e[0] == "exec.build"]


def test_exec_build_is_one_span_a_batch(traced):
    """`exec.build`: ONE span per batch of built jobs, before the
    batch's `exec.dispatch`; attrs queries (cache-missing,
    de-duplicated), shapes, templates_built; `exec.template_builds` /
    `exec.template_hits` move with it."""
    das, _db = _tensor_das()
    two_shapes = _concept_queries(["mammal", "reptile", "animal"]) + [And([
        Link("Similarity", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Node("Concept", "mammal")], True),
    ])]
    das.query_many_dispatch(two_shapes + two_shapes[:1]).settle()
    (build,) = _builds()                     # the duplicate: not built
    assert build[8]["queries"] == 4
    assert build[8]["shapes"] == build[8]["templates_built"] == 2
    assert obs.counter("exec.template_builds").value == 2
    assert obs.counter("exec.template_hits").value == 2
    enqueues = [e for e in obs.events() if e[0] == "exec.dispatch"]
    assert enqueues and all(build[2] + build[3] <= e[2] for e in enqueues)
    # a second batch of the same shapes is filled from the kept
    # templates; a batch the cache answers whole builds nothing
    das.query_many_dispatch(
        _concept_queries(["human", "monkey", "chimp"])).settle()
    assert len(_builds()) == 2 and _builds()[1][8]["templates_built"] == 0
    assert obs.counter("exec.template_builds").value == 2
    assert obs.counter("exec.template_hits").value == 5
    das.query_many_dispatch(two_shapes).settle()
    assert len(_builds()) == 2
    # a commit drops the templates: the shape is built again
    das.load_metta_text(COMMIT)
    das.query_many_dispatch(_concept_queries(["mammal", "reptile"])).settle()
    assert _builds()[-1][8]["templates_built"] == 1
    assert obs.counter("exec.template_builds").value == 3


def test_exec_build_sits_inside_serve_dispatch(traced):
    """Served: every `exec.build` lies inside a `serve.dispatch` of the
    worker thread, at most one a group."""
    das, _db = _tensor_das()
    _serve(das, _concept_queries(["mammal", "reptile", "animal"]))
    outers = [e for e in obs.events() if e[0] == "serve.dispatch"]
    builds = _builds()
    assert 1 <= len(builds) <= len(outers)
    for build in builds:
        assert sum(
            o[7] == build[7] and o[2] <= build[2]
            and build[2] + build[3] <= o[2] + o[3] for o in outers
        ) == 1


def test_exec_build_disabled_path_allocates_nothing(monkeypatch):
    """Tracing off: the builder packs no attribute dict, builds no span
    object, reads no clock of the obs layer and moves no counter."""
    from das_tpu.obs import recorder
    from das_tpu.query import fused

    assert not obs.enabled()

    def no_span(*_a, **_k):
        raise AssertionError("a span object was built with tracing off")

    monkeypatch.setattr(recorder._Span, "__init__", no_span)
    monkeypatch.setattr(recorder, "time", _NoClock())
    monkeypatch.setattr(obs, "time", _NoClock())
    das, db = _tensor_das()
    before = {k: c.value for k, c in obs.metrics.COUNTERS.items()}
    queries = _concept_queries(["mammal", "reptile", "animal"])
    answers = das.query_many_dispatch(queries).settle()
    assert answers == [das.query(q) for q in queries]
    assert len(fused.get_executor(db)._templates) == 1
    assert obs.events() == []
    assert {k: c.value for k, c in obs.metrics.COUNTERS.items()} == before
