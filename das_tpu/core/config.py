"""One typed configuration object.

Replaces the reference's three config mechanisms (env vars, module-level
flag constants, argparse — SURVEY.md §5) with a single dataclass.  Env vars
are still honored as *overrides* so container deployments keep working.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: THE declared set of DAS_TPU_* environment flags, mapping each name to
#: (DasConfig field or None for module-local flags, one-line description).
#: daslint rule DL003 (das_tpu/analysis) pins this registry against the
#: code in both directions — an `os.environ` read of an undeclared name
#: fails lint, and so does a registered name nothing reads — and
#: scripts/gen_env_table.py renders it into ARCHITECTURE.md §11 so the
#: operator docs cannot drift from the code either.  Module-local flags
#: (field None) are debug/bring-up switches read at their point of use;
#: anything a deployment should tune belongs on DasConfig.
ENV_REGISTRY: Dict[str, Tuple[Optional[str], str]] = {
    "DAS_TPU_BACKEND": (
        "backend", "storage backend: memory / tensor / sharded"),
    "DAS_TPU_PLATFORM": (
        "platform", "force a jax platform (e.g. cpu) for the store"),
    "DAS_TPU_CHECKPOINT": (
        "checkpoint_path",
        "checkpoint dir auto-loaded by a bare DistributedAtomSpace()"),
    "DAS_TPU_PLANNER": (
        "use_planner",
        "cost-based query planner: auto (on) / on / off "
        "(das_tpu/planner/__init__.py enabled())"),
    "DAS_TPU_PLANNER_DP_MAX": (
        None,
        "clause ceiling for the planner's exact DP join-order search; "
        "larger conjunctions order greedily (das_tpu/planner/search.py; "
        "default 8)"),
    "DAS_TPU_TREE_FUSION": (
        "use_tree_fusion",
        "whole-tree fused execution of Or/negation plan trees: auto "
        "(on) / on / off (das_tpu/query/tree.py tree_fusion_enabled())"),
    "DAS_TPU_COALESCE_MAX_BATCH": (
        "coalesce_max_batch",
        "widest batch one coalescer drain may form (service/coalesce.py)"),
    "DAS_TPU_PIPELINE_DEPTH": (
        "pipeline_depth",
        "floor of the in-flight dispatch window; 1 = serial (no "
        "adaptation)"),
    "DAS_TPU_PIPELINE_DEPTH_MAX": (
        "pipeline_depth_max",
        "ceiling of the RTT-adaptive in-flight window "
        "(service/coalesce.py sizes it as ceil(rtt/dispatch_cost))"),
    "DAS_TPU_COALESCE_QUEUE_MAX": (
        "coalesce_queue_max",
        "coalescer submit-queue backpressure bound; past it submits "
        "are rejected (CoalescerSaturatedError); 0 = unbounded"),
    "DAS_TPU_RESULT_CACHE": (
        "result_cache_size",
        "delta-versioned result cache entries per executor; 0 disables"),
    "DAS_TPU_DEADLINE_MS": (
        "query_deadline_ms",
        "per-query serving deadline in ms: queued/grouped entries past "
        "it expire with DasDeadlineError and RPC waits are bounded "
        "(service/coalesce.py, service/server.py); 0 = off"),
    "DAS_TPU_BREAKER_THRESHOLD": (
        "breaker_failure_threshold",
        "consecutive retryable settle failures that trip a tenant's "
        "serving circuit breaker to degraded mode (das_tpu/fault "
        "CircuitBreaker); 0 disables the breaker"),
    "DAS_TPU_BREAKER_COOLDOWN_MS": (
        "breaker_cooldown_ms",
        "open-breaker cooldown before a half-open probe may restore "
        "full service (das_tpu/fault CircuitBreaker)"),
    "DAS_TPU_FAULT": (
        None,
        "deterministic fault-injection spec, e.g. "
        "seed=7;sites=settle_fetch,commit_apply;rate=0.25;max=4 "
        "(das_tpu/fault; unset = off, no-allocation fast path)"),
    "DAS_TPU_SNAPSHOT_DIR": (
        "snapshot_dir",
        "dasdur snapshot root (storage/durable.py): crash-consistent "
        "generational snapshots + write-ahead delta log auto-attach; a "
        "bare DistributedAtomSpace() restores the newest valid "
        "generation + WAL replay; unset = no durability"),
    "DAS_TPU_WAL": (
        "wal",
        "write-ahead delta log mode: auto (armed whenever a snapshot "
        "root is attached) / off (snapshots only — commits after the "
        "last snapshot are lost on crash) (storage/durable.py "
        "wal_enabled)"),
    "DAS_TPU_SNAPSHOT_KEEP": (
        "snapshot_keep",
        "completed snapshot generations retained after each new "
        "snapshot (storage/durable.py prune_generations; default 2)"),
    "DAS_TPU_XLA_CACHE": (
        None,
        "=0 disables the persistent XLA compile cache and the CapStore "
        "(das_tpu/__init__.py cache_root: JAX_COMPILATION_CACHE_DIR "
        "when set, else <checkout>/.jax_cache)"),
    "DAS_TPU_COALESCE": (
        None, "=0 disables serving-edge query coalescing "
              "(service/server.py)"),
    "DAS_TPU_STAR": (
        None, "=0 disables the star-count degree-product fast path "
              "(query/starcount.py)"),
    "DAS_TPU_STAR_FOLD": (
        None, "star-count fold placement: host (default) / device "
              "(query/starcount.py)"),
    "DAS_TPU_HOST_COUNT": (
        None, "=0 disables the host-side count shortcut in the fused "
              "executor (query/fused.py)"),
    "DAS_TPU_COLUMNAR": (
        None, "=0 disables the columnar ingest fast path "
              "(ingest/pipeline.py)"),
    "DAS_TPU_NO_NATIVE": (
        None, "set to skip the C++ native ingest .so (ingest/native.py)"),
    "DAS_TPU_NATIVE_LIB": (
        None, "override path of the native ingest .so (ingest/native.py)"),
    "DAS_TPU_FINALIZE_VERBOSE": (
        None, "set to log per-phase columnar finalize timings "
              "(storage/columnar.py)"),
    "DAS_TPU_TEST_PLATFORM": (
        None, "test-suite jax platform override (tests/conftest.py; "
              "default cpu with an 8-device virtual mesh)"),
    "DAS_TPU_TRACE": (
        None, "=1/on enables the structured trace recorder + metric "
              "layer (das_tpu/obs; default off = no-allocation no-op)"),
    "DAS_TPU_PROFLOG": (
        None, "=1/on enables the program ledger — per-signature XLA "
              "compile wall time, cost/memory analysis "
              "(das_tpu/obs/proflog.py; default off = identity fast "
              "path, programs run exactly un-instrumented)"),
    "DAS_TPU_TRACE_RING": (
        None, "span ring-buffer capacity of the trace recorder "
              "(das_tpu/obs/recorder.py; default 65536, oldest drop)"),
    "DAS_TPU_TRACE_JAX": (
        None, "=1 wraps the dispatch/settle halves in jax.profiler "
              "TraceAnnotation scopes (das_tpu/obs/jaxprof.py) so host "
              "spans line up with the XLA device timeline"),
    "DAS_TPU_TRACE_DIR": (
        "profiler_trace_dir",
        "jax.profiler start_trace output dir (obs/jaxprof.py "
        "maybe_start_trace; unset = no device trace)"),
    "DAS_TPU_METRICS_PORT": (
        None, "Prometheus text-exposition HTTP port on the service "
              "(service/server.py GET /metrics); unset/0 = off; setting "
              "it implies DAS_TPU_TRACE=1 unless that is explicitly 0"),
}

#: registry names whose readers live outside das_tpu/ (DL003 skips its
#: "declared but never read" leg for these)
ENV_DECLARED_EXTERNAL: Tuple[str, ...] = ("DAS_TPU_TEST_PLATFORM",)


@dataclass
class DasConfig:
    # --- storage / backend selection -------------------------------------
    backend: str = "tensor"          # "memory" | "tensor" | "sharded"
    platform: Optional[str] = None   # None = jax default; "cpu" to force host
    # checkpoint dir auto-loaded at construction — the TPU-native analogue
    # of the reference's env-var Mongo/Redis endpoints: a bare
    # `DistributedAtomSpace()` (reference scripts/benchmark.py:203) attaches
    # to this persisted store instead of a database server
    checkpoint_path: Optional[str] = None
    # dasdur durability root (ISSUE 15, storage/durable.py): when set, a
    # bare DistributedAtomSpace() RESTORES the newest valid snapshot
    # generation + WAL replay (seconds instead of minutes for a replica
    # cold start), and live commits append fsynced write-ahead records —
    # a crash loses nothing past the last completed fsync.  None = no
    # durability (the pre-dasdur behavior exactly).
    snapshot_dir: Optional[str] = None
    # write-ahead delta log mode: "auto" arms the WAL whenever a
    # snapshot root is attached; "off" keeps snapshots only (commits
    # after the last snapshot are lost on crash)
    wal: str = "auto"
    # completed snapshot generations kept after each new snapshot
    # (older ones — and their WALs — are pruned)
    snapshot_keep: int = 2

    # --- mesh / sharding --------------------------------------------------
    mesh_shape: Optional[Tuple[int, ...]] = None  # None = all local devices
    mesh_axis_names: Tuple[str, ...] = ("shards",)

    # --- query engine -----------------------------------------------------
    no_overload: bool = False  # forbid two vars sharing a value in ordered asn
    # capacity (rows) for padded device result buffers; doubled on overflow
    initial_result_capacity: int = 1 << 14
    max_result_capacity: int = 1 << 24
    # incremental commits: total delta atoms held as an LSM overlay before
    # the store is fully re-finalized (storage/tensor_db.py refresh)
    delta_merge_threshold: int = 1 << 16
    # cost-based whole-plan query planner (das_tpu/planner/): cardinality
    # estimates from the wildcard-index degree statistics pick join
    # order, expected route, and the initial capacity of every
    # intermediate BEFORE anything is dispatched — replacing the
    # greedy smallest-first ordering and the blind
    # initial_result_capacity seed so most queries settle in retry
    # round 0.  "auto" = on (the planner is pure host arithmetic);
    # "off" restores the legacy heuristics (the bench A/B flips this).
    # Env DAS_TPU_PLANNER overrides (see das_tpu/planner/__init__.py).
    use_planner: str = "auto"
    # whole-tree fused execution (ISSUE 10): an Or/negation plan tree
    # whose every node is an ordered conjunction over one shared
    # variable universe compiles to ONE planner-costed program — every
    # conjunction site plus the in-program union (concat + dedup) and
    # negation (anti-join) settle in a single dispatch/transfer, where
    # the tree executor pays one dispatch/settle round trip per site.
    # "auto" = on (answers are bit-identical to the tree executor —
    # ineligible shapes fall back to it); "off" restores per-site tree
    # execution (the bench A/B flips this).  Env DAS_TPU_TREE_FUSION
    # overrides (see das_tpu/query/tree.py tree_fusion_enabled()).
    use_tree_fusion: str = "auto"
    # sharded backend: where unordered/negated/nested query trees run —
    # "mesh" (default: the tree evaluator with row-sharded composite
    # tables, parallel/sharded_tree.py), "tensor" (legacy single-device
    # tree over a replicated store copy), or "host"
    sharded_tree_fallback: str = "mesh"

    # --- serving edge -----------------------------------------------------
    # widest batch one coalescer drain may form (service/coalesce.py); the
    # served path's throughput knob — the pre-PR-1 chip records showed per-query cost
    # halving as concurrency doubles, so deployments need to tune this
    coalesce_max_batch: int = 256
    # coalescer execution pipelining (service/coalesce.py): the FLOOR of
    # the in-flight dispatch window.  Depth 2 lets batch N+1's device
    # program execute while batch N's host settle/materialization runs;
    # 1 restores strictly serial batches (and disables adaptation).
    pipeline_depth: int = 2
    # ceiling of the RTT-adaptive window: the worker sizes the window to
    # ceil(settle_rtt / dispatch_cost) from its own EWMAs — where a
    # settle costs many dispatches it deepens toward this bound; where
    # the two are comparable the ratio stays near 1 and the
    # pipeline_depth floor holds
    pipeline_depth_max: int = 8
    # backpressure bound on the coalescer submit queue: past it,
    # submit() rejects with CoalescerSaturatedError instead of letting
    # an open-loop client population grow host memory without limit.
    # 0 = unbounded (the pre-bound behavior).
    coalesce_queue_max: int = 8192
    # per-query serving deadline (ms): the coalescer worker expires
    # queued/grouped entries past it with a typed DasDeadlineError,
    # settle abandons expired futures host-side, and the RPC wait in
    # service/server.py is bounded — no RPC thread ever blocks forever.
    # 0 = off (the pre-deadline behavior exactly).
    query_deadline_ms: int = 0
    # per-tenant serving circuit breaker (das_tpu/fault CircuitBreaker,
    # driven by service/coalesce.py): this many CONSECUTIVE
    # retryable-class settle failures (or saturation rejections) trip
    # the tenant to degraded mode — speculation off, window at its
    # floor, cache-hit answers still served, fresh dispatches rejected
    # retryable with a retry-after hint.  0 disables the breaker.
    breaker_failure_threshold: int = 8
    # how long an OPEN breaker waits before granting ONE half-open
    # probe; the probe's success restores full service, its failure
    # restarts the cooldown
    breaker_cooldown_ms: int = 250
    # device-resident query result cache (query/fused.py ResultCache):
    # max cached results per executor, keyed by plan shape + grounded
    # values and guarded by the backend's incremental-commit counter
    # (storage/delta.py delta_version) so commits invalidate stale
    # entries.  0 disables.  Consulted by the serving/batched paths —
    # repeated hot queries skip the device entirely.
    result_cache_size: int = 256

    # --- ingest -----------------------------------------------------------
    pattern_black_list: List[str] = field(default_factory=list)
    ingest_chunk_size: int = 10_000_000
    use_native_ingest: bool = True   # C++ fast path when the .so is present

    # --- observability ----------------------------------------------------
    log_file: str = "/tmp/das_tpu.log"
    log_level: str = "INFO"
    # jax.profiler start_trace output directory (env DAS_TPU_TRACE_DIR):
    # when set (and the obs layer is on), serve()/dump_trace start a
    # device trace here so the hardware run can correlate host spans
    # (das_tpu/obs) with the XLA device timeline in Perfetto.  None =
    # no device trace (the default; host-side tracing is independent).
    profiler_trace_dir: Optional[str] = None

    @staticmethod
    def from_env(**overrides) -> "DasConfig":
        cfg = DasConfig(**overrides)
        backend = os.environ.get("DAS_TPU_BACKEND")
        if backend:
            cfg.backend = backend
        platform = os.environ.get("DAS_TPU_PLATFORM")
        if platform:
            cfg.platform = platform
        checkpoint = os.environ.get("DAS_TPU_CHECKPOINT")
        if checkpoint:
            cfg.checkpoint_path = checkpoint
        snapshot_dir = os.environ.get("DAS_TPU_SNAPSHOT_DIR")
        if snapshot_dir:
            cfg.snapshot_dir = snapshot_dir
        wal = os.environ.get("DAS_TPU_WAL")
        if wal:
            cfg.wal = wal
        snapshot_keep = os.environ.get("DAS_TPU_SNAPSHOT_KEEP")
        if snapshot_keep:
            cfg.snapshot_keep = int(snapshot_keep)
        planner = os.environ.get("DAS_TPU_PLANNER")
        if planner:
            cfg.use_planner = planner
        tree_fusion = os.environ.get("DAS_TPU_TREE_FUSION")
        if tree_fusion:
            cfg.use_tree_fusion = tree_fusion
        max_batch = os.environ.get("DAS_TPU_COALESCE_MAX_BATCH")
        if max_batch:
            cfg.coalesce_max_batch = int(max_batch)
        depth = os.environ.get("DAS_TPU_PIPELINE_DEPTH")
        if depth:
            cfg.pipeline_depth = int(depth)
        depth_max = os.environ.get("DAS_TPU_PIPELINE_DEPTH_MAX")
        if depth_max:
            cfg.pipeline_depth_max = int(depth_max)
        queue_max = os.environ.get("DAS_TPU_COALESCE_QUEUE_MAX")
        if queue_max:
            cfg.coalesce_queue_max = int(queue_max)
        cache = os.environ.get("DAS_TPU_RESULT_CACHE")
        if cache:
            cfg.result_cache_size = int(cache)
        deadline = os.environ.get("DAS_TPU_DEADLINE_MS")
        if deadline:
            cfg.query_deadline_ms = int(deadline)
        breaker_threshold = os.environ.get("DAS_TPU_BREAKER_THRESHOLD")
        if breaker_threshold:
            cfg.breaker_failure_threshold = int(breaker_threshold)
        breaker_cooldown = os.environ.get("DAS_TPU_BREAKER_COOLDOWN_MS")
        if breaker_cooldown:
            cfg.breaker_cooldown_ms = int(breaker_cooldown)
        trace_dir = os.environ.get("DAS_TPU_TRACE_DIR")
        if trace_dir:
            cfg.profiler_trace_dir = trace_dir
        return cfg
