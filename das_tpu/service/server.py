"""Multi-tenant gRPC service hosting named AtomSpaces.

Role of /root/reference/service/server.py:109-257, rebuilt for the TPU
backend with three deliberate departures:

* **No global lock.**  The reference serializes every RPC behind one
  Condition (server.py:114-115); here each atom space carries its own
  lock so tenants never block each other, and read RPCs on the device
  backend are just jitted probes.
* **Error-path status.**  The reference's async KB loader has no failure
  path (server.py:92-106); loading here transitions READY→LOADING→READY
  or →FAILED(msg), observable via check_das_status.
* **No protoc codegen.**  gRPC generic handlers + the JSON codec in
  protocol.py carry the identical 10-RPC contract.

KB sources accepted by load_knowledge_base: a local path (file or
directory of .metta/.scm files), a ``file://`` URL, or a ``.tgz``/``.tar``
archive of those (unpacked with tarfile, not os.system).
"""

from __future__ import annotations

import random
import shutil
import string
import tarfile
import tempfile
import threading
import traceback
from concurrent import futures
from concurrent.futures import TimeoutError as FuturesTimeoutError
from enum import Enum
from typing import Dict, Optional

import grpc

from das_tpu import obs
from das_tpu.api.atomspace import DistributedAtomSpace, QueryOutputFormat
from das_tpu.core.exceptions import (
    BreakerOpenError,
    CoalescerSaturatedError,
    DasDeadlineError,
)
from das_tpu.service import protocol
from das_tpu.service.query_dsl import parse_query
from das_tpu.utils.logger import logger

#: the final backstop on any coalesced future wait when deadlines are
#: OFF: the worker normally resolves every future (expiry included),
#: so this only fires if the serving loop itself wedged — but "an RPC
#: thread never blocks forever" must hold unconditionally (ISSUE 13)
_RPC_WAIT_BACKSTOP_S = 600.0


class AtomSpaceStatus(str, Enum):
    READY = "Ready"
    LOADING = "Loading knowledge base"
    FAILED = "Load failed"


_OUTPUT_FORMATS = {
    "HANDLE": QueryOutputFormat.HANDLE,
    "DICT": QueryOutputFormat.ATOM_INFO,
    "JSON": QueryOutputFormat.JSON,
}


def _random_token(length: int = 20) -> str:
    return "".join(random.choice(string.ascii_lowercase) for _ in range(length))


class _Tenant:
    def __init__(self, name: str, das: DistributedAtomSpace):
        self.name = name
        self.das = das
        self.status = AtomSpaceStatus.READY
        self.status_detail = ""
        self.lock = threading.RLock()
        #: per-TENANT query coalescer (service/coalesce.py), created on
        #: first use: tenants never serialize behind each other's batches
        #: (the service's no-global-lock design holds under coalescing)
        self.coalescer = None
        self._coalescer_lock = threading.Lock()

    def get_coalescer(self):
        if self.coalescer is None:
            with self._coalescer_lock:
                if self.coalescer is None:
                    from das_tpu.service.coalesce import QueryCoalescer

                    # ceiling and pipeline depth come from the tenant's
                    # DasConfig (DAS_TPU_COALESCE_MAX_BATCH /
                    # DAS_TPU_PIPELINE_DEPTH via from_env), not hardcoded
                    # constants: the served path's throughput knobs must
                    # be deployment-tunable
                    cfg = getattr(self.das, "config", None)
                    self.coalescer = QueryCoalescer(
                        max_batch=getattr(cfg, "coalesce_max_batch", None),
                        pipeline_depth=getattr(cfg, "pipeline_depth", None),
                        pipeline_depth_max=getattr(
                            cfg, "pipeline_depth_max", None
                        ),
                        queue_max=getattr(cfg, "coalesce_queue_max", None),
                        deadline_ms=getattr(cfg, "query_deadline_ms", None),
                        breaker_threshold=getattr(
                            cfg, "breaker_failure_threshold", None
                        ),
                        breaker_cooldown_ms=getattr(
                            cfg, "breaker_cooldown_ms", None
                        ),
                    )
        return self.coalescer


class _KnowledgeBaseLoader(threading.Thread):
    """Async KB fetch+load with an explicit failure transition."""

    def __init__(self, tenant: _Tenant, url: str):
        super().__init__(daemon=True)
        self.tenant = tenant
        self.url = url

    def run(self):
        temp_dir = tempfile.mkdtemp()
        try:
            path = self.url
            if path.startswith("file://"):
                path = path[len("file://"):]
            if path.endswith((".tgz", ".tar.gz", ".tar")):
                with tarfile.open(path) as tar:
                    tar.extractall(temp_dir, filter="data")
                source = temp_dir
            else:
                source = path
            with self.tenant.lock:
                self.tenant.das.load_knowledge_base(source)
                self.tenant.status = AtomSpaceStatus.READY
                self.tenant.status_detail = ""
        except Exception as exc:  # noqa: BLE001 — surfaced via status RPC
            logger().info(f"KB load failed for '{self.tenant.name}': {exc}")
            self.tenant.status = AtomSpaceStatus.FAILED
            self.tenant.status_detail = str(exc)
        finally:
            shutil.rmtree(temp_dir, ignore_errors=True)


class DasService:
    """RPC method implementations (request dict -> Status dict)."""

    def __init__(self, backend: Optional[str] = None):
        import os

        self.backend = backend
        self.tenants: Dict[str, _Tenant] = {}
        self.registry_lock = threading.Lock()
        # serving-edge query coalescing: concurrent singles batch into one
        # device program + one fetch, PER TENANT (service/coalesce.py);
        # DAS_TPU_COALESCE=0 restores the direct per-RPC path
        self.coalesce_enabled = os.environ.get("DAS_TPU_COALESCE", "1") != "0"

    def coalescer_stats(self) -> Dict[str, int]:
        """Aggregate serving-path observability (bench/tests): per-tenant
        coalescer counters, the execution pipeline's in-flight high-water
        mark, the result caches' hit/miss/invalidation counters (the
        conjunctive, tree-composite and count-batch caches all fold in),
        and the process-wide route counters — incl. the sharded mesh
        route (`sharded`) now that mesh tenants ride the same pipeline.  `tenants` breaks the aggregates down per tenant
        name so a noisy mesh tenant is distinguishable from a quiet
        single-device one."""
        out = {
            "batches": 0, "items": 0, "max_batch": 0, "max_batch_limit": 0,
            "pipeline_depth": 0, "pipeline_depth_max": 0,
            "effective_depth": 0, "rtt_ewma_ms": 0.0,
            "dispatch_ewma_ms": 0.0, "inflight_peak": 0,
            "speculative_dispatches": 0, "early_settles": 0,
            "queue_rejections": 0,
            "deadline_expired": 0, "breaker_rejections": 0,
            "breaker_trips": 0, "breaker_recoveries": 0,
            "breaker_open_tenants": 0,
            "cache_hits": 0, "cache_misses": 0, "cache_invalidations": 0,
            "tenants": {},
        }
        for tenant in list(self.tenants.values()):
            per = {
                "backend": getattr(
                    getattr(tenant.das, "config", None), "backend", None
                ),
                "inflight_peak": 0,
            }
            c = tenant.coalescer
            if c is not None:
                snap = c.snapshot()
                out["batches"] += snap["batches"]
                out["items"] += snap["items"]
                out["max_batch"] = max(out["max_batch"], snap["max_batch"])
                out["max_batch_limit"] = max(
                    out["max_batch_limit"], snap["max_batch_limit"]
                )
                out["pipeline_depth"] = max(
                    out["pipeline_depth"], snap["pipeline_depth"]
                )
                out["pipeline_depth_max"] = max(
                    out["pipeline_depth_max"], snap["pipeline_depth_max"]
                )
                # the deepest adaptive window any tenant reached, with
                # BOTH inputs of THAT tenant's ceil(rtt/dispatch) sizing
                # — taking independent maxima across tenants would pair
                # one tenant's wire with another's dispatch cost, a
                # ratio no window actually uses; per-tenant dicts below
                # are the authoritative breakdown.  Without the dispatch
                # EWMA an operator cannot tell "wire is fast" from
                # "dispatch cost inflated" when the window sticks at
                # the floor (§10)
                if snap["effective_depth"] >= out["effective_depth"]:
                    out["effective_depth"] = snap["effective_depth"]
                    out["rtt_ewma_ms"] = snap["rtt_ewma_ms"]
                    out["dispatch_ewma_ms"] = snap["dispatch_ewma_ms"]
                out["inflight_peak"] = max(
                    out["inflight_peak"], snap["inflight_peak"]
                )
                out["speculative_dispatches"] += snap["speculative_dispatches"]
                out["early_settles"] += snap["early_settles"]
                out["queue_rejections"] += snap["queue_rejections"]
                # robustness aggregates (ISSUE 13): deadline misses,
                # degraded-mode rejections and the breaker lifecycle —
                # per-tenant state below tells WHICH tenant is degraded
                out["deadline_expired"] += snap["deadline_expired"]
                out["breaker_rejections"] += snap["breaker_rejections"]
                out["breaker_trips"] += snap["breaker_trips"]
                out["breaker_recoveries"] += snap["breaker_recoveries"]
                if snap["breaker_state"] != "closed":
                    out["breaker_open_tenants"] += 1
                per.update(
                    batches=snap["batches"],
                    items=snap["items"],
                    max_batch=snap["max_batch"],
                    inflight_peak=snap["inflight_peak"],
                    effective_depth=snap["effective_depth"],
                    rtt_ewma_ms=snap["rtt_ewma_ms"],
                    dispatch_ewma_ms=snap["dispatch_ewma_ms"],
                    speculative_dispatches=snap["speculative_dispatches"],
                    early_settles=snap["early_settles"],
                    queue_rejections=snap["queue_rejections"],
                    deadline_expired=snap["deadline_expired"],
                    breaker_state=snap["breaker_state"],
                    breaker_rejections=snap["breaker_rejections"],
                    breaker_trips=snap["breaker_trips"],
                    breaker_recoveries=snap["breaker_recoveries"],
                    # last-K (rtt_ewma, dispatch_ewma, effective_depth)
                    # samples (ISSUE 12 satellite) — the §10
                    # window-formula history, per tenant
                    window_history=snap["window_history"],
                )
            db = getattr(tenant.das, "db", None)
            if db is not None:
                from das_tpu.query.fused import result_cache_stats

                cache = result_cache_stats(db)
                out["cache_hits"] += cache["hits"]
                out["cache_misses"] += cache["misses"]
                out["cache_invalidations"] += cache["invalidations"]
                per["cache_hits"] = cache["hits"]
                per["cache_misses"] = cache["misses"]
            out["tenants"][tenant.name] = per
        from das_tpu.query.compiler import ROUTE_COUNTS

        out["routes"] = dict(ROUTE_COUNTS)
        # cost-based planner telemetry (das_tpu/planner, ISSUE 8):
        # planned-vs-greedy traffic, retry rounds planned programs still
        # paid, and the summed estimated-vs-actual join rows whose ratio
        # is the production estimator-error signal
        from das_tpu import planner

        out["planner"] = planner.snapshot()
        # program ledger (das_tpu/obs/proflog.py, ISSUE 14): XLA
        # compiles observed, total/cold-start compile seconds, the
        # ledger hit rate, and the per-site byte-model calibration
        # aggregate — the device-side compile story next to the host
        # serving counters above
        from das_tpu.obs import proflog

        out["programs"] = proflog.snapshot()
        # dasdur durability (ISSUE 15, storage/durable.py): active
        # snapshot generation, WAL records appended/replayed, torn-tail
        # truncations and the last restore's wall seconds — the
        # replica-fleet cold-start story next to the serving counters
        from das_tpu.storage import durable

        out["durability"] = durable.snapshot_stats()
        return out

    def metrics_text(self) -> str:
        """Prometheus text exposition of the obs metric layer (ISSUE 12)
        plus the serving-path aggregate gauges out of coalescer_stats() —
        ONE scrape surface for counters, latency histograms
        (p50/p95/p99 via histogram_quantile) and the adaptive-window
        state.  Served over HTTP when env DAS_TPU_METRICS_PORT is set
        (serve() starts the exposition thread); also callable in-process
        by tests/benches."""
        stats = self.coalescer_stats()
        gauges = {
            f"serving.{k}": float(stats[k])
            for k in (
                "batches", "items", "inflight_peak", "effective_depth",
                "rtt_ewma_ms", "dispatch_ewma_ms",
                "speculative_dispatches", "early_settles",
                "queue_rejections", "deadline_expired",
                "breaker_rejections", "breaker_trips",
                "breaker_recoveries", "breaker_open_tenants",
                "cache_hits", "cache_misses",
                "cache_invalidations",
            )
        }
        # program-ledger gauges (ISSUE 14) — the prof.compile_ms
        # histogram rides the declared HISTOGRAMS surface automatically;
        # these are the scalar compile/cold-start/hit-rate aggregates
        progs = stats.get("programs") or {}
        for k in ("compiles", "compile_s", "cold_start_s",
                  "persistent_cache_hits", "ledger_hits"):
            gauges[f"programs.{k}"] = float(progs.get(k) or 0)
        if progs.get("hit_rate") is not None:
            gauges["programs.hit_rate"] = float(progs["hit_rate"])
        # durability gauges (ISSUE 15): generation / wal_records /
        # recovery_replayed / last restore seconds
        dur = stats.get("durability") or {}
        for k in ("generation", "snapshots", "wal_records",
                  "recovery_replayed", "torn_tail_truncations",
                  "corrupt_generations"):
            gauges[f"durability.{k}"] = float(dur.get(k) or 0)
        if dur.get("last_restore_s") is not None:
            gauges["durability.last_restore_s"] = float(
                dur["last_restore_s"]
            )
        return obs.prometheus_text(extra_gauges=gauges)

    # -- helpers -----------------------------------------------------------

    def _new_tenant(self, name: str):
        with self.registry_lock:
            if any(t.name == name for t in self.tenants.values()):
                return None, protocol.status(False, f"DAS named '{name}' already exists")
            token = self._fresh_token()
            kwargs = {"database_name": name}
            if self.backend:
                kwargs["backend"] = self.backend
            self.tenants[token] = _Tenant(name, DistributedAtomSpace(**kwargs))
            return token, None

    def _tenant_ready(self, key: str):
        tenant = self.tenants.get(key)
        if tenant is None:
            return None, protocol.status(False, "Invalid DAS key")
        if tenant.status == AtomSpaceStatus.LOADING:
            return None, protocol.status(False, f"DAS {key} is busy")
        return tenant, None

    @staticmethod
    def _map_failure(exc: Exception):
        """Typed retryable statuses (ISSUE 13): saturation, deadline
        expiry, and breaker rejections each map to a DISTINCT
        machine-parsable status with a retry-after hint
        (protocol.retryable_status) — clients back off and retry
        instead of treating a transient rejection as a hard failure.
        Everything else keeps the generic traceback status."""
        if isinstance(exc, CoalescerSaturatedError):
            return protocol.retryable_status("saturated", 50, str(exc))
        if isinstance(exc, DasDeadlineError):
            # the hint says when capacity may RETURN, which the expired
            # deadline's duration says nothing about — a momentary
            # backlog clears in milliseconds; use the same short beat
            # as saturation rather than parking clients for a full
            # deadline
            return protocol.retryable_status("deadline", 50, str(exc))
        if isinstance(exc, BreakerOpenError):
            hint = getattr(exc, "retry_after_ms", None)
            return protocol.retryable_status(
                "breaker_open", 250 if hint is None else hint, str(exc)
            )
        lines = traceback.format_exc().splitlines()
        return protocol.status(False, f"{exc} {lines}")

    def _call(self, key: str, method: str, args: list):
        tenant, err = self._tenant_ready(key)
        if err:
            return err
        try:
            with tenant.lock:
                answer = getattr(tenant.das, method)(*args)
        except Exception as exc:  # noqa: BLE001 — RPC surface, never raise
            return self._map_failure(exc)
        return protocol.status(True, answer)

    @staticmethod
    def _format(request) -> QueryOutputFormat:
        return _OUTPUT_FORMATS.get(
            request.get("output_format", "HANDLE"), QueryOutputFormat.HANDLE
        )

    # -- the 10 RPCs -------------------------------------------------------

    def create(self, request):
        token, err = self._new_tenant(request.get("name", ""))
        return err if err else protocol.status(True, token)

    def reconnect(self, request):
        # same semantics as create for a stateless-storage deployment: a
        # fresh token bound to the named space (reference server.py:152-164)
        token, err = self._new_tenant(request.get("name", ""))
        return err if err else protocol.status(True, token)

    def load_knowledge_base(self, request):
        key = request.get("key", "")
        # atomic check-then-set: two concurrent loads on one key must not
        # both pass the LOADING guard
        with self.registry_lock:
            tenant, err = self._tenant_ready(key)
            if err:
                return err
            tenant.status = AtomSpaceStatus.LOADING
        _KnowledgeBaseLoader(tenant, request.get("url", "")).start()
        return protocol.status(True, AtomSpaceStatus.LOADING.value)

    def check_das_status(self, request):
        tenant = self.tenants.get(request.get("key", ""))
        if tenant is None:
            return protocol.status(False, "Invalid DAS key")
        msg = tenant.status.value
        if tenant.status_detail:
            msg = f"{msg}: {tenant.status_detail}"
        return protocol.status(True, msg)

    def clear(self, request):
        return self._call(request.get("key", ""), "clear_database", [])

    def count(self, request):
        return self._call(request.get("key", ""), "count_atoms", [])

    def get_atom(self, request):
        return self._call(
            request.get("key", ""),
            "get_atom",
            [request.get("handle", ""), self._format(request)],
        )

    def search_nodes(self, request):
        return self._call(
            request.get("key", ""),
            "get_nodes",
            [
                request.get("node_type") or None,
                request.get("node_name") or None,
                self._format(request),
            ],
        )

    def search_links(self, request):
        return self._call(
            request.get("key", ""),
            "get_links",
            [
                request.get("link_type") or None,
                request.get("target_types") or None,
                request.get("targets") or None,
                self._format(request),
            ],
        )

    def query(self, request):
        # the trace id of a query is born here, on the gRPC thread: the
        # mark rides into coalescer.submit, so wire.query / wire.parse
        # share it with serve.submit ... serve.answer.  None (and the
        # shared no-op span) when tracing is off
        mark = obs.mark()
        with obs.span("wire.query", trace=mark[0] if mark else 0):
            return self._query(request, mark)

    def _query(self, request, mark):
        with obs.span("wire.parse", trace=mark[0] if mark else 0):
            query = parse_query(request.get("query", ""))
        if query is None:
            return protocol.status(False, "Invalid query")
        if self.coalesce_enabled:
            tenant, err = self._tenant_ready(request.get("key", ""))
            if err:
                return err
            coalescer = tenant.get_coalescer()
            future = coalescer.submit(
                tenant, query, self._format(request), mark
            )
            # BOUNDED wait (ISSUE 13): the worker resolves every future
            # (deadline expiry included), so the timeout is a backstop —
            # with a deadline configured it tracks it with slack, and
            # even with deadlines off no RPC thread blocks forever
            deadline_ms = coalescer.deadline_ms
            timeout = (
                deadline_ms / 1e3 * 2 + 30.0
                if deadline_ms > 0 else _RPC_WAIT_BACKSTOP_S
            )
            try:
                return protocol.status(True, future.result(timeout=timeout))
            except FuturesTimeoutError:
                future.cancel()
                return self._map_failure(
                    DasDeadlineError(
                        "coalesced query timed out at the RPC wait "
                        "backstop", deadline_ms=deadline_ms,
                    )
                )
            except Exception as exc:  # noqa: BLE001 — RPC surface
                return self._map_failure(exc)
        return self._call(
            request.get("key", ""), "query", [query, self._format(request)]
        )

    # -- test/bench plumbing ----------------------------------------------

    def attach_tenant(self, name: str, das) -> str:
        """Register an already-constructed DistributedAtomSpace as a tenant
        (tests and benches attach a pre-built store instead of re-loading
        through the create+load RPCs).  Same registry rules as create."""
        with self.registry_lock:
            if any(t.name == name for t in self.tenants.values()):
                raise ValueError(f"DAS named '{name}' already exists")
            token = self._fresh_token()
            self.tenants[token] = _Tenant(name, das)
            return token

    def _fresh_token(self) -> str:
        """Caller holds registry_lock."""
        while True:
            token = _random_token()
            if token not in self.tenants:
                return token


def _message_to_dict(msg) -> dict:
    """Protobuf request message -> the plain request dict the RPC
    implementations consume (repeated fields become lists)."""
    out = {}
    for f in msg.DESCRIPTOR.fields:
        value = getattr(msg, f.name)
        # feature-detect: modern protobuf deprecates .label in favor of
        # .is_repeated; older runtimes have only .label
        repeated = (
            f.is_repeated
            if hasattr(f, "is_repeated")
            else f.label == f.LABEL_REPEATED
        )
        out[f.name] = list(value) if repeated else value
    return out


def _make_servicer(service: DasService):
    """Protobuf wire contract — byte-compatible with the reference's
    generated service (service_spec/das.proto:49-60), so an unmodified
    reference service/client.py can drive this server.  One
    ServiceDefinitionServicer subclass whose methods adapt protobuf
    messages to the dict-based RPC implementations."""
    from das_tpu.service.service_spec import das_pb2, das_pb2_grpc

    def adapt(method):
        def call(request, context):
            d = method(_message_to_dict(request))
            return das_pb2.Status(success=d["success"], msg=d["msg"])

        return staticmethod(call)

    methods = {
        rpc: adapt(getattr(service, rpc))
        for rpc in das_pb2_grpc.RPC_REQUEST_TYPES
    }
    servicer_cls = type(
        "DasServicer", (das_pb2_grpc.ServiceDefinitionServicer,), methods
    )
    return servicer_cls()


def start_metrics_http(service: DasService, port: int):
    """Prometheus text-exposition endpoint (`GET /metrics`) on a daemon
    thread — stdlib http.server, no new dependency.  Returns the bound
    HTTPServer (`.server_port` for port-0 tests)."""
    from http.server import BaseHTTPRequestHandler, HTTPServer

    class _Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
            if self.path.rstrip("/") not in ("", "/metrics"):
                self.send_error(404)
                return
            body = service.metrics_text().encode()
            self.send_response(200)
            self.send_header(
                "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
            )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # scrapes must not spam stderr
            pass

    httpd = HTTPServer(("0.0.0.0", port), _Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    logger().info(f"metrics exposition on port {httpd.server_port}")
    return httpd


def serve(
    port: int = protocol.DEFAULT_PORT,
    backend: Optional[str] = None,
    max_workers: int = 10,
    block: bool = True,
):
    """Start the service; returns (grpc_server, DasService)."""
    import os

    service = DasService(backend=backend)
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=max_workers))
    from das_tpu.service.service_spec import das_pb2_grpc

    das_pb2_grpc.add_ServiceDefinitionServicer_to_server(
        _make_servicer(service), server
    )
    bound = server.add_insecure_port(f"[::]:{port}")
    server.bound_port = bound  # ephemeral-port tests read this back
    # Prometheus exposition (ISSUE 12): env DAS_TPU_METRICS_PORT opens
    # GET /metrics with the obs metric layer + serving gauges; unset/0
    # keeps the old surface exactly
    metrics_port = os.environ.get("DAS_TPU_METRICS_PORT")
    if metrics_port and int(metrics_port) > 0:
        # asking for exposition IS asking for the metric layer: every
        # .inc()/.observe() site is behind obs.enabled(), so a scrape
        # endpoint over a disabled recorder would serve permanently-zero
        # counters — the silent-dashboard failure DL014 exists to
        # prevent.  DAS_TPU_TRACE=0 alongside the port still wins
        # (explicit off beats implied on).
        if not obs.enabled() and os.environ.get("DAS_TPU_TRACE") is None:
            obs.configure(enabled=True)
        server.metrics_http = start_metrics_http(service, int(metrics_port))
    # jax.profiler device trace (obs/jaxprof.py): starts only when a
    # DasConfig.profiler_trace_dir (env DAS_TPU_TRACE_DIR) is configured
    from das_tpu.core.config import DasConfig

    obs.maybe_start_trace(DasConfig.from_env())
    server.start()
    logger().info(f"DAS service listening on port {bound}")
    if block:
        server.wait_for_termination()
    return server, service


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="DAS TPU gRPC service")
    ap.add_argument("--port", type=int, default=protocol.DEFAULT_PORT)
    ap.add_argument("--backend", default=None, help="memory | tensor | sharded")
    args = ap.parse_args()
    serve(port=args.port, backend=args.backend)
