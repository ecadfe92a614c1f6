"""dasprof — the program ledger (ISSUE 14 tentpole).

The engine compiles
whole-plan programs and records nothing about what XLA actually did — compile wall time, FLOPs,
bytes accessed, HBM footprint are all dark.  This module closes the
device side of the observability story (dastrace, ARCHITECTURE §13,
closed the host side): a bounded per-signature **program ledger** that
records, for every instrumented jitted entry point, the first-compile
wall time plus the AOT `jax.jit(...).lower(...).compile()` statistics
where the backend provides them — `cost_analysis()` flops /
bytes-accessed and `memory_analysis()` argument / output / temp / peak
bytes — keyed by the plan-signature digest the executor caches already
use.

How instrumentation works: the program builders (`build_fused`,
`build_fused_tree`, `build_fused_exact`, the count-batch site, and the
sharded twins) pass their freshly-jitted callable through
`instrument(site, digest, fn)`.  Disabled (`DAS_TPU_PROFLOG` unset — the
default), `instrument` returns `fn` ITSELF: the serving path is
byte-for-byte the pre-ledger path (tests/test_zprof.py pins the
identity), no wrapper objects, no per-call overhead — the dastrace
no-allocation idiom.  Enabled, the returned `_InstrumentedProgram`
AOT-compiles on first call per argument-shape signature (`lower()` +
`compile()` — the SAME executable `jax.jit` would build, so answers are
bit-identical), records the ledger entry, and serves subsequent calls
from the compiled object (a "ledger hit").  Any AOT failure — an
exotic argument tree, a backend without AOT support — falls back to the
plain jitted path and records the error string instead of raising:
the ledger can cost accuracy, never answers.  Calls that arrive with
TRACER arguments (a program re-entered inside another program's trace;
a `jax.eval_shape` probe) delegate straight to
the jitted fn — a program nested inside another program is priced by
its parent's ledger entry.

Cold-start accounting: a jax monitoring listener classifies each
compile as fresh or served by the persistent XLA cache
(`das_tpu.enable_compile_cache`); `snapshot()["cold_start_s"]` sums the
wall time of the FRESH compiles only — the time-to-first-answer compile
cost a warm replica (ROADMAP replica-fleet item) would not pay.

`PROGRAM_SITES` below is the closed registry of every scope in das_tpu/
that constructs a device program (`jax.jit`), mapping
each to its ledger site label or None for declared-exempt scopes.
daslint rule DL016 pins it both ways against the actual program
construction sites — a new jit call in an undeclared scope fails
lint, an instrumented scope without its ledger hook fails lint, and a
stale entry fails full runs (the DL013 FETCH_SITES idiom).

Thread/lock discipline (daslint DL006): ledger mutation is serialized
on `_lock` (compiles are seconds-scale; the lock is noise), and the
per-compile persistent-cache event counters live in a THREAD-LOCAL so
concurrent tenant compiles cannot attribute each other's cache hits.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from das_tpu.obs.recorder import TRUTHY

#: daslint DL006 — post-__init__ ledger state owners.  Everything is
#: serialized on `_lock`; `enabled` flips only via configure() (tests,
#: scripts/dump_trace.py).
LOCK_DISCIPLINE = {
    "ProgramLedger.enabled": "_lock",
    "ProgramLedger.capacity": "_lock",
    "ProgramLedger.entries": "_lock",
    "ProgramLedger.compiles": "_lock",
    "ProgramLedger.compile_s": "_lock",
    "ProgramLedger.cold_start_s": "_lock",
    "ProgramLedger.persistent_cache_hits": "_lock",
    "ProgramLedger.calls": "_lock",
    "ProgramLedger.hits": "_lock",
    "ProgramLedger.errors": "_lock",
    "ProgramLedger._listener_on": "_lock",
    "_InstrumentedProgram._compiled": "_lock",
}

WORKER_METHODS: Dict[str, Tuple[str, ...]] = {}

#: THE closed registry of program-construction scopes (daslint DL016,
#: the DL013 FETCH_SITES idiom): every scope in das_tpu/ whose AST
#: references `jax.jit`, attributed to its
#: OUTERMOST enclosing function ("module.func" / "module.Class.meth").
#: Value = the ledger site label the scope must pass to
#: `instrument(...)`, or None for declared-exempt scopes — per-op
#: staged programs already counted by DISPATCH_COUNTS, or cold index/
#: bootstrap programs outside the serving path.  An entry here is a
#: reviewed decision; a jit call in an UNdeclared scope fails lint.
PROGRAM_SITES: Dict[str, Optional[str]] = {
    # -- instrumented: the whole-plan program builders -------------------
    "fused.build_fused": "fused",
    "fused.build_fused_group": "fused_group",
    "fused.build_fused_tree": "fused_tree",
    "fused.build_fused_exact": "fused_exact",
    "fused.FusedExecutor._run_batch_group": "count_batch",
    "fused_sharded._ShardedExecJob.dispatch": "sharded",
    "fused_sharded._ShardedExecJob._build_group": "sharded_group",
    "fused_sharded._ShardedTreeExecJob._build": "sharded_tree",
    # -- declared-exempt: staged-path per-op programs (ops/posting.py,
    #    ops/join.py — one generic op each, counted by DISPATCH_COUNTS
    #    "lowered"; the staged pipeline is the retry/fallback tier, not
    #    the serving hot path) -------------------------------------------
    "posting._range_probe_jit": None,
    "posting._full_scan_jit": None,
    "posting._verify_positions_jit": None,
    "posting.verify_multiset": None,
    "posting.dedup_sorted": None,
    "posting.count_valid": None,
    "join._join_tables_jit": None,
    "join._anti_join_jit": None,
    "join._build_term_table_jit": None,
    "join._dedup_table_jit": None,
    # -- declared-exempt: the mesh's per-op table programs (the tree
    #    evaluator's op layer and the staged route: one jitted shard_map
    #    per table operation, each cached by its statics) ----------------
    "mesh.table_program": None,
    # -- declared-exempt: star-count degree fold programs (count-only
    #    fast path, host-side fold by default — query/starcount.py) -----
    "starcount._deg_vector": None,
    "starcount._scatter_deg": None,
    "starcount._gather_col": None,
    "starcount._star_fold": None,
    # -- declared-exempt: store build/commit programs (ingest/commit
    #    time, outside query serving) -----------------------------------
    "tensor_db._merge_padded": None,
    "tensor_db._insert_rows": None,
    "sharded_db.ShardedTables.stage_delta": None,
}

#: ledger entry bound: past it the OLDEST entries drop (the recorder's
#: ring idiom — a long-running service keeps the recent window)
_MAX_ENTRIES = 1024


def _env_enabled() -> bool:
    return os.environ.get("DAS_TPU_PROFLOG", "0").lower() in TRUTHY


def sig_digest(*parts) -> str:
    """Stable digest of a plan signature (plus variant discriminators
    like count_only) — the executor-cache keys are frozen dataclasses
    with deterministic reprs, so this is the same identity the compiled
    -program caches already key on, folded to 16 hex chars."""
    return hashlib.md5(repr(parts).encode()).hexdigest()[:16]


class ProgramLedger:
    """Bounded map of (site, digest) -> per-program compile/cost/memory
    record, plus the aggregate counters coalescer_stats()["programs"]
    surfaces."""

    def __init__(self, enabled: Optional[bool] = None):
        self.enabled = _env_enabled() if enabled is None else bool(enabled)
        self.capacity = _MAX_ENTRIES
        self.entries: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self.compiles = 0
        self.compile_s = 0.0
        self.cold_start_s = 0.0
        self.persistent_cache_hits = 0
        self.calls = 0
        self.hits = 0
        self.errors = 0
        # reentrant: record_* hold it while _entry takes it again (the
        # lexical with-block is what DL006 pins)
        self._lock = threading.RLock()
        self._tls = threading.local()
        self._listener_on = False

    # -- configuration ---------------------------------------------------

    def configure(self, enabled: Optional[bool] = None) -> None:
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)

    def reset(self) -> None:
        with self._lock:
            self.entries = {}
            self.compiles = 0
            self.compile_s = 0.0
            self.cold_start_s = 0.0
            self.persistent_cache_hits = 0
            self.calls = 0
            self.hits = 0
            self.errors = 0

    # -- persistent-XLA-cache hit classification -------------------------

    def _ensure_listener(self) -> None:
        """Register ONE process-wide jax monitoring listener that feeds
        the calling thread's compile-window counters.  Private-API
        guarded: if the monitoring module moves, every compile simply
        classifies as fresh (cold_start_s upper-bounds, never lies
        low)."""
        if self._listener_on:
            return
        try:
            from jax._src import monitoring

            def _on_event(name: str, **_kw) -> None:
                win = getattr(self._tls, "cache_window", None)
                if win is None:
                    return
                if name == "/jax/compilation_cache/cache_hits":
                    win["hits"] += 1
                elif name == "/jax/compilation_cache/cache_misses":
                    win["misses"] += 1

            monitoring.register_event_listener(_on_event)
        except Exception:
            pass
        with self._lock:
            self._listener_on = True

    def _open_cache_window(self) -> None:
        self._ensure_listener()
        self._tls.cache_window = {"hits": 0, "misses": 0}

    def _close_cache_window(self) -> bool:
        """True = this compile was served by the persistent XLA cache:
        more cache-hit than cache-miss events in the window.  Majority
        vote, not all-hits — one executable triggers several
        sub-compiles (convert_element_type and friends) and a single
        cold helper must not reclassify a warm main program."""
        win = getattr(self._tls, "cache_window", None)
        self._tls.cache_window = None
        return bool(win and win["hits"] > win["misses"])

    # -- recording --------------------------------------------------------

    def _entry(self, site: str, digest: str) -> Dict[str, Any]:
        with self._lock:
            key = (site, digest)
            e = self.entries.get(key)
            if e is not None:
                return e
            if len(self.entries) >= self.capacity:
                # drop oldest (insertion order) — recorder ring idiom
                self.entries.pop(next(iter(self.entries)))
            e = {
                "site": site,
                "digest": digest,
                "compiles": 0,
                "compile_s": 0.0,
                "first_compile_s": None,
                "persistent_cache_hit": False,
                "flops": None,
                "bytes_accessed": None,
                "arg_bytes": None,
                "out_bytes": None,
                "temp_bytes": None,
                "peak_bytes": None,
                "calls": 0,
                "hits": 0,
                "error": None,
            }
            self.entries[key] = e
            return e

    def record_compile(
        self, site: str, digest: str, wall_s: float,
        cost: Optional[Dict[str, float]],
        mem: Optional[Any],
        persistent_hit: bool,
    ) -> None:
        with self._lock:
            e = self._entry(site, digest)
            e["compiles"] += 1
            e["compile_s"] += wall_s
            if e["first_compile_s"] is None:
                e["first_compile_s"] = wall_s
            e["persistent_cache_hit"] = persistent_hit
            if cost:
                e["flops"] = cost.get("flops")
                e["bytes_accessed"] = cost.get("bytes accessed")
            if mem is not None:
                arg = getattr(mem, "argument_size_in_bytes", None)
                out = getattr(mem, "output_size_in_bytes", None)
                tmp = getattr(mem, "temp_size_in_bytes", None)
                ali = getattr(mem, "alias_size_in_bytes", 0) or 0
                e["arg_bytes"] = arg
                e["out_bytes"] = out
                e["temp_bytes"] = tmp
                if out is not None and tmp is not None:
                    # peak live-at-once estimate: outputs + temporaries
                    # (+ aliased) — arguments are the caller's resident
                    # store, not this program's allocation
                    e["peak_bytes"] = out + tmp + ali
            self.compiles += 1
            self.compile_s += wall_s
            if persistent_hit:
                self.persistent_cache_hits += 1
            else:
                self.cold_start_s += wall_s
        from das_tpu import obs

        obs.counter("prof.compiles").inc()
        obs.histogram("prof.compile_ms").observe(wall_s * 1e3)
        # the compile lane (scripts/dump_trace.py): when dastrace is on
        # too, each compile lands as a span in a dedicated "compile"
        # Perfetto lane, duration = the wall time recorded above
        obs.REC.record(
            "prof.compile", "X", time.perf_counter() - wall_s, wall_s, 0,
            {"site": site, "digest": digest,
             "persistent_cache_hit": persistent_hit},
            lane="compile",
        )

    def record_error(self, site: str, digest: str, err: BaseException) -> None:
        with self._lock:
            e = self._entry(site, digest)
            e["error"] = repr(err)[:200]
            self.errors += 1

    def record_call(self, site: str, digest: str, hit: bool) -> None:
        with self._lock:
            e = self._entry(site, digest)
            e["calls"] += 1
            self.calls += 1
            if hit:
                e["hits"] += 1
                self.hits += 1

    # -- readout ----------------------------------------------------------

    def rows(
        self, site: Optional[str] = None, digest: Optional[str] = None
    ) -> List[Dict[str, Any]]:
        with self._lock:
            out = []
            for e in self.entries.values():
                if site is not None and e["site"] != site:
                    continue
                if digest is not None and e["digest"] != digest:
                    continue
                out.append(dict(e))
            return out

    def snapshot(self) -> Dict[str, Any]:
        """The coalescer_stats()["programs"] surface: compiles, total
        compile seconds, ledger hit rate and the cold-start
        decomposition."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "compiles": self.compiles,
                "compile_s": round(self.compile_s, 4),
                "calls": self.calls,
                "ledger_hits": self.hits,
                "hit_rate": round(self.hits / self.calls, 4)
                if self.calls else None,
                "cold_start_s": round(self.cold_start_s, 4),
                "persistent_cache_hits": self.persistent_cache_hits,
                "errors": self.errors,
                "entries": len(self.entries),
            }


#: THE process ledger — env-initialized, reconfigurable (tests, scripts/dump_trace.py)
LEDGER = ProgramLedger()


def enabled() -> bool:
    return LEDGER.enabled


def configure(enabled: Optional[bool] = None) -> None:
    LEDGER.configure(enabled=enabled)


def reset() -> None:
    LEDGER.reset()


def snapshot() -> Dict[str, Any]:
    return LEDGER.snapshot()


def rows(site: Optional[str] = None,
         digest: Optional[str] = None) -> List[Dict[str, Any]]:
    return LEDGER.rows(site=site, digest=digest)


class _InstrumentedProgram:
    """One instrumented jitted program: AOT-compiles per argument-shape
    signature, records the ledger entry, serves repeat calls from the
    compiled executable.  Never raises on ledger business: every
    failure path delegates to the plain jitted fn."""

    __slots__ = ("site", "digest", "fn", "_compiled", "_lock")

    def __init__(self, site: str, digest: str, fn):
        self.site = site
        self.digest = digest
        self.fn = fn
        self._compiled: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()

    def _shape_key(self, leaves) -> Optional[Tuple]:
        """Abstract signature of the call's argument leaves, or None
        when any leaf is a tracer (we are INSIDE someone else's trace —
        the nested program is priced by its parent's entry)."""
        import jax

        key = []
        for leaf in leaves:
            if isinstance(leaf, jax.core.Tracer):
                return None
            shape = getattr(leaf, "shape", None)
            if shape is not None:
                key.append((tuple(shape), str(getattr(leaf, "dtype", ""))))
            else:
                key.append(("py", type(leaf).__name__))
        return tuple(key)

    def _aot_compile(self, key: Tuple, args: Tuple):
        """lower().compile() with the ledger bookkeeping; None on any
        failure (the caller falls back to the jitted path)."""
        led = LEDGER
        led._open_cache_window()
        t0 = time.perf_counter()
        try:
            compiled = self.fn.lower(*args).compile()
        except Exception as err:
            led._close_cache_window()
            led.record_error(self.site, self.digest, err)
            return None
        wall = time.perf_counter() - t0
        persistent_hit = led._close_cache_window()
        cost: Optional[Dict[str, float]] = None
        mem = None
        try:
            ca = compiled.cost_analysis()
            cost = ca[0] if isinstance(ca, (list, tuple)) else ca
        except Exception:
            pass
        try:
            mem = compiled.memory_analysis()
        except Exception:
            pass
        led.record_compile(
            self.site, self.digest, wall, cost, mem, persistent_hit
        )
        return compiled

    def __call__(self, *args):
        led = LEDGER
        if not led.enabled:
            return self.fn(*args)
        import jax

        leaves = jax.tree_util.tree_leaves(args)
        key = self._shape_key(leaves)
        if key is None:
            return self.fn(*args)
        compiled = self._compiled.get(key)
        hit = compiled is not None
        if compiled is None:
            # the compile itself runs under the wrapper lock: two
            # tenants racing the same uncached shape must not each pay
            # a seconds-scale duplicate AOT compile (and double-count
            # the ledger) — the loser of the race re-checks and hits
            with self._lock:
                compiled = self._compiled.get(key)
                hit = compiled is not None
                if compiled is None:
                    compiled = self._aot_compile(key, args)
                    if compiled is not None:
                        self._compiled[key] = compiled
            if compiled is None:
                return self.fn(*args)
        led.record_call(self.site, self.digest, hit=hit)
        try:
            return compiled(*args)
        except Exception:
            # an AOT-compiled executable is stricter about argument
            # placement than jit; never let that strictness cost an
            # answer — drop to the jitted path and stop using the entry
            with self._lock:
                self._compiled.pop(key, None)
            return self.fn(*args)


def instrument(site: str, digest: str, fn):
    """Route one freshly-jitted program through the ledger.

    DISABLED (the default): returns `fn` unchanged — `instrument(s, d,
    fn) is fn` is the identity contract tests/test_zprof.py pins; the
    serving path allocates nothing and dispatch halves stay exactly the
    pre-ledger code (DL001/DL010).  Enabled: returns the AOT-compiling
    wrapper.  `site` must be a PROGRAM_SITES label (daslint DL016 pins
    the literal at the call site)."""
    if not LEDGER.enabled:
        return fn
    return _InstrumentedProgram(site, digest, fn)
