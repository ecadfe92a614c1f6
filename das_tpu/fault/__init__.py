"""dasfault — deterministic, seeded fault injection plus the recovery
machinery it exercises (ISSUE 13 tentpole).

The serving stack's failure paths were ad hoc: RPC threads blocked on
futures with no timeout, the settle-fetch transport retry was a
hard-coded retry-once, and nothing proved a mid-commit crash leaves
`delta_version` and the store consistent.  This module is the substrate
that makes those paths *testable* and *bounded*:

  * **Injection** — `maybe_fail(site)` at each declared `FAULT_SITES`
    seam, driven by a seeded schedule from the `DAS_TPU_FAULT` spec
    string.  Injection raises a typed `InjectedFault` (or sleeps, in
    latency mode) — never silent corruption.  Default off with a
    no-allocation fast path: one module-global read and a None check
    (the obs NOOP_SPAN idiom; tests pin `_PLAN is None` identity).
  * **RetryPolicy** — ONE shared retry/backoff implementation (max
    attempts, exponential backoff, deterministic jitter, per-class
    retryability) replacing the scattered retry-once sites; covers
    settle fetches (query/fused.py) and commit applies
    (storage/delta.py).
  * **CircuitBreaker** — the per-tenant degraded-mode state machine the
    coalescer (service/coalesce.py) drives: repeated retryable settle
    failures or sustained saturation trip it OPEN (speculation off,
    window at floor, cache-hit answers still served, fresh dispatches
    rejected retryable); after a cooldown a HALF_OPEN probe restores it.

The chaos-parity contract this buys (tests/test_zfault.py): under ANY
injected schedule, every query returns either bit-identical answers to
the fault-free run or a typed `DasError` subclass — never a wrong
answer, never a stranded future, never a dead worker — and the store
stays consistent (storage/delta.py stage-then-swap).

daslint rule DL015 pins `FAULT_SITES` both ways (an undeclared
`maybe_fail` site fires; a stale entry fails full runs) and bans
injection calls from the dispatch halves — the async code paths must
stay exactly as reviewed (DL001/DL010).

Spec string (`DAS_TPU_FAULT`, or `fault.configure(spec)`):
semicolon-separated `key=value` pairs —

    seed=7;sites=settle_fetch,commit_apply;rate=0.25;max=4
    seed=1;sites=*;every=3;max=2;mode=latency;latency_ms=5

  seed        deterministic schedule seed (default 0)
  sites       comma list of FAULT_SITES members, or `*` (required)
  rate        per-call failure probability, decided by a seeded hash
              of (seed, site, call index) — same spec, same schedule
  every       fire on every Nth call of a site (overrides rate)
  max         per-site cap on injected failures (default 4) — bounds
              every schedule so the system eventually heals; note a
              cap at or above RetryPolicy's attempts (3) can still
              fail one operation typed before the site goes quiet
  mode        error (raise InjectedFault, default) | latency (sleep)
  latency_ms  sleep duration for latency mode (default 1.0)
"""

from __future__ import annotations

import os
import threading
import time
import zlib
from typing import Callable, Dict, Optional, Tuple

from das_tpu.core.exceptions import DasError, InjectedFault

#: the CLOSED set of host-side seams `maybe_fail` may guard (daslint
#: DL015, the COLLECTIVE_SITES/FETCH_SITES idiom applied to fault
#: injection).  Every entry names a recovery path the chaos suite
#: exercises; adding a seam means adding it here, under review, with
#: its degradation story.  Injection is banned from the dispatch
#: halves — those stay bit-identical to the reviewed fault-free code
#: (DL001/DL010).
FAULT_SITES = (
    #: coalescer submit path (service/coalesce.py submit) — the caller
    #: sees the typed error on its future, like any per-query failure
    "submit_queue",
    #: top of the coalescer worker loop (service/coalesce.py _run) —
    #: proves the worker survives anything its iteration raises
    "worker_iteration",
    #: host-side group enqueue seam (service/coalesce.py
    #: _dispatch_group, OUTSIDE the DL001 dispatch halves) — the group
    #: degrades to per-query settle fallbacks
    "dispatch_enqueue",
    #: the settle round's host transfer (query/fused.py
    #: settle_pending_iter / _run_batch_group) — RetryPolicy's beat
    "settle_fetch",
    #: delta-versioned result-cache insert (query/fused.py
    #: ResultCache.put) — a cache failure degrades to "not cached",
    #: never to a failed query
    "cache_insert",
    #: incremental-commit apply, after staging and before the swap
    #: (storage/delta.py _apply_delta) — the mid-commit crash point the
    #: stage-then-swap ordering makes atomic
    "commit_apply",
    #: -- dasdur persistence seams (ISSUE 15, storage/durable.py): the
    #: chaos-parity contract extends to durability — inject a crash at
    #: any of these, recover via restore(), and query answers are
    #: bit-identical (tests/test_zdur.py crash-point matrix) --
    #: start of one atomic section write, before any byte lands
    #: (durable.atomic_write) — the prior file/generation survives
    "snapshot_write",
    #: between a section's fsync and its rename into place, and before
    #: the generation directory's final rename (durable.atomic_write /
    #: write_snapshot) — the torn-rename crash the dot-temp layout makes
    #: invisible to restore
    "snapshot_rename",
    #: start of one WAL record append, before framing (durable.DeltaLog
    #: .append) — the commit fails pre-swap, store stays consistent
    "wal_append",
    #: after the WAL record's write and before its fsync — the record
    #: may or may not be durable; a retried commit's twin record dedups
    #: by delta_version at replay
    "wal_fsync",
    #: restore-path section/WAL reads (durable._verified_bytes /
    #: read_wal) — a transient read flake retries on the shared
    #: RetryPolicy; real corruption stays a typed SnapshotCorruptError
    "restore_read",
)

#: per-site injected-failure tally (the FETCH_COUNTS idiom: plain +=
#: under the GIL, torn reads tolerated) — bench/tests read it to assert
#: a schedule actually fired
INJECT_COUNTS: Dict[str, int] = {site: 0 for site in FAULT_SITES}


class FaultSpecError(DasError):
    """Malformed `DAS_TPU_FAULT` spec string."""


def _hash_unit(seed: int, site: str, n: int) -> float:
    """Deterministic uniform in [0, 1) from (seed, site, call index) —
    the schedule is a pure function of the spec, never of RNG state."""
    h = zlib.crc32(f"{seed}:{site}:{n}".encode()) & 0xFFFFFFFF
    return h / 2.0**32


class _FaultPlan:
    """One parsed, armed injection schedule.  All counters live behind
    one lock — injection is a cold path by construction (the disabled
    fast path never reaches here)."""

    __slots__ = (
        "spec", "seed", "sites", "rate", "every", "max_failures",
        "mode", "latency_ms", "_calls", "_fails", "_lock",
    )

    def __init__(self, spec: str, seed: int, sites: Tuple[str, ...],
                 rate: float, every: int, max_failures: int,
                 mode: str, latency_ms: float):
        self.spec = spec
        self.seed = seed
        self.sites = frozenset(sites)
        self.rate = rate
        self.every = every
        self.max_failures = max_failures
        self.mode = mode
        self.latency_ms = latency_ms
        self._calls: Dict[str, int] = {}
        self._fails: Dict[str, int] = {}
        self._lock = threading.Lock()

    def _fires(self, site: str, n: int) -> bool:
        if self.every > 0:
            return (n + 1) % self.every == 0
        return _hash_unit(self.seed, site, n) < self.rate

    def check(self, site: str) -> None:
        with self._lock:
            n = self._calls.get(site, 0)
            self._calls[site] = n + 1
            if site not in self.sites:
                return
            if self._fails.get(site, 0) >= self.max_failures:
                return
            if not self._fires(site, n):
                return
            self._fails[site] = self._fails.get(site, 0) + 1
        INJECT_COUNTS[site] += 1
        from das_tpu import obs

        if obs.enabled():
            obs.event("fault.inject", site=site, call=n, mode=self.mode)
            obs.counter("fault.injected").inc()
        if self.mode == "latency":
            time.sleep(self.latency_ms / 1e3)
            return
        raise InjectedFault(site, n)

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "spec": self.spec,
                "calls": dict(self._calls),
                "failures": dict(self._fails),
            }


def parse_spec(spec: Optional[str]) -> Optional[_FaultPlan]:
    """Parse a `DAS_TPU_FAULT` spec string; None/empty means off.
    Unknown keys and undeclared site names are hard errors — a typo'd
    chaos schedule that silently injects nothing is worse than none."""
    if not spec:
        return None
    fields = {
        "seed": "0", "sites": "", "rate": "0.5", "every": "0",
        "max": "4", "mode": "error", "latency_ms": "1.0",
    }
    for pair in spec.split(";"):
        pair = pair.strip()
        if not pair:
            continue
        if "=" not in pair:
            raise FaultSpecError(f"malformed DAS_TPU_FAULT pair {pair!r}")
        key, value = pair.split("=", 1)
        key = key.strip()
        if key not in fields:
            raise FaultSpecError(f"unknown DAS_TPU_FAULT key {key!r}")
        fields[key] = value.strip()
    raw_sites = fields["sites"]
    if not raw_sites:
        raise FaultSpecError("DAS_TPU_FAULT needs sites=<name,...> or sites=*")
    if raw_sites == "*":
        sites = FAULT_SITES
    else:
        sites = tuple(s.strip() for s in raw_sites.split(",") if s.strip())
        unknown = [s for s in sites if s not in FAULT_SITES]
        if unknown:
            raise FaultSpecError(
                f"undeclared fault site(s) {unknown} — FAULT_SITES "
                f"declares {list(FAULT_SITES)}"
            )
    mode = fields["mode"]
    if mode not in ("error", "latency"):
        raise FaultSpecError(f"unknown DAS_TPU_FAULT mode {mode!r}")
    return _FaultPlan(
        spec=spec,
        seed=int(fields["seed"]),
        sites=sites,
        rate=float(fields["rate"]),
        every=int(fields["every"]),
        max_failures=int(fields["max"]),
        mode=mode,
        latency_ms=float(fields["latency_ms"]),
    )


#: THE armed schedule — None is the disabled fast path (identity-pinned
#: by tests/test_zfault.py, the obs NOOP_SPAN idiom): `maybe_fail` on
#: the serve path then costs one global read + a None check, allocating
#: nothing
_PLAN: Optional[_FaultPlan] = parse_spec(os.environ.get("DAS_TPU_FAULT"))


def configure(spec: Optional[str]) -> None:
    """Arm (or with None/"" disarm) an injection schedule — the test /
    bench entry point; the env var covers deployments."""
    global _PLAN
    _PLAN = parse_spec(spec)


def enabled() -> bool:
    return _PLAN is not None


def plan() -> Optional[_FaultPlan]:
    """The armed schedule (None when off) — tests read its snapshot."""
    return _PLAN


def maybe_fail(site: str) -> None:
    """The injection seam: no-op unless a schedule is armed AND decides
    this call fires.  `site` must be a FAULT_SITES member (daslint
    DL015 pins the literals both ways)."""
    armed = _PLAN
    if armed is None:
        return
    armed.check(site)


# -- retry / backoff ---------------------------------------------------------


def is_retryable(exc: BaseException) -> bool:
    """Per-class retryability shared by every recovery site: injected
    faults (unless marked terminal), jax runtime failures (a device
    that is lost or reset surfaces as JaxRuntimeError), and plain
    OS-level connection errors.  Semantic errors — bad queries,
    capacity ceilings, deadline expiry — are NOT retryable here: each
    has its own, smarter recovery path."""
    if isinstance(exc, InjectedFault):
        return exc.retryable
    if isinstance(exc, ConnectionError):
        return True
    try:
        import jax

        if isinstance(exc, jax.errors.JaxRuntimeError):
            return True
    except Exception:  # noqa: BLE001 — no jax in a docs/lint venv
        pass
    return False


class RetryPolicy:
    """Bounded retry with exponential backoff and DETERMINISTIC jitter.

    One shared implementation for every transport-class recovery site
    (the settle fetch, the commit apply) — replacing the hard-coded
    retry-once idiom.  The jitter derives from (seed, attempt), never
    from RNG state, so a chaos run's timing is a pure function of its
    spec and the determinism test can pin the exact backoff sequence.
    """

    __slots__ = ("max_attempts", "base_ms", "multiplier", "max_backoff_ms",
                 "jitter_frac", "seed", "classify")

    def __init__(self, max_attempts: int = 3, base_ms: float = 1.0,
                 multiplier: float = 2.0, max_backoff_ms: float = 50.0,
                 jitter_frac: float = 0.25, seed: int = 0,
                 classify: Optional[Callable[[BaseException], bool]] = None):
        self.max_attempts = max(1, int(max_attempts))
        self.base_ms = float(base_ms)
        self.multiplier = float(multiplier)
        self.max_backoff_ms = float(max_backoff_ms)
        self.jitter_frac = float(jitter_frac)
        self.seed = int(seed)
        self.classify = classify or is_retryable

    def backoff_ms(self, attempt: int) -> float:
        """Delay before retry `attempt` (1-based): exponential from
        base_ms, capped, with deterministic jitter in
        [0, jitter_frac] of the raw delay."""
        raw = min(
            self.base_ms * self.multiplier ** (attempt - 1),
            self.max_backoff_ms,
        )
        return raw * (1.0 + self.jitter_frac
                      * _hash_unit(self.seed, "backoff", attempt))

    def run(self, fn: Callable, on_retry: Optional[Callable] = None):
        """Call `fn()` up to max_attempts times.  Retries only
        classify()-retryable failures, sleeping backoff_ms between
        attempts; the final failure re-raises typed and untouched.
        `on_retry(attempt, exc)` (optional) runs before each retry —
        call sites keep their own per-attempt accounting there (e.g.
        the FETCH_COUNTS tally stays at the fetch site, DL013)."""
        attempt = 0
        while True:
            try:
                return fn()
            except Exception as exc:  # noqa: BLE001 — classified below
                attempt += 1
                if attempt >= self.max_attempts or not self.classify(exc):
                    raise
                from das_tpu import obs

                if obs.enabled():
                    obs.counter("fault.retries").inc()
                if on_retry is not None:
                    on_retry(attempt, exc)
                delay = self.backoff_ms(attempt)
                if delay > 0:
                    time.sleep(delay / 1e3)


def fetch_retry() -> RetryPolicy:
    """The settle-fetch policy (replaces query/fused.py's retry-once):
    3 attempts, millisecond-scale backoff — a transient runtime
    failure costs one beat, a real outage surfaces typed after two
    retries."""
    return RetryPolicy(max_attempts=3, base_ms=1.0, max_backoff_ms=50.0)


def commit_retry() -> RetryPolicy:
    """The commit-apply policy: stage-then-swap (storage/delta.py) makes
    a failed apply side-effect-free, so a transient failure retries the
    whole staged commit safely."""
    return RetryPolicy(max_attempts=3, base_ms=1.0, max_backoff_ms=50.0)


# -- circuit breaker ---------------------------------------------------------

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"


class CircuitBreaker:
    """Per-tenant degraded-mode state machine (driven by the coalescer
    worker thread — single-threaded by construction, service/coalesce.py
    LOCK_DISCIPLINE).

    CLOSED --(threshold consecutive retryable failures)--> OPEN
    OPEN   --(cooldown elapsed, one probe granted)-------> HALF_OPEN
    HALF_OPEN --(probe succeeds)--> CLOSED   (a recovery)
    HALF_OPEN --(probe fails)----> OPEN      (cooldown restarts)

    While OPEN the coalescer serves cache hits and rejects fresh
    dispatches retryable (`BreakerOpenError` + retry-after hint);
    `failure_threshold <= 0` disables the breaker entirely (allow()
    always True, nothing ever trips)."""

    __slots__ = ("failure_threshold", "cooldown_ms", "clock", "state",
                 "consecutive_failures", "opened_at", "trips", "probes",
                 "recoveries")

    def __init__(self, failure_threshold: int = 8,
                 cooldown_ms: float = 250.0,
                 clock: Callable[[], float] = time.monotonic):
        self.failure_threshold = int(failure_threshold)
        self.cooldown_ms = float(cooldown_ms)
        self.clock = clock
        self.state = CLOSED
        self.consecutive_failures = 0
        self.opened_at = 0.0
        self.trips = 0
        self.probes = 0
        self.recoveries = 0

    def _transition(self, to: str) -> None:
        frm, self.state = self.state, to
        from das_tpu import obs

        if obs.enabled():
            obs.event("serve.breaker", frm=frm, to=to)

    def allow(self) -> bool:
        """True when a fresh dispatch may proceed.  OPEN past the
        cooldown grants exactly ONE half-open probe; further calls stay
        rejected until that probe's verdict lands."""
        if self.failure_threshold <= 0 or self.state == CLOSED:
            return True
        if self.state == OPEN:
            if (self.clock() - self.opened_at) * 1e3 >= self.cooldown_ms:
                self._transition(HALF_OPEN)
                self.probes += 1
                return True
            return False
        return False  # HALF_OPEN: the granted probe is still in flight

    def record_success(self) -> None:
        if self.state == HALF_OPEN:
            self._transition(CLOSED)
            self.recoveries += 1
            from das_tpu import obs

            if obs.enabled():
                obs.counter("serve.breaker_recoveries").inc()
        self.consecutive_failures = 0

    def record_failure(self) -> None:
        if self.failure_threshold <= 0:
            return
        if self.state == HALF_OPEN:
            # the probe failed: re-open, restart the cooldown
            self._transition(OPEN)
            self.opened_at = self.clock()
            return
        self.consecutive_failures += 1
        if self.state == CLOSED and (
            self.consecutive_failures >= self.failure_threshold
        ):
            self._transition(OPEN)
            self.opened_at = self.clock()
            self.trips += 1
            from das_tpu import obs

            if obs.enabled():
                obs.counter("serve.breaker_trips").inc()

    def retry_after_ms(self) -> float:
        """Hint for rejected callers: remaining cooldown (OPEN), or one
        full cooldown (HALF_OPEN/CLOSED edge races)."""
        if self.state == OPEN:
            elapsed = (self.clock() - self.opened_at) * 1e3
            return max(0.0, self.cooldown_ms - elapsed)
        return self.cooldown_ms

    def snapshot(self) -> Dict:
        return {
            "state": self.state,
            "consecutive_failures": self.consecutive_failures,
            "trips": self.trips,
            "probes": self.probes,
            "recoveries": self.recoveries,
        }
