"""Low-overhead structured trace recorder (ISSUE 12 tentpole).

One process-wide `TraceRecorder` holds a bounded ring of span/instant
events.  A trace id is born in the RPC handler (`wire.query`; at
coalescer submit for a direct caller: `new_trace`), rides the
submit-queue tuple to the worker, and every deeper layer —
drain/group/plan/dispatch/settle-fetch/materialize-or-cache-hit down
to answer delivery — attaches either that id or the GROUP id the
worker publishes through a thread-local (`set_context`), so a
Perfetto/Chrome-trace view can line a query's answer up with the exact
device dispatch and settle transfer that produced it.

Disabled fast path (env `DAS_TPU_TRACE`, default off): `span()` returns
ONE shared no-op context manager and `event()` returns before touching
its arguments' containers — no span objects, no ring appends, no
timestamps (tests/test_zobs.py pins the no-allocation contract
structurally).  Hot call sites (the executor dispatch halves) guard on
`enabled()` so even their attribute packing is skipped.

Timing discipline: `time.perf_counter()` for wall time and
`time.thread_time()` for a span's `cpu_ms` — host clocks only, no
device sync (DL001/DL010: the dispatch halves stay sync-free; the
recorder never calls into jax).  Ring bound: env `DAS_TPU_TRACE_RING`
(default 65536 events); past it the OLDEST events drop (a long-running
service keeps the recent window, which is the one the operator asks
for).

Lock discipline (daslint DL006): every post-__init__ recorder attribute
mutation happens under `_lock` — configure/reset swap whole structures
and new_trace bumps the id counter there; the ring deque's `append` is
a single atomic op on a maxlen deque, and readers (`events()`) snapshot
under the same lock.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

#: daslint DL006 — who may mutate each piece of post-__init__ recorder
#: state.  Everything structural is serialized on `_lock` (configure /
#: reset / new_trace are cold paths; the hot path only APPENDS to the
#: maxlen ring, which is atomic under the GIL and covered by the deque
#: itself).  A new mutable attribute fails lint until it declares its
#: owner here.
LOCK_DISCIPLINE = {
    "TraceRecorder.enabled": "_lock",
    "TraceRecorder.capacity": "_lock",
    "TraceRecorder._ring": "_lock",
    "TraceRecorder._next": "_lock",
    "TraceRecorder._t_origin": "_lock",
    # a span is never shared: it lives and dies on the thread that
    # opened it, which is the only one to touch its clocks and attrs
    "_Span.t0": "worker",
    "_Span._cpu0": "worker",
    "_Span.attrs": "worker",
}

WORKER_METHODS: Dict[str, Tuple[str, ...]] = {
    "_Span": ("__enter__", "__exit__"),
}

#: the accepted "on" spellings for obs env switches — ONE definition
#: (jaxprof's DAS_TPU_TRACE_JAX gate reuses it), so the two flags
#: cannot drift in what they accept
TRUTHY = frozenset(("1", "on", "true", "yes"))


def env_truthy(name: str, default: str = "0") -> bool:
    return os.environ.get(name, default).lower() in TRUTHY


def _env_enabled() -> bool:
    return env_truthy("DAS_TPU_TRACE")


def _env_ring() -> int:
    raw = os.environ.get("DAS_TPU_TRACE_RING")
    try:
        n = int(raw) if raw else 65536
    except ValueError:
        n = 65536
    return max(16, n)


class _NoopSpan:
    """THE disabled-path span: one shared instance, no state, no
    timestamps.  `span()` hands this back when tracing is off, so the
    disabled path allocates nothing per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        return False

    def set(self, **_attrs):
        """No-op attribute update (mirrors _Span.set)."""


NOOP_SPAN = _NoopSpan()


class _Span:
    """One live span: its clocks start at `__enter__` (a span built
    ahead of a `with lock, span:` pair must not time the wait for the
    lock) and it records itself on `__exit__`.  Besides wall time it
    takes the calling thread's CPU time (`time.thread_time`) at both
    ends and records the difference as attr `cpu_ms`: on a span that
    never blocks on I/O, a lock or the device, wall minus CPU is the
    time the thread waited for the interpreter.  No post-construction
    mutation of recorder state — the single ring append happens at
    exit."""

    __slots__ = ("_rec", "name", "trace", "attrs", "t0", "_cpu0")

    def __init__(self, rec: "TraceRecorder", name: str, trace: int, attrs):
        self._rec = rec
        self.name = name
        self.trace = trace
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. the drained
        width, known only after the blocking get returns)."""
        self.attrs.update(attrs)

    def __enter__(self):
        self._cpu0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *_exc):
        t0 = self.t0
        dur = time.perf_counter() - t0
        attrs = self.attrs
        attrs["cpu_ms"] = (time.thread_time() - self._cpu0) * 1e3
        self._rec.record(self.name, "X", t0, dur, self.trace, attrs)
        return False


class TraceRecorder:
    """Bounded ring of (name, phase, t0, dur, trace, group, lane,
    thread, attrs) event tuples plus the trace-id source and the
    worker-published thread-local context."""

    def __init__(self, enabled: Optional[bool] = None,
                 capacity: Optional[int] = None):
        self.enabled = _env_enabled() if enabled is None else bool(enabled)
        self.capacity = _env_ring() if capacity is None else max(16, capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._next = 0
        self._lock = threading.Lock()
        self._tls = threading.local()
        #: perf_counter origin: exported timestamps are relative to
        #: recorder construction/reset so traces start near t=0
        self._t_origin = time.perf_counter()

    # -- configuration (tests / server) ---------------------------------

    def configure(self, enabled: Optional[bool] = None,
                  capacity: Optional[int] = None) -> None:
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if capacity is not None:
                self.capacity = max(16, int(capacity))
                self._ring = deque(self._ring, maxlen=self.capacity)

    def reset(self) -> None:
        with self._lock:
            self._ring = deque(maxlen=self.capacity)
            self._next = 0
            # re-base so a post-reset trace starts near t=0 (the
            # "relative to construction/reset" contract below); spans
            # already open across a reset land at negative ts — reset
            # is a window boundary, not a mid-flight operation
            self._t_origin = time.perf_counter()

    # -- trace ids + worker context --------------------------------------

    def new_trace(self) -> int:
        """A fresh trace id (monotone, process-local); 0 when disabled —
        callers thread 0 around for free and nothing records."""
        if not self.enabled:
            return 0
        with self._lock:
            self._next += 1
            return self._next

    def _thread_ctx(self) -> list:
        """This thread's `[thread name, lane, group]`, made at the
        thread's first event and kept in the thread-local: what is
        fixed per thread (its name, read once: a thread renamed later
        keeps the name it recorded first under) or set once per group
        (`set_context`) is not looked up again per event.  The list
        lives and dies with its thread, so it needs no lock."""
        tls = self._tls
        try:
            return tls.ctx
        except AttributeError:
            ctx = tls.ctx = [threading.current_thread().name, None, 0]
            return ctx

    def set_context(self, lane: Optional[str] = None,
                    group: int = 0) -> None:
        """Publish the worker's current (tenant lane, group id): deeper
        spans recorded on this THREAD (executor dispatch/settle halves,
        cache events) inherit them without signature changes.  Lane maps
        to a Perfetto track; group links a device span back to the
        submit traces it served."""
        ctx = self._thread_ctx()
        ctx[1] = lane
        ctx[2] = group

    # -- recording --------------------------------------------------------

    def record(self, name: str, phase: str, t0: float, dur: float,
               trace: int, attrs, lane: Optional[str] = None) -> None:
        """`lane` overrides the thread-local context lane for events
        that belong to a dedicated Perfetto track regardless of which
        tenant's thread produced them (the proflog compile lane)."""
        if not self.enabled:
            return
        thread, ctx_lane, group = self._thread_ctx()
        self._ring.append((
            name, phase, t0 - self._t_origin, dur, trace, group,
            ctx_lane if lane is None else lane, thread, attrs,
        ))

    def span(self, name: str, trace: int = 0, **attrs):
        """Context manager recording one complete span; the shared
        no-op when tracing is off.  Never hold one open across a
        `yield`: the consumer's spans would nest under it and its own
        time would be booked to the producer (registry.py)."""
        if not self.enabled:
            return NOOP_SPAN
        return _Span(self, name, trace, attrs)

    def event(self, name: str, trace: int = 0, **attrs) -> None:
        """One instant event; no-op when tracing is off."""
        if self.enabled:
            self.record(name, "i", time.perf_counter(), 0.0, trace, attrs)

    # -- readout ----------------------------------------------------------

    def origin(self) -> float:
        """The `time.perf_counter()` reading every recorded timestamp
        is relative to (construction or the last reset)."""
        return self._t_origin

    def events(self) -> List[Tuple]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        return len(self._ring)
