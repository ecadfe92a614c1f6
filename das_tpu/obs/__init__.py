"""das_tpu.obs — structured per-query tracing + typed metrics (ISSUE 12).

The serving engine's window into itself: a trace id born in the RPC
handler (`wire.query`, PR 26; at coalescer submit only for a direct
caller) threads through submit/drain/group/plan/dispatch/settle-fetch/
verdict/materialize-or-cache-hit to answer delivery, each stage recording a
host-monotonic span into a bounded ring (obs/recorder.py), while the
metric layer (obs/metrics.py) keeps counters and fixed log-bucket
latency histograms that answer p50/p95/p99 without sample retention.
Exporters (obs/export.py) render the ring as Perfetto-loadable Chrome
trace JSON (`scripts/dump_trace.py`), as one thread's account of own
wall and CPU time per span name (`worker_account`), and the metrics as
Prometheus text exposition (service/server.py `metrics_text`); obs/jaxprof.py
optionally wraps the dispatch/settle halves in
`jax.profiler.TraceAnnotation` so host spans line up with the XLA
device timeline on hardware runs.

Everything is behind env `DAS_TPU_TRACE` (default OFF) with a
no-allocation disabled fast path: `span()` returns one shared no-op
context, `event()`/`mark()` return immediately, `new_trace()` returns
0.  Span/metric names are a closed declared set (obs/registry.py,
daslint rule DL014).  ARCHITECTURE §13 is the operator story.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

from das_tpu.obs import metrics as metrics  # noqa: F401 — public surface
from das_tpu.obs.export import (  # noqa: F401
    account_text,
    chrome_trace,
    dump_chrome_trace,
    prometheus_text,
    worker_account,
)
from das_tpu.obs.jaxprof import (  # noqa: F401
    annotation,
    maybe_start_trace,
    maybe_stop_trace,
)
from das_tpu.obs.metrics import (  # noqa: F401
    counter,
    histogram,
    reset_metrics,
)
from das_tpu.obs.recorder import NOOP_SPAN, TraceRecorder  # noqa: F401
from das_tpu.obs.registry import (  # noqa: F401
    COUNTER_NAMES,
    HISTOGRAM_NAMES,
    PROGRAM_NAMES,
    SPAN_NAMES,
)

# the program ledger (ISSUE 14) — imported after the metric layer it
# records into; gated by its OWN env (DAS_TPU_PROFLOG), not DAS_TPU_TRACE
from das_tpu.obs import proflog as proflog  # noqa: F401, E402

#: THE process recorder — env-initialized, reconfigurable for tests and
#: long-running services (obs.configure)
REC = TraceRecorder()


def enabled() -> bool:
    """Hot-path guard: call sites that would otherwise pack attribute
    dicts (the executor dispatch halves) check this first so the
    disabled path costs one attribute read."""
    return REC.enabled


def configure(enabled: Optional[bool] = None,
              capacity: Optional[int] = None) -> None:
    REC.configure(enabled=enabled, capacity=capacity)


def reset() -> None:
    """Drop the ring and zero the metric layer (bench/test arms start
    from a clean window)."""
    REC.reset()
    reset_metrics()


#: `obs.span(name, trace=0, **attrs)` / `obs.event(...)`: the process
#: recorder's own methods, bound once (REC is never rebound), so a call
#: site pays one call and one packing of its attrs.  `name` must be an
#: obs/registry.py member (daslint DL014)
span = REC.span
event = REC.event


def new_trace() -> int:
    return REC.new_trace()


def set_context(lane: Optional[str] = None, group: int = 0) -> None:
    REC.set_context(lane, group)


def mark() -> Optional[Tuple[int, float]]:
    """Birth certificate of one traced unit of work: (fresh trace id,
    perf_counter now) — or None when tracing is off, so carrying a mark
    through a queue costs nothing on the disabled path.  The coalescer
    attaches one per submitted query; answer delivery closes it
    (serve.answer event + serve.answer_ms histogram)."""
    if not REC.enabled:
        return None
    return REC.new_trace(), time.perf_counter()


def events():
    return REC.events()


def origin() -> float:
    """`time.perf_counter()` at the recorder's origin: an event's
    timestamp plus this is its perf_counter time, which the `obs.sync`
    annotation (obs/jaxprof.py) ties to a device trace's clock."""
    return REC.origin()


def named_program(name: str, fn=None, count_only: bool = False):
    """Give the function about to be jitted its declared module name
    (obs/registry.py PROGRAM_NAMES; `_count` appended for a count-only
    variant), so the device trace shows `jit_<name>` instead of
    `jit_fn`.  Trace-time only.  Without `fn`: a decorator, to sit
    under `@jax.jit`."""
    if name not in PROGRAM_NAMES:
        raise KeyError(f"undeclared device program name {name!r}")
    if fn is None:
        return lambda f: named_program(name, f, count_only)
    fn.__name__ = fn.__qualname__ = name + ("_count" if count_only else "")
    return fn
