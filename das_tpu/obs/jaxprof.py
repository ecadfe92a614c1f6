"""Optional jax.profiler integration (ISSUE 12): named host scopes the
XLA device timeline can be lined up against.

With env `DAS_TPU_TRACE_JAX=1`, `annotation(name)` wraps a block in
`jax.profiler.TraceAnnotation` — the dispatch and settle halves use it
so a captured device trace (Perfetto, via `jax.profiler.start_trace`)
shows which host-side dispatch enqueued which device program and where
the settle fetch sat relative to device execution.  Off (the default)
it returns ONE shared null context: no jax import, no allocation — the
recorder's disabled-path contract.

`maybe_start_trace(config)` / `maybe_stop_trace()` plumb
`DasConfig.profiler_trace_dir` (env `DAS_TPU_TRACE_DIR`) through to
`jax.profiler.start_trace`/`stop_trace`: the hardware-closeout runbook
is "set DAS_TPU_TRACE=1 DAS_TPU_TRACE_JAX=1 DAS_TPU_TRACE_DIR=/tmp/tb,
run the workload, open both the obs trace and the device trace in
Perfetto" (ARCHITECTURE §13).  The two files share one axis through
the `obs.sync` annotation (SYNC_NAME below) and the origin the obs
trace carries in its metadata.
"""

from __future__ import annotations

import os
import time

from das_tpu.obs.recorder import NOOP_SPAN, TRUTHY

_started = {"dir": None}

#: the host annotation `maybe_start_trace` writes into the device trace
#: right after it starts: its `t_ns` stat is `time.perf_counter_ns()`
#: at that moment, so (t_ns - the event's trace time) carries any
#: recorder timestamp + `obs.origin()` onto the device trace's axis
SYNC_NAME = "obs.sync"

#: memoized on the RAW env string: annotation() sits on the dispatch
#: and settle-fetch hot paths outside the obs.enabled() guard, so the
#: disabled path must cost one environ dict lookup — not a str.lower
#: + tuple scan per device-program enqueue.  A changed env value
#: (tests monkeypatch it) re-evaluates because the raw string moves.
_gate = {"raw": object(), "on": False}


def jax_annotations_enabled() -> bool:
    raw = os.environ.get("DAS_TPU_TRACE_JAX")
    if raw != _gate["raw"]:
        _gate["raw"] = raw
        _gate["on"] = (raw or "0").lower() in TRUTHY
    return _gate["on"]


def annotation(name: str):
    """A jax.profiler.TraceAnnotation when DAS_TPU_TRACE_JAX is on,
    else the shared no-op context.  Span names are registry members
    (obs/registry.py, DL014) so host trace and device trace agree on
    vocabulary."""
    if not jax_annotations_enabled():
        return NOOP_SPAN
    import jax

    return jax.profiler.TraceAnnotation(name)


def maybe_start_trace(config=None) -> bool:
    """Start a jax.profiler trace into `config.profiler_trace_dir` when
    configured (idempotent — a second call with a trace running is a
    no-op).  Returns True when a trace is running."""
    trace_dir = getattr(config, "profiler_trace_dir", None)
    if not trace_dir:
        return False
    if _started["dir"] is not None:
        return True
    import jax

    jax.profiler.start_trace(trace_dir)
    _started["dir"] = trace_dir
    with jax.profiler.TraceAnnotation(
            SYNC_NAME, t_ns=time.perf_counter_ns()):
        pass
    return True


def maybe_stop_trace() -> bool:
    """Stop the running jax.profiler trace, if any."""
    if _started["dir"] is None:
        return False
    import jax

    jax.profiler.stop_trace()
    _started["dir"] = None
    return True
