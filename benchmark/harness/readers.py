"""Arithmetic the per-layer readers of PR 26 share: device programs by
their declared module names, and sums / medians over the program's own
spans.

A jitted program shows on the device trace's modules line as
`jit_<name>(<fingerprint>)`.  The program gives its served and commit
programs declared names (`das_tpu/obs/registry.py PROGRAM_NAMES`): the
query programs are `das_fused*`, `das_count*`, `das_sharded*`, the
commit programs `das_merge*` / `das_insert*`.  A program without such a
name (an eager jnp op, a program of an older tree: `jit_fn`) is
"unnamed".
"""

from __future__ import annotations

from benchmark.harness import devtrace, stats

QUERY, COMMIT, UNNAMED = "query", "commit", "unnamed"
COMMIT_PREFIXES = ("das_merge", "das_insert")


def program_name(event_name: str) -> str:
    """`jit_das_fused(609536...)` -> `das_fused`."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def kind(event_name: str) -> str:
    name = program_name(event_name)
    if name.startswith(COMMIT_PREFIXES):
        return COMMIT
    return QUERY if name.startswith("das_") else UNNAMED


def programs_in_slice(trace, window) -> dict:
    """{kind: [seconds, programs]} over the modules line of every device
    plane, each program clipped to the traced slice
    (`window["trace_window_ns"]`; the whole trace where the clocks were
    not aligned).  None without a device trace."""
    planes = devtrace.device_planes(trace) if trace is not None else []
    if not planes:
        return None
    lo, hi = window.get("trace_window_ns") or (None, None)
    out = {QUERY: [0.0, 0], COMMIT: [0.0, 0], UNNAMED: [0.0, 0]}
    for plane in planes:
        for name, start, dur, *_ in devtrace._line(plane,
                                                   devtrace.MODULE_LINES):
            a = start if lo is None else max(start, lo)
            b = start + dur if hi is None else min(start + dur, hi)
            if b <= a:
                continue
            acc = out[kind(name)]
            acc[0] += (b - a) / 1e9
            acc[1] += 1
    return out


def durations_ms(spans, *names) -> list:
    """Durations of the complete ("X") spans with one of `names`."""
    return [s["dur"] * 1e3 for s in spans
            if s["phase"] == "X" and s["name"] in names]


def median_ms(spans, name):
    ms = durations_ms(spans, name)
    return stats.percentile(ms, 0.5) if ms else None


def in_slice(spans, window, name) -> int:
    """Events called `name` whose timestamp lies in the traced slice."""
    return sum(1 for s in spans if s["name"] == name
               and window["slice_t0"] <= s["t"] <= window["slice_t1"])
