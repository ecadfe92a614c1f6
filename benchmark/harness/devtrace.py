"""From a profiler trace to numbers: busy time, idle gaps, time by
operation.  The reduction works on a small intermediate form

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns, {stat: value}], ..]}]}]}

so that it can be checked on a recorded trace kept as JSON
(`benchmark/tests/data/`), and `load_xplane` is the only part that
needs the profiler's own file.  Times inside are nanoseconds from the
start of the trace; results are seconds.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

#: stats worth keeping per event (the rest is dropped at load time)
KEEP_STATS = ("hlo_module", "hlo_op", "program_id", "t_ns", "run_id")
#: the line of a device plane that holds one event per executed HLO op
OP_LINES = ("XLA Ops",)
#: the line that holds one event per executed program
MODULE_LINES = ("XLA Modules",)
SYNC_NAME = "bench.sync"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, keep_host: bool = False) -> dict:
    """The profiler's file -> the intermediate form.  Device planes are
    kept whole; of the host planes only the sync annotation (and, with
    `keep_host`, everything)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if not (is_device or keep_host or ev.name == SYNC_NAME):
                    continue
                stats = {}
                for key, value in ev.stats:
                    if key in KEEP_STATS:
                        stats[key] = value
                events.append([ev.name, float(ev.start_ns),
                               float(ev.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    """Planes of accelerator devices that ran at least one operation."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:") and _line(plane, OP_LINES):
            out.append(plane)
    return out


def _line(plane: dict, names) -> list:
    for line in plane["lines"]:
        if line["name"] in names:
            return line["events"]
    return []


def merged_intervals(events, lo: float = None, hi: float = None) -> list:
    """Union of [start, start+duration) as sorted disjoint [a, b]
    pairs, clipped to [lo, hi] when given."""
    spans = []
    for _name, start, dur, *_ in events:
        a, b = start, start + dur
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b > a:
            spans.append((a, b))
    spans.sort()
    out = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def trace_extent(trace: dict) -> tuple:
    """(first start, last end) over the device planes' operations."""
    lo, hi = None, None
    for plane in device_planes(trace):
        for _n, start, dur, *_ in _line(plane, OP_LINES):
            lo = start if lo is None else min(lo, start)
            hi = start + dur if hi is None else max(hi, start + dur)
    return lo, hi


def busy_seconds(trace: dict, lo: float = None, hi: float = None) -> float:
    """Seconds in which an operation ran on the device: the union of the
    op intervals, averaged over the devices that ran any."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    total = 0.0
    for plane in planes:
        total += sum(b - a for a, b in
                     merged_intervals(_line(plane, OP_LINES), lo, hi))
    return total / len(planes) / 1e9


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """An HLO op's event name is its whole instruction: keep the result
    name, the opcode, the start of the result shape, and a custom call's
    target."""
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else "?"
    shape = rest[:m.start()].strip() if m else ""
    out = f"{head} {opcode} {shape[:48]}"
    if 'custom_call_target="' in rest:
        out += " -> " + rest.split('custom_call_target="', 1)[1].split('"')[0]
    return out


def seconds_by_name(trace: dict, lines=OP_LINES, top: int = 10) -> list:
    """[[name, seconds], ..] summed over the devices, largest first."""
    acc = {}
    for plane in device_planes(trace):
        for name, _start, dur, *_ in _line(plane, lines):
            name = short_name(name)
            acc[name] = acc.get(name, 0.0) + dur / 1e9
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]


def module_seconds(trace: dict) -> float:
    """Summed device time of whole programs (the modules line)."""
    return sum(dur for plane in device_planes(trace)
               for _n, _s, dur, *_ in _line(plane, MODULE_LINES)) / 1e9


def module_count(trace: dict) -> int:
    return sum(len(_line(plane, MODULE_LINES))
               for plane in device_planes(trace))


def idle_gaps(trace: dict, lo: float, hi: float, top: int = 10) -> list:
    """The longest intervals of [lo, hi] in which no operation ran on
    the first device: [[start_ns, duration_ns], ..]."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = merged_intervals(_line(planes[0], OP_LINES), lo, hi)
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append([at, a - at])
        at = max(at, b)
    if hi > at:
        gaps.append([at, hi - at])
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]


def sync_offset_ns(trace: dict):
    """perf_counter_ns minus trace time, from the `bench.sync`
    annotation the harness writes with its own clock reading as the
    `t_ns` stat.  None when the trace holds no such event."""
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, start, _dur, stats in line["events"]:
                if name == SYNC_NAME and "t_ns" in stats:
                    return float(stats["t_ns"]) - start
    return None


#: the span only the coalescer's worker thread records (one per drain)
WORKER_SPAN = "serve.drain"
NO_SPAN = "worker: no span open (waiting for requests)"


def innermost_segments(spans: list) -> list:
    """Spans [name, start_s, duration_s] of ONE thread (they nest) ->
    sorted disjoint [start_s, end_s, name] segments, each named by the
    innermost span open in it: a span's own time, without its children's."""
    out, stack = [], []        # stack of [name, end]

    def emit(a, b):
        if stack and b > a:
            out.append([a, b, stack[-1][0]])

    at = None
    for name, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            emit(at, stack[-1][1])
            at = stack.pop()[1]
        emit(at, start)
        stack.append([name, start + dur])
        at = start
    while stack:
        emit(at, stack[-1][1])
        at = stack.pop()[1]
    return out


def attribute_gaps(gaps: list, worker_spans: list, offset_ns) -> list:
    """Name each gap by what the host's worker thread was doing: of its
    spans [name, start_s, duration_s] (perf_counter seconds) the
    INNERMOST one open over most of the gap, `NO_SPAN` where none was.
    [[name, seconds], ..], gaps with the same name summed, longest
    first."""
    if offset_ns is None:
        total = sum(dur for _start, dur in gaps) / 1e9
        return [["host: clocks not aligned", total]] if gaps else []
    segments = innermost_segments(worker_spans)
    starts = [seg[0] for seg in segments]
    acc = {}
    for start, dur in gaps:
        a = (start + offset_ns) / 1e9
        b = a + dur / 1e9
        cover = {}
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s0, s1, name = segments[i]
            if min(b, s1) > max(a, s0):
                cover[name] = cover.get(name, 0.0) + min(b, s1) - max(a, s0)
            i += 1
        cover[NO_SPAN] = (b - a) - sum(cover.values())
        label = max(cover, key=cover.get)
        acc[label] = acc.get(label, 0.0) + dur / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]
