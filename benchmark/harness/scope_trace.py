"""Device time under one of the program's `jax.named_scope` names.

A device operation's scope path (`jit(das_fused)/join.pair_verify/..`)
is in the profiler's file, as the stat `tf_op` of the operation's event
METADATA; `jax.profiler.ProfileData` hands out an event's own stats
only, so `devtrace.load_xplane` never sees it.  This module reads the
file itself: the few fields of the XSpace protocol buffer it needs
(tsl/profiler/protobuf/xplane.proto), decoded by hand so that the
process that holds the chip imports no second framework.

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 (map) .stat_metadata=5 (map)
    XLine.name=2 .timestamp_ns=3 .events=4
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3
    XEventMetadata.id=1 .name=2 .stats=5
    XStat.metadata_id=1 .str_value=5 .ref_value=7
    XStatMetadata.id=1 .name=2

The harness keeps a traced run's file under its own temporary
directory (`cell.Run.workdir`, prefix `das_bench_`) until the result is
printed, and hands a reader the loaded trace but not the file's path;
`own_trace` finds the file by what it holds, the loaded trace's own
operations, so a file another run left there is never read.
"""

from __future__ import annotations

import functools
import glob
import os
import tempfile

from benchmark.harness import devtrace

SCOPE_STAT = "tf_op"


def _varint(buf, at: int):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """(field number, value) of one message: ints for varints, a
    memoryview for length-delimited fields; fixed-width fields are
    skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire == 1:
            at += 8
        elif wire == 5:
            at += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _map_entry(buf):
    """(key, value message) of one entry of a map<int64, message>."""
    key = value = None
    for number, item in fields(buf):
        if number == 1:
            key = item
        elif number == 2:
            value = item
    return key, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


@functools.lru_cache(maxsize=4)
def device_op_scopes(path: str) -> list:
    """[[scope path, start_ns, duration_ns], ..] of every operation on
    the `XLA Ops` line of every device plane, times as
    `devtrace.load_xplane` gives them."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = []
    for number, plane in fields(space):
        if number != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for n, item in fields(plane):
            if n == 2:
                name = _text(item)
            elif n == 3:
                lines.append(item)
            elif n == 4:
                key, value = _map_entry(item)
                event_meta[key] = value
            elif n == 5:
                key, value = _map_entry(item)
                stat_names[key] = next(
                    (_text(v) for f, v in fields(value) if f == 2), "")
        if not name.startswith("/device:"):
            continue
        scope_ids = {k for k, v in stat_names.items() if v == SCOPE_STAT}
        scopes = {}

        def scope_of(meta_id):
            if meta_id not in scopes:
                found = ""
                for n, item in fields(event_meta.get(meta_id, b"")):
                    if n != 5:
                        continue
                    stat = dict(fields(item))
                    if stat.get(1) in scope_ids:
                        found = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
                scopes[meta_id] = found
            return scopes[meta_id]

        for line in lines:
            line_name, t0_ns, events = "", 0, []
            for n, item in fields(line):
                if n == 2:
                    line_name = _text(item)
                elif n == 3:
                    t0_ns = item
                elif n == 4:
                    events.append(item)
            if line_name not in devtrace.OP_LINES:
                continue
            for event in events:
                ev = dict(fields(event))
                out.append([scope_of(ev.get(1)),
                            t0_ns + ev.get(2, 0) / 1e3, ev.get(3, 0) / 1e3])
    return out


def in_scope(path_of_op: str, scope: str) -> bool:
    """Whether `scope` is one component of the operation's scope path."""
    return scope in path_of_op.rstrip(":").split("/")


def scope_seconds(path: str, scope: str, lo=None, hi=None) -> float:
    """Seconds of [lo, hi] (trace nanoseconds; the whole trace without)
    in which an operation under `scope` ran: the union of their
    intervals, so a loop and the operations of its body count once."""
    events = [[name, start, dur] for name, start, dur in device_op_scopes(path)
              if in_scope(name, scope)]
    return sum(b - a for a, b in
               devtrace.merged_intervals(events, lo, hi)) / 1e9


#: the scopes of the program's joins into a whole-type term
#: (das_tpu/obs/registry.py PAIR_JOIN_SCOPE, INDEX_JOIN_SCOPE; not
#: imported: the harness reads the program's output, never its modules)
PAIR_JOIN_SCOPE = "join.pair_verify"
INDEX_JOIN_SCOPE = "join.index_probe"


def seconds_in_slice(trace, window: dict, scope: str):
    """Device seconds under `scope` inside the traced slice of the run
    in progress; None where the run has no device trace, or its file is
    not found."""
    path = own_trace(trace, window) if trace is not None else None
    if path is None:
        return None
    lo, hi = window.get("trace_window_ns") or (None, None)
    return scope_seconds(path, scope, lo, hi)


def holds(path: str, trace: dict) -> bool:
    """Whether the file at `path` is the one `trace` was loaded from:
    the same device operations, one for one, at the same nanosecond
    (`ProfileData` hands out whole nanoseconds, the file picoseconds).
    A file another run left, or writes beside this one, holds other
    operations at other times."""
    mine = [(start, dur) for plane in devtrace.device_planes(trace)
            for _n, start, dur, *_ in devtrace._line(plane, devtrace.OP_LINES)]
    theirs = device_op_scopes(path)
    return len(mine) == len(theirs) > 0 and all(
        abs(a - c) < 1.0 and abs(b - d) < 1.0
        for (a, b), (_n, c, d) in zip(mine, theirs))


def own_trace(trace: dict, window: dict = None):
    """The profiler's file behind `trace`, the device trace the harness
    loaded for the run in progress: the one the window names
    (`xplane_path`), else, among the files under the harness's
    temporary directories (`window` does not name the run's own yet),
    the one that holds `trace`'s operations (`holds`): never a file
    chosen by its age.  None where there is none."""
    if window and window.get("xplane_path"):
        return window["xplane_path"]
    for path in glob.glob(os.path.join(
            tempfile.gettempdir(), "das_bench_*", "device_trace", "plugins",
            "profile", "*", "*.xplane.pb")):
        if holds(path, trace):
            return path
    return None
