"""What the mesh readers share: which device operations are collectives,
and seconds per device plane.  A device trace of a mesh run has one
plane per chip; an operation's event name is its HLO name
(`%all-gather.3`, `%all-reduce-start.1`) or, in some versions, its whole
instruction (`%ag = s32[..] all-gather(..)`)."""

from __future__ import annotations

import json
import os

from benchmark.harness import devtrace, hbm_model, readers

#: HLO opcodes that move data between chips; an asynchronous one shows
#: as its `-start` and `-done` halves
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter",
               "collective-broadcast")
MESH_PROGRAMS = ("das_sharded",)


def opcode(event_name: str) -> str:
    if " = " in event_name:
        m = devtrace._OPCODE.search(" " + event_name.split(" = ", 1)[1])
        return m.group(1) if m else ""
    return event_name.lstrip("%").split(".", 1)[0]


def is_collective(event_name: str) -> bool:
    return opcode(event_name).startswith(COLLECTIVES)


def planes(trace) -> list:
    return devtrace.device_planes(trace) if trace is not None else []


def collective_seconds(plane: dict) -> float:
    """Seconds this chip spent in collective operations."""
    return sum(dur for name, _s, dur, *_ in
               devtrace._line(plane, devtrace.OP_LINES)
               if is_collective(name)) / 1e9


def busy_seconds(plane: dict, window: dict) -> float:
    """Seconds of the traced slice in which this chip ran an operation."""
    lo, hi = window.get("trace_window_ns") or (None, None)
    return sum(b - a for a, b in devtrace.merged_intervals(
        devtrace._line(plane, devtrace.OP_LINES), lo, hi)) / 1e9


def mesh_program_seconds(trace) -> float:
    """Summed device time, over all planes, of the mesh query programs
    (`das_sharded*` on the modules line)."""
    return sum(dur for plane in planes(trace)
               for name, _s, dur, *_ in devtrace._line(
                   plane, devtrace.MODULE_LINES)
               if readers.program_name(name).startswith(MESH_PROGRAMS)) / 1e9


def peak(window: dict, file_name: str, key: str) -> float:
    with open(os.path.join(window["bench_dir"], "harness", file_name)) as fh:
        peaks = json.load(fh)["devices"]
    if window["device_kind"] not in peaks:
        raise KeyError(f"no peak for device {window['device_kind']!r}")
    return peaks[window["device_kind"]][key]


def hbm_bytes_in_slice(window: dict) -> float:
    return sum(hbm_model.query_bytes(shape, rows, window["store"])
               for shape, per_query in window["rows_by_shape_in_slice"].items()
               for rows in per_query)
