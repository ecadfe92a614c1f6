"""Device time under one of the program's `jax.named_scope` names, chip
by chip.

`scope_trace.scope_seconds` unites the intervals of every device plane,
which is one chip's time when the chips run a program side by side.  A
mesh reader wants chip-seconds: a sum over the planes, or their mean.
`scope_trace.device_op_scopes` hands out the operations of all planes
in one list, in the order of the loaded trace (`scope_trace.holds`
finds the file by exactly that: the same operations, one for one), so
the loaded trace's planes cut the list, and an operation's scope path
from the file lies beside its HLO name from the loaded trace.
"""

from __future__ import annotations

from benchmark.harness import devtrace, mesh_trace, readers, scope_trace

#: das_tpu/obs/registry.py PAIR_PARTITION_SCOPE / the scope of
#: parallel/fused_sharded.py _repartition (not imported: the harness
#: reads the program's output, never its modules)
PAIR_PARTITION_SCOPE = "mesh.pair_partition"
REPARTITION_SCOPE = "mesh.repartition"


def moves_data(event_name: str) -> bool:
    """`mesh_trace.is_collective`, also for an operation the compiler
    named after the program's primitive (`%all_to_all.6` for the HLO
    opcode `all-to-all`), where the event's name is the HLO name alone."""
    return (mesh_trace.is_collective(event_name)
            or mesh_trace.is_collective(event_name.replace("_", "-")))


def plane_seconds(trace, window: dict, scope: str, collectives: bool = False):
    """Per device plane, the seconds of the traced slice in which an
    operation under `scope` ran (the union of their intervals, so a loop
    and its body count once); with `collectives`, only the operations
    that move data between chips.  None where the run has no device
    trace or its file is not found."""
    path = scope_trace.own_trace(trace, window) if trace is not None else None
    if path is None:
        return None
    scoped = scope_trace.device_op_scopes(path)
    lo, hi = window.get("trace_window_ns") or (None, None)
    out, at = [], 0
    for plane in devtrace.device_planes(trace):
        ops = devtrace._line(plane, devtrace.OP_LINES)
        mine = scoped[at:at + len(ops)]
        at += len(ops)
        events = [
            [path_of_op, start, dur]
            for (path_of_op, start, dur), (name, *_rest) in zip(mine, ops)
            if scope_trace.in_scope(path_of_op, scope)
            and (not collectives or moves_data(name))
        ]
        out.append(sum(b - a for a, b in
                       devtrace.merged_intervals(events, lo, hi)) / 1e9)
    return out


def programs_per_plane(trace, window: dict) -> float:
    """Query programs run in the slice on ONE chip (the mean of the
    planes), a program cut by the slice's edge as the share of its
    duration that lies inside."""
    planes = devtrace.device_planes(trace) if trace is not None else []
    if not planes:
        return 0.0
    lo, hi = window.get("trace_window_ns") or (None, None)
    total = 0.0
    for plane in planes:
        for name, start, dur, *_ in devtrace._line(plane,
                                                   devtrace.MODULE_LINES):
            if readers.kind(name) != readers.QUERY or dur <= 0:
                continue
            a = start if lo is None else max(start, lo)
            b = start + dur if hi is None else min(start + dur, hi)
            total += max(0.0, b - a) / dur
    return total / len(planes)


def one_shape(window: dict):
    """(shape, mean rows of its answers in the slice) where the slice
    holds ONE query shape, else None."""
    by_shape = window.get("rows_by_shape_in_slice") or {}
    if len(by_shape) != 1:
        return None
    (shape, rows), = by_shape.items()
    return shape, (sum(rows) / len(rows) if rows else 0.0)
