"""Trace/metric exporters: Chrome trace-event JSON (Perfetto-loadable),
Prometheus text exposition, and one thread's span account.

Chrome trace format (the subset Perfetto ingests): one "X" complete
event per span and one "i" instant event per point event, timestamps
and durations in MICROseconds, `pid` = the tenant lane and `tid` = the
recording thread — so the Perfetto timeline renders one process row
per tenant with one track per worker/RPC thread, and the submit →
drain → dispatch → settle → answer cascade reads left-to-right on the
worker track.  "M" metadata events name the lanes/threads; trace and
group ids ride in `args` so a flow can be followed by query.

Prometheus text exposition (the service/server.py hook): counters as
`das_tpu_obs_<name>_total`, histograms in the native histogram triple
(`_bucket{le=...}` cumulative, `_sum`, `_count`) — scrape-ready,
derivable p50/p95/p99 via `histogram_quantile`.

Span account (`worker_account`): where ONE thread's time went, per span
name its OWN wall and CPU time (its children's taken out) — the table
PERF.md §5 ranks the coalescer worker's bottlenecks by
(`scripts/worker_account.py`, `scripts/dump_trace.py --account`).
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

from das_tpu.obs import metrics as _metrics


def chrome_trace(events: List[Tuple], origin: Optional[float] = None) -> Dict:
    """Render recorder event tuples (TraceRecorder.events()) into a
    Chrome trace-event dict — `json.dumps` of it loads in Perfetto /
    chrome://tracing.  `origin` (default: the process recorder's) is
    the perf_counter second that `ts` 0 stands for; it goes into the
    metadata so the file can be laid over a device trace that holds
    the `obs.sync` annotation."""
    if origin is None:
        from das_tpu import obs

        origin = obs.origin()
    lanes: Dict[Optional[str], int] = {}
    threads: Dict[str, int] = {}
    out: List[Dict] = []
    for name, phase, t0, dur, trace, group, lane, thread, attrs in events:
        pid = lanes.setdefault(lane, len(lanes) + 1)
        tid = threads.setdefault(thread, len(threads) + 1)
        args = dict(attrs) if attrs else {}
        if trace:
            args["trace"] = trace
        if group:
            args["group"] = group
        ev = {
            "name": name,
            "ph": phase,
            "ts": round(t0 * 1e6, 3),
            "pid": pid,
            "tid": tid,
            "args": args,
        }
        if phase == "X":
            ev["dur"] = round(dur * 1e6, 3)
        else:
            ev["s"] = "t"  # thread-scoped instant
        out.append(ev)
    meta: List[Dict] = []
    for lane, pid in lanes.items():
        meta.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": lane or "das_tpu"},
        })
    for thread, tid in threads.items():
        for pid in lanes.values():
            meta.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": thread},
            })
    return {"traceEvents": meta + out, "displayTimeUnit": "ms",
            "metadata": {"perf_counter_origin_s": origin}}


def dump_chrome_trace(events: List[Tuple], path: str) -> str:
    """Write the Perfetto-loadable JSON to `path`; returns the path."""
    with open(path, "w") as f:
        json.dump(chrome_trace(events), f)
    return path


#: an account is of the thread that records this span (the coalescer's
#: worker, one per drain); benchmark/harness/devtrace.py names the idle
#: gaps of the ledger's `breakdown` by the same thread
WORKER_SPAN = "serve.drain"

#: numeric span attrs an account sums per span name: what a span
#: carries for its whole group in place of an event per query
ACCOUNT_ATTRS = ("queries", "lanes", "lock_wait_ms", "wait_ms", "resolve_ms")


def worker_account(events: List[Tuple], t0: Optional[float] = None,
                   t1: Optional[float] = None) -> Dict:
    """Recorder event tuples -> the account of ONE thread, the one with
    the most `serve.drain` spans (the coalescer's worker): per span
    name its count, total wall seconds, OWN wall and OWN CPU seconds (a
    span's duration / `cpu_ms` less its direct children's: the time
    inside it with no child span open) and the sums of its
    `ACCOUNT_ATTRS`; instants by count.  `t0` / `t1`: keep the events
    that START in that stretch of the recorder's clock, whole (no span
    is cut).

    Own wall minus own CPU of a span that never blocks is the wait for
    the interpreter lock.  Spans of one thread nest (nothing is open across a `yield`);
    spans with equal intervals nest in recording order (`mesh.fetch`
    inside `exec.settle_fetch`)."""
    drains: Dict[str, int] = {}
    for ev in events:
        if ev[0] == WORKER_SPAN:
            drains[ev[7]] = drains.get(ev[7], 0) + 1
    thread = max(drains, key=drains.get) if drains else None
    mine = [ev for ev in events if ev[7] == thread
            and (t0 is None or ev[2] >= t0) and (t1 is None or ev[2] <= t1)]
    spans: Dict[str, Dict] = {}
    instants: Dict[str, int] = {}
    stack: List[List] = []      # [end, row, child wall, child cpu, wall, cpu]

    def close(top) -> None:
        _end, row, child_wall, child_cpu, wall, cpu = top
        row["own_wall_s"] += wall - child_wall
        row["own_cpu_s"] += cpu - child_cpu

    ordered = sorted(
        (ev for ev in mine if ev[1] == "X"), key=lambda ev: (ev[2], -ev[3])
    )
    for name, _ph, start, dur, _tr, _g, _lane, _th, attrs in ordered:
        while stack and stack[-1][0] <= start + 1e-9:
            close(stack.pop())
        row = spans.get(name)
        if row is None:
            row = spans[name] = {"count": 0, "wall_s": 0.0,
                                 "own_wall_s": 0.0, "own_cpu_s": 0.0,
                                 "attrs": {}}
        attrs = attrs or {}
        cpu = attrs.get("cpu_ms", 0.0) / 1e3
        row["count"] += 1
        row["wall_s"] += dur
        sums = row["attrs"]
        for key in ACCOUNT_ATTRS:
            value = attrs.get(key)
            if value is not None and not isinstance(value, bool):
                sums[key] = sums.get(key, 0) + value
        if stack:
            stack[-1][2] += dur
            stack[-1][3] += cpu
        stack.append([start + dur, row, 0.0, 0.0, dur, cpu])
    while stack:
        close(stack.pop())
    for ev in mine:
        if ev[1] != "X":
            instants[ev[0]] = instants.get(ev[0], 0) + 1
    ranked = sorted(spans.items(), key=lambda kv: -kv[1]["own_wall_s"])
    return {"thread": thread, "t0": t0, "t1": t1,
            "spans": dict(ranked), "instants": instants}


def account_text(account: Dict, per: Optional[int] = None) -> str:
    """The account as a table, one line per span name, largest own
    wall first; with `per` (a count of answers) a column of own
    milliseconds per answer."""
    head = f"{'span':<20}{'count':>8}{'wall s':>10}{'own s':>10}" \
           f"{'own cpu s':>11}"
    lines = [f"thread {account['thread']}",
             head + (f"{'own ms/answer':>15}" if per else "")]
    for name, row in account["spans"].items():
        line = (f"{name:<20}{row['count']:>8}{row['wall_s']:>10.3f}"
                f"{row['own_wall_s']:>10.3f}{row['own_cpu_s']:>11.3f}")
        if per:
            line += f"{row['own_wall_s'] * 1e3 / per:>15.4f}"
        if row["attrs"]:
            line += "  " + " ".join(
                f"{k}={v:.6g}" for k, v in sorted(row["attrs"].items()))
        lines.append(line)
    lines.append("instants: " + " ".join(
        f"{k}={v}" for k, v in sorted(account["instants"].items())))
    return "\n".join(lines)


def _prom_name(name: str) -> str:
    return "das_tpu_obs_" + name.replace(".", "_").replace("-", "_")


def prometheus_text(extra_gauges: Optional[Dict[str, float]] = None) -> str:
    """The metric layer in Prometheus text exposition format.  The
    serving facade (service/server.py metrics_text) folds its aggregate
    coalescer gauges in via `extra_gauges` — one scrape surface for the
    whole serving path."""
    lines: List[str] = []
    for name, c in sorted(_metrics.COUNTERS.items()):
        pn = _prom_name(name) + "_total"
        lines.append(f"# TYPE {pn} counter")
        lines.append(f"{pn} {c.value}")
    for name, h in sorted(_metrics.HISTOGRAMS.items()):
        pn = _prom_name(name)
        lines.append(f"# TYPE {pn} histogram")
        cum = 0
        for upper, count in h.nonzero_buckets():
            cum += count
            lines.append(f'{pn}_bucket{{le="{upper:g}"}} {cum}')
        lines.append(f'{pn}_bucket{{le="+Inf"}} {h.total}')
        lines.append(f"{pn}_sum {h.sum_ms:g}")
        lines.append(f"{pn}_count {h.total}")
    for name, value in sorted((extra_gauges or {}).items()):
        pn = _prom_name(name)
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {value:g}")
    return "\n".join(lines) + "\n"
