"""The coalescer's worker thread in the program's own spans: what the
per-layer readers of PR 42 share.

Every query of a tenant is planned, enqueued, fetched, judged,
materialised, printed and delivered by ONE thread, the one that records
`serve.drain`.  A span's OWN time is the part of it with no child span
open (`devtrace.innermost_segments`); what a span sums over its group
rides it as an attr (`lock_wait_ms`, `resolve_ms`, `wait_ms`,
`inflight`).  A tree older than a span or an attr reads nothing here
(`None`), it does not raise.
"""

from __future__ import annotations

from benchmark.harness import devtrace


def worker_spans(spans: list) -> list:
    """The complete ("X") spans of the thread(s) that record
    `serve.drain`."""
    workers = {s["thread"] for s in spans
               if s["name"] == devtrace.WORKER_SPAN}
    return [s for s in spans if s["phase"] == "X" and s["thread"] in workers]


def own_segments(spans: list) -> list:
    """Sorted disjoint [start_s, end_s, name] of the worker thread, each
    named by the innermost span open in it."""
    return devtrace.innermost_segments(
        [[s["name"], s["t"], s["dur"]] for s in worker_spans(spans)])


def own_ms(spans: list, name: str):
    """Own time of the worker's spans called `name`, summed; None
    where it recorded none."""
    if not any(s["name"] == name for s in worker_spans(spans)):
        return None
    return sum(b - a for a, b, n in own_segments(spans) if n == name) * 1e3


def attr_values(spans: list, name: str, attr: str) -> list:
    """The numeric attr `attr` of every complete span called `name`
    that carries it."""
    values = [(s["attrs"] or {}).get(attr) for s in spans
              if s["name"] == name and s["phase"] == "X"]
    return [v for v in values
            if isinstance(v, (int, float)) and not isinstance(v, bool)]


def answers(spans: list) -> int:
    """Answers delivered: `serve.answer` instants."""
    return sum(1 for s in spans if s["name"] == "serve.answer")
