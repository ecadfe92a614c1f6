#!/usr/bin/env python3
"""recorder_cost.py — what one trace event costs the thread that records
it: microseconds per span, per instant and per disabled span, through
`TraceRecorder` and through the `obs.span` / `obs.event` the call sites
use.  Best of `--repeat` loops of `--n` events; one JSON line.

    JAX_PLATFORMS=cpu python3 scripts/recorder_cost.py [--root CHECKOUT]

`--root`: measure another checkout's recorder (a parent commit unpacked
beside this one) with the same loop.  A host clock: the numbers belong
to the machine they were taken on.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def best_us(fn, n: int, repeat: int) -> float:
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root",
                    default=str(Path(__file__).resolve().parent.parent))
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--repeat", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path.insert(0, args.root)

    from das_tpu import obs
    from das_tpu.obs.recorder import TraceRecorder

    rec = TraceRecorder(enabled=True, capacity=1 << 16)
    rec.set_context("lane", 7)
    off = TraceRecorder(enabled=False)
    obs.configure(enabled=True, capacity=1 << 16)
    obs.set_context("lane", 7)

    def span():
        with rec.span("exec.format", rows=3):
            pass

    def obs_span():
        with obs.span("exec.format", rows=3):
            pass

    def off_span():
        with off.span("exec.format", rows=3):
            pass

    def instant():
        rec.event("serve.answer", trace=3, error=False)

    def obs_event():
        obs.event("serve.answer", trace=3, error=False)

    loops = {
        "span_us": span, "instant_us": instant, "obs_span_us": obs_span,
        "obs_event_us": obs_event, "disabled_span_us": off_span,
        "thread_time_us": time.thread_time,
        "perf_counter_us": time.perf_counter,
    }
    out = {"root": args.root, "n": args.n, "repeat": args.repeat}
    for name, fn in loops.items():
        out[name] = best_us(fn, args.n, args.repeat)
    obs.reset()
    obs.configure(enabled=False)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
