#!/usr/bin/env python3
"""worker_account.py — run one cell of the benchmark traced and write
where the coalescer's ONE worker thread spent the window: per span name
its count, total wall, OWN wall and OWN CPU seconds and the sums of the
attrs a span carries for its whole group (obs/export.py
`worker_account`).  PERF.md §5's tables are this output.

    chiprun -- python3 scripts/worker_account.py --workload mem-uniform-closed \\
        --seed 7 --out chiprun_out/account.json

Arguments this script does not know go on to `benchmark/run.py`
(`--rehearse 0.002` walks the flow on the CPU and is no measurement).
`--root CHECKOUT` runs another checkout's benchmark and program (a
parent commit unpacked beside this one) and makes the account of ITS
ring with this tree's function, where that tree has none.
Standard output is `benchmark/run.py`'s own (the LAST line is the
cell's traced result); the account goes to `--out` as JSON and to
standard error as a table.  The window is the run's own: the harness's
`Run` is wrapped to keep what its `window()` returns, and nothing else
is patched (the recorder's ring is reset as the window opens and is
still in the process when the run returns).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent


def keep_the_window(cell_module) -> dict:
    """Have `cell.run_cell` build a `Run` that remembers its window;
    returns the dict it will be left in: `t0` / `t1`, the window's
    bounds on the recorder's clock (the arithmetic of
    `cell.traced_metrics`)."""
    kept = {}

    class Run(cell_module.Run):
        def window(self, events):
            from das_tpu import obs

            win = super().window(events)
            origin = obs.REC._t_origin - self.perf_minus_mono
            kept.update(t0=win["t0"] - origin, t1=win["t_end"] - origin)
            return win

    cell_module.Run = Run
    return kept


def account_functions(obs):
    """(`worker_account`, `account_text`) of the tree that ran; of this
    script's tree, loaded by file, where that one is older than they
    are (`--root`: the parent's side of a pair)."""
    if hasattr(obs, "worker_account"):
        return obs.worker_account, obs.account_text
    spec = importlib.util.spec_from_file_location(
        "_account_export", HERE / "das_tpu" / "obs" / "export.py")
    export = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(export)
    return export.worker_account, export.account_text


def dispatch_by_inflight(events, thread, t0, t1) -> dict:
    """The thread's `exec.dispatch` spans by the programs they found in
    flight (attr `inflight`): {inflight: {count, wall_s, cpu_s}}.  The
    same jitted call on the same arguments costs the host more wall,
    or more CPU, the deeper the device's queue?  This says."""
    out = {}
    for name, phase, start, dur, _tr, _g, _lane, th, attrs in events:
        if (name == "exec.dispatch" and phase == "X" and th == thread
                and t0 <= start <= t1 and "inflight" in attrs):
            row = out.setdefault(int(attrs["inflight"]),
                                 {"count": 0, "wall_s": 0.0, "cpu_s": 0.0})
            row["count"] += 1
            row["wall_s"] += dur
            row["cpu_s"] += attrs.get("cpu_ms", 0.0) / 1e3
    return dict(sorted(out.items()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--root", default=str(HERE))
    ap.add_argument("--out", default=None,
                    help="default: chiprun_out/account.<workload>.<seed>.json")
    args, passed_on = ap.parse_known_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)

    from benchmark import run as bench_run
    from benchmark.harness import cell

    window = keep_the_window(cell)
    code = bench_run.main([
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", "1", *passed_on,
    ])
    rehearsed = "--rehearse" in passed_on and code == cell.EXIT_NO_ACCELERATOR
    if (code != 0 and not rehearsed) or not window:
        return code or 1
    from das_tpu import obs
    from das_tpu.obs import metrics

    worker_account, account_text = account_functions(obs)
    events = obs.events()
    t0, t1 = window["t0"], window["t1"]
    account = worker_account(events, t0=t0, t1=t1)
    answered = account["instants"].get("serve.answer", 0)
    account.update(
        workload=args.workload, seed=args.seed, window_s=args.seconds,
        root=root, answered=answered,
        dispatch_by_inflight=dispatch_by_inflight(
            events, account["thread"], t0, t1),
        counters={name: c.value for name, c in metrics.COUNTERS.items()
                  if c.value},
    )
    out = args.out or str(
        HERE / "chiprun_out" / f"account.{args.workload}.{args.seed}.json")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as fh:
        json.dump(account, fh, indent=1)
    print(f"window [{t0:.3f}, {t1:.3f}] s of the ring, {root}",
          file=sys.stderr)
    print(account_text(account, per=answered or None), file=sys.stderr)
    for depth, row in account["dispatch_by_inflight"].items():
        print(f"exec.dispatch at inflight {depth:>3}: {row['count']:>6} "
              f"programs, {row['wall_s'] * 1e3 / row['count']:.3f} ms wall, "
              f"{row['cpu_s'] * 1e3 / row['count']:.3f} ms CPU each",
              file=sys.stderr)
    print(f"worker_account: wrote {out}", file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main())
