#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Earlier lines of stdout are JSON logs (store, warm-up, every number
compared beside its limit, counters); the LAST line is the result:
`correct`, `attempted`, `failed`, `metrics`, `device` (+ `breakdown`
when traced) and, last, `compared`: each number compared with its limit,
which are also the last lines of stderr.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits non-zero.

    --rehearse SCALE   run every phase at SCALE on whatever platform JAX
                       has, print the result on stderr, then refuse as
                       above (a CPU rehearsal of the control flow)
"""

from __future__ import annotations

import time

_T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, default=None, metavar="SCALE")
    args = ap.parse_args(argv)

    from benchmark.harness import cell
    from benchmark.harness.spec import SpecError

    try:
        result, code = cell.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            _T_PROCESS_START, require_chip=args.rehearse is None,
            scale=args.rehearse)
    except SpecError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    except ImportError as exc:
        # a directory that holds only the benchmark: nothing to measure
        print(f"benchmark: the program is not here: {exc}", file=sys.stderr)
        return 2
    if result is None:
        return code
    # the last lines on standard error: each number compared, its limit
    for name, item in result["compared"].items():
        print(f"compared {name}: {item['value']} (limit {item['limit']})",
              file=sys.stderr)
    if args.rehearse is not None:
        print(json.dumps(result), file=sys.stderr)
        print("benchmark: rehearsal done; refusing to print a result "
              "(not a measurement)", file=sys.stderr)
        return cell.EXIT_NO_ACCELERATOR
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
