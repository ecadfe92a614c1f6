#!/usr/bin/env python
"""dump_trace.py — render a das_tpu obs trace as Perfetto-loadable
Chrome trace-event JSON (ISSUE 12 exporter).

Two modes:

  * demo (default): build a small bio KB, enable tracing AND the
    program ledger, run a 3-var conjunctive workload (plus grounded
    repeats for cache-hit events and one incremental commit for the
    invalidation event) through the serving coalescer, and write the
    resulting trace — the acceptance artifact: submit → drain → plan →
    dispatch → settle → answer spans with route/est-vs-actual
    attributes, one lane per tenant/worker, plus a "compile" lane with
    one prof.compile span per XLA compile the workload paid (ISSUE 14 —
    the per-query spans show WHERE first-contact latency went).

        JAX_PLATFORMS=cpu python scripts/dump_trace.py -o /tmp/das_trace.json

  * `--self`: no workload — dump whatever the CURRENT process recorder
    holds (importable `dump_current(path)` for embedding in services).

  * `--account`: also print where the coalescer's worker thread spent
    the traced time, per span name its own wall and CPU seconds
    (obs/export.py worker_account; `scripts/worker_account.py` makes
    the same table of a benchmark cell's window).

Open the output at https://ui.perfetto.dev or chrome://tracing.  With
DAS_TPU_TRACE_JAX=1 / DAS_TPU_TRACE_DIR the same run also captures a
jax.profiler device trace to correlate against (obs/jaxprof.py).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def dump_current(path: str) -> str:
    """Write the current process recorder's ring to `path`."""
    from das_tpu import obs

    return obs.dump_chrome_trace(obs.events(), path)


def _demo_workload(n_clients: int, scale: float):
    from das_tpu import obs
    from das_tpu.api.atomspace import (
        DistributedAtomSpace,
        QueryOutputFormat,
    )
    from das_tpu.core.config import DasConfig
    from das_tpu.models.bio import build_bio_atomspace
    from das_tpu.query.ast import And, Link, Node, Variable
    from das_tpu.service.coalesce import QueryCoalescer
    from das_tpu.service.server import _Tenant
    from das_tpu.storage.tensor_db import TensorDB

    obs.configure(enabled=True)
    obs.reset()
    # program ledger on (ISSUE 14): every XLA compile the workload pays
    # lands as a prof.compile span in a dedicated "compile" Perfetto
    # lane, next to the serving lanes it stalls
    obs.proflog.configure(enabled=True)
    obs.proflog.reset()
    cfg = DasConfig.from_env()
    obs.maybe_start_trace(cfg)

    data, genes, _procs = build_bio_atomspace(
        n_genes=max(64, int(1000 * scale)),
        n_processes=max(16, int(200 * scale)),
        members_per_gene=5,
        n_interactions=max(128, int(2000 * scale)),
    )
    db = TensorDB(data, cfg)
    das = DistributedAtomSpace(database_name="trace-demo", db=db)
    tenant = _Tenant("trace-demo", das)
    coal = QueryCoalescer()

    three_var = And([
        Link("Member", [Variable("V1"), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Interacts", [Variable("V1"), Variable("V2")], True),
    ])

    def grounded(g):
        name = das.get_node_name(g)
        return And([
            Link("Member", [Node("Gene", name), Variable("V3")], True),
            Link("Member", [Variable("V2"), Variable("V3")], True),
            Link("Interacts", [Node("Gene", name), Variable("V2")], True),
        ])

    # the 3-var acceptance query plus grounded per-client queries
    # (repeats exercise the cache-hit lifecycle arm)
    workload = [three_var] + [
        grounded(genes[i % 8]) for i in range(n_clients - 1)
    ]
    futs = [
        coal.submit(tenant, q, QueryOutputFormat.HANDLE) for q in workload
    ]
    for f in futs:
        f.result(timeout=600)
    # the same workload again: delta-versioned cache hits (zero-dispatch
    # answers) land as cache.hit events on the trace
    futs = [
        coal.submit(tenant, q, QueryOutputFormat.HANDLE) for q in workload
    ]
    for f in futs:
        f.result(timeout=600)
    # one incremental commit -> commit.delta + cache.invalidate events
    das.load_metta_text(
        '(: "GENE:TRACE" Gene)\n(: "GO:TRACE" BiologicalProcess)\n'
        '(Member "GENE:TRACE" "GO:TRACE")'
    )
    futs = [
        coal.submit(tenant, workload[1], QueryOutputFormat.HANDLE)
        for _ in range(2)
    ]
    for f in futs:
        f.result(timeout=600)
    time.sleep(0.1)  # let the worker's settle span land in the ring
    obs.maybe_stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-o", "--out", default="/tmp/das_trace.json")
    ap.add_argument(
        "--self", action="store_true", dest="self_only",
        help="dump the current recorder ring; run no demo workload",
    )
    ap.add_argument(
        "--account", action="store_true",
        help="print the worker thread's account: own wall / CPU "
             "seconds per span name",
    )
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--scale", type=float, default=0.1,
                    help="bio KB size factor (default 0.1)")
    args = ap.parse_args(argv)
    if not args.self_only:
        _demo_workload(args.clients, args.scale)
    path = dump_current(args.out)
    with open(path) as f:
        n = len(json.load(f)["traceEvents"])
    print(f"wrote {n} trace events to {path} — open in ui.perfetto.dev")
    if args.account:
        from das_tpu import obs

        account = obs.worker_account(obs.events())
        print(obs.account_text(
            account, per=account["instants"].get("serve.answer")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
