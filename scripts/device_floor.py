#!/usr/bin/env python
"""Attribute the ~0.3 ms single-query device floor (VERDICT r04 item 8).

The headline `value` is the WIDTH SLOPE of the sequential fori_loop count
program — per-iteration device cost with dispatch/transport cancelled.
This experiment separates the two candidate attributions:

  * capacity-proportional work — the loop body probes/joins over
    capacity-PADDED buffers, so per-query cost should track KB size
    (probe capacity classes), shrinking on smaller stores;
  * fixed per-iteration floor — while-loop iteration overhead + fixed
    kernel shapes, flat across KB sizes.

Method: the same grounded 3-clause query family on bio KBs of increasing
size; per-query loop slope + the dispatch intercept (t1 - w1*slope: the
fixed cost of ONE dispatch+fetch) at each size, plus the learned probe capacities for context.

Run on the TPU host:  python scripts/device_floor.py
Emits one JSON line per KB size and a merged final line.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import das_tpu  # noqa: F401
import jax


def main() -> int:
    import bench
    from das_tpu.core.config import DasConfig
    from das_tpu.models.bio import build_bio_atomspace
    from das_tpu.query import compiler
    from das_tpu.query.fused import get_executor
    from das_tpu.storage.tensor_db import TensorDB

    sizes = [
        ("14k", dict(n_genes=2_000, n_processes=200, members_per_gene=5,
                     n_interactions=1_500, n_evaluations=500)),
        ("140k", dict(n_genes=20_000, n_processes=2_000, members_per_gene=5,
                      n_interactions=15_000, n_evaluations=5_000)),
        ("1.4M", dict(n_genes=200_000, n_processes=20_000,
                      members_per_gene=5, n_interactions=150_000,
                      n_evaluations=50_000)),
    ]
    rows = []
    for label, cfg in sizes:
        data, _, _ = build_bio_atomspace(**cfg)
        nodes, links = data.count_atoms()
        db = TensorDB(data, DasConfig(initial_result_capacity=1 << 16))
        genes = db.get_all_nodes("Gene", names=True)
        plan_cache = {}

        def plans_for(w):
            if w not in plan_cache:
                plan_cache[w] = [
                    compiler.plan_query(db, bench.grounded_query(g))
                    for g in genes[:w]
                ]
            return plan_cache[w]

        ex = get_executor(db)
        w1, w2 = 16, 128
        run1, _ = ex.build_count_loop(plans_for(w1))
        run2, _ = ex.build_count_loop(plans_for(w2))
        t1 = bench._best_of(run1, 5)
        t2 = bench._best_of(run2, 5)
        slope = (t2 - t1) / (w2 - w1)
        if slope <= 0:  # clock noise swamped the width delta (bench.py:173)
            slope = t2 / w2
        slope_ms = slope * 1e3
        intercept_ms = max(t1 * 1e3 - w1 * slope_ms, 0.0)
        row = {
            "kb": label,
            "kb_links": links,
            "per_query_ms": round(slope_ms, 4),
            "dispatch_intercept_ms": round(intercept_ms, 2),
            "w1_s": round(t1, 4),
            "w2_s": round(t2, 4),
            "platform": jax.devices()[0].platform,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
        # drop EVERYTHING holding the old KB (the closure cells and the
        # compiled loop executables pin db/genes) before the next build
        del db, data, ex, plan_cache, plans_for, run1, run2, genes
        import gc

        gc.collect()

    flat = rows[-1]["per_query_ms"] / max(rows[0]["per_query_ms"], 1e-9)
    merged = {
        "table": rows,
        # >3x growth across 100x KB size = capacity-proportional work;
        # <1.5x = fixed per-iteration floor
        "per_query_growth_14k_to_1p4M": round(flat, 2),
    }
    print(json.dumps(merged), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
