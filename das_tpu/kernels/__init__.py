"""Pallas fused query kernels (probe→gather→join) and their routing.

The round-5 VERDICT's depth item: the query pipeline's hot ops were all
generic XLA primitives, and each conjunctive term still lowered to a
chain of separate ops (`searchsorted` ×2, clip, gather, mask, then the
join's sort/searchsorted cascade), every stage round-tripping its
cap-sized intermediates through HBM.  This package fuses the two hot
chains into single Pallas kernels (TrieJax, arXiv:1905.08021; tensor-
runtime query processing, arXiv:2203.01877):

  * kernels/probe.py — Kernel 1: posting-key binary search + permutation
    window gather + target-column gather + positional verification +
    term-table emit, one VMEM-resident pass (replaces
    ops/posting.py:range_probe → verify_positions →
    ops/join.py:build_term_table);
  * kernels/join.py  — Kernel 2: the hash-join inner loop — sort-probe of
    the left key column against the right + pair materialization under a
    static capacity (replaces ops/join.py:_join_tables_impl and its
    posting-index variant _index_join_impl) — plus the anti-join
    membership kernel (replaces _anti_join_impl, ROUTE_COUNTS
    `anti_kernel`).

Eligibility and layout come from the BYTES planner (kernels/budget.py):
per-stage VMEM byte models pick single-block → grid-chunked → lowered
against a configurable budget (env DAS_TPU_VMEM_BUDGET), re-derived per
capacity-retry round.  The grid-chunked layouts (this PR) stream the
capacity window in fixed-row chunks, so shapes past the old
single-block row bound (2^18 — exactly the FlyBase-scale whole-table
terms) stay on the kernel route instead of falling back to the lowered
op chains.

Routing: `DasConfig.use_pallas_kernels` ("auto" | "on" | "off", env
override DAS_TPU_PALLAS).  "auto" = the LOWERED XLA route on every
platform: the chip's compiler (Mosaic, v5e, JAX 0.9.0) refuses every
kernel here today — join / anti-join / multiway with
`NotImplementedError: 64-bit types are not supported`, the probe with a
`RecursionError` under `pallas_call_tpu_lowering_rule` reached from
common.unrolled_search — and the lowered route is the one that compiles
(tests/test_tpu_compile.py pins each verdict; the PR that makes a kernel
Mosaic-clean flips its case there and `auto` here together, ROADMAP
"Mosaic-clean kernels").  "on" on a TPU issues the real `pl.pallas_call`
and RAISES WHAT THE COMPILER RAISES — it never discharges, never
interprets and never gives way to the lowered route.  "on" off-TPU
executes the SAME kernel bodies in interpret mode — by direct
ref-discharge to ordinary XLA ops (kernels/common.py run_kernel /
run_grid_kernel; DAS_TPU_PALLAS_INTERPRET=1 forces the full Pallas
interpreter) — answer-identical and tier-1-testable under
JAX_PLATFORMS=cpu (the differential suites in tests/test_zkernels.py and
tests/test_ztiled.py and the bench A/Bs all run that way).  Interpret
mode is a CPU-test facility only: a correctness vehicle, not a fast
path, and never taken when the platform is `tpu`.  The
sharded mesh programs route their shard-LOCAL probe/join bodies through
the same kernels (parallel/fused_sharded.py, ShardedPlanSig.use_kernels;
collectives stay lowered), and the vmapped count-batch groups route
through FusedPlanSig.use_kernels (query/fused.py count_batch) — see
ARCHITECTURE.md §9.
"""

from __future__ import annotations

import os
from functools import lru_cache

from das_tpu.ops.counters import DISPATCH_KEYS

__all__ = [
    "DISPATCH_COUNTS",
    "anti_join",
    "anti_join_impl",
    "budget",
    "enabled",
    "index_join_impl",
    "interpret_mode",
    "join_tables",
    "join_tables_impl",
    "multiway_join_impl",
    "probe_term_table",
    "probe_term_table_impl",
    "record_dispatch",
    "reset_dispatch_counts",
    "route_label",
]

#: host-side launches of compiled device programs, by path.  "lowered" =
#: one generic jitted op (ops/posting.py, ops/join.py wrappers), "kernel"
#: = one fused Pallas call, "fused" = one whole-plan single-dispatch
#: program (query/fused.py), "sharded" = one whole-plan shard_map mesh
#: program (parallel/fused_sharded.py), "count" = one vmapped count-batch
#: group program (query/fused.py count_batch); the *_kernel variants
#: count the subset whose bodies routed through the Pallas kernels, and
#: the *_tiled variants the further subset whose planner verdict was the
#: GRID-CHUNKED layout (kernels/budget.py) — so a byte-model regression
#: that silently re-routes eligible large shapes to the lowered chains
#: (or quietly de-tiles them) breaks a pinned count, not just a perf
#: number.  The dispatch-count regression tests pin the per-query totals
#: so a refactor can't silently re-fragment the pipeline.  Keys are
#: DECLARED in das_tpu/ops/counters.py — the one registry daslint rule
#: DL004 pins every counting literal against — and the dict is built
#: from it so dict and registry cannot drift.
DISPATCH_COUNTS = {k: 0 for k in DISPATCH_KEYS}


def record_dispatch(kind: str, n: int = 1) -> None:
    DISPATCH_COUNTS[kind] = DISPATCH_COUNTS.get(kind, 0) + n
    from das_tpu import obs

    if obs.enabled():
        # the obs metric layer's one aggregate dispatch tick — every
        # device-program enqueue funnels through here, so the Prometheus
        # surface gets a total without a counter per DISPATCH_KEYS route
        obs.counter("exec.dispatches").inc(n)


def reset_dispatch_counts() -> None:
    for k in DISPATCH_COUNTS:
        DISPATCH_COUNTS[k] = 0


@lru_cache(maxsize=1)
def _platform() -> str:
    import jax

    return jax.devices()[0].platform


def interpret_mode() -> bool:
    """True off-TPU: the kernel bodies discharge to plain XLA ops — same
    answers, no Mosaic compile (kernels/common.py run_kernel).  A
    CPU-test facility: on a TPU it is False, and a kernel launch is the
    real `pl.pallas_call`, which raises whatever Mosaic raises."""
    return _platform() != "tpu"


def enabled(config=None) -> bool:
    """Resolve kernel routing.  Env DAS_TPU_PALLAS beats the config so a
    deployment (or a bench A/B) can flip the path without code changes."""
    mode = os.environ.get("DAS_TPU_PALLAS")
    if mode is None and config is not None:
        mode = getattr(config, "use_pallas_kernels", "auto")
    mode = str("auto" if mode is None else mode).lower()
    # auto: the lowered route everywhere, until a kernel passes the
    # Mosaic compile (see module docstring; tests/test_tpu_compile.py
    # pins today's verdicts)
    return mode in ("on", "1", "true")


def route_label(config=None) -> str:
    """Bench/telemetry label for the active kernel route."""
    if not enabled(config):
        return "off"
    return "pallas-interpret" if interpret_mode() else "pallas"


# -- jitted single-dispatch wrappers (staged-path entry points) -----------
#
# The *_impl functions trace INSIDE a caller's program (query/fused.py
# build_fused) and are not counted; these wrappers are the staged
# pipeline's per-stage launches, so each counts exactly one dispatch
# ("kernel", plus "kernel_tiled" when the planner picked the
# grid-chunked layout for the shape — recomputed here from the same
# byte model the traced body consults, so counter and program agree).


def probe_term_table(
    sorted_keys, perm, targets, probe_key, fixed_vals, capacity: int,
    *, var_cols, eq_pairs, extra_fixed,
):
    """One fused probe→gather→term-table dispatch.  Returns
    (vals[cap, k] int32, mask[cap] bool, range_count) device arrays."""
    from das_tpu.kernels.probe import probe_term_table_jit

    record_dispatch("kernel")
    if budget.probe_plan(
        sorted_keys.shape[0], targets.shape[0], targets.shape[1],
        len(var_cols), capacity,
    ).tiled:
        record_dispatch("kernel_tiled")
    return probe_term_table_jit(
        sorted_keys, perm, targets, probe_key, fixed_vals,
        capacity=capacity, var_cols=tuple(var_cols),
        eq_pairs=tuple(eq_pairs), extra_fixed=tuple(extra_fixed),
        interpret=interpret_mode(), vmem_budget=budget.vmem_budget(),
    )


def join_tables(
    left_vals, left_valid, right_vals, right_valid,
    pairs, right_extra, capacity: int,
):
    """One fused equi-join dispatch (pair materialization under capacity).
    Returns (out_vals, out_valid bool, total int64) device arrays."""
    from das_tpu.kernels.join import join_tables_jit

    record_dispatch("kernel")
    if budget.join_plan(
        left_vals.shape[0], left_vals.shape[1],
        right_vals.shape[0], right_vals.shape[1],
        len(pairs), left_vals.shape[1] + len(right_extra), capacity,
    ).tiled:
        record_dispatch("kernel_tiled")
    return join_tables_jit(
        left_vals, left_valid, right_vals, right_valid,
        pairs=tuple(pairs), right_extra=tuple(right_extra),
        capacity=capacity, interpret=interpret_mode(),
        vmem_budget=budget.vmem_budget(),
    )


def anti_join(left_vals, left_valid, right_vals, right_valid, pairs):
    """One fused anti-join dispatch (negation membership filter).
    Returns the filtered left validity mask (bool device array)."""
    from das_tpu.kernels.join import anti_join_jit

    record_dispatch("kernel")
    return anti_join_jit(
        left_vals, left_valid, right_vals, right_valid,
        pairs=tuple(pairs), interpret=interpret_mode(),
    )


# imported LAST: budget's lazy helpers import back from this package at
# call time (interpret_mode), and probe/join import budget at module load
from das_tpu.kernels import budget  # noqa: E402
from das_tpu.kernels.probe import probe_term_table_impl  # noqa: E402
from das_tpu.kernels.join import (  # noqa: E402
    anti_join_impl,
    index_join_impl,
    join_tables_impl,
)
from das_tpu.kernels.multiway import multiway_join_impl  # noqa: E402
