"""Cost model for whole-plan pricing (search.py consumes this).

Each candidate join step is priced as BYTES MOVED:

  * the estimated materialized output — rows × int32 row width — which
    TrieJax identifies as the term that dominates real join cost
    (intermediate blow-up, not per-tuple CPU);
  * a footprint of the step at the capacity the estimate implies: both
    tables with their sort and offset vectors, plus the output window
    in one block or in chunks, against an 8 MiB working set; a step
    whose tables alone pass it, or whose window needs more than 256
    chunks, pays a penalty factor — a sort-merge over tables that size
    materializes full sort/offset vectors in HBM.

The footprint arithmetic and its constants are those of the byte model
that planned the Pallas join kernel's VMEM layout until PR 31 (the
kernels are gone; `git log -- das_tpu/kernels/budget.py`).  They stay
because they decide every join order the chip runs today and
tests/test_plan_identity.py pins those orders; whether an 8 MiB step
function is the right price of a lowered XLA join on a v5e has never
been measured (ROADMAP Queue 3).

The model is deliberately coarse — it must only ORDER plans correctly,
not predict milliseconds — and every constant is a power of two so unit
tests can pin exact costs.
"""

from __future__ import annotations

#: int32 columns everywhere
ROW_BYTES = 4

#: headroom multiplier between an estimated row count and the capacity
#: the plan seeds for it: one doubling absorbs the estimator's
#: uniformity error on mildly skewed data while keeping the buffers an
#: order of magnitude under the blind initial_result_capacity seed for
#: serving-shaped queries
CAP_MARGIN = 2

#: pricing penalty for a step whose footprint passes every layout
#: below: the sort-merge pays full-table sorts and scatter
#: materialization in HBM
LOWERED_PENALTY = 4

#: flat per-stage charge (bytes-equivalent): every extra stage is more
#: traced program, more retry surface, and one more stats slot — breaks
#: cost ties toward shorter chains
STAGE_OVERHEAD = 1 << 12

#: the working set one join step is priced against
STEP_BUDGET = 8 * 1024 * 1024

#: a chunked output window streams at most this fraction of what the
#: resident tables leave of the budget
_BLOCK_FRACTION = 4

#: chunk granularity, floor, and the most chunks one step may take
LANE_ROWS = 128
MIN_CHUNK_ROWS = 1024
MAX_GRID_STEPS = 256


def pow2_at_least(n: int, lo: int = 64) -> int:
    c = lo
    while c < n:
        c *= 2
    return c


def cap_for(est_rows: float, max_capacity: int, exact: bool = False) -> int:
    """Initial capacity for an estimated intermediate: margin, power of
    two, clamped to the configured ceiling (an over-clamped cap just
    re-enters the existing overflow-retry ladder).  `exact` drops the
    margin — a degree-product figure is a hard bound on what the
    overflow stats can report, so padding past its power-of-two rung
    only buys bigger buffers."""
    want = int(est_rows) + 1 if exact else int(est_rows * CAP_MARGIN) + 1
    return min(pow2_at_least(max(64, want)), max(int(max_capacity), 64))


def term_cost(rows: int, width: int) -> float:
    """Materializing one probed term table."""
    return float(rows) * (width or 1) * ROW_BYTES + STAGE_OVERHEAD


def join_step_cost(
    left_rows: float,
    left_width: int,
    right_rows: float,
    right_width: int,
    n_pairs: int,
    cap_rows: float,
    out_width: int,
    max_capacity: int,
) -> float:
    """Price one binary join: the footprint of the step at the capacity
    the estimate implies, plus the estimated materialized window, with
    the penalty when the tables or the window pass every layout.
    `cap_rows` is the capacity-relevant row estimate (the rows of the
    join; for a join into a whole-type term see stats.pair_join_rows),
    i.e. the buffer the step actually writes.  The footprint holds both
    tables whole with their sort vectors: that is also what the
    verified join of two or more shared variables pays (ops/join.py
    _pair_join_impl sorts the whole right table with the left rows),
    however few rows it keeps."""
    cap = cap_for(cap_rows, max_capacity)
    stage, lowered = _join_footprint(
        int(min(left_rows, 2**31 - 1)), max(left_width, 1),
        int(min(right_rows, 2**31 - 1)), max(right_width, 1),
        max(out_width, 1), cap,
    )
    if lowered:
        stage *= LOWERED_PENALTY
    return stage + cap_rows * out_width * ROW_BYTES + STAGE_OVERHEAD


def _join_footprint(
    n_left: int, k_left: int, n_right: int, k_right: int,
    k_out: int, capacity: int,
):
    """(bytes, lowered) of one sort-merge step.  Resident: both tables
    with their masks, keys and sort/offset vectors.  Per output row:
    the pair gathers and the emitted row.  The window is held whole
    when everything fits the budget, else streamed in lane-aligned
    chunks sized against what the resident set leaves."""
    resident = n_left * (4 * k_left + 28) + n_right * (4 * k_right + 24)
    per_row = 4 * k_out + 4 * k_left + 4 * k_right + 16
    if resident + per_row * capacity <= STEP_BUDGET:
        return float(resident + per_row * capacity), False
    if resident > STEP_BUDGET:
        return float(resident + per_row * capacity), True
    chunk = (STEP_BUDGET - resident) // _BLOCK_FRACTION // per_row
    chunk = max(chunk // LANE_ROWS * LANE_ROWS, MIN_CHUNK_ROWS)
    chunk = min(chunk, -(-max(capacity, 1) // LANE_ROWS) * LANE_ROWS)
    lowered = (
        resident + per_row * chunk > STEP_BUDGET
        or -(-capacity // chunk) > MAX_GRID_STEPS
    )
    return float(resident + per_row * chunk), lowered
