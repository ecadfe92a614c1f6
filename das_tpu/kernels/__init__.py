"""The dispatch counters' old address.

The Pallas kernels that lived here were deleted in PR 31 (the v5e's
compiler refused all four; `git log -- das_tpu/kernels/` and
ARCHITECTURE.md §9 keep the story).  The counters moved to
das_tpu/ops/counters.py; this module stays only because
benchmark/harness/cell.py reads `kernels.DISPATCH_COUNTS`, and goes
when a benchmark PR points that at das_tpu.ops.counters (ROADMAP S0).
"""

from das_tpu.ops.counters import (  # noqa: F401
    DISPATCH_COUNTS,
    record_dispatch,
    reset_dispatch_counts,
)
