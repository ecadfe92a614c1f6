"""AOT compiles for a DESCRIBED TPU v5e: the staged join at the largest
capacity the config allows, and the commit's programs.  One of three
files (tests/test_tpu_compile.py says why, and what a run under several
workers needs).
"""

import jax.numpy as jnp
import pytest

from das_tpu.core.config import DasConfig
from das_tpu.storage.delta import delta_class
from tests.described_v5e import (  # noqa: F401  (fixtures by name)
    SMOKE_ARITY2_CAPACITY,
    _shape,
    _table,
    compile_for_chip,
    no_persistent_cache,
    one_chip,
    topo,
)

#: cell 2 of the benchmark (`wal-mixed95-closed`, FlyBase shape x 0.1):
#: the arity-2 bucket's capacity there
CELL2_ARITY2_CAPACITY = 2_961_251


# -- the lowered route: the one that must compile -------------------------


def test_lowered_join_at_max_capacity(compile_for_chip):
    """The pair-expansion join at the largest capacity class the config
    allows (the scoped-vmem-sensitive int64 cumsum scales with the LEFT
    table, the cummax with the output capacity — the r03 failure mode,
    das_tpu/ops/join.py)."""
    from das_tpu.ops.join import _join_tables_impl

    cap = int(DasConfig().max_result_capacity)
    lv, lm = _table(1 << 16, 3)
    rv, rm = _table(1 << 20, 2)

    def f(lv, lm, rv, rm):
        return _join_tables_impl(lv, lm, rv, rm, ((0, 0),), (1,), cap)

    compile_for_chip(f, lv, lm, rv, rm)


@pytest.mark.parametrize("cap,dcap,key_dtype", [
    (SMOKE_ARITY2_CAPACITY, delta_class(10), jnp.int64),  # the smoke's
    (CELL2_ARITY2_CAPACITY, 64, jnp.int64),    # cell 2: 5 of a commit's 8
    (CELL2_ARITY2_CAPACITY, 64, jnp.int32),    # cell 2: the other 3
    (CELL2_ARITY2_CAPACITY, 65536, jnp.int64),  # the widest delta class
    (CELL2_ARITY2_CAPACITY, 65536, jnp.int32),
])
def test_commit_merge_programs(compile_for_chip, cap, dcap, key_dtype):
    """The fixed-shape sorted-index merge of one delta class into the
    capacity-padded base (storage/tensor_db.py).  The merge builds every
    slot by reading: the compiled program holds no scatter at any delta
    class (a whole-table scatter was 1.27 s of device time per commit,
    PERF.md PR 27)."""
    from das_tpu.storage.tensor_db import _merge_padded

    merge = compile_for_chip(
        _merge_padded,
        _shape((cap,), key_dtype), _shape((cap,), jnp.int32),
        _shape((dcap,), key_dtype), _shape((dcap,), jnp.int32),
    )
    assert "scatter" not in merge.as_text()


def test_commit_insert_program(compile_for_chip):
    """The commit's row-block insert at a traced offset."""
    from das_tpu.storage.tensor_db import _insert_rows

    cap, dcap = SMOKE_ARITY2_CAPACITY, delta_class(10)
    compile_for_chip(
        _insert_rows,
        _shape((cap, 2), jnp.int32), _shape((dcap, 2), jnp.int32),
        _shape((), jnp.int32),
    )
