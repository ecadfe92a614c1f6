"""The posting-index join's expansion (PR 48): ranges -> output rows.

`ops/join.py _expand_index_ranges` reads a left row through the slot's
owner, by static shape: from `PACKED_EXPAND_MIN_SLOTS` slots on ONCE,
as one packed int32 row `[lo - prev, left_vals...]`, a slot valid where
`j < total`, the slot arithmetic 32-bit under int64 offsets; under it
four times on 64-bit slots.  Every case here runs BOTH ways (the
fixture `reads` forces the packed reads at these small shapes) and is
held against plain numpy (`np.repeat` of the left rows by `cnt`,
positions `lo + arange`):

  * rows with `cnt == 0` first, last and in runs; invalid left rows
    (the precondition: `cnt == 0` there); `total == 0`, `== capacity`,
    `> capacity` (the first `capacity` rows, `total` exact);
  * first slots past 2^31 and past 2^32 for rows that own no slot (the
    int32 base wraps, one of them INTO the buffer's range, and no slot
    reads it);
  * 1, 2 and 4 left columns; no `right_extra`; under `jax.vmap`;
  * the whole `whole_type_join` against `tests/test_ops_oracle.py`'s
    brute force at a shape that takes the slice search;
  * the rule itself, by what each side of it reads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from das_tpu.ops import join as join_ops
from tests.test_ops_oracle import _index_join_oracle, _posting, _rows

N_KEYS = 600


def _store(rng, n=N_KEYS):
    """`perm` and the arity's targets behind an index of `n` positions."""
    perm = rng.permutation(n).astype(np.int32)
    targets = rng.integers(0, 10_000, (n, 2)).astype(np.int32)
    return perm, targets


def _reference(lv, lo, cnt, perm, targets, right_var_cols, right_extra,
               capacity):
    """Left row i, `cnt[i]` times over, beside the store's rows at index
    positions `lo[i], lo[i] + 1, ...`; the first `capacity` of them."""
    cnt = cnt.astype(object)            # Python ints: sums pass 2^63 safely
    prev = np.cumsum(cnt) - cnt
    total = int(cnt.sum())
    take = np.array([min(max(capacity - p, 0), c)
                     for p, c in zip(prev, cnt)], dtype=np.int64)
    rows = np.repeat(np.arange(len(cnt)), take)
    within = np.arange(len(rows)) - prev[rows].astype(np.int64)
    right = targets[perm[lo[rows] + within]]
    cols = [lv[rows]] + (
        [right[:, [right_var_cols[rc] for rc in right_extra]]]
        if right_extra else [])
    vals = np.zeros((capacity, lv.shape[1] + len(right_extra)), np.int32)
    vals[:len(rows)] = np.concatenate(cols, axis=1)
    valid = np.arange(capacity) < min(total, capacity)
    assert len(rows) == valid.sum()
    return vals, valid, total


@pytest.fixture(params=["packed", "four_reads"])
def reads(request, monkeypatch):
    """Every case on both sides of the rule: these shapes sit under it,
    so the packed reads are forced."""
    if request.param == "packed":
        monkeypatch.setattr(join_ops, "PACKED_EXPAND_MIN_SLOTS", 0)
    return request.param


def _expansion(right_var_cols, right_extra, capacity):
    """The function under test behind the join's own prefix sum."""
    def fn(lv, lm, lo, cnt, perm, targets):
        offsets = join_ops._cumsum_i64(cnt)
        return join_ops._expand_index_ranges(
            lv, lm, lo, cnt, offsets, offsets[-1], perm, targets,
            right_var_cols, right_extra, capacity)
    return fn


def _expand(lv, lm, lo, cnt, perm, targets, right_var_cols, right_extra,
            capacity):
    return jax.jit(_expansion(right_var_cols, right_extra, capacity))(
        *(jnp.asarray(a) for a in (lv, lm, lo, cnt, perm, targets)))


def _ranges(rng, cnt, n_keys=N_KEYS):
    """A `lo` for every row such that `[lo, lo + cnt)` lies in the index
    (a count longer than the index starts at 0: only the slots inside
    the buffer are ever read)."""
    return np.array([rng.integers(0, max(n_keys - int(c), 0) + 1)
                     for c in cnt], np.int32)


def _counts(rng, n, zero_at=()):
    cnt = rng.integers(1, 9, n).astype(np.int64)
    cnt[list(zero_at)] = 0
    return cnt


#: name -> (cnt of the left rows, capacity or a rule, left columns,
#:          right_extra)
CASES = {
    "zero_counts_first": (lambda r: _counts(r, 40, range(0, 7)), 512, 2, (1,)),
    "zero_counts_last": (lambda r: _counts(r, 40, range(31, 40)), 512, 2, (1,)),
    "zero_counts_in_runs": (
        lambda r: _counts(r, 60, [*range(3, 9), 20, 21, *range(40, 52), 59]),
        512, 2, (1,)),
    "one_row_owns_every_slot": (
        lambda r: np.array([0, 0, 100, 0], np.int64), 100, 2, (1,)),
    "total_zero": (lambda r: np.zeros(25, np.int64), 64, 2, (1,)),
    "total_equals_capacity": (lambda r: _counts(r, 50), "exact", 2, (1,)),
    "total_over_capacity": (lambda r: _counts(r, 50), "half", 2, (1,)),
    "capacity_one": (lambda r: _counts(r, 10, [0]), 1, 2, (1,)),
    "one_left_row": (lambda r: np.array([5], np.int64), 16, 2, (1,)),
    "one_left_column": (lambda r: _counts(r, 30, [4, 5]), 256, 1, (1,)),
    "four_left_columns": (lambda r: _counts(r, 30, [4, 5]), 256, 4, (0, 1)),
    "no_right_extra": (lambda r: _counts(r, 30, [0, 29]), 256, 2, ()),
    "both_right_columns_swapped": (
        lambda r: _counts(r, 30, [7]), 256, 2, (1, 0)),
    # rows 2.. start past 2^31; the long row's slots inside the buffer
    # are read, nothing of the rows behind it
    "first_slot_past_2_31": (
        lambda r: np.array([3, 2**31 + 5, 0, 4, 7, 0, 2], np.int64),
        32, 2, (1,)),
    # row 2 starts at 2^32 + 2: cut to 32 bits that is slot 2, inside
    # the buffer, and row 2 still owns nothing
    "first_slot_wraps_into_the_buffer": (
        lambda r: np.array([3, 2**32 - 1, 5, 0, 6], np.int64), 32, 2, (1,)),
}


@pytest.mark.parametrize("name", CASES)
def test_the_expansion_is_np_repeat_of_the_left_rows(name, reads):
    make_cnt, capacity, k_left, right_extra = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    cnt = make_cnt(rng)
    n = len(cnt)
    if capacity == "exact":
        capacity = int(cnt.sum())
    elif capacity == "half":
        capacity = int(cnt.sum()) // 2
    perm, targets = _store(rng)
    lv = rng.integers(-5, 10_000, (n, k_left)).astype(np.int32)
    lo = _ranges(rng, cnt)
    lm = cnt > 0
    want_vals, want_valid, want_total = _reference(
        lv, lo, cnt, perm, targets, (0, 1), right_extra, capacity)
    vals, valid, total = _expand(
        lv, lm, lo, cnt, perm, targets, (0, 1), right_extra, capacity)
    assert total.dtype == jnp.int64 and int(total) == want_total
    assert vals.dtype == jnp.int32 and valid.dtype == jnp.bool_
    assert vals.shape == (capacity, k_left + len(right_extra))
    assert (np.asarray(valid) == want_valid).all()
    if (name, reads) == ("first_slot_wraps_into_the_buffer", "four_reads"):
        # the four reads scatter the 64-bit first slot as it is, and the
        # scatter cuts it to 32 bits: past 2^32 slots a row behind the
        # buffer is written into it.  Such a `total` is an overflow,
        # which the caller retries on the exact `total` above
        return
    # row for row, in order; zeros behind the last row
    assert (np.asarray(vals) == want_vals).all()


def test_an_invalid_left_row_has_no_count_and_no_slot(reads):
    """The precondition as `_index_join_impl` holds it: `cnt` is zero
    wherever `left_valid` is false, whatever range the row's value
    would find, so validity is `j < total` and the mask is not read."""
    rng = np.random.default_rng(48)
    n, capacity = 80, 512
    lm = rng.random(n) < 0.6
    lm[[0, 1, n - 1]] = False
    found = rng.integers(0, 9, n).astype(np.int64)   # hi - lo of every row
    cnt = np.where(lm, found, 0)
    perm, targets = _store(rng)
    lv = rng.integers(0, 10_000, (n, 2)).astype(np.int32)
    lo = _ranges(rng, found)
    want = _reference(lv, lo, cnt, perm, targets, (0, 1), (1,), capacity)
    got = _expand(lv, lm, lo, cnt, perm, targets, (0, 1), (1,), capacity)
    for g, w in zip(got, want):
        assert (np.asarray(g) == w).all()
    # every valid output row is a valid left row's
    valid_left = {tuple(r) for r in lv[lm]}
    assert all(tuple(r[:2]) in valid_left
               for r in np.asarray(got[0])[np.asarray(got[1])])


def test_lanes_expand_alone_under_vmap(reads):
    """`das_fused_group` runs the join under `vmap` over lanes: every
    lane its own ranges, counts and `total` (one empty, one over the
    capacity), the store unbatched."""
    rng = np.random.default_rng(4)
    lanes, n, capacity = 5, 24, 64
    perm, targets = _store(rng)
    cnt = rng.integers(0, 5, (lanes, n)).astype(np.int64)
    cnt[1] = 0
    cnt[2] = rng.integers(3, 9, n)
    assert cnt[2].sum() > capacity > cnt[0].sum()
    lo = np.stack([_ranges(rng, c) for c in cnt])
    lv = rng.integers(0, 10_000, (lanes, n, 2)).astype(np.int32)
    lm = cnt > 0

    vals, valid, total = jax.jit(jax.vmap(
        _expansion((0, 1), (1,), capacity), in_axes=(0, 0, 0, 0, None, None)))(
        *(jnp.asarray(a) for a in (lv, lm, lo, cnt, perm, targets)))
    for i in range(lanes):
        want_vals, want_valid, want_total = _reference(
            lv[i], lo[i], cnt[i], perm, targets, (0, 1), (1,), capacity)
        assert int(total[i]) == want_total
        assert (np.asarray(valid[i]) == want_valid).all()
        assert (np.asarray(vals[i]) == want_vals).all()


def test_the_rule_reads_a_left_row_once_from_its_slots_on():
    """`PACKED_EXPAND_MIN_SLOTS`, by the static `capacity`: at it the
    traced expansion holds three gathers (the packed row, `perm`,
    `targets`) and no 64-bit slot; one slot under it the six gathers it
    has always held, on 64-bit slots."""
    n, rule = 8, 64

    def traced(capacity):
        args = (
            jnp.zeros((n, 2), jnp.int32), jnp.ones((n,), bool),
            jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.int64),
            jnp.zeros((16,), jnp.int32), jnp.zeros((16, 2), jnp.int32))
        text = str(jax.make_jaxpr(_expansion((0, 1), (), capacity))(*args))
        return text.count(" gather["), f"i64[{capacity}]" in text

    with pytest.MonkeyPatch.context() as m:
        m.setattr(join_ops, "PACKED_EXPAND_MIN_SLOTS", rule)
        assert traced(rule) == (3, False)
        assert traced(rule - 1) == (6, True)
    # a lane of the grounded shapes sits under the rule as it is set
    # (2,048 slots, 65,536 up its ladder), the whole-store conjunction's
    # first join over it
    assert 65_536 < join_ops.PACKED_EXPAND_MIN_SLOTS <= 1 << 22


def test_a_capacity_past_32_bits_is_refused_where_it_is_traced():
    """`capacity` is static, under `core/config.py max_result_capacity`
    (2^24 by default, a setting): the slot arithmetic is 32-bit, so a
    buffer it cannot count raises and does not wrap."""
    z = jnp.zeros((1,), jnp.int64)
    with pytest.raises(ValueError, match="32-bit slot arithmetic"):
        jax.eval_shape(
            lambda: join_ops._expand_index_ranges(
                jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), bool),
                jnp.zeros((1,), jnp.int32), z, z, z[0],
                jnp.zeros((4,), jnp.int32), jnp.zeros((4, 2), jnp.int32),
                (0, 1), (1,), 2**31))


@pytest.mark.parametrize("capacity", ["ample", "half"])
def test_the_whole_join_at_a_slice_search_shape_is_the_oracles(
        capacity, reads):
    """`whole_type_join` on one variable, its ranges from the slice
    search (the key cap lowered to a CPU's size, as
    tests/test_index_slice_search.py does), against the brute force of
    tests/test_ops_oracle.py: the multiset of rows and the exact
    `total`; over the capacity, `capacity` true rows."""
    rng = np.random.default_rng(480)
    n_left, n_links, domain, t = 1_100, 1_000, 40, 1
    lv = rng.integers(0, domain, (n_left, 2)).astype(np.int32)
    lm = rng.random(n_left) < 0.85
    targets = rng.integers(0, domain, (n_links, 2)).astype(np.int32)
    type_id = rng.integers(0, 3, n_links).astype(np.int32)
    keys_sorted, perm = _posting(targets, type_id, 0)
    want, want_total = _index_join_oracle(
        lv, lm, targets, type_id, t, ((0, 0),), (0, 1), (1,))
    capacity = 1 << 14 if capacity == "ample" else want_total // 2
    with pytest.MonkeyPatch.context() as m:
        m.setattr(join_ops, "SORT_SEARCH_MAX_KEYS", 256)
        assert join_ops.index_search_method(
            n_left, n_links) == join_ops.SLICE_SEARCH
        vals, valid, total = jax.jit(
            lambda lv, lm, keys, perm, targets, type_id:
            join_ops.whole_type_join(
                lv, lm, (keys, perm, targets, type_id), np.int32(t),
                ((0, 0),), (0, 1), (1,), capacity))(
            *(jnp.asarray(a) for a in
              (lv, lm, keys_sorted, perm, targets, type_id)))
    got = _rows(vals, valid)
    assert int(total) == want_total > 0
    if want_total <= capacity:
        assert got == want
    else:
        assert int(np.asarray(valid).sum()) == capacity
        assert not got - want
