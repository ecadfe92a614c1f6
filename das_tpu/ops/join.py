"""Vectorized binding-table joins (device kernels).

The reference joins variable assignments with a quadratic Python nested
loop (pattern_matcher.py:732-738).  Here a binding set is a padded int32
matrix — one row per candidate assignment, one column per variable (values
are global atom row ids) — and conjunction is a sort-merge equi-join:

  1. mix the shared columns of each side into a 64-bit key,
  2. argsort the right side, `searchsorted` the left keys into it,
  3. expand the [lo, hi) ranges positionally into a fixed-capacity pair
     vector (exact pair index arithmetic via cumulative offsets),
  4. verify the shared columns exactly (the mix is only a route, never
     trusted), and gather the output columns.

Everything is static-shape; `total` reports the exact pair count so the
host can retry on capacity overflow.
"""

from __future__ import annotations

import contextlib
import threading
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from das_tpu.obs.registry import (
    INDEX_EXPAND_SCOPE,
    INDEX_JOIN_SCOPE,
    INDEX_SEARCH_SCOPE,
    PAIR_JOIN_SCOPE,
)

_SENTINEL_L = jnp.int64(2**63 - 1)
_SENTINEL_R = jnp.int64(2**63 - 2)


def _cumsum_i64(x):
    """Inclusive int64 prefix sum via `associative_scan` (log-depth shifted
    adds) instead of `jnp.cumsum`.

    On TPU, a 64-bit cumsum lowers to a variadic (u32, u32) reduce-window
    — s64 is emulated as u32 pairs — and inside a fused `fori_loop` count
    body that reduce-window's stack allocation overflows the v5e 16MB
    scoped-vmem budget (observed: "reduce-window ... (u32[4,128],
    u32[4,128]) ... 19.10M and limit 16.00M", the r03 driver record) even
    though the identical body compiles standalone.  associative_scan lowers to
    slice+add steps with no scoped scratch.  The summed arrays here are
    left-table row counts (≤ the term capacity), so the log-depth cost is
    noise."""
    if x.shape[0] <= 1:
        return x
    if x.shape[0] > ASSOC_SCAN_MAX_ROWS:
        return _cumsum_i64_by_carries(x)
    return jax.lax.associative_scan(jnp.add, x)


#: longest vector `_cumsum_i64` sums with the log-depth scan; a longer
#: one (the left side of a whole-store join: 10^6 rows) is summed in
#: two 32-bit passes, because the scan's twenty levels of 64-bit slices
#: are 80 s of compile for the chip at a million rows (PERF.md §6 PR 44)
ASSOC_SCAN_MAX_ROWS = 1 << 16


def _cumsum_i64_by_carries(x):
    """Inclusive int64 prefix sum of NON-NEGATIVE addends below 2^32
    (row counts) from two 32-bit cumsums, which lower to plain s32/u32
    reduce-windows: the low words summed modulo 2^32, and the number of
    times that sum wrapped so far (a wrap shows as a decrease, every
    addend being under 2^32) as the high word.  Exact."""
    low = _padded_scan(jnp.cumsum, x.astype(jnp.uint32), 0)
    wrapped = jnp.concatenate(
        [jnp.zeros((1,), dtype=bool), low[1:] < low[:-1]]
    )
    high = _padded_scan(jnp.cumsum, wrapped.astype(jnp.uint32), 0)
    return (high.astype(jnp.int64) << 32) | low.astype(jnp.int64)


#: lengths (over the first, up to the second) at which the chip's
#: compiler takes tens of seconds for ONE running sum, maximum or
#: minimum (a reduce-window over [n / 128, 128]), and the length such a
#: vector is padded to instead.  Compiles for a described v5e, one
#: `lax.cummax` of int32 (PERF.md §6 PR 47): 131,072 elements 4.5 s,
#: 262,144 7.5, 524,288 14, every length tried from 786,432 to
#: 2,883,584 27-50 s (a shard's 2,220,890 index keys: 34-37 s; the
#: 1,048,576 left rows of a mesh join, as u32 sums: 27-36 s each), then
#: 2,961,251 6 s, 4,194,304 7-11 s, 8,388,608 2-4 s: under some 23,000
#: rows of 128 the compiler unrolls, above it loops.  One such op is
#: most of a first request's budget under the analytic cells' statement
#: deadline, and a pass over 8 M elements costs the device a
#: millisecond or two more than one over 1-2 M.  The lower bound is
#: where one op still compiles in a quarter of a minute; programs
#: whose vectors lie outside the interval (the one-chip cell's: 524,288
#: left rows, 2,961,251 keys, 4,194,304 and 7,155,555 slots) are traced
#: exactly as before.
SLOW_SCAN_ROWS = (1 << 19, 23_000 * 128)
FAST_SCAN_ROWS = 1 << 23


def _padded_scan(scan, x, fill, **kwargs):
    """`scan(x, **kwargs)` (a forward running sum or maximum, or a
    REVERSE running minimum) with `x` padded AT ITS END by `fill`, the
    scan's identity, to FAST_SCAN_ROWS where its length falls in
    SLOW_SCAN_ROWS, and cut back: the first `len(x)` results are those
    of the unpadded scan either way (a forward scan never reads ahead,
    and a reverse one meets only identities before the last element).
    By static shape; any other length is scanned as it is."""
    n = x.shape[0]
    if not SLOW_SCAN_ROWS[0] < n <= SLOW_SCAN_ROWS[1]:
        return scan(x, **kwargs)
    pad = jnp.full((FAST_SCAN_ROWS - n,), fill, dtype=x.dtype)
    return scan(jnp.concatenate([x, pad]), **kwargs)[:n]


def _searchsorted_method(n_queries: int, n_keys: int) -> str:
    """Static per-shape choice of jnp.searchsorted lowering.  'sort' keeps
    MANY queries in the fast TPU sort unit (the scan default does a
    dependent-gather binary search per query — ~100ms at 10^5 queries),
    but it re-sorts the QUERY side together with the keys, which is
    catastrophic when the query side is small relative to a huge sorted
    table (e.g. a 64-row accumulated table joining into a 33M-row
    whole-table term at FlyBase scale: 'sort' pays a 33M-element sort per
    batch member, 'scan' pays 64 binary searches).  The cutover is
    relative: scan while queries are far fewer than keys.

    Inside a lane-batched program (lane_batched) a 'sort' against a key
    table of at most LANE_COMPARE_KEYS rows is 'compare_all' instead:
    the batched variadic sort is 23 s of such a program's 26 s compile
    for the chip (2,048 queries against a 16-row table, PERF.md §6 PR
    30) while the [queries, keys] compare compiles in 0.3 s, and a
    program first met while serving stalls every query behind it.

    Past SORT_SEARCH_MAX_KEYS keys the answer is 'scan' whatever the
    query side (the co-sort's compile: see the constant).  Asked by
    `_join_tables_impl`, `_anti_join_impl` and the verified join's
    expansion, whose keys are 64-bit mixes or running counts; the
    posting-index join asks `index_search_method`, which has one more
    answer for a large left side."""
    method = "sort" if _many_queries(n_queries, n_keys) else "scan"
    if n_keys > SORT_SEARCH_MAX_KEYS:
        # the co-sort of a million queries with a multi-million-row
        # key table is two minutes of compile for the chip (an int64
        # argsort and a scatter a side; 119 s for 1 M queries into
        # 8.9 M keys, PERF.md §6 PR 44), past any statement deadline
        # for the query that meets the program first; the scan's
        # dependent gathers compile in a second and cost ~3 x the
        # co-sort's device time there
        method = "scan"
    if (method == "sort" and n_keys <= LANE_COMPARE_KEYS
            and getattr(_LANES, "on", False)):
        return "compare_all"
    return method


def _many_queries(n_queries: int, n_keys: int) -> bool:
    """The relative cutover of `_searchsorted_method`: the query side is
    large enough against the keys to be worth more than a dependent
    gather a step and a query."""
    return n_queries > max(1024, n_keys // 16)


#: widest key table a 'sort' searchsorted co-sorts with its queries;
#: past it every search is a 'scan', and the posting-index join of a
#: LARGE left side (`index_search_method`) searches its type's slice
#: once, on 32-bit words.  A co-sort on 32-bit words was priced for that
#: join and stays out (compiles for a described v5e, 524,288 probes into
#: 2,961,251 keys, PERF.md §6 PR 45): the two scans compile in 4.0 s,
#: the slice search in 6.0 s, one unstable `lax.sort` of (word, tag)
#: over both sides plus the running counts in 21.6 s, 35.6 s with the
#: second sort that brings the ranges back to row order, on a first
#: request of 41.5 s that has to end inside 60 (the analytic cell's
#: statement deadline)
SORT_SEARCH_MAX_KEYS = 1 << 20

#: `index_search_method`'s third answer (`_slice_ranges`)
SLICE_SEARCH = "slice"

#: fan-out of the slice search's descent (`_search_words`): the words a
#: step reads as ONE row, and the most words its root holds (the level
#: every probe compares whole, with no gather).  Chosen on the chip, a
#: property of this compiler version like SLOW_SCAN_ROWS (PERF.md
#: section 6, PR 49; v5e, `_slice_ranges` alone, ms a call | compile s,
#: at cell 5's shapes, 524,288 probes into 2,961,251 keys, and a shard's
#: of cell 6, 1,048,576 into 2,220,890; the binary search it replaces:
#: 101.1 | 7.9 and 200.7 | 3.3):
#:   F = 8 (7 gathered levels)  17.2 | 26.2   41.0 | 14.5
#:   F = 16 (5)                 13.3 | 11.4   25.8 | 7.4
#:   F = 32 (4)                 11.7 | 4.7    24.5 | 2.8
#:   F = 64 (3)                 11.2 | 7.0    21.9 | 2.9
#:   F = 128 (3)                11.2 | 6.1    21.8 | 2.9
#:   F = 256 (2)                11.7 | 4.5    22.8 | 2.8
#:   F = 512 (2)                14.9 | 4.3    28.5 | 2.8
#:   F = 128, a root of 256 (2)  9.7 | 5.3    19.1 | 2.9
#:   F = 64, a root of 768 (2)  10.6 | 4.4    18.8 | 2.8
#: A gathered level costs 1.4 ms for 524,288 probes at any width up to
#: 128 words (2.7 ns a row: a row of 128 costs what ONE word of the
#: binary search cost a step, 7.2 ns, and less), so the widest row that
#: is still one tile row wins, and past 128 words a row costs by its
#: bytes.  The gathered rows are a temporary of `probes x 128 x 4`
#: bytes a level whatever F <= 128 is (a narrower row is padded to the
#: tile's 128 lanes): 268 MB in cell 5, 537 MB a chip in cell 6.  The
#: root of two rows saves the cells' third gathered level (181 and 136
#: separators: a compare of 256 words a probe is 0.1 ms).
SEARCH_FANOUT = 128
SEARCH_ROOT_WORDS = 256


def index_search_method(n_left: int, n_keys: int) -> str:
    """Static per-shape choice of the posting-index join's range lookup
    (`_index_join_impl` consults it, and query/fused.py to count the
    rows that took it): SLICE_SEARCH exactly where the left side is
    large against the index by `_searchsorted_method`'s own relative
    rule AND the key cap forces that rule's 'sort' down to 'scan' (the
    whole-store conjunction's first join: 524,288 left rows into a
    2,961,251-row index), otherwise `_searchsorted_method`'s answer, for
    the two 64-bit searches the join has always run.  A small left side
    (16-2,048 rows a lane into the same index: every grounded shape)
    keeps them: the slice search makes two passes over the whole index
    per program, which cost more than 2 x 23 steps of a few rows'
    gathers."""
    if n_keys > SORT_SEARCH_MAX_KEYS and _many_queries(n_left, n_keys):
        return SLICE_SEARCH
    return _searchsorted_method(n_left, n_keys)


#: fewest output slots at which the posting-index join's expansion
#: (`_expand_index_ranges`) reads a left row as ONE packed row, by
#: static shape.  The whole-store conjunction's first join holds
#: 4,194,304 slots and its expansion fell 250 -> 74 ms there; a lane of
#: the grounded shapes holds 64-2,048 (65,536 at the top of its
#: ladder), and compiled for the described v5e the 32-lane group
#: program of those reported 102-175 MB of temporaries with the packed
#: reads for 39 MB without (PERF.md section 6, PR 48; the guard is
#: tests/test_tpu_compile.py test_fused_group_at_cell1_shapes): under
#: the rule every grounded program stays what it was, letter for letter
PACKED_EXPAND_MIN_SLOTS = 1 << 20

#: widest key table a lane-batched 'sort' searchsorted lowers as
#: 'compare_all' (a [queries, keys] compare a lane)
LANE_COMPARE_KEYS = 256

_LANES = threading.local()


@contextlib.contextmanager
def lane_batched():
    """Held (by query/fused.py lanes_program) while the body of a
    lane-batched program is TRACED: the static lowering choices above
    see that every op here carries a lanes axis.  Per thread: another
    thread's trace of a lone program is untouched."""
    prev = getattr(_LANES, "on", False)
    _LANES.on = True
    try:
        yield
    finally:
        _LANES.on = prev


def _mix_columns(vals, cols: Tuple[int, ...], valid, sentinel):
    """64-bit mix of the selected int32 columns; invalid rows get a
    side-specific sentinel so they can never pair up."""
    # golden-ratio multiplier 0x9E3779B97F4A7C15 as a signed int64
    mult = jnp.int64(-7046029254386353131)
    acc = jnp.zeros(vals.shape[0], dtype=jnp.int64)
    for c in cols:
        acc = acc * mult + vals[:, c].astype(jnp.int64)
        acc = acc ^ (acc >> 29)
    return jnp.where(valid, acc, sentinel)


@partial(jax.jit, static_argnames=("pairs", "right_extra", "capacity"))
def _join_tables_jit(left_vals, left_valid, right_vals, right_valid,
                     pairs, right_extra, capacity):
    return _join_tables_impl(
        left_vals, left_valid, right_vals, right_valid, pairs, right_extra, capacity
    )


def join_tables(
    left_vals,
    left_valid,
    right_vals,
    right_valid,
    pairs: Tuple[Tuple[int, int], ...],
    right_extra: Tuple[int, ...],
    capacity: int,
):
    """Equi-join two binding tables.

    pairs       — (left_col, right_col) equality constraints (shared vars)
    right_extra — right columns appended after all left columns
    Returns (out_vals[capacity, kL+len(right_extra)], out_valid, total).
    With no shared columns this degenerates to the cross product.
    """
    from das_tpu.ops.counters import record_dispatch

    record_dispatch("lowered")
    return _join_tables_jit(
        left_vals, left_valid, right_vals, right_valid, pairs, right_extra, capacity
    )


@partial(jax.jit, static_argnames=("pairs",))
def _anti_join_jit(left_vals, left_valid, right_vals, right_valid, pairs):
    return _anti_join_impl(left_vals, left_valid, right_vals, right_valid, pairs)


def anti_join(left_vals, left_valid, right_vals, right_valid, pairs: Tuple[Tuple[int, int], ...]):
    """NOT-filtering: invalidate left rows whose shared-column projection
    matches any right row (the ordered-assignment `check_negation`
    semantics when the tabu variable set is a subset of the output's:
    tabu ⊆ assignment ⇒ excluded).  Uses the 64-bit mix as the match key;
    a false exclusion needs a full 64-bit collision (~2^-64 per pair) —
    documented engineering tolerance of the compiled path; the host
    algebra path is collision-free."""
    from das_tpu.ops.counters import record_dispatch

    record_dispatch("lowered")
    return _anti_join_jit(left_vals, left_valid, right_vals, right_valid, pairs)


def _anti_join_impl(left_vals, left_valid, right_vals, right_valid, pairs):
    """Un-jitted anti-join core (callable inside shard_map)."""
    lcols = tuple(lc for lc, _ in pairs)
    rcols = tuple(rc for _, rc in pairs)
    key_l = _mix_columns(left_vals, lcols, left_valid, _SENTINEL_L)
    key_r = _mix_columns(right_vals, rcols, right_valid, _SENTINEL_R)
    key_r_sorted = jnp.sort(key_r)
    method = _searchsorted_method(key_l.shape[0], key_r_sorted.shape[0])
    lo = jnp.searchsorted(key_r_sorted, key_l, side="left", method=method)
    hi = jnp.searchsorted(key_r_sorted, key_l, side="right", method=method)
    found = hi > lo
    return left_valid & ~found


@partial(jax.jit, static_argnames=("var_cols", "eq_pairs"))
def _build_term_table_jit(targets, local, mask, var_cols, eq_pairs):
    return _build_term_table_impl(targets, local, mask, var_cols, eq_pairs)


def build_term_table(targets, local, mask, var_cols: Tuple[int, ...], eq_pairs: Tuple[Tuple[int, int], ...]):
    """Project probed candidate links into a binding table: one column per
    variable (first occurrence position); `eq_pairs` enforces same-variable
    repeated positions."""
    from das_tpu.ops.counters import record_dispatch

    record_dispatch("lowered")
    return _build_term_table_jit(targets, local, mask, var_cols, eq_pairs)


def _build_term_table_impl(targets, local, mask, var_cols, eq_pairs):
    safe = jnp.clip(local, 0, targets.shape[0] - 1)
    rows = targets[safe]
    for p1, p2 in eq_pairs:
        mask = mask & (rows[:, p1] == rows[:, p2])
    vals = rows[:, jnp.array(var_cols, dtype=jnp.int32)]
    vals = jnp.where(mask[:, None], vals, jnp.int32(0))
    return vals, mask


def _join_tables_impl(left_vals, left_valid, right_vals, right_valid, pairs, right_extra, capacity):
    """Un-jitted join core (callable inside shard_map)."""
    lcols = tuple(lc for lc, _ in pairs)
    rcols = tuple(rc for _, rc in pairs)
    key_l = _mix_columns(left_vals, lcols, left_valid, _SENTINEL_L)
    key_r = _mix_columns(right_vals, rcols, right_valid, _SENTINEL_R)

    order = jnp.argsort(key_r)
    key_r_sorted = key_r[order]
    method = _searchsorted_method(key_l.shape[0], key_r_sorted.shape[0])
    lo = jnp.searchsorted(key_r_sorted, key_l, side="left", method=method).astype(jnp.int32)
    hi = jnp.searchsorted(key_r_sorted, key_l, side="right", method=method).astype(jnp.int32)
    # int64 totals: sum of per-row ranges can exceed 2^31 (cross-ish joins
    # of big tables), and a wrapped negative total would silently mask
    # every output row instead of triggering the overflow retry
    cnt = (hi - lo).astype(jnp.int64)
    offsets = _cumsum_i64(cnt)
    total = offsets[-1] if cnt.shape[0] > 0 else jnp.int64(0)

    # pair expansion: output slot j belongs to left row li where
    # prev[li] <= j < offsets[li].  Instead of binary-searching offsets per
    # slot, scatter a marker at each row's start and prefix-sum — pure
    # scatter+cumsum, runs at memory speed
    j = jnp.arange(capacity, dtype=jnp.int64)
    prev_all = offsets - cnt
    row_ids = jnp.arange(cnt.shape[0], dtype=jnp.int32)
    # rows with cnt>0 own distinct start slots; empty rows scatter -1 and
    # are skipped by the running max (exactly searchsorted's side='right')
    seg = jnp.full(capacity, -1, dtype=jnp.int32).at[prev_all].max(
        jnp.where(cnt > 0, row_ids, -1), mode="drop"
    )
    li = jax.lax.cummax(seg)
    li_safe = jnp.clip(li, 0, max(left_vals.shape[0] - 1, 0))
    prev = prev_all[li_safe]
    ri_sorted = lo[li_safe] + (j - prev).astype(jnp.int32)
    ri_safe = jnp.clip(ri_sorted, 0, max(right_vals.shape[0] - 1, 0))
    ri = order[ri_safe].astype(jnp.int32)

    out_valid = j < total
    for lc, rc in pairs:
        out_valid = out_valid & (left_vals[li_safe, lc] == right_vals[ri, rc])
    out_valid = out_valid & left_valid[li_safe] & right_valid[ri]

    parts = [left_vals[li_safe]]
    if right_extra:
        parts.append(right_vals[ri][:, jnp.array(right_extra, dtype=jnp.int32)])
    out_vals = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    out_vals = jnp.where(out_valid[:, None], out_vals, jnp.int32(0))
    return out_vals, out_valid, total


def _index_ranges(keys_sorted, type_key, left_vals, lc0, left_valid):
    """`[lo, hi)`, int32: where the keys `(type_key << 32) | v` of the
    left rows' values `v` (column `lc0`) lie in the posting index
    `keys_sorted`; what an invalid row gets is the caller's to mask."""
    method = index_search_method(left_vals.shape[0], keys_sorted.shape[0])
    if method == SLICE_SEARCH:
        return _slice_ranges(keys_sorted, type_key, left_vals[:, lc0])
    type_key = jnp.asarray(type_key, jnp.int64)
    probe = jnp.where(
        left_valid,
        (type_key << 32) | left_vals[:, lc0].astype(jnp.int64),
        jnp.int64(-1),
    )
    lo = jnp.searchsorted(keys_sorted, probe, side="left", method=method).astype(jnp.int32)
    hi = jnp.searchsorted(keys_sorted, probe, side="right", method=method).astype(jnp.int32)
    return lo, hi


def _slice_ranges(keys_sorted, type_key, left_col):
    """`_index_ranges` for a LARGE left side: one wide-fan-out search
    over 32-bit words inside the probed type's slice, the range's end
    read and not searched.  The same `[lo, hi)` as the two 64-bit
    searches for every int32 value of a left row, exact.

    The index holds int64 `(type << 32) | target`, sorted, pads (int64
    max) last; on the chip a gather from it is TWO u32 gathers, and the
    high word is compared although every key of the probed type has the
    same one (2 x 22 steps of them over 524,288 rows: 0.55 s of the
    whole-store conjunction's 0.84 s program, PERF.md §5).  So, once a
    program and elementwise over the index, each key becomes ONE word:
    the target where the key is of the probed type, the lowest int32
    where it is a dangling target's (storage/atom_table.py
    _combine_type_pos turns target -1 into the key -1 whatever the
    type: it sorts first, and a left value of -1 has always probed
    exactly that key), the next lowest for a smaller type, the highest
    for a larger type or a pad.  Rows of a type are contiguous and
    sorted by target, and atom row ids lie in [0, 2^31 - 1), so the
    words are non-decreasing over the WHOLE index: a search of them
    (`_search_words`) returns the global position, and `perm[...]`
    downstream is untouched.  `run_end[i]` is the position after the
    last word equal to word i (a reverse running minimum over the
    index), so `hi` is one read at `lo` where the word there is the
    probed one."""
    n = keys_sorted.shape[0]
    lowest = jnp.int32(-(2**31))
    words = _slice_words(keys_sorted, type_key)
    ends_run = jnp.concatenate(
        [words[1:] != words[:-1], jnp.ones((1,), dtype=bool)]
    )
    run_end = _padded_scan(
        jax.lax.cummin,
        jnp.where(ends_run, jnp.arange(1, n + 1, dtype=jnp.int32), _NO_ROW),
        _NO_ROW, reverse=True,
    )
    # -1 probes the dangling targets' key; no other negative value and
    # not 2^31 - 1 is any key's target, and their words stand for rows
    # of other types
    probe = jnp.where(left_col == -1, lowest, left_col)
    is_key = (left_col >= -1) & (left_col != _NO_ROW)
    with jax.named_scope(INDEX_SEARCH_SCOPE):
        lo, found = _search_words(words, probe)
    at = jnp.clip(lo, 0, n - 1)
    hi = jnp.where(is_key & found, run_end[at], lo)
    return lo, hi


def _slice_words(keys_sorted, type_key):
    """The posting index as ONE non-decreasing int32 word a key, for
    the probed type (`_slice_ranges` says which word stands for what)."""
    lowest = jnp.int32(-(2**31))
    type_word = (keys_sorted >> 32).astype(jnp.int32)
    of_type = jnp.asarray(type_key).astype(jnp.int32)
    return jnp.where(
        type_word < 0, lowest,
        jnp.where(
            type_word < of_type, lowest + 1,
            jnp.where(
                type_word > of_type, _NO_ROW, keys_sorted.astype(jnp.int32)
            ),
        ),
    )


def _search_levels(n: int) -> Tuple[int, ...]:
    """Rows of SEARCH_FANOUT words a level of `_search_words` holds over
    `n` words, the leaves first, the root last: the first level that
    fits SEARCH_ROOT_WORDS words, or is down to one row."""
    fanout = SEARCH_FANOUT
    rows = [n // fanout + 1]          # the leaves end in 1..fanout pads
    while rows[-1] > max(1, SEARCH_ROOT_WORDS // fanout):
        rows.append(-(-rows[-1] // fanout))
    return tuple(rows)


def _search_words(words, probe):
    """`(searchsorted(words, probe, side="left"), whether the word there
    is the probe)` for non-decreasing int32 `words`, by a descent of
    fan-out SEARCH_FANOUT: a step reads a ROW of separators where a
    binary search reads one word (device-trace scope
    `join.index_search`; scripts/index_join_parts.py times it alone).

    Levels, by static shape (`_search_levels`): the leaves are `words`
    with 1 to F pads (`_NO_ROW`, the highest int32) at their end, seen
    as rows of F; a level above holds the LAST word of every row of the
    one below, padded to rows of F the same way, up to a root of at
    most SEARCH_ROOT_WORDS words (2,961,251 words at F = 128: 23,135 /
    181 / 2 rows).

    Why the descent is the exact lower bound.  A row's separator is its
    greatest word, and rows follow each other in order, so
    `c = count(separators < probe)` over the separators of consecutive
    rows is the number of rows that lie WHOLLY below the probe: row `c`
    is the first that holds a word `>= probe`, and inside it the count
    of words below the probe is the offset of the first such word.
    Counting `<` and never `<=` lands on the FIRST of a run of equal
    words, also where the run crosses a row or a level.  The last leaf
    ends in a pad, which no int32 is above, so every level's last
    separator is a pad: a count never reaches past the row it is taken
    over (`c < F` under the root), no index needs a clamp and
    `lo <= n`.  The root
    is the same words for every probe and is compared whole (a count
    over all its rows is the row below to go to); every level under it
    is ONE gather of a row a probe.  The row the descent ends in holds
    `words[lo]`, so "the word at `lo` is the probe" is a second count
    over the leaf row and no read of its own (past the table that word
    is a pad: only the probe `_NO_ROW` equals it, which is no key)."""
    fanout = SEARCH_FANOUT
    levels = []
    level = words
    for rows in _search_levels(words.shape[0]):
        pad = jnp.full((rows * fanout - level.shape[0],), _NO_ROW, jnp.int32)
        level = jnp.concatenate([level, pad]).reshape(rows, fanout)
        levels.append(level)
        level = level[:, fanout - 1]
    below = probe[:, None]
    row = levels[-1].reshape(1, -1)
    node = jnp.sum(row < below, axis=1, dtype=jnp.int32)
    for level in levels[-2::-1]:
        row = level[node]
        node = node * fanout + jnp.sum(row < below, axis=1, dtype=jnp.int32)
    return node, jnp.any(row == below, axis=1)


def _index_join_impl(
    left_vals, left_valid, keys_sorted, perm, targets, type_key,
    pairs, right_var_cols, right_extra, capacity,
):
    """Join the left table INTO a whole-type term via the prebuilt
    (type<<32|target) positional posting index — no term-table
    materialization, no re-sort of the big side.

    The right side is implicit: every link of one type, variable columns
    at `right_var_cols` positions.  For each left row, the shared
    variable's value keys a searchsorted range in `keys_sorted` (exact —
    the packed key is injective); ranges expand positionally exactly like
    _join_tables_impl.  ONE shared variable (`pairs` holds one pair):
    every candidate is a match; two or more go the verified join
    (whole_type_join).  This is what makes joins against multi-million-row
    whole-table terms (FlyBase scale) capacity- and compile-cheap: buffers
    scale with the JOIN OUTPUT, never with the table.

    The ranges come from `_index_ranges`: two 64-bit searches of the
    whole index for a small left side, ONE 32-bit search inside the
    type's slice for a large one (`index_search_method`, by static
    shape); `lo`, `cnt`, `total`, the expansion and the row order are
    the same either way."""
    ((lc0, _rc0),) = pairs
    lo, hi = _index_ranges(keys_sorted, type_key, left_vals, lc0, left_valid)
    # int64: per-row ranges against an UNCAPPED whole-type term (tens of
    # millions of rows) can sum past 2^31; a wrapped total would silently
    # zero the output instead of triggering the overflow retry
    cnt = jnp.where(left_valid, hi - lo, 0).astype(jnp.int64)
    offsets = _cumsum_i64(cnt)
    total = offsets[-1] if cnt.shape[0] > 0 else jnp.int64(0)
    return _expand_index_ranges(
        left_vals, left_valid, lo, cnt, offsets, total, perm, targets,
        right_var_cols, right_extra, capacity,
    )


def _expand_index_ranges(
    left_vals, left_valid, lo, cnt, offsets, total, perm, targets,
    right_var_cols, right_extra, capacity,
):
    """The posting-index join's second half: every left row's `cnt`
    index positions from `lo` on, expanded positionally into `capacity`
    slots (the offsets arithmetic of _join_tables_impl) and read
    through `perm` into the store's rows.  Slot j belongs to the last
    left row with `cnt > 0` whose first slot `prev = offsets - cnt` is
    at or before j (a scatter of row ids, a running maximum); `perm` and
    `targets` are the two reads the join is for
    (scripts/index_join_parts.py times the expansion alone; device-trace
    scope `join.index_expand`).

    From `PACKED_EXPAND_MIN_SLOTS` slots on (by static shape) a left row
    is read ONCE per slot, as one packed int32 row
    `[lo - prev, left_vals...]`, `left_valid` is not read per slot and
    the slot arithmetic is 32-bit; under it the row is read four times
    through the owner (`prev`, `lo`, `left_valid`, `left_vals`) on
    64-bit slots.  Same outputs either way.

    The packed reads' precondition: `cnt > 0` only where `left_valid`
    (_index_join_impl builds `cnt` as `where(left_valid, hi - lo, 0)`).
    The segments of the rows with `cnt > 0` tile `[0, total)` exactly
    and only such a row is scattered, so a slot is valid where
    `j < total`.

    Their slot arithmetic is 32-bit although `offsets` and `total` are
    int64 (ranges into an uncapped type can sum past 2^31, and the
    overflow retry reads the exact `total`): a slot
    `j < min(total, capacity)` is owned by a row with
    `prev <= j < 2^31`, and `lo + (j - prev)` is an index position, so
    `(lo - prev) mod 2^32 + j` IS that position; a row whose `prev`
    lies at or past `capacity` owns no slot (the scatter drops it) and
    nothing reads its wrapped base."""
    if capacity >= 2**31:
        raise ValueError(
            f"join capacity {capacity} does not fit 32-bit slot arithmetic"
        )
    packed = capacity >= PACKED_EXPAND_MIN_SLOTS
    # the reads keep their order on both sides of the rule: the lowered
    # text of every accepted cell's program is pinned
    # (tests/test_tpu_compile.py PARENT_LOWERED)
    with jax.named_scope(INDEX_EXPAND_SCOPE):
        j = jnp.arange(capacity, dtype=jnp.int32 if packed else jnp.int64)
        prev_all = offsets - cnt
        row_ids = jnp.arange(cnt.shape[0], dtype=jnp.int32)
        seg = jnp.full(capacity, -1, dtype=jnp.int32)
        # packed: an index is cut to 32 bits before the scatter looks at
        # it; a first slot past the buffer goes to `capacity`, which it
        # drops
        first = (
            jnp.minimum(prev_all, capacity).astype(jnp.int32)
            if packed else prev_all
        )
        seg = seg.at[first].max(jnp.where(cnt > 0, row_ids, -1), mode="drop")
        li = _padded_scan(jax.lax.cummax, seg, -1)
        li_safe = jnp.clip(li, 0, max(left_vals.shape[0] - 1, 0))
        if packed:
            base = lo - prev_all.astype(jnp.int32)
            row = jnp.concatenate([base[:, None], left_vals], axis=1)[li_safe]
            ri_sorted = row[:, 0] + j
        else:
            prev = prev_all[li_safe]
            ri_sorted = lo[li_safe] + (j - prev).astype(jnp.int32)
        local = perm[jnp.clip(ri_sorted, 0, perm.shape[0] - 1)]
        row_t = targets[jnp.clip(local, 0, targets.shape[0] - 1)]

        if packed:
            out_valid = j < jnp.minimum(total, capacity).astype(jnp.int32)
            parts = [row[:, 1:]]
        else:
            out_valid = (j < total) & left_valid[li_safe]
            parts = [left_vals[li_safe]]
        if right_extra:
            parts.append(
                row_t[:, jnp.array([right_var_cols[rc] for rc in right_extra], dtype=jnp.int32)]
            )
        out_vals = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
        out_vals = jnp.where(out_valid[:, None], out_vals, jnp.int32(0))
        return out_vals, out_valid, total


def whole_type_join(
    left_vals, left_valid, index_arrays, type_key,
    pairs, right_var_cols, right_extra, capacity,
):
    """Join the left table INTO a whole-type term, the right side never
    materialized: `index_arrays` = (sorted (type<<32|target) keys of the
    probed position, their permutation, the arity's target matrix, its
    type ids) as query/fused.py index_join_arrays hands them.  ONE
    shared variable: the posting-index join (_index_join_impl, every
    candidate is a match).  Two or more: the verified join
    (_pair_join_impl), which counts and writes a pair only when EVERY
    shared column agrees.  Returns (out_vals, out_valid, total), `total`
    the exact number of rows of the join either way."""
    keys_sorted, perm, targets, type_ids = index_arrays
    if len(pairs) > 1:
        return _pair_join_impl(
            left_vals, left_valid, targets, type_ids, type_key,
            pairs, right_var_cols, right_extra, capacity,
        )
    with jax.named_scope(INDEX_JOIN_SCOPE):
        return _index_join_impl(
            left_vals, left_valid, keys_sorted, perm, targets, type_key,
            pairs, right_var_cols, right_extra, capacity,
        )


#: sorts last and marks a row that takes no part: atom row ids are
#: indexes into tables far shorter than 2^31 - 1
_NO_ROW = 2**31 - 1


def _pair_join_impl(
    left_vals, left_valid, targets, type_ids, type_key,
    pairs, right_var_cols, right_extra, capacity,
):
    """L ⋈ R = {(l, r) : l[v] = r[v] for EVERY shared variable v}, R the
    rows of one link type READ IN PLACE (`targets` of the arity,
    `type_ids` picks the type; nothing is gathered per candidate), for
    k >= 2 shared variables: `_verify_pairs` with the store's rows as
    its right side.  The caller on one chip, and on the mesh wherever
    the left side is gathered onto every shard; a mesh join that
    PARTITIONS both sides instead (parallel/fused_sharded.py
    pair_join_partitions) hands the rows it received to
    `pair_join_received`: the same sort, counts and expansion."""
    with jax.named_scope(PAIR_JOIN_SCOPE):
        of_type = type_ids == jnp.asarray(type_key).astype(type_ids.dtype)
        return _verify_pairs(
            left_vals, left_valid, targets, of_type,
            pairs, right_var_cols, right_extra, capacity,
        )


def pair_join_received(
    left_vals, left_valid, right_vals, right_valid,
    pairs, right_extra, capacity,
):
    """The verified join against a right TABLE (one column a variable,
    a validity mask) in place of the store's rows: what a shard of the
    mesh runs on the two sides an exchange brought it, its own key
    range of the join (parallel/fused_sharded.py).  `_verify_pairs`
    under the same device-trace scope as `_pair_join_impl`."""
    with jax.named_scope(PAIR_JOIN_SCOPE):
        return _verify_pairs(
            left_vals, left_valid, right_vals, right_valid,
            pairs, tuple(range(right_vals.shape[1])), right_extra, capacity,
        )


def _verify_pairs(
    left_vals, left_valid, right_rows, right_live,
    pairs, right_cols, right_extra, capacity,
):
    """The verified join's one implementation.  The right side is
    `right_rows` [n_r, a] where `right_live`, variable `rc` of it in
    column `right_cols[rc]`: the store's target matrix with the probed
    type's mask (`_pair_join_impl`), or a received table
    (`pair_join_received`).  A pair is verified BEFORE it is counted,
    so `total`, the output buffer and the overflow the retry ladder
    reads are sized by the rows of the join, never by the candidates of
    its first variable (the 3-clause whole-store conjunction at FlyBase
    scale 0.3: 9 M left rows, 90 M candidates through the posting index
    of one variable, ~1.7 k rows).

    One lexicographic sort of both sides together: the shared columns
    are the sort keys as they are, no hash, so equal neighbours ARE
    matches; which row an element was rides along as a payload.  The
    sort is UNSTABLE and the payload no key: on the chip a sort's
    compile time grows with every operand and every key (25.7 M
    elements: one s32 operand 29 s, three keys 112 s, PERF.md §6 PR
    44), so nothing may depend on the order inside a group of equal
    keys.  A running count of right rows, read at a group's two ends,
    gives every left row the size of its group; the kept pairs expand
    positionally into `capacity` slots (the same offsets arithmetic as
    _join_tables_impl, searched instead of scattered: there are
    `capacity` slots, not left rows, to place), the r-th right row of a
    group found by its rank in that running count.  Nothing is
    gathered per candidate: on a v5e a sort moves a row in ~3 ns where
    a gather through an index costs 6-26 ns.  Under vmap (a group
    program) every step batches."""
    n_r, n_l = right_rows.shape[0], left_vals.shape[0]
    n = n_r + n_l
    cols = []
    for k, (lc, rc) in enumerate(pairs):
        r = right_rows[:, right_cols[rc]]
        l = left_vals[:, lc]
        if k == 0:
            r = jnp.where(right_live, r, _NO_ROW)
            l = jnp.where(left_valid, l, _NO_ROW)
        cols.append(jnp.concatenate([r, l]))
    # which row an element was: right rows are tags < n_r
    tag = jnp.arange(n, dtype=jnp.int32)
    *cols, tag = jax.lax.sort(
        (*cols, tag), num_keys=len(cols), is_stable=False
    )
    live = cols[0] != _NO_ROW
    is_r = (live & (tag < n_r)).astype(jnp.int32)
    is_l = live & (tag >= n_r)
    differs = cols[0][1:] != cols[0][:-1]
    for c in cols[1:]:
        differs = differs | (c[1:] != c[:-1])
    edge = jnp.ones((1,), dtype=bool)
    first = jnp.concatenate([edge, differs])
    last = jnp.concatenate([differs, edge])
    # right rows seen so far; how many there were before the
    # element's group began and when it ended: the difference is
    # the number of right rows every left row of the group pairs
    # with
    seen_r = _padded_scan(jnp.cumsum, is_r, 0)
    before = _padded_scan(
        jax.lax.cummax, jnp.where(first, seen_r - is_r, 0), 0
    )
    after = _padded_scan(
        jax.lax.cummin, jnp.where(last, seen_r, _NO_ROW), _NO_ROW,
        reverse=True,
    )
    cnt = jnp.where(is_l, after - before, 0)
    # int64 total: a cross-ish join can pass 2^31; the int32
    # offsets then wrap, and the overflow retry discards the round
    total = cnt.astype(jnp.int64).sum()
    offsets = _padded_scan(jnp.cumsum, cnt, 0)

    # slot j belongs to the left element `at` and is its rank-th
    # pair: with the right row whose running count is before + rank
    # + 1 (right rows of other groups lie outside that window)
    j = jnp.arange(capacity, dtype=jnp.int32)
    method = _searchsorted_method(capacity, n)
    at = jnp.searchsorted(offsets, j, side="right", method=method)
    at = jnp.clip(at, 0, n - 1).astype(jnp.int32)
    rank = j - (offsets[at] - cnt[at])
    r_at = jnp.searchsorted(
        seen_r, before[at] + rank + 1, side="left", method=method
    )
    r_at = jnp.clip(r_at, 0, n - 1).astype(jnp.int32)
    ri = jnp.clip(tag[r_at], 0, max(n_r - 1, 0))
    li = jnp.clip(tag[at] - n_r, 0, max(n_l - 1, 0))

    # every slot below `total` holds a pair that agrees on all the
    # shared columns: they were the sort keys
    out_valid = j.astype(jnp.int64) < total
    parts = [left_vals[li]]
    if right_extra:
        parts.append(right_rows[ri][:, jnp.array(
            [right_cols[rc] for rc in right_extra], dtype=jnp.int32
        )])
    out_vals = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    out_vals = jnp.where(out_valid[:, None], out_vals, jnp.int32(0))
    return out_vals, out_valid, total


def _dedup_table_impl(vals, valid):
    """Unjitted dedup body — shared by the jitted single-device wrapper
    below and the shard-local mesh path (parallel/sharded_tree.py)."""
    k = vals.shape[1]
    big = jnp.where(valid[:, None], vals, jnp.int32(2**31 - 1))
    order = jnp.lexsort([big[:, c] for c in range(k - 1, -1, -1)])
    s = big[order]
    same_as_prev = jnp.concatenate(
        [jnp.zeros((1,), dtype=bool), (s[1:] == s[:-1]).all(axis=1)]
    )
    keep = ~same_as_prev & valid[order]
    return s, keep, keep.sum(dtype=jnp.int32)


@jax.jit
def _dedup_table_jit(vals, valid):
    return _dedup_table_impl(vals, valid)


def dedup_table(vals, valid):
    """Invalidate duplicate rows (exact: lexicographic sort over all
    columns, neighbor comparison).  Returns (vals_sorted, keep, count)."""
    from das_tpu.ops.counters import record_dispatch

    record_dispatch("lowered")
    return _dedup_table_jit(vals, valid)
