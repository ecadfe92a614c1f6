"""The posting-index join's range lookup for a LARGE left side (PR 45;
since PR 49 a wide-fan-out descent where a binary search ran).

Where the left side is large against a big index (`ops/join.py
index_search_method`: the whole-store conjunction's first join, 524,288
rows into 2,961,251 keys) a left row's `[lo, hi)` comes from ONE search
over 32-bit words inside the probed type's slice, and the range's end
is read (`_slice_ranges`).  The search (`_search_words`) is a descent
of fan-out `SEARCH_FANOUT`: a step gathers a ROW of separators and
counts those below the probe.  Small left sides (every shape of the
grounded cells) keep the two 64-bit searches.

  * the descent alone against `np.searchsorted(words, probe, "left")`
    at fan-outs 2 to 128: fewer words than a row, one under / at / one
    over a row and a row of rows, runs of equal words across a row and
    a level, probes below, inside and above the table;
  * the lookup alone against `np.searchsorted` on the int64 keys: rows
    of other types on both sides of the slice, pads, the first and the
    last type, a type with no row, absent values, repeated probes, a
    probe of -1 with and without a dangling target in the index, every
    other int32 value a row cannot hold, a one-row index;
  * `whole_type_join` at a shape that takes it against the same join
    forced through the two scans: rows, their order, `total`, an
    overflowing capacity; under `vmap` and inside `shard_map`;
  * the static rule: which shapes take it.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from das_tpu.ops import join as join_ops
from das_tpu.query import fused

I32_MIN, I32_MAX = -(2**31), 2**31 - 1
PAD = np.int64(2**63 - 1)


def _index(rng, rows_by_type, n_targets, dangling=0, pads=0):
    """A posting index as storage/atom_table.py builds it: `(type << 32)
    | target` sorted, a dangling target (-1) the key -1 whatever its
    type, pads last.  Returns (keys, perm): `perm` a permutation of the
    live rows, as the join's `order_by_type_pos`."""
    keys = [np.full(dangling, -1, np.int64)]
    for tid, n in rows_by_type.items():
        targets = rng.integers(0, n_targets, n).astype(np.int64)
        keys.append((np.int64(tid) << 32) | targets)
    keys = np.sort(np.concatenate(keys))
    perm = rng.permutation(len(keys)).astype(np.int32)
    return (np.concatenate([keys, np.full(pads, PAD)]),
            np.concatenate([perm, np.zeros(pads, np.int32)]))


def _numpy_ranges(keys, tid, vals):
    """What the two 64-bit searches answer: the probe key is the type in
    the high word OR the SIGN-EXTENDED value, as _index_ranges packs
    it."""
    probe = (np.int64(tid) << 32) | vals.astype(np.int64)
    return (np.searchsorted(keys, probe, side="left"),
            np.searchsorted(keys, probe, side="right"))


def _probes(rng, keys, tid, n_targets, n):
    """Present values (repeated), absent ones, and every kind of int32
    a row id cannot be."""
    low = keys[(keys >> 32) == tid].astype(np.int32)
    present = (rng.choice(low, n) if len(low)
               else np.zeros(0, np.int32))
    absent = rng.integers(0, 2 * n_targets, n).astype(np.int32)
    odd = np.array([-1, -1, -2, I32_MIN, I32_MIN + 1, I32_MAX, I32_MAX - 1,
                    0, n_targets - 1, n_targets], np.int32)
    return np.concatenate([present, present[:5], absent, odd])


#: name -> (rows by type, probed type, dangling rows, pads)
TABLES = {
    "types_on_both_sides_and_pads": ({2: 40, 4: 300, 7: 60}, 4, 0, 25),
    "the_first_type": ({2: 300, 4: 40, 7: 60}, 2, 0, 9),
    "the_last_type_no_pad": ({2: 40, 4: 60, 7: 300}, 7, 0, 0),
    "the_last_type_then_pads": ({2: 40, 4: 60, 7: 300}, 7, 0, 31),
    "a_type_with_no_row": ({2: 40, 7: 60}, 4, 0, 12),
    "a_type_below_every_row": ({2: 40, 7: 60}, 0, 3, 12),
    "a_type_above_every_row": ({2: 40, 7: 60}, 9, 3, 12),
    "dangling_targets_in_the_index": ({2: 40, 4: 300, 7: 60}, 4, 6, 25),
    "dangling_targets_and_the_first_type": ({2: 300, 7: 60}, 2, 4, 0),
    "only_the_type": ({4: 200}, 4, 0, 0),
    "only_pads_and_dangling": ({}, 4, 5, 7),
    "one_row_of_the_type": ({4: 1}, 4, 0, 0),
    "one_row_of_another_type": ({2: 1}, 4, 0, 0),
    "one_dangling_row": ({}, 4, 1, 0),
    "one_pad": ({}, 4, 0, 1),
    "type_zero": ({0: 150, 1: 50}, 0, 2, 3),
}


#: fan-outs the lookup is held at beside the module's own: a narrow
#: one makes the hand-made indexes (a few hundred keys) four or five
#: levels deep
FANOUTS = [None, 4]


@pytest.mark.parametrize("fanout", FANOUTS)
@pytest.mark.parametrize("name", TABLES)
def test_the_lookup_is_np_searchsorted_on_the_int64_keys(
        name, fanout, monkeypatch):
    if fanout:
        monkeypatch.setattr(join_ops, "SEARCH_FANOUT", fanout)
        monkeypatch.setattr(join_ops, "SEARCH_ROOT_WORDS", fanout)
    rows_by_type, tid, dangling, pads = TABLES[name]
    rng = np.random.default_rng(sorted(TABLES).index(name))
    n_targets = 50           # few targets: long runs of equal keys
    keys, _perm = _index(rng, rows_by_type, n_targets, dangling, pads)
    vals = _probes(rng, keys, tid, n_targets, 40)
    lo, hi = jax.jit(lambda k, t, v: join_ops._slice_ranges(k, t, v))(
        jnp.asarray(keys), np.int32(tid), jnp.asarray(vals))
    want_lo, want_hi = _numpy_ranges(keys, tid, vals)
    assert lo.dtype == hi.dtype == jnp.int32
    assert (np.asarray(hi) - np.asarray(lo) == want_hi - want_lo).all()
    # `lo` is the GLOBAL position wherever the range holds a row; an
    # empty range's position feeds nothing
    found = want_hi > want_lo
    assert (np.asarray(lo)[found] == want_lo[found]).all()
    if name == "dangling_targets_in_the_index":
        # pinned: what the parent answers for a left value of -1: the
        # dangling targets' rows, of ANY type (they share the key -1)
        at = np.flatnonzero(vals == -1)
        assert (want_hi - want_lo)[at].tolist() == [dangling] * len(at)
        assert (np.asarray(hi) - np.asarray(lo))[at].tolist() == [6, 6]


# -- the descent alone ------------------------------------------------------

#: name -> the words of a table as a function of the fan-out F: how many
#: words against a row of F and a row of rows, and where runs of equal
#: words fall
WORDS = {
    "one_word": lambda F, rng: [7],
    "fewer_than_a_row": lambda F, rng: _runs(rng, max(1, F // 2)),
    "one_under_a_row": lambda F, rng: _runs(rng, F - 1),
    "a_row": lambda F, rng: _runs(rng, F),
    "one_over_a_row": lambda F, rng: _runs(rng, F + 1),
    "one_under_a_row_of_rows": lambda F, rng: _runs(rng, F * F - 1),
    "a_row_of_rows": lambda F, rng: _runs(rng, F * F),
    "one_over_a_row_of_rows": lambda F, rng: _runs(rng, F * F + 1),
    "three_levels_and_a_bit": lambda F, rng: _runs(rng, 2 * F * F + 3),
    # every word the same: ONE run across every row and level
    "one_run": lambda F, rng: [11] * (F * F + 1),
    # a run that starts on a row's first word and crosses a level
    "a_run_from_a_rows_edge": lambda F, rng: [5] * F + [9] * (F * F) + [12],
    # a run that ends on a row's last word, the next begins the next row
    "a_run_to_a_rows_edge": lambda F, rng: [5] * (2 * F) + [6] * (3 * F),
    # as `_slice_ranges` makes them: other types' words on both sides,
    # the slice between, nothing but pads (the highest word) after it
    "a_slice_between_other_types": lambda F, rng: (
        [I32_MIN] * 3 + [I32_MIN + 1] * (F + 2) + _runs(rng, 3 * F)
        + [I32_MAX] * (F * F)),
    "all_pads": lambda F, rng: [I32_MAX] * (F + 3),
    "distinct_words": lambda F, rng: list(range(0, 3 * (F * F + F), 3)),
}


def _runs(rng, n):
    """`n` sorted words of few distinct values: runs of equal words
    longer than a row at the small fan-outs."""
    return np.sort(rng.integers(-3, max(2, n // 7), n)).tolist()


@pytest.mark.parametrize("fanout,root", [(2, 2), (4, 4), (4, 16), (16, 16),
                                         (16, 64), (128, 128), (128, 256)])
@pytest.mark.parametrize("name", WORDS)
def test_the_descent_is_np_searchsorted_left(name, fanout, root, monkeypatch):
    """`_search_words` against numpy for every word of the table, its
    neighbours, every row's LAST word (a separator) and first, and the
    probes no key holds: below every word, above every word, -1, -2,
    the lowest int32 and 2^31 - 1."""
    monkeypatch.setattr(join_ops, "SEARCH_FANOUT", fanout)
    monkeypatch.setattr(join_ops, "SEARCH_ROOT_WORDS", root)
    rng = np.random.default_rng(fanout + sorted(WORDS).index(name))
    words = np.asarray(WORDS[name](fanout, rng), np.int32)
    n = len(words)
    assert (np.diff(words.astype(np.int64)) >= 0).all()
    near = words.astype(np.int64)[:, None] + np.array([-1, 0, 1])
    probes = np.concatenate([
        near.ravel().clip(I32_MIN, I32_MAX),
        words[fanout - 1::fanout], words[::fanout],
        [-1, -2, I32_MIN, I32_MIN + 1, I32_MAX, I32_MAX - 1,
         int(words[0]), int(words[-1])],
    ]).astype(np.int32)
    lo, found = jax.jit(lambda w, p: join_ops._search_words(w, p))(
        jnp.asarray(words), jnp.asarray(probes))
    want = np.searchsorted(words, probes, side="left")
    assert lo.dtype == jnp.int32 and found.dtype == jnp.bool_
    assert (np.asarray(lo) == want).all()
    # the word at `lo`; past the table it is a pad, which only the
    # probe 2^31 - 1 equals (no key: `_slice_ranges` masks it)
    padded = np.append(words, np.int32(I32_MAX))
    assert (np.asarray(found) == (padded[want] == probes)).all()
    # some probe lies below every word, and one above unless the table
    # ends in the highest word there is
    assert 0 in want and (n in want or words[-1] == I32_MAX)


@pytest.mark.parametrize("n,fanout,root,rows", [
    (2_961_251, 128, 128, (23_135, 181, 2, 1)),     # cell 5's index
    (2_961_251, 128, 256, (23_135, 181, 2)),        # a root of two rows
    (2_961_251, 16, 16, (185_079, 11_568, 723, 46, 3, 1)),
    (2_220_890, 128, 128, (17_351, 136, 2, 1)),     # a shard's of cell 6
    (2_220_890, 128, 256, (17_351, 136, 2)),
    (127, 128, 128, (1,)),      # fewer words than a row: the root alone
    (128, 128, 128, (2, 1)),    # a full row: its pad starts a second
    (128, 128, 256, (2,)),
    (1, 2, 2, (1,)),
    (4, 2, 2, (3, 2, 1)),
    (4, 2, 1, (3, 2, 1)),       # a root is one row at least
    (4, 2, 4, (3, 2)),
])
def test_the_levels_follow_from_the_key_count(n, fanout, root, rows,
                                              monkeypatch):
    monkeypatch.setattr(join_ops, "SEARCH_FANOUT", fanout)
    monkeypatch.setattr(join_ops, "SEARCH_ROOT_WORDS", root)
    assert join_ops._search_levels(n) == rows
    assert rows[0] * fanout > n >= (rows[0] - 1) * fanout   # 1..F pads
    assert rows[-1] * fanout <= max(root, fanout)


@pytest.mark.parametrize("fanout,root", [(4, 4), (4, 16), (128, 256)])
def test_the_search_is_row_gathers_under_its_scope_and_no_loop(
        fanout, root, monkeypatch):
    """What the traced lookup holds: no loop anywhere, under
    `join.index_search` ONE gather of a row a level below the root, and
    outside it one read (`run_end` at `lo`)."""
    from das_tpu.obs.registry import INDEX_SEARCH_SCOPE

    monkeypatch.setattr(join_ops, "SEARCH_FANOUT", fanout)
    monkeypatch.setattr(join_ops, "SEARCH_ROOT_WORDS", root)
    n_keys, n_left = 1000, 64
    jaxpr = jax.make_jaxpr(
        lambda k, v: join_ops._slice_ranges(k, np.int32(4), v))(
        jnp.zeros((n_keys,), jnp.int64), jnp.zeros((n_left,), jnp.int32))
    assert INDEX_SEARCH_SCOPE == "join.index_search"
    prims = [(e.primitive.name, INDEX_SEARCH_SCOPE in str(e.source_info.name_stack),
              e) for e in jaxpr.jaxpr.eqns]
    names = {p for p, _in, _e in prims}
    assert not names & {"while", "scan", "sort"}
    gathers = [(inside, e) for p, inside, e in prims if p == "gather"]
    levels = join_ops._search_levels(n_keys)
    searched = [e for inside, e in gathers if inside]
    assert len(searched) == len(levels) - 1
    for e, rows in zip(searched, levels[-2::-1]):      # from the top down
        assert e.invars[0].aval.shape == (rows, fanout)
        assert e.outvars[0].aval.shape == (n_left, fanout)
        assert e.outvars[0].aval.dtype == jnp.int32
    (outside,) = [e for inside, e in gathers if not inside]
    assert outside.invars[0].aval.shape == (n_keys,)


# -- the join, slice search against the two scans ---------------------------


def _tables(rng, n_left, rows_by_type, tid, n_targets, dangling=0, pads=0):
    """A left table with some invalid rows and some values of -1, the
    index of position 0 and the arity's target matrix behind it."""
    keys, perm = _index(rng, rows_by_type, n_targets, dangling, pads)
    n, live = len(keys), len(keys) - pads
    targets = np.zeros((n, 2), np.int32)
    targets[perm[:live], 0] = keys[:live].astype(np.int32)  # dangling: -1
    targets[:, 1] = rng.integers(0, 1000, n)
    lv = np.stack([rng.integers(0, int(n_targets * 1.3), n_left),
                   rng.integers(0, 1000, n_left)], 1).astype(np.int32)
    lv[rng.integers(0, n_left, max(1, n_left // 50)), 0] = -1
    lm = rng.random(n_left) < 0.9
    return lv, lm, keys, perm, targets


#: the static rule at a CPU's size: with the key cap at 256 keys a
#: 1,100-row left side into a 1,000-row index takes the slice search
SMALL_CAP = 256


def _traced(rule, fn, *args):
    """`fn(*args)` under a fresh jit (so the lookup is chosen anew),
    the range lookup forced to "scan", or left to the static rule with
    the key cap at `rule` (None: as it is).  Returns (outputs, whether
    the slice search was traced)."""
    seen = []
    real = join_ops._slice_ranges
    with pytest.MonkeyPatch.context() as m:
        if rule == "scan":
            m.setattr(join_ops, "index_search_method", lambda a, b: "scan")
        elif rule is not None:
            m.setattr(join_ops, "SORT_SEARCH_MAX_KEYS", rule)
        m.setattr(join_ops, "_slice_ranges",
                  lambda *a: seen.append(1) or real(*a))
        out = jax.jit(fn)(*(jnp.asarray(a) for a in args))
    return tuple(np.asarray(o) for o in out), bool(seen)


def _one_variable_join(tid, capacity):
    def fn(lv, lm, keys, perm, targets):
        return join_ops.whole_type_join(
            lv, lm, (keys, perm, targets, None), tid,
            ((0, 0),), (0, 1), (1,), capacity)
    return fn


def _same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert (g == w).all()


def _numpy_total(lv, lm, keys, tid):
    lo, hi = _numpy_ranges(keys, tid, lv[:, 0])
    return int((hi - lo)[lm].sum())


def test_a_big_left_side_takes_it_and_answers_as_the_two_scans():
    """At a shape the static rule sends to the slice search, nothing
    patched: rows, their order, validity and `total` are the scans'."""
    rng = np.random.default_rng(45)
    n_keys = join_ops.SORT_SEARCH_MAX_KEYS + 4_096
    n_left = n_keys // 16 + 1_000
    assert join_ops.index_search_method(n_left, n_keys) == "slice"
    tables = _tables(
        rng, n_left, {2: 100_000, 4: n_keys - 102_104, 7: 2_000}, 4,
        600_000, dangling=8, pads=96)
    lv, lm, keys = tables[:3]
    assert len(keys) == n_keys
    fn = _one_variable_join(np.int32(4), 1 << 18)
    sliced, took = _traced(None, fn, *tables)
    scanned, took_scan = _traced("scan", fn, *tables)
    assert took and not took_scan
    _same(sliced, scanned)
    _vals, valid, total = sliced
    assert 0 < int(total) == int(valid.sum()) <= 1 << 18
    assert int(total) == _numpy_total(lv, lm, keys, 4)


@pytest.mark.parametrize("dangling", [0, 7])
@pytest.mark.parametrize("capacity", [4096, 64])
def test_rows_order_total_and_overflow_are_the_scans(capacity, dangling):
    """Also past the buffer: `total` is the exact row count, the slots
    hold the first `capacity` pairs in the same order."""
    rng = np.random.default_rng(capacity + dangling)
    tables = _tables(rng, 1100, {2: 150, 4: 700, 7: 100}, 4, 400, dangling,
                     pads=50)
    lv, lm, keys = tables[:3]
    fn = _one_variable_join(np.int32(4), capacity)
    sliced, took = _traced(SMALL_CAP, fn, *tables)
    scanned, took_scan = _traced("scan", fn, *tables)
    assert took and not took_scan
    _same(sliced, scanned)
    total = int(sliced[2])
    assert total == _numpy_total(lv, lm, keys, 4)
    assert (total > capacity) == (capacity == 64)
    if dangling and capacity == 4096:
        # a valid left row of -1 pairs with every dangling row, as ever
        minus = ((lv[:, 0] == -1) & lm).sum()
        assert minus and ((sliced[0][:, 0] == -1) & sliced[1]).sum() == (
            minus * dangling)


def test_under_vmap_a_lane_its_own_left_side_and_type():
    rng = np.random.default_rng(7)
    _lv, _lm, keys, perm, targets = _tables(
        rng, 1100, {2: 400, 4: 500, 7: 100}, 4, 300, 5, pads=40)
    lefts = [_tables(rng, 1100, {4: 1}, 4, 300)[:2] for _ in range(3)]
    lv = np.stack([l[0] for l in lefts])
    lm = np.stack([l[1] for l in lefts])
    tids = np.array([4, 2, 5], np.int32)      # 5: a type with no row

    def run(lv, lm, tids, keys, perm, targets):
        def lane(lv, lm, tid):
            return _one_variable_join(tid, 2048)(lv, lm, keys, perm, targets)

        with join_ops.lane_batched():
            return jax.vmap(lane)(lv, lm, tids)

    args = (lv, lm, tids, keys, perm, targets)
    sliced, took = _traced(SMALL_CAP, run, *args)
    scanned, took_scan = _traced("scan", run, *args)
    assert took and not took_scan
    _same(sliced, scanned)
    totals = sliced[2].tolist()
    assert totals == [_numpy_total(l[0], l[1], keys, t)
                      for l, t in zip(lefts, tids)]
    # a type with no row: only its left values of -1 pair, with the
    # five dangling rows
    minus = ((lefts[2][0][:, 0] == -1) & lefts[2][1]).sum()
    assert totals[2] == 5 * minus and min(totals[:2]) > totals[2]


def test_inside_shard_map_a_shard_its_own_index():
    """The mesh's way (parallel/fused_sharded.py): the left side whole
    on every shard, each shard probing its own slab's index."""
    from jax import shard_map

    rng = np.random.default_rng(8)
    shards = [_tables(rng, 1100, {2: 100, 4: 800, 7: 60}, 4, 300, 3, pads=40)
              for _ in range(4)]
    lv, lm = shards[0][:2]
    keys, perm, targets = (np.stack([s[k] for s in shards])
                           for k in (2, 3, 4))
    mesh = Mesh(np.array(jax.devices()[:4]), ("s",))

    def shard(lv, lm, keys, perm, targets):
        vals, valid, total = _one_variable_join(np.int32(4), 4096)(
            lv, lm, keys[0], perm[0], targets[0])
        return vals[None], valid[None], total[None]

    fn = shard_map(
        shard, mesh=mesh, in_specs=(P(), P(), P("s"), P("s"), P("s")),
        out_specs=(P("s"), P("s"), P("s")))
    args = (lv, lm, keys, perm, targets)
    sliced, took = _traced(SMALL_CAP, fn, *args)
    scanned, took_scan = _traced("scan", fn, *args)
    assert took and not took_scan
    _same(sliced, scanned)
    assert sliced[2].tolist() == [_numpy_total(lv, lm, k, 4) for k in keys]


# -- the static rule --------------------------------------------------------

#: the posting indexes of the cells' stores: capacity classes of the
#: arity-2 bucket at scale 0.1 (cells 2 and 5) and 0.3 (cells 1, 3, 4),
#: and a mesh shard's quarter of the second
KEYS_01, KEYS_03 = 2_961_251, 8_883_562
KEYS_SHARD = -(-KEYS_03 // 4)


@pytest.mark.parametrize("n_left,n_keys", [
    (1 << 19, KEYS_01),     # cell 5: the Interacts term's capacity
    (1 << 21, KEYS_03),     # the same query at scale 0.3
    (1 << 20, KEYS_01),
    (KEYS_01 // 16 + 1, KEYS_01),
])
def test_the_analytic_cells_shapes_take_the_slice_search(n_left, n_keys):
    assert join_ops.index_search_method(n_left, n_keys) == "slice"
    with join_ops.lane_batched():
        assert join_ops.index_search_method(n_left, n_keys) == "slice"
    # every other search of the program keeps its scan
    assert join_ops._searchsorted_method(n_left, n_keys) == "scan"


@pytest.mark.parametrize("n_keys", [KEYS_01, KEYS_03, KEYS_SHARD, 1 << 20,
                                    1 << 16, 256, 16])
@pytest.mark.parametrize("n_left", [16, 64, 256, 1024, 2048])
def test_the_grounded_cells_shapes_keep_what_they_had(n_left, n_keys):
    """16-2,048 rows a lane into the cells' indexes, one chip and a
    mesh shard, alone and under lanes: `_searchsorted_method`'s answer,
    never the slice search."""
    for lanes in (False, True):
        with join_ops.lane_batched() if lanes else contextlib.nullcontext():
            got = join_ops.index_search_method(n_left, n_keys)
            assert got == join_ops._searchsorted_method(n_left, n_keys)
            assert got != "slice"
    if n_keys > join_ops.SORT_SEARCH_MAX_KEYS or n_left <= 1024:
        assert got == "scan"


@pytest.mark.parametrize("n_left,n_keys,want", [
    (KEYS_01 // 16, KEYS_01, "scan"),        # the relative rule's edge
    (1 << 19, 1 << 20, "sort"),              # the key cap's edge: a co-sort
    (1 << 19, (1 << 20) + 1, "slice"),
    (1025, 1 << 20, "scan"),
    (2048, 16, "sort"),
])
def test_the_rules_edges(n_left, n_keys, want):
    assert join_ops.index_search_method(n_left, n_keys) == want


def test_only_the_posting_index_join_asks_the_new_rule(monkeypatch):
    """`_join_tables_impl`, `_anti_join_impl` and the verified join's
    expansion ask `_searchsorted_method`, whose answers are the three it
    had."""
    asked = []
    monkeypatch.setattr(
        join_ops, "index_search_method",
        lambda a, b: asked.append((a, b)) or "scan")
    rng = np.random.default_rng(1)
    lv, lm, keys, perm, targets = _tables(rng, 40, {4: 90}, 4, 30)
    lv[:, 0] = np.maximum(lv[:, 0], 0)
    tids = np.full(len(keys), 4, np.int32)
    args = [jnp.asarray(a) for a in (lv, lm)]
    jax.jit(lambda a, b: join_ops._join_tables_impl(
        a, b, a, b, ((0, 0),), (1,), 256))(*args)
    jax.jit(lambda a, b: join_ops._anti_join_impl(
        a, b, a, b, ((0, 0),)))(*args)
    jax.jit(lambda a, b: join_ops.whole_type_join(
        a, b, (None, None, jnp.asarray(targets), jnp.asarray(tids)),
        np.int32(4), ((0, 0), (1, 1)), (0, 1), (), 256))(*args)
    assert asked == []
    jax.jit(lambda a, b: join_ops.whole_type_join(
        a, b, tuple(jnp.asarray(x) for x in (keys, perm, targets, tids)),
        np.int32(4), ((0, 0),), (0, 1), (1,), 256))(*args)
    assert asked == [(40, len(keys))]
    for n_q in (16, 2048, 1 << 19, 1 << 24):
        for n_k in (16, 1 << 20, KEYS_01, KEYS_03):
            assert join_ops._searchsorted_method(n_q, n_k) in ("scan", "sort")


# -- the counters that say it engaged ---------------------------------------


def _settled(key_cap, grounded):
    """One settled job of the fused route under tracing, the key cap at
    `key_cap` (None: as it is): the whole-store 3-clause conjunction,
    or its grounded sibling.  Returns (result, probe rows counted, slice
    rows counted, the job)."""
    from das_tpu import obs
    from das_tpu.core.config import DasConfig
    from das_tpu.models.bio import build_bio_atomspace
    from das_tpu.query import compiler
    from das_tpu.query.ast import And, Link, Node, Variable
    from das_tpu.storage.tensor_db import TensorDB

    data, genes, _ = build_bio_atomspace(
        n_genes=500, n_processes=40, members_per_gene=3, n_interactions=700,
        seed=45)
    db = TensorDB(data, DasConfig(result_cache_size=0))
    v = Variable
    first = Node("Gene", "GENE:0000007") if grounded else v("V1")
    plans = [list(compiler.plan_query(db, And([
        Link("Interacts", [first, v("V2")], True),
        Link("Member", [first, v("V3")], True),
        Link("Member", [v("V2"), v("V3")], True),
    ])))]
    ex = fused.get_executor(db)
    jobs = []
    build = lambda *a: jobs.extend(ex._build_jobs(*a)) or jobs  # noqa: E731
    with pytest.MonkeyPatch.context() as m:
        if key_cap is not None:
            m.setattr(join_ops, "SORT_SEARCH_MAX_KEYS", key_cap)
        obs.configure(enabled=True)
        try:
            obs.reset()
            probed = obs.counter("join.index_probe_rows").value
            sliced = obs.counter("join.index_slice_rows").value
            fetches = fused.FETCH_COUNTS["n"]
            pending = fused.dispatch_pending(
                ex.results, ex._exec_job, plans, False, build_jobs=build)
            (result,) = fused.settle_pending(ex.results, pending)
            probed = obs.counter("join.index_probe_rows").value - probed
            sliced = obs.counter("join.index_slice_rows").value - sliced
        finally:
            obs.configure(enabled=False)
    assert fused.FETCH_COUNTS["n"] == fetches + jobs[0].rounds
    return result, probed, sliced, jobs[0]


def test_the_counters_are_declared_and_the_metric_listed():
    from benchmark.harness import spec
    from das_tpu import obs

    assert {"join.index_probe_rows",
            "join.index_slice_rows"} <= set(obs.COUNTER_NAMES)
    assert set(obs.metrics.COUNTERS) == set(obs.COUNTER_NAMES)
    (entry,) = [m for m in spec.load_benchmark()["per_layer"]
                if m["name"] == "ops.index_join_slice_share"]
    assert entry["workloads"] == ["mem-analytic"]
    read = spec.Cell("mem-analytic").layer_reader(entry["name"])
    assert read([], {"obs.join.index_probe_rows": 8,
                     "obs.join.index_slice_rows": 8}, None, {}) == 100.0
    assert read([], {}, None, {}) is None


def test_a_big_first_join_counts_its_rows_as_sliced():
    """The whole-store conjunction with the key cap at a CPU's size:
    its first join (Interacts x Member on one variable, 2,048 slots of
    left side) takes the slice search and says so; the second is the
    verified join and counts in neither.  The answer is the scans'."""
    sliced_run = _settled(SMALL_CAP, grounded=False)
    scanned_run = _settled(None, grounded=False)
    result, probed, sliced, job = sliced_run
    pair_steps, probe_steps, first = fused.whole_type_join_steps(
        job.sigs, job.index_joins)
    assert (pair_steps, probe_steps) == ((1,), ((0, 1),))
    n_keys = job.arrays[1][0].shape[0]
    assert job.term_caps[first] > max(1024, n_keys // 16)
    rows = job.last_ranges[first]          # the Interacts term: both ways
    assert probed == sliced == rows > 1024
    other, probed_scan, sliced_scan, _job = scanned_run
    assert (probed_scan, sliced_scan) == (rows, 0)
    assert result.count == other.count > 0
    assert (result.host_vals == other.host_vals).all()
    assert (result.host_valid == other.host_valid).all()


def test_a_grounded_query_counts_its_rows_and_none_sliced():
    """A grounded shape's index joins (a few rows into the same index)
    keep the two searches: probe rows move, slice rows stand still,
    whatever the key cap."""
    for key_cap in (None, SMALL_CAP):
        _result, probed, sliced, job = _settled(key_cap, grounded=True)
        _pairs, probe_steps, _first = fused.whole_type_join_steps(
            job.sigs, job.index_joins)
        assert probe_steps and probed > 0 and sliced == 0
