"""The lowered ops against a plain numpy oracle.

The four op chains every device program is traced from — the term
probe (`ops/posting.py range_probe -> verify_positions ->
ops/join.py build_term_table`), the sort-merge join
(`_join_tables_impl`), the join into a whole-type term (`whole_type_join`) and
the anti join (`_anti_join_impl`) — checked against brute force in
numpy over seeded random tables, on the shape classes where static
capacities bite: empty inputs, one row, a capacity exactly met, a
capacity overflowed (the op must REPORT the exact total so the host can
retry), sizes that are no multiple of 128, duplicate keys, and repeated
variables / several shared columns.

What is compared: the reported total exactly; the valid output rows as
a multiset when they fit the capacity; when they do not, that exactly
`capacity` candidates came back and each is an oracle row.
"""

from collections import Counter
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from das_tpu.ops.join import (
    _anti_join_impl,
    anti_join,
    build_term_table,
    join_tables,
    whole_type_join,
)
from das_tpu.ops.posting import range_probe, verify_positions


def _rng(name: str) -> np.random.Generator:
    return np.random.default_rng([ord(c) for c in name])


def _rows(vals, valid) -> Counter:
    vals, valid = np.asarray(vals), np.asarray(valid)
    return Counter(tuple(int(x) for x in r) for r in vals[valid])


def _check_window(got: Counter, want: Counter, total: int, want_total: int,
                  capacity: int, n_candidates: int) -> None:
    assert total == want_total
    if want_total <= capacity:
        assert got == want
    else:
        # overflowed: the window is full, and holds only true rows
        assert n_candidates == capacity
        assert not got - want


# -- op 1: the term probe chain ------------------------------------------


def _bucket(rng, n, arity, n_types, domain):
    targets = rng.integers(0, domain, (n, arity)).astype(np.int32)
    type_id = rng.integers(0, n_types, n).astype(np.int32)
    return targets, type_id


def _posting(targets, type_id, pos):
    key = (type_id.astype(np.int64) << 32) | targets[:, pos].astype(np.int64)
    perm = np.argsort(key, kind="stable").astype(np.int32)
    return key[perm], perm


#: name -> (n rows, arity, domain, extra fixed positions, var_cols,
#:          eq_pairs, capacity rule)
PROBE_CASES = {
    "empty_range": (300, 3, 8, (), (1, 2), (), "ample"),
    "one_row": (1, 2, 1, (), (1,), (), "ample"),
    "capacity_met": (500, 3, 4, (), (1, 2), (), "exact"),
    "capacity_overflowed": (700, 3, 3, (), (1, 2), (), "half"),
    "odd_sizes": (333, 3, 5, (), (1, 2), (), "odd"),
    "extra_fixed_position": (900, 3, 4, (2,), (1,), (), "ample"),
    "repeated_variable": (600, 3, 3, (), (1,), ((1, 2),), "ample"),
}


@pytest.mark.parametrize("case", sorted(PROBE_CASES))
def test_probe_chain_vs_oracle(case):
    n, arity, domain, extra, var_cols, eq_pairs, rule = PROBE_CASES[case]
    rng = _rng("probe." + case)
    targets, type_id = _bucket(rng, n, arity, 3, domain)
    keys_sorted, perm = _posting(targets, type_id, 0)
    t, v = 1, (domain + 5 if case == "empty_range" else int(targets[0, 0]))
    if case != "empty_range":
        type_id[0] = t  # the probed key has at least one row
        keys_sorted, perm = _posting(targets, type_id, 0)
    fixed = tuple((p, int(targets[0, p])) for p in extra)

    in_range = (type_id == t) & (targets[:, 0] == v)
    want_total = int(in_range.sum())
    ok = in_range.copy()
    for p, val in fixed:
        ok &= targets[:, p] == val
    for p1, p2 in eq_pairs:
        ok &= targets[:, p1] == targets[:, p2]
    want = Counter(
        tuple(int(x) for x in targets[r, list(var_cols)])
        for r in np.nonzero(ok)[0]
    )
    capacity = {
        "ample": 1024, "exact": max(want_total, 1),
        "half": max(want_total // 2, 1), "odd": 257,
    }[rule]

    probe_key = (np.int64(t) << 32) | np.int64(v)
    local, valid, count = range_probe(
        jnp.asarray(keys_sorted), jnp.asarray(perm), probe_key, capacity)
    n_candidates = int(np.asarray(valid).sum())
    mask = verify_positions(
        jnp.asarray(targets), jnp.asarray(type_id), local, valid, t, fixed)
    vals, mask = build_term_table(
        jnp.asarray(targets), local, mask, var_cols, eq_pairs)
    _check_window(_rows(vals, mask), want, int(count), want_total,
                  capacity, n_candidates)
    # rows outside the mask are zeroed, never stale
    assert not np.asarray(vals)[~np.asarray(mask)].any()


# -- op 2: the sort-merge join -------------------------------------------


def _table(rng, n, k, domain, p_valid=0.85):
    vals = rng.integers(0, domain, (n, k)).astype(np.int32)
    valid = rng.random(n) < p_valid
    return vals, valid


def _join_oracle(lv, lm, rv, rm, pairs, extra):
    out, total = Counter(), 0
    for i in np.nonzero(lm)[0]:
        for j in np.nonzero(rm)[0]:
            if all(lv[i, lc] == rv[j, rc] for lc, rc in pairs):
                total += 1
                out[tuple(int(x) for x in lv[i])
                    + tuple(int(rv[j, c]) for c in extra)] += 1
    return out, total


#: name -> (n_left, k_left, n_right, k_right, domain, pairs, extra, rule)
JOIN_CASES = {
    "empty_left": (40, 2, 50, 2, 6, ((1, 0),), (1,), "ample"),
    "empty_right": (40, 2, 50, 2, 6, ((1, 0),), (1,), "ample"),
    "one_row_each": (1, 2, 1, 2, 1, ((1, 0),), (1,), "ample"),
    "capacity_met": (60, 2, 70, 2, 9, ((1, 0),), (1,), "exact"),
    "capacity_overflowed": (90, 2, 80, 2, 4, ((1, 0),), (1,), "half"),
    "odd_sizes": (131, 3, 257, 2, 12, ((2, 0),), (1,), "odd"),
    "duplicate_keys": (64, 2, 64, 2, 2, ((1, 0),), (1,), "ample"),
    "two_shared_columns": (120, 3, 150, 3, 4, ((0, 0), (2, 1)), (2,),
                           "ample"),
    "cross_product": (13, 1, 11, 2, 5, (), (0, 1), "ample"),
}


@pytest.mark.parametrize("case", sorted(JOIN_CASES))
def test_join_tables_vs_oracle(case):
    nl, kl, nr, kr, domain, pairs, extra, rule = JOIN_CASES[case]
    rng = _rng("join." + case)
    lv, lm = _table(rng, nl, kl, domain)
    rv, rm = _table(rng, nr, kr, domain)
    if case == "empty_left":
        lm[:] = False
    if case == "empty_right":
        rm[:] = False
    if case == "one_row_each":
        lm[:] = rm[:] = True
    want, want_total = _join_oracle(lv, lm, rv, rm, pairs, extra)
    capacity = {
        "ample": 1 << 13, "exact": max(want_total, 1),
        "half": max(want_total // 2, 1), "odd": 3001,
    }[rule]
    if rule == "odd":
        assert want_total <= capacity
    out_vals, out_valid, total = join_tables(
        jnp.asarray(lv), jnp.asarray(lm), jnp.asarray(rv), jnp.asarray(rm),
        pairs, extra, capacity)
    _check_window(_rows(out_vals, out_valid), want, int(total), want_total,
                  capacity, int(np.asarray(out_valid).sum()))
    assert np.asarray(out_vals).shape == (capacity, kl + len(extra))


# -- op 3: the posting-index join ----------------------------------------


@partial(jax.jit, static_argnames=(
    "pairs", "right_var_cols", "right_extra", "capacity"))
def _index_join(lv, lm, keys_sorted, perm, targets, type_id, type_key, *,
                pairs, right_var_cols, right_extra, capacity):
    return whole_type_join(
        lv, lm, (keys_sorted, perm, targets, type_id), type_key,
        pairs, right_var_cols, right_extra, capacity)


def _index_join_oracle(lv, lm, targets, type_id, t, pairs, var_cols, extra):
    out, total = Counter(), 0
    links = np.nonzero(type_id == t)[0]
    for i in np.nonzero(lm)[0]:
        for r in links:
            # a row counts once it agrees on EVERY shared column: one
            # pair rides the posting index, two or more the verified join
            if all(targets[r, var_cols[rc]] == lv[i, lc] for lc, rc in pairs):
                total += 1
                out[tuple(int(x) for x in lv[i])
                    + tuple(int(targets[r, var_cols[rc]]) for rc in extra)
                    ] += 1
    return out, total


#: name -> (n_left, k_left, n_links, domain, pairs, var_cols, extra, rule)
INDEX_JOIN_CASES = {
    "empty_left": (30, 2, 400, 9, ((1, 1),), (0, 1), (0,), "ample"),
    "no_link_of_type": (30, 2, 400, 9, ((1, 1),), (0, 1), (0,), "ample"),
    "one_row": (1, 1, 1, 1, ((0, 0),), (0, 1), (1,), "ample"),
    "capacity_met": (40, 2, 600, 12, ((1, 1),), (0, 1), (0,), "exact"),
    "capacity_overflowed": (50, 2, 900, 5, ((1, 1),), (0, 1), (0,), "half"),
    "odd_sizes": (77, 2, 1001, 30, ((0, 0),), (0, 1), (1,), "odd"),
    "duplicate_keys": (64, 2, 512, 2, ((1, 1),), (0, 1), (0,), "ample"),
    "second_pair_verifies": (80, 2, 700, 5, ((0, 0), (1, 1)), (0, 1), (),
                             "ample"),
}


@pytest.mark.parametrize("case", sorted(INDEX_JOIN_CASES))
def test_index_join_vs_oracle(case):
    nl, kl, n_links, domain, pairs, var_cols, extra, rule = (
        INDEX_JOIN_CASES[case])
    rng = _rng("index." + case)
    lv, lm = _table(rng, nl, kl, domain)
    targets, type_id = _bucket(rng, n_links, 2, 3, domain)
    t = 5 if case == "no_link_of_type" else 1
    if case == "empty_left":
        lm[:] = False
    if case == "one_row":
        lm[:], type_id[:] = True, t
    probed_pos = var_cols[pairs[0][1]]
    keys_sorted, perm = _posting(targets, type_id, probed_pos)
    want, want_total = _index_join_oracle(
        lv, lm, targets, type_id, t, pairs, var_cols, extra)
    capacity = {
        "ample": 1 << 14, "exact": max(want_total, 1),
        "half": max(want_total // 2, 1), "odd": 777,
    }[rule]
    if rule == "odd":
        assert want_total <= capacity
    out_vals, out_valid, total = _index_join(
        jnp.asarray(lv), jnp.asarray(lm), jnp.asarray(keys_sorted),
        jnp.asarray(perm), jnp.asarray(targets), jnp.asarray(type_id),
        np.int64(t), pairs=pairs, right_var_cols=var_cols, right_extra=extra,
        capacity=capacity)
    got = _rows(out_vals, out_valid)
    assert int(total) == want_total
    if want_total <= capacity:
        assert got == want
    else:
        assert not got - want
    assert np.asarray(out_vals).shape == (capacity, kl + len(extra))


# -- op 4: the anti join -------------------------------------------------


#: name -> (n_left, k_left, n_right, k_right, domain, pairs)
ANTI_JOIN_CASES = {
    "empty_left": (40, 2, 30, 1, 6, ((1, 0),)),
    "empty_right": (40, 2, 30, 1, 6, ((1, 0),)),
    "one_row_match": (1, 1, 1, 1, 1, ((0, 0),)),
    "every_row_matches": (50, 2, 200, 1, 3, ((1, 0),)),
    "odd_sizes": (129, 3, 257, 2, 40, ((2, 1),)),
    "duplicate_keys": (64, 2, 64, 2, 2, ((0, 0),)),
    "two_shared_columns": (150, 3, 120, 2, 5, ((0, 0), (2, 1))),
}


@pytest.mark.parametrize("case", sorted(ANTI_JOIN_CASES))
def test_anti_join_vs_oracle(case):
    nl, kl, nr, kr, domain, pairs = ANTI_JOIN_CASES[case]
    rng = _rng("anti." + case)
    lv, lm = _table(rng, nl, kl, domain)
    rv, rm = _table(rng, nr, kr, domain)
    if case == "empty_left":
        lm[:] = False
    if case == "empty_right":
        rm[:] = False
    if case in ("one_row_match", "every_row_matches"):
        lm[:] = rm[:] = True
    tabu = {tuple(int(rv[j, rc]) for _lc, rc in pairs)
            for j in np.nonzero(rm)[0]}
    want = np.array([
        bool(lm[i]) and tuple(int(lv[i, lc]) for lc, _rc in pairs) not in tabu
        for i in range(nl)
    ])
    keep = anti_join(
        jnp.asarray(lv), jnp.asarray(lm), jnp.asarray(rv), jnp.asarray(rm),
        pairs)
    assert np.array_equal(np.asarray(keep), want)
    if case == "every_row_matches":
        assert not want.any()
    # the un-jitted body, as the fused programs trace it, agrees
    assert np.array_equal(
        np.asarray(_anti_join_impl(
            jnp.asarray(lv), jnp.asarray(lm), jnp.asarray(rv),
            jnp.asarray(rm), pairs)),
        want)
