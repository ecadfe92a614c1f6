"""Sorted-index probe primitives (device kernels).

The Redis pattern/template namespaces of the reference
(redis_mongo_db.py:147-151, 235-275) become `searchsorted` range probes over
argsort permutations built at finalize time (storage/atom_table.py).  Every
probe is a fixed-capacity kernel: it returns a padded candidate vector, a
validity mask and the *exact* match count, so the host can detect capacity
overflow and retry with a doubled buffer — the standard static-shape
escape hatch under XLA.

All kernels work on bucket-local int32 row indices; int64 appears only in
the probe keys (``type_id << 32 | target_row`` — exact, collision-free).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

INVALID_ROW = jnp.int32(2**31 - 1)


@partial(jax.jit, static_argnames=("capacity",))
def _range_probe_jit(sorted_keys, perm, probe_key, capacity: int):
    lo = jnp.searchsorted(sorted_keys, probe_key, side="left")
    hi = jnp.searchsorted(sorted_keys, probe_key, side="right")
    count = (hi - lo).astype(jnp.int32)
    offs = jnp.arange(capacity, dtype=jnp.int32)
    valid = offs < count
    idx = jnp.clip(lo.astype(jnp.int32) + offs, 0, sorted_keys.shape[0] - 1)
    local = jnp.where(valid, perm[idx], INVALID_ROW)
    return local, valid, count


def range_probe(sorted_keys, perm, probe_key, capacity: int):
    """Bucket-local rows whose sort key equals `probe_key`.

    Returns (local[capacity] int32, valid[capacity] bool, count int32).
    """
    from das_tpu.ops.counters import record_dispatch

    record_dispatch("lowered")
    return _range_probe_jit(sorted_keys, perm, probe_key, capacity)


@partial(jax.jit, static_argnames=("capacity",))
def _full_scan_jit(size, capacity: int):
    offs = jnp.arange(capacity, dtype=jnp.int32)
    valid = offs < size
    return jnp.where(valid, offs, INVALID_ROW), valid, jnp.int32(size)


def full_scan(size, capacity: int):
    """All bucket rows as a padded candidate vector (type-and-targets all
    wildcard probes)."""
    from das_tpu.ops.counters import record_dispatch

    record_dispatch("lowered")
    return _full_scan_jit(size, capacity)


@partial(jax.jit, static_argnames=("fixed",))
def _verify_positions_jit(targets, type_id, local, valid, probe_type, fixed):
    safe = jnp.clip(local, 0, targets.shape[0] - 1)
    mask = valid
    mask = jnp.where(probe_type >= 0, mask & (type_id[safe] == probe_type), mask)
    for pos, val in fixed:
        mask = mask & (targets[safe, pos] == val)
    return mask


def verify_positions(targets, type_id, local, valid, probe_type, fixed: Tuple[Tuple[int, int], ...]):
    """Positional wildcard-pattern verification: keep candidates whose
    type matches `probe_type` (pass -1 to skip) and whose target columns
    equal each (position, row) pair in `fixed`."""
    from das_tpu.ops.counters import record_dispatch

    record_dispatch("lowered")
    return _verify_positions_jit(targets, type_id, local, valid, probe_type, fixed)


@partial(jax.jit, static_argnames=("required",))
def verify_multiset(targets, type_id, local, valid, probe_type, required: Tuple[Tuple[int, int], ...]):
    """Unordered (Set/Similarity) verification: candidate must contain each
    required target row with at least the required multiplicity."""
    pair_vals = jnp.asarray([v for v, _ in required], dtype=jnp.int32)
    pair_cnts = jnp.asarray([c for _, c in required], dtype=jnp.int32)
    return verify_multiset_traced(
        targets, type_id, local, valid, probe_type,
        pair_vals, pair_cnts, len(required),
    )


def verify_multiset_traced(
    targets, type_id, local, valid, probe_type, pair_vals, pair_cnts, n_pairs: int
):
    """`verify_multiset` with the required (value, multiplicity) pairs as
    TRACED arrays instead of static tuples, so one compiled program serves
    every probe of the same shape (only `n_pairs` is baked in)."""
    safe = jnp.clip(local, 0, targets.shape[0] - 1)
    rows = targets[safe]
    mask = valid
    mask = jnp.where(probe_type >= 0, mask & (type_id[safe] == probe_type), mask)
    for i in range(n_pairs):
        mask = mask & ((rows == pair_vals[i]).sum(axis=1) >= pair_cnts[i])
    return mask


@jax.jit
def dedup_sorted(local, valid):
    """Sort candidates by row id and invalidate duplicates (used after
    union-over-position unordered probes).  Returns (sorted_local, keep)."""
    key = jnp.where(valid, local, INVALID_ROW)
    order = jnp.argsort(key)
    s = key[order]
    first = jnp.concatenate([jnp.ones((1,), dtype=bool), s[1:] != s[:-1]])
    keep = first & (s != INVALID_ROW)
    return s, keep


@jax.jit
def count_valid(valid):
    return valid.sum(dtype=jnp.int32)
