"""merge_sorted_index (storage/delta.py) against a plain numpy stable
merge: the merged keys and perm, element for element, over key dtype,
delta class and the layouts a commit can produce.  The device
formulation builds every slot by reading (shift networks, no scatter);
what it must return is exactly what a stable sort of base ++ delta
returns — ties place the base first."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from das_tpu.storage.delta import merge_sorted_index

DTYPES = (np.int32, np.int64)
DELTA_CLASSES = (64, 1024, 65536)
LAYOUTS = (
    "all_pads", "before", "after", "interleaved", "ties", "pad_tail_cap",
)

_merge = jax.jit(merge_sorted_index, static_argnames="size")


def _stable_merge(bk, bp, dk, dp, size):
    keys = np.concatenate([bk, dk])
    order = np.argsort(keys, kind="stable")   # equal keys: base first
    return keys[order][:size], np.concatenate([bp, dp])[order][:size]


def _case(layout, dtype, nd, rng):
    """(base_keys, base_perm, delta_keys, delta_perm, size): sorted keys,
    delta pads at the dtype's maximum, delta perm offset past the base's
    real rows — what _stage_delta_merge hands the program."""
    kmax = np.iinfo(dtype).max
    top = 1 << 20
    n_real, cap, d_real = 3000, 3000, max(nd // 2, 1)
    size = None
    base = np.sort(rng.integers(top, 2 * top, n_real)).astype(dtype)
    if layout == "all_pads":
        d_real = 0
        delta = np.empty(0, dtype)
    elif layout == "before":
        delta = np.sort(rng.integers(0, top, d_real)).astype(dtype)
    elif layout == "after":
        delta = np.sort(rng.integers(2 * top, 3 * top, d_real)).astype(dtype)
    elif layout == "interleaved":
        delta = np.sort(rng.integers(0, 3 * top, d_real)).astype(dtype)
    elif layout == "ties":
        base = np.sort(rng.integers(0, 40, n_real)).astype(dtype)
        delta = np.sort(rng.integers(0, 40, d_real)).astype(dtype)
    else:  # pad_tail_cap: the capacity-padded base of _merge_padded
        cap = n_real + nd + 37
        size = cap
        delta = np.sort(rng.integers(0, 3 * top, d_real)).astype(dtype)
    bk = np.full(cap, kmax, dtype)
    bk[:n_real] = base
    bp = np.zeros(cap, np.int32)
    bp[:n_real] = rng.permutation(n_real).astype(np.int32)
    dk = np.full(nd, kmax, dtype)
    dk[:d_real] = delta
    dp = np.zeros(nd, np.int32)
    dp[:d_real] = n_real + np.arange(d_real, dtype=np.int32)
    return bk, bp, dk, dp, size


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("nd", DELTA_CLASSES)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_merge_matches_stable_merge(dtype, nd, layout):
    rng = np.random.default_rng(
        [DTYPES.index(dtype), nd, LAYOUTS.index(layout)]
    )
    bk, bp, dk, dp, size = _case(layout, dtype, nd, rng)
    keys, perm = _merge(*map(jnp.asarray, (bk, bp, dk, dp)), size=size)
    want_keys, want_perm = _stable_merge(bk, bp, dk, dp, size)
    assert keys.dtype == dtype and perm.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(keys), want_keys)
    np.testing.assert_array_equal(np.asarray(perm), want_perm)


@pytest.mark.parametrize("nb,nd", [(0, 5), (7, 0), (1, 1), (50, 3), (9, 100)])
def test_merge_odd_shapes(nb, nd):
    """Widths that are no delta class (not a power of two, one side
    empty or shorter than the other) and every cut of the output."""
    rng = np.random.default_rng([nb, nd])
    bk = np.sort(rng.integers(0, 20, nb)).astype(np.int64)
    dk = np.sort(rng.integers(0, 20, nd)).astype(np.int64)
    bp = np.arange(nb, dtype=np.int32)
    dp = nb + np.arange(nd, dtype=np.int32)
    for size in (None, 0, nb, (nb + nd) // 2):
        keys, perm = _merge(*map(jnp.asarray, (bk, bp, dk, dp)), size=size)
        want_keys, want_perm = _stable_merge(bk, bp, dk, dp, size)
        np.testing.assert_array_equal(np.asarray(keys), want_keys)
        np.testing.assert_array_equal(np.asarray(perm), want_perm)
    with pytest.raises(ValueError):
        merge_sorted_index(bk, bp, dk, dp, size=nb + nd + 1)
