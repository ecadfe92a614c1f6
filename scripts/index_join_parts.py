"""Chip micro-benchmark (PR 45): the FIRST join of the whole-store
3-clause conjunction (ops/join.py _index_join_impl: Interacts x Member
on one variable) in its parts, alone, at the shapes of benchmark cell
`mem-analytic`: 524,288 left slots (300,000 live) into the 2,961,187-key
posting index, 4,194,304 output slots.

  lookup_two_scans   the range lookup as it was: two 64-bit binary
                     searches of the whole index (what a small left
                     side still runs)
  lookup_slice       the slice lookup (ops/join.py _slice_ranges): two
                     passes over the index (a key -> ONE 32-bit word;
                     `run_end`, a reverse running minimum), the search
                     of the words, the range's end read at `lo`
  search             of it the search proper, alone (ops/join.py
                     _search_words, device-trace scope
                     `join.index_search`): the levels of separators
                     (strided slices of the words) and the descent, ONE
                     gather of a row of SEARCH_FANOUT words a level and
                     a probe (a binary search's 22 dependent one-word
                     gathers before PR 49)
  prefix_sum         the int64 prefix sum of the row counts
  expansion          ranges -> output rows (ops/join.py
                     _expand_index_ranges: the slot owner's scatter and
                     running maximum, ONE packed row gather of the left
                     side, the reads through `perm` and `targets`)
  join_two_scans     the whole join, each way
  join_slice

One JSON line a part: seconds of its compile (persistent cache off, so
every compile is from nothing) and milliseconds a call (median and
minimum of 10).  PERF.md section 6 has the chip's readings (PRs 45,
48 and 49).

    chiprun --chips 1 -- python3 scripts/index_join_parts.py [scale]

`scale` 0.1 is the cell's; a CPU rehearsal takes 0.002.
"""
import json, os, sys, time
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from das_tpu.ops import join as J
from das_tpu.storage.delta import capacity_class

jax.config.update("jax_enable_compilation_cache", False)

SCALE = float(sys.argv[1]) if len(sys.argv) > 1 else 0.1
# the FlyBase profile x SCALE: Member 24 M rows (10 a gene), Interacts
# 3 M, two small types beside them; type ids as the store interns them
N_MEMBER, N_INTERACTS, N_OTHER = (int(n * SCALE) for n in (24_000_000, 3_000_000, 870_000))
N_GENES, N_PROCESSES = N_MEMBER // 10, max(1, int(180_000 * SCALE))
T_INTERACTS, T_MEMBER, T_OTHER = 3, 4, 6


def _pow2(n):
    return 1 << max(4, int(n - 1).bit_length())


N_LEFT, CAP = _pow2(N_INTERACTS), _pow2(N_INTERACTS * 10)
rng = np.random.default_rng(45)
live = N_MEMBER + N_INTERACTS + N_OTHER
n_keys = capacity_class(live)
tids = np.concatenate([np.full(N_INTERACTS, T_INTERACTS), np.full(N_MEMBER, T_MEMBER),
                       np.full(N_OTHER, T_OTHER)]).astype(np.int32)
first = np.concatenate([rng.integers(0, N_GENES, N_INTERACTS),
                        np.repeat(np.arange(N_GENES), 10)[:N_MEMBER],
                        rng.integers(0, N_GENES, N_OTHER)]).astype(np.int32)
order = rng.permutation(live)
tids, first = tids[order], first[order]
targets = np.zeros((n_keys, 2), np.int32)
targets[:live, 0] = first
targets[:live, 1] = rng.integers(0, N_PROCESSES, live)
key = (tids.astype(np.int64) << 32) | first.astype(np.int64)
perm = np.argsort(key, kind="stable").astype(np.int32)
keys = np.concatenate([key[perm], np.full(n_keys - live, 2**63 - 1, np.int64)])
perm = np.concatenate([perm, np.zeros(n_keys - live, np.int32)])
lv = np.zeros((N_LEFT, 2), np.int32)
lv[:N_INTERACTS] = rng.integers(0, N_GENES, (N_INTERACTS, 2))
lm = np.arange(N_LEFT) < N_INTERACTS
PAIRS, RVC, EXTRA = ((0, 0),), (0, 1), (1,)
TYPE = np.int32(T_MEMBER)


def forced(rule, fn):
    """`fn` traced with the range lookup's static rule answering `rule`
    ("scan": as it was before PR 45), whatever the shapes."""
    def traced(*args):
        real = J.index_search_method
        J.index_search_method = lambda n_left, n_keys: rule
        try:
            return fn(*args)
        finally:
            J.index_search_method = real
    return traced


def lookup(lv, lm, keys):
    return J._index_ranges(keys, TYPE, lv, 0, lm)


def search(words, lv):
    return J._search_words(words, lv[:, 0])


def counts(lm, lo, hi):
    return jnp.where(lm, hi - lo, 0).astype(jnp.int64)


def expansion(lv, lm, lo, cnt, offsets, perm, targets):
    return J._expand_index_ranges(lv, lm, lo, cnt, offsets, offsets[-1], perm, targets,
                                  RVC, EXTRA, CAP)


def join(lv, lm, keys, perm, targets):
    return J._index_join_impl(lv, lm, keys, perm, targets, TYPE, PAIRS, RVC, EXTRA, CAP)


def timed(name, fn, *args):
    t0 = time.time()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.time() - t0
    out = jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(10):
        t0 = time.time()
        jax.block_until_ready(compiled(*args))
        ts.append((time.time() - t0) * 1e3)
    print(json.dumps({"part": name, "compile_s": round(compile_s, 2),
                      "ms_median": round(sorted(ts)[5], 3), "ms_min": round(min(ts), 3)}),
          flush=True)
    return out


dev = jax.devices()[0]
print(json.dumps({"device": dev.device_kind, "platform": dev.platform, "scale": SCALE,
                  "keys": n_keys, "left_slots": N_LEFT, "left_rows": N_INTERACTS,
                  "slots": CAP, "rule_here": J.index_search_method(N_LEFT, n_keys),
                  "search_fanout": J.SEARCH_FANOUT,
                  "search_root_words": J.SEARCH_ROOT_WORDS,
                  "search_levels": J._search_levels(n_keys)}),
      flush=True)
d_lv, d_lm, d_keys, d_perm, d_targets = (jnp.asarray(a) for a in (lv, lm, keys, perm, targets))
lo_s, hi_s = timed("lookup_two_scans", forced("scan", lookup), d_lv, d_lm, d_keys)
lo, hi = timed("lookup_slice", forced(J.SLICE_SEARCH, lookup), d_lv, d_lm, d_keys)
lo_w, _found = timed("search", search, jax.jit(J._slice_words)(d_keys, TYPE), d_lv)
cnt = jax.jit(counts)(d_lm, lo, hi)
same = bool((cnt == jax.jit(counts)(d_lm, lo_s, hi_s)).all()) and bool(
    jnp.where(cnt > 0, lo == lo_s, True).all()) and bool((lo_w == lo).all())
offsets = timed("prefix_sum", J._cumsum_i64, cnt)
timed("expansion", expansion, d_lv, d_lm, lo, cnt, offsets, d_perm, d_targets)
a = timed("join_two_scans", forced("scan", join), d_lv, d_lm, d_keys, d_perm, d_targets)
b = timed("join_slice", forced(J.SLICE_SEARCH, join), d_lv, d_lm, d_keys, d_perm, d_targets)
same = same and all(bool((x == y).all()) for x, y in zip(a, b))
print(json.dumps({"rows": int(b[2]), "same_ranges_and_rows": same}), flush=True)
sys.exit(0 if same else 1)
