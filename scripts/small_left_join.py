"""Chip micro-benchmark (PR 44): a SMALL left side joined into a
multi-million-row type on two shared variables: the verified join
(ops/join.py whole_type_join, which sorts the whole table) against the
way of before PR 44 (the posting index of the first variable, then
verify), lone and under vmap of 32 lanes.  PERF.md section 6 has the
chip's reading.

    chiprun --chips 1 -- python3 scripts/small_left_join.py [right rows]
"""
import json, os, sys, time
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from das_tpu.ops import join as J

N_R, N_L, CAP = int(sys.argv[1]) if len(sys.argv) > 1 else 2_400_000, 16, 1024
rng = np.random.default_rng(7)
targets = np.stack([rng.integers(0, 240_000, N_R), rng.integers(0, 18_000, N_R)], 1).astype(np.int32)
tids = np.full(N_R, 4, np.int32)
key = (tids.astype(np.int64) << 32) | targets[:, 0].astype(np.int64)
perm = np.argsort(key, kind="stable").astype(np.int32)
ks = key[perm]
take = rng.integers(0, N_R, N_L)
lv = targets[take].copy(); lv[N_L // 2:, 1] += 1   # half of them match
lm = np.ones(N_L, bool)
pairs, rvc, extra = ((0, 0), (1, 1)), (0, 1), ()
dev = [jnp.asarray(a) for a in (ks, perm, targets, tids)]

def verified(lv, lm):
    return J.whole_type_join(lv, lm, tuple(dev), np.int32(4), pairs, rvc, extra, CAP)

def parents(lv, lm):
    # the parent's index join: candidates of the first variable, the
    # second column verified after the expansion
    vals, valid, total = J._index_join_impl(lv, lm, dev[0], dev[1], dev[2], np.int32(4),
                                            pairs[:1], rvc, (1,), CAP)
    ok = valid & (vals[:, 2] == vals[:, 1])
    return vals[:, :2], ok, total

def timed(name, fn, *args):
    t0 = time.time(); out = fn(*args); jax.block_until_ready(out); first = time.time() - t0
    ts = []
    for _ in range(10):
        t0 = time.time(); out = fn(*args); jax.block_until_ready(out); ts.append((time.time() - t0) * 1e3)
    rows = np.asarray(out[1]).sum(axis=-1)
    print(json.dumps({"case": name, "first_call_s": round(first, 2), "ms_median": sorted(ts)[5],
                      "ms_min": min(ts), "rows": rows.tolist() if rows.ndim else int(rows)}), flush=True)

print(json.dumps({"device": jax.devices()[0].device_kind, "right_rows": N_R, "left_rows": N_L}), flush=True)
a, b = jnp.asarray(lv), jnp.asarray(lm)
timed("parent_index_then_verify", jax.jit(parents), a, b)
timed("verified_join", jax.jit(verified), a, b)
la, lb = jnp.stack([a] * 32), jnp.stack([b] * 32)
def lanes(f):
    def run(x, y):
        with J.lane_batched():
            return jax.vmap(f)(x, y)
    return jax.jit(run)
timed("parent_index_then_verify_x32", lanes(parents), la, lb)
timed("verified_join_x32", lanes(verified), la, lb)
