"""Chip micro-benchmark (PR 47): ONE shard's share of the mesh program of
the whole-store 3-clause conjunction, in its parts, alone, at the
per-shard shapes of benchmark cell `sharded4-analytic` (FlyBase shape x
0.3 on 4 shards): 1,048,576 gathered left slots (900,000 live) into a
slab's 2,220,890-key posting index, 4,194,304 output slots, 1,048,576
exchange slots a destination.

  first_join    the one-variable join (ops/join.py _index_join_impl):
                the slice lookup, the prefix sum, the expansion (one
                packed row gather of the left side, then `perm` and
                `targets`)
  lookup_slice  of it the slice lookup (ops/join.py _slice_ranges): two
                passes over the slab's index (a key -> ONE 32-bit word;
                `run_end`), the search of the words, the range's end
                read at `lo`
  search        of that the search proper, alone (ops/join.py
                _search_words, device-trace scope `join.index_search`):
                the levels of separators and the descent, ONE gather of
                a row of SEARCH_FANOUT words a level and a probe, over
                all 1,048,576 gathered left slots
  send_left     the left side's send buffer (parallel/fused_sharded.py
                _send_buffer: the mix, ONE sort of (destination, row)
                words, S slices, a row gather into S x q slots)
  send_right    the slab's rows of the probed type, the same way
  verify        the local verified join of two such tables
                (ops/join.py pair_join_received)
  chain         all four in one program: what a shard compiles and runs
                but for the two all_to_alls between send and verify
                (the send buffers stand in for the received tables: the
                same sizes and fill, a shard sends about what it gets)

One JSON line a part: seconds of its compile (persistent cache off, so
every compile is from nothing; the chip host's cores, not the
sandbox's) and milliseconds a call (median and minimum of 10).  PERF.md
section 6 has the chip's reading.

    chiprun --chips 1 -- python3 scripts/mesh_join_parts.py [scale]

`scale` 0.3 is the cell's; a CPU rehearsal takes 0.004.
"""
import json, os, sys, time, types
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from das_tpu.ops import join as J
from das_tpu.parallel import fused_sharded as fs
from das_tpu.planner.cost import cap_for
from das_tpu.planner.search import shard_cap_seed
from das_tpu.storage.delta import capacity_class

jax.config.update("jax_enable_compilation_cache", False)

SCALE = float(sys.argv[1]) if len(sys.argv) > 1 else 0.3
S = 4
# the FlyBase profile x SCALE, the whole store: Member 24 M rows (10 a
# gene), Interacts 3 M, two small types beside them
N_MEMBER, N_INTERACTS, N_OTHER = (int(n * SCALE) for n in (24_000_000, 3_000_000, 870_000))
N_GENES, N_PROCESSES = N_MEMBER // 10, max(1, int(180_000 * SCALE))
T_INTERACTS, T_MEMBER, T_OTHER = 3, 4, 6


# the mesh executor's own seeds for the cell's job, from its rules
_rules = types.SimpleNamespace(n_shards=S)
TERM_CAP = fs.ShardedFusedExecutor._shard_cap(_rules, N_INTERACTS)
N_LEFT = S * TERM_CAP
JOIN_ROWS = N_INTERACTS * 10
CAP = shard_cap_seed(cap_for(JOIN_ROWS, 1 << 24, exact=True), JOIN_ROWS, S)
Q = fs.ShardedFusedExecutor._exchange_slots(_rules, JOIN_ROWS, N_MEMBER)

rng = np.random.default_rng(47)
# this shard's quarter of every type, dealt without regard to content
m_member, m_interacts, m_other = N_MEMBER // S, N_INTERACTS // S, N_OTHER // S
live = m_member + m_interacts + m_other
n_keys = capacity_class(-(-(N_MEMBER + N_INTERACTS + N_OTHER) // S))
member_rows = rng.choice(N_MEMBER, m_member, replace=False)
tids = np.concatenate([np.full(m_interacts, T_INTERACTS), np.full(m_member, T_MEMBER),
                       np.full(m_other, T_OTHER)]).astype(np.int32)
first = np.concatenate([rng.integers(0, N_GENES, m_interacts), member_rows // 10,
                        rng.integers(0, N_GENES, m_other)]).astype(np.int32)
# a gene's k-th process: a fixed pseudo-random function of (gene, k), so
# that two genes share a process at the store's rate
second = np.concatenate([
    rng.integers(0, N_GENES, m_interacts),
    ((member_rows // 10).astype(np.int64) * 2654435761 + (member_rows % 10) * 40503)
    % N_PROCESSES,
    rng.integers(0, N_PROCESSES, m_other)]).astype(np.int32)
order = rng.permutation(live)
tids, first, second = tids[order], first[order], second[order]
targets = np.zeros((n_keys, 2), np.int32)
targets[:live, 0], targets[:live, 1] = first, second
type_ids = np.concatenate([tids, np.full(n_keys - live, -1, np.int32)])
key = (tids.astype(np.int64) << 32) | first.astype(np.int64)
perm = np.argsort(key, kind="stable").astype(np.int32)
keys = np.concatenate([key[perm], np.full(n_keys - live, 2**63 - 1, np.int64)])
perm = np.concatenate([perm, np.zeros(n_keys - live, np.int32)])
# the gathered left: every shard's Interacts rows, each in its own block
lv = np.zeros((N_LEFT, 2), np.int32)
lm = np.zeros(N_LEFT, bool)
for s in range(S):
    at = s * TERM_CAP
    lv[at:at + m_interacts] = rng.integers(0, N_GENES, (m_interacts, 2))
    lm[at:at + m_interacts] = True
TYPE = np.int32(T_MEMBER)
PAIRS2 = ((1, 0), (2, 1))       # ($2, $3) of the left = Member's two variables


def first_join(lv, lm, keys, perm, targets):
    return J._index_join_impl(lv, lm, keys, perm, targets, TYPE, ((0, 0),), (0, 1), (1,), CAP)


def lookup_slice(lv, keys):
    return J._slice_ranges(keys, TYPE, lv[:, 0])


def search(words, lv):
    return J._search_words(words, lv[:, 0])


def send_left(vals, valid):
    return fs._send_buffer(vals, valid, (1, 2), J._SENTINEL_L, S, Q)


def send_right(targets, type_ids):
    return fs._send_buffer(targets, type_ids == TYPE, (0, 1), J._SENTINEL_R, S, Q)


def verify(lbuf, rbuf):
    lbuf, rbuf = lbuf.reshape(S * Q, -1), rbuf.reshape(S * Q, -1)
    return J.pair_join_received(lbuf[:, :3], lbuf[:, 3].astype(bool),
                                rbuf[:, :2], rbuf[:, 2].astype(bool), PAIRS2, (), 2048)


def chain(lv, lm, keys, perm, targets, type_ids):
    vals, valid, total = first_join(lv, lm, keys, perm, targets)
    (lbuf, l_counts), (rbuf, r_counts) = send_left(vals, valid), send_right(targets, type_ids)
    return verify(lbuf, rbuf), total, l_counts, r_counts


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
                  "cpu_count": os.cpu_count(), "keys": n_keys, "left_slots": N_LEFT,
                  "left_rows": S * m_interacts, "slots": CAP, "exchange_slots": Q,
                  "rule_here": J.index_search_method(N_LEFT, n_keys),
                  "search_fanout": J.SEARCH_FANOUT,
                  "search_root_words": J.SEARCH_ROOT_WORDS,
                  "search_levels": J._search_levels(n_keys)}), flush=True)
d = [jnp.asarray(a) for a in (lv, lm, keys, perm, targets, type_ids)]
vals, valid, total = timed("first_join", first_join, *d[:5])
lo, hi = timed("lookup_slice", lookup_slice, d[0], d[2])
lo_w, _found = timed("search", search, jax.jit(J._slice_words)(d[2], TYPE), d[0])
lbuf, l_counts = timed("send_left", send_left, vals, valid)
rbuf, r_counts = timed("send_right", send_right, d[4], d[5])
out, out_valid, rows = timed("verify", verify, lbuf, rbuf)
(c_out, c_valid, c_rows), c_total, c_l, c_r = timed("chain", chain, *d)
same = (int(c_total) == int(total) and int(c_rows) == int(rows)
        and bool((lo_w == lo).all())
        and int(jnp.where(d[1], hi - lo, 0).sum()) == int(total)
        and bool((c_l == l_counts).all()) and bool((c_r == r_counts).all()))
print(json.dumps({
    "first_join_rows": int(total), "left_worst_destination": int(l_counts.max()),
    "right_worst_destination": int(r_counts.max()),
    "exchange_fill": round(max(int(l_counts.max()), int(r_counts.max())) / Q, 4),
    "verified_rows": int(rows), "chain_agrees": same}), flush=True)
sys.exit(0 if same else 1)
