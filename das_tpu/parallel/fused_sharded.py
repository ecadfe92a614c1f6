"""Single-dispatch sharded execution of compiled conjunctive plans.

Round-1's sharded pipeline (parallel/sharded_db.py) launched one shard_map
program per stage, synced exact counts to the host between stages, and
joined by all_gathering the FULL right table to every shard — O(S x cap)
ICI traffic per join and a host round trip per stage.  Here the whole plan
— every shard-local probe, term table, join, anti-join and the count
reduction — lowers to ONE shard_map program per plan shape:

  * term probes stay slab-local (zero communication), mirroring Redis
    cluster client-side slot routing except all shards probe in parallel;
  * each join picks its collective statically, from shapes and
    estimates known where the job is built (_exec_job):
      - small right side  -> broadcast-right (one tiled `all_gather` of a
        table that fits in the broadcast budget);
      - large right side  -> HASH-PARTITIONED join: both sides send
        rows to `mix(join_cols) % S` via `all_to_all`, equal keys
        co-locate, and each shard joins only its key range — ICI moves
        each row once instead of S copies;
      - a join INTO a whole-type term (index join: the right side is
        the store's own rows, never a probed table) gathers the LEFT
        onto every shard and lets each probe its own slab — unless it
        shares two or more variables with a left side that, gathered,
        would outweigh the slab (pair_join_partitions): then both
        sides go to the key's owner and each shard VERIFIES its own
        key range (the whole-store 3-clause conjunction's second join:
        9 M left rows at FlyBase scale 0.3).  The join on ONE variable
        always gathers: a key's postings lie on every slab, so every
        shard has to see every probe;
  * negation filters broadcast the (small) tabu tables once;
  * exact counts reduce in-program (`psum` for totals, `pmax` for
    per-shard capacity checks) into one replicated stats vector — the
    host fetches it in a single transfer and decides overflow/reseed,
    exactly like the single-device fused executor (query/fused.py).

Capacity discipline matches query/fused.py: all shapes static, learned per
plan signature, doubled on overflow (per-shard probe ranges, per-join
output rows, and per-destination exchange slots — the hash-partition
equivalent of the reference's hub-key skew problem)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from das_tpu import obs
from das_tpu.obs.registry import PAIR_PARTITION_SCOPE
from das_tpu.ops.counters import record_dispatch
from das_tpu.ops.join import (
    _SENTINEL_L,
    _SENTINEL_R,
    _anti_join_impl,
    _join_tables_impl,
    _mix_columns,
    pair_join_received,
    whole_type_join,
)
from das_tpu.parallel.mesh import SHARD_AXIS
from das_tpu.query.fused import (
    ROUTE_CTYPE,
    ROUTE_TYPE,
    ROUTE_TYPE_POS,
    FusedResult,
    FusedTermSig,
    ResultCache,
    _GroupHooks,
    _pow2_at_least,
    _probe,
    _TreeExecJob,
    apply_index_joins,
    canonical_tree_names,
    clamp_index_terms,
    conj_stats_len,
    dispatch_pending,
    estimate_plan_rows,
    fold_join_meta,
    lanes_program,
    order_plans,
    remember_caps,
    prepare_tree_job,
    run_tree_job,
    same_positive_order,
    settle_pending,
    settle_pending_iter,
)
from das_tpu.ops.join import _dedup_table_impl

#: right tables whose capacity fits here are broadcast (one all_gather);
#: larger ones hash-partition with all_to_all
BROADCAST_LIMIT = 4096

#: a probed range of this many rows a shard is sized near its even
#: share (ShardedFusedExecutor._shard_cap)
LARGE_RANGE_ROWS = 1 << 16


@dataclass(frozen=True)
class ShardedPlanSig:
    terms: Tuple[FusedTermSig, ...]
    term_caps: Tuple[int, ...]   # per-shard probe capacities
    join_caps: Tuple[int, ...]   # per-shard join output capacities
    #: per join, the slots a destination has in an exchange (both sides
    #: of the join use the one figure); 0 = no exchange: a table join
    #: broadcasts its right side, an index join gathers its left
    exch_caps: Tuple[int, ...]
    n_shards: int
    #: per join: -1 = move tables (broadcast or all_to_all); else an INDEX
    #: JOIN into the whole-type term's own rows, which never
    #: materialize as a table.  With no exchange slots: gather the LEFT
    #: once and let every shard probe its own slab (the posting index
    #: at this position for ONE shared variable, the verified join over
    #: the slab's rows for two or more).  With exchange slots (two or
    #: more shared variables and a large left side,
    #: pair_join_partitions): both sides go to `mix(shared columns) %
    #: S` and every shard verifies its own key range.
    index_joins: Tuple[int, ...] = ()
    #: the cost-based planner ordered this plan and seeded its per-shard
    #: capacities — cache-key honesty for the planner A/B
    #: (FusedPlanSig.planned)
    planned: bool = False


class ShardedFusedResult(FusedResult):
    """One mesh conjunction's answer: query/fused.py FusedResult with
    `vals` [S, capF, k] and `valid` [S, capF] row-sharded (a lane of a
    group program hands them as callables, sliced on first use) and
    `host_vals` / `host_valid` their prefetched host copies (one
    transfer with the stats).  A mesh job never reports `overflow`: its
    settle grows the capacity and asks for the program again."""

    __slots__ = ()

    def __init__(
        self, var_names, vals, valid, count, reseed_needed,
        host_vals=None, host_valid=None,
    ):
        super().__init__(
            var_names, vals, valid, count, reseed_needed, False,
            host_vals, host_valid,
        )


class _Moved:
    """Trace-time tally of the bytes ONE mesh program's collectives move
    between chips, summed over the S shards, from the per-shard operand
    shapes: an all_gather hands every shard the other S-1 operands, an
    all_to_all sends (S-1)/S of each shard's buffer, an all-reduce (as
    a ring) sends 2(S-1)/S of the operand from every shard.  The
    collective helpers below add to it as the program is traced;
    `_MeshProgram` adds the sum to counter `mesh.collective_bytes` per
    dispatch.  `left_rows`: the slots the program all_gathers onto
    every shard as the LEFT side of an index join (a shard's operand
    times S), for counter `mesh.left_gathered_rows`."""

    __slots__ = ("S", "bytes", "left_rows")

    def __init__(self, n_shards: int):
        self.S = n_shards
        self.reset()

    def reset(self) -> None:
        """A re-trace counts the program once."""
        self.bytes = 0
        self.left_rows = 0

    def gathered(self, x) -> None:
        self.bytes += self.S * (self.S - 1) * x.size * x.dtype.itemsize

    def exchanged(self, buf) -> None:
        self.bytes += (self.S - 1) * buf.size * buf.dtype.itemsize

    def reduced(self, x) -> None:
        self.bytes += 2 * (self.S - 1) * x.size * x.dtype.itemsize


class _MeshProgram:
    """A jitted mesh program beside the tally of what its collectives
    move: a call enqueues it (async, no sync) and, with tracing on,
    adds the tally to counters `mesh.collective_bytes` and
    `mesh.left_gathered_rows`."""

    __slots__ = ("fn", "moved")

    def __init__(self, fn, moved: _Moved):
        self.fn = fn
        self.moved = moved

    def __call__(self, *args):
        out = self.fn(*args)
        if obs.enabled():
            obs.counter("mesh.collective_bytes").inc(self.moved.bytes)
            if self.moved.left_rows:
                obs.counter("mesh.left_gathered_rows").inc(
                    self.moved.left_rows)
        return out


#: most rows `_repartition` places in its send buffer by a scatter; a
#: longer table is placed by ONE sort of 32-bit words and S slices.  On
#: a v5e a scatter writes a row in tens to hundreds of ns (a
#: whole-table scatter of 3 M rows was 1.27 s of a commit, PERF.md §6 PR
#: 27) where a sort moves an element in 2-3 ns and a gather reads a row
#: in 6-26; a short table keeps the scatter, which compiles in no time
#: and, under the lanes of a group program, batches without a sort
SCATTER_PLACE_MAX_ROWS = 1 << 16


def _send_order(dest, valid, S: int, q: int):
    """Which row fills which slot of the [S, q] send buffer, for a LONG
    table: (`src` [S * q] row indexes, `live` [S * q], per-destination
    row counts [S]).  Slot (d, s) takes the s-th valid row bound for
    shard d in row order, as the scatter of a short table does; rows
    past q are dropped there as here (the occupancy tells the host).

    One unstable sort of ONE int32 operand: the word `(destination <<
    bits) | row`, rows that take no part under destination S, so the
    rows of a destination lie together in row order and start where the
    counts of the destinations before it end: S dynamic slices of q."""
    n = dest.shape[0]
    bits = max(1, (n - 1).bit_length())
    assert (S + 1) << bits <= 1 << 31, "destination and row share 31 bits"
    d = jnp.where(valid, dest, S).astype(jnp.int32)
    word = (d << bits) | jnp.arange(n, dtype=jnp.int32)
    (order,) = lax.sort((word,), num_keys=1, is_stable=False)
    counts = (
        d[:, None] == jnp.arange(S, dtype=jnp.int32)[None, :]
    ).sum(axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    # a slice that would run past the end is moved back by
    # dynamic_slice: pad, so a destination's slice starts where it says
    order = jnp.concatenate([order, jnp.zeros((q,), dtype=jnp.int32)])
    slot = jnp.arange(q, dtype=jnp.int32)
    src = jnp.concatenate([
        lax.dynamic_slice(order, (starts[k],), (q,)) for k in range(S)
    ]) & ((1 << bits) - 1)
    live = jnp.concatenate([slot < counts[k] for k in range(S)])
    return src, live, counts


def _send_buffer(vals, valid, cols, sentinel, S: int, q: int):
    """`_repartition`'s local half: the [S, q, k + 1] buffer whose block
    d holds this shard's rows bound for shard `mix(cols) % S == d`
    (validity as the extra column: ONE all_to_all moves the table) and
    the per-destination row counts [S].  Filled by a scatter for a short
    table and from one sort for a long one (SCATTER_PLACE_MAX_ROWS, by
    static shape): the same rows in the same slots either way, rows past
    `q` dropped."""
    n, k = vals.shape
    key = _mix_columns(vals, cols, valid, sentinel)
    dest = ((key % S) + S) % S
    if n > SCATTER_PLACE_MAX_ROWS:
        src, live, dest_counts = _send_order(dest, valid, S, q)
        buf = jnp.concatenate(
            [jnp.where(live[:, None], vals[src], 0),
             live.astype(vals.dtype)[:, None]], axis=1,
        ).reshape(S, q, k + 1)
        return buf, dest_counts
    dest = jnp.where(valid, dest, S - 1).astype(jnp.int32)
    onehot = dest[:, None] == jnp.arange(S, dtype=jnp.int32)[None, :]
    onehot = onehot & valid[:, None]
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    slot = jnp.take_along_axis(rank, dest[:, None], axis=1)[:, 0]
    dest_counts = onehot.sum(axis=0, dtype=jnp.int32)
    # invalid rows or overflow slots get slot >= q -> dropped by the scatter
    slot = jnp.where(valid, slot, q)
    packed = jnp.concatenate([vals, valid.astype(vals.dtype)[:, None]], axis=1)
    buf = jnp.zeros((S, q, k + 1), dtype=vals.dtype).at[dest, slot].set(
        packed, mode="drop"
    )
    return buf, dest_counts


def _repartition(vals, valid, cols, sentinel, S: int, q: int, moved: _Moved):
    """Send rows to shard `mix(cols) % S` via one all_to_all.

    Returns ([S*q, k] rows now resident on the key-owning shard, their
    mask, and this shard's worst per-destination occupancy for overflow
    detection).  Equal join keys always co-locate because the destination
    is a function of the same mix the join verifies exactly."""
    k = vals.shape[1]
    buf, dest_counts = _send_buffer(vals, valid, cols, sentinel, S, q)
    with jax.named_scope("mesh.repartition"):
        recv = lax.all_to_all(buf, SHARD_AXIS, split_axis=0, concat_axis=0)
    moved.exchanged(buf)
    recv = recv.reshape(S * q, k + 1)
    return recv[:, :k], recv[:, k].astype(bool), dest_counts.max()


def _gather_packed(vals, valid, moved: _Moved):
    """Broadcast a table to every shard with ONE collective (validity
    packed as an extra column)."""
    k = vals.shape[1]
    packed = jnp.concatenate([vals, valid.astype(vals.dtype)[:, None]], axis=1)
    with jax.named_scope("mesh.gather_packed"):
        full = lax.all_gather(packed, SHARD_AXIS, tiled=True)
    moved.gathered(packed)
    return full[:, :k], full[:, k].astype(bool)


def _worst_shard(n, moved: _Moved):
    """The worst shard's row count (a capacity-retry figure), as int32 —
    a declared collective helper (parallel/mesh.py COLLECTIVE_SITES).
    Pair totals are int64, and the TPU compiler lowers 64-bit all-reduce
    for Sum only ("UNIMPLEMENTED: Supported lowering only of Sum all
    reduce", v5e 2x2 AOT, tests/test_tpu_compile.py): the max runs on
    the value clamped to int32, which loses nothing — the figure is only
    ever compared with capacities <= max_result_capacity."""
    n = jnp.minimum(n, jnp.iinfo(jnp.int32).max).astype(jnp.int32)
    moved.reduced(n)
    with jax.named_scope("mesh.worst_shard"):
        return lax.pmax(n, SHARD_AXIS)


def _global_sum(n, moved: _Moved):
    """The sum over the shards of a per-shard int32 figure (ONE psum) —
    a declared collective helper (parallel/mesh.py COLLECTIVE_SITES,
    daslint DL009)."""
    moved.reduced(n)
    with jax.named_scope("mesh.global_sum"):
        return lax.psum(n, SHARD_AXIS)


def _global_count(valid, moved: _Moved):
    """Global surviving-row count of a row-sharded validity mask."""
    return _global_sum(valid.sum(dtype=jnp.int32), moved)


def pair_join_partitions(n_pairs: int, left_slots: int, n_shards: int,
                         right_rows: int) -> bool:
    """Static per-shape choice of how a join INTO a whole-type term
    brings its sides together on the mesh (_exec_job asks it where the
    job is built, as ops/join.py index_search_method is asked at its
    shapes; `left_slots`: the left side's capacity a shard, `right_rows`:
    a slab's rows of the probed arity).  True = PARTITION: both sides go
    to the key's owner by one all_to_all each and every shard verifies
    its own key range.  False = GATHER the left onto every shard and
    join it with the shard's own slab.

    Partition exactly where the join shares two or more variables and
    the gathered left, `n_shards x left_slots`, outweighs the slab.
    The verified join sorts both sides together, so after a gather
    every shard sorts the WHOLE left (the 3-clause whole-store
    conjunction at FlyBase scale 0.3 on 4 shards: 4 x 4.2 M slots
    against a slab of 2.2 M rows, the same work on all four chips, and
    a sort whose first compile passes the statement deadline); after a
    partition a shard sorts about 1/S of each side, and every row
    crosses the interconnect once, not S - 1 times.  A small left side
    (a grounded query's 16 to 2,048 rows a lane against the same slab)
    keeps the gather: one small collective, no exchange buffers.  A
    join on ONE variable gathers whatever the sizes: it reads the
    posting index, a key's postings lie on every slab, so every shard
    has to see every probe, and partitioning the probes would only
    choose which shard misses which rows."""
    return n_pairs >= 2 and n_shards * left_slots > right_rows


def _partitioned_pair_join(
    left_vals, left_valid, index_arrays, type_key,
    pairs, right_var_cols, right_extra, capacity, S: int, q: int,
    moved: _Moved,
):
    """One shard's part of a verified join that PARTITIONS both sides
    (pair_join_partitions; inside a shard_map body): ops/join.py
    whole_type_join's arguments, `index_arrays` this slab's, plus the
    shards and the per-destination exchange slots.  Both sides go to
    the owner of `mix(shared columns) % S` by one all_to_all each: the
    left as it stands, the right = this slab's rows of the probed type,
    their variable columns only.  Equal keys co-locate (the destination
    is a function of the columns the join verifies), so each shard
    verifies its own key range with the one-chip join's own
    sort-count-expand (ops/join.py pair_join_received) and the union
    over shards is the join.  Returns (vals, valid, total, occupancy):
    this shard's rows of the join, their exact count, and its worst
    destination's rows over both sides — past `q` the exchange dropped
    rows, and the host grows the slots and asks again.  The whole step
    sits in device-trace scope `mesh.pair_partition`, each exchange in
    `mesh.repartition`, the local verify in `join.pair_verify`."""
    _keys, _perm, targets, type_ids = index_arrays
    with jax.named_scope(PAIR_PARTITION_SCOPE):
        lv, lm, l_occ = _repartition(
            left_vals, left_valid, tuple(lc for lc, _ in pairs),
            _SENTINEL_L, S, q, moved,
        )
        rv, rm, r_occ = _repartition(
            targets[:, jnp.array(right_var_cols, dtype=jnp.int32)],
            type_ids == jnp.asarray(type_key).astype(type_ids.dtype),
            tuple(rc for _, rc in pairs), _SENTINEL_R, S, q, moved,
        )
        vals, valid, total = pair_join_received(
            lv, lm, rv, rm, pairs, right_extra, capacity
        )
    return vals, valid, total, jnp.maximum(l_occ, r_occ)


def _trace_sharded_conj(sig: ShardedPlanSig, bucket_arrays, keys, fixed_vals,
                        moved: _Moved):
    """Trace ONE conjunction inside a shard_map body — shard-local
    probes/joins, the per-step collective choice, and the in-program
    stat reductions.  Returns (acc_vals, acc_valid, stats_list) with
    stats_list = [count, reseed, any_pos_empty, *per-term worst shard
    ranges, *per-join worst shard totals, *per-partitioned-join worst
    destination occupancy] as traced scalars.  This is
    build_fused_sharded's whole body, extracted so the sharded
    whole-tree program (build_sharded_tree_fused, ISSUE 10) can trace
    several sites in one mesh executable.  Every collective goes
    through a declared helper (parallel/mesh.py COLLECTIVE_SITES,
    daslint DL009) that names its scope for the device trace and adds
    its bytes to `moved`."""
    S = sig.n_shards
    positives, _negatives, names, join_meta, anti_meta = fold_join_meta(sig.terms)
    index_joins = sig.index_joins or tuple(
        [-1] * max(0, len(positives) - 1)
    )
    index_right = {
        positives[1 + n]: n for n, p in enumerate(index_joins) if p >= 0
    }

    # blocks arrive with a leading [1, ...] slab dim; the probe body
    # itself is the single-device one (query/fused.py _probe) — probes
    # are slab-local, zero communication
    tables = {}
    term_ranges = []
    pos_count = {}
    for i, t in enumerate(sig.terms):
        arrays = tuple(a[0] for a in bucket_arrays[i])
        if i in index_right:
            # index-join right side: never materialized.  Candidate
            # count = the type's slab key ranges, summed over shards.
            keys_sorted = arrays[0]
            tid = jnp.asarray(keys[i], jnp.int64)
            lo = jnp.searchsorted(keys_sorted, tid << 32, side="left")
            hi = jnp.searchsorted(keys_sorted, (tid + 1) << 32, side="left")
            pos_count[i] = _global_sum((hi - lo).astype(jnp.int32), moved)
            tables[i] = None
            term_ranges.append(jnp.int32(0))
            continue
        vals, mask, rng = _probe(
            t, arrays, keys[i], fixed_vals[i], sig.term_caps[i]
        )
        tables[i] = (vals, mask)
        pos_count[i] = _global_count(mask, moved)
        term_ranges.append(_worst_shard(rng, moved))

    any_pos_empty = jnp.bool_(False)
    for i in positives:
        any_pos_empty = any_pos_empty | (pos_count[i] == 0)

    acc_vals, acc_valid = tables[positives[0]]
    if len(positives) > 1:
        reseed = pos_count[positives[0]] == 0
    else:
        reseed = jnp.bool_(False)
    join_totals = []
    exch_stats = []
    for n, i in enumerate(positives[1:]):
        pairs, extra = join_meta[n]
        jc = sig.join_caps[n]
        q = sig.exch_caps[n]
        if index_joins[n] >= 0:
            if q > 0:
                acc_vals, acc_valid, total, occ = _partitioned_pair_join(
                    acc_vals, acc_valid,
                    tuple(a[0] for a in bucket_arrays[i]), keys[i], pairs,
                    sig.terms[i].var_cols, extra, jc, S, q, moved,
                )
                exch_stats.append(_worst_shard(occ, moved))
            else:
                # gather the left once; every shard joins it with its
                # own slab (the posting index of ONE shared variable,
                # the slab's rows verified on two or more) — union over
                # shards is the full join (each link lives in exactly
                # one slab).  Small left sides, and every join on one
                # variable (pair_join_partitions)
                lv_full, lm_full = _gather_packed(acc_vals, acc_valid, moved)
                moved.left_rows += lv_full.shape[0]
                acc_vals, acc_valid, total = whole_type_join(
                    lv_full, lm_full,
                    tuple(a[0] for a in bucket_arrays[i]), keys[i], pairs,
                    sig.terms[i].var_cols, extra, jc,
                )
                exch_stats.append(jnp.int32(0))
            join_totals.append(_worst_shard(total, moved))
            if n < len(positives) - 2:
                reseed = reseed | (_global_count(acc_valid, moved) == 0)
            continue
        rv, rm = tables[i]
        if q == 0:
            # broadcast-right: ONE tiled all_gather of the small side
            # (validity packed as an extra column)
            rv_full, rm_full = _gather_packed(rv, rm, moved)
            acc_vals, acc_valid, total = _join_tables_impl(
                acc_vals, acc_valid, rv_full, rm_full,
                pairs, extra, jc,
            )
            exch_stats.append(jnp.int32(0))
        else:
            # hash-partitioned: co-locate equal keys, join locally
            lcols = tuple(lc for lc, _ in pairs)
            rcols = tuple(rc for _, rc in pairs)
            lv2, lm2, l_occ = _repartition(
                acc_vals, acc_valid, lcols, _SENTINEL_L, S, q, moved
            )
            rv2, rm2, r_occ = _repartition(
                rv, rm, rcols, _SENTINEL_R, S, q, moved
            )
            acc_vals, acc_valid, total = _join_tables_impl(
                lv2, lm2, rv2, rm2, pairs, extra, jc
            )
            exch_stats.append(
                _worst_shard(jnp.maximum(l_occ, r_occ), moved)
            )
        join_totals.append(_worst_shard(total, moved))
        if n < len(positives) - 2:
            reseed = reseed | (_global_count(acc_valid, moved) == 0)

    for i, pairs in anti_meta:
        rv, rm = tables[i]
        rv_full, rm_full = _gather_packed(rv, rm, moved)
        acc_valid = _anti_join_impl(
            acc_vals, acc_valid, rv_full, rm_full, pairs
        )

    count = _global_count(acc_valid, moved)
    reseed = reseed & ~any_pos_empty
    stats_list = [
        count,
        reseed.astype(jnp.int32),
        any_pos_empty.astype(jnp.int32),
        *term_ranges,
        *join_totals,
        *exch_stats,
    ]
    return acc_vals, acc_valid, stats_list


def _site_in_specs(sig: ShardedPlanSig):
    """shard_map in_specs of ONE conjunction's (bucket_arrays, keys,
    fixed_vals): the four index arrays of every term row-sharded, the
    probe keys and fixed values replicated."""
    spec = P(SHARD_AXIS)
    return (
        tuple(tuple(spec for _ in range(4)) for _ in sig.terms),
        tuple(P() for _ in sig.terms),
        tuple(P() for _ in sig.terms),
    )


def _sharded_body(sig: ShardedPlanSig, count_only: bool, moved: _Moved):
    """The per-shard traced function of one sharded plan signature
    (query/fused.py _fused_body's mesh twin): what build_fused_sharded
    runs for one query and build_fused_sharded_group for the lanes of a
    group.  Returns (vals [cap, k], valid [cap], stats), stats alone
    when count_only; the collectives' bytes add to `moved`."""

    def fn(bucket_arrays, keys, fixed_vals):
        acc_vals, acc_valid, stats_list = _trace_sharded_conj(
            sig, bucket_arrays, keys, fixed_vals, moved
        )
        stats = jnp.stack(stats_list)
        if count_only:
            return stats
        return acc_vals, acc_valid, stats

    return fn


def build_fused_sharded(sig: ShardedPlanSig, mesh, count_only: bool = False,
                        moved: Optional[_Moved] = None):
    """Lower one sharded plan signature to a single shard_map program.

    Call convention: fn(bucket_arrays, keys, fixed_vals) like
    query/fused.py build_fused, with bucket arrays shaped [S, m(, a)].
    Stats layout (replicated):
      [count, reseed, any_pos_empty,
       *per-term worst shard ranges, *per-join worst shard totals,
       *per-partitioned-join worst destination occupancy]
    The conjunction body itself lives in _trace_sharded_conj (shared
    with the whole-tree mesh program builder).  `moved`, when given,
    holds after the first call what the program's collectives move.
    """
    _pos, _neg, names, _jm, _am = fold_join_meta(sig.terms)
    moved = moved if moved is not None else _Moved(sig.n_shards)
    shard = _sharded_body(sig, count_only, moved)

    def body(bucket_arrays, keys, fixed_vals):
        moved.reset()
        out = shard(bucket_arrays, keys, fixed_vals)
        if count_only:
            return out
        vals, valid, stats = out
        return vals[None], valid[None], stats

    spec = P(SHARD_AXIS)
    out_specs = P() if count_only else (spec, spec, P())
    fn = shard_map(
        body, mesh=mesh, in_specs=_site_in_specs(sig), out_specs=out_specs
    )
    return fn, names


def build_fused_sharded_group(sig: ShardedPlanSig, mesh, count_only,
                              key_axes, fval_axes,
                              moved: Optional[_Moved] = None):
    """build_fused_sharded's program for a GROUP of same-signature mesh
    jobs (_ShardedExecJob's group hooks): the same body under
    query/fused.py lanes_program INSIDE the shard_map, so the bucket
    arrays ride unbatched (the slab re-layout `a[0]`, the key splits
    and every other table-sized op run once a PROGRAM, not once a
    query) and every collective carries the lanes axis: one all_gather
    moves the lanes' tables together.  Call convention:
    fn(bucket_arrays, keys, fixed_vals) over stack_lanes' inputs;
    outputs vals [lanes, S, cap, k] and valid [lanes, S, cap] sharded
    on axis 1, stats [lanes, n] replicated (stats alone when
    count_only): a lane of them is build_fused_sharded's output.
    `moved` holds after the first call what the program's collectives
    move: the per-lane tally (the helpers see a lane's shapes under
    vmap) times the lanes, the padded ones included."""
    _pos, _neg, names, _jm, _am = fold_join_meta(sig.terms)
    moved = moved if moved is not None else _Moved(sig.n_shards)
    lane_moved = _Moved(sig.n_shards)
    lanes = lanes_program(
        _sharded_body(sig, count_only, lane_moved), key_axes, fval_axes
    )

    def body(bucket_arrays, keys, fixed_vals):
        lane_moved.reset()
        out = lanes(bucket_arrays, keys, fixed_vals)
        stats = out if count_only else out[2]
        moved.bytes = stats.shape[0] * lane_moved.bytes
        moved.left_rows = stats.shape[0] * lane_moved.left_rows
        if count_only:
            return stats
        return out[0][:, None], out[1][:, None], stats

    spec = P(None, SHARD_AXIS)
    out_specs = P() if count_only else (spec, spec, P())
    fn = shard_map(
        body, mesh=mesh, in_specs=_site_in_specs(sig), out_specs=out_specs
    )
    return fn, names


@dataclass(frozen=True)
class ShardedTreeSig:
    """Shape-static description of ONE whole-tree fused MESH program
    (ISSUE 10) — the sharded twin of query/fused.py FusedTreeSig.
    Nested ShardedPlanSigs carry per-site per-shard capacities and
    collective choices, so cache-key honesty is inherited (daslint
    DL002)."""

    sites: Tuple[ShardedPlanSig, ...]
    neg: Optional[ShardedPlanSig] = None


def build_sharded_tree_fused(sig: ShardedTreeSig, mesh, count_only: bool = False,
                             moved: Optional[_Moved] = None):
    """Lower a whole Or/negation plan tree to ONE shard_map program:
    every conjunction site traces via _trace_sharded_conj (shard-local
    bodies, declared collectives), the positive branches union with a
    per-shard concat + SHARD-LOCAL dedup, and the optional negative
    branch anti-joins the gathered union on all columns.

    Shard-local dedup is deliberate (the sharded_tree.py ShardedTreeOps
    rule): cross-shard duplicate assignments — possible when two Or
    branches ground the same answer through links living on different
    shards — survive on device and are removed by the host
    assignment-set identity at materialization, which establishes
    reference-exact dedup semantics anyway.  The difference branch DOES
    gather the union whole first (one packed all_gather): a negative
    row must be removed on whichever shard it lives, not only where its
    union twin happens to live.  The replicated final count therefore
    upper-bounds the distinct answer count (matched verdicts only need
    count > 0 per site, which psum reports exactly).

    Call convention: fn(*site_inputs), one (bucket_arrays, keys,
    fixed_vals) triple per positive site then one for the negative
    site.  Stats layout: [final_count, *site_0_block, ..., *neg_block]
    with each block exactly build_fused_sharded's stats vector."""
    out_names = canonical_tree_names(sig.sites[0].terms)
    K = len(out_names)
    perms = []
    for ssig in sig.sites + ((sig.neg,) if sig.neg is not None else ()):
        _p, _n, names, _jm, _am = fold_join_meta(ssig.terms)
        assert tuple(sorted(names)) == out_names, (
            "tree fusion requires one shared variable universe"
        )
        perms.append(tuple(names.index(v) for v in out_names))
    moved = moved if moved is not None else _Moved(sig.sites[0].n_shards)

    def body(*site_inputs):
        moved.reset()
        blocks = []
        parts = []
        for i, ssig in enumerate(sig.sites):
            ba, ks, fv = site_inputs[i]
            v, m, sl = _trace_sharded_conj(ssig, ba, ks, fv, moved)
            blocks.append(sl)
            parts.append((v[:, jnp.asarray(perms[i], dtype=jnp.int32)], m))
        union_vals = jnp.concatenate([v for v, _ in parts], axis=0)
        union_valid = jnp.concatenate([m for _, m in parts], axis=0)
        if sig.neg is not None:
            ba, ks, fv = site_inputs[len(sig.sites)]
            nv, nm, nsl = _trace_sharded_conj(sig.neg, ba, ks, fv, moved)
            blocks.append(nsl)
            nv = nv[:, jnp.asarray(perms[-1], dtype=jnp.int32)]
            # replicate the minus side (tree.py difference() contract);
            # the union is only a membership set here — duplicates are
            # harmless, so the raw concat gathers without a dedup sort
            uv_full, um_full = _gather_packed(
                union_vals, union_valid, moved
            )
            all_pairs = tuple((c, c) for c in range(K))
            nm = _anti_join_impl(nv, nm, uv_full, um_full, all_pairs)
            out_vals, out_valid = nv, nm
        else:
            # shard-local dedup only (module docstring): cross-shard
            # duplicates die in the host assignment set
            out_vals, out_valid, _local = _dedup_table_impl(
                union_vals, union_valid
            )
        count = _global_count(out_valid, moved)
        stats = jnp.stack(
            [count] + [s for block in blocks for s in block]
        )
        if count_only:
            return stats
        return out_vals[None], out_valid[None], stats

    spec = P(SHARD_AXIS)
    in_specs = tuple(
        _site_in_specs(ssig)
        for ssig in sig.sites + ((sig.neg,) if sig.neg is not None else ())
    )
    out_specs = P() if count_only else (spec, spec, P())
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return fn, out_names


class ShardedFusedExecutor:
    """Per-database cache of compiled sharded plan programs with capacity
    learning — the mesh counterpart of query/fused.py FusedExecutor."""

    def __init__(self, db):
        self.db = db
        self.mesh = db.mesh
        self.n_shards = int(db.mesh.devices.size)
        self.broadcast_limit = BROADCAST_LIMIT
        self._cache: Dict[Tuple, Tuple] = {}
        #: the served path's group programs (the job's group hooks):
        #: (plan_sig, count_only, lanes, key_axes, fval_axes) ->
        #: (_MeshProgram, names)
        self._group_cache: Dict[Tuple, Tuple] = {}
        self._caps: Dict[Tuple, Tuple] = {}
        #: answered-result cache, delta-version guarded (query/fused.py
        #: ResultCache).  The mesh serving path (sharded_db
        #: _run_conjunctive) opts in with execute(use_cache=True); the
        #: incremental-commit counter (sharded_db.refresh ->
        #: storage/delta.py) invalidates on commit, and a FULL
        #: re-partition replaces db.tables and with it this executor.
        self.results = ResultCache(db)
        #: tree-composite cache (query/tree.py) — same version guard,
        #: dropped wholesale with this executor on a full re-partition
        self.tree_results = ResultCache(db)
        #: whole-tree fused mesh programs (ISSUE 10): ShardedTreeSig ->
        #: (jitted fn, names); bounded in _ShardedTreeExecJob.dispatch
        self._tree_progs: Dict[ShardedTreeSig, Tuple] = {}

    # -- plan mapping ------------------------------------------------------

    def _term_args(self, plan):
        sb = self.db.tables.buckets.get(plan.arity)
        if sb is None:
            return None
        if plan.ctype is not None:
            route, p0, extra = ROUTE_CTYPE, -1, ()
            arrays = (sb.key_ctype, sb.order_by_ctype, sb.targets, sb.type_id)
            key = np.int64(plan.ctype)
        elif plan.type_id is not None and plan.fixed:
            p0, v0 = plan.fixed[0]
            route, extra = ROUTE_TYPE_POS, tuple(p for p, _ in plan.fixed[1:])
            arrays = (
                sb.key_type_pos[p0], sb.order_by_type_pos[p0],
                sb.targets, sb.type_id,
            )
            key = np.int64((np.int64(plan.type_id) << 32) | np.int64(v0))
        else:
            assert plan.type_id is not None, "TermPlan without type or ctype"
            route, p0, extra = ROUTE_TYPE, -1, ()
            # the sharded type index stores int64 keys
            arrays = (sb.key_type, sb.order_by_type, sb.targets, sb.type_id)
            key = np.int64(plan.type_id)
        fixed_vals = np.asarray(
            [v for _, v in plan.fixed[1:]] if route == ROUTE_TYPE_POS else [],
            dtype=np.int32,
        )
        sig = FusedTermSig(
            arity=plan.arity,
            route=route,
            p0=p0,
            extra_fixed=extra,
            var_cols=plan.var_cols,
            eq_pairs=plan.eq_pairs,
            var_names=plan.var_names,
            negated=plan.negated,
        )
        return sig, arrays, key, fixed_vals

    def _estimate(self, plan) -> int:
        # shared with the single-device executor; sums the base bucket and
        # any incremental-commit overlay segments (sharded_db.refresh)
        return estimate_plan_rows(self.db, plan)

    def _shard_cap(self, global_est: int) -> int:
        """Per-shard probe capacity: even split plus 2x skew headroom
        (slabs are round-robin, so type/pattern ranges spread evenly; the
        headroom plus overflow retry covers hub-heavy skew).  A LONG
        range (LARGE_RANGE_ROWS a shard or more: a whole type probed
        unbound) takes an eighth of headroom instead, as the planner's
        long join buffers do (planner/search.py shard_cap_seed): rows
        dealt round-robin put such a range within a per mille of even
        on every slab, and where the range is gathered as a join's left
        side every slot is a probe on every chip (the whole-store
        conjunction's Interacts side at FlyBase scale 0.3: 225,000 rows
        a shard seeded 524,288 slots, 2.1 M probes a chip for 0.9 M
        rows)."""
        per = -(-max(global_est, 1) // self.n_shards)
        if per >= LARGE_RANGE_ROWS:
            return _pow2_at_least(per + per // 8)
        return _pow2_at_least(2 * per)

    def _exchange_slots(self, left_rows: int, right_rows: int) -> int:
        """Per-destination slots of a partitioned verified join, for
        both its sides: the larger side's rows (whole-store figures:
        the planner's estimate of the left side where there is one,
        else its capacity; the right type's exact row count) dealt
        over S sources x S destinations by a hash, with an eighth of
        headroom before the power of two.  No 2x skew headroom as in
        _shard_cap: the destination is a 64-bit mix of two or more
        columns, so a hub of ONE column spreads, and the tables this
        rule meets are long (a million rows a destination deviate by a
        per mille); an overflow is a counted retry that grows the slots
        exactly as before."""
        per = -(-max(left_rows, right_rows, 1) // self.n_shards ** 2)
        return _pow2_at_least(max(16, per + per // 8))

    # -- execution ---------------------------------------------------------

    def _exec_job(self, plans, count_only: bool) -> Optional["_ShardedExecJob"]:
        """Prepare one mesh execution's state (ordering, term args,
        capacity seeds incl. the per-join collective choice).  None when a
        bucket is missing or the merged caps exceed the configured ceiling
        — the caller falls back to the staged mesh path, as before.

        The collective choice per join, from the job's static shapes: a
        table join broadcasts a right side that fits the broadcast
        budget and hash-partitions a larger one; an index join gathers
        its left side unless pair_join_partitions says its two sides
        are better sent to the key's owner (two or more shared
        variables, a left side that gathered would outweigh the slab).

        The cost-based planner hook mirrors the single-device executor
        (query/fused.py _exec_job): behind DasConfig.use_planner it fixes
        join order and PER-SHARD capacity seeds from the same host-side
        degree statistics (the mesh store exposes identical
        host_bucket_segments)."""
        from das_tpu import planner as _planner

        planned = (
            _planner.plan_conjunction(self.db, plans, n_shards=self.n_shards)
            if _planner.enabled(self.db.config) else None
        )
        if planned is not None:
            ordered = [plans[i] for i in planned.order]
        else:
            ordered = order_plans(plans, self._estimate)
        same_order = same_positive_order(ordered, plans)
        plans = ordered
        mapped = []
        for plan in plans:
            m = self._term_args(plan)
            if m is None:
                return None
            mapped.append(m)
        sigs = tuple(m[0] for m in mapped)
        arrays = tuple(m[1] for m in mapped)
        keys = tuple(m[2] for m in mapped)
        fvals = tuple(m[3] for m in mapped)

        cfg = self.db.config
        ests = [self._estimate(p) for p in plans]
        term_caps = tuple(self._shard_cap(e) for e in ests)
        index_joins, index_right, arrays, term_caps = apply_index_joins(
            self.db.tables.buckets, sigs, arrays, term_caps
        )
        n_joins = max(0, sum(1 for p in plans if not p.negated) - 1)
        grounded = [
            e for p, e in zip(plans, ests)
            if p.fixed and p.ctype is None and not p.negated
        ]
        if grounded:
            # the estimator's row bound rides below the configured clamp
            # (query/fused.py _join_cap_seed): an operator-shrunk
            # initial_result_capacity must not seed under the exact
            # grounded row counts — that is a guaranteed retry round
            mg = max(grounded)
            jcap0 = _pow2_at_least(
                max(64, min(cfg.initial_result_capacity, 4 * mg), mg)
            )
        else:
            jcap0 = _pow2_at_least(
                max(cfg.initial_result_capacity // self.n_shards, *term_caps)
            )
        if planned is not None and len(planned.join_cap_seeds) == n_joins:
            join_caps = planned.join_cap_seeds  # per-shard costed seeds
        else:
            join_caps = tuple([jcap0] * n_joins)
        # static per-join collective choice: index-joinable right
        # sides gather the LEFT (one collective, nothing materialized)
        # or, on two or more variables with a large left side,
        # partition both sides (pair_join_partitions); otherwise
        # broadcast the right when its whole table fits the budget,
        # else hash-partition
        pos_sig_idx = [i for i, s in enumerate(sigs) if not s.negated]
        n_pairs = [len(m[0]) for m in fold_join_meta(sigs)[3]]
        exch_caps = []
        for t in range(len(index_joins)):
            if index_joins[t] >= 0:
                right = pos_sig_idx[1 + t]
                left_slots = (
                    join_caps[t - 1] if t else term_caps[pos_sig_idx[0]]
                )
                if pair_join_partitions(
                    n_pairs[t], left_slots, self.n_shards,
                    arrays[right][2].shape[1],
                ):
                    if not t:
                        left_rows = ests[pos_sig_idx[0]]
                    elif planned is not None:
                        left_rows = planned.est_join_rows[t - 1]
                    else:
                        left_rows = self.n_shards * left_slots
                    exch_caps.append(
                        self._exchange_slots(left_rows, ests[right])
                    )
                else:
                    exch_caps.append(0)
                continue
            right_cap = term_caps[pos_sig_idx[1 + t]]
            if right_cap * self.n_shards <= self.broadcast_limit:
                exch_caps.append(0)
            else:
                exch_caps.append(_pow2_at_least(2 * max(jcap0 // self.n_shards, 16)))
        exch_caps = tuple(exch_caps)
        learned = self._caps.get(sigs)
        # length guard (query/fused.py _learned_caps rationale)
        if learned is not None and (
            len(learned[0]) != len(term_caps)
            or len(learned[1]) != len(join_caps)
            or len(learned[2]) != len(exch_caps)
        ):
            learned = None
        if learned is not None:
            term_caps = clamp_index_terms(
                tuple(max(a, b) for a, b in zip(term_caps, learned[0])),
                index_right,
            )
            join_caps = tuple(max(a, b) for a, b in zip(join_caps, learned[1]))
            # a table join exchanges where the learned entry did; an
            # index join where this job's shapes say so (the rule above)
            exch_caps = tuple(
                (0 if (a if n_ij >= 0 else b) == 0 else max(a, b))
                for (a, b), n_ij in zip(zip(exch_caps, learned[2]), index_joins)
            )
        if max(term_caps + join_caps, default=0) > cfg.max_result_capacity:
            return None
        # counted only once the job exists (query/fused.py _exec_job):
        # declines run the staged mesh fallback under legacy accounting
        if planned is not None:
            _planner.record_planned(planned)
        else:
            _planner.PLANNER_COUNTS["greedy"] += 1
        return _ShardedExecJob(
            self, count_only, same_order, sigs, arrays, keys, fvals,
            term_caps, join_caps, exch_caps, index_joins, planned=planned,
        )

    def execute(
        self, plans, count_only: bool = False, use_cache: bool = False
    ) -> Optional[ShardedFusedResult]:
        """use_cache mirrors the single-device executor's contract: the
        serving path (sharded_db._run_conjunctive) opts in; the bare call
        stays uncached so repeated-execute measurements (the mesh scaling
        bench) keep timing the shard_map program, not a dict lookup."""
        if use_cache:
            cache_key = self.results.key(plans, count_only)
            hit = self.results.get(cache_key)
            if hit is not None:
                return hit
            cache_version = self.results.version()
        job = self._exec_job(plans, count_only)
        if job is None:
            return None
        from das_tpu.query.fused import FETCH_COUNTS

        while True:
            out = job.dispatch()
            FETCH_COUNTS["n"] += 1
            if job.settle(jax.device_get(out), out):
                if use_cache:
                    self.results.put(cache_key, job.result, cache_version)
                return job.result

    def dispatch_many(self, plans_lists, count_only: bool = False,
                      cache_only: bool = False):
        """Serving-pipeline phase 1 on the mesh (query/fused.py
        dispatch_many contract): resolve result-cache hits, dedup
        identical in-batch queries, and ENQUEUE each remaining job's first
        shard_map round — asynchronous, no host transfer.  The mesh
        executes this batch while the coalescer settles the previous one
        (the pipeline_depth window now covers mesh tenants too).  With
        cache_only (degraded-mode serving, ISSUE 13 breaker) no shard_map
        program is enqueued: hits answer, misses decline."""
        return dispatch_pending(
            self.results, self._exec_job, plans_lists, count_only,
            cache_only=cache_only,
        )

    def settle_many(self, pending) -> List[Optional[ShardedFusedResult]]:
        """Phase 2: one host transfer per retry round, per-job verdicts,
        version-guarded cache inserts — the shared settle loop
        (query/fused.py settle_pending)."""
        return settle_pending(self.results, pending)

    def settle_many_iter(self, pending):
        """Streaming phase 2 (ISSUE 6): yields (index, ShardedFusedResult)
        as each query's verdict lands — the shared streaming settle loop
        (query/fused.py settle_pending_iter), so mesh tenants' first rows
        reach their clients one RTT after their own dispatch too.  Each
        round's transfer pulls every job's per-shard result slabs to the
        host: span `mesh.fetch` (with tracing on; the interval and the
        attrs of exec.settle_fetch, a group program's block one array
        for all its lanes)."""
        return settle_pending_iter(
            self.results, pending, on_fetch=self._record_fetch
        )

    def _record_fetch(self, t0: float, seconds: float, fetched,
                      attrs) -> None:
        obs.REC.record(
            "mesh.fetch", "X", t0, seconds, 0,
            {**attrs, "shards": self.n_shards,
             "bytes": sum(a.nbytes for a in jax.tree.leaves(fetched))},
        )

    def execute_many(
        self, plans_lists, count_only: bool = False
    ) -> List[Optional[ShardedFusedResult]]:
        return self.settle_many(self.dispatch_many(plans_lists, count_only))

    def tree_exec_job(self, pos_sites, neg_plans=None):
        """Prepare one whole-tree mesh execution (ISSUE 10) — the
        shared query/fused.py prepare_tree_job with the sharded job
        class (per-shard capacities and collective choices ride each
        site's _ShardedExecJob)."""
        return prepare_tree_job(
            self, pos_sites, neg_plans, _ShardedTreeExecJob
        )

    def execute_tree(self, pos_sites, neg_plans=None):
        """Run a whole Or/negation tree as ONE shard_map program (retry
        loop included) — the mesh twin of query/fused.py execute_tree,
        driven by the shared run_tree_job loop."""
        job = self.tree_exec_job(pos_sites, neg_plans)
        if job is None:
            return None
        return run_tree_job(job)


class _ShardedExecJob(_GroupHooks):
    """One mesh execute()'s mutable state, split into dispatch / settle
    halves (the query/fused.py _ExecJob idiom) so the coalescer can keep
    pipeline_depth sharded batches in flight.  Semantics are exactly the
    old synchronous execute(): same program cache, same capacity retry
    (term / join / exchange-slot), same reseed verdict, same cap
    learning.  The same-signature jobs of a batch ride ONE
    `das_sharded_group` program (_GroupHooks)."""

    __slots__ = (
        "ex", "count_only", "same_order", "sigs", "arrays", "keys", "fvals",
        "term_caps", "join_caps", "exch_caps", "index_joins",
        "names", "result", "planned", "rounds", "last_ranges",
        "last_join_rows", "last_exch_rows", "_sig",
    )

    #: the mesh builds its jobs per query (_exec_job): no lane columns
    #: of a batch's builder, a group stacks its jobs' own values
    lanes = None

    def __init__(
        self, ex, count_only, same_order, sigs, arrays, keys, fvals,
        term_caps, join_caps, exch_caps, index_joins, planned=None,
    ):
        self.ex = ex
        self.count_only = count_only
        self.same_order = same_order
        self.sigs = sigs
        self.arrays = arrays
        self.keys = keys
        self.fvals = fvals
        self.term_caps = term_caps
        self.join_caps = join_caps
        self.exch_caps = exch_caps
        self.index_joins = index_joins
        self.names = None
        self.result: Optional[ShardedFusedResult] = None
        #: PlannedProgram that ordered/seeded this job (query/fused.py
        #: _ExecJob mirror); settle feeds estimates to planner telemetry
        self.planned = planned
        self.rounds = 0
        self.last_ranges = None
        self.last_join_rows = None
        self.last_exch_rows = None   # final-round worst occupancies
        self._sig = None

    def plan_sig(self) -> ShardedPlanSig:
        """The sharded plan signature at the CURRENT capacities, ONE
        object until a settle grows a capacity (settle assigns new
        tuples, so identity tells; _dispatch_round tells jobs apart by
        their signature object first, so it must live as long as the
        job).  Shared by dispatch() and the whole-tree mesh job
        (_ShardedTreeExecJob)."""
        sig = self._sig
        if (
            sig is None
            or sig.term_caps is not self.term_caps
            or sig.join_caps is not self.join_caps
            or sig.exch_caps is not self.exch_caps
        ):
            sig = self._sig = ShardedPlanSig(
                self.sigs, self.term_caps, self.join_caps, self.exch_caps,
                self.ex.n_shards, self.index_joins, self.planned is not None,
            )
        return sig

    def dispatch(self, plan_sig=None):
        """Queue the shard_map program at the current capacities
        (async, no sync); `plan_sig`: the signature there, where the
        caller has it."""
        ex = self.ex
        if plan_sig is None:
            plan_sig = self.plan_sig()
        entry = ex._cache.get((plan_sig, self.count_only))
        if entry is None:
            moved = _Moved(ex.n_shards)
            fn, out_names = build_fused_sharded(
                plan_sig, ex.mesh, self.count_only, moved
            )
            # program ledger (ISSUE 14): identity when DAS_TPU_PROFLOG
            # is off; the mesh program's compile/cost/memory record
            # keys on the sharded plan-sig digest like the single-device
            # twin (host-side bookkeeping only — dispatch stays
            # sync-free, DL001/DL010)
            entry = (
                _MeshProgram(obs.proflog.instrument(
                    "sharded",
                    obs.proflog.sig_digest(plan_sig, self.count_only),
                    jax.jit(obs.named_program(
                        "das_sharded", fn, self.count_only
                    )),
                ), moved),
                out_names,
            )
            ex._cache[(plan_sig, self.count_only)] = entry
        fn, self.names = entry
        self.rounds += 1
        with self._enqueue_span(plan_sig), obs.annotation("exec.dispatch"):
            return fn(self.arrays, self.keys, self.fvals)

    def _enqueue_span(self, plan_sig, jobs=()):
        """Tally ONE mesh program about to be enqueued (this job's own,
        or the group program this job leads for `jobs`), a retry per
        JOB that a shard's overflow sends again, and return the trace
        span to hold around the enqueue: _ExecJob's vocabulary, the
        sharded route's names."""
        record_dispatch("sharded")
        if obs.enabled():
            again = sum(1 for j in jobs or (self,) if j.rounds > 1)
            if again:
                obs.counter("mesh.retries").inc(again)
        return self._program_span(plan_sig, "sharded", jobs)

    def _build_group(self, plan_sig, key_axes, fval_axes):
        moved = _Moved(self.ex.n_shards)
        fn, names = build_fused_sharded_group(
            plan_sig, self.ex.mesh, self.count_only, key_axes, fval_axes,
            moved,
        )
        return _MeshProgram(obs.proflog.instrument(
            "sharded_group",
            obs.proflog.sig_digest(
                plan_sig, self.count_only, key_axes, fval_axes
            ),
            jax.jit(obs.named_program(
                "das_sharded_group", fn, self.count_only
            )),
        ), moved), names

    def verdict_attrs(self) -> dict:
        """What a settled mesh job adds to its `exec.verdict` span
        (tracing on; query/fused.py settle_pending_iter), from the
        signature and the stats the round fetched anyway: `partitioned`,
        its verified joins that partition both sides, and
        `exchange_fill`, the worst destination's occupancy over the
        slots it had, summed over the job's exchanging joins.  The same
        figures feed counters `mesh.partitioned_joins` (jobs with at
        least one), `mesh.exchange_rows_max` and `mesh.exchange_slots`."""
        slots = sum(self.exch_caps)
        if not slots or self.last_exch_rows is None:
            return {}
        partitioned = sum(
            1 for q, p in zip(self.exch_caps, self.index_joins)
            if q and p >= 0
        )
        rows = sum(self.last_exch_rows)
        if partitioned:
            obs.counter("mesh.partitioned_joins").inc()
        obs.counter("mesh.exchange_rows_max").inc(rows)
        obs.counter("mesh.exchange_slots").inc(slots)
        return {"partitioned": partitioned, "exchange_fill": rows / slots}

    def settle(self, host_out, dev_out) -> bool:
        """Consume one round's fetched stats.  True = finished (result
        set; None result = capacity ceiling — caller falls back to the
        staged mesh path as before); False = capacities grew, dispatch
        again."""
        if self.count_only:
            vals = valid = host_vals = host_valid = None
            stats = np.asarray(host_out)
        else:
            # ONE host transfer carried the row-sharded binding table and
            # the stats; device refs stay alongside for callers that keep
            # joining on device (the mesh tree executor's conj leaves)
            host_vals, host_valid, stats = host_out
            vals, valid, _ = dev_out
        n_terms = len(self.sigs)
        n_joins = len(self.join_caps)
        count, reseed = int(stats[0]), bool(stats[1])
        pos_empty = bool(stats[2])
        ranges = stats[3 : 3 + n_terms]
        jtotals = stats[3 + n_terms : 3 + n_terms + n_joins]
        eoccs = stats[3 + n_terms + n_joins :]
        new_tc = tuple(
            _pow2_at_least(int(r)) if int(r) > c else c
            for r, c in zip(ranges, self.term_caps)
        )
        new_jc = tuple(
            _pow2_at_least(int(t)) if int(t) > c else c
            for t, c in zip(jtotals, self.join_caps)
        )
        new_ec = tuple(
            (0 if c == 0 else (_pow2_at_least(int(o)) if int(o) > c else c))
            for o, c in zip(eoccs, self.exch_caps)
        )
        if (new_tc, new_jc, new_ec) != (
            self.term_caps, self.join_caps, self.exch_caps
        ):
            if (
                max(new_tc + new_jc + new_ec, default=0)
                > self.ex.db.config.max_result_capacity
            ):
                return True  # staged mesh path owns overflow policy
            self.term_caps, self.join_caps, self.exch_caps = (
                new_tc, new_jc, new_ec
            )
            return False
        remember_caps(
            self.ex._caps, (self.ex._cache, self.ex._group_cache), self.sigs,
            (self.term_caps, self.join_caps, self.exch_caps),
            lambda ps: (ps.term_caps, ps.join_caps, ps.exch_caps),
        )
        self.last_ranges = [int(r) for r in ranges]
        self.last_join_rows = [int(t) for t in jtotals]
        self.last_exch_rows = [int(o) for o in eoccs]
        if self.planned is not None:
            from das_tpu.planner import observe_settle

            observe_settle(
                self.planned, self.last_join_rows, self.rounds,
                shards=self.ex.n_shards,
            )
        n_positive = sum(1 for s in self.sigs if not s.negated)
        self.result = ShardedFusedResult(
            var_names=self.names,
            vals=vals,
            valid=valid,
            count=count,
            reseed_needed=reseed
            or (
                count == 0
                and n_positive > 1
                and not pos_empty
                and not self.same_order
            ),
            host_vals=host_vals,
            host_valid=host_valid,
        )
        return True


class _ShardedTreeExecJob(_TreeExecJob):
    """One whole-tree MESH execution's mutable state (ISSUE 10): the
    query/fused.py _TreeExecJob base with the executor-specific hooks
    overridden — sharded tree signature/builder, the per-site block
    length (exchange occupancies appended), the row-sharded result
    class, and the sharded counter-key literals (DL004 pins counting
    sites as declared-key literals, so the thin dispatch/settle
    wrappers stay per-class)."""

    __slots__ = ()

    def tree_sig(self) -> ShardedTreeSig:
        return ShardedTreeSig(
            tuple(j.plan_sig() for j in self.site_jobs),
            self.neg_job.plan_sig() if self.neg_job is not None else None,
        )

    def _build(self, tree_sig):
        moved = _Moved(self.ex.n_shards)
        fn, out_names = build_sharded_tree_fused(
            tree_sig, self.ex.mesh, moved=moved
        )
        return _MeshProgram(obs.proflog.instrument(
            "sharded_tree", obs.proflog.sig_digest(tree_sig, False),
            jax.jit(obs.named_program("das_sharded_tree", fn)),
        ), moved), out_names

    def _blk_len(self, j) -> int:
        return conj_stats_len(
            len(j.sigs), len(j.join_caps)
        ) + len(j.exch_caps)

    def _make_result(self, vals, valid, count, host_vals, host_valid):
        return ShardedFusedResult(
            var_names=self.names,
            vals=vals,
            valid=valid,
            count=count,
            reseed_needed=False,
            host_vals=host_vals,
            host_valid=host_valid,
        )

    def dispatch(self):
        """Queue the whole-tree shard_map program (async, no sync)."""
        record_dispatch("sharded_tree_fused")
        sp = obs.NOOP_SPAN
        if obs.enabled():
            if self.rounds:
                obs.counter("mesh.retries").inc()
            sp = obs.span("exec.dispatch", route="sharded_tree_fused",
                          sites=len(self.site_jobs))
        with sp, obs.annotation("exec.dispatch"):
            return self._dispatch_common()

    def settle(self, host_out, dev_out) -> bool:
        done = self._settle_common(host_out, dev_out)
        if done and self.result is not None:
            from das_tpu.query.compiler import ROUTE_COUNTS

            ROUTE_COUNTS["sharded_tree_fused"] += 1
        return done


def get_sharded_executor(db) -> ShardedFusedExecutor:
    ex = getattr(db.tables, "_fused_executor", None)
    if ex is None or ex.db is not db:
        ex = ShardedFusedExecutor(db)
        db.tables._fused_executor = ex
    return ex
