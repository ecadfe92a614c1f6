"""Mesh-sharded AtomSpace backend.

TPU counterpart of the reference's Redis-cluster hash-slot sharding
(SURVEY.md §2.10 P1): link-bucket rows are partitioned round-robin over the
mesh axis; every shard holds its own slab *plus slab-local sorted probe
indexes*, stacked into ``[n_shards, m_local, ...]`` arrays laid out with
`NamedSharding(P("shards"))` so slab s physically lives on device s.

Query execution (`sharded_execute`) runs the same probe→term-table→join
pipeline as the single-device compiler (query/compiler.py) but under
`shard_map`:

  * term probes are shard-local (no communication at all — the analogue of
    Redis cluster client-side slot routing, except *every* shard probes its
    slab in parallel instead of one client hitting one node);
  * joins are broadcast-right: the smaller right table is `all_gather`ed
    over ICI and joined against the resident left slab, so the accumulated
    table stays row-sharded end to end;
  * counts fan in with `psum`; only the final binding table is pulled to
    the host for (global) dedup + materialization.

The generic DBInterface surface is inherited from MemoryDB — answer-exact
and hardware-free — so this backend is always correct and uses the mesh
for the hot conjunctive path.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from das_tpu import obs
from das_tpu.core.config import DasConfig
from das_tpu.core.exceptions import CapacityOverflowError
from das_tpu.ops.join import _anti_join_impl, _join_tables_impl, _build_term_table_impl
from das_tpu.parallel.mesh import SHARD_AXIS, make_mesh, table_program
from das_tpu.query import compiler as qc
from das_tpu.query.ast import LogicalExpression, PatternMatchingAnswer
from das_tpu.storage.atom_table import AtomSpaceData, Finalized
from das_tpu.storage.delta import (
    FULL,
    NOOP,
    IncrementalCommitMixin,
    capacity_class,
    delta_class,
    merge_sorted_index,
)
from das_tpu.storage.memory_db import MemoryDB

_I64_MAX = np.int64(2**63 - 1)
_I32_MAX = np.int32(2**31 - 1)


@dataclass
class ShardedBucket:
    """Slab-stacked device arrays, CAPACITY-padded along the local axis:
    m_local includes ~6% slack beyond the largest slab's real rows, so
    incremental commits scatter deltas into the slack with FIXED-shape
    shard_map programs — neither the merge nor cached query executables
    recompile per commit (mirrors storage/tensor_db.py DeviceBucket)."""

    arity: int
    n_shards: int
    m_local: int                   # padded local capacity
    size: int                      # global real (unpadded) row count
    #: per-shard real row counts [S] — host side, drives delta placement
    slab_sizes: np.ndarray
    type_id: jax.Array             # [S, m] int32, pad -1
    ctype: jax.Array               # [S, m] int64
    targets: jax.Array             # [S, m, a] int32, pad -2
    #: canonically sorted target multisets — the unordered (Set/Similarity)
    #: value blocks built by the mesh uterm probes (parallel/sharded_tree.py)
    targets_sorted: jax.Array      # [S, m, a] int32, pad -2
    key_type: jax.Array            # [S, m] int64 sorted, pad I64_MAX
    order_by_type: jax.Array
    key_ctype: jax.Array           # [S, m] int64 sorted, pad I64_MAX
    order_by_ctype: jax.Array
    key_type_pos: List[jax.Array]  # per pos: [S, m] int64 sorted
    order_by_type_pos: List[jax.Array]
    key_pos: List[jax.Array]       # [S, m] int64 sorted
    order_by_pos: List[jax.Array]


def _build_sharded_bucket(b, mesh: Mesh) -> ShardedBucket:
    """Partition one finalized LinkBucket round-robin over the mesh axis
    and build slab-local sorted probe indexes (one stacked [S, m_local]
    array family, physically laid out so slab s lives on device s).
    m_local is capacity-padded (see ShardedBucket)."""
    S = mesh.devices.size
    shard = NamedSharding(mesh, P(SHARD_AXIS))
    arity, m = b.arity, b.size
    m_local = capacity_class(max(1, -(-m // S)))
    slabs = [np.arange(s, m, S, dtype=np.int64) for s in range(S)]

    def padded(build, fill, dtype, extra_shape=()):
        out = np.full((S, m_local, *extra_shape), fill, dtype=dtype)
        for s, rows in enumerate(slabs):
            out[s, : len(rows)] = build(rows)
        return out

    type_id = padded(lambda r: b.type_id[r], -1, np.int32)
    ctype = padded(lambda r: b.ctype[r], _I64_MAX, np.int64)
    targets = padded(lambda r: b.targets[r], -2, np.int32, (arity,))
    targets_sorted = padded(lambda r: b.targets_sorted[r], -2, np.int32, (arity,))

    def sorted_index(keys_of):
        key_arr = np.full((S, m_local), _I64_MAX, dtype=np.int64)
        ord_arr = np.zeros((S, m_local), dtype=np.int32)
        for s, rows in enumerate(slabs):
            k = keys_of(rows).astype(np.int64)
            o = np.argsort(k, kind="stable")
            key_arr[s, : len(rows)] = k[o]
            ord_arr[s, : len(rows)] = o
        return key_arr, ord_arr

    key_type, order_by_type = sorted_index(lambda r: b.type_id[r])
    key_ctype, order_by_ctype = sorted_index(lambda r: b.ctype[r])
    key_type_pos, order_by_type_pos = [], []
    key_pos, order_by_pos = [], []
    for p in range(arity):
        k, o = sorted_index(
            lambda r, p=p: (b.type_id[r].astype(np.int64) << 32)
            | b.targets[r, p].astype(np.int64)
        )
        key_type_pos.append(jax.device_put(k, shard))
        order_by_type_pos.append(jax.device_put(o, shard))
        k2, o2 = sorted_index(lambda r, p=p: b.targets[r, p])
        key_pos.append(jax.device_put(k2, shard))
        order_by_pos.append(jax.device_put(o2, shard))

    return ShardedBucket(
        arity=arity,
        n_shards=S,
        m_local=m_local,
        size=m,
        slab_sizes=np.array([len(r) for r in slabs], dtype=np.int32),
        type_id=jax.device_put(type_id, shard),
        ctype=jax.device_put(ctype, shard),
        targets=jax.device_put(targets, shard),
        targets_sorted=jax.device_put(targets_sorted, shard),
        key_type=jax.device_put(key_type, shard),
        order_by_type=jax.device_put(order_by_type, shard),
        key_ctype=jax.device_put(key_ctype, shard),
        order_by_ctype=jax.device_put(order_by_ctype, shard),
        key_type_pos=key_type_pos,
        order_by_type_pos=order_by_type_pos,
        key_pos=key_pos,
        order_by_pos=order_by_pos,
    )


class SlabCapacityExhausted(Exception):
    """A commit no longer fits the slab slack: time for an early LSM
    compaction (full re-partition) of the sharded store."""


class ShardedTables:
    def __init__(self, fin: Finalized, mesh: Mesh):
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.buckets: Dict[int, ShardedBucket] = {
            arity: _build_sharded_bucket(b, mesh)
            for arity, b in fin.buckets.items()
        }
        #: (arity, m_local, dcap) -> compiled fixed-shape merge program
        self._merge_cache: Dict[Tuple, object] = {}
        #: True when restored from a sharded checkpoint (observability/tests)
        self.restored = False

    @classmethod
    def from_buckets(
        cls, buckets: Dict[int, ShardedBucket], mesh: Mesh
    ) -> "ShardedTables":
        """Checkpoint-restore construction (storage/checkpoint.py
        try_restore_sharded): the slabs arrive ready-made — no
        re-partition, no per-slab index rebuild."""
        self = cls.__new__(cls)
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.buckets = buckets
        self._merge_cache = {}
        self.restored = True
        return self

    def stage_delta(self, delta):
        """COMPUTE one arity's slab extension by a small commit bucket in
        O(n) device work and O(delta) host<->device traffic -- the mesh
        analogue of TensorDB._stage_delta_merge.  Returns (swap,
        became_base, slots): the merged ShardedBucket only becomes
        visible when the deferred `swap` assignment runs (the
        stage-then-swap commit contract, storage/delta.py _apply_delta
        -- a failure mid-compute, SlabCapacityExhausted included,
        leaves `self.buckets` untouched).

        Delta rows continue the round-robin rotation (delta row j goes to
        shard (size+j) % S) and land in each slab's capacity SLACK (local
        positions slab_sizes[s]..): the stacked array shapes never change,
        so the single shard_map merge program -- slab-local sorted-index
        merges (storage/delta.py merge_sorted_index) plus per-shard column
        inserts at traced offsets -- compiles ONCE per (arity, shape
        class) and every later commit is pure device work.  When the
        slack cannot absorb a commit, SlabCapacityExhausted asks the
        backend for an early LSM compaction (full re-partition).

        Returns (became_base, slots): slots = real delta rows — with
        fixed capacities, memory amplification is structurally bounded by
        the slack itself, so the LSM threshold charges real atoms."""
        arity, d = delta.arity, delta.size
        base = self.buckets.get(arity)
        if base is None or base.size == 0:
            built = _build_sharded_bucket(delta, self.mesh)

            def swap_base():
                self.buckets[arity] = built

            return swap_base, True, d
        S, m_local = self.n_shards, base.m_local
        shard = NamedSharding(self.mesh, P(SHARD_AXIS))
        js = [
            [j for j in range(d) if (base.size + j) % S == s] for s in range(S)
        ]
        worst = max(len(x) for x in js)
        dcap = delta_class(worst)
        if int(base.slab_sizes.max()) + dcap > m_local:
            raise SlabCapacityExhausted(
                f"arity-{arity} slab slack exhausted "
                f"({int(base.slab_sizes.max())}+{dcap} > {m_local})"
            )

        def d_padded(col, fill, dtype, extra_shape=()):
            out = np.full((S, dcap, *extra_shape), fill, dtype=dtype)
            for s, rows in enumerate(js):
                out[s, : len(rows)] = col[rows]
            return jax.device_put(out, shard)

        d_cols = [
            d_padded(delta.type_id, -1, np.int32),
            d_padded(delta.ctype, _I64_MAX, np.int64),
            d_padded(delta.targets, -2, np.int32, (arity,)),
            d_padded(delta.targets_sorted, -2, np.int32, (arity,)),
        ]

        def d_sorted(keys_of):
            key_arr = np.full((S, dcap), _I64_MAX, dtype=np.int64)
            perm_arr = np.zeros((S, dcap), dtype=np.int32)
            for s, rows in enumerate(js):
                k = keys_of(np.array(rows, dtype=np.int64)).astype(np.int64)
                o = np.argsort(k, kind="stable")
                key_arr[s, : len(rows)] = k[o]
                # the i-th delta row of shard s sits at slab_sizes[s] + i
                perm_arr[s, : len(rows)] = base.slab_sizes[s] + o.astype(
                    np.int32
                )
            return jax.device_put(key_arr, shard), jax.device_put(perm_arr, shard)

        idx_pairs = [
            ((base.key_type, base.order_by_type),
             d_sorted(lambda r: delta.type_id[r])),
            ((base.key_ctype, base.order_by_ctype),
             d_sorted(lambda r: delta.ctype[r])),
        ]
        for p in range(arity):
            idx_pairs.append((
                (base.key_type_pos[p], base.order_by_type_pos[p]),
                d_sorted(
                    lambda r, p=p: (delta.type_id[r].astype(np.int64) << 32)
                    | delta.targets[r, p].astype(np.int64)
                ),
            ))
            idx_pairs.append((
                (base.key_pos[p], base.order_by_pos[p]),
                d_sorted(lambda r, p=p: delta.targets[r, p]),
            ))

        fn = self._merge_cache.get((arity, m_local, dcap))
        if fn is None:
            def kernel(base_cols, delta_cols, base_idx, delta_idx, starts):
                s0 = starts[0]
                cols = [
                    jax.lax.dynamic_update_slice_in_dim(
                        b[0], e[0], s0, axis=0
                    )[None]
                    for b, e in zip(base_cols, delta_cols)
                ]
                idx = []
                for (bk, bo), (dk, do) in zip(base_idx, delta_idx):
                    k, o = merge_sorted_index(
                        bk[0], bo[0], dk[0], do[0], size=bk.shape[1]
                    )
                    idx.append((k[None], o[None]))
                return cols, idx

            spec = P(SHARD_AXIS)
            fn = jax.jit(obs.named_program("das_merge_sharded", shard_map(
                kernel, mesh=self.mesh,
                in_specs=(spec, spec, spec, spec, spec),
                out_specs=(spec, spec),
            )))
            self._merge_cache[(arity, m_local, dcap)] = fn
        base_cols = [base.type_id, base.ctype, base.targets, base.targets_sorted]
        starts = jax.device_put(base.slab_sizes, shard)
        cols, idx = fn(
            base_cols, d_cols,
            [b for b, _ in idx_pairs], [e for _, e in idx_pairs],
            starts,
        )
        merged = ShardedBucket(
            arity=arity,
            n_shards=S,
            m_local=m_local,
            size=base.size + d,
            slab_sizes=base.slab_sizes
            + np.array([len(x) for x in js], dtype=np.int32),
            type_id=cols[0],
            ctype=cols[1],
            targets=cols[2],
            targets_sorted=cols[3],
            key_type=idx[0][0],
            order_by_type=idx[0][1],
            key_ctype=idx[1][0],
            order_by_ctype=idx[1][1],
            key_type_pos=[idx[2 + 2 * p][0] for p in range(arity)],
            order_by_type_pos=[idx[2 + 2 * p][1] for p in range(arity)],
            key_pos=[idx[3 + 2 * p][0] for p in range(arity)],
            order_by_pos=[idx[3 + 2 * p][1] for p in range(arity)],
        )

        def swap():
            self.buckets[arity] = merged

        return swap, False, d


@dataclass
class ShardedTable:
    var_names: Tuple[str, ...]
    vals: jax.Array    # [S, cap, k] row-sharded
    valid: jax.Array   # [S, cap]
    count: int         # global exact count
    host_vals: Optional[np.ndarray] = None   # prefetched host copies (the
    host_valid: Optional[np.ndarray] = None  # fused settle's one transfer)


def _shard_rows(vals, valid) -> np.ndarray:
    """The mesh's part of `exec.materialize`: the `[S, cap, k]` stack of
    per-shard rows flattened, its valid rows each once."""
    with obs.span("mesh.dedup") as sp:
        vals = np.asarray(vals)
        vals = vals.reshape(-1, vals.shape[-1])
        rows = vals[np.asarray(valid).reshape(-1)]
        distinct = qc.distinct_rows(rows)
        sp.set(rows=len(rows), distinct=len(distinct))
    return distinct


def _probe_kernel(key_sorted, perm, targets, type_id, probe_key, fixed_vals,
                  *, fixed_pos, cap, var_cols, eq_pairs):
    """Shard-local probe + term-table build.  Runs inside shard_map: blocks
    arrive as [1, m(, a)] slabs; outputs carry the same leading block dim.
    The probed key and the grounded values are replicated OPERANDS, so a
    program is keyed by shapes and positions, not by the atom asked for."""
    key_sorted, perm, targets = key_sorted[0], perm[0], targets[0]
    lo = jnp.searchsorted(key_sorted, probe_key, side="left")
    hi = jnp.searchsorted(key_sorted, probe_key, side="right")
    range_count = (hi - lo).astype(jnp.int32)
    offs = jnp.arange(cap, dtype=jnp.int32)
    valid = offs < range_count
    idx = jnp.clip(lo.astype(jnp.int32) + offs, 0, key_sorted.shape[0] - 1)
    local = perm[idx]
    safe = jnp.clip(local, 0, targets.shape[0] - 1)
    mask = valid
    for i, pos in enumerate(fixed_pos):
        mask = mask & (targets[safe, pos] == fixed_vals[i])
    vals, mask = _build_term_table_impl(targets, local, mask, var_cols, eq_pairs)
    return vals[None], mask[None], range_count[None]


class ShardedDB(IncrementalCommitMixin, MemoryDB):
    """MemoryDB surface + mesh-sharded conjunctive execution."""

    def __init__(
        self,
        data: Optional[AtomSpaceData] = None,
        config: Optional[DasConfig] = None,
        mesh: Optional[Mesh] = None,
    ):
        super().__init__(data)
        self.config = config or DasConfig()
        self.fin: Finalized = self.data.finalize()
        self.mesh = mesh if mesh is not None else make_mesh(
            None
            if self.config.mesh_shape is None
            else int(np.prod(self.config.mesh_shape))
        )
        tables = None
        if self.config.checkpoint_path:
            # shard-local restore: device_put the saved slabs directly
            # instead of re-partitioning the host-global Finalized
            from das_tpu.storage import checkpoint

            tables = checkpoint.try_restore_sharded(
                self.config.checkpoint_path, self.fin, self.mesh
            )
        self.tables = tables or ShardedTables(self.fin, self.mesh)
        #: statics -> ONE jitted shard_map program of the staged route
        #: (`_staged_program`); shapes are the jit's own cache key, so
        #: the programs outlive a commit and a re-partition
        self._staged_programs: Dict[Tuple, object] = {}
        self._reset_delta_state()

    def __repr__(self):
        return f"<ShardedDB over {self.tables.n_shards} shards>"

    def refresh(self) -> None:
        """Re-sync the sharded store after transaction commits.  Small
        deltas extend the slab-stacked device tables in place
        (`ShardedTables.stage_delta`) — O(delta) host↔device traffic,
        one shard_map merge program, no re-partition of the base.  The
        full-vs-delta decision, atom interning, and the incoming-set
        overlay are shared with TensorDB (storage/delta.py); past
        config.delta_merge_threshold accumulated atoms the store fully
        re-finalizes and re-partitions.

        Cache invalidation contract (mirrors TensorDB.refresh): the
        incremental path bumps the mixin's `delta_version`, which the
        sharded fused executor's result cache keys on
        (parallel/fused_sharded.py); the FULL path (threshold or slab
        exhaustion) replaces `self.tables`, dropping the executor and its
        cache wholesale.

        The host scan lists of MemoryDB (`prefetch`: one Python list
        entry per link and index) are NOT rebuilt here: queries answer
        from the mesh and never read them, every host scan that does
        (`get_matched_*`) brings them up to date itself, and at the
        FlyBase shape x 0.3 building them eagerly was 116 s of a 155 s
        load (sandbox CPU run, PR 29)."""
        action = self._plan_refresh()
        if action == NOOP:
            return
        if action == FULL:
            # WAL (ISSUE 15): log the pending host tail fsynced before
            # the re-partition becomes visible (TensorDB.refresh has
            # the full rationale — shared contract)
            wal = self._wal
            if wal is not None:
                wal.append(self.data, self.delta_version + 1, kind="full")
            self.fin = self.data.finalize()
            self.tables = ShardedTables(self.fin, self.mesh)
            self._reset_delta_state()
            return
        self._commit_delta_with_retry(action)

    @classmethod
    def restore(cls, path: str, config: Optional[DasConfig] = None) -> "ShardedDB":
        """Warm-state restore on the mesh (ISSUE 15, storage/durable.py):
        newest VALID snapshot generation + WAL replay + warm bundle; the
        saved shard-local slabs device_put directly when the mesh size
        and content sig still match (checkpoint.try_restore_sharded)."""
        from das_tpu.storage import durable

        return durable.restore(path, config=config, backend="sharded")

    # _apply_delta / _reset_delta_state / host_bucket_segments come from
    # IncrementalCommitMixin; the backend-specific part is the device merge:

    def _stage_delta_merge(self, commit_bucket):
        return self.tables.stage_delta(commit_bucket)

    def _commit_delta_with_retry(self, action) -> None:
        try:
            super()._commit_delta_with_retry(action)
        except SlabCapacityExhausted:
            # early LSM compaction: a slab's capacity slack is gone before
            # the atom-count threshold tripped.  The aborted commit staged
            # but never swapped (stage-then-swap), so the full
            # re-partition starts from a clean pre-commit store.
            self.fin = self.data.finalize()
            self.tables = ShardedTables(self.fin, self.mesh)
            self._reset_delta_state()

    def _type_id(self, link_type: str) -> Optional[int]:
        h = self.data.table.get_named_type_hash(link_type)
        return self.fin.type_id_of_hash.get(h)

    # -- sharded pipeline --------------------------------------------------

    def _staged_program(self, key, kernel, n_in, n_out, replicated_in=()):
        """The staged route's one door to a mesh program: `kernel` over
        slab-stacked operands (parallel/mesh.py `table_program`), kept
        under `key`, which holds every static the kernel closes over: a
        capacity retry and a second query of a shape run the program the
        first one compiled."""
        fn = self._staged_programs.get(key)
        if fn is None:
            fn = table_program(self.mesh, kernel, n_in, n_out, replicated_in)
            self._staged_programs[key] = fn
        return fn

    def _term_table(self, plan: qc.TermPlan) -> Optional[ShardedTable]:
        sb = self.tables.buckets.get(plan.arity)
        if sb is None:
            return None
        if plan.ctype is not None:
            key_sorted, perm = sb.key_ctype, sb.order_by_ctype
            probe_key = np.int64(plan.ctype)
            fixed = ()
        elif plan.type_id is not None and plan.fixed:
            p0, v0 = plan.fixed[0]
            key_sorted, perm = sb.key_type_pos[p0], sb.order_by_type_pos[p0]
            probe_key = np.int64((plan.type_id << 32) | v0)
            fixed = tuple(plan.fixed[1:])
        else:
            # plan_query guarantees type_id for every non-template plan
            key_sorted, perm = sb.key_type, sb.order_by_type
            probe_key = np.int64(plan.type_id)
            fixed = ()

        cap = min(self.config.initial_result_capacity, max(sb.m_local, 16))
        fixed_pos = tuple(p for p, _ in fixed)
        probe_key = np.asarray(probe_key, dtype=np.int64)
        fixed_vals = np.asarray([v for _, v in fixed], dtype=np.int32)
        while True:
            fn = self._staged_program(
                ("term", fixed_pos, cap, plan.var_cols, plan.eq_pairs),
                partial(
                    _probe_kernel,
                    fixed_pos=fixed_pos,
                    cap=cap,
                    var_cols=plan.var_cols,
                    eq_pairs=plan.eq_pairs,
                ),
                n_in=6, n_out=3, replicated_in=(4, 5),
            )
            vals, mask, range_counts = fn(
                key_sorted, perm, sb.targets, sb.type_id, probe_key, fixed_vals
            )
            worst = int(np.max(np.asarray(range_counts)))
            if worst <= cap:
                count = int(np.asarray(mask).sum())
                if count == 0:
                    return None
                return ShardedTable(plan.var_names, vals, mask, count)
            if cap >= self.config.max_result_capacity:
                raise CapacityOverflowError(
                    f"probe needs {worst} rows > max_result_capacity "
                    f"{self.config.max_result_capacity}"
                )
            cap = min(max(cap * 2, worst), self.config.max_result_capacity)

    def _join(self, left: ShardedTable, right: ShardedTable) -> ShardedTable:
        pairs = tuple(
            (left.var_names.index(v), right.var_names.index(v))
            for v in left.var_names
            if v in right.var_names
        )
        extra = tuple(
            i for i, v in enumerate(right.var_names) if v not in left.var_names
        )
        out_names = left.var_names + tuple(
            v for v in right.var_names if v not in left.var_names
        )
        cap = max(64, min(left.count * right.count, self.config.initial_result_capacity))
        while True:
            # `cap` is bound here, not read from this frame: the kept
            # program may trace again (a new shape) after the loop moved on
            def kernel(lv, lm, rv, rm, cap=cap):
                # broadcast-right: gather the full right table to this shard
                with jax.named_scope("mesh.all_gather_right"):
                    rv_full = jax.lax.all_gather(rv[0], SHARD_AXIS, tiled=True)
                    rm_full = jax.lax.all_gather(rm[0], SHARD_AXIS, tiled=True)
                vals, valid, total = _join_tables_impl(
                    lv[0], lm[0], rv_full, rm_full, pairs, extra, cap
                )
                return vals[None], valid[None], total[None]

            fn = self._staged_program(
                ("join", pairs, extra, cap), kernel, n_in=4, n_out=3
            )
            vals, valid, totals = fn(left.vals, left.valid, right.vals, right.valid)
            worst = int(np.max(np.asarray(totals)))
            if worst <= cap:
                count = int(np.asarray(valid).sum())
                return ShardedTable(out_names, vals, valid, count)
            if cap >= self.config.max_result_capacity:
                raise CapacityOverflowError(
                    f"join needs {worst} rows > max_result_capacity "
                    f"{self.config.max_result_capacity}"
                )
            cap = min(max(cap * 2, worst), self.config.max_result_capacity)

    def _anti_join(self, left: ShardedTable, tabu: ShardedTable) -> ShardedTable:
        pairs = tuple(
            (left.var_names.index(v), tabu.var_names.index(v))
            for v in tabu.var_names
        )

        def kernel(lv, lm, rv, rm):
            with jax.named_scope("mesh.all_gather_tabu"):
                rv_full = jax.lax.all_gather(rv[0], SHARD_AXIS, tiled=True)
                rm_full = jax.lax.all_gather(rm[0], SHARD_AXIS, tiled=True)
            return _anti_join_impl(lv[0], lm[0], rv_full, rm_full, pairs)[None]

        fn = self._staged_program(("anti", pairs), kernel, n_in=4, n_out=1)
        valid = fn(left.vals, left.valid, tabu.vals, tabu.valid)
        return ShardedTable(
            left.var_names, left.vals, valid, int(np.asarray(valid).sum())
        )

    def sharded_execute(self, plans: List[qc.TermPlan]) -> Optional[ShardedTable]:
        """The STAGED mesh pipeline: one shard_map program per stage and
        a host sync between stages.  The served path comes here only
        when the fused mesh program declined (counter
        `mesh.staged_fallbacks`)."""
        if obs.enabled():
            obs.counter("mesh.staged_fallbacks").inc()
        tabu: List[ShardedTable] = []
        accumulated: Optional[ShardedTable] = None
        for plan in plans:
            table = self._term_table(plan)
            if plan.negated:
                if table is not None:
                    tabu.append(table)
                continue
            if table is None:
                return None
            if accumulated is None or accumulated.count == 0:
                accumulated = table
            else:
                accumulated = self._join(accumulated, table)
        if accumulated is None:
            return None
        for t in tabu:
            if set(t.var_names) <= set(accumulated.var_names):
                accumulated = self._anti_join(accumulated, t)
        return accumulated

    def materialize(self, table: Optional[ShardedTable], answer: PatternMatchingAnswer) -> bool:
        """The stacked per-shard rows of a mesh answer into the answer's
        block: one chip's `exec.materialize` (query/compiler.py), with
        the mesh's own step inside it — the valid rows of all shards,
        each once (`mesh.dedup`: two Or branches may ground one answer
        on two shards)."""
        return qc.materialize(self, table, answer, valid_rows=_shard_rows)

    def _run_conjunctive(self, plans: List[qc.TermPlan]) -> Optional[ShardedTable]:
        """One conjunctive plan on the mesh: the fused single-dispatch
        program first (one shard_map launch, one stats transfer); plans it
        declines (reseed condition, capacity ceiling) replay on the staged
        reference-order pipeline, which is answer-identical."""
        from das_tpu.parallel.fused_sharded import get_sharded_executor

        # the serving path opts into the delta-versioned result cache;
        # bare executor.execute stays uncached (measurement honesty)
        res = get_sharded_executor(self).execute(plans, use_cache=True)
        if res is not None and not res.reseed_needed:
            return ShardedTable(
                res.var_names, res.vals, res.valid, res.count,
                host_vals=res.host_vals, host_valid=res.host_valid,
            )
        return self.sharded_execute(plans)

    def _or_branch_plans(self, query) -> Optional[List[List[qc.TermPlan]]]:
        """Plans for each branch of an all-positive Or of compilable
        conjunctions, or None.  Reference Or semantics for positive terms
        is a plain union of branch answer sets (query/ast.py Or.matched),
        so each branch can run on the mesh independently; any Not branch
        (de-Morgan joint-negative handling) disqualifies."""
        from das_tpu.query.ast import Not, Or

        if not isinstance(query, Or) or not query.terms:
            return None
        if any(isinstance(t, Not) for t in query.terms):
            return None
        branch_plans = []
        for term in query.terms:
            plans = qc.plan_query(self, term, unknown_atom_empty=True)
            if plans is qc.EMPTY_PLAN:
                continue  # grounded on a nonexistent atom: statically empty
            if plans is None:
                return None
            branch_plans.append(plans)
        return branch_plans

    @property
    def tree_ops(self):
        """Mesh op layer for the generalized tree evaluator — built lazily,
        invalidated whenever the sharded tables object is replaced (full
        re-finalize) so probes never read a stale store."""
        ops = getattr(self, "_tree_ops", None)
        if ops is None or ops.tables is not self.tables:
            from das_tpu.parallel.sharded_tree import ShardedTreeOps

            ops = ShardedTreeOps(self)
            ops.tables = self.tables
            self._tree_ops = ops
        return ops

    def query_sharded(self, query: LogicalExpression, answer: PatternMatchingAnswer) -> Optional[bool]:
        """Compiled sharded execution; None when not compilable.

        Conjunctive queries run on the mesh (`_run_conjunctive`); an Or of
        compilable conjunctions runs each branch on the mesh and unions
        the materialized assignment sets (set insertion dedups by the
        engines' hash identity, exactly like Or.matched's union).

        Everything else in the compilable language (unordered links,
        nested And/Or, negated Or branches) ALSO runs on the mesh: the
        generalized tree evaluator (query/tree.py) executes with this
        backend's ShardedTreeOps op layer (parallel/sharded_tree.py), so
        composite tables stay row-sharded across all chips.  Legacy
        config.sharded_tree_fallback values: 'tensor' re-enables the
        round-2 single-chip replicated tree copy; 'host' skips device
        trees entirely."""
        plans = qc.plan_query(self, query)
        if plans is not None:
            return self.materialize(self._run_conjunctive(plans), answer)
        branch_plans = self._or_branch_plans(query)
        if branch_plans is not None:
            # whole-tree fusion (ISSUE 10) BEFORE the per-branch Or
            # decomposition: an eligible N-branch Or settles as ONE
            # shard_map program and one transfer where the branch loop
            # below pays one mesh program + one materialization per
            # branch.  Attempted only HERE — every other non-conjunctive
            # shape reaches query_tree below, whose own fused attempt
            # runs the eligibility analysis exactly once.  Gated on the
            # "mesh" tree mode: "tensor"/"host" promise no mesh tree
            # programs, and the fused tree IS one.  A decline falls
            # through to the decomposition, answer-identical.
            from das_tpu.query import assignment as asn_mod
            from das_tpu.query import tree as tree_mod

            if (
                tree_mod.tree_fusion_enabled(self.config)
                and getattr(self.config, "sharded_tree_fallback", "mesh")
                == "mesh"
                and not asn_mod.CONFIG.get("no_overload")
            ):
                from das_tpu.query.plan import NotCompilable, build_plan

                try:
                    node = build_plan(self, query)
                except NotCompilable:
                    node = None
                if node is not None:
                    matched = tree_mod.query_tree_fused(
                        self, node, answer, tree_mod._tree_cache(self)
                    )
                    if matched is not None:
                        return matched
            matched = False
            for plans in branch_plans:
                table = self._run_conjunctive(plans)
                matched = self.materialize(table, answer) or matched
            return matched
        from das_tpu.query.tree import query_tree

        mode = getattr(self.config, "sharded_tree_fallback", "mesh")
        if mode == "host":
            return None  # host algebra
        try:
            if mode == "tensor":
                return query_tree(self._tree_db(), query, answer)
            return query_tree(self, query, answer)
        except CapacityOverflowError:
            raise
        except Exception as exc:  # degrade, never crash the query API
            from das_tpu.utils.logger import logger

            logger().warning(
                f"sharded tree execution failed ({exc!r}); host algebra"
            )
            answer.assignments.clear()
            answer.negation = False
            return None

    def _tree_db(self):
        """Single-device TensorDB view over the same AtomSpaceData, built
        on first use and refreshed when the sharded tables were."""
        from das_tpu.storage.tensor_db import TensorDB

        db = getattr(self, "_tree_tensor_db", None)
        if db is None or db.data is not self.data:
            # the replica may adopt the shared cached Finalized: delta
            # interning is idempotent across backends (fin.interned
            # counters) and bucket bases are per-backend (_base_buckets),
            # both in storage/delta.py — asserted by
            # tests/test_incremental.py::test_shared_finalized_no_double_intern
            db = TensorDB(self.data, self.config)
            self._tree_tensor_db = db
        else:
            db.refresh()  # no-op when the data hasn't changed
        return db
