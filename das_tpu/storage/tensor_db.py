"""Device-resident AtomSpace backend (the production TPU path).

Role of the reference RedisMongoDB (redis_mongo_db.py:49-335), re-designed
for HBM residency: at construction every finalized bucket (storage/
atom_table.py) is `device_put` to the target platform; wildcard-pattern,
type-template and type probes execute as jitted `searchsorted` range
kernels (das_tpu/ops/posting.py) with capacity-doubling retry; the host
only touches small result vectors for API materialization (hex handles).

Probe routing (host-side, static per query shape):
  * type + ≥1 grounded target  → exact (type<<32|target) key index
  * type only                  → type-sorted index
  * grounded target(s) only    → position-sorted index
  * nothing grounded           → full bucket scan (padded)
  * unordered link types       → union-over-sorted-positions probe +
                                 multiset verification (position-free)

The full DBInterface contract (including dict/deep representations) is
inherited from MemoryDB; only the probe surface is overridden to run on
device.  The compiled conjunctive path (query/compiler.py) reaches the
device arrays directly through `.dev`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from das_tpu import obs
from das_tpu.core.config import DasConfig
from das_tpu.core.schema import UNORDERED_LINK_TYPES, WILDCARD
from das_tpu.ops import posting
from das_tpu.storage.atom_table import (
    AtomSpaceData,
    Finalized,
    LinkBucket,
)
from das_tpu.storage.delta import (
    FULL,
    NOOP,
    IncrementalCommitMixin,
    capacity_class,
    delta_class,
    merge_sorted_index,
)
from das_tpu.storage.memory_db import MemoryDB


@dataclass
class DeviceBucket:
    """Device arrays are CAPACITY-padded: length `capacity` >= `size` (real
    rows), with per-dtype sentinels in the slack (sorted keys pad with the
    dtype max so they sort last and no real probe key can hit them).
    Incremental commits scatter deltas into the slack with FIXED-shape
    programs, so neither the merge nor any compiled query executable
    recompiles per commit — shapes only change on rare capacity growth."""

    arity: int
    size: int        # real rows
    capacity: int    # array length
    rows: jax.Array
    type_id: jax.Array
    ctype: jax.Array
    targets: jax.Array
    targets_sorted: jax.Array
    order_by_type: jax.Array
    key_type: jax.Array
    order_by_ctype: jax.Array
    key_ctype: jax.Array
    order_by_type_pos: List[jax.Array]
    key_type_pos: List[jax.Array]
    order_by_pos: List[jax.Array]
    key_pos: List[jax.Array]
    order_by_type_spos: List[jax.Array]
    key_type_spos: List[jax.Array]


def _pad_rows(x: np.ndarray, capacity: int, fill) -> np.ndarray:
    n = x.shape[0]
    if n >= capacity:
        return x
    out = np.full((capacity, *x.shape[1:]), fill, dtype=x.dtype)
    out[:n] = x
    return out


def _key_pad(dtype) -> int:
    return np.iinfo(dtype).max


def upload_bucket(b: LinkBucket, device=None) -> DeviceBucket:
    """device_put every column/index of one finalized bucket, padded to
    its capacity class (see DeviceBucket)."""
    cap = capacity_class(b.size)
    put = lambda x, fill: jax.device_put(_pad_rows(x, cap, fill), device)
    return DeviceBucket(
        arity=b.arity,
        size=b.size,
        capacity=cap,
        rows=put(b.rows, -1),
        type_id=put(b.type_id, -1),
        ctype=put(b.ctype, _key_pad(np.int64)),
        targets=put(b.targets, -2),
        targets_sorted=put(b.targets_sorted, -2),
        order_by_type=put(b.order_by_type, 0),
        key_type=put(b.key_type, _key_pad(b.key_type.dtype)),
        order_by_ctype=put(b.order_by_ctype, 0),
        key_ctype=put(b.key_ctype, _key_pad(np.int64)),
        order_by_type_pos=[put(x, 0) for x in b.order_by_type_pos],
        key_type_pos=[put(x, _key_pad(np.int64)) for x in b.key_type_pos],
        order_by_pos=[put(x, 0) for x in b.order_by_pos],
        key_pos=[put(x, _key_pad(x.dtype)) for x in b.key_pos],
        order_by_type_spos=[put(x, 0) for x in b.order_by_type_spos],
        key_type_spos=[put(x, _key_pad(np.int64)) for x in b.key_type_spos],
    )


class DeviceTables:
    """All device-resident arrays for one AtomSpace."""

    def __init__(self, fin: Finalized, device=None):
        import das_tpu

        das_tpu.enable_compile_cache()
        put = lambda x: jax.device_put(x, device)
        self.node_type_id = put(fin.node_type_id)
        self.incoming_offsets = put(fin.incoming_offsets)
        self.incoming_links = put(fin.incoming_links)
        self.buckets: Dict[int, DeviceBucket] = {
            arity: upload_bucket(b, device) for arity, b in fin.buckets.items()
        }


# NOTE: deliberately NOT donating buffers in the commit kernels — a commit
# must be atomic.  A runtime error (out of device memory, a lost device)
# mid-way through the ~3*arity+2 merge calls would otherwise leave the
# live bucket referencing deleted buffers, bricking the store.  The transient cost is one extra copy of one array
# at a time.
@jax.jit
@obs.named_program("das_merge_padded")
def _merge_padded(base_keys, base_perm, delta_keys, delta_perm):
    """Fixed-shape sorted-index merge into a capacity-padded base: delta
    pad entries (dtype-max keys) sort past the base's pad region, beyond
    the `size` slots the merge builds, so the array length never changes.
    Compiled once per (capacity, delta-class, key dtype) shape — commits
    after the first reuse it."""
    return merge_sorted_index(
        base_keys, base_perm, delta_keys, delta_perm,
        size=base_keys.shape[0],
    )


@jax.jit
@obs.named_program("das_insert_rows")
def _insert_rows(col, block, n):
    """Write a fixed-size delta block at (traced) row offset n — the
    column's shape is static, so this never recompiles per commit."""
    return jax.lax.dynamic_update_slice_in_dim(col, block, n, axis=0)


def _next_capacity(count: int, current: int, maximum: int) -> int:
    if count > maximum:
        from das_tpu.core.exceptions import CapacityOverflowError

        raise CapacityOverflowError(
            f"probe needs {count} rows > max_result_capacity {maximum}"
        )
    cap = max(current, 16)
    while cap < count:
        cap *= 2
    return min(cap, maximum)


class TensorDB(IncrementalCommitMixin, MemoryDB):
    # every scan-indexed get_matched_* is overridden with device probes
    # below, so MemoryDB.prefetch's handle lists are never read
    _needs_scan_indexes = False

    def __init__(self, data: Optional[AtomSpaceData] = None, config: Optional[DasConfig] = None, device=None):
        super().__init__(data)
        self.config = config or DasConfig()
        self._device = device
        self.fin: Finalized = self.data.finalize()
        self.dev = DeviceTables(self.fin, device=device)
        self._reset_delta_state()

    def __repr__(self):
        return "<TensorDB>"

    def refresh(self) -> None:
        """Re-sync the device store after host-side mutations (transaction
        commits).  Small deltas take the INCREMENTAL path: only the new
        records are columnized (a small delta bucket per arity), only those
        columns travel to the device, and each device-resident sorted probe
        index is extended by a two-sorted-array merge (a handful of
        binary searches, then shift networks and one cumsum — no
        re-sort, no scatter, no full re-upload; storage/delta.py
        merge_sorted_index).  The reference's update path is
        likewise incremental (das/das_update_test.py:141-192); a full
        re-finalize at millions of links costs minutes.  Deltas accumulate
        LSM-style; past config.delta_merge_threshold total new atoms the
        store is fully re-finalized and the overlay cleared.  The
        full-vs-delta decision and host-side interning are shared with the
        sharded backend (storage/delta.py).

        Every non-NOOP outcome advances `delta_version` (the mixin's
        commit counter): the incremental path bumps it in _apply_delta,
        and the FULL path replaces `self.dev` outright — which drops the
        cached fused executor AND its delta-version-guarded result cache
        (query/fused.py ResultCache), so no pre-commit answer can survive
        either route."""
        self.prefetch()
        action = self._plan_refresh()
        if action == NOOP:
            return
        if action == FULL:
            # WAL (ISSUE 15): a full rebuild consumes host mutations the
            # incremental log would otherwise miss — record the pending
            # tail (fsynced) BEFORE the rebuild becomes visible, same
            # version the _reset_delta_state bump will land on.  Replay
            # re-inserts the same atoms and lets ITS refresh pick
            # full-vs-incremental; content (and answers) are identical
            # either way.
            wal = self._wal
            if wal is not None:
                wal.append(self.data, self.delta_version + 1, kind="full")
            self.fin = self.data.finalize()
            self.dev = DeviceTables(self.fin, device=self._device)
            self._reset_delta_state()
            return
        self._commit_delta_with_retry(action)

    @classmethod
    def restore(cls, path: str, config: Optional[DasConfig] = None) -> "TensorDB":
        """Warm-state restore (ISSUE 15, storage/durable.py): newest
        VALID snapshot generation under `path` + WAL replay to head +
        warm bundle (CapStore capacities, planner degree statistics,
        count-cache entries) — the replica-fleet cold-start path.
        Commits on the restored store append to the generation's WAL."""
        from das_tpu.storage import durable

        return durable.restore(path, config=config, backend="tensor")

    # -- incremental delta machinery --------------------------------------
    # _apply_delta / _reset_delta_state / host_bucket_segments come from
    # IncrementalCommitMixin; the backend-specific part is the device merge:

    def _grow_bucket(self, base: DeviceBucket, new_cap: int) -> DeviceBucket:
        """Re-pad a bucket to a larger capacity class (rare: only when
        accumulated commits exhaust the ~6% slack).  Real rows — and real
        sorted keys/perms, which occupy the leading positions — are
        preserved; the new slack is sentinel-filled."""
        n = base.size

        def grow(arr, fill):
            pad = jnp.full(
                (new_cap - n, *arr.shape[1:]), fill, dtype=arr.dtype
            )
            return jnp.concatenate([arr[:n], pad], axis=0)

        kmax = lambda a: _key_pad(np.dtype(a.dtype))
        return DeviceBucket(
            arity=base.arity,
            size=n,
            capacity=new_cap,
            rows=grow(base.rows, -1),
            type_id=grow(base.type_id, -1),
            ctype=grow(base.ctype, kmax(base.ctype)),
            targets=grow(base.targets, -2),
            targets_sorted=grow(base.targets_sorted, -2),
            order_by_type=grow(base.order_by_type, 0),
            key_type=grow(base.key_type, kmax(base.key_type)),
            order_by_ctype=grow(base.order_by_ctype, 0),
            key_ctype=grow(base.key_ctype, kmax(base.key_ctype)),
            order_by_type_pos=[grow(x, 0) for x in base.order_by_type_pos],
            key_type_pos=[grow(x, kmax(x)) for x in base.key_type_pos],
            order_by_pos=[grow(x, 0) for x in base.order_by_pos],
            key_pos=[grow(x, kmax(x)) for x in base.key_pos],
            order_by_type_spos=[grow(x, 0) for x in base.order_by_type_spos],
            key_type_spos=[grow(x, kmax(x)) for x in base.key_type_spos],
        )

    def _stage_delta_merge(self, delta: LinkBucket):
        """COMPUTE a commit bucket's merge into the device tables and
        return (swap, became_base, slots): `swap` is the deferred pure
        assignment that makes the merged bucket visible (the
        stage-then-swap commit contract, storage/delta.py _apply_delta),
        became_base when the delta is the first bucket of its arity,
        slots = device rows occupied (flat layout — exactly the delta
        size).  Nothing here mutates `self.dev` — jax arrays are
        immutable, so a failure mid-compute leaves the pre-commit
        tables fully intact.

        Deltas land in the capacity slack with FIXED-shape programs
        (_merge_padded / _insert_rows): after the first commit in a
        capacity class, a commit is pure device work — no retrace, no
        recompile of the merge or of any cached query executable."""
        arity = delta.arity
        put = lambda x: jax.device_put(x, self._device)
        base = self.dev.buckets.get(arity)
        if base is None or base.size == 0:
            # first links of this arity: the delta IS the base
            merged = upload_bucket(delta, self._device)

            def swap():
                self.dev.buckets[arity] = merged

            return swap, True, delta.size
        n, d = base.size, delta.size
        dcap = delta_class(d)
        if n + dcap > base.capacity:
            base = self._grow_bucket(base, capacity_class(n + dcap))

        def dpad(x, fill):
            return put(_pad_rows(x, dcap, fill))

        n_dev = jnp.int32(n)

        def merge(bk, bo, dk, do):
            return _merge_padded(
                bk, bo,
                dpad(dk, _key_pad(dk.dtype)),
                dpad(do.astype(np.int32) + n, 0),
            )

        mt = [merge(base.key_type_pos[p], base.order_by_type_pos[p],
                    delta.key_type_pos[p], delta.order_by_type_pos[p])
              for p in range(arity)]
        mp = [merge(base.key_pos[p], base.order_by_pos[p],
                    delta.key_pos[p], delta.order_by_pos[p])
              for p in range(arity)]
        ms = [merge(base.key_type_spos[p], base.order_by_type_spos[p],
                    delta.key_type_spos[p], delta.order_by_type_spos[p])
              for p in range(arity)]
        kt, ot = merge(base.key_type, base.order_by_type,
                       delta.key_type, delta.order_by_type)
        kc, oc = merge(base.key_ctype, base.order_by_ctype,
                       delta.key_ctype, delta.order_by_ctype)
        ins = lambda col, block, fill: _insert_rows(
            col, dpad(block, fill), n_dev
        )
        merged = DeviceBucket(
            arity=arity,
            size=n + d,
            capacity=base.capacity,
            rows=ins(base.rows, delta.rows, -1),
            type_id=ins(base.type_id, delta.type_id, -1),
            ctype=ins(base.ctype, delta.ctype, _key_pad(np.int64)),
            targets=ins(base.targets, delta.targets, -2),
            targets_sorted=ins(base.targets_sorted, delta.targets_sorted, -2),
            order_by_type=ot,
            key_type=kt,
            order_by_ctype=oc,
            key_ctype=kc,
            order_by_type_pos=[o for _, o in mt],
            key_type_pos=[k for k, _ in mt],
            order_by_pos=[o for _, o in mp],
            key_pos=[k for k, _ in mp],
            order_by_type_spos=[o for _, o in ms],
            key_type_spos=[k for k, _ in ms],
        )

        def swap():
            self.dev.buckets[arity] = merged

        return swap, False, d

    # host_bucket_segments: backend-local base bucket + overlay segments —
    # provided by IncrementalCommitMixin (shared with the sharded backend)

    # -- low-level probes (shared with the query compiler) -----------------

    def _type_id(self, link_type: str) -> Optional[int]:
        h = self.data.table.get_named_type_hash(link_type)
        return self.fin.type_id_of_hash.get(h)

    def _row_of(self, handle_hex: str) -> Optional[int]:
        return self.fin.row_of_hex.get(handle_hex)

    def probe_ordered_padded(
        self,
        arity: int,
        type_id: Optional[int],
        fixed: Tuple[Tuple[int, int], ...],
    ):
        """Padded device probe with capacity retry: returns (local, mask)
        device arrays, or None when the bucket is empty."""
        db = self.dev.buckets.get(arity)
        if db is None or db.size == 0:
            return None
        cap = min(self.config.initial_result_capacity, max(db.size, 16))
        while True:
            local, mask, range_count = self._probe_ordered_padded(
                db, type_id, fixed, cap
            )
            # overflow is judged on the *range* count (the pre-verification
            # superset): candidates beyond `cap` were never verified
            if int(range_count) <= cap:
                return local, mask
            cap = _next_capacity(int(range_count), cap, self.config.max_result_capacity)

    def probe_ordered(
        self,
        arity: int,
        type_id: Optional[int],
        fixed: Tuple[Tuple[int, int], ...],
    ) -> np.ndarray:
        """Bucket-local rows matching a positional wildcard pattern.
        `fixed` = ((position, global_target_row), ...).  Returns int32[n]."""
        padded = self.probe_ordered_padded(arity, type_id, fixed)
        if padded is None:
            return np.empty(0, dtype=np.int32)
        local, mask = padded
        return np.asarray(local)[np.asarray(mask)]

    def _probe_ordered_padded(self, db: DeviceBucket, type_id, fixed, cap: int):
        """One padded probe round: returns (local, verified_mask, range_count)."""
        if type_id is not None and fixed:
            p0, v0 = fixed[0]
            key = (np.int64(type_id) << 32) | np.int64(v0)
            local, valid, range_count = posting.range_probe(
                db.key_type_pos[p0], db.order_by_type_pos[p0], key, cap
            )
            rest = tuple(fixed[1:])
            mask = posting.verify_positions(
                db.targets, db.type_id, local, valid, jnp.int32(-1), rest
            )
        elif type_id is not None:
            local, valid, range_count = posting.range_probe(
                db.key_type, db.order_by_type, np.int32(type_id), cap
            )
            mask = valid
        elif fixed:
            p0, v0 = fixed[0]
            local, valid, range_count = posting.range_probe(
                db.key_pos[p0], db.order_by_pos[p0], np.int32(v0), cap
            )
            rest = tuple(fixed[1:])
            mask = posting.verify_positions(
                db.targets, db.type_id, local, valid, jnp.int32(-1), rest
            )
        else:
            local, valid, range_count = posting.full_scan(np.int32(db.size), cap)
            mask = valid
        return local, mask, range_count

    def probe_unordered_padded(
        self,
        arity: int,
        type_id: Optional[int],
        required: Tuple[Tuple[int, int], ...],
    ):
        """Padded unordered (multiset) probe: returns (local, mask) device
        arrays, or None when the bucket is empty.  Candidates contain every
        required (global_row, count) with multiplicity, any position."""
        db = self.dev.buckets.get(arity)
        if db is None or db.size == 0:
            return None
        if not required:
            return self.probe_ordered_padded(arity, type_id, ())
        cap = min(self.config.initial_result_capacity, max(db.size * arity, 16))
        v0 = required[0][0]
        while True:
            locals_, valids, counts = [], [], []
            for p in range(arity):
                if type_id is not None:
                    key = (np.int64(type_id) << 32) | np.int64(v0)
                    local, valid, range_count = posting.range_probe(
                        db.key_type_spos[p], db.order_by_type_spos[p], key, cap
                    )
                else:
                    local, valid, range_count = posting.range_probe(
                        db.key_pos[p], db.order_by_pos[p], np.int32(v0), cap
                    )
                locals_.append(local)
                valids.append(valid)
                counts.append(range_count)
            max_range = max(int(c) for c in counts)
            if max_range > cap:
                cap = _next_capacity(max_range, cap, self.config.max_result_capacity)
                continue
            local = jnp.concatenate(locals_)
            valid = jnp.concatenate(valids)
            local, keep = posting.dedup_sorted(local, valid)
            mask = posting.verify_multiset(
                db.targets,
                db.type_id,
                local,
                keep,
                jnp.int32(-1 if type_id is None else type_id),
                tuple(required),
            )
            return local, mask

    def probe_unordered(
        self,
        arity: int,
        type_id: Optional[int],
        required: Tuple[Tuple[int, int], ...],
    ) -> np.ndarray:
        """Bucket-local rows containing every required (global_row, count)
        with multiplicity, irrespective of position."""
        padded = self.probe_unordered_padded(arity, type_id, required)
        if padded is None:
            return np.empty(0, dtype=np.int32)
        local, mask = padded
        return np.asarray(local)[np.asarray(mask)]

    def probe_ctype_padded(self, arity: int, ctype_i64: int):
        """Padded template-index probe for one arity bucket."""
        db = self.dev.buckets.get(arity)
        if db is None or db.size == 0:
            return None
        cap = min(self.config.initial_result_capacity, max(db.size, 16))
        while True:
            local, valid, count = posting.range_probe(
                db.key_ctype, db.order_by_ctype, np.int64(ctype_i64), cap
            )
            if int(count) <= cap:
                return local, valid
            cap = _next_capacity(int(count), cap, self.config.max_result_capacity)

    def probe_ctype(self, ctype_i64: int) -> Dict[int, np.ndarray]:
        """Rows per arity whose composite type hash matches (template index)."""
        out = {}
        for arity in self.dev.buckets:
            padded = self.probe_ctype_padded(arity, ctype_i64)
            if padded is None:
                continue
            local, valid = padded
            sel = np.asarray(local)[np.asarray(valid)]
            if sel.size:
                out[arity] = sel
        return out

    # -- materialization helpers ------------------------------------------

    def _materialize(self, arity: int, local_rows: np.ndarray):
        """Bucket-local rows -> (handle, target hexes); locals past the base
        bucket size index into the per-commit delta overlay segments."""
        segments = self.host_bucket_segments(arity)
        hexes = self.fin.hex_of_row
        out = []
        for i in local_rows:
            j = int(i)
            for b in segments:
                if j < b.size:
                    break
                j -= b.size
            row = int(b.rows[j])
            tg = tuple(
                hexes[int(t)] if int(t) >= 0 else WILDCARD
                for t in b.targets[j]
            )
            out.append((hexes[row], tg))
        return out

    # -- DBInterface probe overrides ---------------------------------------

    def get_matched_links(self, link_type: str, target_handles: List[str]):
        if link_type != WILDCARD and WILDCARD not in target_handles:
            handle = self.get_link_handle(link_type, target_handles)
            return [handle] if handle in self.data.links else []
        arity = len(target_handles)
        black_list = self.data.pattern_black_list
        if link_type == WILDCARD:
            type_id = None
        else:
            if link_type in black_list:
                return []  # no pattern index for blacklisted types
            type_id = self._type_id(link_type)
            if type_id is None:
                return []
        unordered = link_type in UNORDERED_LINK_TYPES and link_type != WILDCARD
        grounded: List[Tuple[int, int]] = []
        for p, h in enumerate(target_handles):
            if h == WILDCARD:
                continue
            row = self._row_of(h)
            if row is None:
                return []
            grounded.append((p, row))
        if unordered:
            counts: Dict[int, int] = {}
            for _, row in grounded:
                counts[row] = counts.get(row, 0) + 1
            local = self.probe_unordered(
                arity, type_id, tuple(sorted(counts.items()))
            )
        else:
            local = self.probe_ordered(arity, type_id, tuple(grounded))
        out = self._materialize(arity, local)
        if type_id is None and black_list:
            out = [
                (h, tg) for h, tg in out
                if self.data.links[h].named_type not in black_list
            ]
        return out

    def get_matched_type_template(self, template):
        hashed = self._hash_template(template)
        template_hash = self._flatten_template_hash(hashed)
        from das_tpu.core.hashing import hex_to_i64

        per_arity = self.probe_ctype(int(hex_to_i64(template_hash)))
        out = []
        for arity, local in sorted(per_arity.items()):
            out.extend(self._materialize(arity, local))
        return out

    def get_matched_type(self, link_type: str):
        type_id = self._type_id(link_type)
        if type_id is None:
            return []
        out = []
        for arity in sorted(self.dev.buckets):
            local = self.probe_ordered(arity, type_id, ())
            if local.size:
                out.extend(self._materialize(arity, local))
        return out

    # get_incoming: base CSR + delta overlay — provided by
    # IncrementalCommitMixin (shared with the sharded backend)
