"""Shared incremental-commit machinery for device-resident backends.

The reference's update path is incremental: a transaction commit re-parses
only the new expressions and inserts them into the live Mongo collections
and Redis index sets (das/das_update_test.py:141-192,
distributed_atom_space.py:326-334).  The TPU analogue — re-finalizing and
re-uploading the whole store — would cost minutes at millions of links, so
both device backends (storage/tensor_db.py, parallel/sharded_db.py) commit
deltas instead:

  * the host-side part is IDENTICAL for both and lives here: decide
    whether a delta is safe (`plan_refresh`), intern the new atoms into
    the live `Finalized` registries (`intern_delta`), and maintain the
    delta incoming-set overlay consulted by `get_incoming`;
  * the device-side part differs by layout: TensorDB extends flat
    `[m]` sorted indexes, ShardedDB extends stacked `[S, m_local]`
    slab-local indexes under `shard_map` — both with the same
    two-sorted-array merge (`merge_sorted_index`: |delta| binary
    searches, then shift networks of O(log |delta|) elementwise passes
    and one cumsum — no re-sort, no scatter, no whole-table gather).

Deltas accumulate LSM-style; past `config.delta_merge_threshold` total new
atoms the caller fully re-finalizes and clears the overlay.
"""

from __future__ import annotations

from itertools import islice
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


#: sentinel returned by plan_refresh when only a full rebuild is safe
FULL = "full"
#: sentinel returned by plan_refresh when nothing changed
NOOP = "noop"


def capacity_class(n: int) -> int:
    """Device-bucket capacity for n real rows: ~6% slack (min 64) absorbs
    commits without changing array shapes.  Shared by both backends so
    they grow/compact at the same ratio; deterministic so compile caches
    hit across processes for the same store size."""
    return n + max(64, n >> 4)


def delta_class(d: int) -> int:
    """Pow2 size class (min 64) for a commit's padded delta block — keeps
    the set of compiled fixed-shape merge programs small."""
    return max(64, 1 << (d - 1).bit_length()) if d > 1 else 64


def _shifted(x, step: int, fill):
    """x read `step` slots to the left (step > 0: out[q] = x[q - step]) or
    to the right (step < 0: out[q] = x[q + |step|]); `fill` where that
    slot does not exist.  A static shift: one pad and one slice."""
    n = x.shape[0]
    pad = jnp.full((min(abs(step), n),), fill, x.dtype)
    if step > 0:
        return jnp.concatenate([pad, x[: max(n - step, 0)]])
    return jnp.concatenate([x[min(-step, n):], pad])


def _bit_set(s, k: int):
    return ((s >> k) & 1) == 1


def _stage(owed, k: int, step: int):
    """One stage of a shift network: an element moves by `step` slots
    (signed: > 0 to the right) iff bit k of what it owes is set.  Returns
    the mask of slots an element arrives at, and `owed` after the move
    (-1 where no element is left)."""
    source = _shifted(owed, step, -1)
    arrives = (source >= 0) & _bit_set(source, k)
    stays = (owed >= 0) & ~_bit_set(owed, k)
    return arrives, jnp.where(arrives, source, jnp.where(stays, owed, -1))


def _expand(vals, owed, nbits: int):
    """Shift network, moving right: the element at slot q still owes
    `owed[q]` slots (-1: the slot is a hole) and ends at q + owed[q]; its
    values ride along in `vals`.  One stage per bit, most significant
    first, each a select between a slot and the slot 2^k to its left.
    The shifts must not decrease from one element to the next: then no
    two elements ever meet, whatever prefix of the bits has been walked.
    Holes keep whatever value was there; `owed` says which slots count."""
    for k in reversed(range(nbits)):
        arrives, owed = _stage(owed, k, 1 << k)
        vals = [jnp.where(arrives, _shifted(v, 1 << k, 0), v) for v in vals]
    return vals, owed


def _compress(owed, nbits: int):
    """The inverse walk, moving left: the element at slot q moves to
    q - owed[q] (-1: no element) and takes its count with it.  Least
    significant bit first; the counts must not decrease from one element
    to the next and may grow by at most the holes between them."""
    for k in range(nbits):
        _, owed = _stage(owed, k, -(1 << k))
    return owed


def _fit(x, n: int, fill=0):
    """x cut to n rows, or extended to them with `fill`."""
    if x.shape[0] >= n:
        return x[:n]
    return jnp.concatenate([x, jnp.full((n - x.shape[0],), fill, x.dtype)])


def merge_sorted_index(base_keys, base_perm, delta_keys, delta_perm,
                       size=None):
    """Extend a device-resident sorted index by a small sorted delta and
    return the first `size` slots of the merge (default: all nb + nd).
    Ties place base elements first (side='right'), preserving stability.
    delta_perm must already be offset into the merged row space.

    Every output slot is built by READING: no operation of the program
    is a scatter, and the only gathers are the nd binary searches.  The
    cost is O(size * log nd) in elementwise passes over static shifts:

      * `ins` = where each delta row lands in the base (nd searches);
        delta row i ends at slot ins_i + i;
      * the delta rows travel there.  The shift ins_i is split at the
        delta width w = 2^lw: the high part names the w-wide stretch
        of slots the row starts in (one broadcast compare: stretch m
        holds row i at its i-th slot iff ins_i >> lw == m), the low
        part (< w) is walked by an expand network of lw stages;
      * the slots they reached are the marks; one cumsum counts the
        marks before each slot, a compress network carries that count
        from each base element's final slot back to its index, and a
        second expand network moves keys and perm of the base by it;
      * a select puts the two together.
    """
    nb, nd = base_keys.shape[0], delta_keys.shape[0]
    if size is None:
        size = nb + nd
    if not 0 <= size <= nb + nd:
        raise ValueError(f"size {size} outside the merge's {nb + nd} slots")
    if nd == 0:
        return base_keys[:size], base_perm[:size]
    lw = (nd - 1).bit_length()
    w = 1 << lw
    # named scopes label the two stages in a device trace (trace-time
    # only): where the delta lands, and the networks that build the
    # merged arrays
    with jax.named_scope("searchsorted"):
        ins = jnp.searchsorted(
            base_keys, delta_keys, side="right"
        ).astype(jnp.int32)
    with jax.named_scope("shift_network"):
        slot = jnp.arange(size, dtype=jnp.int32)

        def per_stretch(x, fill=0):
            # slot q reads x[q mod w]: the delta block, once per stretch
            return jnp.tile(_fit(x, w, fill), -(-size // w))[:size]

        start = per_stretch(ins, -1)
        owed = jnp.where(
            (start >= 0) & ((start >> lw) == (slot >> lw)),
            start & (w - 1), -1,
        )
        (d_keys, d_perm), owed = _expand(
            [per_stretch(delta_keys), per_stretch(delta_perm)], owed, lw,
        )
        is_delta = owed >= 0
        marks = is_delta.astype(jnp.int32)
        before = jnp.cumsum(marks) - marks   # delta slots left of each slot
        nbits = nd.bit_length()              # a base element moves 0..nd
        shift = _compress(jnp.where(is_delta, -1, before), nbits)
        (b_keys, b_perm), _ = _expand(
            [_fit(base_keys, size), _fit(base_perm, size)], shift, nbits
        )
        keys = jnp.where(is_delta, d_keys, b_keys)
        perm = jnp.where(is_delta, d_perm, b_perm)
    return keys, perm


class IncrementalCommitMixin:
    """Host-side delta-commit state shared by TensorDB and ShardedDB.

    Expects the host class to provide `self.data` (AtomSpaceData),
    `self.fin` (the live Finalized), and `self.config` (DasConfig).
    """

    #: write-ahead delta log (ISSUE 15, storage/durable.py DeltaLog) —
    #: armed by durable.attach/restore when a snapshot root is
    #: configured.  The class-level None IS the disabled fast path:
    #: with no WAL, `_apply_delta` reads one attribute and branches —
    #: byte-for-byte the pre-dasdur commit behavior, no allocations
    #: (the disabled-path identity pin, tests/test_zdur.py).
    _wal = None
    #: snapshot root this backend persists under (durable.attach)
    _snapshot_root = None

    def _reset_delta_state(self) -> None:
        # monotone commit counter: bumps on every device-table mutation —
        # full rebuilds land here, incremental commits in _apply_delta.
        # Device-resident result caches (query/fused.py ResultCache) key
        # on it, so a commit invalidates exactly the entries written
        # against the pre-commit store and nothing else survives stale.
        self.delta_version = getattr(self, "delta_version", 0) + 1
        from das_tpu import obs

        if obs.enabled():
            # full (re)build: every cached answer and degree statistic
            # keyed on the previous version is now stale — the trace
            # event that explains a post-rebuild cold stretch
            obs.event("commit.rebuild", version=self.delta_version)
            obs.counter("commit.rebuilds").inc()
        self._base_counts = (len(self.data.nodes), len(self.data.links))
        self._delta_incoming: Dict[int, list] = {}  # target_row -> [link_rows]
        self._delta_total = 0
        # backend-LOCAL view of the finalized buckets: several backends may
        # share one Finalized, and each backend's delta segments must pair
        # with the base its own device tables were built from — a shared
        # fin.buckets entry must never be overwritten by whichever backend
        # commits a new arity first
        self._base_buckets: Dict[int, object] = dict(self.fin.buckets)
        self._host_delta: Dict[int, list] = {}  # arity -> overlay segments

    def host_bucket_segments(self, arity: int):
        """Host-side column segments — the backend's base bucket plus one
        overlay segment per incremental commit — for exact candidate
        estimates (query/fused.py estimate_plan_rows) and, on TensorDB,
        bucket-local row materialization.  Their concatenation (in order)
        mirrors this backend's merged device row space exactly."""
        out = []
        base = self._base_buckets.get(arity)
        if base is not None and base.size:
            out.append(base)
        out.extend(self._host_delta.get(arity, ()))
        return out

    def _plan_refresh(self):
        """Classify the pending host mutations: NOOP (nothing changed),
        FULL (only a rebuild is safe), or the (new_node_hexes,
        new_link_hexes) of an applicable incremental commit."""
        n_nodes, n_links = len(self.data.nodes), len(self.data.links)
        d_nodes = n_nodes - self._base_counts[0]
        d_links = n_links - self._base_counts[1]
        if d_nodes == 0 and d_links == 0:
            return NOOP
        if (
            d_nodes < 0
            or d_links < 0
            or self.fin.atom_count == 0  # bulk load onto an empty store
            or self._delta_total + d_nodes + d_links
            > self.config.delta_merge_threshold
        ):
            return FULL
        new_node_hexes = list(islice(reversed(self.data.nodes), d_nodes))[::-1]
        new_link_hexes = list(islice(reversed(self.data.links), d_links))[::-1]
        dangled_on = self.fin.dangling_hexes
        if dangled_on is None:
            # restored store with sentinel targets but no recorded set:
            # cannot prove the commit is safe -> rebuild once
            return FULL
        if dangled_on and any(
            h in dangled_on for h in (*new_node_hexes, *new_link_hexes)
        ):
            # an existing link's sentinel (-1) target just materialized;
            # sorted positional indexes can't be retro-patched in place
            return FULL
        return new_node_hexes, new_link_hexes

    def _intern_type(self, named_type_hash: str, named_type: str) -> int:
        tid = self.fin.type_id_of_hash.get(named_type_hash)
        if tid is None:
            tid = len(self.fin.type_names)
            self.fin.type_id_of_hash[named_type_hash] = tid
            self.fin.type_names.append(named_type)
        return tid

    def _intern_delta(
        self, new_node_hexes: List[str], new_link_hexes: List[str]
    ) -> Dict[int, list]:
        """Append the new atoms to the live row registries (nodes first,
        then links bucket-major, matching finalize()'s global row order)
        and return the new link records grouped by arity.

        IDEMPOTENT across backends: the Finalized may be shared (a
        ShardedDB and its tree-fallback TensorDB over one AtomSpaceData),
        so only atoms beyond `fin.interned` are appended — a backend whose
        device tables lag behind still gets its full per-device delta in
        the returned grouping, but never double-interns rows another
        backend already registered."""
        fin = self.fin
        if fin.interned is None:
            # restored checkpoint predating the counters: at restore time
            # the registry exactly covers the records (load() verifies)
            fin.interned = [fin.node_count, fin.atom_count - fin.node_count]
        n_nodes_new = len(self.data.nodes) - fin.interned[0]
        n_links_new = len(self.data.links) - fin.interned[1]
        # the tail of this backend's delta that nobody has interned yet
        # (new_*_hexes are the trailing entries of the insertion-ordered
        # record dicts, so the registry tail is a suffix of them)
        to_intern_nodes = new_node_hexes[len(new_node_hexes) - n_nodes_new:] if n_nodes_new > 0 else []
        to_intern_links = new_link_hexes[len(new_link_hexes) - n_links_new:] if n_links_new > 0 else []
        for h in to_intern_nodes:
            rec = self.data.nodes[h]
            self._intern_type(rec.named_type_hash, rec.named_type)
            fin.row_of_hex[h] = len(fin.hex_of_row)
            fin.hex_of_row.append(h)
        intern_by_arity: Dict[int, list] = {}
        for h in to_intern_links:
            rec = self.data.links[h]
            intern_by_arity.setdefault(len(rec.elements), []).append((h, rec))
        for arity in sorted(intern_by_arity):
            for h, _rec in intern_by_arity[arity]:
                fin.row_of_hex[h] = len(fin.hex_of_row)
                fin.hex_of_row.append(h)
        fin.atom_count = len(fin.hex_of_row)
        fin.interned = [len(self.data.nodes), len(self.data.links)]
        # the caller's device merge needs ALL of its new links, interned
        # here or by another backend earlier
        by_arity: Dict[int, list] = {}
        for h in new_link_hexes:
            rec = self.data.links[h]
            by_arity.setdefault(len(rec.elements), []).append((h, rec))
        return by_arity

    def _record_delta_incoming(self, incoming_pairs) -> None:
        """incoming_pairs: (target_rows, link_rows) numpy array chunks as
        produced by build_bucket."""
        for trows, lrows in incoming_pairs:
            for trow, lrow in zip(trows.tolist(), lrows.tolist()):
                self._delta_incoming.setdefault(trow, []).append(lrow)

    def _apply_delta(self, new_node_hexes: List[str], new_link_hexes: List[str]) -> None:
        """One incremental commit, STAGE-THEN-SWAP (ISSUE 13): intern the
        atoms (idempotent — see _intern_delta), columnize each arity's
        new links (storage/atom_table.py build_bucket), and COMPUTE every
        device merge via the backend's `_stage_delta_merge`, which
        returns (swap, became_base, slots) — jax arrays are immutable,
        so staging produces entirely new structures and the returned
        `swap` thunk is a pure reference assignment.  Only after every
        arity staged do the swaps, the incoming-overlay updates, and the
        `delta_version` bump run, so a failure ANYWHERE in the fallible
        half leaves the store exactly at the pre-commit state: version
        unbumped, result/tree caches still valid, device tables
        untouched — and re-running the same commit succeeds (the chaos
        atomicity pin, tests/test_zfault.py).  `fault.maybe_fail` marks
        the declared mid-commit crash point between the halves.
        Memory amplification is bounded STRUCTURALLY: both device layouts
        are capacity-padded with fixed slack, and a layout that can't
        absorb a commit triggers growth (tensor) or early LSM compaction
        (sharded) on its own — both raised while staging, i.e. before
        anything became visible."""
        from das_tpu import fault, obs
        from das_tpu.storage.atom_table import build_bucket

        fin = self.fin
        # spans where the work happens: commit.apply holds commit.stage
        # (the host cost of enqueueing the merges: device dispatch is
        # asynchronous), dur.wal_append (durable.py) and commit.swap; a
        # failure in the fallible half leaves no commit.swap behind
        with obs.span(
            "commit.apply", version=self.delta_version + 1,
            nodes=len(new_node_hexes), links=len(new_link_hexes),
        ):
            # -- fallible half: stage (no visible mutation) ---------------
            with obs.span("commit.stage"):
                by_arity = self._intern_delta(new_node_hexes, new_link_hexes)
                staged = []
                for arity, entries in sorted(by_arity.items()):
                    # (target_rows, link_rows) array chunks from build_bucket
                    incoming_pairs: list = []
                    commit_bucket = build_bucket(
                        arity, entries, fin.row_of_hex, self._intern_type,
                        incoming_pairs, fin.dangling_hexes,
                    )
                    swap, became_base, slots = self._stage_delta_merge(
                        commit_bucket
                    )
                    staged.append(
                        (arity, commit_bucket, incoming_pairs, swap,
                         became_base, slots)
                    )
            fault.maybe_fail("commit_apply")
            # -- write-ahead log (ISSUE 15): the interned delta is
            # framed, checksummed and FSYNCED before the swap makes
            # anything visible, so a crash on either side of the swap is
            # recoverable (logged-but-unswapped replays at restore;
            # swapped-and-logged is simply durable).  A WAL failure lands
            # in the fallible half — store untouched, the shared
            # RetryPolicy re-stages, and a retried append's duplicate
            # record dedups by delta_version at replay
            # (durable.replay_wal).  No WAL configured (`_wal` is the
            # class-level None): one attribute read, zero new work.
            wal = self._wal
            if wal is not None:
                wal.append(self.data, self.delta_version + 1)
            # -- infallible half: swap (pure assignments) -----------------
            with obs.span("commit.swap"):
                slot_growth = 0
                for arity, commit_bucket, incoming_pairs, swap, \
                        became_base, slots in staged:
                    swap()
                    self._record_delta_incoming(incoming_pairs)
                    slot_growth += slots
                    if became_base:
                        # first links of this arity: the delta bucket is
                        # the base for THIS backend (fin.buckets may be
                        # shared with another backend whose device
                        # tables differ)
                        self._base_buckets[arity] = commit_bucket
                    else:
                        self._host_delta.setdefault(arity, []).append(
                            commit_bucket
                        )
                self._base_counts = (
                    len(self.data.nodes), len(self.data.links)
                )
                self._delta_total += max(
                    slot_growth, len(new_node_hexes) + len(new_link_hexes)
                )
                # the device tables just changed under any live executor:
                # answers cached against the pre-commit version must stop
                # hitting
                self.delta_version += 1
        if obs.enabled():
            obs.event(
                "commit.delta", version=self.delta_version,
                nodes=len(new_node_hexes), links=len(new_link_hexes),
            )
            obs.counter("commit.deltas").inc()
        if self.data.columnar is not None:
            # a commit happened, so more commits (and their membership
            # probes) are likely: build the digest indexes NOW — the
            # commit that just ran kept its own probes on the cheap
            # linear path, every later one gets microsecond lookups
            self.data.columnar.ensure_indexes()

    def _commit_delta_with_retry(self, action) -> None:
        """Both backends' refresh() commit entry: the shared
        fault.RetryPolicy (ISSUE 13) retries a transport-class apply
        failure — safe precisely because _apply_delta is
        stage-then-swap, so a failed attempt left no visible state.
        Non-retryable failures (SlabCapacityExhausted, semantic errors)
        propagate untouched to the backend's own recovery."""
        from das_tpu import fault

        fault.commit_retry().run(lambda: self._apply_delta(*action))

    def get_incoming(self, handle: str) -> List[str]:
        """Incoming set = base CSR rows + the delta overlay (links committed
        since the last full finalize)."""
        row = self.fin.row_of_hex.get(handle)
        if row is None:
            return []
        out: List[str] = []
        if row + 1 < self.fin.incoming_offsets.shape[0]:  # base CSR rows
            lo = int(self.fin.incoming_offsets[row])
            hi = int(self.fin.incoming_offsets[row + 1])
            out = [
                self.fin.hex_of_row[int(r)]
                for r in self.fin.incoming_links[lo:hi]
            ]
        for r in self._delta_incoming.get(row, ()):
            out.append(self.fin.hex_of_row[int(r)])
        return out
