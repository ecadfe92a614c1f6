"""Mesh execution of the generalized query tree (VERDICT r02 item 5).

`ShardedTreeOps` plugs into the tree evaluator's op layer
(das_tpu/query/tree.py `TreeOps`): the SAME evaluator — join condition
matrix, union/difference, negation filtering, the reseed quirk — runs with
every CTable's rows sharded across the mesh, so unordered (Set/Similarity)
links and negation trees execute on all chips instead of a replicated
single-chip tree copy (the round-2 design,
parallel/sharded_db.py:596-631).

Representation: a sharded CTable holds GLOBAL jax.Arrays of shape
[S*cap, k] with `NamedSharding(mesh, P("shards"))` on the row axis — each
shard owns a contiguous [cap, k] block.  Row-wise mask algebra
(ops/composite.py) runs eagerly on these arrays with sharding propagation
(no collectives: every mask is per-row; these are the only per-primitive
dispatches left on this route).  Cross-row combinators and leaf probes are
COMPILED programs: each is one `jax.jit(shard_map(...))` built by `_smap`
(parallel/mesh.py `table_program`) and kept in `_fn_cache` under its
statics (pairs, extra, cap, perm, arity, counts), so a second query of a
shape, and a capacity retry back at a capacity already seen, dispatch one
cached executable:

  * leaf probes  — slab-local searchsorted over the ShardedBucket probe
                   indexes (ZERO communication; each link lives on exactly
                   one shard, so leaf tables have no cross-shard
                   duplicates);
  * join         — broadcast-RIGHT: ONE tiled all_gather of the right
                   (newly-joined) table, then shard-local
                   `_join_tables_impl`.  join_ctables keeps the
                   accumulator on the left, so the gathered side is the
                   per-term table; side selection by size (the
                   fused_sharded strategy) is a future refinement;
  * dedup        — shard-local only.  Cross-shard duplicates (possible
                   after projections) survive on device and are removed by
                   the host assignment-set identity at materialization,
                   which tree.py establishes anyway for reference-exact
                   dedup semantics;
  * anti_join /
    difference   — the tabu side is REPLICATED first (`replicate`: one
                   all_gather), because a row must be removed on whichever
                   shard it lives — shard-local tabu would miss
                   cross-shard twins;
  * counts       — `valid.sum()` on the sharded validity vector (XLA
                   inserts the cross-shard reduction).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from das_tpu.core.exceptions import CapacityOverflowError
from das_tpu.parallel.mesh import SHARD_AXIS, table_program
from das_tpu.ops import composite as comp_ops
from das_tpu.ops import posting
from das_tpu.ops.join import _anti_join_impl, _dedup_table_impl, _join_tables_impl
from das_tpu.query import compiler as qc
from das_tpu.query.plan import PUTermPlan
from das_tpu.query.tree import CTable, TreeOps, _finish_uterm


class ShardedTreeOps(TreeOps):
    """Mesh implementation of the tree evaluator's op layer."""

    def __init__(self, db):
        super().__init__(db)
        self.mesh = db.mesh
        self.S = db.mesh.devices.size
        #: id(t) -> (t, replicated) — the SOURCE table is kept alive so a
        #: freed id can never be recycled onto a different table (a bare
        #: id-keyed cache silently returned the previous query's rows)
        self._replicated: Dict[int, Tuple[CTable, CTable]] = {}
        #: static-params -> ONE jitted shard_map program (`_smap`).  The
        #: key holds every static the body closes over; data rides as
        #: operands.  A bare shard_map is not compiled as a whole (its
        #: body dispatches primitive by primitive), and a fresh jit per
        #: call would compile on every join/dedup/anti/replicate of
        #: every query node
        self._fn_cache: Dict[Tuple, object] = {}

    # -- shard_map plumbing ------------------------------------------------

    def _smap(self, fn, n_in, n_out, **specs):
        """The one door to a mesh table program (parallel/mesh.py
        `table_program`: `fn` over row-sharded operands, jitted whole)."""
        return table_program(self.mesh, fn, n_in, n_out, **specs)

    def _cached(self, key, build):
        fn = self._fn_cache.get(key)
        if fn is None:
            fn = build()
            self._fn_cache[key] = fn
        return fn

    def _flatten(self, vals, valid):
        """[S, cap, k] / [S, cap] stacked slabs -> [S*cap, k] / [S*cap]
        global row-sharded arrays (pure local reshape, zero comm)."""
        def body(v, m):
            return v.reshape(-1, v.shape[-1]), m.reshape(-1)

        return self._cached(("flatten",), lambda: self._smap(body, 2, 2))(
            vals, valid
        )

    # -- leaves ------------------------------------------------------------

    def run_term(self, plan) -> Optional[CTable]:
        st = self.db._term_table(plan)
        if st is None or st.count == 0:
            return None
        vals, valid = self._flatten(st.vals, st.valid)
        return CTable(
            kind="O",
            onames=st.var_names,
            ocols=tuple(range(len(st.var_names))),
            ugroups=(),
            vals=vals,
            valid=valid,
            count=st.count,
        )

    def run_uterm(self, plan: PUTermPlan) -> Optional[CTable]:
        sb = self.db.tables.buckets.get(plan.arity)
        if sb is None or sb.size == 0:
            return None
        arity = plan.arity
        required = tuple(plan.required)
        probe_type = -1
        if plan.ctype is not None:
            probes = [(sb.key_ctype, sb.order_by_ctype, np.int64(plan.ctype))]
        elif required:
            v0 = required[0][0]
            if plan.type_id is not None:
                probe_type = plan.type_id
                probes = [
                    (sb.key_type_pos[p], sb.order_by_type_pos[p],
                     np.int64((plan.type_id << 32) | v0))
                    for p in range(arity)
                ]
            else:
                probes = [
                    (sb.key_pos[p], sb.order_by_pos[p], np.int64(v0))
                    for p in range(arity)
                ]
        elif plan.type_id is not None:
            probes = [(sb.key_type, sb.order_by_type, np.int64(plan.type_id))]
        else:
            probes = None  # full slab scan
        req_vals = np.asarray(
            [v for v, c in required for _ in range(c)], dtype=np.int32
        )
        k = len(plan.var_names)
        cap = min(
            self.config_cap(), max(sb.m_local * max(1, len(probes or [1])), 16)
        )
        keys = [p[0] for p in (probes or [])]
        perms = [p[1] for p in (probes or [])]
        n_keys = len(keys)
        # per-call DATA rides as traced replicated args; only shape-defining
        # statics key the function cache, so capacity retries and repeated
        # mesh uterm probes of the same shape reuse one compiled program
        pk_arr = np.asarray([p[2] for p in (probes or [])], dtype=np.int64)
        pair_vals = np.asarray([v for v, _ in required], dtype=np.int32)
        pair_cnts = np.asarray([c for _, c in required], dtype=np.int32)
        pt_arr = np.asarray([probe_type], dtype=np.int32)
        n_pairs = len(required)
        n_req = int(req_vals.size)

        def build(cap):
            def body(*args):
                targets, targets_sorted, type_col = args[:3]
                ks = args[3 : 3 + n_keys]
                ps = args[3 + n_keys : 3 + 2 * n_keys]
                pk_a, pv_a, pc_a, rv_a, pt_a = args[3 + 2 * n_keys :]
                t, ts, tc = targets[0], targets_sorted[0], type_col[0]
                if n_keys == 0:
                    m = t.shape[0]
                    local = jnp.arange(m, dtype=jnp.int32)
                    keep = tc != -1
                    worst = jnp.int32(0)
                else:
                    locs, valids, cnts = [], [], []
                    for i in range(n_keys):
                        local, valid, cnt = posting.range_probe(
                            ks[i][0], ps[i][0], pk_a[i], cap
                        )
                        locs.append(local)
                        valids.append(valid)
                        cnts.append(cnt)
                    local = jnp.concatenate(locs)
                    valid = jnp.concatenate(valids)
                    local, keep = posting.dedup_sorted(local, valid)
                    worst = jnp.max(jnp.stack(cnts))
                mask = posting.verify_multiset_traced(
                    t, tc, local, keep, pt_a[0], pv_a, pc_a, n_pairs
                )
                tvals, tmask = comp_ops.build_uterm_table(
                    ts, local, mask, rv_a, n_req, k
                )
                return tvals[None], tmask[None], worst[None]

            n_in = 3 + 2 * n_keys + 5
            return self._smap(
                body, n_in, 3, replicated_in=tuple(range(n_in - 5, n_in))
            )

        while True:
            fn = self._cached(
                ("uterm", arity, n_keys, cap, n_pairs, n_req, k),
                lambda: build(cap),
            )
            vals, mask, worsts = fn(
                sb.targets, sb.targets_sorted, sb.type_id, *keys, *perms,
                pk_arr, pair_vals, pair_cnts, req_vals, pt_arr,
            )
            worst = int(np.max(np.asarray(worsts)))
            if worst <= cap:
                break
            if cap >= self.db.config.max_result_capacity:
                raise CapacityOverflowError(
                    f"uterm probe needs {worst} rows > max_result_capacity"
                )
            cap = min(max(cap * 2, worst), self.db.config.max_result_capacity)

        vals, mask = self._flatten(vals, mask)
        return _finish_uterm(self, plan, vals, mask)

    def config_cap(self) -> int:
        return self.db.config.initial_result_capacity

    def conj(self, plans) -> Optional[CTable]:
        st = self.db._run_conjunctive(plans)
        if st is None or st.count == 0:
            return None
        vals, valid = self._flatten(st.vals, st.valid)
        return CTable(
            kind="O",
            onames=st.var_names,
            ocols=tuple(range(len(st.var_names))),
            ugroups=(),
            vals=vals,
            valid=valid,
            count=st.count,
        )

    # -- table combinators -------------------------------------------------

    @staticmethod
    def _gather_table(v, m):
        """Move one row-sharded table whole to every shard in ONE tiled
        all_gather (validity packed into the value block)."""
        packed = jnp.concatenate([v, m[:, None].astype(v.dtype)], axis=1)
        with jax.named_scope("mesh.gather_table"):
            full = jax.lax.all_gather(packed, SHARD_AXIS, tiled=True)
        return full[:, :-1], full[:, -1] != 0

    def _join_fn(self, pairs, extra, cap, gather_left=False, perm=None):
        """Traceable mesh join.  Default broadcast-RIGHT: gather the right
        table, join shard-locally against the left shards.  With
        gather_left, roles swap (the caller supplies swapped pairs/extras
        and the output-column permutation restoring the canonical
        layout)."""

        def build():
            def body(lv, lm, rv, rm):
                if gather_left:
                    av_full, am_full = self._gather_table(lv, lm)
                    vals, valid, total = _join_tables_impl(
                        rv, rm, av_full, am_full, pairs, extra, cap
                    )
                else:
                    rv_full, rm_full = self._gather_table(rv, rm)
                    vals, valid, total = _join_tables_impl(
                        lv, lm, rv_full, rm_full, pairs, extra, cap
                    )
                if perm is not None:
                    vals = vals[:, perm]
                return vals, valid, total[None]

            return self._smap(body, 4, 3)

        return self._cached(
            ("join", pairs, extra, cap, gather_left,
             None if perm is None else tuple(perm)),
            build,
        )

    def _swapped_join_fn(self, pairs, extra, cap, n_a, n_b):
        """Broadcast-LEFT variant for when the accumulator is the smaller
        table: gather `a`, keep `b` row-sharded as the local side, then
        permute the joined columns back to the canonical
        [a-cols..., b-extras...] layout join_ctables expects.  Every a
        column is either a join key (equal to b's paired column) or
        carried as a right-extra, so the permutation is total."""
        pairs_sw = tuple((bc, ac) for ac, bc in pairs)
        shared_a = {ac: bc for ac, bc in pairs}
        a_extra = tuple(c for c in range(n_a) if c not in shared_a)
        perm = []
        for c in range(n_a):
            if c in shared_a:
                perm.append(shared_a[c])          # == b's paired column
            else:
                perm.append(n_b + a_extra.index(c))
        perm.extend(extra)                         # b extras keep b positions
        return self._join_fn(
            pairs_sw, a_extra, cap, gather_left=True,
            perm=np.asarray(perm, dtype=np.int32),
        )

    def join_tables(self, av, am, bv, bm, pairs, extra, cap, counts=None):
        if counts is not None and counts[0] < counts[1]:
            # accumulator is smaller: broadcast IT and join on b's shards
            fn = self._swapped_join_fn(
                pairs, extra, cap, av.shape[1], bv.shape[1]
            )
            vals, valid, totals = fn(av, am, bv, bm)
        else:
            vals, valid, totals = self._join_fn(pairs, extra, cap)(av, am, bv, bm)
        return vals, valid, int(np.max(np.asarray(totals)))

    def dedup(self, vals, valid):
        def body(v, m):
            s, keep, cnt = _dedup_table_impl(v, m)
            return s, keep, cnt[None]

        fn = self._cached(("dedup",), lambda: self._smap(body, 2, 3))
        vals, keep, counts = fn(vals, valid)
        return vals, keep, int(np.asarray(counts).sum())

    def _anti_fn(self, pairs):
        """Traceable mesh anti-join: the tabu side arrives REPLICATED
        (difference/apply_forbidden call replicate() first), so removal is
        purely shard-local — zero collectives."""

        def build():
            def body(v, m, tabu_v, tabu_m):
                return _anti_join_impl(v, m, tabu_v, tabu_m, pairs)

            return self._smap(body, 4, 1, replicated_in=(2, 3))

        return self._cached(("anti", pairs), build)

    def anti_join(self, lv, lm, rv, rm, pairs):
        return self._anti_fn(pairs)(lv, lm, rv, rm)

    def concat(self, parts):
        def body(*arrs):
            n = len(arrs) // 2
            return (
                jnp.concatenate(arrs[:n], axis=0),
                jnp.concatenate(arrs[n:], axis=0),
            )

        flat = [v for v, _ in parts] + [m for _, m in parts]
        fn = self._cached(
            ("concat", len(flat)), lambda: self._smap(body, len(flat), 2)
        )
        return fn(*flat)

    def _replicate_fn(self):
        def build():
            def body(v, m):
                packed = jnp.concatenate(
                    [v, m[:, None].astype(v.dtype)], axis=1
                )
                with jax.named_scope("mesh.replicate"):
                    full = jax.lax.all_gather(packed, SHARD_AXIS, tiled=True)
                return full[:, :-1], full[:, -1] != 0

            # tiled all_gather IS replication; the static VMA checker
            # just cannot prove it — outputs are identical per shard
            return self._smap(
                body, 2, 2, replicated_out=True, check_vma=False
            )

        return self._cached(("replicate",), build)

    def replicate(self, t: CTable) -> CTable:
        cached = self._replicated.get(id(t))
        if cached is not None and cached[0] is t:
            return cached[1]
        vals, valid = self._replicate_fn()(t.vals, t.valid)
        out = CTable(t.kind, t.onames, t.ocols, t.ugroups, vals, valid, t.count)
        if len(self._replicated) > 256:
            self._replicated.clear()
        self._replicated[id(t)] = (t, out)
        return out
