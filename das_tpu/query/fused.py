"""Fused single-dispatch execution of compiled conjunctive queries.

The staged pipeline in query/compiler.py launches one jitted kernel per
stage (term probe, term-table build, dedup, each join, each anti-join) and
syncs an exact count to the host between stages — ~2T+J dispatches and
device->host round-trips per query.  That is the dominant cost at
query-serving latency scale (the reference's analogue is one Redis
round-trip per probe, redis_mongo_db.py:235-252).

Here the *entire* plan — every probe, term table, dedup, join and
anti-join — is traced into ONE jitted program.  Grounded constants
(probe keys, fixed target rows) enter as dynamic scalar/vector arguments,
so a single compiled executable serves every grounding of the same query
shape: the benchmark loop, the pattern miner's count queries and the
service edge all hit a warm cache after the first call.

Static-shape discipline: per-term and per-join capacities are static
(cache key includes them); the program reports exact per-stage counts so
the host can detect overflow and re-lower with doubled capacities
(powers of two => bounded recompiles).  One reference quirk cannot be
expressed shape-statically: an *empty* intermediate accumulator is
re-seeded by the next positive term (ast.py And.matched, mirroring
pattern_matcher.py:726-738).  The fused program detects that condition
(any intermediate join count of zero with positive terms remaining) and
the caller falls back to the staged path — answers stay exactly
reference-identical.
"""

from __future__ import annotations

import functools
import operator
import os
import threading
import time
import weakref
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from das_tpu import obs
from das_tpu.ops.counters import record_dispatch
from das_tpu.ops.join import (
    _anti_join_impl,
    _build_term_table_impl,
    _dedup_table_impl,
    _join_tables_impl,
    SLICE_SEARCH,
    index_search_method,
    lane_batched,
    whole_type_join,
)

# probe index routes (static per term).  Every compiler.TermPlan pins
# either a link type (type_id) or a composite type (ctype) — plan_query
# rejects anything else — so these three routes are exhaustive.
ROUTE_CTYPE = "ctype"        # template probe: composite-type key
ROUTE_TYPE_POS = "type_pos"  # (type_id<<32|target) at first grounded position
ROUTE_TYPE = "type"          # type-only probe


@dataclass(frozen=True)
class FusedTermSig:
    """Shape-static description of one term (no grounded values)."""

    arity: int
    route: str
    p0: int                        # probe position for *_pos routes, else -1
    extra_fixed: Tuple[int, ...]   # verified positions beyond the probe key
    var_cols: Tuple[int, ...]
    eq_pairs: Tuple[Tuple[int, int], ...]
    var_names: Tuple[str, ...]
    negated: bool


@dataclass(frozen=True)
class FusedPlanSig:
    terms: Tuple[FusedTermSig, ...]
    term_caps: Tuple[int, ...]
    join_caps: Tuple[int, ...]
    #: per join: -1 = sort-merge against the materialized right table;
    #: else the posting-index position for an INDEX JOIN — the right side
    #: stays implicit (whole-type term probed through key_type_pos[p]), so
    #: buffers scale with join output, never with the table (FlyBase-scale
    #: whole-table terms would otherwise force 33M-row buffers and
    #: minutes-long compiles)
    index_joins: Tuple[int, ...] = ()
    #: the cost-based planner (das_tpu/planner) ordered this plan and
    #: seeded its capacities.  Part of the signature for cache-key
    #: honesty: the planner A/B flips DasConfig.use_planner per arm, and
    #: when both arms pick the same order/caps they must still
    #: compile-and-count their own executables, not replay each other's
    planned: bool = False


def plan_index_joins(sigs: Tuple[FusedTermSig, ...]):
    """Static per-join index-join eligibility: right side must be an
    ordered whole-type probe (ROUTE_TYPE, no extra verification, no
    repeated variables), positive, and actually share a variable.
    Returns the per-join tuple and `right_terms`, term index -> join."""
    positives, _neg, _names, join_meta, _anti = fold_join_meta(sigs)
    index_joins = []
    right_terms = {}
    for n in range(max(0, len(positives) - 1)):
        i = positives[n + 1]
        t = sigs[i]
        pairs, _extra = join_meta[n]
        if (
            t.route == ROUTE_TYPE
            and not t.negated
            and not t.eq_pairs
            and not t.extra_fixed
            and pairs
        ):
            p = t.var_cols[pairs[0][1]]
            index_joins.append(p)
            right_terms[i] = n
        else:
            index_joins.append(-1)
    return tuple(index_joins), right_terms


@functools.lru_cache(maxsize=256)
def whole_type_join_steps(sigs: Tuple[FusedTermSig, ...], index_joins):
    """`(pair_steps, probe_steps, first)`: the joins n of a fold that
    run as the VERIFIED join (ops/join.py whole_type_join: an index
    join whose right side shares two or more variables with the left);
    those that run as the posting-index join of ONE shared variable,
    each as `(n, i)`, `i` the term whose arrays hold the index it
    probes; and the term index of the fold's first positive term: what
    _ExecJob.verdict_attrs reads a settled job's stats by.  Static per
    signature; asked under tracing only."""
    positives, _neg, _names, join_meta, _anti = fold_join_meta(sigs)
    shared = [len(join_meta[n][0]) for n in range(len(index_joins))]
    return tuple(
        n for n, p in enumerate(index_joins) if p >= 0 and shared[n] > 1
    ), tuple(
        (n, positives[n + 1]) for n, p in enumerate(index_joins)
        if p >= 0 and shared[n] == 1
    ), (positives[0] if positives else 0)


class FusedResult:
    """One conjunction's answer: the binding table's device references,
    its prefetched host copies, and the settle verdict's flags.

    An answer that rode in a group program (`_ExecJob.dispatch_group`)
    is handed `vals` / `valid` as zero-argument callables over
    `(group output, lane)`: the device slice is made when a reader first
    asks for it, not at settle.  The served path materializes from
    `host_vals` / `host_valid` and never asks."""

    __slots__ = (
        "var_names", "_vals", "_valid", "count", "reseed_needed",
        "overflow", "host_vals", "host_valid",
    )

    def __init__(
        self, var_names, vals, valid, count, reseed_needed, overflow,
        host_vals=None, host_valid=None,
    ):
        self.var_names: Tuple[str, ...] = var_names
        self._vals = vals            # [cap, k] int32 (device)
        self._valid = valid          # [cap] (device)
        self.count: int = count
        self.reseed_needed: bool = reseed_needed  # host falls back to staged
        self.overflow: bool = overflow  # a capacity too small; re-lower
        self.host_vals: Optional[np.ndarray] = host_vals    # prefetched —
        self.host_valid: Optional[np.ndarray] = host_valid  # free to read

    @property
    def vals(self) -> Optional[jax.Array]:
        if callable(self._vals):
            self._vals = self._vals()
        return self._vals

    @property
    def valid(self) -> Optional[jax.Array]:
        if callable(self._valid):
            self._valid = self._valid()
        return self._valid


class _GroupHooks:
    """The group hooks: a job type that has them rides with its
    same-signature batch-mates in ONE program (_dispatch_round) and
    takes its lane out of the one fetched block (settle_pending_iter).
    ONE body for the one-chip job (_ExecJob) and the mesh job
    (parallel/fused_sharded.py _ShardedExecJob): the lanes are stacked,
    the program cached, the jobs told and the enqueue counted here; a
    type brings `_build_group(plan_sig, key_axes, fval_axes)` -> (fn,
    names), its lane-batched program with the lanes as every output's
    LEADING axis, and `_enqueue_span(plan_sig, jobs)`, its own counter
    keys; its executor keeps a `_group_cache`."""

    __slots__ = ()

    @staticmethod
    def dispatch_group(jobs, plan_sig):
        """Queue ONE program for `jobs` (2..GROUP_LANES, all at `plan_sig`
        and one count_only): the lone program's body over their
        lane-stacked probe keys and fixed values, the bucket arrays
        passed once and unbatched.  Outputs carry a leading lanes axis; a
        lane past the last job repeats it and is never read.  Jobs of
        one fill (the first round of a batch whose builder kept lane
        columns) take their rows out of those, one fancy index per term
        slot; jobs built one by one, and jobs that met in a retry
        round, stack their own values."""
        lead = jobs[0]
        ex = lead.ex
        lanes = lead.lanes
        if lanes is not None and all(j.lanes is lanes for j in jobs):
            at = [j.row for j in jobs]
            at += at[-1:] * (GROUP_LANES - len(at))
            keys, key_axes, fvals, fval_axes = hoist_lanes(
                [col[at] for col in lanes[0]], [col[at] for col in lanes[1]]
            )
        else:
            keys, key_axes, fvals, fval_axes = stack_lanes(
                [j.keys for j in jobs], [j.fvals for j in jobs], GROUP_LANES
            )
        cache_key = (
            plan_sig, lead.count_only, GROUP_LANES, key_axes, fval_axes
        )
        entry = ex._group_cache.get(cache_key)
        if entry is None:
            entry = ex._group_cache[cache_key] = lead._build_group(
                plan_sig, key_axes, fval_axes
            )
        fn, names = entry
        for j in jobs:
            j.names = names
            j.rounds += 1
        with lead._enqueue_span(plan_sig, jobs), \
                obs.annotation("exec.dispatch"):
            return fn(lead.arrays, keys, fvals)

    def lane_out(self, host_out, dev_out, lane: int):
        """Lane `lane` of a group program's fetched block and of its
        device outputs, in the form settle() takes: numpy VIEWS of the
        host block, and for the device side callables that slice on
        first use (FusedResult) — no device op per lane here."""
        if self.count_only:
            return host_out[lane], None
        return (
            tuple(h[lane] for h in host_out),
            (
                partial(operator.getitem, dev_out[0], lane),
                partial(operator.getitem, dev_out[1], lane),
                None,
            ),
        )

    def _program_span(self, plan_sig, route: str, jobs):
        """ONE program is about to be enqueued, this job's own or the
        group program it leads for `jobs`: tick the planner's program
        count and return the `exec.dispatch` span to hold around the
        enqueue (ISSUE 12): host-monotonic timestamps only — the
        dispatch half stays sync-free (DL001/DL010); attrs carry the
        route and the planner's estimated rows so settle's actuals line
        up against them in one Perfetto lane.  Guarded: the disabled
        path packs no attribute dict."""
        if plan_sig.planned:
            from das_tpu.planner import PLANNER_COUNTS

            PLANNER_COUNTS["programs"] += 1
        if not obs.enabled():
            return obs.NOOP_SPAN
        return obs.span(
            "exec.dispatch", route=route, round=self.rounds,
            count_only=self.count_only,
            est_join_rows=(
                list(self.planned.est_join_rows)
                if self.planned is not None else None
            ),
            inflight=programs_in_flight(),
            **({"lanes": len(jobs)} if jobs else {}),
        )


class _ExecJob(_GroupHooks):
    """One execute()'s mutable state, split into dispatch / settle halves
    so execute_many can interleave many queries' dispatches before paying
    a single host transfer (each fetch is a host sync that waits for the
    device).
    Semantics are exactly execute()'s: same program cache, same capacity
    retry, same reseed verdict, same cap learning."""

    __slots__ = (
        "ex", "count_only", "same_order", "sigs", "arrays", "keys", "fvals",
        "term_caps", "join_caps", "index_joins", "names",
        "result", "planned", "rounds", "last_ranges", "last_join_rows",
        "_sig", "lanes", "row",
    )

    def __init__(
        self, ex, count_only, same_order, sigs, arrays, keys, fvals,
        term_caps, join_caps, index_joins, planned=None,
        sig=None, lanes=None, row=0,
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
        self.index_joins = index_joins
        self.names = None
        self.result: Optional[FusedResult] = None
        #: the PlannedProgram that ordered/seeded this job (None =
        #: legacy heuristics); settle feeds its estimates back to the
        #: planner counters so estimator error is observable
        self.planned = planned
        self.rounds = 0
        self.last_ranges = None      # final-round per-term exact ranges
        self.last_join_rows = None   # final-round per-join exact totals
        #: the signature at the capacities the job was built with: ONE
        #: object for every job of a batch that ended with equal
        #: capacities (FusedExecutor._fill), whose `term_caps` /
        #: `join_caps` tuples ARE this job's
        self._sig = sig
        #: the builder's lane columns, `(key columns, fixed-value
        #: columns)` per term slot with the batch's same-shape queries
        #: along axis 0, and this job's row in them: what
        #: dispatch_group stacks from (`keys` / `fvals` are this row)
        self.lanes = lanes
        self.row = row

    def plan_sig(self) -> FusedPlanSig:
        """The plan signature at the CURRENT capacities: the builder's
        shared object until a settle grows a capacity (settle assigns
        new tuples, so identity tells).  Shared by dispatch() and the
        whole-tree job (_TreeExecJob), whose tree signature nests one
        of these per site."""
        sig = self._sig
        if (
            sig is None
            or sig.term_caps is not self.term_caps
            or sig.join_caps is not self.join_caps
        ):
            sig = self._sig = FusedPlanSig(
                self.sigs, self.term_caps, self.join_caps, self.index_joins,
                self.planned is not None,
            )
        return sig

    def dispatch(self, plan_sig=None):
        """Queue the program at the current capacities (async, no sync);
        `plan_sig`: the signature there, where the caller has it."""
        if plan_sig is None:
            plan_sig = self.plan_sig()
        entry = self.ex._cache.get((plan_sig, self.count_only))
        if entry is None:
            entry = build_fused(plan_sig, self.count_only)
            self.ex._cache[(plan_sig, self.count_only)] = entry
        fn, self.names = entry
        self.rounds += 1
        with self._enqueue_span(plan_sig), obs.annotation("exec.dispatch"):
            return fn(self.arrays, self.keys, self.fvals)

    def _enqueue_span(self, plan_sig, jobs=()):
        """Tally ONE program about to be enqueued (this job's own, or
        the group program this job leads for `jobs`) and return the
        trace span to hold around the enqueue."""
        record_dispatch("fused")
        return self._program_span(plan_sig, "fused", jobs)

    def _build_group(self, plan_sig, key_axes, fval_axes):
        return build_fused_group(
            plan_sig, self.count_only, key_axes, fval_axes
        )

    def verdict_attrs(self) -> dict:
        """What a settled job adds to its `exec.verdict` span (tracing
        on; settle_pending_iter): for the verified joins of its fold
        the rows OFFERED (the left side's count: the join before it,
        or the first term's range) and the rows KEPT, summed, from the
        stats the round fetched anyway; the same two feed the counters
        `join.pair_left_rows` / `join.pair_rows`.  The posting-index
        joins of one variable feed counters alone: the rows offered to
        them (`join.index_probe_rows`) and, of those, the rows whose
        ranges came from the slice search (`join.index_slice_rows`):
        which joins those are is static per signature, and whether one
        took the slice search is ops/join.py's own rule on the two
        shapes the program was traced at."""
        if self.last_join_rows is None:
            return {}
        pair_steps, probe_steps, first = whole_type_join_steps(
            self.sigs, self.index_joins
        )
        rows = self.last_join_rows

        def offered(n):
            return rows[n - 1] if n else self.last_ranges[first]

        probed = sliced = 0
        for n, i in probe_steps:
            probed += offered(n)
            if index_search_method(
                self.join_caps[n - 1] if n else self.term_caps[first],
                self.arrays[i][0].shape[0],
            ) == SLICE_SEARCH:
                sliced += offered(n)
        if probed:
            obs.counter("join.index_probe_rows").inc(probed)
        if sliced:
            obs.counter("join.index_slice_rows").inc(sliced)
        if not pair_steps:
            return {}
        left = sum(offered(n) for n in pair_steps)
        kept = sum(rows[n] for n in pair_steps)
        obs.counter("join.pair_left_rows").inc(left)
        obs.counter("join.pair_rows").inc(kept)
        return {"pair_left_rows": left, "pair_rows": kept}

    def settle(self, host_out, dev_out) -> bool:
        """Consume one round's fetched stats.  True = finished (result is
        set; None result = capacity ceiling, caller falls back as before);
        False = capacities grew, dispatch again."""
        if self.count_only:
            vals = valid = host_vals = host_valid = None
            stats = np.asarray(host_out)
        else:
            # ONE host transfer carried result + stats: fetching stats
            # first and the binding table later would triple the per-query
            # latency floor.  Device refs are kept alongside for callers
            # that keep joining on device (tree executor).
            host_vals, host_valid, stats = host_out
            vals, valid, _ = dev_out
        count, reseed = int(stats[0]), bool(stats[1])
        pos_empty = bool(stats[2])
        ranges = stats[3 : 3 + len(self.sigs)]
        jcounts = stats[3 + len(self.sigs) :]
        new_tc = tuple(
            _pow2_at_least(int(r)) if int(r) > c else c
            for r, c in zip(ranges, self.term_caps)
        ) if ranges.size else self.term_caps
        new_jc = tuple(
            _pow2_at_least(int(t)) if int(t) > c else c
            for t, c in zip(jcounts, self.join_caps)
        ) if jcounts.size else self.join_caps
        if new_tc != self.term_caps or new_jc != self.join_caps:
            if (
                max(new_tc + new_jc, default=0)
                > self.ex.db.config.max_result_capacity
            ):
                return True  # staged path clamps and owns overflow policy
            self.term_caps, self.join_caps = new_tc, new_jc
            return False
        self.ex._remember_caps(self.sigs, self.term_caps, self.join_caps)
        self.last_ranges = [int(r) for r in ranges]
        self.last_join_rows = [int(t) for t in jcounts]
        if self.planned is not None:
            from das_tpu.planner import observe_settle

            observe_settle(self.planned, self.last_join_rows, self.rounds)
        n_positive = sum(1 for s in self.sigs if not s.negated)
        self.result = FusedResult(
            var_names=self.names,
            vals=vals,
            valid=valid,
            count=count,
            # an empty result under a REORDERED multi-term join could mask
            # the reference's reseed quirk in its original order — redo it
            # on the exact path; in reference order the in-program flag is
            # authoritative, and an empty POSITIVE TERM is always definitive
            reseed_needed=reseed
            or (
                count == 0
                and n_positive > 1
                and not pos_empty
                and not self.same_order
            ),
            overflow=False,
            host_vals=host_vals,
            host_valid=host_valid,
        )
        return True


class _PendingMany:
    """One dispatched-but-unsettled batch: cache-prefilled results, the
    enqueued round as `(members, device output)` per PROGRAM (members:
    the `(indices, job, cache key)` entries it carries; one for a job
    that ran alone, two or more for a group program's lanes), and the
    delta version the round was dispatched against (guards the
    settle-time cache insert against a racing commit)."""

    __slots__ = ("results", "programs", "version", "fetch_ms",
                 "__weakref__")

    def __init__(self, results, programs, version):
        self.results = results
        self.programs = programs
        self.version = version
        if obs.enabled():
            _live_pendings().add(self)
        # wall-ms of each settle round's host transfer, timed where it
        # happens (settle_pending_iter) — fetch_ms[0] IS the settle
        # round-trip the coalescer's adaptive window sizes from; an
        # all-hit or declined round leaves it empty, so host-side work
        # can never masquerade as the wire
        self.fetch_ms: List[float] = []


#: lanes of a served group program: a group of 2..32 jobs is padded to
#: it, a wider one cut into programs of it.  ONE rung: every rung is one
#: more compiled program per query shape and capacity step, and a rung
#: first met inside a serving window is a compile inside it.  A padded
#: lane is device time: a 32-lane program costs the device 4-7 ms
#: whether 2 or 32 of its lanes carry a query (PERF.md §5: 0.41 ms a
#: query at 11 lanes a program, 1.2 ms at 2.3), which one rung accepts
#: while the ONE host thread, not the device, sets the pace
GROUP_LANES = 32

#: the dispatched batches this thread holds (tracing on): what
#: `programs_in_flight` sums.  Held weakly: a round that a commit
#: overtook is dropped unfetched with its object (api/atomspace.py
#: settle_iter) and leaves with it, no site has to say so
_LIVE = threading.local()


def _live_pendings():
    try:
        return _LIVE.pendings
    except AttributeError:
        live = _LIVE.pendings = weakref.WeakSet()
        return live


def programs_in_flight() -> int:
    """Device programs this thread enqueued (_dispatch_round appends
    each to its batch's `_PendingMany.programs` as it goes) and has not
    fetched (settle_pending_iter empties the list): the device's queue
    as the ONE worker thread knows it, attr `inflight` of spans
    `exec.dispatch` and `exec.settle_fetch`.  Read under tracing only;
    a batch dispatched before tracing came on is not seen."""
    return sum(len(p.programs) for p in _live_pendings())


def _dispatch_round(entries, programs):
    """Enqueue one round of `(indices, job, cache key)` entries — the
    first of a dispatch_pending, or a settle round's capacity retries.
    Jobs whose type offers the group hooks (`dispatch_group`,
    `lane_out`) and that share `(plan_sig, count_only)` — same terms,
    same capacities, same route — ride ONE program; a job alone in its
    signature, and every job of a type without the hooks (a tree
    job), is enqueued by its own `dispatch()`, the program and cache
    entry it always had.  The jobs of a batch that the builder gave
    equal capacities hold ONE signature object, so a signature (three
    nested dataclasses) is hashed once per group here, not once per
    job: jobs are told apart by the identity of their signature first,
    and only distinct objects meet in the dict that compares them.
    Appends `(members, device output)`, one per program, to `programs`
    (the batch's `_PendingMany.programs`, empty: each is in flight
    from the moment it is enqueued, `programs_in_flight`) and returns
    it."""
    groups: Dict[Tuple, List] = {}
    by_object: Dict[Tuple, List] = {}
    for entry in entries:
        job = entry[1]
        if hasattr(job, "dispatch_group"):
            plan_sig = job.plan_sig()
            seen = (id(plan_sig), job.count_only)
            members = by_object.get(seen)
            if members is None:
                members = by_object[seen] = groups.setdefault(
                    (type(job), plan_sig, job.count_only), []
                )
            members.append(entry)
        else:
            programs.append(([entry], job.dispatch()))
    for (_cls, plan_sig, _co), members in groups.items():
        for at in range(0, len(members), GROUP_LANES):
            cut = members[at : at + GROUP_LANES]
            jobs = [job for _, job, _ in cut]
            programs.append((cut, (
                jobs[0].dispatch(plan_sig) if len(jobs) == 1
                else jobs[0].dispatch_group(jobs, plan_sig)
            )))
    if obs.enabled():
        obs.counter("exec.group_programs").inc(len(programs))
        obs.counter("exec.group_lanes").inc(len(entries))
    return programs


def dispatch_pending(results_cache, exec_job, plans_lists, count_only,
                     cache_only=False, build_jobs=None):
    """Phase-1 shared loop (pendant of settle_pending): resolve
    result-cache hits, dedup identical in-batch queries, build the
    remaining queries' jobs and ENQUEUE their first round — all
    asynchronous, one program per same-signature group
    (_dispatch_round).  `exec_job(plans, count_only)` returns a
    dispatchable job or None (a decline: missing bucket, capacity
    ceiling — per query); an executor that builds the jobs of a batch
    together passes `build_jobs(plans_lists, count_only)` -> one job
    or None per entry (FusedExecutor._build_jobs: once per query SHAPE,
    the rest filled per query) and the loop hands it every
    cache-missing, de-duplicated query of the batch at once.
    Shared by the single-device and sharded executors so the dedup
    invariant (duplicates alias ONE shared index list BEFORE the cache
    look-up, and never record their own cache miss) lives in exactly
    one place."""
    results: List = [None] * len(plans_lists)
    version = results_cache.version()
    todo = []
    by_key: Dict[Tuple, List[int]] = {}
    for i, plans in enumerate(plans_lists):
        key = results_cache.key(plans, count_only)
        dup = by_key.get(key)
        if dup is not None:
            # in-batch dedup BEFORE the cache lookup: concurrent
            # identical queries (the hot serving case) share ONE
            # program and must not each record a cache miss — the
            # hit-rate figure would under-report exactly this
            # workload.  The others alias the result at settle time.
            dup.append(i)
            continue
        hit = results_cache.get(key)
        if hit is not None:
            results[i] = hit
            continue
        if cache_only:
            # degraded-mode serving (ISSUE 13 breaker): answer from the
            # delta-versioned cache ONLY — a miss stays a dispatch-time
            # decline (results[i] None, no device program enqueued)
            continue
        idxs = by_key[key] = [i]
        todo.append((idxs, plans, key))
    if build_jobs is None or not todo:
        built = [exec_job(plans, count_only) for _, plans, _ in todo]
    else:
        built = build_jobs([plans for _, plans, _ in todo], count_only)
    jobs = [
        (idxs, job, key)
        for (idxs, _, key), job in zip(todo, built) if job is not None
    ]
    pending = _PendingMany(results, [], version)
    _dispatch_round(jobs, pending.programs)
    return pending


def settle_pending_iter(results_cache, pending, on_fetch=None):
    """Streaming settle of a _PendingMany (ISSUE 6 early-settle): yields
    `(index, result)` as each query's answer becomes FINAL — cache hits
    first (they were answered at dispatch with zero transfer), then, per
    retry round, every job whose verdict landed in that round's ONE host
    transfer: the outputs of every program of the round, a group
    program's as one block whose lane i goes to its job i.  A query
    that settled in round 1 streams to its caller while its
    batch-mates' capacity retries are still re-dispatching (grouped
    again by their new signatures) — its first rows arrive one RTT
    after its own dispatch, not after the whole group settles.
    Settle-time cache inserts stay guarded by the dispatch-time delta
    version (daslint DL007).  Indices the dispatch phase declined (no
    job, no cache hit) are never yielded — drain the iterator and read
    `pending.results` (None = declined), or use settle_pending.  Shared
    by the single-device and sharded executors — their jobs expose the
    same dispatch()/settle() halves, so the serving pipeline's second
    phase is ONE implementation.  With tracing on, every job's verdict
    is one `exec.verdict` span and `on_fetch` hears of every round's
    transfer (fetch_outputs; the mesh executor records its own span
    there)."""
    from das_tpu import fault

    retry = fault.fetch_retry()
    for i, hit in enumerate(pending.results):
        if hit is not None:
            yield i, hit
    programs = pending.programs
    while programs:
        traced = obs.enabled()
        fetched, fetch_s = fetch_outputs(
            tuple(out for _, out in programs),
            {"jobs": sum(len(m) for m, _ in programs),
             "programs": len(programs)} if traced else None,
            retry=retry, on_fetch=on_fetch,
        )
        pending.programs = []       # fetched: no longer in flight
        pending.fetch_ms.append(fetch_s * 1e3)
        nxt = []
        for (members, out), host in zip(programs, fetched):
            lanes = len(members)
            for lane, (idxs, job, key) in enumerate(members):
                # the verdict of ONE job, named (exec.verdict) and
                # closed before the yield: a span open across a yield
                # would take the consumer's spans for its children
                sp = (obs.span("exec.verdict", lanes=lanes) if traced
                      else obs.NOOP_SPAN)
                with sp:
                    if lanes == 1:
                        done = job.settle(host, out)
                    else:
                        done = job.settle(*job.lane_out(host, out, lane))
                    if done:
                        results_cache.put(key, job.result, pending.version)
                    if traced:
                        more = getattr(job, "verdict_attrs", None)
                        sp.set(done=done, **(more() if done and more else {}))
                if done:
                    for i in idxs:
                        pending.results[i] = job.result
                        yield i, job.result
                else:
                    nxt.append((idxs, job, key))
        programs = _dispatch_round(nxt, pending.programs) if nxt else []


def fetch_outputs(outs, attrs=None, retry=None, on_fetch=None):
    """The ONE host transfer of a settle round (settle_pending_iter) or
    of a tree round (run_tree_job): `jax.device_get(outs)`, timed where
    it happens.  Returns `(fetched, seconds)`.  Under `retry` (the
    shared RetryPolicy, das_tpu/fault, ISSUE 13) a transient runtime
    failure or an injected settle_fetch fault retries with
    deterministic backoff instead of failing the whole group, and EVERY
    attempt tallies FETCH_COUNTS: the fetches-per-query telemetry
    counts real transfers, not logical rounds (DL013's tally leg).

    With tracing on (`attrs` given: the span's own) the worker first
    WAITS for the outputs (`jax.block_until_ready`), then copies: span
    `exec.settle_fetch` carries `wait_ms`, the part of its duration
    in which the device had not finished, and `inflight`
    (`programs_in_flight`: a settle round's own programs among them).
    `on_fetch(t0, seconds, fetched, attrs)` hears of the same
    interval (`attrs`: that span's own, `jobs`, `programs`, `wait_ms`,
    `cpu_ms`, `inflight`).  With tracing
    off: the one `device_get`, which waits and copies in one call."""
    from das_tpu import fault

    traced = attrs is not None
    wait_s = 0.0

    def attempt():
        nonlocal wait_s
        FETCH_COUNTS["n"] += 1
        if retry is not None:
            fault.maybe_fail("settle_fetch")
        if traced:
            t_wait = time.perf_counter()
            jax.block_until_ready(outs)
            wait_s += time.perf_counter() - t_wait
        return jax.device_get(outs)

    cpu0 = time.thread_time() if traced else 0.0
    t0 = time.perf_counter()
    with obs.annotation("exec.settle_fetch"):
        fetched = attempt() if retry is None else retry.run(attempt)
    fetch_s = time.perf_counter() - t0
    if traced:
        # the wire, where it happens: one span per transfer, one
        # histogram sample (the RTT distribution the adaptive window
        # must hide), one fetch counter tick
        attrs.update(wait_ms=wait_s * 1e3,
                     cpu_ms=(time.thread_time() - cpu0) * 1e3,
                     inflight=programs_in_flight())
        obs.counter("exec.fetches").inc()
        obs.histogram("exec.settle_fetch_ms").observe(fetch_s * 1e3)
        obs.REC.record("exec.settle_fetch", "X", t0, fetch_s, 0, attrs)
        if on_fetch is not None:
            on_fetch(t0, fetch_s, fetched, attrs)
    return fetched, fetch_s


def settle_pending(results_cache, pending) -> List:
    """Drive a _PendingMany to completion (the non-streaming form of
    settle_pending_iter): one host transfer per retry round, per-job
    settle verdicts, version-guarded cache inserts.  Returns the full
    results list (None = the dispatch phase declined that entry)."""
    for _ in settle_pending_iter(results_cache, pending):
        pass
    return pending.results


#: largest per-term candidate window the exact (reference-order) variant
#: will materialize; beyond this the staged path answers instead
EXACT_TERM_CAP_LIMIT = 1 << 20

#: host fetches of device results — each one is a host sync that waits
#: for the device; fetches per query decompose host-visible latency
FETCH_COUNTS = {"n": 0}

#: the CLOSED set of scopes allowed to call jax.device_get (daslint
#: DL013, the COLLECTIVE_SITES idiom applied to host transfers): calls
#: attribute to their outermost enclosing function, qualified by module
#: stem (package name for __init__ modules).  Every entry must both
#: contain a device_get AND tally FETCH_COUNTS (starcount tallies its
#: own FETCHES, read the same way) — "one transfer per
#: settle round" is only a checkable contract if the transfer sites are
#: enumerable and the telemetry cannot undercount.  Adding a fetch site
#: means adding it here, under review, with its RTT story.
FETCH_SITES = (
    #: the serving pipeline's ONE transfer per settle round (§10:
    #: settle_pending_iter) and the whole-tree retry loop's one per
    #: tree round (ISSUE 10: run_tree_job), through one helper
    "fused.fetch_outputs",
    #: single-query execute()'s settle fetch
    "fused.FusedExecutor.execute",
    #: reference-order exact variant's settle fetch
    "fused.FusedExecutor.execute_exact",
    #: planner explain(execute=True) driving a real job to settle
    "planner._explain_plans",
    #: star-count device fold: one fetch per GROUP of lanes
    "starcount._device_count_group",
    #: materialization fallbacks when no prefetched host copy exists —
    #: one transfer per table/batch, never on the cache-hit path
    "compiler.materialize",
    "tree.materialize_tables",
    "tree._tree_entry",
    #: sharded execute()'s settle fetch (mesh twin of execute)
    "fused_sharded.ShardedFusedExecutor.execute",
)


def _pow2_at_least(n: int, lo: int = 16) -> int:
    c = lo
    while c < n:
        c *= 2
    return c


def term_sig(plan) -> FusedTermSig:
    """The shape-static signature of one compiler.TermPlan: its probe
    route and everything else the traced program reads of it, no
    grounded value."""
    if plan.ctype is not None:
        route, p0, extra = ROUTE_CTYPE, -1, ()
    elif plan.type_id is not None and plan.fixed:
        p0 = plan.fixed[0][0]
        route, extra = ROUTE_TYPE_POS, tuple(p for p, _ in plan.fixed[1:])
    else:
        # plan_query guarantees type_id or ctype is set (TermPlan
        # invariant) — an untyped plan cannot reach the fused path
        assert plan.type_id is not None, "TermPlan without type or ctype"
        route, p0, extra = ROUTE_TYPE, -1, ()
    return FusedTermSig(
        arity=plan.arity,
        route=route,
        p0=p0,
        extra_fixed=extra,
        var_cols=plan.var_cols,
        eq_pairs=plan.eq_pairs,
        var_names=plan.var_names,
        negated=plan.negated,
    )


def route_arrays(bucket, sig: FusedTermSig):
    """(sorted keys, permutation, targets, type ids) of the index a
    term's route probes, off a DeviceBucket."""
    if sig.route == ROUTE_CTYPE:
        return (bucket.key_ctype, bucket.order_by_ctype, bucket.targets,
                bucket.type_id)
    if sig.route == ROUTE_TYPE_POS:
        return (bucket.key_type_pos[sig.p0], bucket.order_by_type_pos[sig.p0],
                bucket.targets, bucket.type_id)
    return (bucket.key_type, bucket.order_by_type, bucket.targets,
            bucket.type_id)


def _probe(sig: FusedTermSig, arrays, key, fixed_vals, cap: int):
    """Trace one term probe + verification + term-table build.

    arrays = (sorted_keys, perm, targets, type_id) device arrays for the
    term's bucket/route; key is a traced scalar; fixed_vals a traced
    int32[len(extra_fixed)] vector.
    """
    sorted_keys, perm, targets, type_id = arrays
    # named scopes: the stages an operator reads in XProf (trace-time
    # only — they label the ops, they add none)
    with jax.named_scope("probe"):
        lo = jnp.searchsorted(sorted_keys, key, side="left")
        hi = jnp.searchsorted(sorted_keys, key, side="right")
        range_count = (hi - lo).astype(jnp.int32)
        offs = jnp.arange(cap, dtype=jnp.int32)
        valid = offs < range_count
        idx = jnp.clip(
            lo.astype(jnp.int32) + offs, 0, sorted_keys.shape[0] - 1
        )
    with jax.named_scope("gather"):
        local = jnp.where(valid, perm[idx], jnp.int32(2**31 - 1))
        safe = jnp.clip(local, 0, targets.shape[0] - 1)
        mask = valid
        for i, pos in enumerate(sig.extra_fixed):
            mask = mask & (targets[safe, pos] == fixed_vals[i])
        vals, mask = _build_term_table_impl(
            targets, local, mask, sig.var_cols, sig.eq_pairs
        )
    return vals, mask, range_count


def fold_join_meta(terms: Tuple[FusedTermSig, ...]):
    """Static join metadata for a positive-term fold: output name order,
    per-join (pairs, extra) column maps, and which negated terms filter
    (NO_COVERING rule: a tabu with variables outside the output never
    excludes).  Shared by the single-device and sharded program builders —
    this derivation is load-bearing for answer correctness."""
    positives = [i for i, t in enumerate(terms) if not t.negated]
    negatives = [i for i, t in enumerate(terms) if t.negated]
    names: Tuple[str, ...] = ()
    join_meta = []
    for n, i in enumerate(positives):
        t = terms[i]
        if n == 0:
            names = t.var_names
            continue
        pairs = tuple(
            (names.index(v), t.var_names.index(v))
            for v in names
            if v in t.var_names
        )
        extra = tuple(j for j, v in enumerate(t.var_names) if v not in names)
        join_meta.append((pairs, extra))
        names = names + tuple(v for v in t.var_names if v not in names)
    anti_meta = []
    for i in negatives:
        t = terms[i]
        if set(t.var_names) <= set(names):
            anti_meta.append(
                (i, tuple((names.index(v), t.var_names.index(v)) for v in t.var_names))
            )
    return positives, negatives, names, join_meta, anti_meta


def remember_caps(caps_dict, caches, sigs, new_caps, caps_of) -> None:
    """Record learned capacities for a signature and evict superseded
    smaller-capacity executables from the given caches (whose keys all lead
    with the plan signature), so long-running services don't accumulate one
    compiled program per retry tier.  `caps_of` extracts the signature's
    capacity tuple (shape differs between executors)."""
    if caps_dict.get(sigs) == new_caps:
        return
    caps_dict[sigs] = new_caps
    for cache in caches:
        for key in list(cache):
            ps = key[0]
            if ps.terms == sigs and caps_of(ps) != new_caps:
                del cache[key]


class CapStore:
    """Cross-process persistence of learned capacities, keyed by a stable
    hash of the plan signature.  Every capacity-retry tier compiles a new
    XLA executable (minutes at FlyBase scale), so starting a fresh process
    at the last learned tier — alongside the persistent XLA cache — turns
    repeat benchmarks and service restarts from re-learning into cache
    hits.  Capacities are perf hints only: a stale entry merely costs a
    retry, never correctness."""

    def __init__(self, tag: str):
        import os

        import das_tpu

        root = das_tpu.cache_root()
        self.path = None if root is None else os.path.join(
            root, f"caps_{tag}.json"
        )
        self._data = {}
        if self.path and os.path.exists(self.path):
            try:
                import json

                with open(self.path) as fh:
                    self._data = json.load(fh)
            except Exception:
                self._data = {}

    @staticmethod
    def _key(sigs, salt: str) -> str:
        import hashlib

        return hashlib.md5((repr(sigs) + "|" + salt).encode()).hexdigest()

    def load(self, sigs, salt: str = ""):
        caps = self._data.get(self._key(sigs, salt))
        return None if caps is None else tuple(tuple(c) for c in caps)

    def save(self, sigs, caps, salt: str = "") -> None:
        key = self._key(sigs, salt)
        as_lists = [list(c) for c in caps]
        if self._data.get(key) == as_lists:
            return
        self._data[key] = as_lists
        if self.path is None:
            return
        try:
            import json
            import os

            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = self.path + f".tmp{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(self._data, fh)
            os.replace(tmp, self.path)
        except Exception:
            pass  # persistence is best-effort


def _trace_conj(sig: FusedPlanSig, bucket_arrays, keys, fixed_vals):
    """Trace ONE conjunction — every probe, term table, join and
    anti-join — into the caller's program.  Returns
    (acc_vals, acc_valid, stats_list) where stats_list =
    [count, reseed, any_pos_empty, *term_ranges, *join_counts] as traced
    scalars.  This is build_fused's whole body, extracted so the
    whole-tree program (build_fused_tree, ISSUE 10) can trace several
    conjunction sites side by side in one executable — probes and term
    tables shared by XLA CSE where branches coincide, and all sites
    settling in one transfer."""
    positives, _negatives, names, join_meta, anti_meta = fold_join_meta(sig.terms)
    index_joins = sig.index_joins or tuple(
        [-1] * max(0, len(positives) - 1)
    )
    index_right = {
        positives[1 + n]: n for n, p in enumerate(index_joins) if p >= 0
    }

    tables = {}
    term_ranges = []
    pos_count = {}
    for i, t in enumerate(sig.terms):
        if i in index_right:
            # index-join right side: never materialized.  Its arrays
            # are the (type<<32|target) positional index; the term's
            # candidate count (for the empty-positive-term rule) is the
            # type's key range, and it exerts no capacity pressure.
            keys_sorted = bucket_arrays[i][0]
            tid = jnp.asarray(keys[i], jnp.int64)
            with jax.named_scope("probe"):
                lo = jnp.searchsorted(keys_sorted, tid << 32, side="left")
                hi = jnp.searchsorted(
                    keys_sorted, (tid + 1) << 32, side="left"
                )
            pos_count[i] = (hi - lo).astype(jnp.int32)
            tables[i] = None
            term_ranges.append(jnp.int32(0))
            continue
        vals, mask, rng = _probe(
            t, bucket_arrays[i], keys[i], fixed_vals[i], sig.term_caps[i]
        )
        # no per-term dedup: every route pins the link type (type_id or
        # ctype), so the full target vector is a function of (fixed
        # values, var tuple) and distinct candidate links always yield
        # distinct variable tuples
        tables[i] = (vals, mask)
        pos_count[i] = mask.sum(dtype=jnp.int32)
        term_ranges.append(rng)

    # a positive term with zero verified candidates fails the whole And
    # in the reference (term.matched False -> return False, ast.py
    # And.matched) — a DEFINITIVE empty answer, distinct from the
    # reseed quirk, which fires only when a *join* empties a non-empty
    # accumulator with positive terms remaining
    any_pos_empty = jnp.bool_(False)
    for i in positives:
        any_pos_empty = any_pos_empty | (pos_count[i] == 0)

    acc_vals, acc_valid = tables[positives[0]]
    join_counts = []
    # the reseed quirk needs a *next* positive term; a single-term plan
    # with zero matches is just an empty answer — no fallback needed
    if len(positives) > 1:
        reseed = acc_valid.sum(dtype=jnp.int32) == 0
    else:
        reseed = jnp.bool_(False)
    for n, i in enumerate(positives[1:]):
        pairs, extra = join_meta[n]
        jc = sig.join_caps[n]
        # no post-join dedup: a join of duplicate-free tables is
        # duplicate-free (output row <-> (left row, right row) is a
        # bijection: shared columns agree, extras come from exactly one
        # side, and each side's rows are unique)
        with jax.named_scope("join"):
            if index_joins[n] >= 0:
                acc_vals, acc_valid, total = whole_type_join(
                    acc_vals, acc_valid, bucket_arrays[i], keys[i],
                    pairs, sig.terms[i].var_cols, extra, jc,
                )
            else:
                rv, rm = tables[i]
                acc_vals, acc_valid, total = _join_tables_impl(
                    acc_vals, acc_valid, rv, rm, pairs, extra, jc
                )
        join_counts.append(total)
        if n < len(positives) - 2:
            reseed = reseed | (acc_valid.sum(dtype=jnp.int32) == 0)

    for i, pairs in anti_meta:
        rv, rm = tables[i]
        with jax.named_scope("anti_join"):
            acc_valid = _anti_join_impl(acc_vals, acc_valid, rv, rm, pairs)

    count = acc_valid.sum(dtype=jnp.int32)
    reseed = reseed & ~any_pos_empty
    stats_list = [
        count,
        reseed.astype(jnp.int32),
        any_pos_empty.astype(jnp.int32),
        *term_ranges,
        *join_counts,
    ]
    return acc_vals, acc_valid, stats_list


def _fused_body(sig: FusedPlanSig, count_only: bool):
    """The traced function of one plan signature and its variable
    names: what build_fused jits for one query and build_fused_group
    for the lanes of a group."""
    _positives, _negatives, names, _jm, _am = fold_join_meta(sig.terms)

    def fn(bucket_arrays, keys, fixed_vals):
        acc_vals, acc_valid, stats_list = _trace_conj(
            sig, bucket_arrays, keys, fixed_vals
        )
        stats = jnp.stack(stats_list)
        if count_only:
            # XLA dead-code-eliminates every value gather feeding only the
            # discarded binding table — counts need keys and masks alone
            return stats
        return acc_vals, acc_valid, stats

    return fn, names


def build_fused(sig: FusedPlanSig, count_only: bool = False):
    """Lower one plan signature to a single jitted callable.

    Call convention: fn(bucket_arrays, keys, fixed_vals) where
      bucket_arrays — tuple of per-term (sorted_keys, perm, targets, type_id)
      keys          — tuple of per-term traced probe keys
      fixed_vals    — tuple of per-term int32 vectors (extra grounded rows)
    Returns (vals, valid, stats); stats = [count, reseed, any_pos_empty,
    *term_ranges, *join_counts] — ONE small vector so the host fetches
    everything it needs to decide overflow/reseed in a single
    device->host transfer (one sync per round, not one per stage).
    The conjunction body itself lives in _trace_conj (shared with the
    whole-tree program builder).
    """
    fn, names = _fused_body(sig, count_only)
    # program ledger (ISSUE 14): identity when DAS_TPU_PROFLOG is off;
    # on, the first call per shape AOT-compiles and records wall time +
    # cost/memory analysis under this signature's digest
    return obs.proflog.instrument(
        "fused", obs.proflog.sig_digest(sig, count_only),
        jax.jit(obs.named_program("das_fused", fn, count_only)),
    ), names


def stack_or_const(rows):
    """One lane-stacked input slot from per-member values: (stacked,
    axis 0) when members differ, (shared value, axis None) when
    identical — None axes let XLA compute constant terms (e.g. an
    ungrounded probe shared by the whole batch) ONCE instead of per
    member."""
    return hoist_lane(np.stack(rows))


def hoist_lane(col):
    """stack_or_const's rule on a slot already stacked (lanes along
    axis 0)."""
    return (col[0], None) if (col == col[0]).all() else (col, 0)


def hoist_lanes(key_cols, fval_cols):
    """The inputs of ONE lane-batched program from its lane columns (per
    term slot an array with the lanes along axis 0): a slot whose lanes
    differ stays stacked (axis 0), one whose lanes are all equal is
    hoisted to its one value (axis None: XLA computes a constant term,
    e.g. the ungrounded probe a whole group shares, ONCE instead of per
    lane).  Returns (keys, key_axes, fvals, fval_axes)."""
    keys, key_axes = zip(*map(hoist_lane, key_cols))
    fvals, fval_axes = zip(*map(hoist_lane, fval_cols))
    if all(a is None for a in key_axes + fval_axes):
        # every lane is the same query (one lane, or duplicates): the
        # first slot stays stacked, so the program has its lanes axis
        keys = (key_cols[0],) + keys[1:]
        key_axes = (0,) + key_axes[1:]
    return keys, key_axes, fvals, fval_axes


def stack_lanes(key_rows, fval_rows, lanes: int):
    """hoist_lanes for callers that hold per-MEMBER values (one tuple of
    per-term probe keys and one of fixed values each: a retry round's
    regrouped jobs, the count batches): padded to `lanes` by repeating
    the last member (jit re-traces per stacked shape, so the lane count
    comes from a ladder; the padded lanes' output rows are dropped),
    then one column per term slot."""
    pad = lanes - len(key_rows)
    if pad:
        key_rows = list(key_rows) + [key_rows[-1]] * pad
        fval_rows = list(fval_rows) + [fval_rows[-1]] * pad
    n_terms = len(key_rows[0])
    return hoist_lanes(
        [np.stack([kr[t] for kr in key_rows]) for t in range(n_terms)],
        [np.stack([fr[t] for fr in fval_rows]) for t in range(n_terms)],
    )


def lanes_program(fn, key_axes, fval_axes):
    """`fn(bucket_arrays, keys, fixed_vals)` over stack_lanes' inputs:
    every output gains a leading lanes axis.  The bucket arrays are an
    ARGUMENT, broadcast with in_axes=None, never a closure: a
    closed-over array is a baked constant — the whole store would be
    serialized into every compiled program (multi-GB at reference
    scale), and a cached entry would keep reading PRE-COMMIT arrays
    after an incremental delta merge replaced them.  Unbatched, the
    table-sized work of a program (key splits, layout copies) is done
    once per GROUP.  The body is traced under lane_batched, so the
    joins pick the lowerings that compile fast with a lanes axis."""

    def lane(bucket_arrays, keys, fixed_vals):
        with lane_batched():
            return fn(bucket_arrays, keys, fixed_vals)

    return jax.vmap(lane, in_axes=(None, tuple(key_axes), tuple(fval_axes)))


def build_fused_group(sig: FusedPlanSig, count_only, key_axes, fval_axes):
    """build_fused's program for a GROUP of same-signature jobs
    (_ExecJob.dispatch_group): the same _trace_conj body under
    lanes_program.  Returns (fn, var names); fn(bucket_arrays, keys,
    fixed_vals) -> vals [lanes, cap, k], valid [lanes, cap], stats
    [lanes, n] (stats alone when count_only)."""
    body, names = _fused_body(sig, count_only)
    return obs.proflog.instrument(
        "fused_group",
        obs.proflog.sig_digest(sig, count_only, key_axes, fval_axes),
        jax.jit(obs.named_program(
            "das_fused_group", lanes_program(body, key_axes, fval_axes),
            count_only,
        )),
    ), names


def conj_stats_len(n_terms: int, n_steps: int) -> int:
    """Length of one conjunction's stats block inside a stacked
    whole-tree stats vector: [count, reseed, any_pos_empty,
    *term_ranges, *join_counts] — the settle halves parse by this (the
    sharded blocks append their exchange occupancies on top)."""
    return 3 + n_terms + n_steps


def canonical_tree_names(terms) -> Tuple[str, ...]:
    """Canonical output layout of a whole-tree program: the site's bound
    variables in SORTED name order — the same canonical column order the
    tree executor's union path projects to (query/tree.py
    _canonicalize), so in-program dedup/anti row equality matches the
    host assignment-set identity exactly."""
    _pos, _neg, names, _jm, _am = fold_join_meta(terms)
    return tuple(sorted(names))


@dataclass(frozen=True)
class FusedTreeSig:
    """Shape-static description of ONE whole-tree fused program (ISSUE
    10): every positive Or branch as a full per-site plan signature,
    plus the joint negative conjunction for the de-Morgan difference
    branch.  Nested FusedPlanSigs carry the per-site capacities and
    planner provenance, so the tree signature inherits their cache-key
    honesty (daslint DL002)."""

    sites: Tuple[FusedPlanSig, ...]
    neg: Optional[FusedPlanSig] = None


def build_fused_tree(sig: FusedTreeSig, count_only: bool = False):
    """Lower a whole Or/negation plan tree to ONE jitted program: every
    conjunction site traces via _trace_conj, the positive branches
    union in-program (projection to the canonical sorted-name column
    order, concat, exact lexsort dedup — the tree executor's
    union_ctables machinery, fused), and the optional negative branch
    anti-joins the union on ALL columns (the de-Morgan difference,
    query/tree.py difference()).  An N-branch Or settles in one
    dispatch and one transfer where the tree executor pays >= N.

    Call convention: fn(*site_inputs) where site_inputs has one
    (bucket_arrays, keys, fixed_vals) triple per positive site, then
    one for the negative site when sig.neg is set.  Stats layout:
      [final_count, *site_0_block, ..., *neg_block]
    with each block = [count, reseed, any_pos_empty, *term_ranges,
    *join_counts] (conj_stats_len per site) — the host parses per-site
    verdicts for capacity retry and the reseed contract out of ONE
    transfer."""
    out_names = canonical_tree_names(sig.sites[0].terms)
    K = len(out_names)
    perms = []
    for ssig in sig.sites + ((sig.neg,) if sig.neg is not None else ()):
        _p, _n, names, _jm, _am = fold_join_meta(ssig.terms)
        assert tuple(sorted(names)) == out_names, (
            "tree fusion requires one shared variable universe"
        )
        perms.append(tuple(names.index(v) for v in out_names))

    def fn(*site_inputs):
        blocks = []
        parts = []
        for i, ssig in enumerate(sig.sites):
            ba, ks, fv = site_inputs[i]
            v, m, sl = _trace_conj(ssig, ba, ks, fv)
            blocks.append(sl)
            parts.append((v[:, jnp.asarray(perms[i], dtype=jnp.int32)], m))
        union_vals = jnp.concatenate([v for v, _ in parts], axis=0)
        union_valid = jnp.concatenate([m for _, m in parts], axis=0)
        if sig.neg is not None:
            ba, ks, fv = site_inputs[len(sig.sites)]
            nv, nm, nsl = _trace_conj(sig.neg, ba, ks, fv)
            blocks.append(nsl)
            nv = nv[:, jnp.asarray(perms[-1], dtype=jnp.int32)]
            # de-Morgan difference: joint negative answers minus the
            # positive union — plain full-row equality removal against
            # the RAW concat (the union is only a membership set here;
            # duplicates are harmless, so no dedup sort is paid)
            all_pairs = tuple((c, c) for c in range(K))
            nm = _anti_join_impl(nv, nm, union_vals, union_valid, all_pairs)
            out_vals, out_valid = nv, nm
            count = nm.sum(dtype=jnp.int32)
        else:
            # exact union dedup (ops/join.py): all sites are ordered
            # tables over one variable set, so positional row equality
            # over the canonical columns IS the reference assignment
            # identity
            with jax.named_scope("dedup"):
                out_vals, out_valid, count = _dedup_table_impl(
                    union_vals, union_valid
                )
        stats = jnp.stack(
            [count] + [s for block in blocks for s in block]
        )
        if count_only:
            return stats
        return out_vals, out_valid, stats

    return obs.proflog.instrument(
        "fused_tree", obs.proflog.sig_digest(sig, count_only),
        jax.jit(obs.named_program("das_fused_tree", fn, count_only)),
    ), out_names


class _TreeExecJob:
    """One whole-tree execution's mutable state (ISSUE 10), split into
    the dispatch/settle halves like _ExecJob.  Wraps one count_only
    per-site _ExecJob per conjunction site: the site jobs own ordering,
    planner seeds, capacity math and the reseed verdict (their settle
    halves parse this job's per-site stats blocks), while THIS job owns
    the single fused tree program — one dispatch, one transfer, where
    the tree executor pays one per site.

    Decline semantics: a site hitting the capacity ceiling, or any
    site's reseed verdict firing, abandons the fused tree (result None,
    needs_fallback) and the tree executor re-answers — bit-identical,
    exactly like the conjunction path's staged fallback.

    The sharded twin (_ShardedTreeExecJob, parallel/fused_sharded.py)
    subclasses this and overrides ONLY the executor-specific hooks —
    tree_sig / _build / _blk_len / _make_result plus the literal
    counter keys (daslint DL004 pins counting sites as declared-key
    literals, so the dispatch/settle wrappers stay per-class) — the
    settle_pending_iter sharing idiom applied to tree jobs."""

    __slots__ = (
        "ex", "site_jobs", "neg_job", "names", "rounds", "result",
        "needs_fallback", "matched_any", "_done",
    )

    def __init__(self, ex, site_jobs, neg_job):
        self.ex = ex
        self.site_jobs = site_jobs
        self.neg_job = neg_job
        self.names = None
        self.rounds = 0
        self.result = None
        #: True once settle decided the tree executor must re-answer
        #: (per-site reseed verdict or capacity ceiling)
        self.needs_fallback = False
        #: the reference Or.matched verdict source: any POSITIVE site
        #: matched (site count > 0) — independent of the difference
        #: branch's final count
        self.matched_any = False
        self._done = set()

    def _all_jobs(self):
        return self.site_jobs + (
            [self.neg_job] if self.neg_job is not None else []
        )

    # -- executor-specific hooks (the sharded twin overrides these) ------

    def tree_sig(self) -> FusedTreeSig:
        return FusedTreeSig(
            tuple(j.plan_sig() for j in self.site_jobs),
            self.neg_job.plan_sig() if self.neg_job is not None else None,
        )

    def _build(self, tree_sig):
        return build_fused_tree(tree_sig)

    def _blk_len(self, j) -> int:
        return conj_stats_len(len(j.sigs), len(j.join_caps))

    def _make_result(self, vals, valid, count, host_vals, host_valid):
        return FusedResult(
            var_names=self.names,
            vals=vals,
            valid=valid,
            count=count,
            reseed_needed=False,
            overflow=False,
            host_vals=host_vals,
            host_valid=host_valid,
        )

    def dispatch(self):
        """Queue the whole-tree program at every site's current
        capacities (async, no sync)."""
        record_dispatch("fused_tree")
        sp = obs.NOOP_SPAN
        if obs.enabled():
            sp = obs.span("exec.dispatch", route="fused_tree",
                          sites=len(self.site_jobs))
        with sp, obs.annotation("exec.dispatch"):
            return self._dispatch_common()

    def settle(self, host_out, dev_out) -> bool:
        done = self._settle_common(host_out, dev_out)
        if done and self.result is not None:
            from das_tpu.query.compiler import ROUTE_COUNTS

            ROUTE_COUNTS["fused_tree"] += 1
        return done

    # -- shared machinery ------------------------------------------------

    def _dispatch_common(self):
        tree_sig = self.tree_sig()
        cache = self.ex._tree_progs
        entry = cache.get(tree_sig)
        if entry is None:
            entry = self._build(tree_sig)
            if len(cache) > 64:
                # superseded-capacity entries have no per-site eviction
                # hook (remember_caps keys on conjunction sigs): bound
                # the program cache instead of leaking one executable
                # per retry tier across long-running services
                cache.clear()
            cache[tree_sig] = entry
        fn, self.names = entry
        self.rounds += 1
        for j in self._all_jobs():
            j.rounds += 1
        if any(j.planned is not None for j in self._all_jobs()):
            from das_tpu.planner import PLANNER_COUNTS

            # ONE program carried every planned site this round — the
            # "programs" counter tracks dispatched device programs, and
            # fewer of them is exactly the fused tree's point
            PLANNER_COUNTS["programs"] += 1
        return fn(*(
            (j.arrays, j.keys, j.fvals) for j in self._all_jobs()
        ))

    def _settle_common(self, host_out, dev_out) -> bool:
        """Consume one round's fetched stats: slice the per-site blocks
        out of the ONE stats vector and run each site job's own settle
        verdict on its block.  True = finished (result set, or decline:
        result None + needs_fallback); False = some site's capacities
        grew — dispatch the whole tree again (still one program)."""
        host_vals, host_valid, stats = host_out
        vals, valid, _ = dev_out
        stats = np.asarray(stats)
        off = 1
        grew = False
        for idx, j in enumerate(self._all_jobs()):
            blk_len = self._blk_len(j)
            blk = stats[off : off + blk_len]
            off += blk_len
            if idx in self._done:
                continue  # its caps fit earlier; the block is stable
            if j.settle(blk, None):
                if j.result is None:
                    # capacity ceiling: the tree executor owns the
                    # overflow policy (exactly the conjunction decline)
                    self.result = None
                    self.needs_fallback = True
                    return True
                self._done.add(idx)
            else:
                grew = True
        if grew:
            return False
        if any(j.result.reseed_needed for j in self._all_jobs()):
            # a site's reseed quirk fired: its in-program answer is not
            # trustworthy under reordering — the tree executor re-runs
            # the whole tree (its conj leaves resolve reseeds on the
            # exact variant), answers stay reference-identical
            self.result = None
            self.needs_fallback = True
            return True
        self.matched_any = any(j.result.count > 0 for j in self.site_jobs)
        self.result = self._make_result(
            vals, valid, int(stats[0]), host_vals, host_valid
        )
        return True


def run_tree_job(job):
    """Drive a tree job's dispatch/settle retry loop to completion (the
    execute() idiom) — ONE implementation for both executors."""
    while True:
        out = job.dispatch()
        fetched, _ = fetch_outputs(
            out, {"tree": True} if obs.enabled() else None
        )
        if job.settle(fetched, out):
            return job


def prepare_tree_job(ex, pos_sites, neg_plans, job_cls):
    """Build one whole-tree job (ISSUE 10) on executor `ex`: one
    count_only site job per positive Or branch (each rides the full
    _exec_job machinery — planner ordering and seeds, learned caps,
    index-join routing), plus one for the joint negative conjunction.
    None when ANY site declines (missing bucket, capacity ceiling) —
    the tree executor answers, bit-identical.  Shared by both
    executors — `job_cls` is their only difference."""
    site_jobs = []
    for site in pos_sites:
        j = ex._exec_job(list(site), True)
        if j is None:
            return None
        site_jobs.append(j)
    neg_job = None
    if neg_plans:
        neg_job = ex._exec_job(list(neg_plans), True)
        if neg_job is None:
            return None
    return job_cls(ex, site_jobs, neg_job)


@dataclass(frozen=True)
class FusedExactSig:
    """Shape-static description of a REFERENCE-ORDER plan for the exact
    (in-program reseed) variant.  chain_caps holds one capacity per suffix
    chain join (s, i), s < i, in _chain_order() order."""

    terms: Tuple[FusedTermSig, ...]
    term_caps: Tuple[int, ...]
    chain_caps: Tuple[int, ...]


def _chain_order(P: int):
    return [(s, i) for s in range(P) for i in range(s + 1, P)]


def _fold_names(var_names_seq):
    """Static fold of output variable names along a join chain; returns the
    final name tuple and per-step (pairs, extra) join metadata (mirrors
    compiler._join ordering)."""
    names: Tuple[str, ...] = ()
    metas = []
    for n, vn in enumerate(var_names_seq):
        if n == 0:
            names = tuple(vn)
            continue
        pairs = tuple((names.index(v), vn.index(v)) for v in names if v in vn)
        extra = tuple(j for j, v in enumerate(vn) if v not in names)
        metas.append((pairs, extra))
        names = names + tuple(v for v in vn if v not in names)
    return names, metas


def build_fused_exact(sig: FusedExactSig, count_only: bool = False):
    """Lower a reference-order plan to ONE program that implements the
    And fold EXACTLY — including the empty-accumulator reseed quirk
    (ast.py And.matched, mirroring pattern_matcher.py:725-738) — so no
    query shape ever needs the staged/host fallback for reseed reasons.

    The reseed makes the accumulator's variable set data-dependent (it can
    restart at any term), which XLA's static shapes can't express directly.
    Trick: every possible reseed point s yields a STATIC suffix chain
    J(s,i) = A_s ⋈ ... ⋈ A_i, so the program computes all P(P-1)/2 chain
    joins with static column metadata, runs the reference fold as a tiny
    automaton over the chains' exact counts (state = latest reseed point),
    and selects the final table of the active state.  Chain totals are
    masked to the ACTIVE path so the host never grows capacity for
    never-taken cross-product chains.

    Returns (fn, names_per_state, cols_per_state): names_per_state[s] is
    the static bound variable tuple of final state s and cols_per_state[s]
    their column indices in the full-K output table — the host picks by
    the returned state.  Call convention matches build_fused; stats layout:
      [count, s_active, any_pos_empty, *term_ranges, *masked_chain_totals]
    """
    positives = [i for i, t in enumerate(sig.terms) if not t.negated]
    negatives = [i for i, t in enumerate(sig.terms) if t.negated]
    P = len(positives)
    chain_pairs = _chain_order(P)
    cap_of = dict(zip(chain_pairs, sig.chain_caps))

    # static metadata per suffix chain
    chain_names: Dict[Tuple[int, int], Tuple[str, ...]] = {}
    chain_meta: Dict[Tuple[int, int], Tuple] = {}
    for s in range(P):
        seq = [sig.terms[positives[i]].var_names for i in range(s, P)]
        names, metas = _fold_names(seq)
        running = tuple(seq[0])
        chain_names[(s, s)] = running
        for off, meta in enumerate(metas):
            i = s + 1 + off
            vn = seq[off + 1]
            running = running + tuple(v for v in vn if v not in running)
            chain_names[(s, i)] = running
            chain_meta[(s, i)] = meta

    # full output layout: all positive variables, first-appearance order
    all_names, _ = _fold_names([sig.terms[i].var_names for i in positives])
    K = len(all_names)
    names_per_state = tuple(chain_names[(s, P - 1)] for s in range(P))
    cols_per_state = tuple(
        tuple(all_names.index(n) for n in names) for names in names_per_state
    )
    cap_final = max(
        cap_of[(s, P - 1)] if s < P - 1 else sig.term_caps[positives[s]]
        for s in range(P)
    )

    def fn(bucket_arrays, keys, fixed_vals):
        tables = {}
        term_ranges = []
        for i, t in enumerate(sig.terms):
            vals, mask, rng = _probe(
                t, bucket_arrays[i], keys[i], fixed_vals[i], sig.term_caps[i]
            )
            tables[i] = (vals, mask)
            term_ranges.append(rng)

        pos_counts = [tables[i][1].sum(dtype=jnp.int32) for i in positives]
        any_pos_empty = jnp.bool_(False)
        for c in pos_counts:
            any_pos_empty = any_pos_empty | (c == 0)

        # all suffix-chain joins (static shapes per chain)
        chain: Dict[Tuple[int, int], Tuple] = {}
        totals: Dict[Tuple[int, int], jax.Array] = {}
        C = jnp.zeros((P, P), dtype=jnp.int32)
        for s in range(P):
            v, m = tables[positives[s]]
            chain[(s, s)] = (v, m)
            C = C.at[s, s].set(pos_counts[s])
            for i in range(s + 1, P):
                rv, rm = tables[positives[i]]
                pairs, extra = chain_meta[(s, i)]
                v, m, tot = _join_tables_impl(
                    chain[(s, i - 1)][0], chain[(s, i - 1)][1],
                    rv, rm, pairs, extra, cap_of[(s, i)],
                )
                chain[(s, i)] = (v, m)
                totals[(s, i)] = tot
                # explicit downcast: tot is an int64 row count; scattering
                # it into the int32 count matrix without astype is a
                # FutureWarning today and an error in future JAX
                C = C.at[s, i].set(
                    jnp.minimum(tot, 2**31 - 1).astype(jnp.int32)
                )

        # the reference fold as an automaton over chain counts:
        # state = latest reseed point; transition BEFORE joining term i
        s_act = jnp.int32(0)
        used: Dict[Tuple[int, int], jax.Array] = {}
        for i in range(1, P):
            prev_empty = C[s_act, i - 1] == 0
            for s in range(i):
                used[(s, i)] = (~prev_empty) & (s_act == s)
            s_act = jnp.where(prev_empty, jnp.int32(i), s_act)

        masked_totals = [
            jnp.where(used[(s, i)], totals[(s, i)], jnp.int32(0))
            for (s, i) in chain_pairs
        ]

        # final state tables: project to the full-K layout, apply negation
        # filters whose variable set the state covers, pad to cap_final
        final_vals = jnp.zeros((cap_final, K), dtype=jnp.int32)
        final_valid = jnp.zeros((cap_final,), dtype=bool)
        count = jnp.int32(0)
        for s in range(P):
            v, m = chain[(s, P - 1)]
            names_s = chain_names[(s, P - 1)]
            for ni in negatives:
                t = sig.terms[ni]
                if set(t.var_names) <= set(names_s):
                    pairs = tuple(
                        (names_s.index(x), t.var_names.index(x))
                        for x in t.var_names
                    )
                    rv, rm = tables[ni]
                    m = _anti_join_impl(v, m, rv, rm, pairs)
            proj = jnp.zeros((v.shape[0], K), dtype=jnp.int32)
            for ci, name in enumerate(names_s):
                proj = proj.at[:, all_names.index(name)].set(v[:, ci])
            pad = cap_final - v.shape[0]
            if pad:
                proj = jnp.concatenate(
                    [proj, jnp.zeros((pad, K), dtype=jnp.int32)]
                )
                m = jnp.concatenate([m, jnp.zeros((pad,), dtype=bool)])
            sel = s_act == s
            final_vals = jnp.where(sel, proj, final_vals)
            final_valid = jnp.where(sel, m, final_valid)
            count = jnp.where(sel, m.sum(dtype=jnp.int32), count)

        count = jnp.where(any_pos_empty, jnp.int32(0), count)
        final_valid = final_valid & ~any_pos_empty
        stats = jnp.stack(
            [
                count,
                s_act,
                any_pos_empty.astype(jnp.int32),
                *term_ranges,
                *masked_totals,
            ]
        )
        if count_only:
            return stats
        return final_vals, final_valid, stats

    return obs.proflog.instrument(
        "fused_exact", obs.proflog.sig_digest(sig, count_only),
        jax.jit(obs.named_program("das_fused_exact", fn, count_only)),
    ), names_per_state, cols_per_state


#: token capacity for index-joined terms — never materialized
INDEX_TERM_TOKEN_CAP = 16


def apply_index_joins(buckets, sigs, arrays, term_caps):
    """Decide per-join index-join routing and rewrite the affected terms'
    inputs: positional posting-index arrays instead of the type-sorted
    window, and a token capacity (the term is never materialized, so it
    exerts no buffer or compile-size pressure).  `buckets` maps arity to
    the executor's bucket objects (single-device DeviceBucket or sharded
    ShardedBucket — both carry key_type_pos/order_by_type_pos/targets/
    type_id), so both executors share one routing convention."""
    index_joins, index_right = plan_index_joins(sigs)
    return (
        index_joins, frozenset(index_right),
        index_join_arrays(buckets, sigs, arrays, index_joins, index_right),
        clamp_index_terms(term_caps, index_right),
    )


def index_join_arrays(buckets, sigs, arrays, index_joins, index_right):
    """`arrays` with every index-joined term's inputs replaced by the
    posting index of the position its join probes (plan_index_joins'
    `index_joins`, `right_terms`)."""
    if not index_right:
        return arrays
    arrays = list(arrays)
    for i, n in index_right.items():
        p = index_joins[n]
        b = buckets[sigs[i].arity]
        arrays[i] = (
            b.key_type_pos[p], b.order_by_type_pos[p], b.targets, b.type_id,
        )
    return tuple(arrays)


def clamp_index_terms(term_caps, index_right):
    """Learned/stored capacities may predate index-join routing for this
    signature; index-joined terms never materialize, so their token
    capacity must survive the merge."""
    return tuple(
        INDEX_TERM_TOKEN_CAP if i in index_right else c
        for i, c in enumerate(term_caps)
    )


#: batching ceiling for one member's largest term capacity: a vmapped
#: group multiplies every padded buffer by the lane count, so a whole-type
#: term at reference scale (tens of millions of rows) must run single-lane
#: (the staged/single-dispatch paths handle it in one ~quarter-GB buffer)
LARGE_TERM_BATCH_LIMIT = 1 << 23


def trivial_plan_count(db, plans) -> Optional[int]:
    """Exact count for a single positive term with distinct variables —
    entirely host-side, zero device work.

    Unconstrained shape (whole-type / whole-template): every row in the
    term's key range yields one distinct assignment (links are
    content-addressed, so no two rows bind identical targets), so the
    host-side range size IS the answer — no materialized multi-GB padded
    table.  This is the pattern miner's all-wildcard candidate shape
    (reference emits a `[*, *targets]` key per link and counts the Redis
    set).

    Grounded shape (type + fixed positions): the most selective fixed
    position's sorted range is gathered from the SAME host copies of the
    probe indexes the device uses, the remaining fixed positions verified
    with numpy compares.  Each surviving row is one distinct assignment
    for the same content-addressing reason — every non-fixed position is
    a distinct variable, so two surviving rows that bound identical
    targets would be the same link.  This is the miner's wildcard-variant
    candidate shape (notebook cell 9): the reference answers each with a
    Redis `patterns` set cardinality; the fused path would compile one
    vmapped program per variant shape (the r04 counting phase spent ~54 s
    there at FlyBase scale).  The one shape whose count the host cannot
    decide locally is a dangling (-1) target in a variable position —
    two distinct links could then bind identical tuples and the device
    path would dedup them — so those rows (nonexistent in converter
    output) fall back to the device (None)."""
    if plans is None or len(plans) != 1:
        return None
    p = plans[0]
    if p.negated or p.eq_pairs:
        return None
    if not p.fixed:
        return estimate_plan_rows(db, p)
    if p.ctype is not None or p.type_id is None:
        return None
    if os.environ.get("DAS_TPU_HOST_COUNT", "1") == "0":
        return None  # test hook: force the device path for grounded terms
    from das_tpu.storage.atom_table import host_probe_locals, host_segments

    # a non-None EMPTY dangling set proves no -1 target exists in any
    # segment (finalize records every unresolved element; the delta path
    # keeps the set current and a restored store without one rebuilds on
    # first commit) — the per-row scan below can then never fire, so skip
    # gathering var columns entirely on the common converter-output path
    dangling = db.fin.dangling_hexes
    scan_dangling = dangling is None or len(dangling) > 0
    total = 0
    for b in host_segments(db, p.arity):
        local = host_probe_locals(b, p.type_id, p.fixed)
        if local.size == 0:
            continue
        if scan_dangling and p.var_cols and b.has_dangling:
            sub = b.targets[np.ix_(local, p.var_cols)]
            if (sub < 0).any():
                return None  # dangling rows: device dedup semantics decide
        total += int(local.size)
    return total


def estimate_plan_rows(db, plan) -> int:
    """EXACT candidate count for one term with zero device work: the same
    sorted key arrays the device probes live in host memory, so binary
    searches give the range size with no device round trip.  Sums over the
    base bucket and any incremental-delta overlay segment
    (`db.host_bucket_segments`, provided by both device backends) —
    together they exactly mirror the merged device index.  Shared by the
    single-device and sharded executors."""
    from das_tpu.storage.atom_table import host_segments

    total = 0
    for b in host_segments(db, plan.arity):
        if plan.ctype is not None:
            keys, key = b.key_ctype, np.int64(plan.ctype)
        elif plan.type_id is not None and plan.fixed:
            p0, v0 = plan.fixed[0]
            keys, key = b.key_type_pos[p0], (np.int64(plan.type_id) << 32) | np.int64(v0)
        else:
            assert plan.type_id is not None, "TermPlan without type or ctype"
            keys, key = b.key_type, np.int32(plan.type_id)
        lo = int(np.searchsorted(keys, key, side="left"))
        hi = int(np.searchsorted(keys, key, side="right"))
        total += hi - lo
    return total


def reference_order_authoritative(positives) -> bool:
    """THE predicate behind the keep-reference-order rule, shared by
    order_plans and the cost-based planner (das_tpu/planner/search.py —
    one copy, so the two paths cannot drift on WHICH queries pay the
    reseed fallback): the positive terms are CONNECTED in reference
    order (every term shares a variable with the terms before it) AND
    at least one is grounded (selective — its candidate set is a
    specific-target probe, so intermediates stay small by construction).
    The compiled program is then the reference fold itself and its
    in-program reseed flag is authoritative: zero-count answers are
    definitive, no exact-variant re-run."""
    if len(positives) <= 1:
        return True
    bound = set(positives[0].var_names)
    for p in positives[1:]:
        if not (set(p.var_names) & bound):
            return False
        bound |= set(p.var_names)
    return any(p.fixed and p.ctype is None for p in positives)


def order_plans(plans, estimate) -> List:
    """Join ordering policy (shared by the single-device and sharded
    executors).  When `reference_order_authoritative` holds, keep the
    reference order (reseed verdicts then need no exact-variant re-run).
    All-wildcard analytic plans and disconnected plans use greedy
    smallest-first ordering, which avoids huge x huge first joins (e.g.
    the ungrounded 3-var bio query: Member x Member in reference order
    materializes sum-of-degree-squared rows; greedy starts from the
    small Interacts table instead).  Negated terms filter at the end
    regardless of order."""
    pos = [(p, estimate(p)) for p in plans if not p.negated]
    neg = [p for p in plans if p.negated]
    if len(pos) <= 1:
        return [p for p, _ in pos] + neg
    if reference_order_authoritative([p for p, _ in pos]):
        return [p for p, _ in pos] + neg
    ordered = []
    bound = set()
    remaining = list(pos)
    while remaining:
        connected = [
            (p, e) for p, e in remaining
            if not bound or (set(p.var_names) & bound)
        ] or remaining
        pick = min(connected, key=lambda pe: pe[1])
        remaining.remove(pick)
        ordered.append(pick[0])
        bound |= set(pick[0].var_names)
    return ordered + neg


def same_positive_order(ordered, plans) -> bool:
    """Reseed semantics depend only on the POSITIVE term order (negated
    terms filter at the end either way)."""
    po = [p for p in ordered if not p.negated]
    pp = [p for p in plans if not p.negated]
    return len(po) == len(pp) and all(a is b for a, b in zip(po, pp))


class ResultCache:
    """Device-resident query result cache, guarded by the backend's
    incremental-commit counter (storage/delta.py delta_version).

    Key = (per-term plan digest, count_only): the TermPlan tuple carries
    the plan SHAPE and every grounded value (type ids, fixed global rows,
    ctype keys), and global rows are stable within one delta version — so
    shape + grounded values + version pin the answer exactly.  A hit
    returns the cached FusedResult (device refs plus the prefetched host
    copies): zero device programs, zero host transfers.  Any commit bumps
    delta_version, which drops the whole cache — every entry was written
    against the pre-commit tables, so that is exactly the stale set.

    Reseed-flagged results are never cached (the exact variant re-answers
    them); entries are LRU-bounded by config.result_cache_size, and a
    non-count result wider than MAX_ENTRY_ROWS is not cached at all —
    each such entry pins cap-sized device AND host buffers, so a
    count-bounded LRU alone could pin (entries x max_result_capacity)
    bytes of HBM.  Serving-shaped (grounded) answers are far below the
    bound; giant analytic tables just stay uncached."""

    #: widest binding table one cache entry may pin (rows x columns);
    #: at int32 this bounds an entry near 4 MB device + 4 MB host
    MAX_ENTRY_ROWS = 1 << 20

    def __init__(self, db):
        import threading
        from collections import OrderedDict

        self.db = db
        self._data: "OrderedDict" = OrderedDict()
        self._version = None
        self._lock = threading.Lock()
        self.stats = {"hits": 0, "misses": 0, "invalidations": 0}

    @staticmethod
    def key(plans, count_only: bool):
        return (
            tuple(
                (
                    p.arity, p.type_id, p.ctype, p.fixed, p.var_names,
                    p.var_cols, p.eq_pairs, p.negated,
                )
                for p in plans
            ),
            count_only,
        )

    def limit(self) -> int:
        return int(getattr(self.db.config, "result_cache_size", 0))

    def version(self):
        return getattr(self.db, "delta_version", None)

    def _sync_version(self) -> None:
        """Caller holds the lock."""
        v = self.version()
        if v != self._version:
            if self._data:
                self.stats["invalidations"] += 1
                if obs.enabled():
                    # a commit just made every entry stale — the event
                    # the trace needs to explain a post-commit latency
                    # step (hits turning into device dispatches)
                    obs.event("cache.invalidate", entries=len(self._data),
                              version=v)
                    obs.counter("cache.invalidations").inc()
            self._data.clear()
            self._version = v

    def get(self, key):
        if self.limit() <= 0:
            return None
        with self._lock:
            self._sync_version()
            hit = self._data.get(key)
            if hit is None:
                self.stats["misses"] += 1
                if obs.enabled():
                    # a counter and no instant: nothing reads one
                    # miss, and the worker pays for every event
                    obs.counter("cache.misses").inc()
                return None
            self._data.move_to_end(key)
            self.stats["hits"] += 1
            if obs.enabled():
                # zero-dispatch answer: the "materialize-or-cache-hit"
                # arm of the traced lifecycle
                obs.event("cache.hit", count=getattr(hit, "count", None))
                obs.counter("cache.hits").inc()
            return hit

    def put(self, key, result, version) -> None:
        """`version` is the delta version the caller DISPATCHED against:
        a commit that landed between dispatch and settle must not smuggle
        a pre-commit answer under the post-commit version."""
        from das_tpu import fault
        from das_tpu.core.exceptions import InjectedFault

        try:
            fault.maybe_fail("cache_insert")
        except InjectedFault:
            # a failed cache insert degrades to "not cached" — the
            # answer was already computed and delivered, so the query
            # must never see this failure (chaos-parity: the only
            # observable effect is a later cache miss)
            return
        limit = self.limit()
        if limit <= 0 or result is None or getattr(
            result, "reseed_needed", False
        ):
            return
        # the prefetched host copy has the table's shape: measuring it
        # leaves the device reference of a group lane unsliced
        vals = getattr(result, "host_vals", None)
        if vals is None:
            vals = getattr(result, "vals", None)
        # total elements, covering both the 2-D [cap, k] single-device
        # table and the 3-D [S, cap, k] sharded layout
        if vals is not None and vals.size > self.MAX_ENTRY_ROWS:
            return  # too wide to pin: see MAX_ENTRY_ROWS
        with self._lock:
            self._sync_version()
            if version != self._version:
                return
            self._data[key] = result
            self._data.move_to_end(key)
            while len(self._data) > limit:
                self._data.popitem(last=False)


def result_cache_stats(db) -> Dict[str, int]:
    """Aggregate hit/miss counters of the db's live executor caches (the
    single-device fused executor and/or the sharded mirror) — serving
    observability without reaching into executor internals."""
    out = {"hits": 0, "misses": 0, "invalidations": 0}
    executors = []
    dev = getattr(db, "dev", None)
    if dev is not None:
        executors.append(getattr(dev, "_fused_executor", None))
    tables = getattr(db, "tables", None)
    if tables is not None:
        executors.append(getattr(tables, "_fused_executor", None))
    for ex in executors:
        for attr in ("results", "tree_results"):
            cache = getattr(ex, attr, None)
            if cache is not None:
                for k in out:
                    out[k] += cache.stats[k]
    return out


def shape_key(plans) -> Tuple:
    """The SHAPE of a conjunction: ResultCache.key's per-term digest
    with every grounded value left out (of `fixed`, the positions
    stay).  Two queries of one shape differ in grounded row ids alone:
    they share a `_JobTemplate`.  `count_only` is not part of it:
    nothing a template keeps depends on it."""
    return tuple(
        (
            p.arity, p.type_id, p.ctype, tuple(q for q, _ in p.fixed),
            p.var_names, p.var_cols, p.eq_pairs, p.negated,
        )
        for p in plans
    )


class _OrderedShape:
    """What one join ORDER of a shape fixes: the term signatures in
    that order, whether it is the reference fold (`same_order`), and
    the index-join routing (plan_index_joins: a pure function of the
    signatures)."""

    __slots__ = ("sigs", "same_order", "index_joins", "index_right",
                 "n_joins")

    def __init__(self, sigs, order):
        self.sigs = tuple(sigs[t] for t in order)
        positives = [t for t in order if not sigs[t].negated]
        # reseed semantics depend only on the POSITIVE term order
        # (same_positive_order)
        self.same_order = positives == sorted(positives)
        self.index_joins, self.index_right = plan_index_joins(self.sigs)
        self.n_joins = max(0, len(positives) - 1)


class _JobTemplate:
    """What the job builder keeps per query SHAPE (FusedExecutor._build):
    everything `_exec_job` used to derive again for every query although
    no grounded value enters it.

      * `rule`: the planner's shape-level verdict
        (planner/search.py conjunction_rule): declined, ordered by the
        reference-order rule, or ordered per query;
      * `sigs`: the FusedTermSig of each term, in the plan list's
        order, and of each term its key recipe (route, type id or
        ctype, probe position: `lane_columns`);
      * per join order met (`ordered`): the signatures in that order,
        `same_order`, `index_joins` / `index_right`, `n_joins`.  A
        rule-ordered shape meets one order; a shape ordered per query
        ("dp", "greedy_tail", the legacy greedy order) one per order
        its queries' counts choose.

    NOT kept: anything a commit replaces.  The bucket arrays are taken
    fresh from `db.dev.buckets` per fill, the table-level statistics
    (distinct counts, the kept whole-table supports) stay with the
    estimator and starcount's caches under their own validity rules,
    the learned capacities with the executor; the templates themselves
    are dropped when `delta_version` moves."""

    __slots__ = ("rule", "sigs", "consts", "orders", "_const_cols")

    def __init__(self, plans):
        from das_tpu.planner.search import conjunction_rule

        self.rule = conjunction_rule(plans)
        self.sigs = tuple(term_sig(p) for p in plans)
        #: per term the probe key of a route no grounded value enters
        #: (FusedExecutor._term_args' keys), None for a type-pos probe
        self.consts = tuple(
            np.int64(p.ctype) if sig.route == ROUTE_CTYPE
            else np.int32(p.type_id) if sig.route == ROUTE_TYPE
            else None
            for p, sig in zip(plans, self.sigs)
        )
        self.orders: Dict[Tuple[int, ...], _OrderedShape] = {}
        self._const_cols: Dict[int, Tuple] = {}

    def ordered(self, order) -> _OrderedShape:
        shape = self.orders.get(order)
        if shape is None:
            shape = self.orders[order] = _OrderedShape(self.sigs, order)
        return shape

    def lane_columns(self, stats, n: int):
        """Per term (plan-list order) the probe keys and the verified
        fixed values of `n` same-shape queries as columns, the queries
        along axis 0: `(type_id << 32 | v0)` over the vector of v0, a
        route's constant key repeated, the fixed values beyond the
        probe as int32 [n, extras]."""
        consts = self._const_cols.get(n)
        if consts is None:
            # the columns no grounded value enters, kept per width
            consts = self._const_cols[n] = (
                [None if c is None else np.full(n, c, c.dtype)
                 for c in self.consts],
                np.zeros((n, 0), np.int32),
            )
        key_cols = [
            stats.key_col(t) if col is None else col
            for t, col in enumerate(consts[0])
        ]
        fval_cols = [
            np.stack(
                [stats.fixed_col(t, k)
                 for k in range(1, 1 + len(sig.extra_fixed))],
                axis=1,
            ).astype(np.int32)
            if sig.extra_fixed else consts[1]
            for t, sig in enumerate(self.sigs)
        ]
        return key_cols, fval_cols


def get_executor(db) -> "FusedExecutor":
    """The per-database executor, cached on the device tables so a
    `refresh()` (which rebuilds them) naturally drops stale programs."""
    ex = getattr(db.dev, "_fused_executor", None)
    if ex is None or ex.db is not db:
        ex = FusedExecutor(db)
        db.dev._fused_executor = ex
    return ex


# -- warm-state bundle (ISSUE 15, storage/durable.py) ------------------------
#
# The state a fresh replica would otherwise RE-LEARN: CapStore learned
# capacities (each re-learned tier is an XLA recompile), the planner's
# exact degree statistics (host searchsorted passes), and the answered
# count-cache entries (the miner's hot loop).  All of it is a perf hint
# — a stale or absent bundle costs retries/recomputation, never
# correctness — so export/apply are best-effort and keyed by
# delta_version exactly like the result caches.


def _warm_executor(db):
    dev = getattr(db, "dev", None)
    if dev is not None:
        return get_executor(db)
    if getattr(db, "tables", None) is not None:
        from das_tpu.parallel.fused_sharded import get_sharded_executor

        return get_sharded_executor(db)
    return None


def _jsonable(obj):
    """Nested tuples -> lists for msgpack (keys round-trip via
    _tuplize)."""
    if isinstance(obj, tuple):
        return [_jsonable(x) for x in obj]
    return obj


def _tuplize(obj):
    if isinstance(obj, list):
        return tuple(_tuplize(x) for x in obj)
    return obj


def export_warm_state(db) -> Optional[Dict]:
    """The warm bundle persisted beside a snapshot (durable.
    write_snapshot): cross-process CapStore dicts (already stable-hash
    keyed), count-only result-cache entries (host ints — the wide
    binding tables stay device-resident and are NOT persisted), and
    the planner estimator's memoized degree statistics.

    Scope: learned CAPACITIES cover the single-device executor only —
    ShardedFusedExecutor keeps its `_caps` keyed by raw sig tuples
    with no stable-hash store, so the mesh bundle carries counts +
    planner stats (giving it a CapStore is the named remainder); a
    mesh replica's planner-seeded capacities are margin-free where the
    statistics are exact, so the retry tier this leaves on the table
    is the estimator-miss residue only."""
    ex = _warm_executor(db)
    if ex is None:
        return None
    out: Dict = {"delta_version": int(getattr(db, "delta_version", 0))}
    caps = {}
    for tag in ("_cap_store", "_exact_cap_store"):
        store = getattr(ex, tag, None)
        if store is not None and store._data:
            caps[tag] = dict(store._data)
    out["caps"] = caps
    counts = []
    results = getattr(ex, "results", None)
    if results is not None:
        with results._lock:
            for key, entry in results._data.items():
                if getattr(entry, "vals", None) is None and isinstance(
                    getattr(entry, "count", None), int
                ):
                    counts.append([_jsonable(key), entry.count])
    out["counts"] = counts
    est = getattr(db, "_planner_estimator", None)
    if est is not None and est.version == getattr(db, "delta_version", None):
        out["planner"] = {
            "rows": [[_jsonable(k), v] for k, v in est._rows.items()],
            "distinct": [
                [_jsonable(k), v] for k, v in est._distinct.items()
            ],
        }
    return out


def apply_warm_state(db, state: Dict) -> bool:
    """Apply a restored warm bundle onto a freshly restored backend.
    The delta_version guard is the SAME staleness rule the result
    caches live by: a bundle recorded at a version the store is no
    longer at (WAL replayed past the snapshot) is discarded whole."""
    if int(state.get("delta_version", -1)) != int(
        getattr(db, "delta_version", 0)
    ):
        return False
    ex = _warm_executor(db)
    if ex is None:
        return False
    for tag, data in (state.get("caps") or {}).items():
        store = getattr(ex, tag, None)
        if store is not None:
            store._data.update(data)
    version = getattr(db, "delta_version", None)
    results = getattr(ex, "results", None)
    if results is not None:
        for key, n in state.get("counts") or ():
            results.put(
                _tuplize(key),
                FusedResult((), None, None, int(n), False, False),
                version,
            )
    planner = state.get("planner")
    if planner:
        from das_tpu.planner.stats import estimator_for

        est = estimator_for(db)
        if est is not None:
            est._rows.update(
                (_tuplize(k), int(v)) for k, v in planner.get("rows", ())
            )
            est._distinct.update(
                (_tuplize(k), int(v))
                for k, v in planner.get("distinct", ())
            )
    return True


class FusedExecutor:
    """Per-database cache: plan signature -> compiled fused executable."""

    def __init__(self, db):
        self.db = db
        self._cache: Dict[Tuple, Tuple] = {}          # (plan_sig, count_only)
        #: answered-result cache (delta-version guarded).  Consulted by
        #: the serving/batched paths (execute_many / dispatch_many /
        #: count_batch) and by execute(use_cache=True); the bare execute()
        #: stays uncached so per-dispatch regression pins keep measuring
        #: the device.
        self.results = ResultCache(db)
        #: tree-composite cache (query/tree.py): whole evaluated plan
        #: trees keyed by plan-tree digest, same version guard
        self.tree_results = ResultCache(db)
        self._batch_cache: Dict[FusedPlanSig, object] = {}
        #: the served path's group programs (_ExecJob.dispatch_group):
        #: (plan_sig, count_only, lanes, key axes, fixed-value axes) ->
        #: (fn, names)
        self._group_cache: Dict[Tuple, Tuple] = {}
        #: whole-tree fused programs (ISSUE 10): FusedTreeSig -> (fn,
        #: names).  Bounded in _TreeExecJob.dispatch (no per-site
        #: remember_caps eviction hook — tree sigs nest many term sigs)
        self._tree_progs: Dict[FusedTreeSig, Tuple] = {}
        self._exact_cache: Dict[Tuple, Tuple] = {}    # (exact_sig, count_only)
        self._exact_batch_cache: Dict[FusedExactSig, Tuple] = {}
        # overflow-corrected capacities learned per plan shape, so later
        # calls start right-sized instead of re-running the overflowing
        # program every time; the CapStores carry them across processes
        self._caps: Dict[Tuple, Tuple[Tuple[int, ...], Tuple[int, ...]]] = {}
        self._exact_caps: Dict[Tuple, Tuple[int, ...]] = {}
        self._cap_store = CapStore("greedy")
        self._exact_cap_store = CapStore("exact")
        #: the job builder's per-shape templates (shape_key ->
        #: _JobTemplate), valid for one delta_version (_build)
        self._templates: Dict[Tuple, "_JobTemplate"] = {}
        self._templates_version = None

    def _cap_salt(self) -> str:
        """Capacities are KB-size dependent: key the cross-process store by
        store shape so flybase-scale caps never seed a toy KB (or vice
        versa — undersized seeds merely retry)."""
        fin = self.db.fin
        return f"{fin.atom_count}:{fin.node_count}"

    def _learned_caps(self, mem, store, sigs, shape_lens):
        """In-memory learned caps, else the cross-process store — BOTH
        validated against the expected per-stage lengths, so a stale or
        foreign store entry cannot zip-truncate into the seed merge."""
        def _valid(caps):
            return caps is not None and len(caps) == len(shape_lens) and all(
                len(c) == n for c, n in zip(caps, shape_lens)
            )

        caps = mem.get(sigs)
        if _valid(caps):
            return caps
        caps = store.load(sigs, self._cap_salt())
        return caps if _valid(caps) else None

    _same_positive_order = staticmethod(same_positive_order)

    @staticmethod
    def _sig_caps(ps) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        second = ps.join_caps if isinstance(ps, FusedPlanSig) else ps.chain_caps
        return (ps.term_caps, second)

    def _remember_caps(self, sigs, term_caps, join_caps) -> None:
        if self._caps.get(sigs) == (term_caps, join_caps):
            # known, and saved when it was learned: no CapStore key
            # (an md5 of the signatures' repr) per settled job
            return
        remember_caps(
            self._caps,
            (self._cache, self._batch_cache, self._group_cache), sigs,
            (term_caps, join_caps), self._sig_caps,
        )
        self._cap_store.save(sigs, (term_caps, join_caps), self._cap_salt())

    # -- plan -> signature + dynamic arguments ----------------------------

    def _term_args(self, plan) -> Optional[Tuple[FusedTermSig, Tuple, object, np.ndarray]]:
        """Map a compiler.TermPlan to (sig, bucket_arrays, key, fixed_vals)."""
        bucket = self.db.dev.buckets.get(plan.arity)
        if bucket is None or bucket.size == 0:
            return None
        sig = term_sig(plan)
        if sig.route == ROUTE_CTYPE:
            key = np.int64(plan.ctype)
        elif sig.route == ROUTE_TYPE_POS:
            key = (np.int64(plan.type_id) << 32) | np.int64(plan.fixed[0][1])
        else:
            key = np.int32(plan.type_id)
        fixed_vals = np.asarray(
            [v for _, v in plan.fixed[1:]] if sig.route == ROUTE_TYPE_POS else [],
            dtype=np.int32,
        )
        return sig, route_arrays(bucket, sig), key, fixed_vals

    def _estimate(self, plan) -> int:
        return estimate_plan_rows(self.db, plan)

    def _apply_index_joins(self, sigs, arrays, term_caps):
        return apply_index_joins(
            self.db.dev.buckets, sigs, arrays, term_caps
        )

    _clamp_index_terms = staticmethod(clamp_index_terms)

    def _join_cap_seed(self, plans, term_caps, rows=None) -> int:
        """First-call join/chain capacity seed.  When the plan has grounded
        (fixed-target) positive terms, real join outputs are near those
        small candidate sets — seeding from the biggest UNGROUNDED term
        (the old policy) made every join pay full-table capacity, which is
        the difference between ~5 ms and ~5 s for a vmapped batch.  Retries
        double capacity on overflow and the result is memoized per shape,
        so a low seed costs at most a few extra compiles on first contact.

        The per-term estimates bound the clamp from BELOW too (ISSUE 8
        satellite): `min(initial_result_capacity, ...)` honors an
        operator-shrunk seed, but an accumulator that starts as a
        grounded term's table already holds max(grounded) exact rows —
        clamping the join capacity under that forces a guaranteed retry
        round (one wasted XLA compile per shape) that no configuration
        can be trying to buy.  `rows`: the plans' exact candidate rows,
        where the caller has counted them."""
        cfg = self.db.config
        grounded = [
            self._estimate(p) if rows is None else rows[k]
            for k, p in enumerate(plans)
            if p.fixed and p.ctype is None and not p.negated
        ]
        if grounded:
            mg = max(grounded)
            return _pow2_at_least(
                max(64, min(cfg.initial_result_capacity, 4 * mg), mg)
            )
        return _pow2_at_least(max([cfg.initial_result_capacity, *term_caps]))

    def _group_cap_seed(self, sigs, est_rows) -> int:
        """_join_cap_seed for a batch group: sigs are shape-static, so
        grounded-ness comes from the route; estimates vary per member."""
        cfg = self.db.config
        grounded_idx = [
            t for t, s in enumerate(sigs)
            if s.route == ROUTE_TYPE_POS and not s.negated
        ]
        if grounded_idx:
            m = max(max(e[t] for t in grounded_idx) for e in est_rows)
            # same lower bound as _join_cap_seed: a shrunk configured
            # seed must not clamp under the exact grounded row counts
            return _pow2_at_least(
                max(64, min(cfg.initial_result_capacity, 4 * m), m)
            )
        term_cap_max = max(
            _pow2_at_least(max(e[t] for e in est_rows))
            for t in range(len(sigs))
        )
        return _pow2_at_least(max(cfg.initial_result_capacity, term_cap_max))

    def _order(self, plans) -> List:
        return order_plans(plans, self._estimate)

    # _ExecJob drives the dispatch/settle halves of execute(); defined
    # after the class (it needs build_fused and FusedResult)

    def _exec_job(self, plans, count_only: bool) -> Optional["_ExecJob"]:
        """Prepare one execution's state (ordering, term args, capacity
        seeds): the batch of one of `_build` (the lone execute(), a
        tree's sites, planner.explain).  None when a bucket is missing
        or the merged caps exceed the configured ceiling — the caller
        falls back, as before."""
        return self._build([plans], count_only)[0][0]

    def _build_jobs(self, plans_lists, count_only: bool) -> List:
        """dispatch_pending's `build_jobs`: the jobs of a batch, one or
        None (a decline) per entry, under ONE `exec.build` span
        (attrs: queries, shapes, templates_built) — per batch, never
        per query: recording a span costs the thread that builds."""
        if not obs.enabled():
            return self._build(plans_lists, count_only)[0]
        with obs.span("exec.build", queries=len(plans_lists)) as sp:
            jobs, shapes, built = self._build(plans_lists, count_only)
            sp.set(shapes=shapes, templates_built=built)
        obs.counter("exec.template_builds").inc(built)
        obs.counter("exec.template_hits").inc(len(plans_lists) - built)
        return jobs

    def _build(self, plans_lists, count_only: bool):
        """THE job builder: `(jobs, shapes, templates built)`.  The
        queries are told apart by SHAPE (shape_key); what a shape
        decides is read once from its kept `_JobTemplate` (built on
        first sight, dropped when `delta_version` moves, like the
        planner's estimator), and `_fill` computes for all queries of
        a shape at once what their grounded values decide."""
        version = getattr(self.db, "delta_version", None)
        if version != self._templates_version or len(self._templates) > 256:
            self._templates.clear()
            self._templates_version = version
        jobs: List = [None] * len(plans_lists)
        by_shape: Dict[Tuple, List[int]] = {}
        for i, plans in enumerate(plans_lists):
            by_shape.setdefault(shape_key(plans), []).append(i)
        built = 0
        for key, members in by_shape.items():
            template = self._templates.get(key)
            if template is None:
                template = self._templates[key] = _JobTemplate(
                    plans_lists[members[0]]
                )
                built += 1
            filled = self._fill(
                template, [plans_lists[i] for i in members], count_only
            )
            for i, job in zip(members, filled):
                jobs[i] = job
        return jobs, len(by_shape), built

    def _fill(self, template, plans_lists, count_only: bool) -> List:
        """The jobs of N same-shape queries.  Per fill: the bucket
        arrays (FRESH from `db.dev.buckets`: a commit replaces them,
        lanes_program's docstring), the probe-key and fixed-value
        columns, the batch's statistics (planner/stats.py
        BatchEstimator: one searchsorted per term, side and host
        segment for all N) and, per join order met, the learned
        capacities.  Per query: the planner's fold on its own numbers
        (behind DasConfig.use_planner the cost-based planner fixes the
        join order and the per-intermediate capacity seeds; where it
        declines or is off the legacy greedy order and blind seeds
        apply — answers are identical either way, only compile/retry
        traffic differs), the merge with the learned capacities, the
        ceiling, the planner counters.  Jobs that end with equal
        capacities hold ONE FusedPlanSig object."""
        from das_tpu import planner as _planner
        from das_tpu.planner.stats import BatchEstimator, estimator_for

        db, cfg = self.db, self.db.config
        n = len(plans_lists)
        buckets = db.dev.buckets
        for sig in template.sigs:
            bucket = buckets.get(sig.arity)
            if bucket is None or bucket.size == 0:
                return [None] * n  # a missing bucket: the shape declines
        stats = BatchEstimator(estimator_for(db), plans_lists)
        rule = template.rule if _planner.enabled(cfg) else None
        key_cols, fval_cols = template.lane_columns(stats, n)
        orders: Dict[Tuple, Tuple] = {}
        jobs: List = []
        for row, plans in enumerate(plans_lists):
            stats.at(row)
            # a shape whose order no rule fixes ("dp", "greedy_tail",
            # the legacy greedy order) is ordered from THIS query's
            # counts: per-query planning, inside the same builder
            planned = (
                _planner.plan_conjunction(db, plans, est=stats, rule=rule)
                if rule is not None else None
            )
            if planned is not None:
                order = planned.order
            else:
                order = tuple(
                    stats.term_of(p) for p in order_plans(plans, stats.rows)
                )
            entry = orders.get(order)
            if entry is None:
                shape = template.ordered(order)
                arrays = index_join_arrays(
                    buckets, shape.sigs,
                    tuple(
                        route_arrays(buckets[sig.arity], sig)
                        for sig in shape.sigs
                    ),
                    shape.index_joins, shape.index_right,
                )
                learned = self._learned_caps(
                    self._caps, self._cap_store, shape.sigs,
                    (len(order), shape.n_joins),
                )
                lanes = (
                    tuple(key_cols[t] for t in order),
                    tuple(fval_cols[t] for t in order),
                )
                entry = orders[order] = (shape, arrays, learned, lanes, {})
            shape, arrays, learned, lanes, by_caps = entry
            rows = (
                planned.est_term_rows if planned is not None
                else [stats.rows(plans[t]) for t in order]
            )
            # exact host-side range counts => term capacities never
            # overflow; an index-joined term is never materialized
            term_caps = tuple(
                INDEX_TERM_TOKEN_CAP if k in shape.index_right
                else _pow2_at_least(r)
                for k, r in enumerate(rows)
            )
            if (
                planned is not None
                and len(planned.join_cap_seeds) == shape.n_joins
            ):
                # the costed seeds: margin × estimated rows per
                # intermediate instead of one blind seed for every join
                # — overflow retry still owns estimate error, the
                # ladder just starts on the right rung for the common
                # case (margin-FREE where the statistic is exact: no
                # configured clamp can shrink a seed back under the
                # exact row count)
                join_caps = planned.join_cap_seeds
            else:
                join_caps = (
                    self._join_cap_seed(
                        [plans[t] for t in order], term_caps, rows
                    ),
                ) * shape.n_joins
            if learned is not None:
                term_caps = self._clamp_index_terms(
                    tuple(max(a, b) for a, b in zip(term_caps, learned[0])),
                    shape.index_right,
                )
                join_caps = tuple(
                    max(a, b) for a, b in zip(join_caps, learned[1])
                )
            # ceiling applies to the MERGED caps: stale/foreign CapStore
            # entries must not smuggle buffers past the configured
            # maximum; shapes past it go to the staged path, which
            # clamps (and owns the overflow error policy)
            if max(term_caps + join_caps, default=0) > cfg.max_result_capacity:
                jobs.append(None)
                continue
            # counted only once the job EXISTS: a decline above (missing
            # bucket, capacity ceiling) runs the legacy fallback, and the
            # planned/greedy decomposition must cover executor traffic the
            # settle observation will actually complete
            if planned is not None:
                _planner.record_planned(planned)
            else:
                _planner.PLANNER_COUNTS["greedy"] += 1
            plan_sig = by_caps.get((term_caps, join_caps))
            if plan_sig is None:
                plan_sig = by_caps[(term_caps, join_caps)] = FusedPlanSig(
                    shape.sigs, term_caps, join_caps, shape.index_joins,
                    planned is not None,
                )
            jobs.append(_ExecJob(
                self, count_only, shape.same_order, shape.sigs, arrays,
                tuple(col[row] for col in lanes[0]),
                tuple(col[row] for col in lanes[1]),
                plan_sig.term_caps, plan_sig.join_caps, shape.index_joins,
                planned=planned, sig=plan_sig, lanes=lanes, row=row,
            ))
        return jobs

    def execute(
        self, plans, count_only: bool = False, use_cache: bool = False
    ) -> Optional[FusedResult]:
        """Run the whole plan in one dispatch.

        With count_only the compiled program returns just the stats vector
        (binding-table materialization is dead-code-eliminated) — the shape
        `count_matches` and the miner want.

        With use_cache, an answered-result hit (same plan digest, same
        delta version) returns with ZERO device work; off by default so
        per-dispatch measurements and regression pins keep timing the
        device, not a dict lookup.

        Returns None when a term's bucket is missing: an unmatched positive
        term means "no match" and an unmatched negated term never filters,
        both of which the staged path already handles — the caller decides.
        """
        if use_cache:
            key = self.results.key(plans, count_only)
            hit = self.results.get(key)
            if hit is not None:
                return hit
            version = self.results.version()
        job = self._exec_job(plans, count_only)
        if job is None:
            return None
        while True:
            out = job.dispatch()
            FETCH_COUNTS["n"] += 1
            if job.settle(jax.device_get(out), out):
                if use_cache:
                    self.results.put(key, job.result, version)
                return job.result

    def tree_exec_job(self, pos_sites, neg_plans=None) -> Optional[_TreeExecJob]:
        """Prepare one whole-tree execution (ISSUE 10) — see
        prepare_tree_job."""
        return prepare_tree_job(self, pos_sites, neg_plans, _TreeExecJob)

    def execute_tree(self, pos_sites, neg_plans=None) -> Optional[_TreeExecJob]:
        """Run a whole Or/negation tree as ONE fused program (retry loop
        included).  Returns the settled job — result None with
        needs_fallback means the tree executor must re-answer (reseed
        verdict or capacity ceiling) — or None when no job could form."""
        job = self.tree_exec_job(pos_sites, neg_plans)
        if job is None:
            return None
        return run_tree_job(job)

    def dispatch_many(self, plans_lists, count_only: bool = False,
                      cache_only: bool = False):
        """First half of the serving pipeline: resolve result-cache hits,
        prepare the remaining jobs, and ENQUEUE their first dispatch round
        — all asynchronous, no host transfer.  The device starts executing
        this batch while the caller is still settling the previous one
        (settle_many); that overlap is the cross-request pipelining the
        coalescer drives (service/coalesce.py).  Returns an opaque pending
        handle for settle_many.  With cache_only (degraded-mode serving,
        ISSUE 13 breaker) NO device program is enqueued: cache hits
        answer, misses stay dispatch-time declines."""
        return dispatch_pending(
            self.results, self._exec_job, plans_lists, count_only,
            cache_only=cache_only, build_jobs=self._build_jobs,
        )

    def settle_many(self, pending) -> List[Optional[FusedResult]]:
        """Second half: pay the host transfer for the dispatched round and
        run each job's settle verdict.  Jobs that overflowed a capacity
        re-dispatch HERE, serially with their fetch — the graceful
        fallback: a retry round cannot overlap the next batch (its caps
        just changed), so it degrades to execute_many's serial loop."""
        return settle_pending(self.results, pending)

    def settle_many_iter(self, pending):
        """Streaming second half (ISSUE 6): yields (index, FusedResult)
        as each query's verdict lands — see settle_pending_iter."""
        return settle_pending_iter(self.results, pending)

    def execute_many(
        self, plans_lists, count_only: bool = False
    ) -> List[Optional[FusedResult]]:
        """Serving-path coalescing (VERDICT r03 item 5): every query in the
        batch dispatches asynchronously, then ONE host transfer fetches all
        results — N concurrent singles pay one host sync per retry round
        instead of one each.  Per-query semantics (capacity retry, reseed
        verdicts, cap learning) are identical to execute(): the same job
        object drives both halves (dispatch_many / settle_many)."""
        return self.settle_many(self.dispatch_many(plans_lists, count_only))

    def _remember_exact_caps(self, sigs, term_caps, chain_caps) -> None:
        remember_caps(
            self._exact_caps, (self._exact_cache, self._exact_batch_cache),
            sigs, (term_caps, chain_caps), self._sig_caps,
        )
        self._exact_cap_store.save(
            sigs, (term_caps, chain_caps), self._cap_salt()
        )

    def execute_exact(self, plans, count_only: bool = False) -> Optional[FusedResult]:
        """Reference-order single-dispatch execution with the reseed quirk
        implemented in-program (build_fused_exact).  `plans` must be in the
        original (reference) term order — NO greedy reordering here, the
        fold is order-sensitive.  Never needs a reseed fallback; returns
        None only on missing buckets or capacity ceiling."""
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
        term_caps = tuple(_pow2_at_least(self._estimate(plan)) for plan in plans)
        P = sum(1 for s in sigs if not s.negated)
        n_chain = len(_chain_order(P))
        chain_caps = tuple([self._join_cap_seed(plans, term_caps)] * n_chain)
        learned = self._learned_caps(
            self._exact_caps, self._exact_cap_store, sigs,
            (len(term_caps), len(chain_caps)),
        )
        if learned is not None:
            term_caps = tuple(max(a, b) for a, b in zip(term_caps, learned[0]))
            chain_caps = tuple(max(a, b) for a, b in zip(chain_caps, learned[1]))
        # the exact variant materializes every term (its suffix chains have
        # no index-join form); past ~1M-row terms the compile alone costs
        # minutes — the staged reference-order path owns that regime.  The
        # ceilings apply to MERGED caps (CapStore must not bypass them).
        if max(term_caps) > min(cfg.max_result_capacity, EXACT_TERM_CAP_LIMIT):
            return None
        if max(chain_caps, default=0) > cfg.max_result_capacity:
            return None

        while True:
            plan_sig = FusedExactSig(sigs, term_caps, chain_caps)
            entry = self._exact_cache.get((plan_sig, count_only))
            if entry is None:
                entry = build_fused_exact(plan_sig, count_only)
                self._exact_cache[(plan_sig, count_only)] = entry
            fn, names_per_state, cols_per_state = entry
            FETCH_COUNTS["n"] += 1
            if count_only:
                host_vals = host_valid = vals = valid = None
                stats = np.asarray(fn(arrays, keys, fvals))
            else:
                out = fn(arrays, keys, fvals)
                vals, valid, _ = out
                host_vals, host_valid, stats = jax.device_get(out)
            count, s_act = int(stats[0]), int(stats[1])
            ranges = stats[3 : 3 + len(sigs)]
            mtotals = stats[3 + len(sigs) :]
            new_tc = tuple(
                _pow2_at_least(int(r)) if int(r) > c else c
                for r, c in zip(ranges, term_caps)
            ) if ranges.size else term_caps
            new_cc = tuple(
                _pow2_at_least(int(t)) if int(t) > c else c
                for t, c in zip(mtotals, chain_caps)
            ) if mtotals.size else chain_caps
            if new_tc == term_caps and new_cc == chain_caps:
                break
            if max(new_tc + new_cc, default=0) > cfg.max_result_capacity:
                return None  # staged path clamps and owns overflow policy
            term_caps, chain_caps = new_tc, new_cc

        self._remember_exact_caps(sigs, term_caps, chain_caps)
        # project the full-K table onto the active state's bound columns so
        # var_names and value columns line up for materialization
        cols = list(cols_per_state[s_act])
        if vals is not None and cols != list(range(vals.shape[1])):
            vals = vals[:, np.asarray(cols)]
            host_vals = host_vals[:, cols]
        return FusedResult(
            var_names=names_per_state[s_act],
            vals=vals,
            valid=valid,
            count=count,
            reseed_needed=False,
            overflow=False,
            host_vals=host_vals,
            host_valid=host_valid,
        )

    # -- batched counting --------------------------------------------------

    def _run_batch_group(
        self, make_sig, cache, build, arrays,
        key_rows, fval_rows, n_terms, term_caps, caps,
    ):
        """Shared machinery for one lane-batched count group: dedup the
        members, stack-or-hoist their inputs (stack_lanes), compile/cache
        the (sig, axes) entry (lanes_program — what the served path's
        group programs are built from too), and retry
        with doubled capacities until no stage overflows.  Returns
        (stats or None, term_caps, caps); stats rows follow the common
        layout [count, flag, flag, *term_ranges, *stage_totals]."""
        cfg = self.db.config
        # dedup identical lanes: the miner's stochastic sampler redraws the
        # same grounded keys constantly — each unique row computes once and
        # fans back out below
        seen: Dict[Tuple, int] = {}
        back: List[int] = []
        uniq_keys, uniq_fvals = [], []
        for kr, fr in zip(key_rows, fval_rows):
            h = (
                tuple(np.asarray(k).tobytes() for k in kr),
                tuple(np.asarray(f).tobytes() for f in fr),
            )
            i = seen.get(h)
            if i is None:
                i = len(uniq_keys)
                seen[h] = i
                uniq_keys.append(kr)
                uniq_fvals.append(fr)
            back.append(i)
        # pad the lane count to a power of two: without padding every
        # distinct member count compiles a fresh program (the miner's
        # joint phase produced dozens)
        keys_stacked, key_axes, fvals_stacked, fval_axes = stack_lanes(
            uniq_keys, uniq_fvals, _pow2_at_least(len(uniq_keys), lo=1)
        )
        while True:
            plan_sig = make_sig(term_caps, caps)
            cache_key = (plan_sig, key_axes, fval_axes)
            record_dispatch("count")
            entry = cache.get(cache_key)
            if entry is None:
                entry = obs.proflog.instrument(
                    "count_batch",
                    obs.proflog.sig_digest(plan_sig, key_axes, fval_axes),
                    jax.jit(obs.named_program(
                        "das_count_batch",
                        lanes_program(build(plan_sig), key_axes, fval_axes),
                    )),
                )
                cache[cache_key] = entry
            # the shared RetryPolicy (das_tpu/fault, ISSUE 13) replaces
            # the old hard-coded retry-once for transient runtime
            # failures: bounded attempts, exponential
            # backoff with deterministic jitter — and every attempt is a
            # real device fetch, so each tallies FETCH_COUNTS (the
            # DL013-pinned per-attempt accounting)
            from das_tpu import fault

            def _count_fetch():
                FETCH_COUNTS["n"] += 1
                fault.maybe_fail("settle_fetch")
                return np.asarray(
                    entry(arrays, keys_stacked, fvals_stacked)
                )

            stats = fault.fetch_retry().run(_count_fetch)
            ranges = stats[:, 3 : 3 + n_terms]
            totals = stats[:, 3 + n_terms :]
            new_tc = tuple(
                _pow2_at_least(int(ranges[:, t].max())) if ranges[:, t].max() > c else c
                for t, c in enumerate(term_caps)
            )
            new_cc = tuple(
                _pow2_at_least(int(totals[:, j].max())) if totals.size and totals[:, j].max() > c else c
                for j, c in enumerate(caps)
            )
            if new_tc == term_caps and new_cc == caps:
                # fan unique-lane rows back out to the original members
                return stats[np.asarray(back)], term_caps, caps
            if max(new_tc + new_cc) > cfg.max_result_capacity:
                return None, term_caps, caps
            term_caps, caps = new_tc, new_cc

    @staticmethod
    def _structural_key(p):
        return (
            p.negated, p.arity, p.ctype is not None, p.type_id is None,
            tuple(pos for pos, _ in p.fixed), p.var_cols, p.eq_pairs,
        )

    def _count_order(self, plans):
        """Ordering for count-only batches.  When every positive term
        shares a common variable (the miner's composites all share V0),
        ANY order is join-connected, so sort by (SIZE CLASS, STRUCTURE)
        instead of the raw greedy estimate: lanes whose greedy orders
        differ would otherwise compile one program per permutation, but a
        purely structural sort can put a whole-table term before a
        grounded one — at FlyBase scale that turned the miner's joint
        phase into huge×huge first joins.  The size class is a coarse
        log16 bucket: selective terms still come first, and same-shape
        lanes whose estimates land in the same bucket share one compile
        (lanes straddling a fixed bucket boundary can still split).
        Queries without a common variable keep the greedy order."""
        pos = [p for p in plans if not p.negated]
        if len(pos) > 1:
            common = set(pos[0].var_names)
            for p in pos[1:]:
                common &= set(p.var_names)
            if common:
                neg = [p for p in plans if p.negated]
                return sorted(
                    pos,
                    key=lambda p: (
                        max(0, int(self._estimate(p)).bit_length() - 1) // 4,
                        self._structural_key(p),
                    ),
                ) + neg
        return self._order(plans)

    @staticmethod
    def _canonical_plans(plans):
        """Rename variables by first occurrence (X0, X1, …) so the batch
        signature depends on join STRUCTURE alone.  A match COUNT is
        invariant under variable renaming, but FusedTermSig.var_names is
        part of the compile key — without this the miner's generated names
        (V0, T0_V2, T1_V2, …) fragment otherwise-identical shapes into
        one compile each.  Count-only paths may use this; result-set paths
        must not (var_names reach the materialized assignments)."""
        import copy as _copy

        mapping: Dict[str, str] = {}
        out = []
        for p in plans:
            names = []
            for n in p.var_names:
                if n not in mapping:
                    mapping[n] = f"X{len(mapping)}"
                names.append(mapping[n])
            q = _copy.copy(p)
            q.var_names = tuple(names)
            out.append(q)
        return out

    def count_batch(self, plans_list) -> List[Optional[int]]:
        """Count many same-or-mixed-shape queries in as few dispatches as
        possible: plans are grouped by shape signature, each group runs as
        ONE vmapped fused program over the stacked grounded keys, and the
        whole group's counts come back in a single stats transfer.  This is
        the pattern-miner hot loop (SimplePatternMiner.ipynb cell 9: one
        Redis round trip per candidate in the reference; here ~one device
        round trip per *shape*).

        Entries that can't run fused (missing bucket) or that need the
        reference reseed quirk come back as None — the caller falls back to
        the staged/host path for those.
        """
        prepared = []  # (index, sigs, arrays, keys, fvals, ests)
        out: List[Optional[int]] = [None] * len(plans_list)
        groups: Dict[Tuple, List[int]] = {}
        # count-batch result cache (ROADMAP "result-cache scope"): the
        # miner's stochastic loop redraws the same joints across calls —
        # an answered (plan digest, count_only=True) entry under the same
        # delta version costs zero device work.  Keys use the ORIGINAL
        # plan tuples (grounded values included); the version captured
        # here guards the put against a commit racing the batch.
        cache_keys: Dict[int, Tuple] = {}
        cache_version = self.results.version()
        for idx, plans in enumerate(plans_list):
            n = trivial_plan_count(self.db, plans)
            if n is not None:
                out[idx] = n
                continue
            cache_keys[idx] = self.results.key(plans, True)
            hit = self.results.get(cache_keys[idx])
            if hit is not None:
                out[idx] = hit.count
                continue
            ordered = self._count_order(plans)
            same_order = self._same_positive_order(ordered, plans)
            mapped = [self._term_args(p) for p in self._canonical_plans(ordered)]
            if any(m is None for m in mapped):
                continue
            sigs = tuple(m[0] for m in mapped)
            prepared.append(
                (
                    idx,
                    sigs,
                    tuple(m[1] for m in mapped),
                    tuple(m[2] for m in mapped),
                    tuple(m[3] for m in mapped),
                    tuple(self._estimate(p) for p in ordered),
                    same_order,
                )
            )
            groups.setdefault(sigs, []).append(len(prepared) - 1)

        def _cache_count(idx: int, n: int) -> None:
            key = cache_keys.get(idx)
            if key is not None:
                self.results.put(
                    key,
                    FusedResult((), None, None, n, False, False),
                    cache_version,
                )

        cfg = self.db.config
        for sigs, members in groups.items():
            term_caps = tuple(
                _pow2_at_least(max(prepared[m][5][t] for m in members))
                for t in range(len(sigs))
            )
            index_joins, index_right, group_arrays, term_caps = (
                self._apply_index_joins(
                    sigs, prepared[members[0]][2], term_caps
                )
            )
            n_joins = max(0, sum(1 for s in sigs if not s.negated) - 1)
            join_cap0 = self._group_cap_seed(
                sigs, [prepared[m][5] for m in members]
            )
            join_caps = tuple([join_cap0] * n_joins)
            learned = self._learned_caps(
                self._caps, self._cap_store, sigs,
                (len(term_caps), len(join_caps)),
            )
            if learned is not None:
                term_caps = self._clamp_index_terms(
                    tuple(max(a, b) for a, b in zip(term_caps, learned[0])),
                    index_right,
                )
                join_caps = tuple(max(a, b) for a, b in zip(join_caps, learned[1]))
            # ceiling on MERGED caps (CapStore must not bypass it)
            if max(term_caps + join_caps, default=0) > cfg.max_result_capacity:
                continue  # caller's fallback handles the giant probes
            if max(term_caps, default=0) > LARGE_TERM_BATCH_LIMIT:
                # a vmapped group multiplies every padded buffer by the
                # lane count: whole-table terms run single-lane instead
                continue
            stats, term_caps, join_caps = self._run_batch_group(
                lambda tc, jc, _s=sigs, _ij=index_joins: FusedPlanSig(
                    _s, tc, jc, _ij
                ),
                self._batch_cache,
                lambda ps: build_fused(ps, count_only=True)[0],
                group_arrays,
                [prepared[m][3] for m in members],
                [prepared[m][4] for m in members],
                len(sigs), term_caps, join_caps,
            )
            if stats is None:
                continue
            self._remember_caps(sigs, term_caps, join_caps)
            n_positive = sum(1 for s in sigs if not s.negated)
            for row, m in zip(stats, members):
                count, reseed, pos_empty = int(row[0]), bool(row[1]), bool(row[2])
                same_order = prepared[m][6]
                if reseed or (
                    count == 0 and n_positive > 1 and not pos_empty and not same_order
                ):
                    continue  # greedy order can't decide — exact pass below
                out[prepared[m][0]] = count
                _cache_count(prepared[m][0], count)

        # exact second pass: entries the greedy program declined (possible
        # reseed) re-run as vmapped REFERENCE-ORDER programs with the
        # in-program reseed automaton — still ~one dispatch per shape group
        exact_groups: Dict[Tuple, List[Tuple]] = {}
        for idx, plans in enumerate(plans_list):
            if out[idx] is not None:
                continue
            mapped = [self._term_args(p) for p in self._canonical_plans(plans)]
            if any(m is None for m in mapped):
                continue  # missing bucket: host fallback handles
            sigs = tuple(m[0] for m in mapped)
            exact_groups.setdefault(sigs, []).append(
                (
                    idx,
                    tuple(m[1] for m in mapped),
                    tuple(m[2] for m in mapped),
                    tuple(m[3] for m in mapped),
                    tuple(self._estimate(p) for p in plans),
                )
            )
        for sigs, members in exact_groups.items():
            term_caps = tuple(
                _pow2_at_least(max(mm[4][t] for mm in members))
                for t in range(len(sigs))
            )
            P = sum(1 for s in sigs if not s.negated)
            cap0 = self._group_cap_seed(sigs, [mm[4] for mm in members])
            chain_caps = tuple([cap0] * len(_chain_order(P)))
            learned = self._learned_caps(
                self._exact_caps, self._exact_cap_store, sigs,
                (len(term_caps), len(chain_caps)),
            )
            if learned is not None:
                term_caps = tuple(max(a, b) for a, b in zip(term_caps, learned[0]))
                chain_caps = tuple(max(a, b) for a, b in zip(chain_caps, learned[1]))
            # ceilings on MERGED caps: whole-table terms (and CapStore
            # imports) stay out of the exact regime — staged path owns it
            if max(term_caps) > min(cfg.max_result_capacity, EXACT_TERM_CAP_LIMIT):
                continue
            if max(chain_caps, default=0) > cfg.max_result_capacity:
                continue
            stats, term_caps, chain_caps = self._run_batch_group(
                lambda tc, cc, _s=sigs: FusedExactSig(_s, tc, cc),
                self._exact_batch_cache,
                lambda ps: build_fused_exact(ps, count_only=True)[0],
                members[0][1],
                [mm[2] for mm in members],
                [mm[3] for mm in members],
                len(sigs), term_caps, chain_caps,
            )
            if stats is None:
                continue
            self._remember_exact_caps(sigs, term_caps, chain_caps)
            for row, mm in zip(stats, members):
                out[mm[0]] = int(row[0])
                _cache_count(mm[0], int(row[0]))
        return out
