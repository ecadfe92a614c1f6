"""Central registry of span/metric names (daslint DL014, ISSUE 12).

The `ops/counters.py` idiom applied to the trace/metric layer: every
span or instant-event name passed to `obs.span(...)` / `obs.event(...)`
/ `obs.annotation(...)`, every counter name passed to `obs.counter(...)`
and every histogram name passed to `obs.histogram(...)` anywhere in
`das_tpu/` must be a member of these tuples — the metric dicts
(obs/metrics.py COUNTERS / HISTOGRAMS) are BUILT from them, the
analyzer (das_tpu/analysis, rule DL014) pins every literal against them
in both directions (an undeclared literal fires; a declared name with
no call site is a stale entry on full-set runs), and tests/test_zobs.py
pins the tuples themselves so a rename cannot slip through unreviewed.

A typo'd name would otherwise trace into a lane nobody watches while
the dashboards / Perfetto queries keyed on the declared name stay
silent — the exact failure mode DL004 closed for the dispatch counters.

This module imports nothing — the recorder, the metric layer, the
exporters and the analyzer's fixtures can all depend on it without
cycles.
"""

#: every span ("X" complete event) and instant-event name the recorder
#: accepts.  Naming: `<layer>.<stage>` — the serving pipeline's
#: lifecycle stages (service/coalesce.py + api/atomspace.py), the
#: executor halves (query/fused.py + parallel/fused_sharded.py), the
#: delta-versioned caches, the commit path, and the planner's
#: statistics.  What is summed over a group rides its span as an attr
#: (`lock_wait_ms`, `resolve_ms`): an event per answered query is paid
#: for by the ONE worker thread, so a name recorded there once a query
#: has to have a reader (obs/export.py worker_account, a file under
#: benchmark/layer_metrics/, scripts/dump_trace.py).
SPAN_NAMES = (
    #: instant: one query accepted into the coalescer submit queue
    #: (service/coalesce.py submit); carries the trace id born at
    #: wire.query (born here only for a direct caller)
    "serve.submit",
    #: instant: backpressure rejection at the queue bound
    "serve.reject",
    #: span: one worker drain — attrs: width limit, queries drained
    "serve.drain",
    #: span: drained batch split into (tenant, format) groups
    "serve.group",
    #: span: per-group query planning (api/atomspace.py _QueryManyJob)
    "serve.plan",
    #: span: per-group device enqueue under the tenant lock — attrs:
    #: group width, speculative flag, effective depth, dispatch EWMA
    "serve.dispatch",
    #: span: per-group streamed settle — attrs: streamed/fallback
    #: counts, settle rtt, `lock_wait_ms` (summed waits for the tenant
    #: lock), `resolve_ms` (summed wall time of delivering the group's
    #: answers: `Future.set_result`, its callbacks, the serve.answer
    #: instant; no event per delivery)
    "serve.settle",
    #: span: queries going again after a commit overtook their round,
    #: or handed to the per-query dispatcher — `route="round"`: the
    #: construction of the re-run job (api/atomspace.py settle_iter;
    #: its serve.plan, exec.build, exec.dispatch are children; the
    #: re-run's fetch, verdicts and answers stream under their own
    #: names); `route="per_query"`: one blocking `das.query`
    #: (settle_iter's last loop, the coalescer's fall-back) — attrs:
    #: queries, route
    "serve.rerun",
    #: instant: one query's future resolved — closes the trace id
    #: opened at serve.submit
    "serve.answer",
    #: span: one device-program enqueue (query/fused.py _ExecJob and
    #: _TreeExecJob dispatch halves + the sharded twins) — attrs:
    #: route, rounds, planner est rows; `lanes` when the program is a
    #: group's (_GroupHooks.dispatch_group: the jobs it carries);
    #: `inflight`: programs enqueued and not yet fetched as this one
    #: is enqueued, itself not counted (query/fused.py
    #: programs_in_flight)
    "exec.dispatch",
    #: span: the build of ONE batch's jobs (query/fused.py
    #: FusedExecutor._build_jobs, inside serve.dispatch, before the
    #: batch's exec.dispatch spans): per query SHAPE a kept template,
    #: per query the planner's fold on the batch's statistics — attrs:
    #: queries (cache-missing, de-duplicated), shapes, templates_built.
    #: One per batch, never one per query
    "exec.build",
    #: span: one settle round's host transfer — a device-to-host sync
    #: (query/fused.py fetch_outputs, DL013's one-transfer site) —
    #: attrs: jobs, programs; `wait_ms`, the part of the duration in
    #: which the worker waited for the device before it copied (with
    #: tracing on it waits first, `jax.block_until_ready`, then
    #: copies); `inflight`, as on exec.dispatch, the round's own
    #: programs among them
    "exec.settle_fetch",
    #: span: the verdict of ONE settled job (query/fused.py
    #: settle_pending_iter, one chip and mesh): its lane taken out of
    #: the fetched block, the stats read, the result object built or
    #: the capacities grown, the result cache's insert — attrs: `done`
    #: (false = a capacity retry rides the next round), `lanes` of the
    #: program it rode in; where the job's fold holds a verified join,
    #: `pair_left_rows` / `pair_rows` (the counters join.pair_* below,
    #: this job's share); on a mesh job with exchange slots,
    #: `partitioned` (its verified joins that partition both sides) and
    #: `exchange_fill` (worst destination occupancy / slots, over its
    #: exchanging joins).  Closed BEFORE the answer is yielded: a span
    #: is never open across a `yield` (the consumer's spans would nest
    #: under it, and own time go to the wrong name)
    "exec.verdict",
    #: span: binding table -> the answer's block of distinct valid rows
    #: (query/compiler.py materialize) — attrs: rows, prefetched
    "exec.materialize",
    #: span: the block (or the assignments) -> the answer string of one
    #: query (api/atomspace.py _formatted) — attrs: rows, bytes
    "exec.format",
    #: instants: delta-versioned result/tree/count cache traffic
    #: (query/fused.py ResultCache)
    #: (a miss is counter `cache.misses` alone)
    "cache.hit",
    "cache.invalidate",
    #: instants: commit-path delta_version bumps (storage/delta.py) —
    #: incremental commit vs full rebuild
    "commit.delta",
    "commit.rebuild",
    #: spans: one incremental commit where the work happens
    #: (storage/delta.py _apply_delta) — commit.apply (attrs: version,
    #: nodes, links) holds commit.stage (intern + columnize + ENQUEUE
    #: of the device merges: host cost only), dur.wal_append when a WAL
    #: is armed, and commit.swap (the visible half); a failed stage
    #: records no commit.swap
    "commit.apply",
    "commit.stage",
    "commit.swap",
    #: span: planner statistics recomputed — the estimator's rebuild
    #: after delta_version moved (planner/stats.py estimator_for) and
    #: each uncached whole-table extraction (distinct_at,
    #: query/starcount.py _table_sparse) — attrs: version, rows
    "planner.stats",
    #: instant: one query expired past its serving deadline
    #: (service/coalesce.py, DasConfig.query_deadline_ms)
    "serve.deadline",
    #: instant: tenant circuit-breaker state transition — attrs: frm/to
    #: (das_tpu/fault CircuitBreaker; closed/open/half_open)
    "serve.breaker",
    #: instant: one injected fault fired at a FAULT_SITES seam
    #: (das_tpu/fault maybe_fail, ISSUE 13)
    "fault.inject",
    #: span: one XLA program compile observed by the program ledger
    #: (das_tpu/obs/proflog.py, ISSUE 14) — rendered in a dedicated
    #: "compile" Perfetto lane; attrs carry site/digest and whether the
    #: persistent XLA cache served it
    "prof.compile",
    #: span: one atomic generational snapshot write (storage/durable.py
    #: write_snapshot, ISSUE 15) — attrs: generation, delta_version
    "dur.snapshot",
    #: span: one warm-state restore — newest valid generation + WAL
    #: replay + warm bundle (storage/durable.py restore)
    "dur.restore",
    #: span: one write-ahead delta-log record — capture + pack + write
    #: + flush + fsync (storage/durable.py DeltaLog.append) — attrs:
    #: version, kind, framed bytes
    "dur.wal_append",
    #: instant: a torn WAL tail record truncated at the last valid
    #: frame boundary (storage/durable.py _truncate_wal)
    "dur.wal_truncate",
    #: span: one query RPC on its gRPC thread, parse to reply
    #: (service/server.py DasService.query) — the trace id is born
    #: here and rides the mark into coalescer.submit, so it is the id
    #: of serve.submit ... serve.answer too
    "wire.query",
    #: span: DSL text -> query AST (child of wire.query)
    "wire.parse",
    #: span: one settle round's pull of the mesh programs' per-shard
    #: result slabs and stats to the host (parallel/fused_sharded.py
    #: settle_many_iter; the same interval as exec.settle_fetch, which
    #: the mesh shares with one chip) — attrs: shards, bytes, and that
    #: span's own (jobs, programs, wait_ms, cpu_ms, inflight)
    "mesh.fetch",
    #: span: stacked per-shard rows -> the distinct valid rows of a mesh
    #: answer (parallel/sharded_db.py materialize; child of
    #: exec.materialize) — attrs: rows in, rows out
    "mesh.dedup",
)

#: monotone counters (obs/metrics.py COUNTERS is built from this)
COUNTER_NAMES = (
    "serve.submitted",
    "serve.answers",
    "serve.rejections",
    "serve.speculative",
    "cache.hits",
    "cache.misses",
    "cache.invalidations",
    "commit.deltas",
    "commit.rebuilds",
    "exec.dispatches",
    "exec.fetches",
    #: programs the shared dispatch loop enqueued (query/fused.py
    #: _dispatch_round: first rounds and capacity retries, one chip and
    #: mesh) and the jobs they carried: lanes / programs is how many
    #: same-signature queries of a coalesced group shared one program
    #: (1.0 where every job rides alone)
    "exec.group_programs",
    "exec.group_lanes",
    #: the job builder's per-shape templates (query/fused.py
    #: _JobTemplate): BUILT (a shape's first query of a delta_version)
    #: and jobs filled from a kept one.  Hit share = hits / (hits +
    #: builds): ~1 on a read-only store, lower by one build per served
    #: shape and commit where commits land
    "exec.template_builds",
    "exec.template_hits",
    #: how an answer left the executor: its HANDLE text printed from
    #: the block of distinct rows (api/atomspace.py _format_answer), or
    #: its block turned into frozen assignment objects because a
    #: consumer touched `answer.assignments` (query/ast.py
    #: PatternMatchingAnswer) — on the served HANDLE path the second
    #: stays 0
    "exec.answers_block",
    "exec.answers_objects",
    #: queries re-run because a commit overtook their dispatched round
    #: (api/atomspace.py settle_iter, `_stale()`): two or more go again
    #: as ONE round, a lone one (and what a second commit leaves of
    #: the re-run round) through the per-query dispatcher
    "exec.stale_reruns",
    #: every query the coalesced path hands to the per-query
    #: dispatcher `das.query`, whatever the cause: settle_iter's last
    #: loop and the coalescer's fall-back
    "exec.per_query_fallbacks",
    #: queries expired past their serving deadline (service/coalesce.py)
    "serve.deadline_misses",
    #: circuit-breaker trips CLOSED->OPEN / recoveries HALF_OPEN->CLOSED
    #: (das_tpu/fault CircuitBreaker)
    "serve.breaker_trips",
    "serve.breaker_recoveries",
    #: injected faults fired / retry attempts taken (das_tpu/fault
    #: maybe_fail + RetryPolicy — the attempt counters ISSUE 13 pins)
    "fault.injected",
    "fault.retries",
    #: XLA program compiles recorded by the program ledger (ISSUE 14)
    "prof.compiles",
    #: dasdur durability counters (ISSUE 15, storage/durable.py):
    #: snapshot generations written / WAL records appended+fsynced /
    #: WAL records replayed by restore()
    "dur.snapshots",
    "dur.wal_records",
    "dur.recovery_replayed",
    #: bytes the collectives of each dispatched fused mesh program move
    #: between chips, summed over the shards: tallied from the operand
    #: shapes when the program is traced (parallel/fused_sharded.py
    #: _moved), added per dispatch
    "mesh.collective_bytes",
    #: re-dispatches of a fused mesh program after a shard overflowed a
    #: capacity (a job's dispatch rounds past its first)
    "mesh.retries",
    #: settled mesh jobs whose fold holds at least one verified join
    #: that PARTITIONS both sides over the chips
    #: (parallel/fused_sharded.py pair_join_partitions; fed at the
    #: job's verdict, _ShardedExecJob.verdict_attrs)
    "mesh.partitioned_joins",
    #: slots all_gathered onto every shard as the LEFT side of a join
    #: into a whole-type term, from the gathered operand's shape when
    #: the program is traced, added per dispatch beside
    #: mesh.collective_bytes (a join that partitions adds none)
    "mesh.left_gathered_rows",
    #: a settled mesh job's exchanging joins (table joins and verified
    #: joins that hash-partition both sides): the worst destination's
    #: occupancy, from the stats the settle fetched anyway, and the
    #: slots a destination had; sums over jobs, their ratio is the fill
    "mesh.exchange_rows_max",
    "mesh.exchange_slots",
    #: answers the STAGED mesh pipeline gave because the fused mesh
    #: program declined — twin of ROUTE_COUNTS["staged"] on the mesh
    "mesh.staged_fallbacks",
    #: whole-table supports (query/starcount.py _table_sparse: the
    #: run-length pass over a link type's slice of the sorted key that
    #: the planner's exact join sizes and a table ⊙ table fold read)
    #: BUILT (each is one `planner.stats` span of what="table_sparse")
    #: and SERVED from the kept entry.  A read-only server shows the
    #: first at the count of its joined (type, position) pairs and
    #: never moving; a commit that swaps an arity's segments costs one
    #: more per joined table of that arity.  Hit share = hits / (hits +
    #: extractions)
    "planner.table_extractions",
    "planner.table_hits",
    #: the verified join (ops/join.py _pair_join_impl: a whole-type
    #: right side that shares two or more variables with the left),
    #: read from the settled job's own stats, no extra fetch
    #: (query/fused.py _ExecJob.verdict_attrs, one chip): rows OFFERED
    #: to it (the left side's row count) and rows KEPT (pairs that agree on every
    #: shared column: what its buffer is sized by); both also ride the
    #: job's exec.verdict span as attrs of the same names
    "join.pair_left_rows",
    "join.pair_rows",
    #: the posting-index join of ONE shared variable (ops/join.py
    #: _index_join_impl), fed beside the two above from the same stats:
    #: left rows OFFERED to it, and those of them whose ranges came
    #: from the slice search (ops/join.py index_search_method ==
    #: SLICE_SEARCH at the shapes the job's program was traced at: one
    #: 32-bit search inside the type's slice; the rest took the two
    #: 64-bit searches of the whole index)
    "join.index_probe_rows",
    "join.index_slice_rows",
)

#: fixed log-bucket latency histograms (obs/metrics.py HISTOGRAMS) —
#: p50/p95/p99 without sample retention; all record wall milliseconds
HISTOGRAM_NAMES = (
    #: submit -> group dispatch (queue + drain + grouping wait)
    "serve.queue_ms",
    #: per-group host-side dispatch cost (the window formula's divisor)
    "serve.dispatch_ms",
    #: per-group streamed settle wall time
    "serve.settle_ms",
    #: wait for one acquire of the tenant lock on the coalescer worker
    #: (dispatch, each settle step, each fall-back query)
    "serve.lock_wait_ms",
    #: submit -> answer delivery (the open-loop latency the bench
    #: derives its p50/p95/p99 headline from)
    "serve.answer_ms",
    #: one settle round's host transfer (the wire the adaptive window
    #: must hide)
    "exec.settle_fetch_ms",
    #: wall time of one XLA program compile (das_tpu/obs/proflog.py,
    #: ISSUE 14) — the compile-seconds histogram the Prometheus surface
    #: exports next to the ledger gauges
    "prof.compile_ms",
    #: wall time of one warm-state restore — snapshot verify + WAL
    #: replay + warm bundle (storage/durable.py restore, ISSUE 15):
    #: the replica-fleet cold-start figure
    "dur.restore_ms",
)

#: the `jax.named_scope`s a device-trace reader keys on (ops/join.py
#: whole_type_join): every operation of the verified join sits under
#: the first, every operation of the posting-index join of ONE shared
#: variable (its range lookup: two searches, or for a large left side
#: ONE and a read; the prefix sum; the expansion) under the second,
#: and NESTED in it the expansion (ops/join.py _expand_index_ranges:
#: ranges -> output rows) under the third and the large left side's
#: search proper (ops/join.py _search_words: the levels of separators
#: and the descent, a row gather a level; no loop carries its name any
#: more) under the fourth; benchmark/layer_metrics/ops.pair_join_*.py,
#: ops.index_join_ms_per_query.py, ops.index_expand_ms_per_query.py
#: and ops.index_search_ms_per_query.py sum the device time of the
#: operations whose scope path holds the name
PAIR_JOIN_SCOPE = "join.pair_verify"
INDEX_JOIN_SCOPE = "join.index_probe"
INDEX_EXPAND_SCOPE = "join.index_expand"
INDEX_SEARCH_SCOPE = "join.index_search"
#: the mesh's verified join that partitions both sides
#: (parallel/fused_sharded.py): the whole step, both exchanges (each
#: collective in `mesh.repartition`) and the local verify (still under
#: PAIR_JOIN_SCOPE inside it); benchmark/layer_metrics/
#: mesh.partition_join_*.py read it
PAIR_PARTITION_SCOPE = "mesh.pair_partition"

#: module names of the jitted device programs on the served and commit
#: paths, as a device trace shows them (`jit_<name>` on the XLA Modules
#: line): `obs.named_program("<name>", fn)` sets the traced function's
#: `__name__` before `jax.jit`, count-only variants append `_count`.
#: The whole-plan builders are `das_<ledger site>` (obs/proflog.py
#: PROGRAM_SITES); the commit programs are `das_merge*` / `das_insert*`.
#: daslint DL014 pins the literals both ways, like the span names.
PROGRAM_NAMES = (
    "das_fused",
    "das_fused_group",
    "das_fused_tree",
    "das_fused_exact",
    "das_count_batch",
    "das_sharded",
    "das_sharded_group",
    "das_sharded_tree",
    "das_merge_padded",
    "das_insert_rows",
    "das_merge_sharded",
)
