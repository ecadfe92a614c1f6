"""`DistributedAtomSpace` — the public API facade.

Method-for-method parity with the reference facade
(/root/reference/das/distributed_atom_space.py:26-414): get_node/get_nodes/
get_link/get_links/get_atom, query, count_atoms, clear_database,
open/commit_transaction, load_knowledge_base, load_canonical_knowledge_base,
plus `QueryOutputFormat`.  Differences are all backend-side: instead of
Mongo+Redis connections resolved from env vars, construction picks an
in-process backend ("memory" | "tensor" | "sharded") and `query()`
transparently routes compilable conjunctive queries through the device
pipeline (das_tpu/query/compiler.py), falling back to the host algebra.

One reference bug not reproduced: query(output_format=ATOM_INFO/JSON)
iterated `assignments.items()` on a set and crashed
(distributed_atom_space.py:311-318); here those formats render each
assignment's variable→atom mapping.
"""

from __future__ import annotations

import json
from enum import Enum, auto
from typing import Dict, List, Optional, Tuple, Union

from das_tpu import obs
from das_tpu.core.config import DasConfig
from das_tpu.core.exceptions import BreakerOpenError
from das_tpu.core.schema import UNORDERED_LINK_TYPES, WILDCARD
from das_tpu.query import compiler as query_compiler
from das_tpu.query.ast import LogicalExpression, PatternMatchingAnswer
from das_tpu.storage.atom_table import AtomSpaceData
from das_tpu.storage.memory_db import MemoryDB
from das_tpu.storage.tensor_db import TensorDB
from das_tpu.utils.logger import logger


class QueryOutputFormat(int, Enum):
    HANDLE = auto()
    ATOM_INFO = auto()
    JSON = auto()


class Transaction:
    """Buffer of toplevel MeTTa expression strings for incremental commit
    (role of /root/reference/das/transaction.py:1-10)."""

    def __init__(self):
        self.expressions: List[str] = []

    def add(self, expression: str) -> None:
        self.expressions.append(expression)

    # reference spelling (transaction.py:6-7) — same operation
    add_toplevel_expression = add

    def metta_string(self) -> str:
        return "\n".join(self.expressions)


class _QueryManyJob:
    """One coalesced batch mid-pipeline: planning and the asynchronous
    device dispatch happen at construction (query_many_dispatch); settle()
    pays the host transfer and materializes.  Queries the fused path
    cannot take (not compilable, missing bucket, capacity ceiling) resolve
    through the per-query dispatcher during settle — the pipeline degrades
    to the serial path for exactly those entries, never for the batch."""

    __slots__ = ("das", "queries", "output_format", "plans_lists", "idxs",
                 "pending", "db_ref", "version", "sharded", "settle_rtt_ms",
                 "cache_only", "stale_round", "is_rerun")

    def __init__(self, das, queries, output_format, cache_only=False,
                 is_rerun=False):
        self.das = das
        self.queries = queries
        self.output_format = output_format
        # this job IS the second go of a round a commit overtook
        # (settle_iter): should a commit overtake it too, what is left
        # re-runs one by one and not as a third round
        self.is_rerun = is_rerun
        # degraded-mode serving (ISSUE 13, the coalescer's open circuit
        # breaker): answer from the delta-versioned result cache ONLY —
        # no device dispatch, no staged fallback, no per-query re-run;
        # entries the cache cannot answer yield a typed, retryable
        # BreakerOpenError instead
        self.cache_only = cache_only
        # a commit overtook the dispatched round (dropped whole at
        # settle, or broken mid-stream): its unanswered queries re-run,
        # counted as `exec.stale_reruns`
        self.stale_round = False
        self.plans_lists: List = []
        self.idxs: List[int] = []
        self.pending = None
        # the streamed round's first host-transfer duration (fused.py
        # _PendingMany.fetch_ms[0]) — the settle round-trip, set once
        # settle_iter's fused/sharded branch finishes streaming; None
        # when no fetch happened (all hits, all declined, commit race),
        # so the coalescer's window estimator is fed ONLY real wire time
        self.settle_rtt_ms = None
        # mesh tenants take the sharded executor's dispatch/settle halves
        # (parallel/fused_sharded.py) — same pipeline shape, shard_map
        # programs instead of single-device fused ones
        self.sharded = hasattr(das.db, "query_sharded")
        # the store (by identity — clear_database swaps the backend and a
        # fresh one restarts the counter) and commit version this batch
        # planned/dispatched against: a commit landing before settle()
        # may re-intern global row ids (a FULL re-finalize moves every
        # link row), so settle must not materialize this snapshot's
        # tables through the new registries
        self.db_ref = das.db
        self.version = getattr(das.db, "delta_version", None)
        if (hasattr(das.db, "dev") or self.sharded) and queries:
            with obs.span("serve.plan", queries=len(queries)) as sp:
                for i, q in enumerate(queries):
                    plans = query_compiler.plan_query(das.db, q)
                    if plans is not None:
                        self.plans_lists.append(plans)
                        self.idxs.append(i)
                sp.set(compilable=len(self.plans_lists))
            if self.plans_lists:
                dispatch = (
                    query_compiler.execute_sharded_many_dispatch
                    if self.sharded
                    else query_compiler.execute_fused_many_dispatch
                )
                self.pending = dispatch(
                    das.db, self.plans_lists, cache_only=cache_only
                )

    def _stale(self) -> bool:
        """True when the dispatched round's row ids and plans no longer
        describe the live store: the backend was swapped, or a commit
        bumped delta_version past the one captured at dispatch."""
        db = self.das.db
        return (db is not self.db_ref
                or getattr(db, "delta_version", None) != self.version)

    def _stream_settled(self, pending, settle_iter_fn, answer_fn):
        """The correctness-critical streaming scaffold, shared by the
        sharded and fused settle branches so its ORDERING exists once:
        (1) record the settle round-trip EAGERLY at the first post-fetch
        yield (fused.py `_PendingMany.fetch_ms`) — a later mid-stream
        failure must not drop the genuine wire sample, or the
        coalescer's estimator would hold a failing tenant at the floor
        forever; (2) re-check the dispatch-time delta_version guard PER
        YIELD — streaming paces settle to the CONSUMER, so a commit
        landing between yields invalidates every not-yet-materialized
        entry (already-yielded answers were consistent when delivered):
        abandon the round, settle_iter's tail re-runs the rest on
        the post-commit store; (3) materialize/format via
        `answer_fn(j, result)`, a failure degrading that entry (and
        only it) to the per-query dispatcher.  Yields
        `(query index, formatted answer)`."""
        for j, res in settle_iter_fn(
            self.das.db, self.plans_lists, pending
        ):
            if self.settle_rtt_ms is None and pending.fetch_ms:
                self.settle_rtt_ms = pending.fetch_ms[0]
            if self._stale():
                self.stale_round = True
                break
            try:
                out_s = answer_fn(j, res)
            except Exception:  # noqa: BLE001 — e.g. CapacityOverflow:
                continue       # per-query dispatcher takes this entry
            yield self.idxs[j], out_s

    def settle_iter(self):
        """Streaming settle (ISSUE 6 early-settle): yields
        `(query index, answer-or-Exception)` as each answer becomes
        FINAL, instead of blocking until the whole group settles and
        materializes.  Fused-settled entries stream first, in
        verdict-arrival order — a query whose first retry round fit is
        materialized and yielded while its batch-mates are still
        settling, so its first rows reach the client one RTT after its
        own dispatch.  A settle-time decline replays on the staged path
        IN verdict order (its slot in the stream pays the replay
        inline); dispatch-time declines and non-compilable queries
        (per-query dispatcher) follow after the stream; a failed entry
        yields its OWN exception, never a batch-mate's.  Every
        index is yielded exactly once; settle() is the drain-to-list
        form.  The dispatch-time delta_version guard is re-checked per
        yield, not just once up front: streaming paces settle to the
        CONSUMER, so a commit can land between yields — when it does,
        the not-yet-materialized remainder re-runs on the post-commit
        store, as one new round (a lone query, and what a second
        commit leaves of that round, through the per-query
        dispatcher)."""
        das = self.das
        done = [False] * len(self.queries)
        if self.pending is not None and self._stale():
            # a commit raced in between dispatch and settle: drop the
            # dispatched round wholesale (its row ids and plans belong to
            # the pre-commit store) and re-run everything on
            # the post-commit store — correctness over the saved
            # transfer.  This is the guard that keeps SPECULATIVE
            # dispatch (a group dispatched before earlier settles
            # landed, service/coalesce.py) sound: however deep the
            # window ran, each group re-checks its dispatch-time version
            # here before materializing anything.
            self.pending = None
            self.stale_round = True
        if self.pending is not None and self.sharded:
            from das_tpu.parallel.sharded_db import ShardedTable

            pending, self.pending = self.pending, None

            def sharded_answer(j, res):
                if res is None:
                    if self.cache_only:
                        # degraded mode: a cache miss must not run the
                        # staged mesh pipeline — degrade this entry to
                        # the typed rejection (the final loop below)
                        raise BreakerOpenError()
                    # fused mesh declined (ceiling/reseed): the staged
                    # mesh pipeline answers — answer-identical, same
                    # fallback _run_conjunctive takes
                    table = das.db.sharded_execute(self.plans_lists[j])
                else:
                    # materialized from the prefetched host copies:
                    # the device references stay unread (an answer
                    # that rode in a group program would slice its
                    # lane out on the read)
                    prefetched = res.host_vals is not None
                    table = ShardedTable(
                        res.var_names,
                        None if prefetched else res.vals,
                        None if prefetched else res.valid,
                        res.count,
                        host_vals=res.host_vals,
                        host_valid=res.host_valid,
                    )
                answer = PatternMatchingAnswer()
                matched = das.db.materialize(table, answer)
                out_s = das._formatted(
                    matched, answer, self.output_format
                )
                # counted once the answer exists, as what it is: an
                # answer of the staged mesh pipeline is "staged" here
                # as on one chip (fused_answer below), so a limit on
                # staged answers holds the mesh too; its twin under
                # tracing is counter mesh.staged_fallbacks
                if res is None:
                    query_compiler.ROUTE_COUNTS["staged"] += 1
                else:
                    query_compiler.ROUTE_COUNTS["sharded"] += 1
                return out_s

            settled = self._stream_settled(
                pending,
                query_compiler.execute_sharded_many_settle_iter,
                sharded_answer,
            )
            # the stream owns the round now: where it gives the round
            # up (a commit between two yields), the round goes with it
            del pending
            for i, out_s in settled:
                done[i] = True
                yield i, out_s
        elif self.pending is not None:
            pending, self.pending = self.pending, None

            def fused_answer(j, table):
                route = "fused"
                if table is None:
                    if self.cache_only:
                        # degraded mode: no staged replay for a cache
                        # miss — the final loop rejects it typed
                        raise BreakerOpenError()
                    # fused declined (ceiling/reseed): go straight to
                    # the answer-identical staged path — re-trying the
                    # fused program via query() would just rediscover
                    # the decline at the cost of another dispatch
                    table = query_compiler.execute_plan(
                        das.db, self.plans_lists[j]
                    )
                    route = "staged"
                answer = PatternMatchingAnswer()
                matched = query_compiler.materialize(das.db, table, answer)
                out_s = das._formatted(
                    matched, answer, self.output_format
                )
                # counted only once the answer exists: a failure re-runs
                # via query(), which counts its own route — incrementing
                # earlier would double-count
                query_compiler.ROUTE_COUNTS[route] += 1
                return out_s

            settled = self._stream_settled(
                pending,
                query_compiler.execute_fused_many_settle_iter,
                fused_answer,
            )
            del pending             # as above: the stream owns it
            for i, out_s in settled:
                done[i] = True
                yield i, out_s
        rest = [i for i in range(len(self.queries)) if not done[i]]
        if (self.stale_round and len(rest) > 1 and not self.is_rerun
                and not self.cache_only):
            # what the commit left unanswered goes again as ONE round on
            # the post-commit store — one plan pass, one program a
            # signature, one fetch — where the per-query dispatcher
            # below pays a lone program and a blocking fetch per query.
            # (The answer path of PR 32 made this visible: with a
            # group's last large answers out in a millisecond, a commit
            # paced by reads lands just after the NEXT dispatch and
            # finds a whole round stale.)
            if obs.enabled():
                planned = set(self.idxs)
                obs.counter("exec.stale_reruns").inc(
                    sum(1 for i in rest if i in planned))
            # serve.rerun names the second round's plan, build and
            # enqueue; closed before the first yield (its fetch,
            # verdicts and answers have their own spans)
            with obs.span("serve.rerun", queries=len(rest), route="round"):
                again = _QueryManyJob(
                    das, [self.queries[i] for i in rest],
                    self.output_format, is_rerun=True)
            for j, out_s in again.settle_iter():
                yield rest[j], out_s
            return
        for i in rest:
            q = self.queries[i]
            if self.cache_only:
                # degraded-mode contract: cache hits streamed above,
                # everything else is rejected RETRYABLE — fresh device
                # dispatches are what the open breaker exists to stop
                # (the coalescer stamps the retry-after hint)
                yield i, BreakerOpenError()
                continue
            if obs.enabled():
                obs.counter("exec.per_query_fallbacks").inc()
                if self.stale_round and i in self.idxs:
                    obs.counter("exec.stale_reruns").inc()
            try:
                with obs.span("serve.rerun", queries=1, route="per_query"):
                    out_s = das.query(q, self.output_format)
            except Exception as exc:  # noqa: BLE001 — per-query isolation
                out_s = exc
            yield i, out_s

    def settle(self) -> List[Union[str, Exception]]:
        """One entry per query: the answer string, or that query's OWN
        exception — a failure never leaks onto a batch-mate (the coalescer
        maps Exception entries to their individual futures).  Drains
        settle_iter; use the iterator directly for streaming delivery."""
        out: List[Union[str, Exception]] = [None] * len(self.queries)
        for i, answer in self.settle_iter():
            out[i] = answer
        return out


class DistributedAtomSpace:
    def __init__(self, **kwargs):
        self.database_name = kwargs.get("database_name", "das")
        self.config: DasConfig = kwargs.get("config") or DasConfig.from_env()
        backend = kwargs.get("backend", self.config.backend)
        self.config.backend = backend
        db = kwargs.get("db")
        if db is not None:
            # wrap an existing backend (service tenants attached to an
            # already-built store — bench/tests; skips checkpoint load
            # and re-upload entirely)
            self.data = db.data
            self.db = db
            self.pattern_black_list = list(self.config.pattern_black_list)
            logger().info(
                f"New Distributed Atom Space '{self.database_name}' "
                f"(attached backend {type(db).__name__})"
            )
            return
        data = kwargs.get("data")
        if (
            data is None
            and self.config.snapshot_dir
            and backend in ("tensor", "sharded")
        ):
            # dasdur warm restore (ISSUE 15): a bare DistributedAtomSpace()
            # with a populated snapshot root comes up from the newest
            # VALID generation + WAL replay + warm bundle — the
            # replica-fleet cold start in seconds instead of minutes —
            # and keeps appending commits to the generation's WAL
            from das_tpu.storage import durable

            if durable.list_generations(self._snapshot_root()):
                self.db = durable.restore(
                    self._snapshot_root(), config=self.config,
                    backend=backend,
                )
                self.data = self.db.data
                self.pattern_black_list = list(
                    self.config.pattern_black_list
                )
                logger().info(
                    f"New Distributed Atom Space '{self.database_name}' "
                    f"(backend={backend}, restored from "
                    f"{self.config.snapshot_dir})"
                )
                return
        if data is None and self.config.checkpoint_path:
            import os

            from das_tpu.storage import checkpoint

            if os.path.isdir(self.config.checkpoint_path):
                data = checkpoint.load(self.config.checkpoint_path)
            else:
                # reference-analogous behavior: env-var endpoints with no
                # data behind them attach to an empty store (and a server's
                # create RPC must not die on a tenant construction error)
                logger().warning(
                    "DAS_TPU_CHECKPOINT path "
                    f"'{self.config.checkpoint_path}' does not exist; "
                    "starting with an empty AtomSpace"
                )
        self.data = data or AtomSpaceData()
        self.db = self._make_backend(backend)
        self.pattern_black_list = list(self.config.pattern_black_list)
        if self.config.snapshot_dir and backend in ("tensor", "sharded"):
            # fresh store under a durability root: write generation 1
            # (the WAL needs a base to replay onto) and arm the delta log
            from das_tpu.storage import durable

            durable.attach(self.db, self._snapshot_root(), self.config)
        logger().info(
            f"New Distributed Atom Space '{self.database_name}' "
            f"(backend={backend})"
        )

    def _snapshot_root(self) -> Optional[str]:
        """This AtomSpace's durability root: `snapshot_dir` NAMESPACED by
        database_name.  One generation lineage holds exactly ONE store's
        history — a shared DAS_TPU_SNAPSHOT_DIR across service tenants
        must not let tenant B restore tenant A's atoms or interleave two
        delta_version sequences into one WAL (replay would fail its
        continuity check and brick the root).  Backend-level callers
        (`TensorDB.restore(path)`) address a lineage dir directly."""
        import os

        if not self.config.snapshot_dir:
            return None
        return os.path.join(self.config.snapshot_dir, self.database_name)

    def _make_backend(self, backend: str):
        if backend == "memory":
            return MemoryDB(self.data)
        if backend == "tensor":
            return TensorDB(self.data, self.config)
        if backend == "sharded":
            from das_tpu.parallel.sharded_db import ShardedDB

            return ShardedDB(self.data, self.config)
        raise ValueError(f"Unknown backend: {backend}")

    def _get_file_list(self, source: str) -> List[str]:
        """Knowledge-base path expansion (reference
        distributed_atom_space.py:81-99; its own test suite probes this
        name directly, so it is part of the compat surface)."""
        from das_tpu.ingest.pipeline import knowledge_base_file_list

        return knowledge_base_file_list(source)

    def _refresh(self) -> None:
        if hasattr(self.db, "refresh"):
            self.db.refresh()
        else:
            self.db.prefetch()

    @property
    def pattern_black_list(self) -> List[str]:
        """Lives on the AtomSpaceData so every backend and planner reads the
        same list; assignment writes through (no aliasing to de-sync)."""
        return self.data.pattern_black_list

    @pattern_black_list.setter
    def pattern_black_list(self, value: List[str]) -> None:
        self.data.pattern_black_list = list(value)

    # -- public API --------------------------------------------------------

    def clear_database(self) -> None:
        black_list = self.pattern_black_list
        self.data = AtomSpaceData()
        self.data.pattern_black_list = black_list
        self.db = self._make_backend(self.config.backend)
        if self.config.snapshot_dir and self.config.backend in (
            "tensor", "sharded",
        ):
            # a durable tenant's clear IS a state change: persist the
            # empty store as a NEW generation (re-attaching the old
            # generation's WAL to a fresh backend would break replay's
            # delta_version continuity)
            from das_tpu.storage import durable

            durable.write_snapshot(self.db, self._snapshot_root())

    def count_atoms(self) -> Tuple[int, int]:
        return self.db.count_atoms()

    def get_atom(
        self, handle: str, output_format: QueryOutputFormat = QueryOutputFormat.HANDLE
    ) -> Union[str, Dict]:
        if output_format == QueryOutputFormat.HANDLE or not handle:
            atom = self.db.get_atom_as_dict(handle)
            return atom["handle"] if atom else ""
        if output_format == QueryOutputFormat.ATOM_INFO:
            return self.db.get_atom_as_dict(handle)
        if output_format == QueryOutputFormat.JSON:
            answer = self.db.get_atom_as_deep_representation(handle)
            return json.dumps(answer, sort_keys=False, indent=4)
        raise ValueError(f"Invalid output format: '{output_format}'")

    def get_node(
        self,
        node_type: str,
        node_name: str,
        output_format: QueryOutputFormat = QueryOutputFormat.HANDLE,
    ) -> Union[str, Dict, None]:
        node_handle = self.db.get_node_handle(node_type, node_name)
        if not self.db.node_exists(node_type, node_name):
            logger().warning(
                f"Attempt to access an invalid Node '{node_type}:{node_name}'"
            )
            return None
        if output_format == QueryOutputFormat.HANDLE:
            return node_handle
        if output_format == QueryOutputFormat.ATOM_INFO:
            return self.db.get_atom_as_dict(node_handle)
        if output_format == QueryOutputFormat.JSON:
            answer = self.db.get_atom_as_deep_representation(node_handle)
            return json.dumps(answer, sort_keys=False, indent=4)
        raise ValueError(f"Invalid output format: '{output_format}'")

    def get_nodes(
        self,
        node_type: str,
        node_name: Optional[str] = None,
        output_format: QueryOutputFormat = QueryOutputFormat.HANDLE,
    ) -> Union[List[str], List[Dict], str]:
        if node_name is not None:
            handle = self.db.get_node_handle(node_type, node_name)
            answer = [handle] if self.db.node_exists(node_type, node_name) else []
        else:
            answer = self.db.get_all_nodes(node_type)
        if output_format == QueryOutputFormat.HANDLE or not answer:
            return answer
        if output_format == QueryOutputFormat.ATOM_INFO:
            return [self.db.get_atom_as_dict(h) for h in answer]
        if output_format == QueryOutputFormat.JSON:
            deep = [self.db.get_atom_as_deep_representation(h) for h in answer]
            return json.dumps(deep, sort_keys=False, indent=4)
        raise ValueError(f"Invalid output format: '{output_format}'")

    def get_link(
        self,
        link_type: str,
        targets: Optional[List[str]] = None,
        output_format: QueryOutputFormat = QueryOutputFormat.HANDLE,
    ) -> Union[str, Dict, None]:
        link_handle = self.db.get_link_handle(link_type, targets or [])
        if not self.db.link_exists(link_type, targets or []):
            return None
        if output_format == QueryOutputFormat.HANDLE:
            return link_handle
        if output_format == QueryOutputFormat.ATOM_INFO:
            return self.db.get_atom_as_dict(link_handle, len(targets or []))
        if output_format == QueryOutputFormat.JSON:
            answer = self.db.get_atom_as_deep_representation(
                link_handle, len(targets or [])
            )
            return json.dumps(answer, sort_keys=False, indent=4)
        raise ValueError(f"Invalid output format: '{output_format}'")

    def _to_handle_list(self, db_answer) -> List[str]:
        if not db_answer:
            return []
        return [
            atom if isinstance(atom, str) else atom[0] for atom in db_answer
        ]

    def _to_link_dict_list(self, db_answer) -> List[Dict]:
        answer = []
        for atom in db_answer or []:
            if isinstance(atom, str):
                handle, arity = atom, -1
            else:
                handle, targets = atom
                arity = len(targets)
            answer.append(self.db.get_atom_as_dict(handle, arity))
        return answer

    def _to_json(self, db_answer) -> str:
        answer = []
        for atom in db_answer or []:
            if isinstance(atom, str):
                handle, arity = atom, -1
            else:
                handle, targets = atom
                arity = len(targets)
            answer.append(self.db.get_atom_as_deep_representation(handle, arity))
        return json.dumps(answer, sort_keys=False, indent=4)

    def get_links(
        self,
        link_type: str,
        target_types: Optional[List[str]] = None,
        targets: Optional[List[str]] = None,
        output_format: QueryOutputFormat = QueryOutputFormat.HANDLE,
    ) -> Union[List[str], List[Dict], str]:
        if link_type is None:
            link_type = WILDCARD
        if target_types is not None and link_type != WILDCARD:
            db_answer = self.db.get_matched_type_template([link_type, *target_types])
        elif targets is not None:
            if link_type in UNORDERED_LINK_TYPES and WILDCARD in targets:
                # Production-DB semantics for an unordered wildcard probe
                # (reference redis_mongo_db.py:249-252 over the ingest keys
                # of parser_threads.py:188-218): the probe key hashes the
                # SORTED handles while ingest emits keys in STORED order,
                # so the probe matches POSITIONALLY against the sorted
                # probe tuple.  The engine keeps the reference StubDB's
                # multiset semantics (stub_db.py:129-146, differentially
                # verified); that probe is a superset, filtered down here.
                probe = sorted(targets)
                db_answer = [
                    m
                    for m in self.db.get_matched_links(link_type, probe)
                    if all(
                        p == WILDCARD or p == t for p, t in zip(probe, m[1])
                    )
                ]
            else:
                db_answer = self.db.get_matched_links(link_type, targets)
        elif link_type != WILDCARD:
            db_answer = self.db.get_matched_type(link_type)
        else:
            raise ValueError("Invalid parameters")
        if output_format == QueryOutputFormat.HANDLE:
            return self._to_handle_list(db_answer)
        if output_format == QueryOutputFormat.ATOM_INFO:
            return self._to_link_dict_list(db_answer)
        if output_format == QueryOutputFormat.JSON:
            return self._to_json(db_answer)
        raise ValueError(f"Invalid output format: '{output_format}'")

    def get_link_type(self, link_handle: str) -> str:
        return self.db.get_link_type(link_handle)

    def get_link_targets(self, link_handle: str) -> List[str]:
        return self.db.get_link_targets(link_handle)

    def get_node_type(self, node_handle: str) -> str:
        return self.db.get_node_type(node_handle)

    def get_node_name(self, node_handle: str) -> str:
        return self.db.get_node_name(node_handle)

    # -- query -------------------------------------------------------------

    def _render_assignment(self, assignment, deep: bool):
        get = (
            self.db.get_atom_as_deep_representation
            if deep
            else self.db.get_atom_as_dict
        )
        if hasattr(assignment, "mapping"):
            return {var: get(h) for var, h in assignment.mapping.items()}
        return repr(assignment)

    def _dispatch_query(self, query: LogicalExpression, answer: PatternMatchingAnswer):
        """Route compilable queries to the device/mesh pipeline, fall back
        to the host algebra otherwise — including when a join legitimately
        exceeds max_result_capacity (a valid query must degrade to the
        host algebra, never crash the API).  Routing lives in
        query_compiler.dispatch so the reference-compat shim shares it."""
        return query_compiler.dispatch(self.db, query, answer)

    def query(
        self,
        query: LogicalExpression,
        output_format: QueryOutputFormat = QueryOutputFormat.HANDLE,
    ) -> str:
        answer = PatternMatchingAnswer()
        matched = self._dispatch_query(query, answer)
        return self._formatted(matched, answer, output_format)

    def query_many(
        self,
        queries: List[LogicalExpression],
        output_format: QueryOutputFormat = QueryOutputFormat.HANDLE,
    ) -> List[str]:
        """Batched `query`: fused-compilable queries on a device backend
        dispatch together and pay ONE host transfer per retry round (the
        serving coalescer's path — each separate fetch is a host sync
        that waits for the device); everything else falls back to the per-query dispatcher.
        Output strings are identical to query()'s."""
        if len(queries) <= 1:
            return [self.query(q, output_format) for q in queries]
        answers = self.query_many_dispatch(queries, output_format).settle()
        for a in answers:
            if isinstance(a, Exception):
                raise a
        return answers

    def query_many_dispatch(
        self,
        queries: List[LogicalExpression],
        output_format: QueryOutputFormat = QueryOutputFormat.HANDLE,
        cache_only: bool = False,
    ) -> "_QueryManyJob":
        """Pipeline half of query_many, for the serving coalescer
        (service/coalesce.py): plan the batch and ENQUEUE its fused device
        programs (async, result-cache aware), returning a job whose
        `.settle()` pays the host transfer, materializes, and resolves
        fallbacks.  Between dispatch and settle the device executes this
        batch while the caller settles the previous one — the bounded
        in-flight pipeline that keeps the device queue full under load.
        settle() returns one entry per query: the formatted answer string,
        or the query's OWN Exception (never a batch-mate's).  cache_only
        is degraded-mode serving (ISSUE 13, open circuit breaker): cache
        hits answer with zero device work, everything else resolves to a
        typed retryable BreakerOpenError."""
        return _QueryManyJob(self, queries, output_format,
                             cache_only=cache_only)

    def _formatted(
        self, matched, answer: PatternMatchingAnswer, output_format
    ) -> str:
        """`_format_answer` under span `exec.format` (attrs: rows,
        bytes) — the per-query answer path's last host stage.  With
        tracing off: the bare call."""
        if not obs.enabled():
            return self._format_answer(matched, answer, output_format)
        with obs.span("exec.format", rows=answer.row_count()) as sp:
            out = self._format_answer(matched, answer, output_format)
            sp.set(bytes=len(out))
        return out

    def _format_answer(
        self, matched, answer: PatternMatchingAnswer, output_format
    ) -> str:
        tag_not = ""
        mapping = ""
        if matched:
            if answer.negation:
                tag_not = "NOT "
            if output_format == QueryOutputFormat.HANDLE:
                # the block goes out as text while nobody has turned
                # it into objects (query/ast.py AnswerBlock)
                block = answer.block
                if block is not None:
                    mapping = block.handle_text()
                    if obs.enabled():
                        obs.counter("exec.answers_block").inc()
                else:
                    mapping = str(answer.assignments)
            elif output_format == QueryOutputFormat.ATOM_INFO:
                mapping = str(
                    [self._render_assignment(a, deep=False) for a in answer.assignments]
                )
            elif output_format == QueryOutputFormat.JSON:
                mapping = json.dumps(
                    [self._render_assignment(a, deep=True) for a in answer.assignments],
                    sort_keys=False,
                    indent=4,
                )
            else:
                raise ValueError(f"Invalid output format: '{output_format}'")
        return f"{tag_not}{mapping}"

    def query_answer(self, query: LogicalExpression) -> Tuple[bool, PatternMatchingAnswer]:
        """Structured query result (assignment objects, not strings)."""
        answer = PatternMatchingAnswer()
        matched = self._dispatch_query(query, answer)
        return bool(matched), answer

    def explain(self, query: LogicalExpression, execute: bool = False,
                compile: bool = False) -> Dict:
        """Costed-plan explain (das_tpu/planner, ISSUE 8): the planner's
        decision for `query` — chosen join order, expected route (an
        ops/counters.py ROUTE_KEYS member), estimated per-term and
        per-join rows, and the capacity seeds — without dispatching
        anything.  With execute=True the query also RUNS through the
        executor's real dispatch/settle halves and the actual per-stage
        rows and retry rounds are reported next to the estimates, so
        estimator error is observable per query (the aggregate lives in
        coalescer_stats()["planner"]).  With compile=True (implies
        execute) each entry gains the program ledger's compile/cost/
        memory record for the dispatched signature (ISSUE 14,
        das_tpu/obs/proflog.py).  Tree composites (Or / negation trees)
        report one entry per ordered-conjunction site; queries outside
        the compiled language report route "host"."""
        return query_compiler.explain(
            self.db, query, execute=execute, compile=compile
        )

    # -- transactions ------------------------------------------------------

    def open_transaction(self) -> Transaction:
        return Transaction()

    def commit_transaction(self, transaction: Transaction) -> None:
        from das_tpu.storage.atom_table import load_metta_text

        load_metta_text(transaction.metta_string(), self.data)
        self._refresh()

    # -- bulk loads --------------------------------------------------------

    def load_knowledge_base(self, source: str) -> None:
        from das_tpu.ingest.pipeline import load_knowledge_base

        load_knowledge_base(self.data, source)
        self._refresh()
        nodes, links = self.count_atoms()
        logger().info(f"Loaded KB: {nodes} nodes, {links} links")

    def load_canonical_knowledge_base(self, source: str) -> None:
        from das_tpu.ingest.pipeline import load_canonical_knowledge_base

        load_canonical_knowledge_base(self.data, source)
        self._refresh()
        nodes, links = self.count_atoms()
        logger().info(f"Loaded canonical KB: {nodes} nodes, {links} links")

    def load_metta_text(self, text: str) -> None:
        """Convenience: load a MeTTa string directly."""
        from das_tpu.storage.atom_table import load_metta_text

        load_metta_text(text, self.data)
        self._refresh()

    # -- checkpoint / resume ----------------------------------------------

    def save_checkpoint(self, path: str, with_indexes: bool = True) -> None:
        """Persist the AtomSpace (records + probe indexes) to a directory.
        On the sharded backend the shard-local slabs are saved too, so a
        restart restores each device's slab directly (no re-partition)."""
        from das_tpu.storage import checkpoint

        if with_indexes and hasattr(self.db, "tables"):
            checkpoint.save_sharded(self.db, path)
        else:
            checkpoint.save(self.data, path, with_indexes=with_indexes)

    def load_checkpoint(self, path: str) -> None:
        """Restore an AtomSpace checkpoint (replaces current contents)."""
        from das_tpu.storage import checkpoint

        self.data = checkpoint.load(path)
        self.db = self._make_backend(self.config.backend)

    # -- durability (ISSUE 15, storage/durable.py) ------------------------

    def save_snapshot(self, path: Optional[str] = None) -> str:
        """One atomic generational snapshot of the live backend: records,
        probe indexes, (sharded) slabs and the warm-state bundle land in
        a new `gen-NNNNNN` directory under the root, verified by a
        CRC-digest manifest; the write-ahead log rotates to the new
        generation.  Returns the generation directory."""
        from das_tpu.storage import durable

        root = path or self._snapshot_root()
        if not root:
            raise ValueError(
                "no snapshot root: pass a path or set "
                "DasConfig.snapshot_dir / DAS_TPU_SNAPSHOT_DIR"
            )
        return durable.write_snapshot(self.db, root)

    def restore_snapshot(self, path: Optional[str] = None) -> None:
        """Replace the current contents with a verified warm restore:
        newest valid generation + WAL replay to head + warm bundle
        (TensorDB.restore / ShardedDB.restore are the backend-level
        spellings)."""
        from das_tpu.storage import durable

        root = path or self._snapshot_root()
        if not root:
            raise ValueError(
                "no snapshot root: pass a path or set "
                "DasConfig.snapshot_dir / DAS_TPU_SNAPSHOT_DIR"
            )
        self.db = durable.restore(
            root, config=self.config, backend=self.config.backend
        )
        self.data = self.db.data
