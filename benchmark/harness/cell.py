"""One run of one cell: build the store from the seed, serve it, warm
up, open the window, close it, compare every answer, report.

The process that runs this holds the chip: the gRPC server is started
in-process (`das_tpu.service.server.serve(block=False)`), and the load
comes from child processes (`loadgen.py`) that never touch a device.
The only writer of a mix with writes lives here too: the wire has no
commit RPC, so it calls `DistributedAtomSpace.commit_transaction` on the
served tenant under the tenant's lock, as a commit RPC would.
"""

from __future__ import annotations

import bisect
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from benchmark.harness import devtrace, stats
from benchmark.harness import traffic as traffic_mod
from benchmark.harness.loadgen import Pace
from benchmark.harness.spec import ROOT, Cell
from benchmark.reference import generator, plain

EXIT_NO_ACCELERATOR = 3
EXIT_BAD_ENVIRONMENT = 2
#: seconds of the window that a traced run hands to jax.profiler
TRACE_SLICE_S = 3.0
TRACE_SLICE_AT_S = 1.0


def log(kind: str, **fields) -> None:
    """One JSON line on stdout, before the last one."""
    print(json.dumps({"log": kind, **fields}, default=str), flush=True)


class CompileEvents:
    """Programs built in this process: every XLA compile request ends in
    one `backend_compile_duration` event, whether the persistent cache
    served it (a hit event) or the compiler ran (a miss event).  A
    program built inside the window is a stall in the window."""

    def __init__(self):
        import jax

        self.builds = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_time(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.builds += 1

    def snapshot(self) -> dict:
        return {"builds": self.builds, "hits": self.hits,
                "misses": self.misses}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}


class Children:
    """The load-generator processes and the JSON-line talk with them."""

    def __init__(self, n: int, workdir: str):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        for name in ("DAS_TPU_TRACE", "DAS_TPU_TRACE_JAX", "DAS_TPU_TRACE_DIR",
                     "DAS_TPU_METRICS_PORT"):
            env.pop(name, None)
        self.stderr = [open(os.path.join(workdir, f"loadgen_{i}.stderr"), "w")
                       for i in range(n)]
        self.procs = [
            subprocess.Popen(
                [sys.executable, os.path.join(ROOT, "benchmark", "harness",
                                              "loadgen.py")],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=self.stderr[i], text=True, env=env, cwd=ROOT)
            for i in range(n)
        ]

    def send(self, i: int, obj: dict) -> None:
        self.procs[i].stdin.write(json.dumps(obj) + "\n")
        self.procs[i].stdin.flush()

    def send_all(self, obj: dict) -> None:
        for i in range(len(self.procs)):
            self.send(i, obj)

    def expect(self, i: int, ev: str) -> dict:
        line = self.procs[i].stdout.readline()
        if not line:
            self.stderr[i].flush()
            with open(self.stderr[i].name) as fh:
                tail = fh.read()[-2000:]
            raise RuntimeError(
                f"load generator {i} ended (exit {self.procs[i].poll()}): "
                f"{tail}")
        msg = json.loads(line)
        if msg.get("ev") != ev:
            raise RuntimeError(f"load generator {i}: expected {ev}, got {msg}")
        return msg

    def expect_all(self, ev: str) -> list:
        return [self.expect(i, ev) for i in range(len(self.procs))]

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.stdin.write(json.dumps({"cmd": "quit"}) + "\n")
                    p.stdin.flush()
                except (BrokenPipeError, OSError):
                    pass
        for p in self.procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        for fh in self.stderr:
            fh.close()


class Writer:
    """The one writer of a mix with writes.

    A transaction is `interacts` new `(Interacts g x)` plus `members`
    new `(Member x p)` around one gene g (p a process of g that x lacks):
    every expression is new, and `grounded3(g)` gains one row for each
    x.  After each acknowledgement it reads `grounded3(g)` back over the
    wire and must see every new row (read-your-write)."""

    def __init__(self, run, spec: dict):
        self.run, self.spec = run, spec
        self.readback_shapes = tuple(spec["readback"])
        self.rng = traffic_mod.rng_for(run.seed, "writer")
        keys = run.cell.traffic["keys"]
        self.zipf = (traffic_mod.ZipfRanks(run.store.n_genes, keys["theta"])
                     if keys["distribution"] == "zipf" else None)
        self.issued = []      # t_issue per commit, commit number = index+1
        self.acked = []       # t_ack
        self.latency_ms = []  # parallel to issued
        self.failures = []
        self.visible_ms = []  # commit call -> its read-back answered
        self.readbacks = 0
        self.readback_misses = 0

    def draw_gene(self) -> int:
        if self.zipf is None:
            return int(self.rng.integers(self.run.store.n_genes))
        return int(self.run.perm[self.zipf.draw(self.rng, 1)[0]])

    def build(self, g: int):
        kb = self.run.kb
        tx = self.run.das.open_transaction()
        mine = sorted(kb.procs_of(g))
        out = kb.out_of(g)
        n = int(self.spec["transaction"]["interacts"])
        if int(self.spec["transaction"]["members"]) != n:
            raise ValueError("a transaction pairs each Interacts with a Member")
        adds = []
        while len(adds) < n:
            x = int(self.rng.integers(self.run.store.n_genes))
            if x == g or x in out or any(x == a for a, _ in adds):
                continue
            theirs = kb.procs_of(x)
            p = next((q for q in mine if q not in theirs), None)
            if p is None:
                continue
            adds.append((x, p))
            tx.add(f'(Interacts "{generator.gene_name(g)}" '
                   f'"{generator.gene_name(x)}")')
            tx.add(f'(Member "{generator.gene_name(x)}" '
                   f'"{generator.proc_name(p)}")')
        return tx, adds

    def commit(self, g: int = None, readback=None) -> float:
        """One transaction, acknowledged; returns its latency in ms."""
        run = self.run
        readback = self.readback_shapes if readback is None else readback
        g = self.draw_gene() if g is None else g
        tx, adds = self.build(g)
        t_issue = time.monotonic()
        try:
            with run.tenant.lock:
                run.commit_fn(tx)
        except Exception as exc:  # noqa: BLE001 — a failed commit is counted
            self.failures.append(f"{type(exc).__name__}: {exc}"[:300])
            return float("nan")
        t_ack = time.monotonic()
        self.issued.append(t_issue)
        self.acked.append(t_ack)
        v = len(self.acked)
        for x, p in adds:
            if not (run.kb.add_interacts(g, x, v) and run.kb.add_member(x, p, v)):
                raise RuntimeError("the writer built a link that was there")
        ms = (t_ack - t_issue) * 1e3
        self.latency_ms.append(ms)
        if run.pace is not None:
            run.pace[0] = v - run.commits_before_window
        for shape in readback:
            self.readbacks += 1
            reply = run.client.call(
                "query", key=run.token, output_format="HANDLE",
                query=run.dsl(shape, g))
            rows = (plain.canonical_answer(reply["msg"])
                    if reply["success"] else None)
            rule = run.cell.rules[shape]
            want = run.kb.canonical_rows(rule.rows(run.kb, g),
                                         columns=rule.COLUMNS)
            if rows != want:
                self.readback_misses += 1
        if readback:
            self.visible_ms.append((time.monotonic() - t_issue) * 1e3)
        return ms

    def window_loop(self, t0: float, t_end: float) -> None:
        run = self.run
        r = int(self.spec["reads_per_write"])
        n_procs = len(run.children.procs)
        while time.monotonic() < t0:
            time.sleep(0.001)
        while time.monotonic() < t_end:
            answered = int(run.pace[8:8 + n_procs].sum())
            in_window = len(self.issued) - run.commits_before_window
            if answered >= r * in_window:
                self.commit()
            else:
                time.sleep(0.0005)


class Run:
    """State of one run; `run_cell` drives the phases."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_process_start: float, scale: float = None,
                 sabotage: str = None):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace = bool(trace)
        self.t_process_start = t_process_start
        self.scale = float(scale if scale is not None
                           else cell.config["scale"])
        self.sabotage = sabotage
        self.reference_s = 0.0
        self.workdir = tempfile.mkdtemp(prefix="das_bench_")
        self.children = self.server = self.service = self.client = None
        self.pace = None
        self.commits_before_window = 0
        self.direct_calls = []

    # -- helpers -----------------------------------------------------------

    def dsl(self, shape: str, key: int) -> str:
        return self.cell.queries[shape]["dsl"].format(
            key=generator.gene_name(key))

    def rpc(self, method: str, **request) -> str:
        reply = self.client.call(method, **request)
        if not reply["success"]:
            raise RuntimeError(f"{method} failed: {reply['msg'][:1000]}")
        return reply["msg"]

    def close(self) -> None:
        if self.children is not None:
            self.children.close()
        if self.client is not None:
            self.client.close()
        if self.server is not None:
            self.server.stop(0).wait()
        shutil.rmtree(self.workdir, ignore_errors=True)

    # -- phases ------------------------------------------------------------

    def build_store(self) -> None:
        t = time.monotonic()
        # the store's profile is the configuration's: its `shape` block
        # (counts at full scale, skew), scaled by its `scale`
        self.store = generator.Store(self.scale, self.seed,
                                     self.cell.config["shape"])
        self.kb_path = os.path.join(self.workdir, "kb.metta")
        lines = generator.write_canonical(self.store, self.kb_path)
        gen_s = time.monotonic() - t
        # the reference's own structures: not part of the system's
        # set-up, so their seconds are taken out of setup_s
        t = time.monotonic()
        self.kb = plain.PlainKB(self.store)
        self.perm = traffic_mod.key_permutation(self.seed, self.store.n_genes)
        self.reference_s += time.monotonic() - t
        log("store", scale=self.scale, seed=self.seed,
            params=self.store.params, skew=self.store.skew,
            expression_lines=lines,
            file_mb=round(os.path.getsize(self.kb_path) / 2 ** 20, 1),
            generate_s=gen_s, reference_build_s=self.reference_s)

    def serve(self) -> None:
        import das_tpu
        from das_tpu.api.atomspace import DistributedAtomSpace
        from das_tpu.core.config import DasConfig
        from das_tpu.service.client import DasClient
        from das_tpu.service.server import serve
        from das_tpu.storage import durable

        cfg = self.cell.config
        fields = dict(cfg.get("das_config", {}))
        durable_cell = bool(cfg.get("durable"))
        t = time.monotonic()
        self.server, self.service = serve(
            port=0, backend=cfg["backend"], block=False,
            max_workers=int(cfg["serve"]["max_workers"]))
        self.client = DasClient(port=self.server.bound_port)
        self.das = DistributedAtomSpace(
            database_name="bench", backend=cfg["backend"],
            config=DasConfig.from_env(**fields))
        self.token = self.service.attach_tenant("bench", self.das)
        self.tenant = self.service.tenants[self.token]
        self.das.load_canonical_knowledge_base(self.kb_path)
        load_s = time.monotonic() - t
        snap_s = 0.0
        if durable_cell:
            # load first, arm durability after: `durable.attach` writes
            # ONE generation that holds the loaded store and points the
            # WAL at it.  (A store created under the snapshot root
            # writes the whole load as one kind="full" WAL record and
            # then needs a second snapshot: 310 s against 138 s at
            # scale 0.3, my chip runs, PR 25.)
            t = time.monotonic()
            self.das.config.snapshot_dir = os.path.join(self.workdir,
                                                        "snapshots")
            durable.attach(
                self.das.db,
                os.path.join(self.das.config.snapshot_dir, "bench"),
                self.das.config)
            snap_s = time.monotonic() - t
        os.remove(self.kb_path)
        self.commit_fn = self.das.commit_transaction
        # the controls (benchmark/tests/test_control.py): the timed path
        # broken underneath, each breaking one stated guarantee
        if self.sabotage == "drop_commits":
            # commits are acknowledged and never applied: answers come
            # from a store missing them
            self.commit_fn = lambda tx: None
        elif self.sabotage == "approximate_answers":
            # an answer of more than 100 rows loses one where it is
            # produced: a set that is nearly the exact one
            exact = self.das._format_answer

            def lossy(matched, answer, output_format):
                if matched and len(answer.assignments) > 100:
                    answer.assignments.pop()
                return exact(matched, answer, output_format)

            self.das._format_answer = lossy
        elif self.sabotage is not None:
            raise ValueError(f"no such control: {self.sabotage!r}")
        count = self.rpc("count", key=self.token)
        want = str(self.kb.counts())
        log("serve", port=self.server.bound_port,
            compile_cache_dir=das_tpu.compile_cache_dir(), load_s=load_s,
            snapshot_s=snap_s, count_rpc=count, count_reference=want,
            durability=durable.snapshot_stats() if durable_cell else None,
            memory=self.memory())
        if count != want:
            raise RuntimeError(f"count {count} != the generator's {want}")
        # who reaches the per-query dispatcher (the coalescer's per-RPC
        # fall-back and atomspace's settle fall-through both end there)
        original = self.das.query

        def spy(query, *args, **kwargs):
            self.direct_calls.append(type(query).__name__)
            return original(query, *args, **kwargs)

        self.das.query = spy

    def memory(self) -> dict:
        import jax

        peak = used = 0
        for d in jax.devices():
            s = d.memory_stats() or {}
            peak = max(peak, int(s.get("peak_bytes_in_use") or 0))
            used = max(used, int(s.get("bytes_in_use") or 0))
        return {"peak_bytes_in_use": peak, "bytes_in_use": used}

    def start_children(self) -> None:
        self.children = Children(int(self.cell.traffic["generator_processes"]),
                                 self.workdir)

    def brief_children(self) -> None:
        tr = self.cell.traffic
        n_procs = len(self.children.procs)
        n_clients = int(tr["clients"])
        pace = None
        if tr.get("writes"):
            pace_file = os.path.join(self.workdir, "pace.bin")
            self.pace = Pace.create(pace_file)
            pace = {"file": pace_file,
                    "reads_per_write": int(tr["writes"]["reads_per_write"]),
                    "lead": int(tr["writes"]["reader_lead"])}
        for i in range(n_procs):
            self.children.send(i, {
                "port": self.server.bound_port, "token": self.token,
                "traffic": tr, "n_keys": self.store.n_genes,
                "seed": self.seed, "proc_index": i, "n_procs": n_procs,
                "n_clients": n_clients,
                "clients": list(range(i, n_clients, n_procs)),
                "workdir": self.workdir, "pace": pace,
                "key_format": generator.GENE_FORMAT,
                "queries": {s: q["dsl"] for s, q in self.cell.queries.items()},
            })
        self.children.expect_all("ready")

    def warm_up(self, events: CompileEvents) -> None:
        """A seeded prefix of the same traffic, round after round, until
        a round builds no program.  With writes: first the climb (see
        the mix file), then commits until one builds none."""
        tr = self.cell.traffic
        warm = tr["warmup"]
        self.writer = Writer(self, tr["writes"]) if tr.get("writes") else None
        rounds = []
        for rnd in range(int(warm["max_rounds"])):
            before = events.builds
            t = time.monotonic()
            self.children.send_all({"cmd": "warm", "round": rnd,
                                    "requests": int(warm["requests_per_client"])})
            done = self.children.expect_all("warm_done")
            failed = sum(d["failed"] for d in done)
            if failed:
                raise RuntimeError(f"warm-up: {failed} requests failed: "
                                   f"{[d['errors'] for d in done if d['errors']]}")
            commits = 0
            if self.writer is not None:
                if rnd == 0:
                    hot = int(self.perm[0])
                    for _ in range(int(warm["climb_commits"])):
                        self.writer.commit(hot, readback=tuple(self.cell.queries))
                        commits += 1
                for _ in range(int(warm["commits_per_round"])):
                    self.writer.commit()
                    commits += 1
                if self.writer.failures:
                    raise RuntimeError(
                        f"warm-up commits failed: {self.writer.failures[:3]}")
            built = events.builds - before
            rounds.append({"round": rnd, "programs_built": built,
                           "commits": commits,
                           "seconds": time.monotonic() - t,
                           "worst_request_ms": max(d["worst_ms"] for d in done)})
            if built == 0 and rnd >= int(warm.get("min_rounds", 1)) - 1:
                break
        log("warmup", rounds=rounds)

    def counters(self) -> dict:
        """Always-on counts (no DAS_TPU_TRACE needed)."""
        from das_tpu import kernels
        from das_tpu.query.fused import FETCH_COUNTS
        from das_tpu.storage import durable

        st = self.service.coalescer_stats()
        out = {f"route.{k}": v for k, v in st["routes"].items()}
        out.update({f"dispatch.{k}": v
                    for k, v in kernels.DISPATCH_COUNTS.items()})
        out["fetches"] = FETCH_COUNTS["n"]
        for k in ("batches", "items", "cache_hits", "cache_misses",
                  "cache_invalidations", "queue_rejections",
                  "deadline_expired", "breaker_rejections",
                  "speculative_dispatches", "early_settles"):
            out[f"coalescer.{k}"] = st[k]
        for k, v in (st.get("planner") or {}).items():
            if isinstance(v, (int, float)):
                out[f"planner.{k}"] = v
        for k, v in durable.snapshot_stats().items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"durability.{k}"] = v
        out["coalescer.max_batch"] = st["max_batch"]
        return out

    def obs_counters(self) -> dict:
        from das_tpu.obs import metrics

        return {name: c.value for name, c in metrics.COUNTERS.items()}

    def window(self, events: CompileEvents) -> dict:
        import jax

        from das_tpu import obs

        if self.writer is not None:
            self.commits_before_window = len(self.writer.acked)
            self.pace[:] = 0
        if self.trace:
            obs.reset()          # ring and histograms start at the window
        before = self.counters()
        obs_before = self.obs_counters()
        builds_before = events.snapshot()
        self.direct_before = len(self.direct_calls)
        t0 = time.monotonic() + 0.25
        t_end = t0 + self.seconds
        # perf_counter (the program's span clock) against monotonic
        self.perf_minus_mono = time.perf_counter() - time.monotonic()
        self.setup_s = (t0 - self.t_process_start) - self.reference_s
        self.children.send_all({"cmd": "run", "t0": t0,
                                "seconds": self.seconds})
        writer_thread = None
        if self.writer is not None:
            writer_thread = threading.Thread(
                target=self.writer.window_loop, args=(t0, t_end), daemon=True)
            writer_thread.start()
        trace_dir = None
        if self.trace:
            trace_dir = os.path.join(self.workdir, "device_trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            time.sleep(max(0.0, t0 + TRACE_SLICE_AT_S - time.monotonic()))
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            self.slice_t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(
                    devtrace.SYNC_NAME, t_ns=time.perf_counter_ns()):
                pass
            time.sleep(min(TRACE_SLICE_S, max(0.1, self.seconds - 1.5)))
            self.slice_t1 = time.monotonic()
            jax.profiler.stop_trace()
        done = self.children.expect_all("run_done")
        if writer_thread is not None:
            writer_thread.join()
        after = self.counters()
        obs_after = self.obs_counters()
        return {"t0": t0, "t_end": t_end, "children": done,
                "counters": delta(after, before),
                "obs_counters": delta(obs_after, obs_before),
                "programs_built": delta(events.snapshot(), builds_before),
                "trace_dir": trace_dir}

    # -- after the window ----------------------------------------------------

    def read_records(self, done: list) -> list:
        records = []
        for d in done:
            with open(d["file"]) as fh:
                records.extend(json.loads(line) for line in fh)
        return records

    def verify(self, records: list) -> dict:
        t = time.monotonic()
        out = verify_records(
            records, self.kb, self.cell.rules,
            self.writer.acked if self.writer else [],
            self.writer.issued if self.writer else [])
        self.verify_s = time.monotonic() - t
        return out


def verify_records(records: list, kb, rules: dict, acked: list,
                   issued: list) -> dict:
    """Every answer of the window against the plain reference: `rules`
    is shape -> its loaded rule (`spec.Cell.rules`).

    An answer must be the exact set of ONE committed state between the
    last commit acknowledged before the query was sent and the last one
    issued before its answer was received: never a row of a commit that
    had not begun, never a state missing an acknowledged commit, never
    a mixture of two states.  `acked` / `issued` are the commits' times
    in commit order; commit numbers start at 1.  A rule whose KEY is
    None answers for the whole store: its rows are worked out once (by
    now every commit is applied, and the stamps say what each cut holds)
    and its canonical text once per cut."""
    wrong, failed, nonempty, raced = [], [], 0, 0
    whole_rows, whole_states = {}, {}    # whole-store rules: by shape;
    #                                      by (shape, cut)

    def rows_of(rec):
        rule = rules[rec["shape"]]
        if rule.KEY is not None:
            return rule.rows(kb, rec["key"])
        if rec["shape"] not in whole_rows:
            whole_rows[rec["shape"]] = rule.rows(kb, None)
        return whole_rows[rec["shape"]]

    def state_at(rec, rows, v):
        """(row count, digest) of the answer at commit number v."""
        rule = rules[rec["shape"]]
        at = (rec["shape"], v)
        if at in whole_states:
            return whole_states[at]
        want = kb.canonical_rows(rows, v, rule.COLUMNS)
        state = (len(want), plain.digest(want))
        if rule.KEY is None:
            whole_states[at] = state
        return state

    for rec in records:
        if not rec["ok"]:
            failed.append(rec)
            continue
        rows = rows_of(rec)
        v_lo = bisect.bisect_right(acked, rec["sent"])
        v_hi = bisect.bisect_right(issued, rec["recv"])
        cuts = {v_lo} | {s for s in rows.values() if v_lo < s <= v_hi}
        if len(cuts) > 1:
            raced += 1
        if rec["n"]:
            nonempty += 1
        if not any(state_at(rec, rows, v) == (rec["n"], rec["d"])
                   for v in sorted(cuts)):
            wrong.append({k: rec[k] for k in ("c", "i", "shape", "key", "n")}
                         | {"want_n_at_send": state_at(rec, rows, v_lo)[0]})
    return {"wrong": wrong, "failed": failed, "nonempty": nonempty,
            "raced_a_commit": raced}


def refuse(message: str, code: int) -> int:
    print(f"benchmark: {message}", file=sys.stderr)
    return code


def prepare_environment(trace: bool) -> str:
    """Environment the program reads at import: tracing on only in a
    traced run, the program ledger never.  Returns a reason to refuse,
    or ''."""
    truthy = ("1", "on", "true", "yes")
    if os.environ.get("DAS_TPU_PROFLOG", "0").lower() in truthy:
        return "unset DAS_TPU_PROFLOG: it swaps jit for AOT compiles"
    if not trace and os.environ.get("DAS_TPU_TRACE", "0").lower() in truthy:
        return "unset DAS_TPU_TRACE for an untraced run"
    if trace:
        os.environ["DAS_TPU_TRACE"] = "1"
        os.environ.setdefault("DAS_TPU_TRACE_RING", str(1 << 22))
    os.environ.pop("DAS_TPU_TRACE_DIR", None)
    return ""


def device_gate(chips: int):
    """(device dict, reason to refuse or '')."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        return device, (f"no accelerator (platform {device['platform']}); "
                        "the benchmark measures only on a TPU")
    if device["count"] != chips:
        return device, (f"the cell asks for {chips} chip(s), "
                        f"JAX finds {device['count']}")
    return device, ""


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process_start: float, require_chip: bool = True,
             scale: float = None, sabotage: str = None, root: str = ROOT):
    """One run.  Returns (result dict or None, exit code).  With
    `require_chip` false (the tests, the rehearsal) the look for a chip
    is skipped and everything else runs."""
    cell = Cell(workload, root)
    reason = prepare_environment(trace)
    if reason:
        return None, refuse(reason, EXIT_BAD_ENVIRONMENT)

    run = Run(cell, seed, seconds, trace, t_process_start, scale, sabotage)
    try:
        # the program's log goes where this run's files go, not to a
        # fixed path under /tmp
        from das_tpu.utils.logger import logger

        logger(log_file=os.path.join(run.workdir, "das_tpu.log"))
        import das_tpu  # noqa: F401  (x64 on before the first jax use)

        device, reason = device_gate(int(cell.workload["chips"]))
        if reason and require_chip:
            return None, refuse(reason, EXIT_NO_ACCELERATOR)
        # children first: their imports overlap this process's set-up
        run.start_children()
        events = CompileEvents()
        log("gate", device=device, workload=workload, seed=seed,
            seconds=seconds, trace=int(trace), cpu_count=os.cpu_count())
        run.build_store()
        run.serve()
        run.brief_children()
        run.warm_up(events)
        win = run.window(events)
        records = run.read_records(win["children"])
        result = report(run, cell, device, win, records)
        return result, 0
    finally:
        run.close()


def report(run: Run, cell: Cell, device: dict, win: dict, records: list):
    from das_tpu.storage import durable

    t_end = win["t_end"]
    checked = run.verify(records)
    wrong, failed = checked["wrong"], checked["failed"]
    bad_keys = {(r["c"], r["i"]) for r in wrong} | {(r["c"], r["i"])
                                                    for r in failed}
    good = [r for r in records if (r["c"], r["i"]) not in bad_keys]
    lat_ms = [(r["recv"] - r["due"]) * 1e3 for r in good]
    in_window = [r for r in good if r["recv"] <= t_end]
    writer = run.writer
    n_before = run.commits_before_window
    commit_ms = writer.latency_ms[n_before:] if writer else []
    visible_ms = writer.visible_ms[n_before:] if writer else []
    n_commits = len(commit_ms)
    c = win["counters"]

    # -- the numbers compared, each beside its limit -----------------------
    compared = []

    def compare(name, value, limit, ok):
        compared.append({"name": name, "value": value, "limit": limit,
                         "ok": bool(ok)})

    compare("wrong_answers", len(wrong), 0, not wrong)
    compare("answers_compared", len(records) - len(failed), ">=1",
            len(records) - len(failed) >= 1)
    compare("route.host_delta", c.get("route.host", 0), 0,
            c.get("route.host", 0) == 0)
    compare("route.staged_delta", c.get("route.staged", 0), 0,
            c.get("route.staged", 0) == 0)
    direct = len(run.direct_calls) - run.direct_before
    if writer is None:
        # read-only: nothing may fall through to the per-query
        # dispatcher.  With commits it is the program's designed path
        # for a group a commit overtook (atomspace._QueryManyJob._stale)
        # and is reported in the window log, not limited.
        compare("per_query_dispatcher_calls", direct, 0, direct == 0)
    count = run.rpc("count", key=run.token)
    compare("count_rpc", count, str(run.kb.counts()),
            count == str(run.kb.counts()))
    if writer is not None:
        compare("commit_failures", len(writer.failures), 0,
                not writer.failures)
        compare("read_your_write_misses", writer.readback_misses, 0,
                writer.readback_misses == 0)
        wal = c.get("durability.wal_records", 0)
        compare("wal_records_delta", wal, n_commits, wal == n_commits)
        compare("commits_in_window", n_commits, ">=1", n_commits >= 1)
    for item in compared:
        log("compare", **item)
    correct = all(item["ok"] for item in compared)

    n_attempted = len(records) + (n_commits + len(writer.failures)
                                  if writer else 0)
    n_failed = len(wrong) + len(failed) + (len(writer.failures)
                                           if writer else 0)
    tail = stats.supported_tail(len(lat_ms)) if lat_ms else None
    late = [(r["sent"] - r["due"]) * 1e3 for r in records]
    log("window", seconds=run.seconds, requests=len(records),
        latency_samples=len(lat_ms), highest_supported_tail=tail,
        latency_ms_at={f"p{q}": stats.percentile(lat_ms, q / 100.0)
                       for q in (50, 75, 90, 93, 95, 97, 99)} if lat_ms else None,
        nonempty_answers_compared=checked["nonempty"],
        answers_that_raced_a_commit=checked["raced_a_commit"],
        failed_requests=len(failed), failed_examples=failed[:3],
        wrong_examples=wrong[:3],
        by_shape={s: sum(1 for r in records if r["shape"] == s)
                  for s in cell.queries},
        commits=n_commits, commit_samples=len(commit_ms),
        write_share=(n_commits / (n_commits + len(records))
                     if records else None),
        readbacks=writer.readbacks if writer else 0,
        commit_ack_ms=({"p50": stats.percentile(commit_ms, 0.5),
                        "p95": stats.percentile(commit_ms, 0.95),
                        "max": max(commit_ms)} if commit_ms else None),
        commit_to_readback_ms=(
            {"p50": stats.percentile(visible_ms, 0.5), "max": max(visible_ms)}
            if visible_ms else None),
        answered_in_window_per_s=len(in_window) / run.seconds,
        per_query_dispatcher_calls=direct,
        generator_cpu_share=[d["cpu_share"] for d in win["children"]],
        generator_lateness_ms=({"p50": stats.percentile(late, 0.5),
                                "p95": stats.percentile(late, 0.95),
                                "max": max(late)} if late else None),
        programs_built_in_window=win["programs_built"],
        counters=c, verify_s=run.verify_s, memory=run.memory(),
        durability=durable.snapshot_stats() if writer else None)

    values = {"setup_s": run.setup_s}
    if in_window:
        values["query_rate"] = len(in_window) / run.seconds
    if lat_ms:
        values["query_p50_ms"] = stats.percentile(lat_ms, 0.5)
        values["query_p95_ms"] = stats.percentile(lat_ms, 0.95)
    if visible_ms:
        values["commit_visible_p50_ms"] = stats.percentile(visible_ms, 0.5)

    breakdown = None
    dev = dict(device)
    dev["memory_peak_bytes"] = run.memory()["peak_bytes_in_use"]
    if run.trace:
        values, breakdown, dev_times = traced_metrics(
            run, cell, win, records, good, lat_ms, commit_ms)
        dev.update(dev_times)
        names = [m["name"] for m in cell.per_layer]
    else:
        names = [m["name"] for m in cell.end_to_end]
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    missing = [n for n in names if n not in values]
    if missing and not run.trace:
        compare("end_to_end_metrics_missing", missing, [], False)
        log("compare", **compared[-1])
        correct = False
    result = {
        "correct": correct, "attempted": n_attempted, "failed": n_failed,
        "metrics": {n: {"value": values[n], "unit": units[n]}
                    for n in names if n in values},
        "device": dev,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # last in the line: every number compared beside its limit
    result["compared"] = {item["name"]: {"value": item["value"],
                                         "limit": item["limit"]}
                          for item in compared}
    return result


def traced_metrics(run, cell, win, records, good, lat_ms, commit_ms):
    """Per-layer metrics: each from a reader of its own."""
    from das_tpu import obs
    from das_tpu.obs import metrics as obs_metrics

    # host spans on the program's clock, brought to seconds of
    # time.monotonic (the window's clock)
    origin = obs.REC._t_origin - run.perf_minus_mono
    spans = [{"name": n, "phase": ph, "t": t + origin, "dur": d, "trace": tr,
              "group": g, "thread": th, "attrs": a}
             for (n, ph, t, d, tr, g, _lane, th, a) in obs.events()]
    hist = {name: {"p50": h.percentile(0.5), "p95": h.percentile(0.95),
                   "count": h.total}
            for name, h in obs_metrics.HISTOGRAMS.items()}
    trace = None
    if win["trace_dir"]:
        trace = devtrace.load_xplane(devtrace.find_xplane(win["trace_dir"]))
    in_slice = [r for r in records
                if r["sent"] >= run.slice_t0 and r["recv"] <= run.slice_t1]
    window = {
        "t0": win["t0"], "t_end": win["t_end"], "seconds": run.seconds,
        "slice_t0": run.slice_t0, "slice_t1": run.slice_t1,
        "latency_ms": lat_ms, "commit_ms": commit_ms,
        "loop": cell.traffic["loop"],
        "lateness_ms": [(r["sent"] - r["due"]) * 1e3 for r in records],
        "requests": len(records), "answered": len(good),
        "commits": len(commit_ms), "histograms": hist,
        "rows_by_shape_in_slice": {
            s: [r.get("n", 0) for r in in_slice if r["shape"] == s]
            for s in cell.queries},
        "requests_in_slice": len(in_slice),
        "store": {"n_genes": run.store.n_genes,
                  "links": run.store.counts()[1],
                  "members_per_gene": run.store.params["members_per_gene"],
                  "mean_out_degree": 2.0 * len(run.store.interactions)
                  / run.store.n_genes},
        "device_kind": None, "memory": run.memory(),
        "bench_dir": cell.bench_dir,
    }
    import jax

    window["device_kind"] = jax.devices()[0].device_kind
    dev_times, breakdown, offset = {}, None, None
    if trace is not None and devtrace.device_planes(trace):
        # the traced slice on the trace's own clock: through the sync
        # annotation when it is found, else the extent of the ops
        offset = devtrace.sync_offset_ns(trace)
        if offset is not None:
            w_lo = (run.slice_t0 + run.perf_minus_mono) * 1e9 - offset
            w_hi = (run.slice_t1 + run.perf_minus_mono) * 1e9 - offset
        else:
            w_lo, w_hi = devtrace.trace_extent(trace)
        window["trace_window_ns"] = [w_lo, w_hi]
        dev_times = {"busy_s": devtrace.busy_seconds(trace, w_lo, w_hi),
                     "window_s": (w_hi - w_lo) / 1e9}
        # a gap is named by what the coalescer's ONE worker thread (the
        # thread that records `serve.drain`) was doing: every gRPC thread
        # has a `wire.query` open all the time, which names nothing
        workers = {s["thread"] for s in spans
                   if s["name"] == devtrace.WORKER_SPAN}
        host = [[s["name"], s["t"] + run.perf_minus_mono, s["dur"]]
                for s in spans if s["phase"] == "X" and s["thread"] in workers]
        breakdown = {
            "device_ops": devtrace.seconds_by_name(trace),
            "idle_gaps": devtrace.attribute_gaps(
                devtrace.idle_gaps(trace, w_lo, w_hi, top=200),
                host, offset)[:10],
        }
    counters = dict(win["counters"])
    counters.update({f"obs.{k}": v for k, v in win["obs_counters"].items()})
    counters.update({f"built.{k}": v
                     for k, v in win["programs_built"].items()})
    values = {}
    for m in cell.per_layer:
        value = cell.layer_reader(m["name"])(spans, counters, trace, window)
        if value is not None:
            values[m["name"]] = float(value)
    if trace is not None:
        log("trace", planes=[{"name": p["name"],
                              "lines": {ln["name"]: len(ln["events"])
                                        for ln in p["lines"]}}
                             for p in trace["planes"]],
            sync_offset_found=offset is not None,
            modules=devtrace.seconds_by_name(trace, devtrace.MODULE_LINES),
            spans_recorded=len(spans), requests_in_slice=len(in_slice))
    return values, breakdown, dev_times
