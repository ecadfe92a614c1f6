#!/usr/bin/env python3
"""chip_smoke.py — the served query path on one TPU chip, end to end.

One process.  It starts the gRPC service in-process
(`das_tpu.service.server.serve(port=0, block=False)`), drives it with
`das_tpu.service.client.DasClient` over localhost, and compares every
answer with a PLAIN REFERENCE that shares no query code with the
program: Python sets over a short parse of the MeTTa file the run wrote.

    python3 chip_smoke.py                    # one chip, FlyBase shape x 0.1
    python3 chip_smoke.py --chips 4          # ONLY the sharded mesh phase
    JAX_PLATFORMS=cpu python3 chip_smoke.py --scale 0.002   # rehearsal

Output: one JSON object per phase on its own line, then — only when
every phase passed AND the platform is `tpu` with the asked-for device
count — the contract's last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without an accelerator the script never prints that line and exits
non-zero: at a rehearsal scale (<= 0.01) it still runs the phases, so
the control flow is checked on the CPU, and then refuses at the gate;
at any larger scale it refuses before building anything.  A phase that
fails raises — there is no try/except that lets the run carry on.

Per-phase wall seconds are SMOKE TIMINGS: they include compilation and
host set-up and are not benchmark numbers.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import re
import shutil
import sys
import tempfile
import time

#: the reference-scale KB shape (BASELINE.json: 2.58 M nodes /
#: 27.9 M links, SimplePatternMiner.ipynb cell 0), multiplied by --scale
FLYBASE = dict(
    n_genes=2_400_000, n_processes=180_000, members_per_gene=10,
    n_interactions=1_500_000, n_evaluations=435_000,
)
#: largest --scale the script will run WITHOUT an accelerator (rehearsal)
REHEARSAL_MAX_SCALE = 0.01
N_GROUNDED = 8
EXIT_NO_ACCELERATOR = 3


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def kb_params(scale: float, seed: int) -> dict:
    p = {
        k: (v if k == "members_per_gene" else max(1, int(v * scale)))
        for k, v in FLYBASE.items()
    }
    p["n_processes"] = max(p["n_processes"], 2 * p["members_per_gene"])
    p["seed"] = seed
    return p


# -- the plain reference ---------------------------------------------------


def handle(node_type: str, name: str) -> str:
    """Node handle as the reference DAS defines it: md5("<type> <name>")."""
    return hashlib.md5(f"{node_type} {name}".encode()).hexdigest()


class PlainKB:
    """Python sets over a short parse of the canonical MeTTa file — the
    answer oracle.  Nothing here imports das_tpu."""

    _NODE = re.compile(r'^\(: "([^"]+)" (\w+)\)$')
    _MEMBER = re.compile(r'^\(Member "Gene ([^"]+)" "BiologicalProcess ([^"]+)"\)$')
    _INTERACTS = re.compile(r'^\(Interacts "Gene ([^"]+)" "Gene ([^"]+)"\)$')
    _EVAL = re.compile(
        r'^\(Evaluation "Predicate ([^"]+)" '
        r'\(List "Gene ([^"]+)" "BiologicalProcess ([^"]+)"\)\)$'
    )

    def __init__(self, path: str):
        self.nodes = set()        # (type, name)
        self.procs_of = {}        # gene -> {process}
        self.genes_of = {}        # process -> {gene}
        self.interacts = {}       # gene -> {gene} (stored orientation a->b)
        self.lists = set()        # (gene, process)
        self.evals = set()        # (predicate, gene, process)
        with open(path) as fh:
            for line in fh:
                line = line.rstrip("\n")
                m = self._MEMBER.match(line)
                if m:
                    self.add_member(*m.groups())
                    continue
                m = self._INTERACTS.match(line)
                if m:
                    self.add_interacts(*m.groups())
                    continue
                m = self._EVAL.match(line)
                if m:
                    pred, g, p = m.groups()
                    self.lists.add((g, p))
                    self.evals.add((pred, g, p))
                    continue
                m = self._NODE.match(line)
                if m:
                    self.nodes.add((m.group(2), m.group(1)))
                    continue
                check(line.startswith("(: ") and line.endswith(" Type)"),
                      f"plain parse: unexpected line {line!r}")

    def add_member(self, g, p) -> bool:
        s = self.procs_of.setdefault(g, set())
        if p in s:
            return False
        s.add(p)
        self.genes_of.setdefault(p, set()).add(g)
        return True

    def add_interacts(self, a, b) -> bool:
        s = self.interacts.setdefault(a, set())
        if b in s:
            return False
        s.add(b)
        return True

    def counts(self):
        """(nodes, links) as count_atoms reports them: typedefs are not
        atoms; a nested (List ..) is a link of its own; a repeated
        expression is one atom."""
        n_member = sum(len(s) for s in self.procs_of.values())
        n_inter = sum(len(s) for s in self.interacts.values())
        return (len(self.nodes),
                n_member + n_inter + len(self.lists) + len(self.evals))

    # -- answers, as sets of frozenset({(variable, handle), ...}) --------

    def grounded3(self, g):
        """And(Member(g,$3), Member($2,$3), Interacts(g,$2))"""
        mine = self.procs_of.get(g, set())
        return {
            frozenset({("$2", handle("Gene", x)),
                       ("$3", handle("BiologicalProcess", p))})
            for x in self.interacts.get(g, ())
            for p in mine & self.procs_of.get(x, set())
        }

    def list_member(self):
        """And(List($1,$2), Member($1,$2))"""
        return {
            frozenset({("$1", handle("Gene", g)),
                       ("$2", handle("BiologicalProcess", p))})
            for g, p in self.lists if p in self.procs_of.get(g, ())
        }

    def member_of(self, g):
        return {
            frozenset({("$1", handle("BiologicalProcess", p))})
            for p in self.procs_of.get(g, ())
        }

    def branch(self, g):
        """And(Member(g,$3), Member($2,$3))"""
        return {
            frozenset({("$2", handle("Gene", x)),
                       ("$3", handle("BiologicalProcess", p))})
            for p in self.procs_of.get(g, ()) for x in self.genes_of[p]
        }


_ASSIGNMENT = re.compile(r"\{([^{}]*)\}")
_BINDING = re.compile(r"'([^']+)': '([0-9a-f]{32})'")


def parse_answer(msg: str):
    """`query`'s HANDLE-format reply -> (negation, set of assignments)."""
    negation = msg.startswith("NOT ")
    body = msg[4:] if negation else msg
    out = set()
    for inner in _ASSIGNMENT.findall(body):
        out.add(frozenset(_BINDING.findall(inner)))
    return negation, out


# -- DSL strings -------------------------------------------------------------


def dsl_grounded3(g: str) -> str:
    return (f"Node g Gene {g}, Link Member g $3, Link Member $2 $3, "
            "Link Interacts g $2, AND")


DSL_LIST_MEMBER = "Link List $1 $2, Link Member $1 $2, AND"


def dsl_or_not(g1: str, g2: str, g3: str) -> str:
    return (f"Node a Gene {g1}, Node b Gene {g2}, Node c Gene {g3}, "
            "Link Member a $1, Link Member b $1, Link Member c $1, NOT, OR")


# -- counters ------------------------------------------------------------------


class CacheEvents:
    """JAX's persistent-compilation-cache events (the ones
    das_tpu/obs/proflog.py reads), counted process-wide."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def counters_snapshot(db=None) -> dict:
    from das_tpu.ops.counters import DISPATCH_COUNTS
    from das_tpu.query.compiler import ROUTE_COUNTS
    from das_tpu.query.fused import FETCH_COUNTS, result_cache_stats

    snap = {
        "route": dict(ROUTE_COUNTS),
        "dispatch": dict(DISPATCH_COUNTS),
        "fetches": FETCH_COUNTS["n"],
    }
    if db is not None:
        snap["result_cache"] = result_cache_stats(db)
    return snap


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] - before.get(k, 0)}


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_stats() -> list:
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")})
    return out


# -- the run ---------------------------------------------------------------------


class Smoke:
    """State shared by the phases of one run."""

    def __init__(self, scale: float, seed: int, chips: int):
        self.scale, self.seed, self.chips = scale, seed, chips
        self.rng = random.Random(seed)
        self.workdir = tempfile.mkdtemp(prefix="das_chip_smoke_")
        self.server = self.service = self.client = None
        self.token = self.das = self.plain = None
        self.genes = []
        self.direct_calls = []     # queries that reached das.query()
        self.timings = {}

    def close(self) -> None:
        if self.client is not None:
            self.client.close()
        if self.server is not None:
            self.server.stop(0).wait()
        shutil.rmtree(self.workdir, ignore_errors=True)

    def timed(self, name: str, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        self.timings[name] = round(time.perf_counter() - t0, 3)
        return out

    # RPC helpers: a failed Status is a failed phase
    def rpc(self, method: str, *args) -> str:
        reply = getattr(self.client, method)(*args)
        check(reply["success"], f"{method}{args!r} failed: {reply['msg'][:2000]}")
        return reply["msg"]

    def rpc_query(self, dsl: str):
        return parse_answer(self.rpc("query", self.token, dsl))

    def wait_ready(self, token: str, timeout_s: float = 600.0) -> None:
        deadline = time.monotonic() + timeout_s
        while True:
            msg = self.rpc("check_das_status", token)
            if msg == "Ready":
                return
            check(msg.startswith("Loading"), f"tenant status: {msg}")
            check(time.monotonic() < deadline, "load did not reach Ready")
            time.sleep(0.1)


def phase_serve(s: Smoke) -> None:
    """Start the service in-process and prove the wire's own load path
    (create + load file:// + status until Ready + count + query) on the
    15-line animals KB: the `load` RPC runs the general MeTTa parser."""
    from das_tpu.models.animals import write_animals_metta
    from das_tpu.service.client import DasClient
    from das_tpu.service.server import serve

    backend = "sharded" if s.chips > 1 else "tensor"
    s.server, s.service = serve(port=0, backend=backend, block=False)
    s.client = DasClient(port=s.server.bound_port)
    path = os.path.join(s.workdir, "animals.metta")
    write_animals_metta(path)
    token = s.rpc("create", "smoke_animals")
    check(s.rpc("load_knowledge_base", token, f"file://{path}")
          .startswith("Loading"), "load RPC did not start")
    s.wait_ready(token)
    count = s.rpc("count", token)
    check(count == "(14, 26)", f"animals count {count}")
    human, mammal = handle("Concept", "human"), handle("Concept", "mammal")
    neg, got = parse_answer(s.rpc(
        "query", token, "Node n Concept human, Link Inheritance n $1, AND"))
    check(not neg and got == {frozenset({("$1", mammal)})},
          f"animals query answered {got}")
    check(s.rpc("search_nodes", token, "Concept", "human") == str([human]),
          "animals search_nodes")
    emit("serve", backend=backend, port=s.server.bound_port,
         rpc_load="create+load file://+status->Ready+count+query on the "
                  "animals KB", animals_count=count)


def phase_store(s: Smoke) -> None:
    """Generate the KB, load it into the served tenant, count it."""
    from das_tpu.ingest import native
    from das_tpu.models.bio import write_bio_canonical

    params = kb_params(s.scale, s.seed)
    path = os.path.join(s.workdir, "bio_canonical.metta")
    lines = s.timed("generate_s", lambda: write_bio_canonical(path, **params))
    s.plain = s.timed("plain_parse_s", PlainKB, path)
    expected = s.plain.counts()

    if s.chips > 1:
        # the mesh size is a DasConfig field with no RPC: build the
        # tenant on make_mesh(chips) and attach it to the service
        from das_tpu.api.atomspace import DistributedAtomSpace
        from das_tpu.core.config import DasConfig

        das = DistributedAtomSpace(
            database_name="smoke", backend="sharded",
            config=DasConfig.from_env(mesh_shape=(s.chips,)),
        )
        s.token = s.service.attach_tenant("smoke", das)
    else:
        s.token = s.rpc("create", "smoke")
    s.das = s.service.tenants[s.token].das
    # the wire's `load` RPC takes general MeTTa (phase_serve proved it);
    # a canonical file goes through the tenant's own
    # load_canonical_knowledge_base — the protocol has no RPC for it
    s.timed("load_s", s.das.load_canonical_knowledge_base, path)
    s.wait_ready(s.token)
    count = s.rpc("count", s.token)
    check(count == str(expected),
          f"count {count} != generator's {expected}")
    ingest = ("columnar native scanner" if native.columnar_available()
              else "native scanner" if native.native_available()
              else "python decoder")
    emit("store", scale=s.scale, seed=s.seed, params=params,
         expression_lines=lines, nodes=expected[0], links=expected[1],
         count_rpc=count, ingest=ingest,
         load_via="DistributedAtomSpace.load_canonical_knowledge_base on "
                  "the served tenant (no canonical-load RPC)",
         file_mb=round(os.path.getsize(path) / 2**20, 1),
         memory_stats=memory_stats())

    # the per-query dispatcher (atomspace.py settle fall-through,
    # coalesce.py per-RPC fallback) is das.query(): count who reaches it
    original = s.das.query

    def spy(query, *args, **kwargs):
        s.direct_calls.append(type(query).__name__)
        return original(query, *args, **kwargs)

    s.das.query = spy

    # genes whose grounded answer is non-empty, sampled from --seed
    eligible = sorted(
        a for a, bs in s.plain.interacts.items()
        if any(s.plain.procs_of.get(a, set()) & s.plain.procs_of.get(b, set())
               for b in bs)
    )
    check(len(eligible) >= N_GROUNDED,
          f"only {len(eligible)} genes with a non-empty grounded answer")
    s.genes = s.rng.sample(eligible, N_GROUNDED)


def _pick_or_not(s: Smoke):
    """g1, g2, g3 with procs(g3) overlapping procs(g1): the NOT branch's
    answer is then a proper subset of procs(g3)."""
    g1, g2 = s.genes[0], s.genes[1]
    for p in sorted(s.plain.procs_of[g1]):
        for g3 in sorted(s.plain.genes_of[p]):
            if g3 not in (g1, g2) and (
                s.plain.procs_of[g3]
                - s.plain.procs_of[g1] - s.plain.procs_of[g2]
            ):
                return g1, g2, g3
    raise SmokeFailure("no gene shares a process with the first sample")


def phase_queries(s: Smoke) -> dict:
    """The served queries, each compared with the plain sets; then the
    route proof over the whole phase."""
    from das_tpu.query.ast import And, Link, Node, Not, Or, Variable

    db = s.das.db
    before = counters_snapshot(db)
    calls_before = len(s.direct_calls)
    results = []

    def compare(name, got, want, via="client query"):
        ok = got == want
        results.append({"query": name, "via": via, "rows": len(want[1]),
                        "equal": ok})
        check(ok, f"{name}: answer differs from the plain reference "
                  f"(got {len(got[1])} rows neg={got[0]}, "
                  f"want {len(want[1])} rows neg={want[0]})")

    # 8 grounded 3-clause conjunctions (the reference's scripts/benchmark.py
    # QUERY_1 shape with $1 bound), then each again: the second answer is a result-cache hit
    for rnd in ("first", "repeat"):
        for g in s.genes:
            compare(f"grounded3[{g}] {rnd}", s.rpc_query(dsl_grounded3(g)),
                    (False, s.plain.grounded3(g)))
    # one all-variable 2-clause join (whole-table Member side)
    want = s.plain.list_member()
    check(want, "all-variable join is empty for this seed")
    compare("all-variable List x Member", s.rpc_query(DSL_LIST_MEMBER),
            (False, want))
    # one Or/Not tree over one variable universe, through the DSL
    g1, g2, g3 = _pick_or_not(s)
    want = (s.plain.member_of(g3)
            - s.plain.member_of(g1) - s.plain.member_of(g2))
    compare("Or(Member,Member,Not(Member))",
            s.rpc_query(dsl_or_not(g1, g2, g3)), (True, want))
    n_rpc_conj, n_rpc_tree = 2 * N_GROUNDED + 1, 1
    after_rpc = counters_snapshot(db)
    rpc_direct = s.direct_calls[calls_before:]

    # conjunction BRANCHES under Or/Not: the postfix DSL folds the whole
    # stack at AND, so two Ands side by side cannot be written in it —
    # this one goes through DistributedAtomSpace.query on the served
    # tenant
    def branch(g):
        return And([
            Link("Member", [Node("Gene", g), Variable("$3")], True),
            Link("Member", [Variable("$2"), Variable("$3")], True),
        ])

    msg = s.das.query(Or([branch(g1), Not(branch(g3))]))
    compare("Or(And(..),Not(And(..)))", parse_answer(msg),
            (True, s.plain.branch(g3) - s.plain.branch(g1)),
            via="DistributedAtomSpace.query on the served tenant "
                "(postfix DSL cannot nest two ANDs)")
    after = counters_snapshot(db)

    route = delta(after["route"], before["route"])
    dispatch = delta(after["dispatch"], before["dispatch"])
    rpc_route = delta(after_rpc["route"], before["route"])
    cache_hits = (after["result_cache"]["hits"]
                  - before["result_cache"]["hits"])
    fused = "sharded" if s.chips > 1 else "fused"
    tree_fused = "sharded_tree_fused" if s.chips > 1 else "fused_tree"
    check(route.get("host", 0) == 0,
          f"{route.get('host')} queries fell to the host algebra")
    check(route.get("staged", 0) == 0,
          f"{route.get('staged')} queries fell to the staged path")
    check(rpc_route.get(fused, 0) == n_rpc_conj + (n_rpc_tree if s.chips > 1 else 0),
          f"route[{fused}] rose by {rpc_route.get(fused, 0)} over the RPC "
          f"queries, expected {n_rpc_conj}")
    check(route.get(tree_fused, 0) == n_rpc_tree + 1,
          f"route[{tree_fused}] rose by {route.get(tree_fused, 0)}, "
          f"expected {n_rpc_tree + 1}")
    # every non-cached conjunction dispatched at least one fused device
    # program (more only through capacity-retry rounds)
    check(dispatch.get(fused, 0) >= N_GROUNDED + 1,
          f"dispatch[{fused}] rose by {dispatch.get(fused, 0)}")
    check(dispatch.get(tree_fused, 0) >= n_rpc_tree + 1,
          f"dispatch[{tree_fused}] rose by {dispatch.get(tree_fused, 0)}")
    check(cache_hits >= N_GROUNDED,
          f"{cache_hits} result-cache hits for {N_GROUNDED} repeats")
    # only the tree query may reach the per-query dispatcher (that is
    # its designed route: plan_query declines Or); a conjunction there
    # means the batched fused path failed and was silently re-run
    check(rpc_direct == ["Or"] * n_rpc_tree,
          f"per-query dispatcher reached by {rpc_direct}")
    emit("queries", results=results, route_delta=route,
         dispatch_delta=dispatch, result_cache_hits=cache_hits,
         per_query_dispatcher=rpc_direct,
         fetches=after["fetches"] - before["fetches"])
    return {"equal": all(r["equal"] for r in results),
            "host_delta": route.get("host", 0), "n_queries": len(results)}


def phase_counts(s: Smoke) -> None:
    """Counts without materialization: the miner's batched count
    program (`FusedExecutor.count_batch`, one dispatch for the eight
    grounded conjunctions) and an all-variable conjunction agree with
    the per-query counts (and with the plain sets)."""
    from das_tpu.query import compiler
    from das_tpu.query.ast import And, Link, Node, Variable
    from das_tpu.query.fused import get_executor

    db = s.das.db

    def grounded(g):
        return And([
            Link("Member", [Node("Gene", g), Variable("V3")], True),
            Link("Member", [Variable("V2"), Variable("V3")], True),
            Link("Interacts", [Node("Gene", g), Variable("V2")], True),
        ])

    want = [len(s.plain.grounded3(g)) for g in s.genes]
    per_query = [compiler.count_matches(db, grounded(g)) for g in s.genes]
    check(per_query == want, f"count_matches {per_query} != plain {want}")
    plans = [compiler.plan_query(db, grounded(g)) for g in s.genes]
    batched = get_executor(db).count_batch(plans)
    check(batched == want, f"count_batch {batched} != plain {want}")
    # the all-variable conjunction of the query phase, counted without
    # materialization (whole-table Member side through the index join)
    all_var = And([
        Link("List", [Variable("V1"), Variable("V2")], True),
        Link("Member", [Variable("V1"), Variable("V2")], True),
    ])
    n = compiler.count_matches(db, all_var)
    want_all = len(s.plain.list_member())
    check(n == want_all, f"all-variable count {n} != plain {want_all}")
    emit("counts", grounded_counts=want, all_variable_count=n)


def phase_commit(s: Smoke) -> None:
    """One 10-expression transaction on the served tenant's store, then
    the acknowledged write read back through `client query`."""
    g = s.genes[0]
    before = s.rpc_query(dsl_grounded3(g))
    check(before == (False, s.plain.grounded3(g)), "pre-commit answer")
    mine = sorted(s.plain.procs_of[g])
    others = [x for x in sorted(s.plain.procs_of)
              if x != g and x not in s.plain.interacts.get(g, ())]
    tx = s.das.open_transaction()
    n_new = 0
    for x in s.rng.sample(others, 5):
        p = next((q for q in mine if q not in s.plain.procs_of[x]), None)
        check(p is not None, "no process left to add")
        tx.add(f'(Interacts "{g}" "{x}")')
        tx.add(f'(Member "{x}" "{p}")')
        n_new += s.plain.add_interacts(g, x) + s.plain.add_member(x, p)
    check(len(tx.expressions) == 10 and n_new == 10, "transaction size")
    version = getattr(s.das.db, "delta_version", None)
    s.das.commit_transaction(tx)
    want = s.plain.grounded3(g)
    after = s.rpc_query(dsl_grounded3(g))
    check(after == (False, want),
          "post-commit answer differs from the plain reference")
    check(len(after[1]) >= len(before[1]) + 5 and after != before,
          "the committed links did not change the answer")
    count = s.rpc("count", s.token)
    check(count == str(s.plain.counts()), f"post-commit count {count}")
    emit("commit", expressions=10, gene=g, rows_before=len(before[1]),
         rows_after=len(after[1]), count_rpc=count,
         delta_version=[version, getattr(s.das.db, "delta_version", None)])


def phase_placement(s: Smoke) -> None:
    """Four chips: the row-sharded tables really live on `chips`
    distinct devices, about 1/chips of the rows each."""
    import numpy as np

    report = {}
    for arity, bucket in s.das.db.tables.buckets.items():
        arr = bucket.targets          # [shards, rows per shard, arity]
        shards = arr.addressable_shards
        devs = sorted({sh.device.id for sh in shards})
        # pad rows carry negative targets: count the real ones per device
        real = [int((np.asarray(sh.data)[..., 0] >= 0).sum()) for sh in shards]
        report[arity] = {"shape": list(arr.shape), "devices": devs,
                         "real_rows_per_device": real}
        check(len(devs) == s.chips and len(shards) == s.chips,
              f"arity {arity}: shards on devices {devs}, want {s.chips}")
        quarter = sum(real) / s.chips
        check(all(abs(r - quarter) <= 0.05 * quarter + 1 for r in real),
              f"arity {arity}: rows per device {real}, not ~1/{s.chips} each")
    check(sum(sum(b["real_rows_per_device"]) for b in report.values())
          == s.plain.counts()[1], "sharded rows do not add up to the links")
    stats = memory_stats()[: s.chips]
    used = [m["bytes_in_use"] for m in stats]
    if device_info()["platform"] == "tpu":
        check(all(u for u in used), f"a device holds nothing: {used}")
        check(max(used) <= 1.5 * min(used),
              f"device memory is not spread evenly: {used}")
    emit("placement", buckets=report, memory_stats=stats)


def run_phases(scale: float, seed: int, chips: int) -> dict:
    """All phases of one run; returns the summary the gate decides on.
    Any failed check raises."""
    import das_tpu

    events = CacheEvents()
    s = Smoke(scale, seed, chips)
    try:
        s.timed("serve_s", phase_serve, s)
        s.timed("store_s", phase_store, s)
        if chips > 1:
            phase_placement(s)
        summary = s.timed("queries_s", phase_queries, s)
        if chips == 1:
            s.timed("counts_s", phase_counts, s)
            s.timed("commit_s", phase_commit, s)
        snap = counters_snapshot(s.das.db)
        emit("counters", route_counts=snap["route"],
             dispatch_counts=snap["dispatch"], fetch_counts=snap["fetches"],
             result_cache=snap["result_cache"],
             compile_cache_dir=das_tpu.compile_cache_dir(),
             compile_cache_hits=events.hits,
             compile_cache_misses=events.misses,
             coalescer={k: v for k, v in s.service.coalescer_stats().items()
                        if k in ("batches", "items", "max_batch",
                                 "cache_hits", "cache_misses")},
             memory_stats=memory_stats(),
             smoke_timings_s=s.timings)
        summary["route_counts"] = snap["route"]
        return summary
    finally:
        s.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    # the DEFAULT path is what is brought up: the program ledger swaps
    # jit for AOT compiles and tracing adds spans to every stage
    for name in ("DAS_TPU_PROFLOG", "DAS_TPU_TRACE"):
        if os.environ.get(name, "0").lower() not in ("", "0", "off", "false"):
            print(f"chip_smoke: unset {name} — it changes the path under "
                  "test", file=sys.stderr)
            return 2

    import das_tpu  # noqa: F401  (x64 on before the first jax use)

    device = device_info()
    on_chip = device["platform"] == "tpu"
    # a refusal prints nothing on stdout: no line there can be read as a
    # result
    if not on_chip and args.scale > REHEARSAL_MAX_SCALE:
        print(f"chip_smoke: no accelerator (platform {device['platform']}); "
              f"only a rehearsal at --scale <= {REHEARSAL_MAX_SCALE} runs "
              "without one", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {device['count']}", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    if on_chip and device["count"] != args.chips:
        print(f"chip_smoke: found {device['count']} chips; pass --chips "
              f"{device['count']} or expose {args.chips}", file=sys.stderr)
        return EXIT_NO_ACCELERATOR

    emit("gate", device=device, scale=args.scale, seed=args.seed,
         chips=args.chips)
    run_phases(args.scale, args.seed, args.chips)

    if not on_chip:
        print("chip_smoke: rehearsal phases passed, but the platform is "
              f"{device['platform']}, not tpu — refusing to report ok",
              file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
