"""The plain reference: the same questions answered from the seeded draws.

Built from `generator.Store`'s arrays (never from the file, never with
any das_tpu import): adjacency as numpy CSR for the base store, plus the
links the run's commits add, each stamped with the number of the commit
that added it.  Commits only add links, so the state after commit `v`
is "every link with stamp <= v", and a rule's rows, cut at v, are exact
for any v.  One rule file per query shape (`rules/<rule>.py`, over the
accessors below); a shape's JSON file names its rule.
"""

from __future__ import annotations

import hashlib
import re

import numpy as np

from benchmark.reference.generator import NODE_NAMES, Store, handle


def _csr(src: np.ndarray, dst: np.ndarray, n: int):
    order = np.argsort(src, kind="stable")
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=starts[1:])
    return starts, dst[order]


class PlainKB:
    def __init__(self, store: Store):
        self.store = store
        n_g, n_p = store.n_genes, store.n_processes
        self.members = store.members
        k = store.members.shape[1]
        self._genes_of = _csr(
            store.members.reshape(-1),
            np.repeat(np.arange(n_g, dtype=np.int32), k), n_p)
        pairs = store.interactions
        self._out = _csr(np.concatenate([pairs[:, 0], pairs[:, 1]]),
                         np.concatenate([pairs[:, 1], pairs[:, 0]]), n_g)
        # links added by commits: key -> {other: commit number}
        self.added_procs = {}     # gene -> {process: v}
        self.added_genes = {}     # process -> {gene: v}
        self.added_out = {}       # gene -> {gene: v}
        self.n_added = 0
        self._handles = {}    # node type -> _Handles
        self._rules = {}      # rule name -> loaded rule (see `rows`)

    # -- state at commit number v (None = everything so far) --------------

    @staticmethod
    def _with(base, added, v):
        """{id: stamp}: base links carry stamp 0."""
        out = dict.fromkeys(base.tolist(), 0)
        if added:
            for x, stamp in added.items():
                if v is None or stamp <= v:
                    out.setdefault(x, stamp)
        return out

    def procs_of(self, g: int, v=None) -> dict:
        return self._with(self.members[g], self.added_procs.get(g), v)

    def genes_of(self, p: int, v=None) -> dict:
        starts, dst = self._genes_of
        return self._with(dst[starts[p]:starts[p + 1]],
                          self.added_genes.get(p), v)

    def out_of(self, g: int, v=None) -> dict:
        starts, dst = self._out
        return self._with(dst[starts[g]:starts[g + 1]],
                          self.added_out.get(g), v)

    # -- commits -----------------------------------------------------------

    def add_member(self, g: int, p: int, v: int) -> bool:
        if p in self.procs_of(g):
            return False
        self.added_procs.setdefault(g, {})[p] = v
        self.added_genes.setdefault(p, {})[g] = v
        self.n_added += 1
        return True

    def add_interacts(self, a: int, b: int, v: int) -> bool:
        if b in self.out_of(a):
            return False
        self.added_out.setdefault(a, {})[b] = v
        self.n_added += 1
        return True

    def counts(self) -> tuple:
        nodes, links = self.store.counts()
        return nodes, links + self.n_added

    # -- answers -----------------------------------------------------------
    # A query shape's rule is a file of its own (`rules/<rule>.py`,
    # `harness.spec.load_rule`): rows(kb, key) -> {tuple of ids: stamp}.

    def rows(self, rule: str, key) -> dict:
        """The named rule's rows, carrying its COLUMNS.  Kept for
        tests/test_mesh_cell.py, which asks by name and which a benchmark
        PR may not edit; the harness goes through the loaded rule."""
        loaded = self._rules.get(rule)
        if loaded is None:
            from benchmark.harness.spec import load_rule

            loaded = self._rules[rule] = load_rule(rule)
        out = _NamedRows(loaded.rows(self, key))
        out.columns = loaded.COLUMNS
        return out

    def canonical_rows(self, rows, v=None, columns=None) -> list:
        """The rows present at commit number v, in the canonical text
        form `canonical_answer` gives a served answer: a row's bindings
        sorted by variable.  `columns` is the rule's COLUMNS."""
        columns = rows.columns if columns is None else columns
        order = sorted(range(len(columns)), key=lambda i: columns[i][0])
        text = ",".join(f"{columns[i][0]}=%s" for i in order)
        kept = [ids for ids, stamp in rows.items() if v is None or stamp <= v]
        # column by column: each a plain list comprehension over a table
        # that works a handle out the first time it is asked for
        handles = []
        for i in order:
            table = self._handles.get(columns[i][1])
            if table is None:
                table = self._handles[columns[i][1]] = _Handles(columns[i][1])
            handles.append([table[ids[i]] for ids in kept])
        return sorted(map(text.__mod__, zip(*handles)))


class _Handles(dict):
    """Node id -> handle of one node type, worked out when first asked."""

    def __init__(self, node_type: str):
        super().__init__()
        self.node_type, self.name = node_type, NODE_NAMES[node_type]

    def __missing__(self, i: int) -> str:
        h = self[i] = handle(self.node_type, self.name(i))
        return h


class _NamedRows(dict):
    """What `PlainKB.rows` returns: the rows with the rule's COLUMNS."""

    columns = None


# -- a served answer, brought to the same canonical form -------------------

_ASSIGNMENT = re.compile(r"\{([^{}]*)\}")
_BINDING = re.compile(r"'([^']+)': '([0-9a-f]{32})'")


def canonical_answer(msg: str):
    """`query`'s HANDLE-format reply -> sorted list of "var=handle,.."
    rows, or None for a reply that is not a plain assignment set."""
    if msg.startswith("NOT "):
        return None
    rows = []
    for inner in _ASSIGNMENT.findall(msg):
        pairs = _BINDING.findall(inner)
        if not pairs:
            return None
        rows.append(",".join(f"{k}={h}" for k, h in sorted(pairs)))
    rows.sort()
    return rows


def digest(rows: list) -> str:
    return hashlib.md5("\n".join(rows).encode()).hexdigest()
