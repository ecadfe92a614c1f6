"""The plain reference: the same questions answered from the seeded draws.

Built from `generator.Store`'s arrays (never from the file, never with
any das_tpu import): adjacency as numpy CSR for the base store, plus the
links the run's commits add, each stamped with the number of the commit
that added it.  Commits only add links, so the state after commit `v`
is "every link with stamp <= v", and `answer(shape, gene, v)` is exact
for any v.  One rule per query shape; a shape's JSON file names its rule.
"""

from __future__ import annotations

import hashlib
import re

import numpy as np

from benchmark.reference.generator import Store, gene_name, handle, proc_name


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
        self._gh = {}
        self._ph = {}

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

    # -- answers: {(gene $2, process $3): stamp} ---------------------------

    def grounded3(self, g: int) -> dict:
        """And(Member(g,$3), Member($2,$3), Interacts(g,$2))"""
        mine = self.procs_of(g)
        rows = {}
        for x, s_int in self.out_of(g).items():
            for p, s_x in self.procs_of(x).items():
                if p in mine:
                    rows[(x, p)] = max(s_int, s_x, mine[p])
        return rows

    def shared2(self, g: int) -> dict:
        """And(Member(g,$3), Member($2,$3))"""
        rows = {}
        for p, s_p in self.procs_of(g).items():
            for x, s_x in self.genes_of(p).items():
                rows[(x, p)] = max(s_p, s_x)
        return rows

    RULES = ("grounded3", "shared2")

    def rows(self, rule: str, g: int) -> dict:
        if rule not in self.RULES:
            raise KeyError(f"the plain reference has no rule {rule!r}")
        return getattr(self, rule)(g)

    def gene_handle(self, i: int) -> str:
        h = self._gh.get(i)
        if h is None:
            h = self._gh[i] = handle("Gene", gene_name(i))
        return h

    def proc_handle(self, i: int) -> str:
        h = self._ph.get(i)
        if h is None:
            h = self._ph[i] = handle("BiologicalProcess", proc_name(i))
        return h

    def canonical_rows(self, rows, v=None) -> list:
        """The rows present at commit number v, in the canonical text
        form `canonical_answer` gives a served answer."""
        return sorted(
            f"$2={self.gene_handle(x)},$3={self.proc_handle(p)}"
            for (x, p), stamp in rows.items() if v is None or stamp <= v
        )


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
