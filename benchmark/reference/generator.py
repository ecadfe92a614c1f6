"""The benchmark's own store generator: a FlyBase-shaped store, in bulk.

Same shape as `das_tpu/models/bio.py write_bio_canonical` (the store
`chip_smoke.py` ran on the chip in PR 22): `n_genes` Gene nodes,
`n_processes` BiologicalProcess nodes, `members_per_gene` DISTINCT
memberships per gene, `n_interactions` gene pairs stored in both
orientations, `n_evaluations` `Evaluation(Predicate, List(gene,
process))` links.  The counts and the `skew` of the draws are a
PROFILE: the `shape` block of the cell's configuration file, scaled by
its `scale`.  `skew` 0 draws every index uniformly; `skew` > 0 sends a
uniform u through u^(1+skew) onto LOW indices, a power-law
participation profile with hub genes and processes (what bio.py's
`_skew_idx` documents, written again here: the reference shares no code
with the program).  One departure: the pairs are DISTINCT, so every
seed gives the same link counts (see Store).
Node names, link types and the canonical one-expression-per-line file
format are the program's input contract; the draws are numpy's, made in
bulk, so a run's store costs seconds of set-up, not a Python loop over
every link.  Nothing here imports das_tpu or jax.
"""

from __future__ import annotations

import hashlib

import numpy as np

#: the reference-scale profile (bench.py FLYBASE; SimplePatternMiner.ipynb
#: cell 0 of the reference repository: 2,584,508 nodes / 27,871,440 links).
#: No run reads it: a run's profile is its configuration's `shape`.  It
#: is the default of callers that give none: tests/ (tier 1), which build
#: `Store(scale, seed)` and which a benchmark PR may not edit.
FLYBASE = dict(
    n_genes=2_400_000, n_processes=180_000, members_per_gene=10,
    n_interactions=1_500_000, n_evaluations=435_000,
    link_types=["Member", "Interacts", "Evaluation", "List"], skew=0,
)

TYPE_NAMES = ("Gene", "BiologicalProcess", "Member", "Interacts",
              "Predicate", "Evaluation", "List")
LINK_TYPES = ("Member", "Interacts", "Evaluation", "List")
PREDICATE = "Predicate:has_name"
#: what `scale` multiplies; members_per_gene (a row width) is kept
COUNTS = ("n_genes", "n_processes", "n_interactions", "n_evaluations")


def kb_params(scale: float, shape: dict) -> dict:
    """`shape` x scale: every count scaled, members_per_gene kept."""
    lacking = [k for k in COUNTS + ("members_per_gene", "skew", "link_types")
               if k not in shape]
    if lacking:
        raise ValueError(f"the store's shape lacks {lacking}")
    if sorted(shape["link_types"]) != sorted(LINK_TYPES):
        raise ValueError(f"the generator writes {LINK_TYPES}, the shape "
                         f"asks for {shape['link_types']}")
    if not shape["skew"] >= 0:
        raise ValueError(f"skew {shape['skew']!r}")
    p = {k: max(1, int(shape[k] * scale)) for k in COUNTS}
    p["members_per_gene"] = int(shape["members_per_gene"])
    p["n_processes"] = max(p["n_processes"], 2 * p["members_per_gene"])
    return p


GENE_FORMAT = "GENE:{:07d}"
PROC_FORMAT = "GO:{:07d}"


def gene_name(i: int) -> str:
    return GENE_FORMAT.format(i)


def proc_name(i: int) -> str:
    return PROC_FORMAT.format(i)


#: node type -> the name of its i-th node
NODE_NAMES = {"Gene": gene_name, "BiologicalProcess": proc_name}


def handle(node_type: str, name: str) -> str:
    """Node handle as the reference DAS defines it: md5("<type> <name>")."""
    return hashlib.md5(f"{node_type} {name}".encode()).hexdigest()


class Store:
    """The seeded draws of one run, as arrays.

    members      int32 [n_genes, members_per_gene]  process ids, distinct per row
    interactions int32 [n_interactions, 2]          distinct unordered pairs, a != b
    evaluations  int32 [n_evaluations, 2]           distinct (gene, process)
    """

    def __init__(self, scale: float, seed: int, shape: dict = None):
        shape = FLYBASE if shape is None else shape
        self.params = kb_params(scale, shape)
        self.scale, self.seed = scale, seed
        self.skew = skew = float(shape["skew"])
        p = self.params
        rng = np.random.default_rng([int(seed), 0x0DA5])
        n_g, n_p, k = p["n_genes"], p["n_processes"], p["members_per_gene"]
        members = _indices(rng, n_p, (n_g, k), skew)
        while True:
            # a row with a repeated process is redrawn whole: k distinct
            # draws, as random.sample gives
            srt = np.sort(members, axis=1)
            bad = np.nonzero((srt[:, 1:] == srt[:, :-1]).any(axis=1))[0]
            if not len(bad):
                break
            members[bad] = _indices(rng, n_p, (len(bad), k), skew)
        self.members = members
        # EXACT counts, whatever the seed: the program pads its tables
        # to n + n/16 rows (storage/delta.py capacity_class), so a store
        # whose distinct-link count moved with the seed would give every
        # seed program shapes of its own and nothing would ever be found
        # in the compile cache.  So: exactly n_interactions distinct
        # unordered pairs, exactly n_evaluations distinct (gene, process).
        self.interactions = _distinct_pairs(
            rng, p["n_interactions"], n_g, n_g, unordered=True, skew=skew)
        self.evaluations = _distinct_pairs(
            rng, p["n_evaluations"], n_g, n_p, unordered=False, skew=skew)

    @property
    def n_genes(self) -> int:
        return self.params["n_genes"]

    @property
    def n_processes(self) -> int:
        return self.params["n_processes"]

    def counts(self) -> tuple:
        """(nodes, links) as `count_atoms` reports them: typedefs are not
        atoms, a nested (List ..) is a link of its own, a repeated
        expression is one atom."""
        nodes = self.n_genes + self.n_processes + (
            1 if len(self.evaluations) else 0)
        # Member + Interacts in both orientations + one List and one
        # Evaluation per evaluation
        return nodes, int(self.members.size + 2 * len(self.interactions)
                          + 2 * len(self.evaluations))


def _indices(rng, n: int, size, skew: float) -> np.ndarray:
    """int32 draws from 0..n-1.  skew 0: uniform, ONE `rng.integers`
    call (the draws every store has had since PR 25); skew > 0: a
    uniform u through u^(1+skew), so index i is drawn with probability
    ((i+1)/n)^(1/(1+skew)) - (i/n)^(1/(1+skew)): mass on low indices."""
    if skew <= 0:
        return rng.integers(0, n, size=size, dtype=np.int32)
    return np.minimum(n - 1, (n * rng.random(size) ** (1.0 + skew))
                      .astype(np.int32))


def _distinct_pairs(rng, n: int, n_a: int, n_b: int, unordered: bool,
                    skew: float = 0.0):
    """int32 [n, 2]: n distinct pairs in draw order, each end drawn by
    `_indices`.  With `unordered`, (a, b) and (b, a) are one pair and
    a != b."""
    if n > (n_a * n_b) // 4:
        raise ValueError("too many distinct pairs asked of too few nodes")
    out = np.empty((0, 2), dtype=np.int32)
    while len(out) < n:
        more = np.stack([_indices(rng, n_a, n, skew),
                         _indices(rng, n_b, n, skew)], axis=1)
        out = np.concatenate([out, more])
        if unordered:
            out = out[out[:, 0] != out[:, 1]]
            lo, hi = out.min(axis=1), out.max(axis=1)
        else:
            lo, hi = out[:, 0], out[:, 1]
        key = lo.astype(np.int64) * np.int64(max(n_a, n_b)) + hi
        _, first = np.unique(key, return_index=True)
        out = out[np.sort(first)]
    return np.ascontiguousarray(out[:n])


def _digits(n: int, width: int = 7) -> np.ndarray:
    """uint8 [n, width]: zero-padded decimal digits of 0..n-1."""
    idx = np.arange(n, dtype=np.int64)
    cols = [(idx // 10 ** k) % 10 for k in range(width - 1, -1, -1)]
    return (np.stack(cols, axis=1) + ord("0")).astype(np.uint8)


def _fixed_lines(template: str, columns: list) -> bytes:
    """Fill a fixed-width line template: the i-th `{}` takes the digit
    rows `table[idx]` of `columns[i] = (table, idx)`."""
    parts = template.split("{}")
    if len(parts) != len(columns) + 1:
        raise ValueError("template/columns mismatch")
    n = len(columns[0][1])
    width = sum(len(x) for x in parts) + sum(t.shape[1] for t, _ in columns)
    out = np.empty((n, width), dtype=np.uint8)
    at = 0
    for lit, col in zip(parts, columns + [None]):
        out[:, at:at + len(lit)] = np.frombuffer(lit.encode(), dtype=np.uint8)
        at += len(lit)
        if col is not None:
            table, idx = col
            out[:, at:at + table.shape[1]] = table[idx]
            at += table.shape[1]
    return out.tobytes()


_CHUNK = 1 << 20


def write_canonical(store: Store, path: str) -> int:
    """The canonical .metta file of `store`: types, then terminals, then
    one toplevel expression per line.  Returns expression lines written."""
    n_g, n_p = store.n_genes, store.n_processes
    gene, proc = '"Gene GENE:{}"', '"BiologicalProcess GO:{}"'
    g_digits, p_digits = _digits(n_g), _digits(n_p)

    def emit(w, template, tables, cols) -> int:
        for lo in range(0, len(cols[0]), _CHUNK):
            w.write(_fixed_lines(
                template,
                [(t, c[lo:lo + _CHUNK]) for t, c in zip(tables, cols)]))
        return len(cols[0])

    with open(path, "wb", buffering=1 << 22) as w:
        for t in TYPE_NAMES:
            w.write(f"(: {t} Type)\n".encode())
        emit(w, '(: "GENE:{}" Gene)\n', [g_digits], [np.arange(n_g)])
        emit(w, '(: "GO:{}" BiologicalProcess)\n', [p_digits],
             [np.arange(n_p)])
        if len(store.evaluations):
            w.write(f'(: "{PREDICATE}" Predicate)\n'.encode())
        k = store.members.shape[1]
        lines = emit(w, f"(Member {gene} {proc})\n", [g_digits, p_digits],
                     [np.repeat(np.arange(n_g, dtype=np.int32), k),
                      store.members.reshape(-1)])
        # both orientations, pair by pair (a->b then b->a)
        pairs = store.interactions
        lines += emit(w, f"(Interacts {gene} {gene})\n", [g_digits, g_digits],
                      [pairs.reshape(-1), pairs[:, ::-1].reshape(-1)])
        ev = store.evaluations
        lines += emit(
            w, f'(Evaluation "Predicate {PREDICATE}" (List {gene} {proc}))\n',
            [g_digits, p_digits], [ev[:, 0], ev[:, 1]])
    return lines
