"""Closed-form counting for star-shaped conjunctions (miner joints).

The pattern miner's composite queries (mining/miner.py `_composite`) are
STAR joins: every positive term shares exactly one variable (V0) and every
other variable is free and appears in exactly one term.  For that shape
the match count has a closed form that needs NO pair expansion:

    count = Σ_v  Π_t  deg_t(v)

where deg_t(v) is the number of links matching term t with the shared
variable bound to atom row v.  Each composite assignment is determined by
one link choice per term (free variables are bijective with a term's
matching rows), so the product over independent per-v choices is exact —
the same number the reference's nested-loop And join (pattern_matcher.py
:732-738) and the fused pair-expansion path produce.

Why this matters: the general fused path materializes the join output
(24M-row capacity buffers at FlyBase scale — r03's joint phase ran
33.5 ms/link against a <20 target, execution-bound).  Here a probed
term contributes its sparse support (unique shared-variable values +
multiplicities) and a whole-table term stays SYMBOLIC: its degree at
any support point is a searchsorted range length on the existing
(type<<32|target) sorted index, so a lane containing any probed term is
a few thousand binary searches and multiply-adds — no join buffers, no
per-shape capacity learning.  A table ⊙ table product (the rare
all-whole-table prefix) extracts the smaller side's support by
run-length over its contiguous sorted-key slice and proceeds sparse —
no dense [atom_count] vector exists anywhere in the host edition.

**The reseed quirk is computed in-program, not dodged.**  The reference
And re-seeds an emptied accumulator from the next positive term
(pattern_matcher.py:725-728; ast.py keeps parity): the accumulator
evolves as E_1 = t_1, E_i = (t_i if E_{i-1} = ∅ else E_{i-1} ⋈ t_i),
and the answer is |E_n|.  On degree vectors that IS the fold

    R ← deg_1 ;  R ← (deg_i  if Σ R = 0  else  R ⊙ deg_i) ;  count = Σ R

because a reseeded accumulator holds exactly term i's assignments —
whose degree vector over the shared variable is deg_i — and every
subsequent join multiplies pointwise.  One special case dominates: an
EMPTY TERM (S_i = Σ deg_i = 0) makes the reference's And return
no-match outright (Link.matched is False before any join), so any
S_i = 0 answers 0 regardless of the fold.  With that guard the star
route is TOTAL for its shape: every lane gets an exact reference-equal
count, zeros included — no general-path fallback, which at FlyBase
scale would mean compiling whole-table join programs just to re-derive
quirk verdicts.

Caches (host edition: keyed on segment identities; device edition: on
the live DeviceBucket identity, so an incremental commit naturally
invalidates): sparse probe supports per (arity, type, fixed) and
whole-table run-length supports per (arity, type, position).  A handful
of terms recur across the miner's hundreds of joints, so everything
amortizes.  Both editions keep the two classes apart: probe supports
are many and cheap (a FIFO bounded by count), whole-table supports are
few and dear (the host edition keeps one per joined (type, position)
for as long as the table's segments live: `_table_sparse`).

Routing: `plan_star` recognizes the shape (ordered terms only, no
negation, no eq_pairs, no templates); everything else falls through to
the general executors.  Known tolerance (shared with the fused path):
dangling (-1) element rows never join here, while the host algebra would
join two danglings with identical hex — impossible in converter output.

**Two executions of the same algebra** (`DAS_TPU_STAR_FOLD`, default
`host`; count-identical, differentially asserted in
tests/test_starcount.py):

* `host` — sparse supports from a host searchsorted probe, whole-table
  degrees as range lengths at the support points; table ⊙ table extracts
  the smaller side's support by run-length over its sorted key slice —
  NO dense [atom_count] vector exists anywhere; zero device work.
  Rationale: a mixed lane's arithmetic is a few thousand
  multiply-adds, while the device edition pays per-lane dispatch +
  one host sync per probe AND its
  whole-table degree bincounts lower to TPU scatter-adds at ~5 s per
  24M-element vector — at r04 those made the joint phase run 21-40 s
  for 374 lanes.  The only edition available on dev-less backends (the
  mesh store folds here regardless of the env).
* `device` — every lane through the jitted degree-vector fold with
  lane-grouped fetches; kept for differential testing and as the
  pattern for a future multi-chip fold."""

from __future__ import annotations

import os
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from das_tpu import obs

FETCHES = {"n": 0}


def _enabled() -> bool:
    return os.environ.get("DAS_TPU_STAR", "1") != "0"


# ---------------------------------------------------------------------------
# shape detection
# ---------------------------------------------------------------------------


class StarLane:
    """One star-shaped count query: per-term degree specs in REFERENCE
    order (the prefix verdict is order-sensitive)."""

    __slots__ = ("specs",)

    def __init__(self, specs):
        # spec: (arity, type_id, v0_pos, fixed) — fixed == () ⇒ whole-table
        self.specs = specs


def plan_star(db, plans) -> Optional[StarLane]:
    """Recognize a star conjunction in a list of compiler.TermPlan.
    Returns None when the shape doesn't apply (caller falls back)."""
    if not _enabled() or plans is None or not isinstance(plans, list):
        return None
    if len(plans) < 2:
        return None
    var_seen: Dict[str, int] = {}
    for p in plans:
        if p.negated or p.ctype is not None or p.type_id is None:
            return None
        if p.eq_pairs:
            return None
        for name in p.var_names:
            var_seen[name] = var_seen.get(name, 0) + 1
    shared = [name for name, n in var_seen.items() if n == len(plans)]
    if len(shared) != 1:
        return None
    if any(n != 1 for name, n in var_seen.items() if name != shared[0]):
        return None
    s = shared[0]
    specs = []
    for p in plans:
        v0_pos = p.var_cols[p.var_names.index(s)]
        specs.append((p.arity, p.type_id, v0_pos, tuple(p.fixed)))
    return StarLane(tuple(specs))


# ---------------------------------------------------------------------------
# degree vectors
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("atom_count",))
def _deg_vector(type_ids, targets_col, type_id, atom_count: int):
    """Dense degree vector: deg[v] = |{links of type_id with column == v}|."""
    mask = type_ids == type_id
    safe = jnp.clip(targets_col, 0, atom_count - 1)
    contrib = (mask & (targets_col >= 0)).astype(jnp.int32)
    return jnp.zeros(atom_count, dtype=jnp.int32).at[safe].add(contrib)


@partial(jax.jit, static_argnames=("atom_count",))
def _scatter_deg(vals, mask, atom_count: int):
    """Degree vector of a probed term's (padded) shared-variable column."""
    ok = mask & (vals >= 0)
    safe = jnp.clip(vals, 0, atom_count - 1)
    return jnp.zeros(atom_count, dtype=jnp.int32).at[safe].add(
        ok.astype(jnp.int32)
    )


def _get_deg(db, arity: int, type_id: int, pos: int):
    """Cached whole-table degree vector.  Validity is (bucket identity,
    atom_count): a commit swaps the buckets it touches, but an UNTOUCHED
    arity keeps its bucket object while fin.atom_count grows — a
    bucket-only check would then serve a stale-length vector into the
    fold (shape mismatch or silent undercount of new atoms)."""
    cache = getattr(db, "_star_deg_cache", None)
    if cache is None:
        cache = db._star_deg_cache = {}
    bucket = db.dev.buckets.get(arity)
    if bucket is None or bucket.size == 0:
        return None
    atom_count = int(db.fin.atom_count)
    key = (arity, type_id, pos)
    hit = cache.get(key)
    if hit is not None and hit[0] is bucket and hit[1] == atom_count:
        return hit[2]
    deg = _deg_vector(
        bucket.type_id, bucket.targets[:, pos], np.int32(type_id), atom_count
    )
    # dense vectors are [atom_count] int32 (~120 MB each at reference
    # scale): bound THEM by count separately from the cheap probe-column
    # entries, or a few dozen distinct whole-table terms would exhaust
    # HBM alongside the store
    # dense keys end in a position INT; probe-column keys end in the
    # fixed tuple
    if sum(isinstance(k[2], int) for k in cache) >= 16:
        _evict_oldest(cache, lambda k: isinstance(k[2], int), 12)
    cache.pop(key, None)  # refresh moves the entry to the FIFO back
    cache[key] = (bucket, atom_count, deg)
    return deg


@partial(jax.jit, static_argnames=("pos",))
def _gather_col(targets, local, pos: int):
    safe = jnp.clip(local, 0, targets.shape[0] - 1)
    return targets[safe, pos]


def _term_deg(db, spec):
    """Degree vector of one term; None when the bucket is missing (the
    term is empty — count 0).  Probed terms are cached like whole-table
    ones: the miner reuses the same ~100 candidate terms across hundreds
    of composites, and each probe pays a capacity-check fetch (a host
    sync) that the cache amortizes away."""
    arity, type_id, v0_pos, fixed = spec
    if not fixed:
        return _get_deg(db, arity, type_id, v0_pos)
    cache = getattr(db, "_star_deg_cache", None)
    if cache is None:
        cache = db._star_deg_cache = {}
    bucket = db.dev.buckets.get(arity)
    if bucket is None or bucket.size == 0:
        return None
    # keyed WITHOUT the shared-variable position: the blocking
    # capacity-check fetch belongs to the probe, and the same probe can
    # appear with the shared variable at different positions — only the
    # cheap jitted gather differs per position
    key = (arity, type_id, fixed)
    hit = cache.get(key)
    if hit is not None and hit[0] is bucket:
        local, mask = hit[2]
    else:
        padded = db.probe_ordered_padded(arity, type_id, fixed)
        local, mask = padded
        # cache SMALL probe columns only: an overflow-grown probe is
        # padded to its learned capacity, and hundreds of multi-MB
        # cached rows would silently compete with the store for HBM
        if local.shape[0] <= (1 << 20):
            if len(cache) > 256:
                _evict_oldest(
                    cache, lambda k: not isinstance(k[2], int), 192
                )
            cache.pop(key, None)  # refresh -> FIFO back
            cache[key] = (bucket, None, (local, mask))
    vals = _gather_col(bucket.targets, local, v0_pos)
    return _scatter_deg(vals, mask, int(db.fin.atom_count))


# ---------------------------------------------------------------------------
# the prefix cascade
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n",))
def _star_fold(degs, n: int):
    """(per-term row counts S[n], reference-fold count) — the reseeding
    accumulator computed on degree vectors (module docstring)."""
    term_totals = jnp.stack([d.sum(dtype=jnp.int64) for d in degs])
    acc = degs[0].astype(jnp.int64)
    for i in range(1, n):
        d = degs[i].astype(jnp.int64)
        # E_{i-1} empty ⇒ this term RESEEDS the accumulator
        acc = jnp.where(acc.sum() == 0, d, acc * d)
    return term_totals, acc.sum()


def _dispatch(db, lane: StarLane):
    """Queue one lane's fold (async); returns device (S, count) or an
    immediate exact 0 (int) when a term's bucket is absent."""
    degs = []
    for spec in lane.specs:
        deg = _term_deg(db, spec)
        if deg is None:
            return 0
        degs.append(deg)
    return _star_fold(tuple(degs), len(degs))


#: lanes dispatched between fetches — each PROBED term materializes a
#: transient dense [atom_count] vector (~120 MB at reference scale), so
#: unbounded batches would queue tens of GB ahead of one transfer; 12
#: bounds transients to ~4.3 GB worst case (3 probed terms per lane)
#: while keeping the fetch count (each a host sync) low
GROUP = 12


# ---------------------------------------------------------------------------
# host edition: sparse supports, zero device round trips
# ---------------------------------------------------------------------------


def _host_cache(db) -> Dict:
    """The FIFO of GROUNDED supports (`_host_sparse_deg`)."""
    cache = getattr(db, "_star_host_cache", None)
    if cache is None:
        cache = db._star_host_cache = {}
    return cache


def _table_cache(db) -> Dict:
    """The kept WHOLE-TABLE supports (`_table_sparse`): one entry per
    (arity, type_id, position) some query joined on, outside the FIFO
    of grounded supports."""
    cache = getattr(db, "_star_table_cache", None)
    if cache is None:
        cache = db._star_table_cache = {}
    return cache


def _same_segments(kept, segments) -> bool:
    """THE validity rule of both host classes: an entry is served only
    while the store's segments are the objects it was read from (a
    commit swaps or extends the segment list of the arities it
    touches)."""
    return len(kept) == len(segments) and all(
        a is b for a, b in zip(kept, segments)
    )


def _evict_oldest(cache, pred, keep: int) -> None:
    """FIFO-evict entries matching ``pred`` down to ``keep`` (dict
    preserves insertion order, so the front of the iteration is the
    oldest).  A miner cycling >256 distinct grounded terms keeps its
    working set instead of rebuilding the whole key class from scratch
    (its whole-table supports are not in this FIFO: `_table_cache`)."""
    matching = [k for k in cache if pred(k)]
    for k in matching[: max(0, len(matching) - keep)]:
        del cache[k]


def _host_sparse_deg(db, spec):
    """((sorted unique shared-variable values, int64 multiplicities),
    total) of a probed term — the shared host probe
    (storage/atom_table.py host_probe_locals: the same algorithm and the
    same index copies in both editions).  Cached: the miner reuses ~100
    candidate terms across hundreds of composites."""
    from das_tpu.storage.atom_table import host_probe_locals, host_segments

    arity, type_id, v0_pos, fixed = spec
    segments = host_segments(db, arity)
    if not segments:
        return None
    cache = _host_cache(db)
    key = ("sparse", arity, type_id, v0_pos, fixed)
    hit = cache.get(key)
    if hit is not None and _same_segments(hit[0], segments):
        return hit[1]
    chunks = []
    for b in segments:
        local = host_probe_locals(b, type_id, fixed)
        if local.size == 0:
            continue
        v0 = b.targets[local, v0_pos]
        v0 = v0[v0 >= 0]  # device parity: dangling rows never scatter
        if v0.size:
            chunks.append(v0)
    if chunks:
        idx, cnt = np.unique(np.concatenate(chunks), return_counts=True)
        cnt = cnt.astype(np.int64)
        ent = ((idx.astype(np.int64), cnt), int(cnt.sum()))
    else:
        e = np.empty(0, dtype=np.int64)
        ent = ((e, e), 0)
    if len(cache) > 256:
        _evict_oldest(cache, lambda k: k[0] == "sparse", 192)
    cache.pop(key, None)  # refresh -> FIFO back
    cache[key] = (tuple(segments), ent)
    return ent


def _mul(acc, d):
    """Pointwise product of two sparse degree representations
    (sorted unique idx, cnt) — intersection of supports."""
    ai, ac = acc
    di, dc = d
    common, ia, ib = np.intersect1d(
        ai, di, assume_unique=True, return_indices=True
    )
    return common, ac[ia] * dc[ib]


def _rep_sum(d) -> int:
    return int(d[1].sum())


def _table_total(db, arity: int, type_id: int, v0_pos: int) -> int:
    """Exact DEGREE-SUM of a whole-table term: rows of the type whose
    shared-variable position holds a REAL atom.  Computed as the
    [tid<<32, tid<<32 + 2^31) range on the (type<<32|target) sorted key
    — a dangling (-1) target ORs to key -1 and falls outside, so this
    equals the dense edition's `col >= 0` bincount sum exactly (a raw
    key_type range would count dangling rows the dense sum excludes,
    corrupting the empty-term guard and any reseed that lands on a
    symbolic table term)."""
    from das_tpu.storage.atom_table import host_segments

    base = np.int64(type_id) << 32
    total = 0
    for b in host_segments(db, arity):
        keys = b.key_type_pos[v0_pos]
        total += int(
            np.searchsorted(keys, base + (np.int64(1) << 31), side="left")
        ) - int(np.searchsorted(keys, base, side="left"))
    return total


def _table_deg_at(db, spec, idx: np.ndarray) -> np.ndarray:
    """deg_t(v) for a WHOLE-TABLE term at the given atom rows only:
    per-segment searchsorted range lengths on the (type<<32|target) sorted
    key — identical numbers to the dense bincount's entries at `idx`,
    without ever materializing a [atom_count] vector (the dense build is
    a ~1 s gather+bincount pass per (type, position) at reference scale;
    a mixed lane only ever needs the degrees on its sparse support)."""
    from das_tpu.storage.atom_table import host_segments

    arity, type_id, v0_pos, _ = spec
    out = np.zeros(idx.shape[0], dtype=np.int64)
    base = np.int64(type_id) << 32
    for b in host_segments(db, arity):
        keys = b.key_type_pos[v0_pos]
        q = base | idx.astype(np.int64)
        lo = np.searchsorted(keys, q, side="left")
        hi = np.searchsorted(keys, q, side="right")
        out += hi - lo
    return out


def _table_sparse(db, spec):
    """((sorted unique shared-variable values, int64 multiplicities),
    total) of a WHOLE-TABLE term, extracted by run-length over the
    CONTIGUOUS (type<<32|target) sorted-key slice — the slice is already
    sorted, so uniques are np.diff boundaries: one linear pass, no
    bincount, no [atom_count] vector.

    KEPT, not cached like the probe supports: the pass is dear (148 ms
    over the 7.2 M-row `Member` slice of the FlyBase store at scale 0.3:
    PERF.md §6, PR 35) and its class is bounded by the schema, one
    entry per joined (type, position), so the entry lives as long as
    the arity's segments are the objects it was read from and is
    replaced in place when they are not.  The grounded supports' FIFO
    (`_host_sparse_deg`: one insert per distinct grounded term, so per
    query under distinct keys) never sees it."""
    arity, type_id, v0_pos, _ = spec
    from das_tpu.storage.atom_table import host_segments

    segments = host_segments(db, arity)
    if not segments:
        return None
    cache = _table_cache(db)
    key = (arity, type_id, v0_pos)
    hit = cache.get(key)
    if hit is not None and _same_segments(hit[0], segments):
        if obs.enabled():
            obs.counter("planner.table_hits").inc()
        return hit[1]
    # a whole-table extraction: the first read of a table (the
    # planner's exact_join_rows, a table ⊙ table fold) and the first
    # after each commit that swapped its arity's segment list
    if obs.enabled():
        obs.counter("planner.table_extractions").inc()
    with obs.span("planner.stats", what="table_sparse") as sp:
        base = np.int64(type_id) << 32
        parts = []  # (idx, cnt) per segment
        for b in segments:
            keys = b.key_type_pos[v0_pos]
            lo = int(np.searchsorted(keys, base, side="left"))
            hi = int(np.searchsorted(keys, base + (np.int64(1) << 31), side="left"))
            if hi <= lo:
                continue
            vals = keys[lo:hi] - base  # sorted, dangling-free by construction
            starts = np.r_[0, np.flatnonzero(np.diff(vals)) + 1]
            parts.append((vals[starts], np.diff(np.r_[starts, vals.size])))
        if not parts:
            ent = ((np.empty(0, np.int64), np.empty(0, np.int64)), 0)
        elif len(parts) == 1:
            idx, cnt = parts[0]
            ent = ((idx, cnt.astype(np.int64)), int(cnt.sum()))
        else:
            # overlay segments: merge run-length pairs (same value can appear
            # in several segments)
            allv = np.concatenate([p[0] for p in parts])
            allc = np.concatenate([p[1] for p in parts]).astype(np.int64)
            order = np.argsort(allv, kind="stable")
            sv, sc = allv[order], allc[order]
            starts = np.r_[0, np.flatnonzero(np.diff(sv)) + 1]
            csum = np.r_[0, np.cumsum(sc)]
            bounds = np.r_[starts, sv.size]
            cnt = csum[bounds[1:]] - csum[bounds[:-1]]
            ent = ((sv[starts], cnt), int(cnt.sum()))
        sp.set(version=getattr(db, "delta_version", None),
               rows=int(ent[1]))
    # the arity's other entries read from segments that are gone would
    # each pin a whole pre-commit bucket until their own next read
    for k in [
        k for k, (kept, _e) in cache.items()
        if k[0] == arity and not _same_segments(kept, segments)
    ]:
        del cache[k]
    cache[key] = (tuple(segments), ent)
    return ent


def _host_count(db, lane: StarLane) -> int:
    """One lane, exact, entirely host-side: the module-docstring fold on
    (representation, total) degree entries.

    Representations: ``("table", spec)`` — a whole-table term held
    SYMBOLIC; sparse ``(idx, cnt)`` — a support with multiplicities.
    The fold multiplies symbolically where it can: sparse ⊙ table is a
    vectorized searchsorted at the support points.  table ⊙ table
    extracts the SMALLER side's support by run-length over its sorted
    key slice (one linear pass) and proceeds sparse — no [atom_count]
    dense vector exists anywhere in this edition."""
    reps = []  # (rep, total)
    for spec in lane.specs:
        arity, type_id, v0_pos, fixed = spec
        if not fixed:
            total = _table_total(db, arity, type_id, v0_pos)
            ent = (("table", spec), total)
        else:
            ent = _host_sparse_deg(db, spec)
        if ent is None or ent[1] == 0:
            return 0  # empty positive term: And fails outright
        reps.append(ent)

    def is_table(r):
        return isinstance(r, tuple) and isinstance(r[0], str)

    def mul(a, a_total, b, b_total):
        a_tab, b_tab = is_table(a), is_table(b)
        if a_tab and b_tab:
            # materialize the smaller table sparsely, keep the other
            # symbolic — the product then rides the sparse ⊙ table path
            if b_total < a_total:
                a, b = b, a
            ent = _table_sparse(db, a[1])
            a = ent[0] if ent is not None else (
                np.empty(0, np.int64), np.empty(0, np.int64)
            )
            a_tab = False
        if a_tab or b_tab:
            rep, tab = (b, a) if a_tab else (a, b)
            idx, cnt = rep  # sparse ⊙ table: degrees at the support
            out = cnt * _table_deg_at(db, tab[1], idx)
            keep = out != 0
            return idx[keep], out[keep]
        return _mul(a, b)

    acc, acc_total = reps[0]
    for d, d_total in reps[1:]:
        if acc_total == 0:
            acc, acc_total = d, d_total  # reference reseed quirk
        else:
            acc = mul(acc, acc_total, d, d_total)  # never symbolic after
            acc_total = _rep_sum(acc)
    return acc_total


def _device_count_group(db, lanes: Sequence[StarLane]) -> List[int]:
    """The device fold over a lane list: async dispatches, one host fetch
    per GROUP of lanes."""
    results: List[int] = []
    for g in range(0, len(lanes), GROUP):
        outs = [_dispatch(db, lane) for lane in lanes[g : g + GROUP]]
        FETCHES["n"] += 1
        fetched = jax.device_get([o for o in outs if not isinstance(o, int)])
        it = iter(fetched)
        for o in outs:
            if isinstance(o, int):
                results.append(o)
                continue
            term_totals, count = next(it)
            if (term_totals == 0).any():
                results.append(0)  # empty positive term: And fails outright
            else:
                results.append(int(count))
    return results


def star_count_many(db, lanes: Sequence[StarLane]) -> List[int]:
    """Count every lane exactly.  Host edition (default): zero device
    work, zero fetches — sparse supports for probed terms, symbolic
    whole-table terms, run-length extraction of the smaller side for
    table ⊙ table products.  Device edition (`DAS_TPU_STAR_FOLD=device`, single-chip
    buffers required — the mesh store always folds host-side): every
    lane through the jitted degree-vector fold, one host fetch per GROUP
    of lanes.  A dense-lane DEVICE batch was tried and reverted: XLA
    lowers the degree bincount as a scatter-add, which at 24M elements
    runs ~5 s/vector on TPU vs ~0.7 s for the host bincount — the
    measured r04 device-fold joint times (21-40 s) were these scatters,
    not dispatch alone.  Every edition computes the reseed semantics
    exactly."""
    if os.environ.get("DAS_TPU_STAR_FOLD", "host") != "device" or not hasattr(
        db, "dev"
    ):
        return [_host_count(db, lane) for lane in lanes]
    return list(_device_count_group(db, lanes))


def try_star_count(db, plans) -> Optional[int]:
    """Single-query surface for compiler.count_matches; None = not star."""
    lane = plan_star(db, plans)
    if lane is None:
        return None
    return star_count_many(db, [lane])[0]
