"""dasdur durability suite (marker `dur`, standalone:
ops/pytests.sh dur) — ISSUE 15.

Pins, in order of load-bearing-ness:
  * CRASH-POINT MATRIX: a seeded fault at EVERY new persist site
    (snapshot_write / snapshot_rename / wal_append / wal_fsync /
    restore_read) × recover via restore() × bio-suite answers
    bit-identical to the uncrashed run — on TensorDB AND the 8-way
    mesh; a WAL-site failure additionally proves commit atomicity
    (store at the pre-commit state, the SAME delta commits after);
  * torn-tail WAL truncation: a crash mid-append leaves a partial
    frame; restore truncates it at the last valid boundary and NEVER
    replays it;
  * corrupt-section fallback: a flipped byte in the newest generation
    is detected by the manifest CRC and restore falls back to the
    prior generation + ITS WAL — same answers, typed telemetry;
  * warm-bundle staleness: a bundle recorded at snapshot version v is
    discarded when WAL replay moved the store past v (the result-cache
    delta_version guard applied to persistence);
  * warm-restore: a restored replica answers with ZERO capacity
    retries (1 compiled program) where a cold replica pays the retry
    tier — the CapStore/planner-stats bundle honored;
  * restore -> commit -> restore round trip;
  * the disabled path is the identity: no WAL configured means
    `_apply_delta` byte-for-byte unchanged (class-level `_wal is
    None`, DeltaLog.append never called, no files written);
  * DL017 on the real tree and a mutated copy (fsync deleted from
    atomic_write -> the analyzer fires).
"""

import os
from pathlib import Path

import pytest

from das_tpu import fault
from das_tpu.ops import counters
from das_tpu.analysis import run_analysis
from das_tpu.api.atomspace import DistributedAtomSpace
from das_tpu.core.config import DasConfig
from das_tpu.core.exceptions import InjectedFault, SnapshotCorruptError
from das_tpu.models.bio import build_bio_atomspace
from das_tpu.query.ast import And, Link, Node, Variable
from das_tpu.storage import checkpoint, durable
from das_tpu.storage.delta import IncrementalCommitMixin
from das_tpu.storage.tensor_db import TensorDB

pytestmark = pytest.mark.dur

REPO = Path(__file__).resolve().parent.parent

#: the five persist seams this PR added (subset of fault.FAULT_SITES —
#: pinned here so the crash matrix cannot silently shrink)
PERSIST_FAULT_SITES = (
    "snapshot_write", "snapshot_rename", "wal_append", "wal_fsync",
    "restore_read",
)


@pytest.fixture(autouse=True)
def _disarm(monkeypatch):
    """Injection off after every test; CapStore/XLA persistence off so
    warm-bundle pins are controlled by THIS suite only."""
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    yield
    fault.configure(None)


def _bio_data(**kw):
    base = dict(n_genes=30, n_processes=5, members_per_gene=3,
                n_interactions=30, n_evaluations=6)
    base.update(kw)
    data, _, _ = build_bio_atomspace(**base)
    return data


def _ast(gene: str):
    return And([
        Link("Member", [Node("Gene", gene), Variable("$3")], True),
        Link("Member", [Variable("$2"), Variable("$3")], True),
        Link("Interacts", [Node("Gene", gene), Variable("$2")], True),
    ])


def _three_var():
    return And([
        Link("Member", [Variable("V1"), Variable("V3")], True),
        Link("Member", [Variable("V2"), Variable("V3")], True),
        Link("Interacts", [Variable("V1"), Variable("V2")], True),
    ])


def _answers(das, queries):
    return [das.query(q) for q in queries]


def _commit_interaction(das, db, i: int):
    """One WAL-logged commit: a fresh gene interacting with an existing
    one (terminals declared — the bio KB is built programmatically, so
    MeTTa needs the `(: ...)` declarations)."""
    g0 = db.get_all_nodes("Gene", names=True)[0]
    tx = das.open_transaction()
    tx.add(f'(: "DURGENE:{i}" Gene)')
    tx.add(f'(: "{g0}" Gene)')
    tx.add(f'(Interacts "DURGENE:{i}" "{g0}")')
    das.commit_transaction(tx)


def _make_backend(data, backend, config=None):
    config = config or DasConfig()
    if backend == "sharded":
        from das_tpu.parallel.sharded_db import ShardedDB

        return ShardedDB(data, config)
    return TensorDB(data, config)


# -- the tentpole pin: crash-point matrix --------------------------------


def _crash_matrix(tmp_path, backend, site, seed):
    """Baseline snapshot -> WAL commit -> injected crash at `site` ->
    recover -> bit-identical answers to the live (uncrashed) store."""
    root = str(tmp_path / "snap")
    data = _bio_data()
    db = _make_backend(data, backend)
    das = DistributedAtomSpace(database_name=f"zdur_{site}", db=db)
    queries = [_ast(g) for g in db.get_all_nodes("Gene", names=True)[:3]]
    durable.write_snapshot(db, root)
    _commit_interaction(das, db, 0)
    live = _answers(das, queries)
    assert any(live), "KB too sparse to prove anything"

    if site in ("snapshot_write", "snapshot_rename"):
        # crash DURING the next snapshot: the new generation never
        # publishes, the prior one + WAL still reconstructs head
        fault.configure(f"seed={seed};sites={site};every=1;max=100")
        with pytest.raises(InjectedFault):
            durable.write_snapshot(db, root)
        fault.configure(None)
        assert [n for n, _ in durable.list_generations(root)] == [1]
        # no stray temp dirs survive a crashed snapshot
        assert not [
            d for d in os.listdir(root) if not d.startswith("gen-")
        ]
    elif site in ("wal_append", "wal_fsync"):
        # crash DURING a commit's WAL append: the commit fails typed
        # PRE-swap (atomicity), the store stays at the pre-commit
        # state, and the SAME delta commits once the fault clears
        v0 = db.delta_version
        g1 = db.get_all_nodes("Gene", names=True)[1]
        tx = das.open_transaction()
        tx.add('(: "DURGENE:crash" Gene)')
        tx.add(f'(: "{g1}" Gene)')
        tx.add(f'(Interacts "DURGENE:crash" "{g1}")')
        fault.configure(f"seed={seed};sites={site};every=1;max=100")
        with pytest.raises(InjectedFault):
            das.commit_transaction(tx)
        assert db.delta_version == v0  # unbumped: stage-then-swap held
        assert _answers(das, queries) == live
        fault.configure(None)
        das._refresh()  # the SAME staged delta commits cleanly
        assert db.delta_version == v0 + 1
        live = _answers(das, queries)
    else:  # restore_read: a transient read flake recovers via retry
        fault.configure(f"seed={seed};sites={site};every=1;max=1")

    if backend == "sharded":
        from das_tpu.parallel.sharded_db import ShardedDB

        restored = ShardedDB.restore(root)
    else:
        restored = TensorDB.restore(root)
    fault.configure(None)
    das2 = DistributedAtomSpace(database_name=f"zdur_{site}_r", db=restored)
    assert _answers(das2, queries) == live  # bit-identical recovery
    assert restored.delta_version == db.delta_version


@pytest.mark.parametrize("site", PERSIST_FAULT_SITES)
def test_crash_matrix_tensor(tmp_path, site):
    _crash_matrix(tmp_path, "tensor", site, seed=11)


@pytest.mark.parametrize("site", PERSIST_FAULT_SITES)
def test_crash_matrix_sharded(tmp_path, site):
    _crash_matrix(tmp_path, "sharded", site, seed=13)


def test_persist_sites_declared_in_fault_registry():
    """The chaos sweep in test_zfault parametrizes over FAULT_SITES —
    the five persist seams must stay members so serving-level chaos
    covers them too."""
    for site in PERSIST_FAULT_SITES:
        assert site in fault.FAULT_SITES, site


# -- WAL mechanics -------------------------------------------------------


def test_torn_tail_wal_truncated_not_replayed(tmp_path):
    root = str(tmp_path / "snap")
    data = _bio_data()
    db = TensorDB(data, DasConfig())
    das = DistributedAtomSpace(database_name="zdur_torn", db=db)
    queries = [_ast(g) for g in db.get_all_nodes("Gene", names=True)[:3]]
    durable.write_snapshot(db, root)
    _commit_interaction(das, db, 0)
    live = _answers(das, queries)

    wal_path = os.path.join(
        durable.list_generations(root)[-1][1], durable.WAL_FILE
    )
    clean_size = os.path.getsize(wal_path)
    assert clean_size > 0
    # a crash mid-append: valid header claiming more payload than ever
    # hit the disk
    with open(wal_path, "ab") as f:
        f.write(durable._WAL_HEADER.pack(durable.WAL_MAGIC, 1 << 20, 0))
        f.write(b"torn payload that never finished")
    before = durable.DUR_STATS["torn_tail_truncations"]
    restored = TensorDB.restore(root)
    assert durable.DUR_STATS["torn_tail_truncations"] == before + 1
    assert os.path.getsize(wal_path) == clean_size  # cut, not replayed
    das2 = DistributedAtomSpace(database_name="zdur_torn_r", db=restored)
    assert _answers(das2, queries) == live
    # ...and the truncated log keeps appending cleanly
    _commit_interaction(das2, restored, 1)
    restored2 = TensorDB.restore(root)
    das3 = DistributedAtomSpace(database_name="zdur_torn_r2", db=restored2)
    assert _answers(das3, queries) == _answers(das2, queries)


def test_midfile_wal_corruption_is_typed_never_truncated(tmp_path):
    """Mid-file corruption is categorically different from a torn tail:
    a fully-present frame failing its CRC may have fsync-acknowledged
    records BEHIND it, so read_wal refuses to truncate and raises
    typed — durable data is never silently destroyed."""
    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(), DasConfig())
    das = DistributedAtomSpace(database_name="zdur_garbage", db=db)
    durable.write_snapshot(db, root)
    _commit_interaction(das, db, 0)
    _commit_interaction(das, db, 1)  # a second fsynced record follows
    wal_path = os.path.join(
        durable.list_generations(root)[-1][1], durable.WAL_FILE
    )
    size = os.path.getsize(wal_path)
    with open(wal_path, "r+b") as f:
        f.seek(durable._WAL_HEADER.size + 2)  # inside record 1's payload
        f.write(b"\xde\xad")
    with pytest.raises(SnapshotCorruptError):
        durable.read_wal(wal_path)
    assert os.path.getsize(wal_path) == size  # refused to truncate
    # ...and the failure surfaces typed from restore too
    with pytest.raises(SnapshotCorruptError):
        TensorDB.restore(root)


def test_wal_record_format_roundtrip(tmp_path):
    """Frame-level unit: append two records, read them back verified,
    fields intact (version, kind, atoms, symbol tail)."""
    from das_tpu.storage.atom_table import load_metta_text

    data = load_metta_text(
        "(: Concept Type)\n(: Inheritance Type)\n"
        '(: "a" Concept)\n(: "b" Concept)\n'
    )
    log = durable.DeltaLog(str(tmp_path / "wal.log"), data)
    load_metta_text('(Inheritance "a" "b")', data)
    log.append(data, 2)
    load_metta_text('(: "c" Concept)\n(Inheritance "c" "b")', data)
    log.append(data, 3, kind="full")
    records, torn = durable.read_wal(log.path)
    assert not torn and [r["v"] for r in records] == [2, 3]
    assert records[0]["kind"] == "delta" and records[1]["kind"] == "full"
    # terminals materialize into data.nodes on first USE (the parser's
    # EOF fixpoint), so record 0 carries "a"/"b" + the link; record 1
    # carries "c" + its link
    assert len(records[0]["links"]) == 1 and len(records[0]["nodes"]) == 2
    assert len(records[1]["nodes"]) == 1 and len(records[1]["links"]) == 1
    assert records[1]["symbols"]["terminal_hash"]


# -- generation verification ---------------------------------------------


def test_corrupt_section_falls_back_to_prior_generation(tmp_path):
    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(), DasConfig(snapshot_keep=4))
    das = DistributedAtomSpace(database_name="zdur_corrupt", db=db)
    queries = [_ast(g) for g in db.get_all_nodes("Gene", names=True)[:3]]
    durable.write_snapshot(db, root)          # gen 1
    _commit_interaction(das, db, 0)           # -> gen 1's WAL
    live = _answers(das, queries)
    gen2 = durable.write_snapshot(db, root)   # gen 2 (same head state)

    # flip bytes inside gen 2's records section
    target = os.path.join(gen2, checkpoint.RECORDS_FILE)
    blob = bytearray(Path(target).read_bytes())
    blob[100:110] = b"\x00" * 10
    Path(target).write_bytes(bytes(blob))

    before = durable.DUR_STATS["corrupt_generations"]
    restored = TensorDB.restore(root)
    assert durable.DUR_STATS["corrupt_generations"] == before + 1
    # gen 1 + its WAL reconstructs the exact same head
    das2 = DistributedAtomSpace(database_name="zdur_corrupt_r", db=restored)
    assert _answers(das2, queries) == live

    # every generation corrupt -> typed, never silent
    gen1 = durable.list_generations(root)[0][1]
    t1 = os.path.join(gen1, checkpoint.RECORDS_FILE)
    blob = bytearray(Path(t1).read_bytes())
    blob[50:60] = b"\xff" * 10
    Path(t1).write_bytes(bytes(blob))
    with pytest.raises(SnapshotCorruptError):
        TensorDB.restore(root)


def test_manifest_absent_is_torn_generation(tmp_path):
    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(), DasConfig())
    gen1 = durable.write_snapshot(db, root)
    gen2 = durable.write_snapshot(db, root)
    os.remove(os.path.join(gen2, durable.MANIFEST_FILE))
    _data, manifest, gen_dir = durable.newest_valid_generation(root)
    assert gen_dir == gen1 and manifest["generation"] == 1


def test_generation_pruning_bounds_history(tmp_path):
    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(n_genes=6, n_interactions=4), DasConfig(
        snapshot_keep=2
    ))
    for _ in range(4):
        durable.write_snapshot(db, root)
    assert [n for n, _ in durable.list_generations(root)] == [3, 4]


def test_backcompat_unverified_checkpoint_warns_and_loads(tmp_path):
    """A pre-dasdur checkpoint (no MANIFEST.json) still loads —
    warn-and-accept once — and the next save records the digests."""
    path = str(tmp_path / "old")
    data = _bio_data(n_genes=6, n_interactions=4)
    checkpoint.save(data, path)
    os.remove(os.path.join(path, durable.MANIFEST_FILE))  # pre-dasdur
    restored = checkpoint.load(path)
    assert restored.count_atoms() == data.count_atoms()
    assert path in checkpoint._UNVERIFIED_WARNED
    checkpoint.save(restored, path)  # upgrade: digests recorded
    assert os.path.exists(os.path.join(path, durable.MANIFEST_FILE))
    durable.verify_generation(path)  # now fully verifiable


# -- warm bundle ---------------------------------------------------------


def test_warm_bundle_stale_on_version_mismatch(tmp_path):
    """CapStore data recorded at snapshot version v must NOT apply when
    WAL replay moved the store past v — the result-cache staleness
    guard applied to persistence."""
    from das_tpu.query.fused import apply_warm_state, get_executor

    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(), DasConfig())
    das = DistributedAtomSpace(database_name="zdur_stale", db=db)
    # learn something bundle-worthy, then snapshot
    das.query(_three_var())
    ex = get_executor(db)
    ex._cap_store._data["sentinel"] = [[1], [2]]
    durable.write_snapshot(db, root)
    _commit_interaction(das, db, 0)  # WAL moves head past the snapshot

    restored = TensorDB.restore(root)
    rex = get_executor(restored)
    assert "sentinel" not in rex._cap_store._data  # stale: discarded
    assert restored.delta_version == db.delta_version

    # the pure-function contract both ways
    state = {"delta_version": restored.delta_version + 1, "caps": {}}
    assert apply_warm_state(restored, state) is False
    state = {"delta_version": restored.delta_version,
             "caps": {"_cap_store": {"k": [[1], [2]]}}, "counts": []}
    assert apply_warm_state(restored, state) is True
    assert rex._cap_store._data["k"] == [[1], [2]]


def test_warm_bundle_applies_at_matching_version(tmp_path):
    """No commits after the snapshot: the bundle applies — CapStore
    data, count-cache entries and planner statistics all inherited."""
    from das_tpu.planner.stats import estimator_for
    from das_tpu.query.fused import get_executor

    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(), DasConfig())
    das = DistributedAtomSpace(database_name="zdur_warm", db=db)
    queries = [_ast(g) for g in db.get_all_nodes("Gene", names=True)[:2]]
    baseline = _answers(das, queries)
    # populate planner statistics + the count cache through real use
    from das_tpu.query import compiler

    das.query(_three_var())
    est = estimator_for(db)
    assert est is not None
    ex = get_executor(db)
    n_counts = ex.count_batch(
        [compiler.plan_query(db, q) for q in queries]
    )
    assert all(n is not None for n in n_counts)
    durable.write_snapshot(db, root)

    restored = TensorDB.restore(root)
    rex = get_executor(restored)
    rest = estimator_for(restored)
    # planner stats arrived without running anything
    assert rest._rows == est._rows and rest._distinct == est._distinct
    # count-cache entries answer with zero device work
    counters.reset_dispatch_counts()
    plans = [compiler.plan_query(restored, q) for q in queries]
    assert rex.count_batch(plans) == n_counts
    assert counters.DISPATCH_COUNTS["count"] == 0
    das2 = DistributedAtomSpace(database_name="zdur_warm_r", db=restored)
    assert _answers(das2, queries) == baseline


def test_warm_restore_zero_capacity_retries(tmp_path):
    """The acceptance pin: a restored replica settles the fan-out query
    in ONE compiled program (0 capacity retries — the bundle's learned
    caps honored) where a cold replica without the bundle pays the
    retry tier (>= 2 programs).  Planner OFF so the greedy seed is the
    thing the bundle rescues."""
    root = str(tmp_path / "snap")
    data, _, _ = build_bio_atomspace(
        n_genes=32, n_processes=100, members_per_gene=50,
        n_interactions=0, seed=3,
    )
    cfg = DasConfig(use_planner="off")
    db = TensorDB(data, cfg)
    das = DistributedAtomSpace(database_name="zdur_caps", db=db)
    proc = db.get_all_nodes("BiologicalProcess", names=True)[0]
    q = And([
        Link("Member", [Variable("G"), Node("BiologicalProcess", proc)],
             True),
        Link("Member", [Variable("G"), Variable("P2")], True),
    ])
    counters.reset_dispatch_counts()
    answer = das.query(q)  # learns the capacity the greedy seed missed
    cold_programs = counters.DISPATCH_COUNTS["fused"]
    assert cold_programs >= 2, counters.DISPATCH_COUNTS
    durable.write_snapshot(db, root)

    restored = TensorDB.restore(root, DasConfig(use_planner="off"))
    das2 = DistributedAtomSpace(database_name="zdur_caps_r", db=restored)
    counters.reset_dispatch_counts()
    assert das2.query(q) == answer
    assert counters.DISPATCH_COUNTS["fused"] == 1, (
        "restored replica was expected to settle in round 0 on the "
        f"bundled caps; dispatches={counters.DISPATCH_COUNTS}"
    )

    # control: a cold replica from the same records (no bundle) still
    # pays the tier — the bundle, not the snapshot, is what helped
    cold = TensorDB(checkpoint.load(
        durable.list_generations(root)[-1][1], _verified=True
    ), DasConfig(use_planner="off"))
    das3 = DistributedAtomSpace(database_name="zdur_caps_c", db=cold)
    counters.reset_dispatch_counts()
    assert das3.query(q) == answer
    assert counters.DISPATCH_COUNTS["fused"] >= 2


# -- round trip + disabled-path identity ---------------------------------


def test_restore_commit_restore_round_trip(tmp_path):
    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(), DasConfig())
    das = DistributedAtomSpace(database_name="zdur_rt", db=db)
    queries = [_ast(g) for g in db.get_all_nodes("Gene", names=True)[:3]]
    durable.write_snapshot(db, root)
    _commit_interaction(das, db, 0)

    r1 = TensorDB.restore(root)
    das1 = DistributedAtomSpace(database_name="zdur_rt1", db=r1)
    assert _answers(das1, queries) == _answers(das, queries)
    _commit_interaction(das1, r1, 1)  # commit on the RESTORED store
    live = _answers(das1, queries)

    r2 = TensorDB.restore(root)
    das2 = DistributedAtomSpace(database_name="zdur_rt2", db=r2)
    assert _answers(das2, queries) == live
    assert r2.delta_version == r1.delta_version


def test_disabled_path_is_identity(tmp_path, monkeypatch):
    """No WAL configured: `_wal` is the CLASS-level None (one attribute
    read on the commit hot path, no new allocations), DeltaLog.append
    is never entered, and no persist file appears anywhere."""
    assert IncrementalCommitMixin._wal is None
    assert IncrementalCommitMixin._snapshot_root is None
    db = TensorDB(_bio_data(n_genes=6, n_interactions=4), DasConfig())
    assert db._wal is IncrementalCommitMixin._wal  # class attr, no copy
    das = DistributedAtomSpace(database_name="zdur_off", db=db)

    def boom(*a, **k):  # pragma: no cover - the pin is that it never runs
        raise AssertionError("DeltaLog.append reached with no WAL")

    monkeypatch.setattr(durable.DeltaLog, "append", boom)
    before = dict(durable.DUR_STATS)
    _commit_interaction(das, db, 0)
    assert db._wal is None
    assert durable.snapshot_stats()["wal_records"] == before["wal_records"]


def test_obs_enabled_durability_spans_and_metrics(tmp_path):
    """The full snapshot→commit→restore cycle with the obs layer ON
    (the serving default under DAS_TPU_TRACE=1): spans/events/counters/
    histogram all record through their REAL APIs — a typo'd metric
    call must fail here, not in production (the live drive caught
    `.record` vs `.observe` exactly once; never again)."""
    from das_tpu import obs

    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(n_genes=6, n_interactions=4), DasConfig())
    das = DistributedAtomSpace(database_name="zdur_obs", db=db)
    obs.configure(enabled=True)
    try:
        obs.reset()
        durable.write_snapshot(db, root)
        _commit_interaction(das, db, 0)
        restored = TensorDB.restore(root)
        assert restored.delta_version == db.delta_version
        assert obs.metrics.COUNTERS["dur.snapshots"].value >= 1
        assert obs.metrics.COUNTERS["dur.wal_records"].value >= 1
        assert obs.metrics.COUNTERS["dur.recovery_replayed"].value >= 1
        assert obs.metrics.HISTOGRAMS["dur.restore_ms"].total >= 1
        names = {e[0] for e in obs.events()}
        assert {"dur.snapshot", "dur.restore", "dur.wal_append"} <= names
    finally:
        obs.configure(enabled=False)
        obs.reset()


def test_stats_surface_and_prometheus_gauges(tmp_path):
    from das_tpu.service.server import DasService, _Tenant

    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(n_genes=6, n_interactions=4), DasConfig())
    das = DistributedAtomSpace(database_name="zdur_stats", db=db)
    durable.write_snapshot(db, root)
    _commit_interaction(das, db, 0)
    TensorDB.restore(root)

    svc = DasService()
    tenant = _Tenant("t", das)
    svc.tenants["t"] = tenant
    stats = svc.coalescer_stats()
    dur = stats["durability"]
    for key in ("generation", "snapshots", "wal_records",
                "recovery_replayed", "torn_tail_truncations",
                "corrupt_generations", "last_restore_s"):
        assert key in dur, key
    assert dur["generation"] >= 1 and dur["wal_records"] >= 1
    assert dur["recovery_replayed"] >= 1
    assert dur["last_restore_s"] is not None
    text = svc.metrics_text()
    assert "durability_generation" in text
    assert "durability_wal_records" in text
    assert "durability_last_restore_s" in text


def test_snapshot_dir_config_auto_restore(tmp_path, monkeypatch):
    """DAS_TPU_SNAPSHOT_DIR end-to-end: a bare DistributedAtomSpace()
    over a populated root restores it; over an empty root it writes
    generation 1 and arms the WAL."""
    root = str(tmp_path / "snap")
    das = DistributedAtomSpace(
        backend="tensor", config=DasConfig(snapshot_dir=root),
    )
    # the API namespaces the root per database_name: one generation
    # lineage = one store (service tenants sharing DAS_TPU_SNAPSHOT_DIR
    # must not restore each other's atoms or interleave WALs)
    lineage = os.path.join(root, das.database_name)
    assert [n for n, _ in durable.list_generations(lineage)] == [1]
    assert not durable.list_generations(root)
    assert das.db._wal is not None
    das.load_metta_text(
        "(: Concept Type)\n(: Inheritance Type)\n"
        '(: "a" Concept)\n(: "m" Concept)\n(Inheritance "a" "m")'
    )
    q = And([Link("Inheritance",
                  [Variable("$x"), Node("Concept", "m")], True)])
    answer = das.query(q)

    das2 = DistributedAtomSpace(
        backend="tensor", config=DasConfig(snapshot_dir=root),
    )
    assert das2.db.count_atoms() == das.db.count_atoms()
    assert das2.query(q) == answer
    # env spelling reaches the same path
    monkeypatch.setenv("DAS_TPU_SNAPSHOT_DIR", root)
    assert DasConfig.from_env().snapshot_dir == root
    monkeypatch.setenv("DAS_TPU_WAL", "off")
    assert not durable.wal_enabled(DasConfig.from_env())


def test_attach_refuses_foreign_root_writes_fresh_generation(tmp_path):
    """Arming a DIFFERENT store's WAL would silently drop (or brick)
    its commits at replay: attach() reuses a populated lineage only
    when the newest generation provably describes the live store;
    anything else gets a fresh generation."""
    root = str(tmp_path / "snap")
    db_a = TensorDB(_bio_data(n_genes=6, n_interactions=4), DasConfig())
    durable.write_snapshot(db_a, root)
    db_b = TensorDB(_bio_data(n_genes=9, n_interactions=6), DasConfig())
    gen_dir = durable.attach(db_b, root)
    assert gen_dir.endswith("gen-000002")  # fresh, not A's lineage
    das_b = DistributedAtomSpace(database_name="zdur_foreign", db=db_b)
    _commit_interaction(das_b, db_b, 0)
    restored = TensorDB.restore(root)
    assert restored.count_atoms() == db_b.count_atoms()  # B, not A
    # ...while re-attaching a store the newest generation already
    # describes (a fresh snapshot of db_b's head) REUSES it
    head_gen = durable.write_snapshot(db_b, root)
    db_c = TensorDB(db_b.data, DasConfig())
    db_c.delta_version = db_b.delta_version
    assert durable.attach(db_c, root) == head_gen
    assert durable.list_generations(root)[-1][1] == head_gen


def test_attach_refuses_generation_with_nonempty_wal(tmp_path):
    """A matched generation whose WAL already holds records is a
    lineage whose head moved PAST the snapshot: re-arming it would let
    a second writer append duplicate delta_versions that replay dedups
    away (silently dropped fsynced commits) — attach must take a fresh
    generation instead."""
    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(n_genes=6, n_interactions=4), DasConfig())
    das = DistributedAtomSpace(database_name="zdur_refuse", db=db)
    gen1 = durable.write_snapshot(db, root)
    _commit_interaction(das, db, 0)  # gen1's WAL now has a record

    # a second process rebuilds the SNAPSHOT-state store (version and
    # content both match gen1's manifest) — but gen1's WAL is not empty
    data2 = checkpoint.load(gen1, _verified=True)
    db2 = TensorDB(data2, DasConfig())
    gen = durable.attach(db2, root)
    assert gen != gen1  # fresh generation, never the moved-on lineage


def test_generational_checkpoint_load_includes_wal_commits(tmp_path):
    """checkpoint.load on a generational root must not silently serve
    the snapshot WITHOUT the fsync-acknowledged WAL commits behind it
    (DAS_TPU_CHECKPOINT pointed at a lineage dir is a documented
    spelling)."""
    root = str(tmp_path / "snap")
    db = TensorDB(_bio_data(n_genes=6, n_interactions=4), DasConfig())
    das = DistributedAtomSpace(database_name="zdur_ckload", db=db)
    durable.write_snapshot(db, root)
    _commit_interaction(das, db, 0)

    data = checkpoint.load(root)
    assert data.count_atoms() == db.data.count_atoms()  # WAL included
    das2 = DistributedAtomSpace(
        backend="tensor", config=DasConfig(checkpoint_path=root),
    )
    assert das2.count_atoms() == db.count_atoms()


def test_flat_checkpoint_missing_optional_section_still_loads(tmp_path):
    """The pre-dasdur contract holds under verification: deleting
    indexes.npz from a flat checkpoint forces the re-finalize slow
    path, never a corruption error — only PRESENT bytes must match."""
    path = str(tmp_path / "flat")
    data = _bio_data(n_genes=6, n_interactions=4)
    checkpoint.save(data, path)
    os.remove(os.path.join(path, checkpoint.INDEXES_FILE))
    restored = checkpoint.load(path)
    assert restored.count_atoms() == data.count_atoms()
    assert restored._fin is None  # re-finalize path, not a crash


# -- DL017 on the real tree ----------------------------------------------


def test_dl017_fires_on_fsyncless_atomic_write(tmp_path):
    """Mutated-copy regression (the DL004/DL015 idiom): delete the
    os.fsync from the REAL atomic_write — the analyzer must fire the
    fsync-before-rename pin."""
    src = (REPO / "das_tpu/storage/durable.py").read_text()
    needle = "            os.fsync(f.fileno())\n        fault.maybe_fail"
    assert needle in src, "durable.py atomic_write layout changed"
    mutated = tmp_path / "durable_mutated.py"
    mutated.write_text(src.replace(
        needle, "            pass\n        fault.maybe_fail", 1
    ))
    findings = run_analysis([mutated], rules=["DL017"], partial=True)
    assert any(
        "os.fsync" in f.message and "atomic_write" in f.message
        for f in findings
    ), "\n".join(f.render() for f in findings)
    # the committed module stays clean
    clean = run_analysis(
        [REPO / "das_tpu/storage/durable.py",
         REPO / "das_tpu/storage/checkpoint.py",
         REPO / "das_tpu/service/seed_checkpoint.py"],
        rules=["DL017"], partial=True,
    )
    assert clean == [], "\n".join(f.render() for f in clean)


def test_dl017_fires_on_bare_write_in_persist_scope(tmp_path):
    """A bare open(..., "wb") added to checkpoint.py must fail lint even
    though the module itself declares no registry — PERSIST_SCOPES
    covers it by path suffix."""
    scope_dir = tmp_path / "das_tpu" / "storage"
    scope_dir.mkdir(parents=True)
    (scope_dir / "durable.py").write_text(
        (REPO / "das_tpu/storage/durable.py").read_text()
    )
    bad = scope_dir / "checkpoint.py"
    bad.write_text(
        "import os\n"
        "def save(path, payload):\n"
        "    with open(path, 'wb') as f:\n"
        "        f.write(payload)\n"
    )
    findings = run_analysis(
        [scope_dir / "durable.py", bad], rules=["DL017"], partial=True
    )
    assert any(
        "bare write-mode open()" in f.message
        and f.path.endswith("checkpoint.py")
        for f in findings
    ), "\n".join(f.render() for f in findings)
