"""dasdur — crash-consistent snapshots, checksummed write-ahead delta
log, verified warm-state restore (ISSUE 15 tentpole).

The ROADMAP's replica-fleet item needs "persist the warm state a
replica should inherit instead of recompute": a fresh process pays
minutes (FlyBase: 178 s build + 76 s finalize + XLA compiles) before
its first answer.  Before this module, `storage/checkpoint.py` wrote
snapshots with bare `open()`/`np.savez` (a crash mid-save corrupts the
only copy), verified nothing on load, and lost every commit made after
the snapshot.  This module is the durability substrate both backends
ride:

  * **Atomic generational snapshots** — `write_snapshot(db, root)`
    writes every section (records / indexes / registry / sharded slabs
    / warm bundle) write-temp -> fsync -> rename via `atomic_write`,
    into a `gen-NNNNNN` directory whose `MANIFEST.json` carries
    per-section CRC-32 digests, the backend's `delta_version`, the
    existing `_content_sig`, and the persistent-XLA-cache dir (so
    dasprof's `cold_start_s` measures the restore win end-to-end).
    The generation directory itself lands by one final fsync + rename,
    so a crash at ANY point leaves either the complete new generation
    or the untouched prior one — never a torn hybrid.  `restore()`
    verifies every section against the manifest, rejects torn/corrupt
    generations with typed `SnapshotCorruptError`, and falls back to
    the newest valid prior generation.

  * **Write-ahead delta log** — `DeltaLog.append` runs inside
    `IncrementalCommitMixin._apply_delta`'s stage-then-swap, AFTER
    staging and BEFORE the swap: a checksummed, length-prefixed
    msgpack record of the interned delta (atoms + the symbol-table
    tail) is fsynced before anything becomes visible.  `restore(root)`
    = newest valid snapshot + WAL replay to head, each replayed commit
    re-verified against `delta_version` continuity; a torn tail record
    (crash mid-append) is truncated safely, never replayed.

  * **Warm-state bundle** — CapStore learned capacities, planner
    degree statistics and count-cache entries persist beside the
    snapshot keyed by `delta_version` (query/fused.py
    export_warm_state / apply_warm_state); a stale bundle — the WAL
    replayed commits past the snapshot — is discarded on the existing
    delta_version guard, exactly like a result-cache entry.

Every new I/O path registers in FAULT_SITES (`snapshot_write`,
`snapshot_rename`, `wal_append`, `wal_fsync`, `restore_read`) and the
chaos-parity contract extends to it: inject a crash at any site,
recover, and query answers are bit-identical (tests/test_zdur.py).

Durability discipline is lint-enforced (daslint DL017): inside the
declared `PERSIST_SCOPES`, every byte written flows through the
`PERSIST_SITES` functions below (no bare `open(..., "w")` /
`np.savez(path)`), and any function that renames a file into place
provably fsyncs first.

Layout under the snapshot root (env DAS_TPU_SNAPSHOT_DIR):

    root/
      gen-000001/
        MANIFEST.json      format, generation, delta_version,
                           content_sig, sections {name: bytes, crc32},
                           wal, warm delta_version, xla_cache_dir
        records.msgpack    host records (checkpoint.py payload)
        indexes.npz        finalized probe indexes
        registry.msgpack   hex_of_row / type registry
        sharded_S.npz      (sharded backend) per-shard slabs
        warm.msgpack       warm-state bundle
        wal.log            commits SINCE this generation
      gen-000002/ ...      newer generations; DAS_TPU_SNAPSHOT_KEEP
                           bounds how many survive pruning
"""

from __future__ import annotations

import io
import json
import os
import struct
import time
import zlib
from itertools import islice
from typing import Callable, Dict, List, Optional, Tuple

import msgpack

import das_tpu

from das_tpu.core.exceptions import SnapshotCorruptError

MANIFEST_FILE = "MANIFEST.json"
WAL_FILE = "wal.log"
WARM_FILE = "warm.msgpack"
SHARDED_PREFIX = "sharded_"
GEN_PREFIX = "gen-"
MANIFEST_FORMAT = 1

#: WAL record framing: "<III" = magic, payload length, payload CRC-32.
WAL_MAGIC = 0x5744_414C  # "WDAL"
_WAL_HEADER = struct.Struct("<III")

#: modules under durability discipline (daslint DL017): every write
#: beneath the snapshot/WAL root in these files must flow through the
#: PERSIST_SITES functions — a bare `open(..., "w")`/`np.savez(path)`
#: fails lint.  Matched by path suffix.
PERSIST_SCOPES = (
    "das_tpu/storage/durable.py",
    "das_tpu/storage/checkpoint.py",
    "das_tpu/service/seed_checkpoint.py",
)

#: the CLOSED set of functions allowed to open persist files for
#: writing (the FAULT_SITES/FETCH_SITES idiom applied to durability).
#: `atomic_write` is the write-temp -> fsync -> rename helper every
#: snapshot section and checkpoint file rides; `DeltaLog.append` is
#: the WAL's append-fsync path; `_truncate_wal` cuts a torn tail.
#: daslint DL017 pins this both ways: an undeclared write-open in a
#: persist scope fires, and a declared site with no write is stale.
PERSIST_SITES = (
    "atomic_write",
    "DeltaLog.append",
    "_truncate_wal",
    "_publish_generation",
)

#: process-wide durability telemetry (the FETCH_COUNTS idiom: plain
#: ints under the GIL, torn reads tolerated) — surfaced via
#: `coalescer_stats()["durability"]` and the Prometheus gauges
#: (service/server.py metrics_text).
DUR_STATS: Dict[str, object] = {
    "generation": 0,          # newest generation written/restored
    "snapshots": 0,           # write_snapshot completions this process
    "wal_records": 0,         # WAL records appended this process
    "recovery_replayed": 0,   # WAL records replayed by restore()
    "torn_tail_truncations": 0,
    "corrupt_generations": 0,  # generations rejected by verification
    "last_restore_s": None,   # wall seconds of the last restore()
}


def snapshot_stats() -> Dict[str, object]:
    """Copy of DUR_STATS for the service stats surface."""
    return dict(DUR_STATS)


# -- atomic write ------------------------------------------------------------


class _CrcWriter:
    """File wrapper tallying CRC-32 + byte count of everything written,
    so `atomic_write` returns the manifest digest without re-reading
    the file it just wrote."""

    __slots__ = ("f", "crc", "nbytes")

    def __init__(self, f):
        self.f = f
        self.crc = 0
        self.nbytes = 0

    def write(self, b):
        self.crc = zlib.crc32(b, self.crc)
        self.nbytes += len(b)
        return self.f.write(b)

    # np.savez wraps the target in a ZipFile; raising here makes
    # zipfile take its UNSEEKABLE-stream write path (every byte flows
    # through write(), so the running CRC sees the whole file) and
    # `read` merely needs to EXIST for numpy to accept a file object
    def read(self, *a):
        raise io.UnsupportedOperation("persist writers are write-only")

    def tell(self):
        raise io.UnsupportedOperation(
            "persist writers are append-only (CRC is a running digest)"
        )

    def seek(self, *a):
        raise io.UnsupportedOperation(
            "persist writers are append-only (CRC is a running digest)"
        )

    def flush(self):
        self.f.flush()

    @property
    def mode(self):
        return self.f.mode

    def fileno(self):
        return self.f.fileno()

    def seekable(self):
        return False

    def readable(self):
        return False

    def writable(self):
        return True


def _fsync_dir(path: str) -> None:
    """fsync a DIRECTORY so a just-renamed entry survives power loss —
    the half of atomic-rename durability `os.replace` alone skips."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return  # platform without directory fds — best effort
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def atomic_write(path: str, writer: Callable) -> Dict[str, int]:
    """THE durable write path (DL017 `PERSIST_SITES`): stream
    `writer(fileobj)` into a temp file, flush + fsync, rename into
    place, fsync the parent directory.  A crash at any point leaves
    either the complete new file or the untouched old one.  Returns
    the manifest digest `{"bytes": n, "crc32": crc}` of what was
    written.  Fault seams: `snapshot_write` before any byte lands,
    `snapshot_rename` between fsync and the rename — the two torn
    states the chaos suite proves recoverable."""
    from das_tpu import fault

    fault.maybe_fail("snapshot_write")
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            cw = _CrcWriter(f)
            writer(cw)
            f.flush()
            os.fsync(f.fileno())
        fault.maybe_fail("snapshot_rename")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    _fsync_dir(os.path.dirname(path) or ".")
    return {"bytes": cw.nbytes, "crc32": cw.crc}


def atomic_write_bytes(path: str, data: bytes) -> Dict[str, int]:
    return atomic_write(path, lambda f: f.write(data))


def _publish_generation(tmp_dir: str, gen_dir: str, root: str) -> None:
    """Make a fully-written generation visible (DL017 `PERSIST_SITES`):
    fsync the temp directory (its entries are already individually
    fsynced by `atomic_write`), rename it into place, fsync the root.
    Until the rename lands, restore sees only prior generations; after
    it, the complete new one."""
    from das_tpu import fault

    fd = os.open(tmp_dir, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    fault.maybe_fail("snapshot_rename")
    os.replace(tmp_dir, gen_dir)
    _fsync_dir(root)


# -- write-ahead delta log ---------------------------------------------------

#: AtomSpaceData record dicts a WAL record captures the tail of
_DATA_DICTS = ("nodes", "links", "typedefs")
#: SymbolTable dicts captured alongside (a replayed replica must
#: resolve handles and parse follow-on transactions exactly like the
#: writer did)
_SYMBOL_DICTS = (
    "named_type_hash", "named_types", "symbol_hash", "terminal_hash",
    "parent_type",
)


def _data_sizes(data) -> Dict[str, int]:
    sizes = {k: len(getattr(data, k)) for k in _DATA_DICTS}
    for k in _SYMBOL_DICTS:
        sizes[k] = len(getattr(data.table, k))
    return sizes


def _dict_tail(d, prev: int) -> List:
    """Keys inserted after position `prev` of an insertion-ordered dict
    (the storage/delta.py `islice(reversed(...))` idiom)."""
    n = len(d) - prev
    if n <= 0:
        return []
    return list(islice(reversed(d), n))[::-1]


class DeltaLog:
    """Append-only checksummed log of incremental commits, one file per
    generation (`gen-NNNNNN/wal.log`).

    Each record frames a msgpack payload with `WAL_MAGIC`, its length
    and its CRC-32: a crash mid-append leaves a torn TAIL that
    `read_wal` detects and `_truncate_wal` cuts — the valid prefix
    replays, the torn bytes never do.  The payload carries the commit's
    post-apply `delta_version` plus the insertion-ordered TAIL of every
    record/symbol dict since the previous append, so replay re-inserts
    atoms in the writer's exact order (bit-identical row interning).

    Appends happen inside `_apply_delta` AFTER staging and BEFORE the
    swap (storage/delta.py): logged-but-not-swapped and
    swapped-and-logged are both consistent outcomes — replay applies
    the record either way, and a retried commit's duplicate record is
    deduplicated by its `delta_version` at replay.  With no WAL
    configured the mixin's `_wal` stays None and `_apply_delta` is
    byte-for-byte the pre-dasdur path (the disabled-path identity pin,
    tests/test_zdur.py)."""

    __slots__ = ("path", "_sizes")

    def __init__(self, path: str, data):
        self.path = path
        self._sizes = _data_sizes(data)

    def _capture(self, data) -> Tuple[Dict, Dict[str, int]]:
        """(payload fragment, new sizes) for everything inserted since
        the last append — pure read, sizes commit only after the
        record is durable."""
        sizes = _data_sizes(data)
        nodes = [
            [h, r.name, r.named_type, r.named_type_hash]
            for h, r in (
                (h, data.nodes[h])
                for h in _dict_tail(data.nodes, self._sizes["nodes"])
            )
        ]
        links = [
            [h, r.named_type, r.named_type_hash, r.composite_type,
             r.composite_type_hash, list(r.elements), r.is_toplevel]
            for h, r in (
                (h, data.links[h])
                for h in _dict_tail(data.links, self._sizes["links"])
            )
        ]
        typedefs = [
            [h, r.name, r.name_hash, r.composite_type_hash,
             r.designator_name]
            for h, r in (
                (h, data.typedefs[h])
                for h in _dict_tail(data.typedefs, self._sizes["typedefs"])
            )
        ]
        t = data.table
        symbols = {}
        for k in _SYMBOL_DICTS:
            d = getattr(t, k)
            tail = _dict_tail(d, self._sizes[k])
            if k == "terminal_hash":  # keys are (type, name) tuples
                symbols[k] = [[a, b, d[(a, b)]] for a, b in tail]
            else:
                symbols[k] = [[key, d[key]] for key in tail]
        return (
            {"nodes": nodes, "links": links, "typedefs": typedefs,
             "symbols": symbols},
            sizes,
        )

    def append(self, data, version: int, kind: str = "delta") -> None:
        """Frame + append + fsync one commit record.  Fault seams:
        `wal_append` before any byte is framed (a failed append leaves
        the file untouched), `wal_fsync` after the write and before
        the fsync (the record may or may not be durable — replay
        deduplicates the retry's twin by delta_version)."""
        from das_tpu import fault, obs

        fault.maybe_fail("wal_append")
        # the span covers capture + pack + write + flush + fsync; a
        # failed append records it too (no bytes attr, no counter)
        with obs.span("dur.wal_append", version=version, kind=kind) as sp:
            fragment, sizes = self._capture(data)
            fragment["v"] = int(version)
            fragment["kind"] = kind
            payload = msgpack.packb(fragment, use_bin_type=True)
            rec = _WAL_HEADER.pack(
                WAL_MAGIC, len(payload), zlib.crc32(payload)
            ) + payload
            with open(self.path, "ab") as f:
                f.write(rec)
                f.flush()
                fault.maybe_fail("wal_fsync")
                os.fsync(f.fileno())
            sp.set(bytes=len(rec))
        self._sizes = sizes
        DUR_STATS["wal_records"] = int(DUR_STATS["wal_records"]) + 1
        if obs.enabled():
            obs.counter("dur.wal_records").inc()


def _truncate_wal(path: str, offset: int) -> None:
    """Cut a torn tail record at the last valid frame boundary (DL017
    `PERSIST_SITES`: the only in-place mutation of a persist file) and
    fsync, so the next append starts from a clean frame."""
    from das_tpu import obs

    with open(path, "r+b") as f:
        f.truncate(offset)
        f.flush()
        os.fsync(f.fileno())
    DUR_STATS["torn_tail_truncations"] = (
        int(DUR_STATS["torn_tail_truncations"]) + 1
    )
    if obs.enabled():
        obs.event("dur.wal_truncate", offset=offset)


def read_wal(path: str, truncate: bool = True) -> Tuple[List[Dict], bool]:
    """Parse a WAL into (records, torn): every frame is re-verified
    (magic, length, CRC).  A torn TAIL — the frame extends past EOF,
    i.e. the crash-mid-append case — is truncated in place when
    `truncate`, so it can never replay; `torn` reports the cut.
    MID-FILE corruption (a fully-present frame failing its CRC, or a
    bad magic with further bytes behind it) is categorically different:
    frames AFTER it were fsync-acknowledged commits, so silently
    truncating would destroy durable data — it raises typed
    `SnapshotCorruptError` instead and touches nothing.  Fault seam:
    `restore_read` (the read half of the chaos matrix)."""
    from das_tpu import fault

    if not os.path.exists(path):
        return [], False
    fault.maybe_fail("restore_read")
    with open(path, "rb") as f:
        buf = f.read()
    records: List[Dict] = []
    off = 0
    torn = False
    while off < len(buf):
        if len(buf) - off < _WAL_HEADER.size:
            torn = True  # header itself ran past EOF: torn append
            break
        magic, ln, crc = _WAL_HEADER.unpack_from(buf, off)
        payload = buf[off + _WAL_HEADER.size: off + _WAL_HEADER.size + ln]
        if magic == WAL_MAGIC and len(payload) < ln:
            torn = True  # framed length runs past EOF: torn append
            break
        if magic != WAL_MAGIC or zlib.crc32(payload) != crc:
            raise SnapshotCorruptError(
                f"WAL {path} corrupt at offset {off}: "
                f"{'bad magic' if magic != WAL_MAGIC else 'CRC mismatch'}"
                " on a fully-present frame — fsynced records may follow,"
                " refusing to truncate"
            )
        records.append(
            msgpack.unpackb(payload, raw=False, strict_map_key=False)
        )
        off += _WAL_HEADER.size + ln
    if torn and truncate:
        _truncate_wal(path, off)
    return records, torn


def _replay_record(data, rec: Dict) -> None:
    """Re-insert one WAL record's atoms + symbol-table tail into a host
    store, in the writer's exact insertion order (row interning — and
    with it positional answers — depends on it)."""
    from das_tpu.storage.atom_table import LinkRec, NodeRec, TypedefRec

    t = data.table
    for k in _SYMBOL_DICTS:
        d = getattr(t, k)
        for entry in rec["symbols"].get(k, ()):
            if k == "terminal_hash":
                a, b, v = entry
                d[(a, b)] = v
            else:
                key, v = entry
                d[key] = v
    for h, name, nh, cth, desig in rec.get("typedefs", ()):
        if h not in data.typedefs:
            data.typedefs[h] = TypedefRec(name, nh, cth, desig)
    for h, name, nt, nth in rec.get("nodes", ()):
        if h not in data.nodes:
            data.nodes[h] = NodeRec(name, nt, nth)
    for h, nt, nth, ct, cth, elements, top in rec.get("links", ()):
        if h not in data.links:
            data.links[h] = LinkRec(nt, nth, ct, cth, tuple(elements), top)
    data._fin = None


# -- generations -------------------------------------------------------------


def _gen_name(n: int) -> str:
    return f"{GEN_PREFIX}{n:06d}"


def list_generations(root: str) -> List[Tuple[int, str]]:
    """(number, absolute dir) of every COMPLETED generation, ascending.
    A generation is completed iff its directory was renamed into place
    (temp dirs carry a leading dot and never match)."""
    if not os.path.isdir(root):
        return []
    out = []
    for name in os.listdir(root):
        if not name.startswith(GEN_PREFIX):
            continue
        try:
            n = int(name[len(GEN_PREFIX):])
        except ValueError:
            continue
        out.append((n, os.path.join(root, name)))
    out.sort()
    return out


def _verified_bytes(path: str, meta: Dict) -> bytes:
    """Read one manifest section and verify byte count + CRC-32; a
    mismatch is a typed corruption, never a silently-served file."""
    from das_tpu import fault

    fault.maybe_fail("restore_read")
    with open(path, "rb") as f:
        b = f.read()
    if len(b) != int(meta["bytes"]) or zlib.crc32(b) != int(meta["crc32"]):
        raise SnapshotCorruptError(
            f"section {os.path.basename(path)} failed verification: "
            f"{len(b)} bytes / crc {zlib.crc32(b):#x} vs manifest "
            f"{meta['bytes']} / {int(meta['crc32']):#x}"
        )
    return b


def read_manifest(gen_dir: str) -> Dict:
    mpath = os.path.join(gen_dir, MANIFEST_FILE)
    if not os.path.exists(mpath):
        raise SnapshotCorruptError(f"{gen_dir}: no manifest (torn write)")
    try:
        with open(mpath, "rb") as f:
            manifest = json.loads(f.read().decode())
    except (ValueError, OSError) as exc:
        raise SnapshotCorruptError(f"{gen_dir}: unreadable manifest: {exc}")
    if manifest.get("format") != MANIFEST_FORMAT:
        raise SnapshotCorruptError(
            f"{gen_dir}: unsupported manifest format "
            f"{manifest.get('format')!r}"
        )
    return manifest


def verify_generation(gen_dir: str, missing_ok: bool = False) -> Dict:
    """Manifest + every section verified; returns the manifest.  Raises
    typed `SnapshotCorruptError` on the first mismatch — the caller
    (restore) falls back to the prior generation.  `missing_ok` is the
    FLAT-checkpoint mode (checkpoint.load): an operator may delete an
    optional section (indexes.npz) to force a re-finalize — absence is
    the documented slow path there, only present-but-mismatched bytes
    are corruption.  Real generations keep the strict default: their
    sections were written together and a missing one is a torn write."""
    manifest = read_manifest(gen_dir)
    for name, meta in manifest["sections"].items():
        path = os.path.join(gen_dir, name)
        if missing_ok and not os.path.exists(path):
            continue
        _verified_bytes(path, meta)
    return manifest


# -- snapshot write ----------------------------------------------------------


def _warm_payload(db) -> Optional[bytes]:
    """Warm-state bundle of a live backend: CapStore learned
    capacities, planner degree statistics, count-cache entries —
    everything a replica can inherit instead of re-learn (query/
    fused.py export_warm_state).  Best-effort: a cold store simply
    has no bundle."""
    try:
        from das_tpu.query.fused import export_warm_state

        state = export_warm_state(db)
    except Exception:  # noqa: BLE001 — warm state is a perf hint only
        return None
    if state is None:
        return None
    return msgpack.packb(state, use_bin_type=True)


def write_snapshot(db, root: str, keep: Optional[int] = None) -> str:
    """One atomic generational snapshot of a live backend (TensorDB or
    ShardedDB): build `gen-NNNNNN` in a dot-temp directory — records,
    finalized indexes, registry, (sharded) slabs, warm bundle, then
    the manifest LAST — fsync everything, and rename the directory
    into place.  Rotates the backend's WAL to the new generation and
    prunes generations beyond `keep` (DasConfig.snapshot_keep).
    Returns the generation directory."""
    from das_tpu import obs
    from das_tpu.storage import checkpoint

    cfg = getattr(db, "config", None)
    if keep is None:
        keep = int(getattr(cfg, "snapshot_keep", 2) or 2)
    os.makedirs(root, exist_ok=True)
    gens = list_generations(root)
    gen = (gens[-1][0] + 1) if gens else 1
    gen_dir = os.path.join(root, _gen_name(gen))
    tmp_dir = os.path.join(root, f".{_gen_name(gen)}.tmp{os.getpid()}")
    version = int(getattr(db, "delta_version", 0))
    with obs.span("dur.snapshot", generation=gen, version=version):
        os.makedirs(tmp_dir, exist_ok=True)
        try:
            data = db.data
            fin = data.finalize()
            sections: Dict[str, Dict[str, int]] = {}
            sections[checkpoint.RECORDS_FILE] = atomic_write_bytes(
                os.path.join(tmp_dir, checkpoint.RECORDS_FILE),
                msgpack.packb(
                    checkpoint._records_payload(data), use_bin_type=True
                ),
            )
            import numpy as np

            sections[checkpoint.INDEXES_FILE] = atomic_write(
                os.path.join(tmp_dir, checkpoint.INDEXES_FILE),
                lambda f: np.savez(f, **checkpoint._indexes_payload(fin)),
            )
            sections[checkpoint.REGISTRY_FILE] = atomic_write_bytes(
                os.path.join(tmp_dir, checkpoint.REGISTRY_FILE),
                msgpack.packb(
                    checkpoint._registry_payload(fin), use_bin_type=True
                ),
            )
            if hasattr(db, "tables"):
                # sharded slabs: restore device_puts them directly —
                # no host-global re-partition (checkpoint.py
                # try_restore_sharded; its content_sig guard degrades
                # a mismatched restore to re-partition, never to a
                # wrong store)
                name = checkpoint.SHARDED_FILE_FMT.format(
                    db.tables.n_shards
                )
                sections[name] = atomic_write(
                    os.path.join(tmp_dir, name),
                    lambda f: np.savez(
                        f, **checkpoint._sharded_payload(db)
                    ),
                )
            warm = _warm_payload(db)
            if warm is not None:
                sections[WARM_FILE] = atomic_write_bytes(
                    os.path.join(tmp_dir, WARM_FILE), warm
                )
            manifest = {
                "format": MANIFEST_FORMAT,
                "generation": gen,
                "delta_version": version,
                "content_sig": checkpoint._content_sig(fin),
                "sections": sections,
                "wal": WAL_FILE,
                "warm_delta_version": None if warm is None else version,
                "xla_cache_dir": das_tpu.compile_cache_dir(),
                "created_unix": time.time(),
            }
            atomic_write_bytes(
                os.path.join(tmp_dir, MANIFEST_FILE),
                json.dumps(manifest, sort_keys=True, indent=1).encode(),
            )
            _publish_generation(tmp_dir, gen_dir, root)
        except BaseException:
            import shutil

            shutil.rmtree(tmp_dir, ignore_errors=True)
            raise
    # the new generation is durable: commits from here log into ITS wal
    if getattr(db, "_wal", None) is not None or wal_enabled(cfg):
        db._wal = DeltaLog(os.path.join(gen_dir, WAL_FILE), db.data)
    db._snapshot_root = root
    DUR_STATS["generation"] = gen
    DUR_STATS["snapshots"] = int(DUR_STATS["snapshots"]) + 1
    if obs.enabled():
        obs.counter("dur.snapshots").inc()
    prune_generations(root, keep)
    return gen_dir


def prune_generations(root: str, keep: int) -> None:
    """Drop the oldest completed generations beyond `keep` (each owns
    its WAL, so pruning can never strand replay state of a survivor)."""
    import shutil

    gens = list_generations(root)
    for _n, path in gens[:-keep] if keep > 0 else []:
        shutil.rmtree(path, ignore_errors=True)


def wal_enabled(config) -> bool:
    """DasConfig.wal (env DAS_TPU_WAL): "auto"/"on" arm the delta log
    whenever a snapshot root is attached; "off" disables it (snapshots
    still work, commits after the last snapshot are lost on crash)."""
    mode = str(getattr(config, "wal", "auto") or "auto").lower()
    return mode not in ("off", "0", "false")


# -- restore -----------------------------------------------------------------


def _load_generation(gen_dir: str):
    """Verify + parse one generation: (AtomSpaceData with restored
    indexes, manifest).  InjectedFault/IO flakes retry on the shared
    RetryPolicy (das_tpu/fault); verification failures are typed and
    NOT retryable — the caller falls back a generation."""
    from das_tpu import fault
    from das_tpu.storage import checkpoint

    def attempt():
        manifest = verify_generation(gen_dir)
        data = checkpoint.load(gen_dir, _verified=True)
        return data, manifest

    return fault.fetch_retry().run(attempt)


def newest_valid_generation(root: str):
    """(data, manifest, gen_dir) of the newest generation that passes
    verification, walking backwards past torn/corrupt ones.  Typed
    `SnapshotCorruptError` when nothing valid remains."""
    from das_tpu.utils.logger import logger

    gens = list_generations(root)
    if not gens:
        raise SnapshotCorruptError(f"no snapshot generations under {root}")
    last_exc: Optional[Exception] = None
    for _n, gen_dir in reversed(gens):
        try:
            data, manifest = _load_generation(gen_dir)
            return data, manifest, gen_dir
        except Exception as exc:  # noqa: BLE001 — typed + logged fallback
            DUR_STATS["corrupt_generations"] = (
                int(DUR_STATS["corrupt_generations"]) + 1
            )
            logger().warning(
                f"snapshot generation {gen_dir} rejected "
                f"({type(exc).__name__}: {exc}); falling back"
            )
            last_exc = exc
    raise SnapshotCorruptError(
        f"no valid snapshot generation under {root}: {last_exc}"
    )


def replay_wal(db, gen_dir: str, manifest: Dict) -> int:
    """Replay the generation's WAL onto a freshly restored backend:
    records at or below the snapshot's delta_version are skipped
    (duplicates of what the snapshot already holds — including a
    retried commit's twin record), later ones re-insert their atoms
    and run the backend's own `refresh()` commit path, re-verified
    against delta_version CONTINUITY: every applied record must land
    the store exactly on its recorded version, else the log lies and
    restore fails typed rather than serve a diverged store."""
    from das_tpu import fault

    records, _torn = fault.fetch_retry().run(
        lambda: read_wal(os.path.join(gen_dir, manifest["wal"]))
    )
    replayed = 0
    for rec in records:
        v = int(rec["v"])
        if v <= db.delta_version:
            continue  # predates the snapshot, or a retried commit's twin
        if v != db.delta_version + 1:
            raise SnapshotCorruptError(
                f"WAL continuity broken: record v{v} after store "
                f"v{db.delta_version}"
            )
        _replay_record(db.data, rec)
        db.refresh()
        if db.delta_version != v:
            raise SnapshotCorruptError(
                f"WAL replay diverged: store v{db.delta_version} after "
                f"applying record v{v}"
            )
        replayed += 1
    DUR_STATS["recovery_replayed"] = (
        int(DUR_STATS["recovery_replayed"]) + replayed
    )
    return replayed


def restore(root: str, config=None, backend: Optional[str] = None):
    """Warm-state restore: newest VALID snapshot generation + WAL
    replay to head + warm bundle — the replica-fleet cold-start path
    (`TensorDB.restore` / `ShardedDB.restore` delegate here).  Returns
    the live backend with durability re-attached (subsequent commits
    append to the restored generation's WAL)."""
    from das_tpu import obs
    from das_tpu.core.config import DasConfig

    t0 = time.perf_counter()
    config = config or DasConfig.from_env()
    backend = backend or config.backend
    with obs.span("dur.restore", backend=backend):
        data, manifest, gen_dir = newest_valid_generation(root)
        if backend == "sharded":
            from das_tpu.parallel.sharded_db import ShardedDB
            import dataclasses

            # checkpoint_path steers ShardedDB's existing slab-restore
            # path at the verified generation dir
            cfg = dataclasses.replace(config, checkpoint_path=gen_dir)
            db = ShardedDB(data, cfg)
        else:
            from das_tpu.storage.tensor_db import TensorDB

            db = TensorDB(data, config)
        db.delta_version = int(manifest["delta_version"])
        replayed = replay_wal(db, gen_dir, manifest)
        if wal_enabled(config):
            db._wal = DeltaLog(os.path.join(gen_dir, WAL_FILE), db.data)
        db._snapshot_root = root
        warm_applied = _apply_warm(db, gen_dir, manifest)
    elapsed = time.perf_counter() - t0
    DUR_STATS["generation"] = int(manifest["generation"])
    DUR_STATS["last_restore_s"] = round(elapsed, 4)
    if obs.enabled():
        obs.counter("dur.recovery_replayed").inc(replayed)
        obs.histogram("dur.restore_ms").observe(elapsed * 1e3)
    from das_tpu.utils.logger import logger

    logger().info(
        f"dasdur restore: gen {manifest['generation']} + {replayed} WAL "
        f"commits in {elapsed:.3f}s (warm bundle "
        f"{'applied' if warm_applied else 'absent/stale'})"
    )
    return db


def _apply_warm(db, gen_dir: str, manifest: Dict) -> bool:
    """Apply the warm-state bundle when its recorded delta_version
    still matches the restored store (the existing staleness guard:
    WAL replay past the snapshot makes the bundle stale, exactly like
    a result-cache entry — discarded, never trusted)."""
    warm_v = manifest.get("warm_delta_version")
    meta = manifest["sections"].get(WARM_FILE)
    if meta is None or warm_v is None:
        return False
    if int(warm_v) != int(db.delta_version):
        return False  # replayed past the snapshot: bundle is stale
    from das_tpu import fault

    try:
        payload = fault.fetch_retry().run(
            lambda: _verified_bytes(os.path.join(gen_dir, WARM_FILE), meta)
        )
        state = msgpack.unpackb(payload, raw=False, strict_map_key=False)
        from das_tpu.query.fused import apply_warm_state

        return apply_warm_state(db, state)
    except SnapshotCorruptError:
        raise
    except Exception:  # noqa: BLE001 — warm state is a perf hint only
        return False


# -- attach (live durability) ------------------------------------------------


def attach(db, root: str, config=None) -> str:
    """Arm durability on a live backend: make the root's newest
    generation REFLECT this store, then point the backend's delta log
    at its WAL.  An empty root gets the initial snapshot (the WAL
    needs a base to replay onto).  A populated root is reused ONLY
    when its newest generation provably describes this exact store
    (delta_version AND content fingerprint match — the restore path
    arms its own WAL, so a mismatch here means the caller attached a
    DIFFERENT store to an old root); anything else gets a fresh
    generation, because appending this store's delta_versions to
    another store's WAL would be silently skipped — or fail the
    continuity check — at replay.  Returns the active generation dir."""
    gens = list_generations(root)
    cfg = config if config is not None else getattr(db, "config", None)
    if gens:
        gen_dir = gens[-1][1]
        try:
            from das_tpu.storage import checkpoint

            manifest = read_manifest(gen_dir)
            # the WAL must also be EMPTY: any record means the lineage's
            # head is already PAST this snapshot — re-arming it would
            # append a second run's versions that replay dedups away
            # (silently dropped fsynced commits); a fresh generation
            # keeps every lineage single-writer-single-history
            wal_records, _torn = read_wal(
                os.path.join(gen_dir, manifest.get("wal", WAL_FILE)),
                truncate=False,
            )
            matches = (
                not wal_records
                and int(manifest.get("delta_version", -1))
                == int(getattr(db, "delta_version", 0))
                and manifest.get("content_sig")
                == checkpoint._content_sig(db.data.finalize())
            )
        except Exception:  # noqa: BLE001 — unreadable = not this store
            matches = False
        if matches:
            if wal_enabled(cfg):
                # position the log at the CURRENT store: appends from
                # here describe commits after attach (earlier state is
                # the snapshot + existing records' job)
                db._wal = DeltaLog(os.path.join(gen_dir, WAL_FILE), db.data)
            db._snapshot_root = root
            DUR_STATS["generation"] = gens[-1][0]
            return gen_dir
    return write_snapshot(db, root)
