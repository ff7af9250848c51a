"""Persisted device-layout cache: warm starts for expensive stage prepares.

The cache-time host work behind the device path — parquet decode, string
dictionary encoding, dense ranking of group keys, the chunked-segment sort,
tile materialization, narrowing — is O(N log N) host work that dominates
cold-start latency at scale (measured 600s of the 737s TPC-H q3 SF=100 cold
query on one core; the warm query is 9s). It is also a pure function of
(stage plan fingerprint, input file mtimes). This module persists the
staged host-side artifacts (narrow numpy tiles, LUTs, group key values,
layout metadata, string dictionary snapshots) so a NEW process skips
straight to the h2d transfer: cold q3 SF=100 drops to roughly disk-read +
transfer time.

This is the scan-side analog of the reference's shuffle materialization
(rust/executor/src/flight_service.rs:104-126 persists every stage output
before downstream consumption); here the persisted artifact is the
device-ready input layout rather than a stage result.

Storage layout (one directory per (stage fingerprint, partition)):
  meta.json          versioned manifest: kind, scalars, array manifest
  a<i>.npy           numpy arrays (cols, luts, pad bits, codes, key values)
  (dictionary snapshots ride as string-array .npy)

Keys hash the kernel dispatcher's stage cache key (plan display + scan
files + mtimes + config flags), so a rewritten input file or changed config
misses cleanly. Writes are capped by ballista.tpu.layout_cache_cap_bytes
(oldest-mtime directories evicted first) and are atomic (tmpdir + rename).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# bump to invalidate all persisted entries. v4: float-bits key planes join
# the staged column set and the fused top-k epilogue forces a
# one-chunk-per-group cover — entries written by v3 lack both. v5
# (ISSUE 15 satellite): batch.size folds into the stage/persist key
# (append-only when non-default), and shared-scan eligibility RELIES on a
# warm entry being at the dispatching batch granularity — a v4 store may
# hold suffix-less entries written at ANY batch size, so it is orphaned
# wholesale rather than trusted. v6 (ISSUE 19): parquet-backed batch
# entries move from one-blob-per-(file set, partition) to one entry per
# (path, mtime, size, chunk_index) so appends re-prepare only new chunks;
# whole-set v5 blobs would shadow the chunk store, so they are orphaned.
# v7 (PR 21): LUT-narrowed columns carry 64-entry tables (runtime.
# _LUT_MAX_VALUES); a v6 entry's 256-entry table decodes through the slow
# TPU gather the narrower table exists to avoid.
_FORMAT = 7


def cache_dir_for(base: str, stage_key: str, partition: int) -> str:
    h = hashlib.sha256(f"v{_FORMAT}|{stage_key}|p{partition}".encode()).hexdigest()
    return os.path.join(base, h[:2], h)


def _write_arrays(d: str, arrays: List[np.ndarray]) -> List[int]:
    ids = []
    for i, a in enumerate(arrays):
        np.save(os.path.join(d, f"a{i}.npy"), a, allow_pickle=False)
        ids.append(i)
    return ids


# in-flight write dirs carry this prefix so eviction never deletes them
# while live; ones untouched this long are crashed writers' orphans
_TMP_PREFIX = ".wip-"
_WIP_ORPHAN_S = 6 * 3600.0  # > any plausible single-entry write


def _dir_bytes(base: str) -> int:
    """Committed bytes under base. In-flight .wip- writer dirs are excluded:
    they are not evictable, so counting them against the cap would let one
    concurrent large write force eviction of every committed entry and still
    decline the incoming save (the cap is best-effort and transient
    overshoot while writers finish is the lesser harm)."""
    total = 0
    for root, dirs, files in os.walk(base):
        dirs[:] = [d for d in dirs if not d.startswith(_TMP_PREFIX)]
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


# per-process running total per cache base: a full os.walk of a ~48 GiB tree
# per save is O(entries^2) stat traffic as the cache fills. The estimate is
# refreshed with a real walk only when it says the cap is exceeded (other
# processes' writes are invisible until then — the cap stays best-effort).
from ballista_tpu.utils.locks import make_lock

_size_lock = make_lock("ops.layout_cache._size_lock")
# guarded-by: _size_lock
_size_cache: Dict[str, int] = {}  # base dir -> bytes


def _size_note(base: str, delta: int) -> None:
    with _size_lock:
        if base in _size_cache:
            _size_cache[base] = max(0, _size_cache[base] + delta)


def _evict_to_cap(base: str, incoming: int, cap: int) -> bool:
    """Evict oldest entry dirs until `incoming` fits under `cap`.
    Returns False when it cannot fit (entry bigger than the whole cap)."""
    if incoming > cap:
        return False
    with _size_lock:
        total = _size_cache.get(base)
    if total is not None and total + incoming <= cap:
        return True
    total = _dir_bytes(base)  # estimate says over-cap (or unknown): re-walk
    with _size_lock:
        _size_cache[base] = total
    if total + incoming <= cap:
        return True
    entries = []
    for shard in os.listdir(base):
        sp = os.path.join(base, shard)
        if not os.path.isdir(sp):
            continue
        for name in os.listdir(sp):
            p = os.path.join(sp, name)
            if not os.path.isdir(p):
                continue
            if name.startswith(_TMP_PREFIX):
                # a LIVE writer's in-flight tmpdir must not be evicted —
                # rmtree mid-write would silently drop the ~600s prepare it
                # is persisting. A crashed writer's orphan, however, would
                # hold disk forever; reclaim once clearly abandoned. (wip
                # bytes are excluded from `total`, so no cap adjustment.)
                try:
                    if time.time() - os.path.getmtime(p) > _WIP_ORPHAN_S:
                        shutil.rmtree(p, ignore_errors=True)
                except OSError:
                    pass
                continue
            try:
                entries.append((os.path.getmtime(p), p, _dir_bytes(p)))
            except OSError:
                pass
    entries.sort()
    for _mtime, p, nbytes in entries:
        if total + incoming <= cap:
            break
        shutil.rmtree(p, ignore_errors=True)
        total -= nbytes
        _size_note(base, -nbytes)
    return total + incoming <= cap


def save_entry(
    base: str,
    stage_key: str,
    partition: int,
    meta: dict,
    arrays: List[np.ndarray],
    cap_bytes: int,
) -> None:
    """Atomically persist one prepared-partition artifact. `meta` must be
    JSON-serializable and reference arrays by index into `arrays`.
    Best-effort: any failure leaves no partial entry and never raises."""
    try:
        target = cache_dir_for(base, stage_key, partition)
        if os.path.isdir(target):
            return
        incoming = sum(a.nbytes for a in arrays)
        os.makedirs(os.path.dirname(target), exist_ok=True)
        if not _evict_to_cap(base, incoming, cap_bytes):
            return
        tmp = tempfile.mkdtemp(dir=os.path.dirname(target), prefix=_TMP_PREFIX)
        try:
            _write_arrays(tmp, arrays)
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump({"format": _FORMAT, **meta}, f)
            try:
                os.rename(tmp, target)
                _size_note(base, incoming)
            except OSError:  # raced with another writer: keep theirs
                shutil.rmtree(tmp, ignore_errors=True)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
    except Exception:
        return


def load_entry(
    base: str, stage_key: str, partition: int
) -> Optional[Tuple[dict, List[np.ndarray]]]:
    """Load a persisted artifact; None on miss or any corruption."""
    d = cache_dir_for(base, stage_key, partition)
    try:
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        if meta.get("format") != _FORMAT:
            return None
        arrays = []
        i = 0
        while os.path.exists(os.path.join(d, f"a{i}.npy")):
            arrays.append(np.load(os.path.join(d, f"a{i}.npy"), allow_pickle=False))
            i += 1
        if i != meta.get("n_arrays", i):
            return None
        try:
            os.utime(d)  # LRU recency for _evict_to_cap
        except OSError:
            pass  # read-only cache: the hit still counts
        return meta, arrays
    except Exception:
        return None


# -- (de)hydration helpers for the stage entry shapes -----------------------

def pack_arrow_arrays(arrays_pa) -> np.ndarray:
    """Serialize a list of equal-length Arrow arrays (group key values — any
    Arrow type: strings, dates, decimals) as one uint8 IPC-file buffer, so
    they ride the numpy-only entry format unchanged."""
    import pyarrow as pa

    cols = {}
    for i, kv in enumerate(arrays_pa):
        if isinstance(kv, pa.ChunkedArray):
            kv = kv.combine_chunks()
        elif not isinstance(kv, pa.Array):
            kv = pa.array(kv)
        cols[f"k{i}"] = kv
    table = pa.table(cols) if cols else pa.table({})
    sink = pa.BufferOutputStream()
    with pa.ipc.new_file(sink, table.schema) as w:
        w.write_table(table)
    return np.frombuffer(sink.getvalue(), dtype=np.uint8).copy()


def unpack_arrow_arrays(buf: np.ndarray) -> List:
    import pyarrow as pa

    table = pa.ipc.open_file(pa.BufferReader(buf.tobytes())).read_all()
    return [table.column(i).combine_chunks() for i in range(table.num_columns)]


def pack_dict_snapshot(dicts) -> Tuple[dict, List[np.ndarray]]:
    """Snapshot a ScanDictionaries registry as (meta, arrays). String codes
    are baked into the persisted tiles; a fresh process must adopt the SAME
    value->code mapping or compiled predicates (built from the live
    dictionary at run time) would compare against different codes."""
    meta = {}
    arrays: List[np.ndarray] = []
    for idx, d in dicts.dicts.items():
        snap = d.snapshot()
        if snap is None:
            continue
        meta[str(idx)] = len(arrays)
        arrays.append(np.asarray(snap.to_pylist(), dtype=object).astype(str))
    return meta, arrays


def adopt_dict_snapshot(dicts, meta: dict, arrays: List[np.ndarray]) -> bool:
    """Restore dictionary state. Refuses (False) when a live dictionary is
    NOT a prefix of the snapshot — codes would be inconsistent with the
    persisted tiles. (Growth is append-only, so a same-plan process that
    compiled the same literals first always passes.)"""
    import pyarrow as pa
    import pyarrow.compute as pc

    for key, ai in meta.items():
        idx = int(key)
        values = pa.array(list(arrays[ai]))
        d = dicts.for_column(idx)
        with d._lock:
            cur = d.values
            if cur is not None:
                if len(cur) > len(values):
                    return False
                if len(cur) and not pc.all(
                    pc.equal(cur, values.slice(0, len(cur)))
                ).as_py():
                    return False
            d.values = values
    return True
