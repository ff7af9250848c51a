"""Arrow <-> device runtime: column transfer, dictionary encoding, padding.

TPU-first data discipline (SURVEY §7 "TPU operator lowering"):
- strings never reach the device as bytes: each string column is encoded to
  int32 codes against a per-scan growing dictionary; predicates on strings
  become code comparisons / table gathers; group keys aggregate over codes
  and decode at the end
- float64 narrows to float32 (TPU vector unit native; f64 is emulated and
  slow), int64 narrows to int32 after a range check, date32 is int32 days
- batches are padded to power-of-two row buckets so XLA compiles a bounded
  set of program shapes (recompilation control)
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ballista_tpu.errors import ExecutionError
from ballista_tpu.utils.locks import make_lock


class UnsupportedOnDevice(Exception):
    """Raised when a column/expr can't lower to the device path; callers
    fall back to the host Arrow kernels."""


class ColumnDictionary:
    """Growing per-column dictionary mapping values -> stable int32 codes.

    Thread-safe: executor task threads can run different partitions of one
    cached stage concurrently, and both prepare-time encode() and
    aux-build-time code_of() extend the dictionary (read-modify-write on
    `values`); an unguarded interleaving would silently re-assign codes
    already baked into pinned device tiles."""

    def __init__(self) -> None:
        self.values: Optional[pa.Array] = None  # distinct values; guarded-by: self._lock
        self._lock = make_lock("ops.runtime._lock")

    def encode(self, arr: pa.Array) -> np.ndarray:
        with self._lock:
            return self._encode(arr)

    # holds-lock: self._lock
    def _encode(self, arr: pa.Array) -> np.ndarray:
        """Encode an Arrow array to codes against this dictionary, extending
        it with novel values. Nulls -> -1."""
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if isinstance(arr, pa.DictionaryArray):
            d = arr  # parquet dictionary pages: codes come for free
        else:
            d = pc.dictionary_encode(arr)
        if isinstance(d, pa.ChunkedArray):
            d = d.combine_chunks()
        local_values = d.dictionary
        # nulls -> -1 BEFORE the numpy conversion (a null-carrying indices
        # array converts via float NaN, whose int cast is undefined)
        local_codes = (
            pc.fill_null(d.indices, -1)
            .to_numpy(zero_copy_only=False)
            .astype(np.int64)
        )
        if self.values is None:
            self.values = local_values
            remap = np.arange(len(local_values), dtype=np.int64)
        else:
            idx = pc.index_in(local_values, value_set=self.values)
            idx_np = idx.to_numpy(zero_copy_only=False).astype(np.float64)
            missing = np.isnan(idx_np)
            if missing.any():
                novel = local_values.filter(pa.array(missing))
                base = len(self.values)
                self.values = pa.concat_arrays(
                    [self.values.cast(novel.type), novel]
                )
                idx_np = np.where(
                    missing, base + np.cumsum(missing) - 1, idx_np
                )
            remap = idx_np.astype(np.int64)
        out = np.where(local_codes >= 0, remap[np.maximum(local_codes, 0)], -1)
        return out.astype(np.int32)

    def snapshot(self) -> Optional[pa.Array]:
        """Consistent point-in-time view of the accumulated values (a
        concurrent encode may grow the dictionary; callers must not read
        `values` twice)."""
        with self._lock:
            return self.values

    def code_of(self, value) -> int:
        """Code for a literal, extending the dictionary so it always exists."""
        with self._lock:
            if self.values is None:
                self.values = pa.array([value])
                return 0
            idx = pc.index_in(pa.scalar(value, type=self.values.type), value_set=self.values)
            if idx.as_py() is None:
                self.values = pa.concat_arrays([self.values, pa.array([value], type=self.values.type)])
                return len(self.values) - 1
            return int(idx.as_py())

    def __len__(self) -> int:
        with self._lock:
            return 0 if self.values is None else len(self.values)


class ScanDictionaries:
    """Per-scan registry of ColumnDictionary keyed by column index."""

    def __init__(self) -> None:
        self.dicts: Dict[int, ColumnDictionary] = {}

    def for_column(self, index: int) -> ColumnDictionary:
        if index not in self.dicts:
            self.dicts[index] = ColumnDictionary()
        return self.dicts[index]


# -- device residency accounting -------------------------------------------
# One chip's HBM is shared by every cached stage; when a new partition would
# push the total past the configured budget, other stages' least-recently
# used pins are evicted to make room (re-prepared on their next touch), and
# only an entry that cannot fit even after eviction streams per query. A
# stage invalidated by the kernel dispatcher releases its reservations.
import threading
import time

_res_lock = make_lock("ops.runtime._res_lock")
_resident_bytes = 0  # guarded-by: _res_lock
_reservations: dict = {}  # token -> bytes; guarded-by: _res_lock
_pinned: dict = {}  # token -> (stage, partition), for LRU; guarded-by: _res_lock
_last_used: dict = {}  # token -> monotonic last-run time; guarded-by: _res_lock


def entry_device_bytes(obj) -> int:
    """Recursive nbytes of the DEVICE (jax) arrays inside a prepared cache
    entry. Host-side metadata (numpy rank orders, arrow key values) rides in
    the same dicts but does not occupy HBM, so it is not counted."""
    try:
        import jax

        if isinstance(obj, jax.Array):
            return int(obj.nbytes)
    except ImportError:
        pass
    if isinstance(obj, dict):
        return sum(entry_device_bytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(entry_device_bytes(v) for v in obj)
    return 0


def reserve_and_pin(stage, partition: int, entry, cache: dict, nbytes: int, budget: int) -> bool:
    """Atomically reserve HBM budget AND insert the prepared entry into the
    stage's cache dict, refusing retired stages.

    When the budget is full, OTHER stages' pinned partitions are evicted
    least-recently-used-first until the new entry fits (touch_residency
    maintains recency). First-come residency would make every query after
    the budget fills stream per-iteration forever — fatal at SF=100, where
    q1's lineitem residency alone is most of a 16 GB chip and the suite
    visits many stages. With LRU, the working set follows the query mix and
    an evicted stage simply re-prepares on its next touch. Eviction is safe
    mid-run: a task thread inside the victim's step holds Python references
    to its device arrays, so compute completes; only the cache entry goes.

    A task thread may still be inside stage.run() when another thread
    evicts that stage (superseded mtimes) and releases its reservations.
    The retired check, the reservation, and the dict insert all happen
    under the same lock release_stage_residency holds for the flag write
    and the cache sweep — so there is no window where a reservation exists
    for a partition the sweep cannot see (which would leak budget
    permanently once the stage is unreachable)."""
    global _resident_bytes
    token = (id(stage), partition)
    with _res_lock:
        if getattr(stage, "_retired", False):
            return False
        if token not in _reservations:
            if nbytes > budget:
                return False  # can never fit; do NOT disturb other pins
            if _resident_bytes + nbytes > budget:
                _evict_lru_locked(stage, nbytes, budget)
            if _resident_bytes + nbytes > budget:
                return False
            _reservations[token] = nbytes
            _resident_bytes += nbytes
            _pinned[token] = (stage, partition)
        _last_used[token] = time.monotonic()
        cache[partition] = entry
        return True


def attach_to_pinned(stage, partition: int, entry: dict, cache: dict,
                     name: str, value, budget: int) -> bool:
    """Keep `value` inside a pinned entry as `entry[name]`, its device arrays
    added to the entry's reservation, so it is evicted and released with the
    entry and `resident_bytes()` returns to what it was. False, and nothing
    kept, where `entry` is not (or no longer) the partition's pinned entry —
    it streams, was evicted or its stage retired — or the budget has no room
    even after evicting other stages' pins. Replacing a value already kept
    under `name` reserves the difference only, so two racing builders of the
    same value reserve it once."""
    global _resident_bytes
    token = (id(stage), partition)
    nbytes = entry_device_bytes(value)
    with _res_lock:
        if cache.get(partition) is not entry or token not in _reservations:
            return False
        delta = nbytes - entry_device_bytes(entry.get(name))
        if _resident_bytes + delta > budget:
            _evict_lru_locked(stage, delta, budget)
        if _resident_bytes + delta > budget:
            return False
        _reservations[token] += delta
        _resident_bytes += delta
        entry[name] = value
        return True


# refuse an eviction plan that frees more than this multiple of the bytes
# requested: re-uploading a 15 GB pin to admit a 2 GB one costs more h2d
# time than the newcomer streaming ever would, and two such stages
# alternating would thrash the whole budget every query
_EVICT_COST_RATIO = 4
# a stage evicted within this window is immune from re-eviction: in an
# A,B,A,B access pattern where A and B fit alone but not together, plain
# LRU would make EVERY query a full re-prepare (both stages thrash); after
# one thrash cycle the cooldown pins the survivor and the other streams —
# the same steady state first-come residency gave that pattern, while
# sequential workloads (the 22-query suite) still evict freely
_EVICT_COOLDOWN_S = 60.0
_evicted_at: dict = {}  # id(stage) -> last eviction time; guarded-by: _res_lock


# holds-lock: _res_lock
def _evict_lru_locked(requesting_stage, nbytes: int, budget: int) -> None:
    """Evict other stages' pinned partitions, oldest touch first, until
    `nbytes` fits. Caller holds _res_lock. The requesting stage's own
    entries are never victims (evicting them to fit a sibling partition of
    the same stage would thrash a multi-partition prepare loop), recently
    evicted stages are immune (thrash cooldown), and the whole plan is
    abandoned — nothing evicted — when it cannot fit the request or would
    free more than _EVICT_COST_RATIO times the request."""
    global _resident_bytes
    now = time.monotonic()
    for sid in [s for s, ts in _evicted_at.items() if now - ts > _EVICT_COOLDOWN_S]:
        del _evicted_at[sid]
    candidates = sorted(
        (
            t
            for t, (s, _p) in _pinned.items()
            if s is not requesting_stage and id(s) not in _evicted_at
        ),
        key=lambda t: _last_used.get(t, 0.0),
    )
    need = _resident_bytes + nbytes - budget
    chosen, freed = [], 0
    for t in candidates:
        if freed >= need:
            break
        size = _reservations.get(t, 0)
        if size > _EVICT_COST_RATIO * nbytes:
            continue  # huge victim for a small need: leave it resident
        chosen.append(t)
        freed += size
    if freed < need or freed > _EVICT_COST_RATIO * nbytes:
        return  # plan doesn't fit or costs more than it buys — evict nothing
    for t in chosen:
        victim_stage, p = _pinned.pop(t)
        _evicted_at[id(victim_stage)] = now
        _last_used.pop(t, None)
        _resident_bytes -= _reservations.pop(t, 0)
        for attr in ("_device_cache", "_prepared"):
            c = getattr(victim_stage, attr, None)
            if c is not None:
                c.pop(p, None)


def make_headroom(stage, nbytes: int, budget: int) -> None:
    """Best-effort LRU eviction BEFORE a large upload. reserve_and_pin only
    evicts at pin time — after the transfer — which is too late to save the
    chip when other stages' pins plus the incoming tiles would exceed HBM."""
    with _res_lock:
        if _resident_bytes + nbytes > budget:
            _evict_lru_locked(stage, nbytes, budget)


def touch_residency(stage, partition: int) -> None:
    """Record a cache hit for LRU ordering. Only refreshes live pins: a
    racing eviction may have dropped the token already, and re-inserting
    _last_used for it would leak bookkeeping no release path sweeps."""
    token = (id(stage), partition)
    with _res_lock:
        if token in _pinned:
            _last_used[token] = time.monotonic()


_stack_jit = None


def fetch_arrays(arrs: list) -> list:
    """Materialize a list of device arrays to numpy with ONE d2h transfer
    per distinct (shape, dtype) group instead of one per array.

    Every transfer pays a fixed d2h latency (not measured on a directly
    attached chip), so a partition split into k row buckets costs k of
    them if fetched array-by-array. Same-shaped outputs are stacked
    on-device (async dispatch, no extra sync) and fetched as one array.
    """
    from ballista_tpu.utils import tracing

    with tracing.span("runtime.readback", arrays=len(arrs)) as sp:
        # the programs that produce `arrs` may still run: their wait is the
        # child, the span's own time is the stacking and the copy
        with tracing.span("runtime.device_wait"):
            for a in arrs:
                if hasattr(a, "block_until_ready"):
                    a.block_until_ready()
        out = _fetch_arrays(arrs)
        sp.set(bytes=sum(int(a.nbytes) for a in out))
    return out


def _synced_copy(x) -> Tuple[np.ndarray, float]:
    """(x on the host, the copy's seconds) under `runtime.readback`. The
    producer is synced first, as the child `runtime.device_wait`: the span's
    own time is the d2h copy alone, and a host blocked on a busy device is
    not read as a slow copy."""
    from ballista_tpu.utils import tracing

    with tracing.span("runtime.readback") as sp:
        if hasattr(x, "block_until_ready"):
            with tracing.span("runtime.device_wait"):
                x.block_until_ready()
        t0 = time.perf_counter()
        arr = np.asarray(x)
        copy_s = time.perf_counter() - t0
        sp.set(bytes=int(arr.nbytes))
    return arr, copy_s


def copy_out(x) -> np.ndarray:
    """One program's output as numpy, under the spans of `_synced_copy`. It
    counts nothing: the caller records rows and bytes (`record_readback`)
    in its own terms, e.g. the slice of a padded bucket that it keeps."""
    return _synced_copy(x)[0]


def _fetch_arrays(arrs: list) -> list:
    global _stack_jit
    if len(arrs) <= 1:
        return [np.asarray(a) for a in arrs]
    import jax
    import jax.numpy as jnp

    if _stack_jit is None:
        _stack_jit = jax.jit(lambda *xs: jnp.stack(xs))
    out: list = [None] * len(arrs)
    groups: Dict[tuple, list] = {}
    for i, a in enumerate(arrs):
        groups.setdefault((tuple(a.shape), str(a.dtype)), []).append(i)
    for idxs in groups.values():
        if len(idxs) == 1:
            out[idxs[0]] = np.asarray(arrs[idxs[0]])
            continue
        # bounded stack arities {2,4,8}: jit caches per (arity, shape), and
        # the batch count is data-dependent — unpadded arities would compile
        # a fresh trivial stack program per distinct count. Short chunks
        # pad by repeating the first member; the duplicate rows are dropped
        # on unpack.
        for lo in range(0, len(idxs), 8):
            chunk = idxs[lo:lo + 8]
            arity = 2 if len(chunk) <= 2 else (4 if len(chunk) <= 4 else 8)
            padded = chunk + [chunk[0]] * (arity - len(chunk))
            # ballista-lint: disable=readback-discipline -- transport-layer batching: callers (stage.run) record the result-readback rows/bytes with aggregate semantics; recording here too would double-count
            stacked = np.asarray(_stack_jit(*[arrs[i] for i in padded]))
            for j, i in enumerate(chunk):
                out[i] = stacked[j]
    return out


def release_residency(token) -> None:
    global _resident_bytes
    with _res_lock:
        _resident_bytes -= _reservations.pop(token, 0)
        _pinned.pop(token, None)
        _last_used.pop(token, None)


def release_stage_residency(stage) -> None:
    """Drop a stage's cached device entries and their reservations (the
    dispatcher calls this when it permanently declines or evicts a stage).
    Runs entirely under the residency lock: the retired flag and the cache
    sweep are one atomic step against reserve_and_pin."""
    global _resident_bytes
    with _res_lock:
        stage._retired = True
        for attr in ("_device_cache", "_prepared"):
            cache = getattr(stage, attr, None)
            if cache:
                for p in list(cache):
                    token = (id(stage), p)
                    _resident_bytes -= _reservations.pop(token, 0)
                    _pinned.pop(token, None)
                    _last_used.pop(token, None)
                cache.clear()


def resident_bytes() -> int:
    with _res_lock:
        return _resident_bytes


def reset_residency() -> None:
    global _resident_bytes
    with _res_lock:
        _resident_bytes = 0
        _reservations.clear()
        _pinned.clear()
        _last_used.clear()
        _evicted_at.clear()


def bucket_rows(n: int, minimum: int = 1024) -> int:
    """Pad row counts to power-of-two buckets to bound XLA recompilation."""
    b = minimum
    while b < n:
        b <<= 1
    return b


_INT32_MIN = -(2**31)
_INT32_MAX = 2**31 - 1


def column_to_numpy(
    arr: pa.Array, dtype: pa.DataType, dictionary: Optional[ColumnDictionary]
) -> np.ndarray:
    """Lower one Arrow column to a device-ready numpy array.

    String columns tolerate nulls: they ride as -1 dictionary codes, and
    every compiled code predicate (eq/neq/LIKE/IN/IS NULL) applies SQL
    three-valued logic to code -1. Group keys are guarded separately
    (_group_codes declines null keys host-side) and code-typed aggregate
    inputs decline at compile, so predicates are the only device consumers.
    Numeric/date/bool columns with nulls decline (no null representation)."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_string(dtype) or pa.types.is_large_string(dtype):
        assert dictionary is not None
        return dictionary.encode(arr)
    if arr.null_count:
        raise UnsupportedOnDevice("null values in device column")
    if pa.types.is_floating(dtype):
        return arr.to_numpy(zero_copy_only=False).astype(np.float32)
    if pa.types.is_date(dtype):
        return arr.cast(pa.int32()).to_numpy(zero_copy_only=False)
    if pa.types.is_integer(dtype):
        vals = arr.to_numpy(zero_copy_only=False)
        if vals.dtype.itemsize > 4:
            if len(vals) and (vals.min() < _INT32_MIN or vals.max() > _INT32_MAX):
                raise UnsupportedOnDevice("int64 values exceed int32 range")
            vals = vals.astype(np.int32)
        return vals
    if pa.types.is_boolean(dtype):
        return arr.to_numpy(zero_copy_only=False).astype(np.bool_)
    raise UnsupportedOnDevice(f"unsupported device dtype {dtype}")


_LUT_MIN_ROWS = 4096
# the LUT's fixed length, and so the most distinct values a column may have
# to be stored as codes. XLA:TPU lowers a gather from a table of <= 64
# entries to a fused chain of selects; from 128 entries up it emits a real
# gather, which on a v5e took ~1.05 s per 120M decoded elements against
# 3.8 ms for 64 (and 1.8 ms for 16), and materialised its f32 output —
# padded 16x for [V, 8] tiles — which is what put q5 at SF=10 past the
# chip's HBM at compile time (my chip run, PR 21: dev probe of `jnp.take`
# over u8[15M, 8] and u8[117k, 1024] tiles, tables of 16..256 entries).
# A column with more distinct values stays wide f32.
_LUT_MAX_VALUES = 64
_LUT_SAMPLE = 65536


def narrow_column(
    npcol: np.ndarray, prior: Optional[str] = None
) -> Tuple[np.ndarray, Optional[np.ndarray], str]:
    """Narrow a device-bound column for residency: (narrow array, optional
    f32 LUT, choice tag).

    HBM capacity and host->device bandwidth — not FLOPs — bound SF=100 on a
    16 GB chip (q1's lineitem columns alone are ~17 GB as int32/f32), so
    columns are stored narrow and widened in-program (widen_cols): int32
    whose range fits goes int8/int16; a float32 column with at most
    _LUT_MAX_VALUES distinct values (TPC-H quantity/discount/tax are
    decimal grids of 50/11/9) becomes uint8
    codes plus an f32 lookup table gathered on device. Compute dtypes after
    widening are exactly the canonical int32/f32, so results are bit-equal.

    `prior` is the choice a previous batch of the SAME column made; passing
    it back keeps the narrow dtype stable across batches so the jitted step
    compiles once (a per-batch min/max decision would retrace per width).
    A batch the prior no longer fits escalates to the next wider choice —
    one bounded retrace, never a flap back. LUTs are padded to a fixed
    _LUT_MAX_VALUES length for the same reason.
    """
    if npcol.dtype == np.int32:
        if not len(npcol):
            return npcol, None, prior or "int32"
        mn, mx = int(npcol.min()), int(npcol.max())
        choice = "int32"
        if -128 <= mn and mx <= 127:
            choice = "int8"
        elif -32768 <= mn and mx <= 32767:
            choice = "int16"
        # never narrow below what an earlier batch needed
        order = {"int8": 0, "int16": 1, "int32": 2}
        if prior in order and order[prior] > order[choice]:
            choice = prior
        if choice == "int32":
            return npcol, None, choice
        return npcol.astype(choice), None, choice
    if npcol.dtype == np.float32 and prior in (None, "lut"):
        if len(npcol) < _LUT_MIN_ROWS and prior != "lut":
            # too small to judge; stay UNDECIDED — a "wide" verdict here
            # would be sticky and lock a large later batch (prepare order
            # across partitions is arbitrary) out of LUT narrowing
            return npcol, None, prior
        # cheap sample gate first: a high-cardinality column (extendedprice
        # at SF=100 is ~1M distinct floats) must not pay a full
        # dictionary_encode just to discover it cannot LUT-encode
        sample = npcol[:: max(1, len(npcol) // _LUT_SAMPLE)][:_LUT_SAMPLE]
        if len(np.unique(sample)) <= _LUT_MAX_VALUES:
            d = pc.dictionary_encode(pa.array(npcol))
            if isinstance(d, pa.ChunkedArray):
                d = d.combine_chunks()
            if len(d.dictionary) <= _LUT_MAX_VALUES:
                lut = np.zeros(_LUT_MAX_VALUES, dtype=np.float32)
                vals = d.dictionary.to_numpy(zero_copy_only=False)
                lut[: len(vals)] = vals.astype(np.float32)
                codes = d.indices.to_numpy(zero_copy_only=False).astype(np.uint8)
                return codes, lut, "lut"
    return npcol, None, "wide"




def widen_cols(cols: dict) -> dict:
    """In-program inverse of narrow_column, applied at the top of every
    jitted device step: sub-4-byte ints widen to int32, (codes, lut) pairs
    gather back to float32. Wide inputs pass through untouched, so callers
    that never narrow (the SPMD mesh programs, filter_batch) share the same
    cores, and XLA reads the narrow representation from HBM while all
    arithmetic stays int32/f32."""
    import jax.numpy as jnp

    out = {}
    for idx, v in cols.items():
        if isinstance(v, tuple):
            codes, lut = v
            out[idx] = jnp.take(lut, codes.astype(jnp.int32))
        elif np.issubdtype(v.dtype, np.integer) and v.dtype.itemsize < 4:
            out[idx] = v.astype(jnp.int32)
        else:
            out[idx] = v
    return out


def pad_to(arr: np.ndarray, n: int, fill=0) -> np.ndarray:
    if len(arr) == n:
        return arr
    pad = np.full(n - len(arr), fill, dtype=arr.dtype)
    return np.concatenate([arr, pad])


# -- pipelined ingestion ----------------------------------------------------
# The per-partition hot path used to be one serial thread: at SF=100 the
# host-side parquet decode costs ~400 s while the device aggregate takes
# ~100 ms, so the chip idled >99% of first-touch wall-clock. These two
# helpers are the bounded producer/consumer shapes the ingest pipeline is
# built from (ops/stage.py scan/decode vs encode/upload; distributed
# shuffle-piece fetches). Both preserve input order exactly — the consume
# side of a stage prepare MUST stay ordered because each batch's narrow
# choice feeds the next batch's narrow_column prior — and both bound the
# number of results in flight so host RSS stays ~depth decoded items.


def ordered_map(fn, items, workers: int, depth: int = 2):
    """Concurrent map over a finite, independent item list, yielding
    results in input order with at most `depth` in flight — depth is the
    host-RSS cap and wins over workers (extra threads beyond it idle).
    workers <= 0 (or a single item) degenerates to the serial loop."""
    items = list(items)
    if workers <= 0 or len(items) <= 1:
        for it in items:
            yield fn(it)
        return
    import collections
    from concurrent.futures import ThreadPoolExecutor

    inflight = max(1, depth)
    ex = ThreadPoolExecutor(max_workers=workers)
    pending: collections.deque = collections.deque()
    i = 0
    try:
        while pending or i < len(items):
            while i < len(items) and len(pending) < inflight:
                pending.append(ex.submit(fn, items[i]))
                i += 1
            yield pending.popleft().result()
    finally:
        for f in pending:
            f.cancel()
        ex.shutdown(wait=True)


def pipelined_map(src, fn, workers: int, depth: int = 2, on_src_time=None):
    """Ordered streaming producer/consumer over an iterator.

    A reader thread pulls items from `src` serially (the pull itself may be
    expensive IO — e.g. a parquet read inside a generator), submits
    fn(item) to a `workers`-thread pool, and the caller consumes results in
    input order. At most `depth` results exist beyond the one being
    consumed. Exceptions from `src` or `fn` re-raise at the consumption
    point in order, so decline signals (UnsupportedOnDevice, TooManyGroups)
    keep their serial-path semantics. `on_src_time(seconds)` is called from
    the reader thread with each pull's duration (ingest scan timing).

    workers <= 0 degenerates to the serial in-thread map."""
    if workers <= 0:
        it = iter(src)
        while True:
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                return
            if on_src_time is not None:
                on_src_time(time.perf_counter() - t0)
            yield fn(item)
    import queue as _queue
    from concurrent.futures import ThreadPoolExecutor

    done = object()
    stop = threading.Event()
    slots = threading.Semaphore(max(1, depth))
    out_q: "_queue.Queue" = _queue.Queue()
    ex = ThreadPoolExecutor(max_workers=workers)

    def _reader() -> None:
        it = iter(src)
        while not stop.is_set():
            # bounded wait so a consumer that stopped early (exception,
            # generator close) can never strand this thread on the semaphore
            if not slots.acquire(timeout=0.05):
                continue
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                slots.release()
                break
            except BaseException as e:  # src failure surfaces in order
                slots.release()
                out_q.put(("err", e))
                return
            if on_src_time is not None:
                on_src_time(time.perf_counter() - t0)
            try:
                out_q.put(("fut", ex.submit(fn, item)))
            except RuntimeError:
                # consumer exited early and its finally shut the pool down
                # while we were blocked in a long pull — nobody is reading
                # out_q anymore, just exit quietly
                return
        out_q.put(done)

    from ballista_tpu.utils import tracing

    consumer = tracing.current()

    def _reader_for_consumer() -> None:
        # spans the pull opens (a mapped scan's dimension build) are
        # children of the span the consumer has open, the stage's
        with tracing.adopt(consumer):
            _reader()

    reader = threading.Thread(target=_reader_for_consumer, name="ingest-reader",
                              daemon=True)
    reader.start()
    try:
        while True:
            msg = out_q.get()
            if msg is done:
                break
            tag, val = msg
            if tag == "err":
                raise val
            yield val.result()
            slots.release()
    finally:
        stop.set()
        # on normal completion the reader has already exited and the pool is
        # drained, so these return immediately. On early consumer exit (a
        # TooManyGroups retry, an exception) do NOT block behind a multi-
        # second in-flight parquet pull or ranking task: the reader is a
        # daemon guarded against post-shutdown submits, in-flight fn work is
        # pure per-batch compute, and the caller (e.g. the sorted-layout
        # retry) should not stall on work it is about to throw away.
        reader.join(timeout=0.2)
        ex.shutdown(wait=False)


# accumulated ingest timings across stage prepares (the benchmark's
# `prepares` and chip_smoke.py read them):
# scan_s = prefetch-stage work (parquet read + dictionary decode + group
# ranking), encode_s = host narrow/encode, upload_s = h2d transfer, wall_s =
# end-to-end prepare. overlap_frac = 1 - wall / (scan + encode + upload):
# 0 on the serial path, > 0 when the pipeline actually hid host work.
_ingest_lock = make_lock("ops.runtime._ingest_lock")
# guarded-by: _ingest_lock
_ingest_totals = {
    "scan_s": 0.0, "encode_s": 0.0, "upload_s": 0.0, "wall_s": 0.0,
    "prepares": 0,
}


def record_ingest(scan_s: float, encode_s: float, upload_s: float,
                  wall_s: float) -> None:
    with _ingest_lock:
        _ingest_totals["scan_s"] += scan_s
        _ingest_totals["encode_s"] += encode_s
        _ingest_totals["upload_s"] += upload_s
        _ingest_totals["wall_s"] += wall_s
        _ingest_totals["prepares"] += 1


def ingest_stats(reset: bool = False) -> Dict[str, float]:
    """Snapshot of accumulated ingest timings plus the derived overlap
    fraction."""
    with _ingest_lock:
        out = dict(_ingest_totals)
        if reset:
            for k in _ingest_totals:
                _ingest_totals[k] = 0.0 if k != "prepares" else 0
    stages = out["scan_s"] + out["encode_s"] + out["upload_s"]
    out["overlap_frac"] = (
        max(0.0, 1.0 - out["wall_s"] / stages) if stages > 0 else 0.0
    )
    return out


# accumulated device->host result readback across stage runs (the
# benchmark's `readback`, benchmarks/chip/run.py::drain_counters): every aggregate-result d2h transfer on
# the device paths — full-column, fused top-k, fact-agg member/top-k —
# records its width here. rows = trailing-axis length of each fetched
# result (groups or selected candidates), bytes = the packed f32 transfer
# size. The fused Sort+Limit epilogue's whole point is to shrink these to
# O(limit); readbacks is the transfer count.
_readback_lock = make_lock("ops.runtime._readback_lock")
_readback_totals = {"rows": 0, "bytes": 0, "readbacks": 0}  # guarded-by: _readback_lock


def record_readback(rows: int, nbytes: int) -> None:
    with _readback_lock:
        _readback_totals["rows"] += int(rows)
        _readback_totals["bytes"] += int(nbytes)
        _readback_totals["readbacks"] += 1


def readback(x, rows: Optional[int] = None) -> np.ndarray:
    """Canonical device->host result materialization: np.asarray + the
    readback accounting in one step. `rows` defaults to the trailing-axis
    length (group/candidate count in the packed [R, G] result convention);
    pass it explicitly when the row axis is not the trailing one. Every
    device-path np.asarray of a compiled-program result must go through
    here (or pair with record_readback) — enforced by
    dev/analysis's readback-discipline pass.

    With the cost model enabled (ISSUE 10), the transfer's wall time lands
    in the cost store as a per-byte readback observation (observability +
    groundwork for transfer-aware admission; no predictor consults it
    yet). The producing computation is synced FIRST so the
    timer measures the d2h transfer, not whatever async dispatch happens
    to still be in flight."""
    from ballista_tpu.ops import costmodel

    arr, copy_s = _synced_copy(x)
    if costmodel.enabled() and arr.nbytes:
        costmodel.observe("readback", arr.nbytes, copy_s)
    record_readback(
        rows if rows is not None else (arr.shape[-1] if arr.ndim else 1),
        arr.nbytes,
    )
    return arr


def readback_stats(reset: bool = False) -> Dict[str, int]:
    """Snapshot of accumulated result-readback totals."""
    with _readback_lock:
        out = dict(_readback_totals)
        if reset:
            for k in _readback_totals:
                _readback_totals[k] = 0
    return out


# accumulated join-path outcomes across join executions (the benchmark's
# `join_paths`): every device-join attempt lands in exactly one bucket —
# "device" (the M:N kernel or the mesh program produced the result),
# "step_aside" (the multiplicity/gather admission tier declined, host join
# ran instead), or "host_fallback" (any other decline or error). Reasons are
# counted verbatim so a run's record says WHY a join left the device path.
_join_lock = make_lock("ops.runtime._join_lock")
# guarded-by: _join_lock
_join_paths: Dict[str, int] = {}  # path -> count
# guarded-by: _join_lock
_join_reasons: Dict[str, int] = {}  # "path: reason" -> count


def record_join_path(path: str, reason: Optional[str] = None) -> None:
    probe = getattr(_probe_tls, "probe", None)
    if probe is not None:
        probe.buf.append(("join_path", (path, reason)))
        return
    with _join_lock:
        _join_paths[path] = _join_paths.get(path, 0) + 1
        if reason:
            key = f"{path}: {reason}"
            _join_reasons[key] = _join_reasons.get(key, 0) + 1


def join_path_stats(reset: bool = False) -> Dict[str, Dict[str, int]]:
    """Snapshot of accumulated join-path counters: {"paths": {path: n},
    "reasons": {"path: reason": n}}."""
    with _join_lock:
        out = {"paths": dict(_join_paths), "reasons": dict(_join_reasons)}
        if reset:
            _join_paths.clear()
            _join_reasons.clear()
    return out


# accumulated adaptive-routing decisions (ISSUE 10): every engine choice
# the cost-model-aware ladder makes — device / host / split — lands here
# with its predicted-vs-observed cost when a prediction existed, plus named
# events (partial-offload splits, skew re-plans, build-side swaps, cost-
# store health). The benchmark's `engines` reads the engine counts.
# A decision whose observed cost deviates from its prediction by more than
# costmodel.MISPREDICT_FACTOR either way counts as a mispredict; the
# mispredict rate is the model's running honesty meter.
_routing_lock = make_lock("ops.runtime._routing_lock")
# guarded-by: _routing_lock
_routing = {
    "engines": {},  # engine -> decision count
    "events": {},  # event -> count (op:engine decision detail + named events)
    "predicted_s": 0.0,
    "observed_s": 0.0,
    "predictions": 0,
    "mispredicts": 0,
    # last tuned h2d chunk size (ISSUE 13 satellite): a VALUE, not a count —
    # what _h2d_chunk_bytes() chose for the most recent chunked upload
    "h2d_chunk_bytes": 0,
}


# speculative-attempt scope: the build-swap re-plan (ops/join.py) probes
# the swapped shape by running the full device ladder on it, and only a
# probe that produced a result becomes the decision — a failed probe is
# followed by the planned-shape attempt, which records the real outcome.
# Decision counters made inside a probe (record_routing / record_join_path)
# therefore buffer in the probe and land only on commit; without this one
# join would count a host decline AND the planned-side decision. Named
# events (record_routing_event: retier, split_oracle_mismatch, ...) pass
# through — they describe work/store mutations that genuinely happened.
_probe_tls = threading.local()


class _RoutingProbe:
    def __init__(self) -> None:
        self.buf: List[tuple] = []

    def commit(self) -> None:
        """Land the buffered decisions (call AFTER the with-block: the
        probe's records ARE the decision). Replays through the public
        recorders, so a still-active outer probe keeps buffering them."""
        buf, self.buf = self.buf, []
        for kind, args in buf:
            if kind == "routing":
                record_routing(*args)
            elif kind == "trace":
                record_decline_trace(*args)
            else:
                record_join_path(*args)


def record_decline_trace(counter: str, message: str) -> None:
    """Decline observability (tracing counter + debug log) that respects an
    active routing probe: a decline inside a speculative attempt buffers
    like the decision counters, so an uncommitted probe leaves no phantom
    host-fallback trace for a join that actually ran on device."""
    probe = getattr(_probe_tls, "probe", None)
    if probe is not None:
        probe.buf.append(("trace", (counter, message)))
        return
    import logging

    from ballista_tpu.utils import tracing

    tracing.incr(counter)
    logging.getLogger("ballista.tpu").debug("%s", message)


@contextmanager
def routing_probe() -> Iterator[_RoutingProbe]:
    """Buffer routing/join-path decision counters recorded in the body.
    The caller commits them only when the probed attempt became the real
    decision; an uncommitted probe's records are dropped."""
    prev = getattr(_probe_tls, "probe", None)
    probe = _RoutingProbe()
    _probe_tls.probe = probe
    try:
        yield probe
    finally:
        _probe_tls.probe = prev


def record_routing(engine: str, op: str = "",
                   predicted_s: Optional[float] = None,
                   observed_s: Optional[float] = None) -> None:
    """Record one routing decision: which engine ran `op`, and (when the
    cost model predicted) how the prediction held up. Cost totals
    accumulate only when BOTH sides exist, so predicted_s and observed_s
    stay comparable sums over the same decision set."""
    from ballista_tpu.ops.costmodel import gross_mispredict

    probe = getattr(_probe_tls, "probe", None)
    if probe is not None:
        probe.buf.append(("routing", (engine, op, predicted_s, observed_s)))
        return
    with _routing_lock:
        _routing["engines"][engine] = _routing["engines"].get(engine, 0) + 1
        if op:
            k = f"{op}:{engine}"
            _routing["events"][k] = _routing["events"].get(k, 0) + 1
        if predicted_s is not None and observed_s is not None:
            _routing["predictions"] += 1
            _routing["predicted_s"] += float(predicted_s)
            _routing["observed_s"] += float(observed_s)
            if gross_mispredict(predicted_s, observed_s):
                _routing["mispredicts"] += 1


def record_routing_event(event: str, n: int = 1) -> None:
    """Count a named routing event (split, skew_replan, join_build_swapped,
    retier, cost_store_corrupt, ...)."""
    with _routing_lock:
        _routing["events"][event] = _routing["events"].get(event, 0) + int(n)


def routing_stats(reset: bool = False) -> Dict[str, object]:
    """Snapshot of accumulated routing decisions + events. mispredict_rate
    is derived here so every consumer sums the accounting identically."""
    with _routing_lock:
        out = {
            "engines": dict(_routing["engines"]),
            "events": dict(_routing["events"]),
            "predicted_s": _routing["predicted_s"],
            "observed_s": _routing["observed_s"],
            "predictions": _routing["predictions"],
            "mispredicts": _routing["mispredicts"],
            "h2d_chunk_bytes": _routing["h2d_chunk_bytes"],
        }
        if reset:
            _routing["engines"] = {}
            _routing["events"] = {}
            _routing["predicted_s"] = 0.0
            _routing["observed_s"] = 0.0
            _routing["predictions"] = 0
            _routing["mispredicts"] = 0
            _routing["h2d_chunk_bytes"] = 0
    out["mispredict_rate"] = (
        out["mispredicts"] / out["predictions"] if out["predictions"] else 0.0
    )
    return out


# -- chunked double-buffered h2d upload (ISSUE 10 satellite) ----------------
# A persisted-layout warm start used to move each staged column to the
# device as ONE bulk transfer: nothing overlaps a 9.6 GB h2d the way the
# ingest pipeline overlaps prepare. Large arrays now go up in bounded
# chunks with exactly one transfer in flight while the previous one is
# timed to completion — later chunks (and the next column's host staging)
# overlap earlier transfers, and the per-chunk timings land in the cost
# store as the h2d observations (observe-only today, like readback: no
# predictor consults the h2d rate yet).

_H2D_CHUNK_BYTES = 64 << 20  # static per-chunk default (cold store)
_H2D_MIN_CHUNKED = 256 << 20  # arrays below this go as one piece
# tuned-chunk candidates (ISSUE 13 satellite): the power-of-two bucket
# sizes the picker compares against the cost store's observed per-chunk
# h2d rates — 16 MB .. 256 MB around the static 64 MB default
_H2D_CHUNK_CANDIDATES = tuple(1 << p for p in range(24, 29))


def _h2d_chunk_bytes() -> int:
    """Per-chunk h2d transfer size, tuned from the cost store (ISSUE 13
    satellite, PR 10 residue): among the power-of-two candidates, pick the
    bucket whose OBSERVED per-chunk h2d rate (seconds per byte, exact
    bucket only — the op-global fallback rate would make every candidate
    tie) is best; buckets without enough observations don't compete, and a
    fully cold store keeps the static 64 MB default. Chunking never
    changes the concatenated bytes, so the choice is bit-identical by
    construction. The pick is surfaced as `h2d_chunk_bytes` in
    routing_stats."""
    from ballista_tpu.ops import costmodel

    best, best_rate = _H2D_CHUNK_BYTES, None
    for cand in _H2D_CHUNK_CANDIDATES:
        r = costmodel.bucket_rate("h2d", cand)
        if r is None:
            continue
        if best_rate is None or r < best_rate:
            best, best_rate = cand, r
    with _routing_lock:
        _routing["h2d_chunk_bytes"] = best
    return best


def upload_array(arr: np.ndarray):
    """Host->device transfer of one numpy array. Arrays past
    _H2D_MIN_CHUNKED split along axis 0 into _h2d_chunk_bytes() chunks
    (the cost store's observed h2d rates pick the chunk size; 64 MB when
    cold), double-buffered (dispatch chunk j, then block on chunk j-1 and
    record its h2d cost), and concatenate on device — bit-identical to the
    single put, with a transient 2x HBM peak for this one array. Small
    arrays — and every array while the cost model is off (the chunked
    path's extra device copy and HBM peak are part of the adaptive tier,
    and its observations would be discarded anyway) — keep the plain async
    jnp.asarray dispatch."""
    from ballista_tpu.utils import tracing

    with tracing.span("runtime.upload", bytes=int(arr.nbytes)):
        return _upload_array(arr)


def _upload_array(arr: np.ndarray):
    import jax.numpy as jnp

    from ballista_tpu.ops import costmodel

    nbytes = arr.nbytes
    rows = arr.shape[0] if arr.ndim else 0
    if not costmodel.enabled() or nbytes < _H2D_MIN_CHUNKED or rows < 2:
        return jnp.asarray(arr)
    row_bytes = max(1, nbytes // rows)
    chunk_rows = max(1, _h2d_chunk_bytes() // row_bytes)
    if chunk_rows >= rows:
        return jnp.asarray(arr)
    chunks = []
    prev = prev_t0 = None
    for lo in range(0, rows, chunk_rows):
        t0 = time.perf_counter()
        c = jnp.asarray(np.ascontiguousarray(arr[lo:lo + chunk_rows]))
        if prev is not None:
            prev.block_until_ready()
            costmodel.observe("h2d", prev.nbytes,
                              time.perf_counter() - prev_t0)
        prev, prev_t0 = c, t0
        chunks.append(c)
    prev.block_until_ready()
    costmodel.observe("h2d", prev.nbytes, time.perf_counter() - prev_t0)
    record_routing_event("h2d_chunked")
    return jnp.concatenate(chunks, axis=0)
