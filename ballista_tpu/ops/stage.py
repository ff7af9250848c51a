"""Fused stage execution on the device backend.

The TPU-first restructuring from SURVEY §7: instead of per-operator batch
kernels, the pipeline under an aggregation — scan -> filter* -> projection ->
partial aggregate — compiles into ONE jitted program per batch shape:

    host: Arrow IO, dictionary-encode strings, evaluate group keys,
          rank batch-local group codes (np.unique)
    device (single jit): filter predicates -> mask; aggregate-input
          arithmetic; masked segment_sum/min/max into per-group partials

Per-batch partial states concatenate into a standard partial-aggregate table,
so the surrounding Partial/Final machinery (and the distributed shuffle above
it) is unchanged — the stage is just a faster partial phase. Batches and
group counts pad to power-of-two buckets to bound XLA recompilation.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ballista_tpu.errors import PlanError
from ballista_tpu.ops.jaxexpr import ExprCompiler
from ballista_tpu.ops.runtime import (
    ScanDictionaries,
    UnsupportedOnDevice,
    bucket_rows,
    column_to_numpy,
    make_headroom,
    narrow_column,
    pad_to,
    widen_cols,
)
from ballista_tpu.physical import expr as px
from ballista_tpu.physical.basic import (
    CoalesceBatchesExec,
    FilterExec,
    MergeExec,
    ProjectionExec,
)
from ballista_tpu.physical.scan import CsvScanExec, MemoryScanExec, ParquetScanExec
from ballista_tpu.utils import tracing
from ballista_tpu.utils.locks import make_lock

_SCAN_TYPES = (CsvScanExec, ParquetScanExec, MemoryScanExec)


def plane_keys(idx: int) -> Tuple[int, int]:
    """cols-dict keys for scan column idx's order-preserving int32 key
    planes (ops/floatbits.py). Negative ints: scan columns are keyed by
    their non-negative schema index, so both spaces share one dict through
    the narrow/stage/persist machinery unchanged (layout-cache metas
    stringify keys and re-int them cleanly). f32 columns use the hi slot
    only; f64 columns carry (hi, lo) whose lexicographic signed order is
    the f64 total order."""
    return -2 * idx - 2, -2 * idx - 3


def groups_out(sp: tracing.Span, table: Optional[pa.Table]) -> Optional[pa.Table]:
    """The table a device aggregate hands the host, counted: its rows as
    `groups` on the `runtime.to_arrow` span that assembled it and as one
    increment of `device.groups_out` a stage result (the groups of a grouped
    aggregate, the survivors of a top-k or a member select). None, a top-k
    that handed nothing over, counts nothing."""
    if table is not None:
        sp.set(groups=table.num_rows)
        tracing.incr("device.groups_out", table.num_rows)
    return table


def _int_keys(arr) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(int64 values, valid mask) of an integer key column; None for any
    other type."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    elif not isinstance(arr, pa.Array):
        arr = pa.array(arr)
    if not pa.types.is_integer(arr.type):
        return None
    valid = (np.ones(len(arr), dtype=bool) if not arr.null_count
             else arr.is_valid().to_numpy(zero_copy_only=False))
    values = arr.fill_null(0).to_numpy(zero_copy_only=False).astype(np.int64)
    return values, valid


class GroupKeyIndex:
    """A prepared partition's groups by key: each group's key values packed
    to one int64 (mixed radix over each column's range among the groups)
    and sorted once, so that a key set of m rows finds its member groups in
    m binary searches of the n groups, never n searches of the set."""

    def __init__(self, lows, spans, packed, order) -> None:
        self.lows, self.spans = lows, spans
        self.packed = packed  # sorted [n]
        self.order = order  # group id of each packed entry

    @classmethod
    def build(cls, key_values) -> Optional["GroupKeyIndex"]:
        """None where a key column is not integral, holds a NULL, or the
        ranges' product does not fit 62 bits."""
        if not key_values:
            return None
        cols = [_int_keys(kv) for kv in key_values]
        if any(c is None or not c[1].all() for c in cols) or not len(cols[0][0]):
            return None
        lows = [int(v.min()) for v, _ in cols]
        spans = [int(v.max()) - lo + 1 for (v, _), lo in zip(cols, lows)]
        total = 1
        for span in spans:
            total *= span
        if total >= 1 << 62:
            return None
        packed = np.zeros(len(cols[0][0]), dtype=np.int64)
        for (v, _), lo, span in zip(cols, lows, spans):
            packed = packed * span + (v - lo)
        order = np.argsort(packed, kind="stable")
        return cls(lows, spans, packed[order], order)

    def members(self, keyset) -> Optional[np.ndarray]:
        """Ascending ids of the groups whose key is a row of `keyset` (one
        array a key column); a NULL or out-of-range key matches nothing.
        None where a column is not integral."""
        cols = [_int_keys(k) for k in keyset]
        if len(cols) != len(self.spans) or any(c is None for c in cols):
            return None
        ok = np.ones(len(cols[0][0]), dtype=bool)
        packed = np.zeros(len(ok), dtype=np.int64)
        for (v, valid), lo, span in zip(cols, self.lows, self.spans):
            ok &= valid & (v >= lo) & (v < lo + span)
            packed = packed * span + np.where(ok, v - lo, 0)
        probe = packed[ok]
        pos = np.minimum(np.searchsorted(self.packed, probe), len(self.packed) - 1)
        hit = self.packed[pos] == probe
        return np.unique(self.order[pos[hit]])


# ceiling for the per-batch unrolled path (G linear passes); beyond it the
# stage switches to the sorted chunked-segment layout (ops/layout.py), which
# is O(N) regardless of group count
MAX_GROUPS = 1024

_INT32_MAX = 2**31 - 1

# widest one-chunk-per-group cover the fused top-k epilogue will force;
# beyond it (or past 4x row padding) the default chunking runs and the
# epilogue's in-program fold variant takes over. HARD CEILING: the layout's
# clen and jnp_expand_clen's arange are int16 (ops/layout.py:113,
# stage.py:142) — an L1 past 2^14 would wrap chunk lengths silently.
TOPK_MAX_L1 = 1 << 14


def _topk_cover_L1(codes: np.ndarray, n_groups: int) -> Optional[int]:
    """L1 giving the one-chunk-per-group cover the fused top-k epilogue
    needs: the chunk fold becomes identity, so the k gathered columns are
    bit-identical to what the full readback would emit. None when the
    longest run exceeds TOPK_MAX_L1 or the cover's zero padding would blow
    past ~4x the real rows (skewed runs) — the caller falls back to the
    default chunking and fusion disables for the partition."""
    if n_groups <= 0:
        return None
    longest = int(np.bincount(codes, minlength=n_groups).max())
    L1 = 8
    while L1 < longest:
        L1 <<= 1
    if L1 > TOPK_MAX_L1 or n_groups * L1 > max(4 * len(codes), 1 << 22):
        return None
    return L1


# the general skew handler splits at most this many dominant groups to the
# in-program segment fold; distributions where more groups blow the cover
# are broad, not skewed, and keep the default chunking
SKEW_MAX_DOMINANT = 64


def skew_split_plan(codes: np.ndarray, n_groups: int) -> Optional[Tuple[int, int]]:
    """General skew handler (ISSUE 10): the q10 monster-group fallback,
    generalized. Called when the one-chunk-per-group cover fails, it
    detects the dominant groups at run time — the few whose runs blow the
    cover bounds — and picks the cover from the TAIL run distribution
    instead: L1 covers every non-dominant run (those groups keep the
    one-chunk fast path, an identity fold), the dominant runs split across
    chunks and segment-fold in program (the existing tstep_fold machinery,
    so bit-identity is the proven contract). Returns (L1, n_dominant) or
    None when the distribution is not skewed (<= SKEW_MAX_DOMINANT
    dominants cannot satisfy the bounds) — the caller then keeps the
    default percentile chunking exactly as before."""
    if n_groups <= 1:
        return None
    lens = np.sort(np.bincount(codes, minlength=n_groups))[::-1]
    budget = max(4 * len(codes), 1 << 22)
    for n_dom in range(1, min(SKEW_MAX_DOMINANT, n_groups - 1) + 1):
        tail_max = int(lens[n_dom])
        L1 = 8
        while L1 < tail_max:
            L1 <<= 1
        if L1 > TOPK_MAX_L1:
            continue  # even the tail needs a wider cover: more dominants
        dom_chunks = int(np.sum(-(-lens[:n_dom] // L1)))
        if (n_groups - n_dom + dom_chunks) * L1 <= budget:
            return L1, n_dom
    return None


class TooManyGroups(UnsupportedOnDevice):
    """Internal signal: per-batch unrolled path declined on cardinality;
    run() retries with the sorted layout before giving up."""


# a gathered run of at most this many padded rows stays one entry whatever
# its padding: under 2^19 rows, a few MiB a column, where each cut costs a
# launch and a program shape (q12's filtered files, about 39k rows each)
_WHOLE_BUCKET_ROWS = 2**20


def _tight_runs(sizes: List[int]) -> List[Tuple[int, int]]:
    """Split consecutive batches of `sizes` rows into runs [lo, hi) whose
    padding to bucket_rows is at most an eighth of the bucket, or whose
    bucket is at most _WHOLE_BUCKET_ROWS, or that hold one batch (padded
    alone, as an entry of one batch is), or that the cut would not pad
    less. A run past that padding is cut at the longest prefix that fits
    half its bucket: a file's 131,072-row chunks just past a power of two
    become entries of 2^k chunks and a tail, not one entry padded to twice
    the file."""
    rows = sum(sizes)
    bucket = bucket_rows(rows)
    whole = [(0, len(sizes))]
    if (len(sizes) == 1 or bucket <= _WHOLE_BUCKET_ROWS
            or bucket - rows <= bucket // 8):
        return whole
    cut, head = 0, 0
    while cut < len(sizes) and head + sizes[cut] <= bucket // 2:
        head += sizes[cut]
        cut += 1
    cut = min(max(cut, 1), len(sizes) - 1)
    runs = _tight_runs(sizes[:cut]) + [
        (lo + cut, hi + cut) for lo, hi in _tight_runs(sizes[cut:])
    ]
    if sum(bucket_rows(sum(sizes[lo:hi])) for lo, hi in runs) >= bucket:
        return whole
    return runs


def _joined(batches: List[tuple]) -> tuple:
    """One entry of consecutive lowered batches, each (rows, lowered
    columns, group codes, key values, group count): the columns joined, and
    each batch's group codes renumbered into the entry's own groups (the
    groups that occur, numbered batch by batch in the order of their
    batch-local codes). A single batch stays as it is."""
    if len(batches) == 1:
        return batches[0]
    groups: Dict[tuple, int] = {}
    codes: List[np.ndarray] = []
    key_pieces: List[List[pa.Array]] = []
    for _, _, batch_codes, key_values, n_groups in batches:
        present = np.flatnonzero(np.bincount(batch_codes, minlength=n_groups))
        keys = (zip(*(kv.take(pa.array(present)).to_pylist() for kv in key_values))
                if key_values else [()] * len(present))
        to_entry = np.zeros(n_groups, dtype=np.int32)
        new: List[int] = []
        for i, key in zip(present, keys):
            g = groups.get(key)
            if g is None:
                g = groups[key] = len(groups)
                new.append(int(i))
            to_entry[i] = g
        if len(groups) > MAX_GROUPS:
            raise TooManyGroups(
                f"{len(groups)} groups in a gathered batch exceed the unrolled path")
        if new:
            key_pieces.append([kv.take(pa.array(new)) for kv in key_values])
        codes.append(to_entry[batch_codes])
    cols = {idx: np.concatenate([b[1][idx] for b in batches]) for idx in batches[0][1]}
    key_values = [pa.concat_arrays([piece[c] for piece in key_pieces])
                  for c in range(len(key_pieces[0]))]
    return (sum(b[0] for b in batches), cols, np.concatenate(codes),
            key_values, len(groups))


class _GatheredBatches:
    """Consecutive lowered batches of one scan partition, held until they
    become entries of the unrolled engine. A partition may come in many
    batches (a cached parquet read hands a file over in 131,072-row chunks,
    which a mapped scan extends one by one; a memory table in its record
    batches), and each entry costs run() a launch, a readback and a partial
    table. Each batch is ranked and lowered alone, so the host works on
    chunk-sized arrays; entries() joins them.

    An entry takes no batch of another scan partition: each row bucket is
    a program of its own to compile, and files alike then give entries
    alike. A stage over eight 7.5M-row files keeps one bucket, 2^23;
    gathered across files up to a batch size of 2^24 rows, its entries
    would take three (2^24, 2^23, 2^21)."""

    def __init__(self, part: int) -> None:
        self.part = part
        self.rows = 0
        self.batches: List[tuple] = []
        # integer SUM/AVG input -> the most rows its int32 sum allows
        self.sum_rows: Dict[str, int] = {}

    def _bound(self, sum_rows: Dict[str, int]) -> Dict[str, int]:
        out = dict(self.sum_rows)
        for c, r in sum_rows.items():
            out[c] = min(r, out.get(c, r))
        return out

    def fits(self, part: int, n: int, sum_rows: Dict[str, int], limit: int) -> bool:
        """Whether a batch of `n` rows of scan partition `part`, whose int32
        sums allow `sum_rows`, may join without passing `limit` rows or the
        int32 sum bound of the entry."""
        rows = self.rows + n
        return (part == self.part and rows <= limit
                and all(rows <= r for r in self._bound(sum_rows).values()))

    def add(self, n: int, npcols: Dict[int, np.ndarray], codes: np.ndarray,
            key_values: List[pa.Array], n_groups: int, sum_rows: Dict[str, int]) -> None:
        self.batches.append((n, npcols, codes, key_values, n_groups))
        self.rows += n
        self.sum_rows = self._bound(sum_rows)

    def entries(self) -> Iterator[tuple]:
        """(rows, lowered columns, group codes, key values, group count) of
        each entry: the batches in runs of bounded padding (_tight_runs)."""
        for lo, hi in _tight_runs([b[0] for b in self.batches]):
            yield _joined(self.batches[lo:hi])


# --- int32 <-> f32-pair packing -------------------------------------------
# Bitcasting int32 to f32 is NOT safe on TPU (small ints are denormal floats
# and get flushed to zero), so int rows travel as two exactly-representable
# halves: hi = v >> 16 (arithmetic), lo = v & 0xFFFF. Encode lives in
# _stack_rows; BOTH decoders below must mirror it.


def decode_packed_rows(stacked: np.ndarray, int_rows) -> List[np.ndarray]:
    """Host-side decode of a packed [R_packed, ...] f32 result: int rows
    come back as int64, float rows as the f32 slices."""
    rows: List[np.ndarray] = []
    i = 0
    for is_int in int_rows:
        if is_int:
            hi = stacked[i].astype(np.int64)
            lo = stacked[i + 1].astype(np.int64)
            rows.append(hi * 65536 + lo)
            i += 2
        else:
            rows.append(stacked[i])
            i += 1
    return rows


def packed_positions(int_rows) -> List[int]:
    """Position of each logical row inside the packed stack."""
    pos, p = [], 0
    for is_int in int_rows:
        pos.append(p)
        p += 2 if is_int else 1
    return pos


def jnp_unpack_i32(hi, lo):
    """In-program decode (exact int32)."""
    import jax.numpy as jnp

    return hi.astype(jnp.int32) * 65536 + lo.astype(jnp.int32)


def jnp_expand_clen(clen, L1: int):
    """In-program [V, L1] valid-slot mask from per-chunk lengths — 16× less
    HBM than shipping the bool tiles (which cost q5 SF=100 its budget)."""
    import jax.numpy as jnp

    return jnp.arange(L1, dtype=jnp.int16)[None, :] < clen[:, None]


def dense_rank(encoded: List[Tuple[np.ndarray, int]]):
    """Combine per-column dictionary codes into dense row ranks.

    encoded: (int64 code array, alphabet size) per key column, all arrays the
    same length. Strides are combined with an overflow guard (repack through
    np.unique before a multiply could overflow int64). Returns
    (rank per row, first row index of each distinct, distinct count)."""
    combined = None
    card = 1
    for codes_i, size in encoded:
        size = max(1, size)
        if combined is None:
            combined, card = codes_i, size
            continue
        if card > (1 << 62) // size:
            _, combined = np.unique(combined, return_inverse=True)
            combined = combined.astype(np.int64)
            card = int(combined.max()) + 1 if len(combined) else 1
        combined = combined * size + codes_i
        card *= size
    uniq, first_idx, inv = np.unique(
        combined, return_index=True, return_inverse=True
    )
    return inv, first_idx, len(uniq)


def substitute_columns(e: px.PhysicalExpr, mapping: List[px.PhysicalExpr]) -> px.PhysicalExpr:
    """Inline projection outputs: ColumnExpr(i) -> mapping[i]."""
    if isinstance(e, px.ColumnExpr):
        return mapping[e.index]
    if isinstance(e, px.LiteralExpr):
        return e
    if isinstance(e, px.BinaryPhysicalExpr):
        return px.BinaryPhysicalExpr(
            substitute_columns(e.left, mapping), e.op, substitute_columns(e.right, mapping)
        )
    if isinstance(e, px.NotExpr):
        return px.NotExpr(substitute_columns(e.expr, mapping))
    if isinstance(e, px.NegativeExpr):
        return px.NegativeExpr(substitute_columns(e.expr, mapping))
    if isinstance(e, px.IsNullExpr):
        return px.IsNullExpr(substitute_columns(e.expr, mapping), e.negated)
    if isinstance(e, px.CastExpr):
        return px.CastExpr(substitute_columns(e.expr, mapping), e.dtype, e.safe)
    if isinstance(e, px.InListExpr):
        return px.InListExpr(
            substitute_columns(e.expr, mapping),
            e.values,
            e.negated,
            None
            if e.value_exprs is None
            else [substitute_columns(v, mapping) for v in e.value_exprs],
        )
    if isinstance(e, px.BetweenExpr):
        return px.BetweenExpr(
            substitute_columns(e.expr, mapping),
            substitute_columns(e.low, mapping),
            substitute_columns(e.high, mapping),
            e.negated,
        )
    if isinstance(e, px.CaseExpr):
        return px.CaseExpr(
            None if e.base is None else substitute_columns(e.base, mapping),
            [
                (substitute_columns(w, mapping), substitute_columns(t, mapping))
                for w, t in e.when_then
            ],
            None if e.else_expr is None else substitute_columns(e.else_expr, mapping),
            e.dtype,
        )
    if isinstance(e, px.ScalarFunctionExpr):
        return px.ScalarFunctionExpr(
            e.fn, [substitute_columns(a, mapping) for a in e.args], e.dtype
        )
    raise UnsupportedOnDevice(f"cannot inline {type(e).__name__}")


def state_column(a, raw: np.ndarray, target: pa.DataType,
                 empty_mask: Optional[np.ndarray]) -> pa.Array:
    """Cast one decoded aggregate-state row to its partial-schema field.
    min/max rows null out empty groups (sentinel fills) via empty_mask;
    date32 states ride as exact int32 day counts (pyarrow has no
    double->date32 cast). Shared by every device assembly path."""
    if a.fn in ("min", "max"):
        if pa.types.is_date32(target):
            arr = pa.array(raw.astype(np.int32), mask=empty_mask)
        else:
            arr = pa.array(raw.astype(np.float64), mask=empty_mask)
    else:
        arr = pa.array(raw.astype(np.float64))
    if arr.type != target:
        arr = pc.cast(arr, target)
    return arr


def _pack_staged(staged: Dict, arrays: List[np.ndarray]) -> Dict[str, dict]:
    """Append a staged {idx: (tiles, lut, choice)} dict's arrays to the
    persistence list, returning the JSON column manifest. Shared by the
    sorted and batches save paths."""
    cols_meta: Dict[str, dict] = {}
    for idx, (tiles, lut, choice) in staged.items():
        spec = {"tiles": len(arrays), "choice": choice, "lut": None}
        arrays.append(tiles)
        if lut is not None:
            spec["lut"] = len(arrays)
            arrays.append(lut)
        cols_meta[str(idx)] = spec
    return cols_meta


def _unpack_staged(cols_meta: Dict[str, dict], arrays: List[np.ndarray],
                   narrow_choice: Dict) -> Optional[Tuple[Dict, int]]:
    """Inverse of _pack_staged: (staged dict, byte total), or None when a
    persisted narrow choice conflicts with one the jitted step already
    compiled against."""
    staged: Dict[int, tuple] = {}
    total = 0
    for k, spec in cols_meta.items():
        idx = int(k)
        tiles = arrays[spec["tiles"]]
        lut = arrays[spec["lut"]] if spec["lut"] is not None else None
        cur = narrow_choice.get(idx)
        if cur is not None and cur != spec["choice"]:
            return None
        staged[idx] = (tiles, lut, spec["choice"])
        total += tiles.nbytes + (0 if lut is None else lut.nbytes)
    return staged, total


def _upload_staged(staged: Dict, choices: Dict) -> Dict:
    """Transfer staged (array, lut, choice) columns, recording the narrow
    choice per key and freeing each host tile right after its device copy
    exists — peak host memory holds one column in flight, not the whole
    stage. The (dev, lut) tuple is the single LUT encoding widen_cols
    understands; both device paths must build it here.

    Large tiles go through runtime.upload_array (ISSUE 10 satellite):
    bounded chunks, double-buffered, so a persisted-layout warm start's
    bulk transfer overlaps the next column's host staging the way the
    ingest pipeline overlaps prepare — and the per-chunk timings land in
    the cost store as h2d observations."""
    import jax.numpy as jnp

    from ballista_tpu.ops.runtime import upload_array

    cols: Dict = {}
    for idx in list(staged):
        arr, lut, choice = staged.pop(idx)
        choices[idx] = choice
        dev = upload_array(arr)
        cols[idx] = dev if lut is None else (dev, jnp.asarray(lut))
    return cols


class FusedAggregateStage:
    """Compiled device pipeline for one HashAggregateExec (partial phase)."""

    def __init__(self, agg, float_bits: bool = True) -> None:
        from ballista_tpu.physical.aggregate import AggregateFunc

        # --- walk the operator chain down to the row source --------------
        # Filters/projections fuse onto the device; whatever sits below them
        # (a scan, or e.g. a host hash join) becomes the row source — so a
        # join-under-aggregate still gets device aggregation.
        node = agg.input
        stack: List[Tuple[str, object]] = []
        # scan_stride: when set to N, this stage's logical partition p reads
        # scan partitions p, p+N, p+2N, ... — used when the partition count
        # the framework drives (aggregate input partitioning) differs from
        # the scan's own count. Crossing a MergeExec (row-transparent; the
        # coalesced SINGLE-mode plan) means ONE driven partition covers
        # every scan partition: stride 1.
        self.scan_stride: Optional[int] = None
        while isinstance(node, (FilterExec, ProjectionExec, CoalesceBatchesExec, MergeExec)):
            if isinstance(node, FilterExec):
                stack.append(("filter", node.predicate))
                node = node.input
            elif isinstance(node, ProjectionExec):
                stack.append(("project", node.exprs))
                node = node.input
            else:
                if isinstance(node, MergeExec):
                    self.scan_stride = 1
                node = node.input
        if self.scan_stride is None:
            # a rewritten aggregate (ops/mappedscan.py) whose driven
            # partition count differs from its scan's: stripe the scan
            hint = getattr(agg, "_scan_stride_hint", None)
            if hint is not None:
                self.scan_stride = int(hint)
        self.scan = node
        # device columns stay resident only for file-backed scans (stable
        # data identity); other sources re-execute per query.
        # ballista_cacheable: composed row sources (ops/mappedscan.py) whose
        # data identity is still file-backed opt in via the class attribute
        self.cacheable = isinstance(node, _SCAN_TYPES) or getattr(
            node, "ballista_cacheable", False
        )
        scan_schema = node.schema()

        # --- re-express every expression against the scan schema --------
        mapping: List[px.PhysicalExpr] = [
            px.ColumnExpr(f.name, i) for i, f in enumerate(scan_schema)
        ]
        filters: List[px.PhysicalExpr] = []
        for kind, payload in reversed(stack):
            if kind == "project":
                mapping = [substitute_columns(e, mapping) for e, _ in payload]
            else:
                filters.append(substitute_columns(payload, mapping))
        # input-schema -> scan-schema expr map, exposed for composers
        # (FactAggregateStage re-expresses extra columns through it)
        self.input_to_scan = mapping

        self.group_exprs = [
            (substitute_columns(e, mapping), name) for e, name in agg.group_exprs
        ]
        self.aggs: List[AggregateFunc] = []
        self.agg_inputs: List[px.PhysicalExpr] = []
        for a in agg.aggr_funcs:
            if a.fn not in ("sum", "min", "max", "avg", "count"):
                raise UnsupportedOnDevice(f"aggregate {a.fn}")
            self.aggs.append(a)
            self.agg_inputs.append(substitute_columns(a.expr, mapping))

        # --- compile device code ----------------------------------------
        self.dicts = ScanDictionaries()
        self.compiler = ExprCompiler(scan_schema, self.dicts)
        self.filter_fns = [self.compiler.compile(f) for f in filters]
        for f in self.filter_fns:
            if f.kind != "bool":
                raise UnsupportedOnDevice("non-boolean filter")
        # WHERE collapse: predicates whose SQL value is NULL exclude the row
        # (three-valued logic over -1 string codes, jaxexpr.predicate_fn)
        from ballista_tpu.ops.jaxexpr import predicate_fn

        self.filter_masks = [predicate_fn(f) for f in self.filter_fns]
        self.value_fns = []
        # integer-typed plain-column inputs accumulate in int32 on device
        # (exact, vs the f32 rounding ADVICE r1 flagged); the value range is
        # bound-checked at prepare time and declines when int32 could
        # overflow a whole-batch masked sum
        self.int_exact: List[bool] = []
        # float MIN/MAX over a plain column routes through the
        # order-preserving bijection (ops/floatbits.py): the column's bits
        # travel as int32 key planes, integer min/max is exact on device,
        # and the readback inverts — bit-exact against the stored f64/f32
        # value, so q2's equality-joined MIN needs no decline. Entries:
        # None (f32 arithmetic path) | "f32" (one plane) | "f64" (hi/lo).
        # The mesh path opts out (float_bits=False): its collectives fold
        # rows independently, which cannot express the hi/lo lexicographic
        # pair, and it keeps its documented f32 min/max semantics.
        self.float_bits: List[Optional[str]] = []
        # scan column index -> "f32" | "f64" (plane columns to materialize)
        self._bit_planes: Dict[int, str] = {}
        exact_required = bool(getattr(agg, "exact_floats", False))
        for a, ie in zip(self.aggs, self.agg_inputs):
            if a.fn == "count":
                # COUNT counts NON-NULL inputs; the device mask-count would
                # count null strings (-1 codes). Wildcard/literal inputs
                # (COUNT(*)) and null-free numeric columns are safe.
                if not isinstance(ie, px.LiteralExpr):
                    probe = self.compiler.compile(ie)
                    if probe.kind == "code":
                        raise UnsupportedOnDevice("COUNT over a string column")
                self.value_fns.append(None)  # mask count only
                self.int_exact.append(False)
                self.float_bits.append(None)
                continue
            if (
                float_bits
                and a.fn in ("min", "max")
                and isinstance(ie, px.ColumnExpr)
                and pa.types.is_floating(scan_schema.field(ie.index).type)
            ):
                # bijected path: do NOT compile the input (that would upload
                # the rounded f32 copy even when nothing else reads it); the
                # planes are materialized directly from the Arrow column
                width = (
                    "f32"
                    if pa.types.is_float32(scan_schema.field(ie.index).type)
                    else "f64"
                )
                prior = self._bit_planes.setdefault(ie.index, width)
                if prior != width:
                    raise UnsupportedOnDevice("conflicting float plane widths")
                self.value_fns.append(None)
                self.int_exact.append(False)
                self.float_bits.append(width)
                continue
            cv = self.compiler.compile(ie)
            if cv.kind == "code":
                raise UnsupportedOnDevice("string aggregate input")
            if (
                exact_required
                and a.fn in ("min", "max")
                and pa.types.is_floating(a.input_type)
            ):
                # equality-consumed float MIN/MAX over a COMPUTED expression:
                # only plain columns carry exact bits; f32 arithmetic would
                # round the result so it matches nothing — host path
                raise UnsupportedOnDevice(
                    "exact float min/max over a computed expression"
                )
            self.value_fns.append(cv)
            # dates lower as int32 day counts: exact int min/max (the
            # f32 route crashed assembling double -> date32, and values
            # past 2^24 days would round)
            self.int_exact.append(
                isinstance(ie, px.ColumnExpr)
                and (
                    pa.types.is_integer(scan_schema.field(ie.index).type)
                    or pa.types.is_date32(scan_schema.field(ie.index).type)
                )
            )
            self.float_bits.append(None)
        self.scan_schema = scan_schema
        self.partial_schema = agg.schema() if agg.mode.value == "partial" else self._partial_schema(agg)
        self._int_rows, self._folds, self._state_specs = self._plan_outputs()
        # planner-annotated Sort+Limit epilogue (physical/planner.py): when
        # eligible, the device step finishes with lax.top_k over the group
        # scores and reads back `limit` rows instead of every group. Only
        # SINGLE-mode aggregates carry the annotation, so one partial IS the
        # final per-group state and on-device selection equals host
        # selection (boundary ties fall back per query, see _topk_tail).
        self.topk: Optional[dict] = self._topk_spec(agg)
        self._topk_step = None  # built on first fused-eligible partition
        self._topk_fold_step = None  # skewed-cover variant (in-program fold)
        self._step = self._build_step()
        self._sorted_step = None  # built on first high-cardinality partition
        self._keyset_take = None  # built on a sorted partition's first key set
        self._device_cache: Dict[int, dict] = {}
        # narrow-residency choice of the first batch, keyed by col index
        # (or "derived:<name>" for derived tiles); kept stable across
        # batches/partitions so the jitted step compiles once
        # (mutated only under _prepare_lock)
        self._narrow_choice: Dict[object, str] = {}
        # executor task threads can run different partitions of one cached
        # stage concurrently; prepare mutates shared state (the growing
        # ColumnDictionary, compiled-step slots), so it is serialized
        self._prepare_lock = make_lock("ops.stage._prepare_lock")
        # name -> fn(row-space npcols dict) -> np row array; materialized as
        # [V, L1] tiles alongside the scan columns on the sorted path
        # (FactAggregateStage derives static mapped columns this way)
        self.derive_columns: Dict[str, Callable] = {}
        # stage cache key (plan display + scan files + mtimes + config
        # flags), set by kernels.hash_aggregate for file-backed stages only;
        # keys the persisted layout cache (ops/layout_cache.py)
        self.persist_key: Optional[str] = None
        # chunk-set delta base (ISSUE 19): plan display + config flags with
        # the file list AND mtimes excluded, set beside persist_key by
        # kernels.resolve_stage. Each prepared chunk persists under
        # chunk_key_base + its own (path, mtime, size, chunk_index), so an
        # appended file re-prepares only its own chunks. None = whole-set
        # persistence only.
        self.chunk_key_base: Optional[str] = None
        # STABLE half of the stage cache key (no mtimes — compiled programs
        # are data-independent), set by kernels.hash_aggregate for every
        # dispatched stage; keys the persistent AOT program cache
        # (ops/aotcache.py). None = the AOT tier stays out of the way.
        self.aot_key: Optional[str] = None

    @staticmethod
    def _partial_schema(agg) -> pa.Schema:
        group_fields = []
        in_schema = agg.input.schema()
        for e, name in agg.group_exprs:
            group_fields.append(pa.field(name, e.data_type(in_schema)))
        state_fields = [f for a in agg.aggr_funcs for f in a.state_fields()]
        return pa.schema(group_fields + state_fields)

    # ------------------------------------------------------------------
    def _plan_outputs(self):
        """Stacked-output plan shared by both device steps: row 0 is counts,
        then one row per aggregate state column — except f64-bijected
        min/max states, which occupy TWO int32 rows (hi/lo key planes whose
        lexicographic order is the f64 total order). Returns (is_int flags,
        fold op names) per stacked row, plus one spec per partial-state
        FIELD: (first logical row, kind, fold) with kind in
        {"int", "num", "f32bits", "f64bits"} — the single source of truth
        for row -> state-column mapping (postprocess_state_rows,
        _fold_state_rows, the top-k epilogues, factagg's score row)."""
        int_rows = [True]  # counts
        folds = ["sum"]
        specs: List[Tuple[int, str, str]] = []
        for a, ix, fb in zip(self.aggs, self.int_exact, self.float_bits):
            row = len(int_rows)
            if a.fn == "count":
                int_rows.append(True)
                folds.append("sum")
                specs.append((row, "int", "sum"))
            elif a.fn in ("sum", "avg"):
                int_rows.append(ix)
                folds.append("sum")
                specs.append((row, "int" if ix else "num", "sum"))
                if a.fn == "avg":
                    int_rows.append(True)
                    folds.append("sum")
                    specs.append((row + 1, "int", "sum"))
            elif fb == "f64":
                int_rows.extend([True, True])
                folds.extend([a.fn, a.fn])  # pair; never folded per-row
                specs.append((row, "f64bits", a.fn))
            elif fb == "f32":
                int_rows.append(True)
                folds.append(a.fn)
                specs.append((row, "f32bits", a.fn))
            else:  # min / max, arithmetic path
                int_rows.append(ix)
                folds.append(a.fn)
                specs.append((row, "int" if ix else "num", a.fn))
        return int_rows, folds, specs

    # keys wider than this decline the fusion ("unsupported multi-key
    # widths"): each f64-bijected key spends TWO of the lexicographic
    # int32 lanes the device sort ranks over
    TOPK_MAX_KEY_LANES = 6

    def _topk_spec(self, agg) -> Optional[dict]:
        """Validate the planner's `_topk_pushdown` annotation against this
        stage's output plan. Returns the enriched spec or None (ineligible:
        the normal full-readback path runs unchanged).

        Every sort key lowers to int32 lanes whose signed order equals the
        key's order — exact int states as-is, f32 scores through the
        floatbits bijection, f64-bijected min/max as their (hi, lo) plane
        pair — so the device ranks one lexicographic int tuple. The group
        index joins as the final lane: ties then resolve to the lowest
        group exactly like the host's stable sort over the group-ordered
        aggregate output, which makes the on-device selection identical to
        the host Sort+Limit whenever the annotation covers every sort key."""
        tk = getattr(agg, "_topk_pushdown", None)
        if tk is None:
            return None
        mode = getattr(agg, "mode", None)
        if mode is not None and mode.value != "single":
            return None  # a per-partition partial top-k ranks partial sums
        if not (1 <= tk["k"] <= (1 << 16)):
            return None
        key_dicts = tk.get("keys") or [
            {"agg_index": tk["agg_index"], "descending": tk["descending"]}
        ]
        keyspecs: List[Tuple[int, str, bool]] = []
        for kd in key_dicts:
            j = kd.get("agg_index", -1)
            if not (0 <= j < len(self.aggs)):
                return None
            if self.aggs[j].fn not in ("sum", "count", "min", "max"):
                # avg finalizes to a RATIO of its two state rows; ranking
                # the sum row would order by the wrong quantity
                return None
            field_idx = sum(len(a.state_fields()) for a in self.aggs[:j])
            row, kind, _fold = self._state_specs[field_idx]
            keyspecs.append((row, kind, bool(kd["descending"])))
        n_lanes = sum(2 if kind == "f64bits" else 1 for _r, kind, _d in keyspecs)
        if not keyspecs or n_lanes > self.TOPK_MAX_KEY_LANES:
            return None
        covered = bool(tk.get("covered", not tk.get("strict", False)))
        return {
            "k": int(tk["k"]),
            "keys": keyspecs,
            "covered": covered,
            "n_lanes": n_lanes,
        }

    def _stack_rows(self, rows):
        """Pack mixed int32/f32 result rows into ONE f32 array -> ONE
        device->host transfer (one fixed d2h latency, not one per row
        kind). Bitcasting int32 to f32 is NOT safe on TPU — small ints are
        denormal floats and get flushed to zero — so each int32 row is split
        into two exactly-f32-representable halves (arithmetic-shift hi,
        unsigned lo); _decode_stacked recombines."""
        import jax.numpy as jnp

        out = []
        for r in rows:
            if r.dtype == jnp.int32:
                out.append((r >> 16).astype(jnp.float32))
                out.append((r & 0xFFFF).astype(jnp.float32))
            else:
                out.append(r)
        return jnp.stack(out)

    def _build_step(self):
        from ballista_tpu.ops import aotcache

        # jit with an AOT disk tier underneath (ops/aotcache.py): a cold
        # process reloads the exported program instead of retracing. A
        # stage without an aot_key (built outside the kernel dispatcher)
        # runs the plain jit path inside the wrapper.
        return aotcache.wrap_step(
            self, "unrolled", self._unrolled_core(), static_argnums=(0,)
        )

    def _unrolled_core(self):
        """Unjitted per-batch unrolled-reduction program; SpmdAggregateExec
        wraps it in shard_map + psum for the mesh path."""
        import jax.numpy as jnp

        filter_masks = self.filter_masks

        # XLA lowers segment_* to scatter, which serializes on TPU (measured
        # 460ms vs ~5ms for 6M rows). Group counts are capped at MAX_GROUPS
        # by run(), so every aggregation is an unrolled per-group masked
        # reduction: pure HBM-bandwidth work on the VPU, G linear passes,
        # each a tree reduction (pairwise-summation accuracy). Integer sums
        # accumulate in int32 (exact; range-checked at prepare time).

        def seg_sum(v, safe_codes, num_segments, zero):
            return jnp.stack(
                [
                    jnp.sum(jnp.where(safe_codes == g, v, zero))
                    for g in range(num_segments)
                ]
            )

        def seg_count(safe_codes, num_segments):
            return jnp.stack(
                [
                    jnp.sum(jnp.where(safe_codes == g, 1, 0), dtype=jnp.int32)
                    for g in range(num_segments)
                ]
            )

        def seg_extreme(v, safe_codes, num_segments, fill, red):
            return jnp.stack(
                [
                    red(jnp.where(safe_codes == g, v, fill))
                    for g in range(num_segments)
                ]
            )

        def seg_extreme_pair(hi, lo, safe_codes, num_segments, fill, red):
            # lexicographic (hi, lo) extreme per group: lo competes only
            # among rows whose hi equals the group's hi extreme
            his, los = [], []
            for g in range(num_segments):
                in_g = safe_codes == g
                h = red(jnp.where(in_g, hi, fill))
                l = red(jnp.where(jnp.logical_and(in_g, hi == h), lo, fill))
                his.append(h)
                los.append(l)
            return jnp.stack(his), jnp.stack(los)

        def step(num_segments, cols, aux, codes, row_valid):
            cols = widen_cols(cols)  # narrow residency -> canonical dtypes
            codes = codes.astype(jnp.int32)
            mask = row_valid
            for fm in filter_masks:
                mask = jnp.logical_and(mask, fm(cols, aux))
            safe_codes = jnp.where(mask, codes, num_segments - 1)
            return self._emit_rows(
                cols,
                aux,
                mask,
                counts=seg_count(safe_codes, num_segments),
                reduce_sum=lambda v, zero: seg_sum(
                    v, safe_codes, num_segments, zero
                ),
                reduce_extreme=lambda v, fill, red: seg_extreme(
                    v, safe_codes, num_segments, fill, red
                ),
                reduce_extreme_pair=lambda hi, lo, fill, red: seg_extreme_pair(
                    hi, lo, safe_codes, num_segments, fill, red
                ),
            )

        return step

    def _build_sorted_step(self):
        from ballista_tpu.ops import aotcache

        return aotcache.wrap_step(
            self, "sorted", self._sorted_core(), static_argnums=(0,)
        )

    def _sorted_core(self):
        """Unjitted device program for the chunked-segment layout
        (ops/layout.py): elementwise exprs over [V, L1] tiles, axis-1
        reductions to per-chunk partials. O(N) for any group count. The
        valid-slot mask expands in-program from per-chunk lengths (L1 is
        the static first argument). FactAggregateStage composes this with
        a membership/top-k epilogue inside one jit."""
        import jax.numpy as jnp

        filter_masks = self.filter_masks

        def pair_axis1(hi, lo, fill, red):
            # lexicographic (hi, lo) extreme per chunk: lo competes only
            # among slots whose hi equals the chunk's hi extreme (masked
            # slots carry fill in both planes, so an all-masked chunk
            # yields the (fill, fill) sentinel pair)
            h = red(hi, axis=1)
            l = red(jnp.where(hi == h[:, None], lo, fill), axis=1)
            return h, l

        def sstep(L1, cols, aux, clen):
            cols = widen_cols(cols)  # narrow residency -> canonical dtypes
            mask = jnp_expand_clen(clen, L1)
            for fm in filter_masks:
                mask = jnp.logical_and(mask, fm(cols, aux))
            return self._emit_rows(
                cols,
                aux,
                mask,
                counts=jnp.sum(mask, axis=1, dtype=jnp.int32),
                reduce_sum=lambda v, zero: jnp.sum(v, axis=1),
                reduce_extreme=lambda v, fill, red: red(v, axis=1),
                reduce_extreme_pair=pair_axis1,
            )

        return sstep

    def _emit_rows(self, cols, aux, mask, counts, reduce_sum, reduce_extreme,
                   reduce_extreme_pair=None):
        """Shared per-aggregate emission for both device cores. The row
        order/dtype contract here must stay in sync with _plan_outputs /
        _stack_rows / decode_packed_rows (and FactAggregateStage._score_row
        builds on it). Integer aggregates stay int32 (exact, range-checked
        at prepare time); masked-out slots use 0 for sums and +/-extreme
        fills for min/max. Float-bijected min/max reduces the int32 key
        planes (pure integer select + compare — no float arithmetic exists
        in that path, so the readback inverts to the bit-exact stored
        value). With NaN declined at prepare, real keys never reach the
        int32 extremes, so the +/-INT32_MAX fills stay out-of-band."""
        import jax.numpy as jnp

        maskf = mask.astype(jnp.float32)
        rows = [counts]
        for a, ie, vf, ix, fb in zip(
            self.aggs, self.agg_inputs, self.value_fns, self.int_exact,
            self.float_bits,
        ):
            if a.fn == "count":
                rows.append(counts)
                continue
            if fb is not None:
                largest = a.fn == "max"
                fill = -_INT32_MAX - 1 if largest else _INT32_MAX
                red = jnp.max if largest else jnp.min
                hk, lk = plane_keys(ie.index)
                hi = jnp.where(mask, jnp.broadcast_to(cols[hk], mask.shape), fill)
                if fb == "f32":
                    rows.append(reduce_extreme(hi, fill, red))
                else:
                    lo = jnp.where(
                        mask, jnp.broadcast_to(cols[lk], mask.shape), fill
                    )
                    h, l = reduce_extreme_pair(hi, lo, fill, red)
                    rows.extend([h, l])
                continue
            v = vf.fn(cols, aux)
            v = jnp.broadcast_to(v, mask.shape)
            if a.fn in ("sum", "avg"):
                if ix:
                    rows.append(reduce_sum(jnp.where(mask, v.astype(jnp.int32), 0), 0))
                else:
                    rows.append(reduce_sum(v.astype(jnp.float32) * maskf, 0.0))
                if a.fn == "avg":
                    rows.append(counts)
            elif a.fn in ("min", "max"):
                largest = a.fn == "max"
                if ix:
                    fill = -_INT32_MAX - 1 if largest else _INT32_MAX
                    v2 = jnp.where(mask, v.astype(jnp.int32), fill)
                else:
                    fill = -jnp.inf if largest else jnp.inf
                    v2 = jnp.where(mask, v.astype(jnp.float32), fill)
                rows.append(
                    reduce_extreme(v2, fill, jnp.max if largest else jnp.min)
                )
        return self._stack_rows(rows)

    # ------------------------------------------------------------------
    def _group_codes(self, batch: pa.RecordBatch) -> Tuple[np.ndarray, List[pa.Array], int]:
        """Host side: evaluate group keys, rank to dense batch-local codes."""
        n = batch.num_rows
        if not self.group_exprs:
            return np.zeros(n, dtype=np.int32), [], 1
        key_arrays = []
        for e, _name in self.group_exprs:
            arr = e.evaluate(batch)
            if isinstance(arr, pa.ChunkedArray):
                arr = arr.combine_chunks()
            key_arrays.append(arr)
        encoded = []
        for arr in key_arrays:
            if isinstance(arr, pa.DictionaryArray):
                d = arr
            else:
                d = pc.dictionary_encode(arr)
            if d.indices.null_count:
                raise UnsupportedOnDevice("null group key")
            codes_i = d.indices.to_numpy(zero_copy_only=False).astype(np.int64)
            encoded.append((codes_i, d.dictionary))

        card = 1
        for _c, dv in encoded:
            card *= max(1, len(dv))

        if card <= 1024:
            # dense fast path: combined dictionary code IS the group id — no
            # np.unique pass; empty groups are dropped later (counts == 0)
            combined = np.zeros(n, dtype=np.int64)
            for codes_i, dv in encoded:
                combined = combined * max(1, len(dv)) + codes_i
            # decompose 0..card-1 into per-column dictionary values
            uniq_rows = []
            gids = np.arange(card, dtype=np.int64)
            rem = gids
            parts = []
            for codes_i, dv in reversed(encoded):
                size = max(1, len(dv))
                parts.append(rem % size)
                rem = rem // size
            for (codes_i, dv), pcodes in zip(encoded, reversed(parts)):
                uniq_rows.append(dv.take(pa.array(np.minimum(pcodes, max(0, len(dv) - 1)))))
            return combined.astype(np.int32), uniq_rows, card

        inv, first_idx, n_groups = dense_rank(
            [(codes_i, len(dv)) for codes_i, dv in encoded]
        )
        # key values for each distinct group = the first row bearing it
        take_idx = pa.array(first_idx.astype(np.int64))
        uniq_rows = [
            (arr.dictionary.take(arr.indices.take(take_idx))
             if isinstance(arr, pa.DictionaryArray) else arr.take(take_idx))
            for arr in key_arrays
        ]
        return inv.astype(np.int32), uniq_rows, n_groups

    # ------------------------------------------------------------------
    def _scan_batches(self, partition: int, ctx):
        """Read the scan partition for device consumption. Parquet fast path:
        eager read_table with dictionary columns (dictionary pages map
        straight to codes — ~10x faster than the streaming dictionary read).
        With scan_stride=N, driven partition p covers scan partitions
        p, p+N, p+2N, ... (N=1: SINGLE mode over MergeExec reads them all)."""
        for _, batch in self._scan_part_batches(partition, ctx):
            yield batch

    def _scan_part_batches(self, partition: int, ctx):
        """_scan_batches' batches, each with the scan partition it is of."""
        if self.scan_stride is not None:
            total = self.scan.output_partitioning().partition_count()
            parts = range(partition, total, self.scan_stride)
        else:
            parts = [partition]
        if isinstance(self.scan, ParquetScanExec):
            from ballista_tpu.ops.runtime import ordered_map

            def read_one(p: int) -> pa.Table:
                return self._read_scan_file(self.scan.source.files[p], ctx)

            # multi-file (scan_stride) reads are independent: decode up to
            # `workers` files concurrently, yielding tables in file order so
            # the batch stream is identical to the serial read
            tables = ordered_map(
                read_one, parts,
                ctx.config.tpu_ingest_workers(), ctx.config.tpu_ingest_depth(),
            )
            for i, table in enumerate(tables):
                for batch in table.to_batches(max_chunksize=ctx.batch_size):
                    yield parts[i], batch
            return
        for p in parts:
            for batch in self.scan.execute(p, ctx):
                yield p, batch

    def _read_scan_file(self, path: str, ctx) -> pa.Table:
        """Eager parquet read of one scan file (dictionary pages map straight
        to codes). Factored out of _scan_batches so the chunk-delta prepare
        reads per file — and so tests can interpose a mid-append mutation
        between the identity stat and the read (ISSUE 19 bugfix)."""
        import pyarrow.parquet as pq

        names = self.scan.schema().names
        strings = [
            f.name
            for f in self.scan.schema()
            if pa.types.is_string(f.type) or pa.types.is_large_string(f.type)
        ]
        return pq.read_table(
            path, columns=names, read_dictionary=strings
        ).combine_chunks()

    def _check_int_ranges(self, batch_cols, n: int) -> None:
        """Integer sums accumulate in int32 on device; decline when a masked
        sum over n rows could overflow (ADVICE r1: silent f32 rounding of
        integer aggregates). batch_cols: one Dict[int, np.ndarray], or a list
        of them when the sum spans several mesh shards (psum adds across
        shards, so the bound uses the GLOBAL row count)."""
        for name, rows in self._int_sum_rows(batch_cols):
            if n > rows:
                raise UnsupportedOnDevice(
                    f"int32 sum over column {name!r} may overflow"
                )

    def _int_sum_rows(self, batch_cols) -> Iterator[Tuple[str, int]]:
        """(input column, the most rows whose int32 sum cannot overflow) of
        each integer SUM or AVG input that holds a non-zero value."""
        col_dicts = batch_cols if isinstance(batch_cols, list) else [batch_cols]
        for a, ie, ix in zip(self.aggs, self.agg_inputs, self.int_exact):
            if not ix or a.fn not in ("sum", "avg"):
                continue
            maxabs = 0
            for bc in col_dicts:
                npcol = bc.get(ie.index)
                if npcol is not None and len(npcol):
                    maxabs = max(
                        maxabs, abs(int(npcol.max())), abs(int(npcol.min()))
                    )
            if maxabs:
                yield ie.name, _INT32_MAX // maxabs

    def _lower_columns(self, batch: pa.RecordBatch) -> Dict[int, np.ndarray]:
        cols: Dict[int, np.ndarray] = {}
        for idx, dtype in self.compiler.used_columns.items():
            d = self.dicts.dicts.get(idx)
            cols[idx] = column_to_numpy(batch.column(idx), dtype, d)
        for idx, width in self._bit_planes.items():
            cols.update(self._lower_planes(batch.column(idx), idx, width))
        return cols

    @staticmethod
    def _lower_planes(arr, idx: int, width: str) -> Dict[int, np.ndarray]:
        """Bijected min/max input: lower the RAW Arrow float column to its
        order-preserving int32 key plane(s) — never through the f32 device
        copy, which would round f64 values. Declines on NaN: Arrow's host
        min/max SKIPS NaN, and no single key order can make a value both
        never-min and never-max."""
        from ballista_tpu.ops import floatbits

        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if arr.null_count:
            raise UnsupportedOnDevice("null values in device column")
        vals = arr.to_numpy(zero_copy_only=False)
        if np.isnan(vals).any():
            raise UnsupportedOnDevice("NaN in float min/max column")
        hk, lk = plane_keys(idx)
        if width == "f32":
            return {hk: floatbits.f32_to_i32(vals.astype(np.float32, copy=False))}
        hi, lo = floatbits.i64_to_planes(floatbits.f64_to_i64(vals))
        return {hk: hi, lk: lo}

    # holds-lock: self._prepare_lock
    def _prepare_partition(self, partition: int, ctx) -> List[dict]:
        """Host work for one partition: scan, encode, pad, transfer. Returns
        the device-input entries, each of consecutive batches of a scan
        partition (jnp column arrays stay resident).
        Like the sorted path, the staged host artifacts persist through
        ops/layout_cache.py: the low-cardinality shapes (q1/q6) pay the
        same full-scan decode at SF=100 (~400 s measured), so a fresh
        process must skip straight to the h2d transfer too.

        Pipelined (ballista.tpu.ingest_workers > 0): the PREFETCH stage —
        parquet read + dictionary decode (inside _scan_batches) and group
        ranking — runs on a small thread pool with at most ingest_depth
        batches in flight, overlapping the CONSUME stage below. Consume
        (narrow/encode/upload) stays strictly IN-ORDER and in-thread: each
        batch's narrow choice must feed the next batch's narrow_column
        prior (one jitted step), the growing ColumnDictionary must assign
        codes in batch order (bit-identical results at any worker count),
        and the non-persisting host peak stays ~depth batches' tiles. When
        persisting, a host snapshot of every batch's tiles is retained
        until the save at the end — up to the HBM budget of extra host
        RSS, for that one prepare."""
        import time as _time

        import jax.numpy as jnp

        from ballista_tpu.ops.runtime import pipelined_map, record_ingest

        persisting = (
            bool(ctx.config.tpu_layout_cache_dir())
            and self.persist_key is not None
        )
        if (
            persisting
            and getattr(self, "chunk_key_base", None) is not None
            and isinstance(self.scan, ParquetScanExec)
        ):
            # chunk-set delta store (ISSUE 19): persist/reuse per
            # (path, mtime, size, chunk_index) instead of one blob per
            # whole file set — appending a file re-prepares only its own
            # chunks and every existing tile loads byte-for-byte
            return self._prepare_partition_chunks(partition, ctx)
        t_wall0 = _time.perf_counter()
        scan_s = 0.0
        encode_s = 0.0
        upload_s = 0.0
        src_times: List[float] = []  # appended by the reader thread only
        records: List[dict] = []
        entries: List[dict] = []
        # all of a partition's batch entries are live on device at once
        # during run(); past the budget, decline to the host path rather
        # than OOM the chip (mirrors the sorted path's staged check)
        budget = ctx.config.tpu_hbm_budget()
        total_bytes = 0

        def _prefetch(item: Tuple[int, pa.RecordBatch]):
            # group codes FIRST: a high-cardinality switch must not pay the
            # column upload. Pure per-batch work (no shared stage state), so
            # batches may rank concurrently; the TooManyGroups decision
            # stays in the ordered consumer below for serial-identical
            # semantics.
            t0 = _time.perf_counter()
            part, batch = item
            codes, key_values, n_groups = self._group_codes(batch)
            return part, batch, codes, key_values, n_groups, _time.perf_counter() - t0

        def _stage_entry(n: int, npcols: Dict[int, np.ndarray], codes: np.ndarray,
                         key_values: List[pa.Array], n_groups: int) -> None:
            nonlocal total_bytes, encode_s, upload_s
            t_enc0 = _time.perf_counter()
            bucket = bucket_rows(n)
            staged: Dict[int, tuple] = {}
            for idx in list(npcols):
                npcol = npcols.pop(idx)
                fill = False if npcol.dtype == np.bool_ else 0
                narrow, lut, choice = narrow_column(
                    npcol, self._narrow_choice.get(idx)
                )
                del npcol
                padded = pad_to(narrow, bucket, fill)
                staged[idx] = (padded, lut, choice)
                total_bytes += padded.nbytes + (0 if lut is None else lut.nbytes)
            total_bytes += 3 * bucket  # int16 codes + bool row_valid
            if total_bytes > budget:
                raise UnsupportedOnDevice(
                    f"stage batches ({total_bytes >> 20} MiB) exceed the HBM budget"
                )
            seg_bucket = bucket_rows(n_groups, 16) + 1  # +1 dump slot
            # group codes fit int16 by construction (n_groups <= MAX_GROUPS)
            codes_pad = pad_to(codes.astype(np.int16), bucket, 0)
            row_valid = np.zeros(bucket, dtype=np.bool_)
            row_valid[:n] = True
            encode_s += _time.perf_counter() - t_enc0
            rec = {
                "n_groups": int(n_groups),
                "seg_bucket": int(seg_bucket),
                "codes_pad": codes_pad,
                "row_valid": row_valid,
                "key_values": key_values,
            }
            if persisting:
                records.append({**rec, "staged": dict(staged)})
            t_up0 = _time.perf_counter()
            make_headroom(self, total_bytes, budget)
            cols = _upload_staged(staged, self._narrow_choice)
            entries.append(
                {
                    "n_groups": rec["n_groups"],
                    "seg_bucket": rec["seg_bucket"],
                    "cols": cols,
                    "codes": jnp.asarray(codes_pad),
                    "row_valid": jnp.asarray(row_valid),
                    "key_values": key_values,
                }
            )
            upload_s += _time.perf_counter() - t_up0

        # consecutive batches of a scan partition are gathered
        # (_GatheredBatches) up to the batch size and while the int32 sum
        # bound holds, and become entries of bounded padding
        held: Optional[_GatheredBatches] = None
        batches_in = 0
        batch_src = (
            (p, b) for p, b in self._scan_part_batches(partition, ctx) if b.num_rows
        )
        for part, batch, codes, key_values, n_groups, dt in pipelined_map(
            batch_src, _prefetch,
            ctx.config.tpu_ingest_workers(), ctx.config.tpu_ingest_depth(),
            on_src_time=src_times.append,
        ):
            scan_s += dt
            batches_in += 1
            n = batch.num_rows
            if n_groups == 0:
                continue
            if n_groups > MAX_GROUPS:
                # beyond the unrolled path's ceiling: run() retries with the
                # sorted chunked-segment layout
                raise TooManyGroups(f"{n_groups} groups exceeds unrolled path")
            t_enc0 = _time.perf_counter()
            npcols = self._lower_columns(batch)
            self._check_int_ranges(npcols, n)
            encode_s += _time.perf_counter() - t_enc0
            sum_rows = dict(self._int_sum_rows(npcols))
            if held is not None and not held.fits(part, n, sum_rows, ctx.batch_size):
                for entry in held.entries():
                    _stage_entry(*entry)
                held = None
            if held is None:
                held = _GatheredBatches(part)
            held.add(n, npcols, codes, key_values, n_groups, sum_rows)
        if held is not None:
            for entry in held.entries():
                _stage_entry(*entry)
        tracing.incr("device.gathered_batches_in", batches_in)
        tracing.incr("device.gathered_batches_out", len(entries))
        if persisting and records:
            self._save_batches_layout(partition, ctx, records)
        scan_s += sum(src_times)
        wall_s = _time.perf_counter() - t_wall0
        record_ingest(scan_s, encode_s, upload_s, wall_s)
        return entries

    def _save_batches_layout(self, partition: int, ctx, records: List[dict]) -> None:
        """Best-effort persist of the unrolled path's staged batches."""
        from ballista_tpu.ops import layout_cache as lc

        arrays: List[np.ndarray] = []
        metas = []
        for rec in records:
            m = {
                "n_groups": rec["n_groups"],
                "seg_bucket": rec["seg_bucket"],
                "cols": _pack_staged(rec["staged"], arrays),
                "codes": len(arrays),
            }
            arrays.append(rec["codes_pad"])
            m["row_valid"] = len(arrays)
            arrays.append(rec["row_valid"])
            m["keys"] = len(arrays)
            arrays.append(lc.pack_arrow_arrays(rec["key_values"]))
            metas.append(m)
        dmeta, darrays = lc.pack_dict_snapshot(self.dicts)
        offset = len(arrays)
        meta = {
            "kind": "batches",
            "batches": metas,
            "dicts": {k: v + offset for k, v in dmeta.items()},
        }
        arrays.extend(darrays)
        meta["n_arrays"] = len(arrays)
        lc.save_entry(
            base=ctx.config.tpu_layout_cache_dir(),
            stage_key=self.persist_key,
            partition=partition,
            meta=meta,
            arrays=arrays,
            cap_bytes=ctx.config.tpu_layout_cache_cap(),
        )

    # -- chunk-set delta store (ISSUE 19) -------------------------------
    #
    # The whole-set batches entry above keys on (plan, file set, mtimes):
    # appending ONE parquet file to a growing directory orphans the entry
    # and re-pays the full scan/decode/encode pipeline. The methods below
    # instead persist each prepared chunk under its OWN identity —
    # (path, mtime, size, chunk_index) beneath the mtime-free
    # chunk_key_base — so a query over files ∪ {new} re-prepares only the
    # new file's chunks and loads every existing tile byte-for-byte.

    def _chunk_context(self) -> str:
        """Hash of the cross-file prepare state a chunk's tiles bake in:
        the sticky narrow choices and every string dictionary's code->value
        mapping as they stood when the file's first chunk was consumed.
        Part of the chunk key: a file set whose sort order interleaves a
        NEW file before an old one shifts the old file's dictionary codes,
        and keying on the context makes that a clean miss (one re-prepare,
        re-saved under the new context) instead of a poisoned hit or a
        permanently unloadable entry."""
        import hashlib

        h = hashlib.sha256()
        for k in sorted(self._narrow_choice, key=str):
            h.update(f"n|{k}={self._narrow_choice[k]}\x00".encode())
        for idx in sorted(self.dicts.dicts):
            snap = self.dicts.dicts[idx].snapshot()
            if snap is None:
                continue
            h.update(f"d|{idx}\x00".encode())
            for v in snap.to_pylist():
                h.update(repr(v).encode())
                h.update(b"\x00")
        return h.hexdigest()[:20]

    def _chunk_stage_key(self, ident: Tuple[str, str, int], context: str) -> str:
        path, mtime, size = ident
        return (
            f"chunk|{self.chunk_key_base}|ctx={context}|{path}|{mtime}|{size}"
        )

    # holds-lock: self._prepare_lock
    def _prepare_partition_chunks(self, partition: int, ctx) -> List[dict]:
        """Chunk-granular variant of _prepare_partition for parquet-backed
        stages with a delta identity: walk the partition's files in order,
        loading each file's persisted chunks when its (path, mtime, size)
        identity and prepare context match, preparing (and persisting) only
        the files that miss. Batch order — and therefore dictionary code
        assignment, narrow choices, and the device batch stream — is
        identical to the serial whole-set prepare."""
        import os
        import time as _time

        from ballista_tpu.ops.runtime import record_ingest

        t_wall0 = _time.perf_counter()
        if self.scan_stride is not None:
            total = self.scan.output_partitioning().partition_count()
            parts = range(partition, total, self.scan_stride)
        else:
            parts = [partition]
        budget = ctx.config.tpu_hbm_budget()
        entries: List[dict] = []
        # cumulative timings + staged-bytes budget ledger shared with the
        # per-file prepare (mirrors _prepare_partition's accounting)
        totals = {"bytes": 0, "scan_s": 0.0, "encode_s": 0.0, "upload_s": 0.0}
        for p in parts:
            path = self.scan.source.files[p]
            try:
                st = os.stat(path)
                ident = (path, str(st.st_mtime), int(st.st_size))
            except OSError:
                ident = None
            context = self._chunk_context()
            loaded = (
                self._load_file_chunks(ident, context, ctx)
                if ident is not None
                else None
            )
            if loaded is not None:
                records, nbytes = loaded
                totals["bytes"] += nbytes
                if totals["bytes"] > budget:
                    raise UnsupportedOnDevice(
                        f"stage batches ({totals['bytes'] >> 20} MiB) "
                        f"exceed the HBM budget"
                    )
                t_up0 = _time.perf_counter()
                reused = 0
                for rec in records:
                    if rec is None:  # empty-chunk marker
                        continue
                    entries.append(self._upload_record(rec, budget, totals))
                    reused += 1
                totals["upload_s"] += _time.perf_counter() - t_up0
                tracing.incr("delta.chunks_reused", reused)
                tracing.incr("delta.bytes_reprepared_saved", nbytes)
                continue
            self._prepare_file_chunks(
                p, ident, context, ctx, entries, totals, budget
            )
        record_ingest(
            totals["scan_s"], totals["encode_s"], totals["upload_s"],
            _time.perf_counter() - t_wall0,
        )
        return entries

    def _upload_record(self, rec: dict, budget: int, totals: dict) -> dict:
        import jax.numpy as jnp

        make_headroom(self, totals["bytes"], budget)
        cols = _upload_staged(rec["staged"], self._narrow_choice)
        return {
            "n_groups": rec["n_groups"],
            "seg_bucket": rec["seg_bucket"],
            "cols": cols,
            "codes": jnp.asarray(rec["codes_pad"]),
            "row_valid": jnp.asarray(rec["row_valid"]),
            "key_values": rec["key_values"],
        }

    def _load_file_chunks(self, ident, context: str, ctx):
        """Load ONE file's persisted chunk set. Returns (records, bytes) —
        records in chunk order, None marking empty chunks — or None on any
        miss. All-or-nothing: every chunk must be present, carry the exact
        identity stamped at save time (a torn mid-append writer is caught
        by the save-side re-stat, this is the load-side belt), and adopt
        its dictionary snapshot cleanly, else the whole file re-prepares."""
        from ballista_tpu.ops import layout_cache as lc

        base = ctx.config.tpu_layout_cache_dir()
        skey = self._chunk_stage_key(ident, context)
        hit = lc.load_entry(base, skey, 0)
        if hit is None:
            return None
        n_chunks = hit[0].get("n_chunks")
        if not isinstance(n_chunks, int) or n_chunks < 1:
            return None
        records: List[Optional[dict]] = []
        total = 0
        for ci in range(n_chunks):
            if hit is None:
                hit = lc.load_entry(base, skey, ci)
            if hit is None:
                return None
            meta, arrays = hit
            hit = None
            if (
                meta.get("kind") != "chunk"
                or meta.get("ident") != list(ident)
                or meta.get("n_chunks") != n_chunks
            ):
                return None
            try:
                if not lc.adopt_dict_snapshot(self.dicts, meta["dicts"], arrays):
                    return None
            except Exception:
                return None
            if meta.get("empty"):
                records.append(None)
                continue
            try:
                unpacked = _unpack_staged(
                    meta["cols"], arrays, self._narrow_choice
                )
                if unpacked is None:
                    return None
                staged, nbytes = unpacked
                rec = {
                    "n_groups": int(meta["n_groups"]),
                    "seg_bucket": int(meta["seg_bucket"]),
                    "staged": staged,
                    "codes_pad": arrays[meta["codes"]],
                    "row_valid": arrays[meta["row_valid"]],
                    "key_values": lc.unpack_arrow_arrays(arrays[meta["keys"]]),
                }
            except Exception:
                return None
            total += nbytes + rec["codes_pad"].nbytes + rec["row_valid"].nbytes
            records.append(rec)
        return records, total

    def _prepare_file_chunks(
        self, p: int, ident, context: str, ctx,
        entries: List[dict], totals: dict, budget: int,
    ) -> None:
        """Prepare one file fresh, persisting each consumed chunk under its
        own (path, mtime, size, chunk_index) entry as it goes. Mid-append
        fail-closed (ISSUE 19 bugfix): the file is re-statted AFTER the
        read — if its identity moved between the stat and the read, the
        bytes just decoded may not be the state `ident` describes, and
        persisting them would poison the entry for every later process
        whose fingerprint resolved at the old mtime. The in-memory prepare
        still uses the data (same exposure as the whole-set path); only
        the save is declined, and recorded."""
        import os
        import time as _time

        from ballista_tpu.ops import layout_cache as lc
        from ballista_tpu.ops.runtime import pipelined_map

        path = self.scan.source.files[p]
        t0 = _time.perf_counter()
        table = self._read_scan_file(path, ctx)
        totals["scan_s"] += _time.perf_counter() - t0
        save = ident is not None
        if save:
            try:
                st = os.stat(path)
                if (str(st.st_mtime), int(st.st_size)) != (ident[1], ident[2]):
                    save = False
                    tracing.incr("delta.save_declined_midappend")
            except OSError:
                save = False
        base = ctx.config.tpu_layout_cache_dir()
        cap = ctx.config.tpu_layout_cache_cap()
        skey = self._chunk_stage_key(ident, context) if save else None
        chunks = table.to_batches(max_chunksize=ctx.batch_size)
        n_chunks = max(len(chunks), 1)

        def _save_chunk(ci: int, body: Optional[dict], staged) -> None:
            if not save:
                return
            arrays: List[np.ndarray] = []
            meta = {
                "kind": "chunk",
                "ident": list(ident),
                "n_chunks": n_chunks,
            }
            if body is None:
                meta["empty"] = True
            else:
                meta["cols"] = _pack_staged(staged, arrays)
                meta["n_groups"] = body["n_groups"]
                meta["seg_bucket"] = body["seg_bucket"]
                meta["codes"] = len(arrays)
                arrays.append(body["codes_pad"])
                meta["row_valid"] = len(arrays)
                arrays.append(body["row_valid"])
                meta["keys"] = len(arrays)
                arrays.append(lc.pack_arrow_arrays(body["key_values"]))
            # cumulative snapshot AFTER this chunk's encode: a loader that
            # adopted every prior chunk in order holds exactly a prefix
            dmeta, darrays = lc.pack_dict_snapshot(self.dicts)
            offset = len(arrays)
            meta["dicts"] = {k: v + offset for k, v in dmeta.items()}
            arrays.extend(darrays)
            meta["n_arrays"] = len(arrays)
            lc.save_entry(base, skey, ci, meta, arrays, cap)

        def _prefetch(item):
            ci, batch = item
            if batch.num_rows == 0:
                return ci, batch, None, None, 0, 0.0
            t0 = _time.perf_counter()
            codes, key_values, n_groups = self._group_codes(batch)
            return (
                ci, batch, codes, key_values, n_groups,
                _time.perf_counter() - t0,
            )

        for ci, batch, codes, key_values, n_groups, dt in pipelined_map(
            iter(enumerate(chunks)), _prefetch,
            ctx.config.tpu_ingest_workers(), ctx.config.tpu_ingest_depth(),
        ):
            totals["scan_s"] += dt
            n = batch.num_rows
            if n == 0 or n_groups == 0:
                _save_chunk(ci, None, None)
                continue
            if n_groups > MAX_GROUPS:
                # partial chunk set stays on disk; the all-chunks-present
                # load check fails it closed
                raise TooManyGroups(f"{n_groups} groups exceeds unrolled path")
            bucket = bucket_rows(n)
            t_enc0 = _time.perf_counter()
            npcols = self._lower_columns(batch)
            self._check_int_ranges(npcols, n)
            staged: Dict[int, tuple] = {}
            for idx in list(npcols):
                npcol = npcols.pop(idx)
                fill = False if npcol.dtype == np.bool_ else 0
                narrow, lut, choice = narrow_column(
                    npcol, self._narrow_choice.get(idx)
                )
                del npcol
                padded = pad_to(narrow, bucket, fill)
                staged[idx] = (padded, lut, choice)
                totals["bytes"] += (
                    padded.nbytes + (0 if lut is None else lut.nbytes)
                )
            totals["bytes"] += 3 * bucket  # int16 codes + bool row_valid
            if totals["bytes"] > budget:
                raise UnsupportedOnDevice(
                    f"stage batches ({totals['bytes'] >> 20} MiB) exceed "
                    f"the HBM budget"
                )
            seg_bucket = bucket_rows(n_groups, 16) + 1  # +1 dump slot
            codes_pad = pad_to(codes.astype(np.int16), bucket, 0)
            row_valid = np.zeros(bucket, dtype=np.bool_)
            row_valid[:n] = True
            rec = {
                "n_groups": int(n_groups),
                "seg_bucket": int(seg_bucket),
                "codes_pad": codes_pad,
                "row_valid": row_valid,
                "key_values": key_values,
            }
            totals["encode_s"] += _time.perf_counter() - t_enc0
            _save_chunk(ci, rec, staged)
            t_up0 = _time.perf_counter()
            rec["staged"] = staged
            entries.append(self._upload_record(rec, budget, totals))
            totals["upload_s"] += _time.perf_counter() - t_up0
            tracing.incr("delta.chunks_prepared")
        if not chunks:
            _save_chunk(0, None, None)

    def _load_batches_layout(self, meta: dict, arrays: List[np.ndarray],
                             ctx) -> Optional[dict]:
        """Rehydrate a persisted batches entry (meta pre-validated as
        kind=batches with an adopted dictionary snapshot)."""
        import jax.numpy as jnp

        from ballista_tpu.ops import layout_cache as lc

        records: List[dict] = []
        total = 0
        try:
            for m in meta["batches"]:
                unpacked = _unpack_staged(
                    m["cols"], arrays, self._narrow_choice
                )
                if unpacked is None:
                    return None
                staged, nbytes = unpacked
                total += nbytes
                records.append(
                    {
                        "n_groups": int(m["n_groups"]),
                        "seg_bucket": int(m["seg_bucket"]),
                        "staged": staged,
                        "codes_pad": arrays[m["codes"]],
                        "row_valid": arrays[m["row_valid"]],
                        "key_values": lc.unpack_arrow_arrays(arrays[m["keys"]]),
                    }
                )
                total += arrays[m["codes"]].nbytes + arrays[m["row_valid"]].nbytes
        except Exception:
            return None
        budget = ctx.config.tpu_hbm_budget()
        if total > budget:
            raise UnsupportedOnDevice(
                f"stage batches ({total >> 20} MiB) exceed the HBM budget"
            )
        make_headroom(self, total, budget)
        entries: List[dict] = []
        for rec in records:
            cols = _upload_staged(rec["staged"], self._narrow_choice)
            entries.append(
                {
                    "n_groups": rec["n_groups"],
                    "seg_bucket": rec["seg_bucket"],
                    "cols": cols,
                    "codes": jnp.asarray(rec["codes_pad"]),
                    "row_valid": jnp.asarray(rec["row_valid"]),
                    "key_values": rec["key_values"],
                }
            )
        return {"kind": "batches", "entries": entries}

    # holds-lock: self._prepare_lock
    def _prepare_partition_sorted(self, partition: int, ctx) -> dict:
        """High-cardinality path: whole-partition chunked-segment layout
        (ops/layout.py). Sorting/ranking/materialization is cache-time host
        work; per-query device work is O(N) elementwise + axis reductions.
        Config ballista.tpu.sorted_kernel=pallas selects the MXU one-hot
        matmul kernel instead (sum/count/avg only).

        The host work (parquet decode, encode, rank, sort, materialize) is a
        pure function of (persist_key, partition) — persisted via
        ops/layout_cache.py so a fresh process skips straight to the h2d
        transfer (measured: it is ~600 of the 737 s of a cold q3 SF=100).
        The pallas kernel path is not persisted (config-gated, flat layout)."""
        import time as _time

        from ballista_tpu.ops.layout import SortedSegmentLayout
        from ballista_tpu.ops.runtime import record_ingest

        loaded = self._load_layout(partition, ctx, want=("sorted",))
        if loaded is not None:
            return loaded
        # the prefetch/consume split here is inside _scan_batches: multi-file
        # partitions decode up to ingest_workers files concurrently; the
        # whole-partition rank/sort/materialize below is one ordered pass
        t_wall0 = _time.perf_counter()
        batches = [b for b in self._scan_batches(partition, ctx) if b.num_rows]
        if not batches:
            return {"kind": "empty"}
        table = pa.Table.from_batches(batches).combine_chunks()
        batch = table.to_batches(max_chunksize=table.num_rows)[0]
        codes, key_values, n_groups = self._group_codes(batch)
        scan_s = _time.perf_counter() - t_wall0
        if n_groups == 0:
            return {"kind": "empty"}
        if (
            ctx.config.tpu_sorted_kernel() == "pallas"
            and all(a.fn in ("sum", "count", "avg") for a in self.aggs)
            and not any(self.int_exact)
            # fact stages (sorted_cover_max) consume [V, L1] tiles + rank
            # metadata the pallas entry doesn't carry
            and not getattr(self, "sorted_cover_max", False)
            # the fused top-k epilogue composes with the layout core only
            and self.topk is None
            # counts accumulate in f32 inside the kernel: exact only below 2^24
            and batch.num_rows <= (1 << 24)
        ):
            return self._prepare_pallas_sorted(batch, codes, key_values, n_groups, ctx)
        layout = None
        if self.topk is not None and not getattr(self, "sorted_cover_max", False):
            # fused top-k wants the one-chunk-per-group cover: the chunk
            # fold becomes identity, so the gathered k columns are the
            # BIT-IDENTICAL values the full readback would emit. The int
            # range check runs against the cover width (a whole-group sum
            # in one chunk); failing either check falls back to the
            # default chunking below — fusion per-partition degrades to the
            # in-program fold or the full readback, the normal path is
            # untouched. Only THIS branch lowers columns before the layout:
            # the default ordering below (layout first, codes freed, then
            # lower) keeps the documented SF=100 host-memory peak.
            npcols = self._lower_columns(batch)
            cover_L1 = _topk_cover_L1(codes, n_groups)
            if cover_L1 is not None:
                try:
                    self._check_int_ranges(npcols, cover_L1)
                    layout = SortedSegmentLayout(codes, n_groups, force_L1=cover_L1)
                except UnsupportedOnDevice:
                    layout = None
            elif ctx.config.tpu_cost_model():
                # general skew handler (ISSUE 10): the cover failed because
                # a few dominant groups blow its bounds. Split THEM to the
                # in-program segment fold and keep every tail group on the
                # one-chunk fast path, instead of degrading the whole
                # partition to percentile chunking. Counted as a runtime
                # re-plan; bit-identity rides the existing fold machinery.
                skew = skew_split_plan(codes, n_groups)
                if skew is not None:
                    L1_tail, _n_dom = skew
                    try:
                        self._check_int_ranges(npcols, L1_tail)
                        layout = SortedSegmentLayout(
                            codes, n_groups, force_L1=L1_tail
                        )
                        from ballista_tpu.ops.runtime import (
                            record_routing_event,
                        )

                        record_routing_event("skew_replan")
                    except UnsupportedOnDevice:
                        layout = None
            if layout is None:
                layout = SortedSegmentLayout(codes, n_groups)
                self._check_int_ranges(npcols, layout.L1)
            del codes
        else:
            layout = SortedSegmentLayout(
                codes, n_groups, cover_max=getattr(self, "sorted_cover_max", False)
            )
            del codes
            npcols = self._lower_columns(batch)
            self._check_int_ranges(npcols, layout.L1)
        # derived columns read row-space npcols; compute BEFORE the staging
        # loop below starts freeing them
        derived_raw = {name: fn(npcols) for name, fn in self.derive_columns.items()}
        # the Arrow buffers are no longer needed: at SF=100 the combined
        # table is ~25 GB that would otherwise sit under the whole
        # materialization peak (this prepare OOM-killed a 125 GB host)
        del batches, table, batch
        # stage narrow tiles HOST-side and check the HBM budget BEFORE any
        # device allocation: the planner's coalesce cap compares compressed
        # leaf bytes, which under-counts columns that fail to narrow — a
        # too-big stage must fall to the host path, not OOM the chip.
        # Row-space columns free as their tiles materialize: the peak holds
        # one column in row space, not every used column at once.
        staged: Dict[int, tuple] = {}
        total = layout.clen.nbytes
        for idx in list(npcols):
            npcol = npcols.pop(idx)
            narrow, lut, choice = narrow_column(npcol, self._narrow_choice.get(idx))
            del npcol
            tiles = layout.materialize(narrow)
            del narrow
            staged[idx] = (tiles, lut, choice)
            total += tiles.nbytes + (lut.nbytes if lut is not None else 0)
        staged_derived: Dict[str, tuple] = {}
        for name in list(derived_raw):
            raw = derived_raw.pop(name)
            if raw.dtype == np.int32:
                # int-only narrowing: derived tiles travel as standalone
                # step arguments (not through widen_cols), so the consumer
                # widens with a plain astype — no LUT tuples here
                key = f"derived:{name}"
                narrow, _lut, choice = narrow_column(raw, self._narrow_choice.get(key))
                tiles = layout.materialize(narrow)
                staged_derived[name] = (tiles, key, choice)
            else:
                staged_derived[name] = (layout.materialize(raw), None, None)
            del raw
            total += staged_derived[name][0].nbytes
        # the take-index served every materialize; drop it before the h2d
        # staging peak (persisted entries never carry it)
        layout.row_take = None
        budget = ctx.config.tpu_hbm_budget()
        if total > budget:
            # checked BEFORE persisting so an undeployable layout is never
            # written to disk
            raise UnsupportedOnDevice(
                f"stage tiles ({total >> 20} MiB) exceed the HBM budget"
            )
        t_enc_end = _time.perf_counter()
        encode_s = t_enc_end - t_wall0 - scan_s
        # persist BEFORE upload: _upload_staged consumes the host tiles
        self._save_sorted_layout(
            partition, ctx, layout, staged, staged_derived, key_values
        )
        t_up0 = _time.perf_counter()
        # the layout-cache disk write is host-side prepare cost: count it in
        # encode_s so wall_s stays the sum of the components and the derived
        # overlap fraction is not dragged down on persisting prepares
        encode_s += t_up0 - t_enc_end
        out = self._finish_sorted(
            ctx, layout, staged, staged_derived, key_values, total
        )
        t_end = _time.perf_counter()
        record_ingest(scan_s, encode_s, t_end - t_up0, t_end - t_wall0)
        return out

    def _finish_sorted(
        self, ctx, layout, staged: Dict, staged_derived: Dict, key_values,
        total: int,
    ) -> dict:
        """Shared tail of the fresh and disk-loaded sorted prepares: budget
        check, headroom, h2d upload, derived upload, step build, entry."""
        import jax.numpy as jnp

        budget = ctx.config.tpu_hbm_budget()
        if total > budget:
            raise UnsupportedOnDevice(
                f"stage tiles ({total >> 20} MiB) exceed the HBM budget"
            )
        make_headroom(self, total, budget)
        cols = _upload_staged(staged, self._narrow_choice)
        derived = {}
        for name in list(staged_derived):
            tiles, key, choice = staged_derived.pop(name)
            if key is not None:
                self._narrow_choice[key] = choice
            derived[name] = jnp.asarray(tiles)
        if self._sorted_step is None:
            self._sorted_step = self._build_sorted_step()
        return {
            "kind": "sorted",
            "layout": layout,
            "cols": cols,
            "clen": jnp.asarray(layout.clen),
            "key_values": key_values,
            "n_groups": layout.n_groups,
            "derived": derived,
        }

    # -- persisted layout cache (ops/layout_cache.py) -------------------
    def _save_sorted_layout(
        self, partition: int, ctx, layout, staged: Dict, staged_derived: Dict,
        key_values,
    ) -> None:
        """Best-effort persist of one prepared sorted partition: layout
        scalars + owner/pad, narrow tiles + LUTs + choices, derived tiles,
        the string-dictionary snapshot (codes baked into the tiles), and the
        group key values (Arrow IPC bytes). Entries are keyed by the stage
        cache key, so file rewrites and config changes miss cleanly; the
        int-range check is NOT re-run on load because the entry only exists
        if the identical data passed it at save time."""
        base = ctx.config.tpu_layout_cache_dir()
        if not base or self.persist_key is None:
            return
        from ballista_tpu.ops import layout_cache as lc

        arrays: List[np.ndarray] = []
        meta: Dict = {"kind": "sorted", "layout": layout.state()}
        meta["owner"] = len(arrays)
        arrays.append(layout.owner)
        meta["clen"] = len(arrays)
        arrays.append(layout.clen)
        meta["cols"] = _pack_staged(staged, arrays)
        derived_meta = {}
        for name, (tiles, nkey, choice) in staged_derived.items():
            derived_meta[name] = {
                "tiles": len(arrays), "key": nkey, "choice": choice,
            }
            arrays.append(tiles)
        meta["derived"] = derived_meta
        dmeta, darrays = lc.pack_dict_snapshot(self.dicts)
        offset = len(arrays)
        meta["dicts"] = {k: v + offset for k, v in dmeta.items()}
        arrays.extend(darrays)
        meta["keys"] = len(arrays)
        arrays.append(lc.pack_arrow_arrays(key_values))
        meta["n_arrays"] = len(arrays)
        lc.save_entry(
            base, self.persist_key, partition, meta, arrays,
            ctx.config.tpu_layout_cache_cap(),
        )

    # holds-lock: self._prepare_lock
    def _load_layout(self, partition: int, ctx, want=("sorted", "batches")):
        """Rehydrate a persisted partition of either kind: adopt the
        dictionary snapshot (live dicts must be a prefix — codes in the
        persisted arrays must mean the same strings), then go straight to
        the h2d transfer. Returns None on any miss/mismatch."""
        base = ctx.config.tpu_layout_cache_dir()
        if not base or self.persist_key is None:
            return None
        from ballista_tpu.ops import layout_cache as lc

        hit = lc.load_entry(base, self.persist_key, partition)
        if hit is None:
            return None
        meta, arrays = hit
        if meta.get("kind") not in want:
            return None
        try:
            if not lc.adopt_dict_snapshot(self.dicts, meta["dicts"], arrays):
                return None
        except Exception:
            return None
        if meta["kind"] == "batches":
            return self._load_batches_layout(meta, arrays, ctx)
        return self._load_sorted_entry(meta, arrays, ctx)

    def _load_sorted_entry(self, meta: dict, arrays, ctx) -> Optional[dict]:
        from ballista_tpu.ops import layout_cache as lc

        if set(meta.get("derived", {})) != set(self.derive_columns):
            return None
        try:
            from ballista_tpu.ops.layout import SortedSegmentLayout

            owner = arrays[meta["owner"]]
            if "clen" in meta:
                clen = arrays[meta["clen"]]
            else:  # legacy entry: bool [V, L1] pad tiles
                clen = arrays[meta["pad"]].sum(axis=1).astype(np.int16)
            layout = SortedSegmentLayout.from_state(meta["layout"], owner, clen)
            unpacked = _unpack_staged(meta["cols"], arrays, self._narrow_choice)
            if unpacked is None:
                return None  # jitted step already compiled another dtype
            staged, col_bytes = unpacked
            total = clen.nbytes + col_bytes
            staged_derived: Dict[str, tuple] = {}
            for name, spec in meta["derived"].items():
                nkey = spec["key"]
                if nkey is not None:
                    cur = self._narrow_choice.get(nkey)
                    if cur is not None and cur != spec["choice"]:
                        return None
                staged_derived[name] = (arrays[spec["tiles"]], nkey, spec["choice"])
                total += arrays[spec["tiles"]].nbytes
            key_values = lc.unpack_arrow_arrays(arrays[meta["keys"]])
        except Exception:
            return None
        # budget overrun raises (not miss): same disposition as a fresh
        # prepare of this partition
        return self._finish_sorted(
            ctx, layout, staged, staged_derived, key_values, total
        )

    def _prepare_pallas_sorted(self, batch, codes, key_values, n_groups, ctx) -> dict:
        """Flat sorted residency for the pallas MXU kernel
        (ops/pallas_kernels.py::sorted_grouped_sum)."""
        import jax.numpy as jnp

        from ballista_tpu.ops.pallas_kernels import SORT_BLOCK

        order = np.argsort(codes, kind="stable")
        n = len(order)
        pad = (-n) % SORT_BLOCK
        codes_sorted = codes[order].astype(np.int32)
        if pad:
            codes_sorted = np.concatenate(
                [codes_sorted, np.full(pad, codes_sorted[-1], np.int32)]
            )
        npcols = self._lower_columns(batch)
        # same pre-allocation budget discipline as the layout path: this
        # path uploads full-width columns, so a too-big partition must
        # decline to the host, not OOM the chip
        budget = ctx.config.tpu_hbm_budget()
        total = (n + pad) * (4 + 1)  # codes int32 + row_valid bool
        for npcol in npcols.values():
            total += (n + pad) * npcol.dtype.itemsize
        if total > budget:
            raise UnsupportedOnDevice(
                f"pallas stage columns ({total >> 20} MiB) exceed the HBM budget"
            )
        make_headroom(self, total, budget)
        cols: Dict[int, object] = {}
        for idx, npcol in npcols.items():
            flat = npcol[order]
            fill = False if flat.dtype == np.bool_ else 0
            cols[idx] = jnp.asarray(pad_to(flat, n + pad, fill))
        row_valid = np.zeros(n + pad, dtype=np.bool_)
        row_valid[:n] = True
        return {
            "kind": "pallas_sorted",
            "codes": jnp.asarray(codes_sorted),
            "cols": cols,
            "row_valid": jnp.asarray(row_valid),
            "key_values": key_values,
            "n_groups": n_groups,
        }

    def _pallas_masked_rows_step(self):
        """Jitted once per stage (a per-call closure would retrace every
        query)."""
        if getattr(self, "_pallas_step", None) is not None:
            return self._pallas_step
        import jax
        import jax.numpy as jnp

        filter_masks = self.filter_masks
        value_fns = self.value_fns

        @jax.jit
        def masked_rows(cols, aux, row_valid):
            cols = widen_cols(cols)
            mask = row_valid
            for fm in filter_masks:
                mask = jnp.logical_and(mask, fm(cols, aux))
            maskf = mask.astype(jnp.float32)
            rows = [maskf]
            for vf in value_fns:
                if vf is None:
                    continue
                v = jnp.broadcast_to(vf.fn(cols, aux), mask.shape)
                rows.append(v.astype(jnp.float32) * maskf)
            return jnp.stack(rows)

        self._pallas_step = masked_rows
        return masked_rows

    def _run_pallas_sorted(self, ent: dict, aux) -> pa.Table:
        from ballista_tpu.ops.pallas_kernels import sorted_grouped_sum
        from ballista_tpu.ops.runtime import readback

        vals = self._pallas_masked_rows_step()(ent["cols"], aux, ent["row_valid"])
        out = readback(
            sorted_grouped_sum(ent["codes"], vals, ent["n_groups"])
        ).astype(np.float64)
        counts = out[0]
        outputs: List[np.ndarray] = []
        vi = 1
        for a in self.aggs:
            if a.fn == "count":
                outputs.append(counts)
                continue
            outputs.append(out[vi])
            vi += 1
            if a.fn == "avg":
                outputs.append(counts)
        return self._assemble_partial(
            outputs, counts, ent["key_values"], ent["n_groups"]
        )

    def run(self, partition: int, ctx, keyset=None) -> Optional[pa.Table]:
        """The partition's partial states. `keyset` (HashAggregateExec.execute)
        lets the sorted engine read back only the groups whose key it holds;
        the other engines return every group."""
        import jax.numpy as jnp

        use_cache = ctx.config.device_cache() and self.cacheable
        if not self.cacheable and not ctx.config.tpu_fuse_volatile():
            # aggregating over a re-executed source (e.g. a host join) pays
            # encode+transfer per query with no residency payoff — not
            # measured on a directly attached chip, so it stays opt-in
            raise UnsupportedOnDevice("volatile row source (enable ballista.tpu.fuse_volatile_sources)")
        prepared = self._device_cache.get(partition) if use_cache else None
        if prepared is not None:
            from ballista_tpu.ops.runtime import touch_residency

            touch_residency(self, partition)  # LRU recency for eviction
        if prepared is None:
            with self._prepare_lock:
                prepared = self._device_cache.get(partition) if use_cache else None
                freshly_prepared = False
                if prepared is None:
                    # persisted sorted layout first: a hit skips the whole
                    # scan+rank pass (the unrolled path would decode parquet
                    # before discovering the cardinality it declines on)
                    prepared = self._load_layout(partition, ctx)
                    freshly_prepared = prepared is not None
                if prepared is None:
                    if self.topk is not None:
                        # the fused top-k epilogue needs ONE device call
                        # over the whole partition (per-batch group codes
                        # are batch-local); the sorted prepare itself
                        # decides per partition whether fusion is live
                        # (one-chunk cover) or the normal path runs
                        prepared = self._prepare_partition_sorted(partition, ctx)
                    else:
                        try:
                            prepared = {"kind": "batches",
                                        "entries": self._prepare_partition(partition, ctx)}
                        except TooManyGroups:
                            prepared = self._prepare_partition_sorted(partition, ctx)
                    freshly_prepared = True
                if freshly_prepared and use_cache:
                    from ballista_tpu.ops.runtime import (
                        entry_device_bytes,
                        reserve_and_pin,
                    )

                    # pin only within the HBM budget; partitions beyond
                    # it stream per query (how SF=100 fits a 16GB chip).
                    # Disk-loaded entries pin too — an unpinned hit would
                    # re-read the multi-GB entry per query AND hold device
                    # arrays the residency ledger never accounted for.
                    reserve_and_pin(
                        self,
                        partition,
                        prepared,
                        self._device_cache,
                        entry_device_bytes(prepared),
                        ctx.config.tpu_hbm_budget(),
                    )

        aux = [jnp.asarray(a) for a in self.compiler.build_aux()]
        if prepared["kind"] == "empty":
            return self.partial_schema.empty_table()
        if prepared["kind"] == "sorted":
            if self._topk_eligible(prepared):
                out = self._run_topk(prepared, aux)
                if out is not None:
                    return out  # None: boundary tie -> full readback below
            return self._run_sorted(prepared, aux, keyset)
        if prepared["kind"] == "pallas_sorted":
            return self._run_pallas_sorted(prepared, aux)

        # dispatch all batches asynchronously, then materialize same-shaped
        # outputs in one stacked d2h transfer — per-batch fetches would pay
        # the d2h latency k times (runtime.fetch_arrays)
        from ballista_tpu.ops.runtime import fetch_arrays, record_readback

        pending = []
        for ent in prepared["entries"]:
            stacked_dev = self._step(
                ent["seg_bucket"], ent["cols"], aux, ent["codes"], ent["row_valid"]
            )
            pending.append((stacked_dev, ent))
        fetched = fetch_arrays([dev for dev, _ in pending])
        record_readback(
            sum(f.shape[-1] for f in fetched), sum(f.nbytes for f in fetched)
        )
        with tracing.span("runtime.to_arrow", engine="unrolled") as sp:
            return groups_out(sp, self._batches_to_table(fetched, pending))

    def _batches_to_table(self, fetched, pending) -> pa.Table:
        partial_tables: List[pa.Table] = []
        for stacked_np, (_, ent) in zip(fetched, pending):
            rows = self._decode_stacked(stacked_np)
            n_groups = ent["n_groups"]
            counts_np = rows[0][:n_groups]
            outputs = [o[:n_groups] for o in self._state_outputs(rows)]
            t = self._assemble_partial(outputs, counts_np, ent["key_values"], n_groups)
            if t.num_rows:
                partial_tables.append(t)
        if not partial_tables:
            return self.partial_schema.empty_table()
        return pa.concat_tables(partial_tables)

    def _decode_stacked(self, stacked: np.ndarray) -> List[np.ndarray]:
        """Undo _stack_rows' int32 hi/lo packing."""
        return decode_packed_rows(stacked, self._int_rows)

    def _state_outputs(self, rows: List[np.ndarray]) -> List[np.ndarray]:
        """Decoded logical rows -> one output column per partial-state
        FIELD (spec-driven; bijected min/max states invert through
        ops/floatbits.py, f64 pairs recombining their planes first). Empty
        groups still carry key-space sentinel fills here — every caller
        masks them with counts==0 before assembly."""
        from ballista_tpu.ops import floatbits

        outs: List[np.ndarray] = []
        for row, kind, _fold in self._state_specs:
            if kind == "f64bits":
                outs.append(
                    floatbits.i64_to_f64(
                        floatbits.planes_to_i64(rows[row], rows[row + 1])
                    )
                )
            elif kind == "f32bits":
                outs.append(
                    floatbits.i32_to_f32(rows[row].astype(np.int32)).astype(
                        np.float64
                    )
                )
            else:
                outs.append(rows[row])
        return outs

    def _fold_state_rows(self, layout, rows: List[np.ndarray]) -> List[np.ndarray]:
        """Fold decoded per-chunk partial rows to per-group state columns.
        f64-bijected pairs recombine into int64 keys BEFORE the fold —
        lexicographic (hi, lo) min/max IS int64 key min/max, and reduceat
        over int keys is exact — then invert to the bit-exact float."""
        from ballista_tpu.ops import floatbits

        folds = {"sum": layout.fold_sum, "min": layout.fold_min,
                 "max": layout.fold_max}
        outs: List[np.ndarray] = []
        for row, kind, fold in self._state_specs:
            if kind == "f64bits":
                keys = floatbits.planes_to_i64(rows[row], rows[row + 1])
                outs.append(floatbits.i64_to_f64(folds[fold](keys)))
            elif kind == "f32bits":
                k32 = folds[fold](rows[row]).astype(np.int32)
                outs.append(floatbits.i32_to_f32(k32).astype(np.float64))
            else:
                outs.append(folds[fold](rows[row]))
        return outs

    def _run_sorted(self, ent: dict, aux, keyset=None) -> pa.Table:
        from ballista_tpu.ops.runtime import copy_out, record_readback

        layout = ent["layout"]
        stacked = self._sorted_step(layout.L1, ent["cols"], aux, ent["clen"])
        if keyset is not None:
            # the membership is host work beside the launched step
            groups = self._keyset_groups(ent, keyset)
            if groups is not None:
                return self._run_keyset(ent, stacked, groups)
            tracing.incr("device.keyset_groups_kept", ent["n_groups"])
            tracing.incr("device.keyset_groups_dropped", 0)
        stacked = copy_out(stacked)
        record_readback(stacked.shape[-1], stacked.nbytes)
        with tracing.span("runtime.to_arrow", engine="sorted") as sp:
            rows = self._decode_stacked(stacked)
            counts = layout.fold_sum(rows[0])
            outputs = self._fold_state_rows(layout, rows)
            return groups_out(sp, self._assemble_partial(
                outputs, counts, ent["key_values"], ent["n_groups"]
            ))

    # -- key-set select (a SEMI join's keys, distributed/planner.py) ------
    def _keyset_groups(self, ent: dict, keyset) -> Optional[np.ndarray]:
        """Ascending ids of the entry's groups whose key is a row of
        `keyset`, or None where the full readback runs: keys that do not
        pack to one int64, or a set that keeps more than half the groups.
        The packed, sorted group keys are built once and kept in the entry."""
        index = ent.get("key_index")
        if index is None:
            index = ent["key_index"] = GroupKeyIndex.build(ent["key_values"]) or False
        if index is False:
            return None
        groups = index.members(keyset)
        if groups is None or 2 * len(groups) > ent["n_groups"]:
            return None
        return groups

    def _run_keyset(self, ent: dict, stacked, groups: np.ndarray) -> pa.Table:
        """Read back only the chunks of `groups`: a take on the device out of
        the sorted step's output, then the same decode, fold and assembly
        over those chunks alone. No reduction changes, so each kept group's
        states are bit for bit the full readback's."""
        import jax.numpy as jnp

        from ballista_tpu.ops.runtime import bucket_rows, copy_out, record_readback

        chunks, sub = ent["layout"].subset(groups)
        n = len(chunks)
        idx = np.zeros(bucket_rows(n), dtype=np.int32)
        idx[:n] = chunks
        if self._keyset_take is None:
            from ballista_tpu.ops import aotcache

            self._keyset_take = aotcache.wrap_step(
                self, "keyset_take",
                lambda stacked, idx: jnp.take(stacked, idx, axis=1),
                static_argnums=(),
            )
        packed = copy_out(self._keyset_take(stacked, jnp.asarray(idx)))
        record_readback(n, packed.nbytes)
        with tracing.span("runtime.to_arrow", engine="sorted", keyset=len(groups)) as sp:
            rows = self._decode_stacked(packed[:, :n])
            counts = sub.fold_sum(rows[0])
            outputs = self._fold_state_rows(sub, rows)
            take = pa.array(groups)
            key_values = [
                (kv if isinstance(kv, (pa.Array, pa.ChunkedArray)) else pa.array(kv)).take(take)
                for kv in ent["key_values"]
            ]
            out = groups_out(sp, self._assemble_partial(outputs, counts, key_values, len(groups)))
        tracing.incr("device.keyset_groups_kept", len(groups))
        tracing.incr("device.keyset_groups_dropped", ent["n_groups"] - len(groups))
        return out

    # -- fused Sort+Limit epilogue (planner _topk_pushdown) -------------
    def _topk_eligible(self, ent: dict) -> bool:
        """Fusion is live for a partition when the selection can actually
        exclude groups AND the device can produce exact per-group states:
        either the layout carries the one-chunk cover (chunk partials ARE
        the group states, bit-identical to the full readback) or the fold
        variant runs (in-program chunk->group segment fold for skewed
        layouts, e.g. q10's dominant unmatched-row group). The fold variant
        sums int32 in-program where the host fold widens to int64, so
        int-exact SUM aggregates disable it — the normal full readback runs
        instead, same entry, identical values."""
        if (
            self.topk is None
            or ent.get("layout") is None
            or ent["n_groups"] <= self.topk["k"]
        ):
            return False
        if ent["layout"].one_chunk_per_group:
            return True
        return not any(
            ix and a.fn in ("sum", "avg")
            for a, ix in zip(self.aggs, self.int_exact)
        )

    def _build_topk_step(self, fold: bool):
        from ballista_tpu.ops import aotcache

        if fold:
            # (L1, cols, aux, clen, G, owner): G is the segment count
            return aotcache.wrap_step(
                self, "topk_fold", self._topk_core(True), static_argnums=(0, 4)
            )
        return aotcache.wrap_step(
            self, "topk", self._topk_core(False), static_argnums=(0,)
        )

    def _topk_core(self, fold: bool):
        """Device Sort+Limit epilogue composed over the sorted core: lower
        every sort key to int32 lanes whose signed order equals the key
        order (exact int states as-is, f32 scores through the floatbits
        bijection, f64-bijected states as their hi/lo plane pair; bitwise
        NOT flips descending keys without overflow), lexicographically sort
        (validity, key lanes..., group index) and gather the k best columns
        of the packed state stack. The trailing group-index lane makes tie
        order identical to the host's stable sort over the group-ordered
        aggregate output. Readback: [R_packed + E, k] instead of
        [R_packed, G] — E carries the k-th and (k+1)-th lane values (the
        boundary-tie probe) and the selected group indices, all as exact
        f32 halves like _stack_rows.

        fold=False: the one-chunk cover — chunk partials are already group
        states. fold=True: chunk partials segment-fold to group states
        in-program first (sum/min/max per _state_specs; f64-bijected pairs
        fold lexicographically — lo competes only among chunks holding the
        group's hi extreme). min/max folds match the host reduceat exactly;
        f32 sums regroup the accumulation (documented device tolerance);
        int-exact sums never take this variant (_topk_eligible)."""
        import jax
        import jax.numpy as jnp

        from ballista_tpu.ops.floatbits import jnp_f32_to_i32

        core = self._sorted_core()
        pos = packed_positions(self._int_rows)
        int_rows = self._int_rows
        specs = self._state_specs
        k = self.topk["k"]
        keyspecs = self.topk["keys"]

        def split16(x):
            return (x >> 16).astype(jnp.float32), (x & 0xFFFF).astype(jnp.float32)

        def select(G, counts, row_of, gstack):
            """Shared tail over per-group states: row_of(r) is the DECODED
            logical row r ([G] int32, or f32 for num rows); gstack the
            packed [R_packed, G] stack the readback decodes."""
            # validity leads the lexicographic key: empty groups (dropped
            # by the unfused assembly) must never displace a real group
            lanes = [jnp.where(counts > 0, 0, 1).astype(jnp.int32)]
            for row, kind, desc in keyspecs:
                if kind == "num":
                    kv = [jnp_f32_to_i32(row_of(row))]
                elif kind == "f64bits":
                    kv = [row_of(row), row_of(row + 1)]
                else:  # "int" / "f32bits": exact int32 state
                    kv = [row_of(row)]
                lanes.extend(~v if desc else v for v in kv)
            iota = jnp.arange(G, dtype=jnp.int32)
            srt = jax.lax.sort(tuple(lanes) + (iota,), num_keys=len(lanes) + 1)
            sel_idx = srt[-1][:k]
            sel = jnp.take(gstack, sel_idx, axis=1)
            extra = []
            for lane_sorted in srt[:-1]:
                for v in (lane_sorted[k - 1], lane_sorted[k]):
                    hi, lo = split16(v)
                    extra.append(jnp.full((k,), hi, jnp.float32))
                    extra.append(jnp.full((k,), lo, jnp.float32))
            ih, il = split16(sel_idx)
            extra.extend([ih, il])
            return jnp.concatenate([sel, jnp.stack(extra)])

        if not fold:

            def tstep(L1, cols, aux, clen):
                stacked = core(L1, cols, aux, clen)  # [R_packed, G]
                G = stacked.shape[1]

                def row_of(row):
                    p = pos[row]
                    if int_rows[row]:
                        return jnp_unpack_i32(stacked[p], stacked[p + 1])
                    return stacked[p]

                return select(G, row_of(0), row_of, stacked)

            return tstep

        seg = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}

        def tstep_fold(L1, cols, aux, clen, G, owner):
            stacked = core(L1, cols, aux, clen)  # [R_packed, V] chunk partials

            def chunk_row(row):
                p = pos[row]
                if int_rows[row]:
                    return jnp_unpack_i32(stacked[p], stacked[p + 1])
                return stacked[p]

            def red(fop, v):
                return seg[fop](v, owner, num_segments=G,
                                indices_are_sorted=True)

            logical = {0: red("sum", chunk_row(0))}  # counts
            for row, kind, fop in specs:
                if kind == "f64bits":
                    hi, lo = chunk_row(row), chunk_row(row + 1)
                    h = red(fop, hi)
                    fill = jnp.int32(
                        _INT32_MAX if fop == "min" else -_INT32_MAX - 1
                    )
                    l = red(fop, jnp.where(hi == jnp.take(h, owner), lo, fill))
                    logical[row], logical[row + 1] = h, l
                else:
                    logical[row] = red(fop, chunk_row(row))
            packed = []
            for row, is_int in enumerate(int_rows):
                if is_int:
                    packed.extend(split16(logical[row]))
                else:
                    packed.append(logical[row])
            return select(G, logical[0], lambda r: logical[r],
                          jnp.stack(packed))

        return tstep_fold

    def _run_topk(self, ent: dict, aux) -> Optional[pa.Table]:
        """Fused-epilogue readback: k columns + boundary probe. Returns
        None (caller falls back to the full readback, same entry, same
        values) when un-fused trailing sort keys exist AND the k-th and
        (k+1)-th groups tie on every fused lane — the only case where the
        device selection could exclude a group the host order admits."""
        from ballista_tpu.ops.runtime import copy_out, record_readback

        import jax.numpy as jnp

        layout = ent["layout"]
        if layout.one_chunk_per_group:
            if self._topk_step is None:
                self._topk_step = self._build_topk_step(fold=False)
            packed = copy_out(
                self._topk_step(layout.L1, ent["cols"], aux, ent["clen"])
            )
        else:
            # skewed cover: fold chunk partials to group states in-program
            if self._topk_fold_step is None:
                self._topk_fold_step = self._build_topk_step(fold=True)
            owner = ent.get("owner_dev")
            if owner is None:
                owner = ent["owner_dev"] = jnp.asarray(
                    layout.owner.astype(np.int32)
                )
            packed = copy_out(
                self._topk_fold_step(layout.L1, ent["cols"], aux, ent["clen"],
                                     ent["n_groups"], owner)
            )
        record_readback(packed.shape[-1], packed.nbytes)
        with tracing.span("runtime.to_arrow", engine="topk") as sp:
            return groups_out(sp, self._topk_to_table(ent, packed))

    def _topk_to_table(self, ent: dict, packed: np.ndarray) -> Optional[pa.Table]:
        spec = self.topk
        nl = 1 + spec["n_lanes"]
        E = 4 * nl + 2
        sel, tail = packed[:-E], packed[-E:]
        lasts, bounds = [], []
        for i in range(nl):
            b = 4 * i
            lasts.append(int(tail[b][0]) * 65536 + int(tail[b + 1][0]))
            bounds.append(int(tail[b + 2][0]) * 65536 + int(tail[b + 3][0]))
        if not spec["covered"] and lasts == bounds and lasts[0] == 0:
            return None  # boundary tie under un-fused tie-breakers
        idx = tail[-2].astype(np.int64) * 65536 + tail[-1].astype(np.int64)
        order = np.argsort(idx, kind="stable")
        idx = idx[order]
        rows = [r[order] for r in self._decode_stacked(sel)]
        counts = rows[0]
        outputs = self._state_outputs(rows)
        take = pa.array(idx)
        key_values = [
            (kv if isinstance(kv, (pa.Array, pa.ChunkedArray)) else pa.array(kv)).take(take)
            for kv in ent["key_values"]
        ]
        return self._assemble_partial(outputs, counts, key_values, len(idx))

    def _assemble_partial(
        self,
        outputs: List[np.ndarray],
        counts: np.ndarray,
        key_values: List[pa.Array],
        n_groups: int,
    ) -> pa.Table:
        """Build a partial-state Arrow table for one batch's groups."""
        arrays: List[pa.Array] = []
        fields = list(self.partial_schema)
        # group key columns
        if self.group_exprs:
            for kv, f in zip(key_values, fields[: len(key_values)]):
                arr = kv if isinstance(kv, pa.Array) else pa.array(kv)
                if arr.type != f.type:
                    arr = pc.cast(arr, f.type)
                arrays.append(arr)
        # aggregate state columns
        oi = 0
        col_pos = len(key_values)
        nonempty = counts > 0
        for a in self.aggs:
            for _f in a.state_fields():
                f = fields[col_pos]
                raw = outputs[oi]
                # groups with no surviving rows carry sentinel fills in
                # min/max rows; null them out so the merge ignores them
                arrays.append(state_column(a, raw, f.type, ~nonempty))
                oi += 1
                col_pos += 1
        # drop groups where every row was filtered out (counts == 0) to match
        # host-partial semantics (those groups never appear)
        t = pa.table(arrays, schema=self.partial_schema)
        if not nonempty.all():
            t = t.filter(pa.array(nonempty))
        return t
