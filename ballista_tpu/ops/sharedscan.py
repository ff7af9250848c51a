"""Shared-scan multi-query execution (ISSUE 13): one upload, one launch,
N queries.

Concurrent DISTINCT queries routinely scan the SAME tables (the multi-
tenant bench's dashboard mix), yet each solo fused-aggregate stage pays its
own parquet decode, its own h2d upload, and its own device program — the
dominant per-query cost at serving scale. The scheduler groups compatible
co-pending stages into one batched task (scheduler/state.py
form_shared_batch); this module is the executor half: it resolves each
member's fused stage (ops/kernels.py resolve_stage), verifies REAL
compatibility, reads the UNION of the members' pruned scan schemas once,
and runs the group as ONE device launch over ONE resident upload — every
member's epilogue (filters + aggregate emission) traced into a single
combined program over the shared scanned tensors. Each member's readback
decodes through its own stage's machinery, so the spliced table is EXACTLY
what that member's solo stage.run would have produced — bit identity to
solo execution is the invariant at every decision point, and any doubt
(string-coded device columns, cardinality past the unrolled ceiling,
un-lowerable columns, budget overruns, plain exceptions) degrades the
member — or the whole group — to solo execution, never to a different
answer.

Why the union read is solo-identical: a member's solo scan reads its
pruned column list from the same parquet files, combine_chunks()es, and
slices into ctx.batch_size row batches — row boundaries depend only on the
row count and the batch size, never on which columns ride along. Selecting
the member's schema columns by name out of the union batch therefore
yields byte-identical member batches, and every shared column is lowered
by the same column_to_numpy/_lower_planes the member's solo prepare uses.

Two launch shapes, one invariant: members whose packed output rows are all
ORDER-INSENSITIVE (int sums, counts, min/max, float-bits min/max) fuse into
the combined one-launch program — integer/lattice folds are exact under any
reassociation, so the combined graph cannot change them. Members with
float-arithmetic sums (f32 sum/avg) run their OWN solo-compiled step over
the same shared upload: XLA may reassociate an f32 reduction differently
inside a different program context, and only the member's own executable on
identical inputs reproduces its solo bits. Cold compositions also take the
own-step path while the combined program warms in the background, so a
serving wave never stalls behind a multi-second trace.

Compatibility (the executor is authoritative; the scheduler's signature is
a cheap heuristic):
- plain FusedAggregateStage (no top-k epilogue, no fact-agg derivations)
  over a Parquet scan — stable content identity, shared decodable read;
- identical (files, mtimes, chunk cover, batch size, HBM budget): members
  must read byte-identical row streams;
- no dictionary-coded (string) device columns: each stage grows its own
  string dictionary, so shared int-code tiles would mean different strings
  to different members (string GROUP BY keys stay host-side and batch
  fine);
- every batch's group count within the unrolled path's MAX_GROUPS ceiling
  (the sorted layout is per-member by construction — its tiles ARE the
  member's group order).

The combined program is AOT-cached like any stage step (ops/aotcache.py),
keyed on the member set's stable stage identities, so repeated batch
compositions skip the trace/compile.
"""

from __future__ import annotations

import hashlib
import logging
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from ballista_tpu.ops.runtime import UnsupportedOnDevice
from ballista_tpu.utils import tracing
from ballista_tpu.utils.locks import make_lock

log = logging.getLogger("ballista.sharedscan")

# order for widening int narrow-choice priors across members
_INT_ORDER = {"int8": 0, "int16": 1, "int32": 2}


class SharedResults:
    """Per-batched-task registry of precomputed member tables, keyed on the
    aggregate node OBJECT inside the member's (deserialized, soon to be
    executed) plan tree plus the partition — so the splice in
    kernels.hash_aggregate can only ever hit the exact node this group ran.
    Node references are pinned for the registry's lifetime, so ids are
    never recycled. take() consumes the entry."""

    def __init__(self) -> None:
        self._tables: Dict[Tuple[int, int], pa.Table] = {}
        self._pins: List[object] = []

    def put(self, node, partition: int, table: pa.Table) -> None:
        self._pins.append(node)
        self._tables[(id(node), partition)] = table

    def take(self, node, partition: int) -> Optional[pa.Table]:
        return self._tables.pop((id(node), partition), None)

    def drop(self, node, partition: int) -> None:
        self._tables.pop((id(node), partition), None)

    def __len__(self) -> int:
        return len(self._tables)


class _Member:
    """One batch member: its plan's aggregate node, resolved fused stage,
    stable identity, partition, task context, and scan-compatibility key.

    `exact` marks stages whose every packed output row is order-insensitive
    (int sums, counts, min/max, float-bits min/max): ONLY those may fuse
    into the combined one-launch program — XLA may reassociate an f32 SUM
    differently inside a different program context, so a float-arithmetic
    sum/avg is bit-identical to solo only under the member's OWN compiled
    step (which still runs over the shared upload)."""

    __slots__ = ("node", "stage", "stable", "partition", "ctx", "group_key",
                 "cover", "exact")

    def __init__(self, node, stage, stable, partition, ctx, group_key,
                 cover) -> None:
        self.node = node
        self.stage = stage
        self.stable = stable
        self.partition = partition
        self.ctx = ctx
        self.group_key = group_key
        self.cover = cover
        self.exact = not any(
            (not ix) and a.fn in ("sum", "avg")
            for a, ix in zip(stage.aggs, stage.int_exact)
        )


def _find_aggregate(plan):
    """The batchable aggregate node under a stage plan: the FIRST
    HashAggregateExec down the single-child operator spine (stage plans put
    sort/projection/coalesce epilogues ABOVE the aggregate — they consume
    its output per member and never affect what the aggregate computes).
    None when the spine forks or ends before an aggregate, or the mode is
    FINAL (final aggregates read shuffles, not scans)."""
    from ballista_tpu.physical.aggregate import AggregateMode, HashAggregateExec

    node = plan
    while not isinstance(node, HashAggregateExec):
        kids = node.children()
        if len(kids) != 1:
            return None
        node = kids[0]
    if node.mode in (AggregateMode.PARTIAL, AggregateMode.SINGLE):
        return node
    return None


def _member_key_map(stage) -> Dict[object, tuple]:
    """Member cols-dict key -> shared column key. The member's compiled
    cores read columns by PRUNED-schema index (plus the float-bits plane
    keys derived from it); the shared staging is keyed by column NAME so
    members with different pruned schemas share one lowered array."""
    from ballista_tpu.ops.stage import plane_keys

    schema = stage.scan_schema
    out: Dict[object, tuple] = {}
    for idx in stage.compiler.used_columns:
        out[idx] = ("col", schema.field(idx).name)
    for idx, width in stage._bit_planes.items():
        hk, lk = plane_keys(idx)
        out[hk] = ("hi", schema.field(idx).name)
        if width == "f64":
            out[lk] = ("lo", schema.field(idx).name)
    return out


def _member_info(plan, partition: int, ctx) -> Optional[_Member]:
    """Resolve one member's stage and compatibility facts, or None when the
    member cannot ride a shared-scan group (it then executes solo through
    the untouched normal path)."""
    import os

    from ballista_tpu.ops import kernels
    from ballista_tpu.ops.stage import FusedAggregateStage
    from ballista_tpu.physical.scan import ParquetScanExec

    if ctx.backend != "tpu":
        return None
    node = _find_aggregate(plan)
    if node is None:
        return None
    try:
        stage, _key, stable, _units = kernels.resolve_stage(node, ctx)
    except Exception:
        log.debug("shared-scan stage resolution failed", exc_info=True)
        return None
    # plain fused stages only: fact-agg subclasses derive columns and run
    # epilogues this group launcher does not model, and a live top-k spec
    # routes the stage through the sorted layout
    if stage is False or type(stage) is not FusedAggregateStage:
        return None
    if stage.topk is not None or stage.derive_columns:
        return None
    scan = stage.scan
    if not isinstance(scan, ParquetScanExec):
        return None
    if ctx.config.device_cache() and stage._device_cache.get(partition) is not None:
        # the member's columns are already RESIDENT: its solo run skips the
        # scan and the upload entirely, which beats re-scanning it into a
        # batch — shared-scan exists to amortize COLD scans across queries,
        # not to undo the residency tier
        return None
    # persisted-layout-warm members are shared-scan-ELIGIBLE since batch
    # size folded into the stage/persist key (ISSUE 15 satellite, PR 13
    # residue): a warm layout entry is always at THIS dispatch's batch
    # granularity, so the shared batch stream is row-identical to the
    # member's layout-cache solo stream and f32 partials fold identically.
    # (The group key below already carries ctx.batch_size, so members of
    # different granularities never group.)
    if stage.dicts.dicts:
        return None  # string-coded device columns: per-stage dictionaries
    schema = stage.scan_schema
    for idx in stage.compiler.used_columns:
        t = schema.field(idx).type
        if pa.types.is_string(t) or pa.types.is_large_string(t):
            return None
    files = tuple(getattr(scan.source, "files", ()) or ())
    if not files:
        return None
    try:
        mtimes = tuple(str(os.path.getmtime(f)) for f in files)
    except OSError:
        return None
    total = scan.output_partitioning().partition_count()
    stride = stage.scan_stride
    # the chunk cover: exactly which scan partitions this member's task
    # reads (ops/stage.py _scan_batches) — members must match it so the
    # shared batch stream is row-identical to each member's solo stream
    cover = tuple(range(partition, total, stride)) if stride else (partition,)
    if any(p >= len(files) for p in cover):
        return None  # out-of-range partition: let the solo path surface it
    group_key = (
        files, mtimes, cover, ctx.batch_size, ctx.config.tpu_hbm_budget(),
    )
    return _Member(node, stage, stable, partition, ctx, group_key, cover)


def precompute(items, max_batch: int = 8) -> SharedResults:
    """Group compatible members and run each group as one shared-scan
    launch. `items` are (stage plan, partition, TaskContext) triples of a
    batched task's members. Returns the per-member precomputed tables;
    members absent from the result simply execute solo — this function
    NEVER fails a member (exceptions degrade the group and are logged)."""
    res = SharedResults()
    if len(items) < 2:
        return res
    groups: Dict[tuple, List[_Member]] = {}
    for plan, partition, ctx in items:
        m = _member_info(plan, partition, ctx)
        if m is None:
            tracing.incr("shared_scan.member_ineligible")
            continue
        groups.setdefault(m.group_key, []).append(m)
    for g in groups.values():
        # canonical member order: the combined program is cached (and AOT-
        # persisted) per ordered member-set composition, and dispatch order
        # varies wave to wave — sorting by stable identity makes repeated
        # compositions hit the same compiled program
        g.sort(key=lambda m: m.stable)
        for lo in range(0, len(g), max(2, max_batch)):
            chunk = g[lo:lo + max(2, max_batch)]
            if len(chunk) < 2:
                continue
            try:
                _run_group(chunk, res)
            except Exception:
                log.warning(
                    "shared-scan group degraded to solo execution",
                    exc_info=True,
                )
                tracing.incr("shared_scan.batch_degraded")
                for m in chunk:
                    res.drop(m.node, m.partition)
    return res


def _codes_fingerprint(stage) -> Optional[tuple]:
    """Sharing key for host-side group ranking: members whose group keys
    are the same plain scan COLUMNS rank identical codes from the same
    batch (dense ranking is a pure function of the evaluated key arrays),
    so one member's _group_codes output serves them all. Computed group
    keys return None — those members rank their own."""
    from ballista_tpu.physical import expr as px

    names = []
    for e, _name in stage.group_exprs:
        if not isinstance(e, px.ColumnExpr):
            return None
        names.append(stage.scan_schema.field(e.index).name)
    return tuple(names)


def _merge_prior(a, b):
    """Widest of two narrow-choice priors (never downgrade a member's
    compiled width; the choice only affects residency dtype, never values)."""
    if a is None:
        return b
    if b is None:
        return a
    if a in _INT_ORDER and b in _INT_ORDER:
        return a if _INT_ORDER[a] >= _INT_ORDER[b] else b
    if "wide" in (a, b):
        return "wide"
    return a


def _scan_union_batches(members: List[_Member]):
    """Read the members' shared chunk cover ONCE with the UNION of their
    pruned scan schemas (strings as dictionary columns, exactly like
    FusedAggregateStage._scan_batches' parquet fast path), yielding
    ctx.batch_size row batches. Row boundaries depend only on row count
    and batch size, so each member's name-selected view of every batch is
    identical to its solo scan stream."""
    import pyarrow.parquet as pq

    names: List[str] = []
    strings: List[str] = []
    for m in members:
        for f in m.stage.scan_schema:
            if f.name not in names:
                names.append(f.name)
                if pa.types.is_string(f.type) or pa.types.is_large_string(f.type):
                    strings.append(f.name)
    files = members[0].stage.scan.source.files
    batch_size = members[0].ctx.batch_size
    for p in members[0].cover:
        table = pq.read_table(
            files[p], columns=names, read_dictionary=strings
        ).combine_chunks()
        yield from table.to_batches(max_chunksize=batch_size)


def _run_group(members: List[_Member], res: SharedResults) -> None:
    """Shared prepare + combined launch for one compatible group. Stage
    state (narrow choices, compiled cores) is touched under every member
    stage's prepare lock, acquired in id order (two identical queries can
    resolve to the SAME stage object — locks dedupe by identity)."""
    locks = {}
    for m in members:
        locks[id(m.stage._prepare_lock)] = m.stage._prepare_lock
    ordered = [locks[k] for k in sorted(locks)]
    for lk in ordered:
        lk.acquire()
    try:
        _run_group_locked(members, res)
    finally:
        for lk in reversed(ordered):
            lk.release()


def _run_group_locked(members: List[_Member], res: SharedResults) -> None:
    import jax.numpy as jnp

    from ballista_tpu.ops.runtime import (
        bucket_rows,
        column_to_numpy,
        make_headroom,
        narrow_column,
        pad_to,
        readback,
    )
    from ballista_tpu.ops.stage import MAX_GROUPS, FusedAggregateStage

    budget = min(m.ctx.config.tpu_hbm_budget() for m in members)
    live = list(members)

    def degrade(m: _Member) -> None:
        if m in live:
            live.remove(m)
            tracing.incr("shared_scan.member_degraded")

    # negotiated narrow choices for the SHARED staged columns (keyed by
    # shared column key): start from the widest of the members' existing
    # priors (a member that already compiled a width must never see a
    # narrower batch), then carry each batch's choice forward exactly like
    # a solo prepare does
    keymaps = {id(m): _member_key_map(m.stage) for m in members}
    shared_choice: Dict[tuple, object] = {}
    for m in members:
        for mkey, skey in keymaps[id(m)].items():
            shared_choice[skey] = _merge_prior(
                shared_choice.get(skey), m.stage._narrow_choice.get(mkey)
            )
    for m in list(members):
        if not m.exact and any(
            shared_choice.get(skey) != m.stage._narrow_choice.get(mkey)
            for mkey, skey in keymaps[id(m)].items()
        ):
            # an inexact member's own step must compile the EXACT dtype
            # graph its solo run would (f32 sums are reassociation-
            # sensitive): any starting prior that differs from the
            # member's OWN — another member's wider history included,
            # even against a fresh None — breaks that guarantee, so the
            # member runs solo. All-fresh and all-warm-equal groups (the
            # common cases) pass untouched.
            members.remove(m)
            live.remove(m)
            tracing.incr("shared_scan.member_degraded")
    if len(live) < 2:
        tracing.incr("shared_scan.batch_degraded")
        return

    batches: List[dict] = []
    total_bytes = 0
    for batch in _scan_union_batches(members):
        n = batch.num_rows
        if not n:
            continue
        bucket = bucket_rows(n)
        # per-member group ranking over the member's name-selected VIEW of
        # the shared batch — exactly the member's own host-side work, so
        # codes/keys are solo-identical. Members whose group keys are the
        # same plain columns share ONE ranking (identical by construction:
        # the dense rank is a pure function of the evaluated key arrays).
        per: Dict[int, tuple] = {}  # id(member) -> (codes, key_values, n_groups)
        codes_cache: Dict[tuple, tuple] = {}
        for m in list(live):
            try:
                fp = _codes_fingerprint(m.stage)
                if fp is not None and fp in codes_cache:
                    codes, key_values, n_groups = codes_cache[fp]
                else:
                    view = batch.select(m.stage.scan_schema.names)
                    codes, key_values, n_groups = m.stage._group_codes(view)
                    if fp is not None:
                        codes_cache[fp] = (codes, key_values, n_groups)
            except UnsupportedOnDevice:
                degrade(m)
                continue
            if n_groups > MAX_GROUPS:
                # solo would retry on the sorted layout; that path is
                # per-member by construction — hand the member back
                degrade(m)
                continue
            if n_groups:
                per[id(m)] = (codes, key_values, n_groups)
        if len(live) < 2:
            break
        # lower the UNION of live members' device columns ONCE, keyed by
        # shared column key (name-based: members prune differently)
        needed: Dict[tuple, tuple] = {}  # skey -> ("col", name, dtype) | ("plane", name, width)
        for m in live:
            schema = m.stage.scan_schema
            for idx, dtype in m.stage.compiler.used_columns.items():
                name = schema.field(idx).name
                needed[("col", name)] = ("col", name, dtype)
            for idx, width in m.stage._bit_planes.items():
                name = schema.field(idx).name
                needed[("plane", name)] = ("plane", name, width)
        shared_np: Dict[tuple, np.ndarray] = {}
        bad: set = set()  # shared keys that failed to lower
        for spec in needed.values():
            kind, name = spec[0], spec[1]
            try:
                if kind == "col":
                    shared_np[("col", name)] = column_to_numpy(
                        batch.column(name), spec[2], None
                    )
                else:
                    # plane_keys(0) == (-2, -3): lower once, remap by name
                    d = FusedAggregateStage._lower_planes(
                        batch.column(name), 0, spec[2]
                    )
                    shared_np[("hi", name)] = d[-2]
                    if spec[2] == "f64":
                        shared_np[("lo", name)] = d[-3]
            except UnsupportedOnDevice:
                bad.add(("col", name) if kind == "col" else ("hi", name))
                bad.add(("lo", name))
        if bad:
            # a column that cannot lower declines the members reading it —
            # solo they would decline to the host path on the same batch
            for m in list(live):
                if any(skey in bad for skey in keymaps[id(m)].values()):
                    degrade(m)
        for m in list(live):
            if id(m) not in per:
                continue
            try:
                npview = {
                    mkey: shared_np[skey]
                    for mkey, skey in keymaps[id(m)].items()
                    if skey in shared_np
                }
                m.stage._check_int_ranges(npview, n)
            except UnsupportedOnDevice:
                degrade(m)
        if len(live) < 2:
            break
        # narrow + pad the shared tiles once; keep only columns live
        # members still read
        live_keys: set = set()
        for m in live:
            live_keys |= set(keymaps[id(m)].values())
        staged: Dict[tuple, tuple] = {}
        for skey in sorted(k for k in shared_np if k in live_keys):
            npcol = shared_np[skey]
            fill = False if npcol.dtype == np.bool_ else 0
            narrow, lut, choice = narrow_column(npcol, shared_choice.get(skey))
            shared_choice[skey] = choice
            padded = pad_to(narrow, bucket, fill)
            staged[skey] = (padded, lut, choice)
            total_bytes += padded.nbytes + (0 if lut is None else lut.nbytes)
        row_valid = np.zeros(bucket, dtype=np.bool_)
        row_valid[:n] = True
        recs = []
        for m in live:
            hit = per.get(id(m))
            if hit is None:
                continue  # no groups in this batch (solo skips it too)
            codes, key_values, n_groups = hit
            seg_bucket = bucket_rows(n_groups, 16) + 1  # +1 dump slot
            codes_pad = pad_to(codes.astype(np.int16), bucket, 0)
            total_bytes += codes_pad.nbytes
            recs.append((m, codes_pad, seg_bucket, n_groups, key_values))
        total_bytes += bucket  # shared bool row_valid
        if total_bytes > budget:
            raise UnsupportedOnDevice(
                f"shared-scan batches ({total_bytes >> 20} MiB) exceed the "
                "HBM budget"
            )
        batches.append(
            {"staged": staged, "row_valid": row_valid, "recs": recs}
        )
    if len(live) < 2:
        tracing.incr("shared_scan.batch_degraded")
        return
    tracing.incr("shared_scan.shared_groups")
    tables: Dict[int, List[pa.Table]] = {id(m): [] for m in live}
    # per-member aux is batch-independent: build + upload once per group
    # (the solo path builds it once per run too)
    aux_by_member = {
        id(m): tuple(jnp.asarray(a) for a in m.stage.compiler.build_aux())
        for m in live
    }
    for rec in batches:
        recs = [r for r in rec["recs"] if r[0] in live]
        if not recs:
            continue
        make_headroom(members[0].stage, total_bytes, budget)
        # ONE upload per shared column — through upload_array, so large
        # tiles keep the chunked double-buffered h2d tier (and its
        # cost-store h2d observations) exactly like the solo path; the
        # members' cols dicts alias the same device buffers under their
        # own pruned-schema keys
        from ballista_tpu.ops.runtime import upload_array

        dev_by_skey: Dict[tuple, object] = {}
        for skey, (padded, lut, _choice) in rec["staged"].items():
            dev = upload_array(padded)
            dev_by_skey[skey] = dev if lut is None else (dev, jnp.asarray(lut))
        rv = jnp.asarray(rec["row_valid"])
        seg_buckets = tuple(sb for _m, _cp, sb, _ng, _kv in recs)
        cols_list = tuple(
            {
                mkey: dev_by_skey[skey]
                for mkey, skey in keymaps[id(m)].items()
                if skey in dev_by_skey
            }
            for m, _cp, _sb, _ng, _kv in recs
        )
        auxs = tuple(
            aux_by_member[id(m)] for m, _cp, _sb, _ng, _kv in recs
        )
        codes_dev = tuple(
            jnp.asarray(cp) for _m, cp, _sb, _ng, _kv in recs
        )
        from ballista_tpu.ops.runtime import fetch_arrays, record_readback

        # split the wave: only EXACT members (order-insensitive packed
        # rows) may fuse into the combined one-launch program; inexact
        # members (f32 sums) run their OWN solo-compiled step over the
        # same shared upload — identical executable, identical inputs,
        # bit-identical result
        fuse_idx = [i for i, r in enumerate(recs) if r[0].exact]
        own_idx = [i for i, r in enumerate(recs) if not r[0].exact]
        blocks: List[Optional[np.ndarray]] = [None] * len(recs)
        combined_plan = None
        if len(fuse_idx) >= 2:
            stages_f = [recs[i][0].stage for i in fuse_idx]
            stables_f = [recs[i][0].stable for i in fuse_idx]
            seg_f = tuple(seg_buckets[i] for i in fuse_idx)
            args = (
                seg_f,
                tuple(cols_list[i] for i in fuse_idx),
                tuple(auxs[i] for i in fuse_idx),
                tuple(codes_dev[i] for i in fuse_idx),
                rv,
            )
            sig = (tuple(stables_f), seg_f, len(rec["row_valid"]))
            if _combined_ready(sig):
                combined_plan = (stages_f, stables_f, args)
            else:
                # tracing the combined program NOW would stall the wave
                # for seconds: warm it in the background and run this
                # wave's fusible members on their own steps too
                _warm_combined(sig, stages_f, stables_f, args)
                own_idx = own_idx + fuse_idx
                fuse_idx = []
        else:
            own_idx = own_idx + fuse_idx
            fuse_idx = []
        pending = [
            (
                i,
                recs[i][0].stage._step(
                    recs[i][2], cols_list[i], list(auxs[i]), codes_dev[i], rv
                ),
            )
            for i in sorted(own_idx)
        ]
        if combined_plan is not None:
            stages_f, stables_f, args = combined_plan
            step = _combined_step(stages_f, stables_f)
            flat = readback(step(*args))
            with _combined_lock:
                # a successful combined launch marks its signature warm —
                # under SYNC_COMPILE (tests / bench warm rounds) this is
                # what primes the ready set for later async waves
                _combined_warm.add(sig)
            tracing.incr("shared_scan.device_launches")
            tracing.incr("shared_scan.launches_saved", len(fuse_idx) - 1)
            off = 0
            for i in fuse_idx:
                m, _cp, seg_bucket, _ng, _kv = recs[i]
                r_packed = sum(2 if b else 1 for b in m.stage._int_rows)
                blocks[i] = flat[off:off + r_packed * seg_bucket].reshape(
                    r_packed, seg_bucket
                )
                off += r_packed * seg_bucket
        if pending:
            fetched = fetch_arrays([dev for _i, dev in pending])
            record_readback(
                sum(f.shape[-1] for f in fetched),
                sum(f.nbytes for f in fetched),
            )
            tracing.incr("shared_scan.device_launches", len(pending))
            if not combined_plan and len(recs) > 1:
                tracing.incr("shared_scan.warm_fallback_launches", len(pending))
            for (i, _dev), arr in zip(pending, fetched):
                blocks[i] = arr
        tracing.incr("shared_scan.uploads_saved", len(recs) - 1)
        for block, (m, _cp, seg_bucket, n_groups, key_values) in zip(
            blocks, recs
        ):
            # the member's OWN decode/assembly — the solo readback path
            rows = m.stage._decode_stacked(block)
            counts = rows[0][:n_groups]
            outputs = [o[:n_groups] for o in m.stage._state_outputs(rows)]
            t = m.stage._assemble_partial(
                outputs, counts, key_values, n_groups
            )
            if t.num_rows:
                tables[id(m)].append(t)
    # carry the negotiated narrow choices into each member's own prior map
    # so its later solo runs keep the exact dtypes this group compiled
    for m in live:
        for mkey, skey in keymaps[id(m)].items():
            if skey in shared_choice:
                m.stage._narrow_choice[mkey] = shared_choice[skey]
    for m in live:
        tabs = tables[id(m)]
        table = (
            pa.concat_tables(tabs) if tabs
            else m.stage.partial_schema.empty_table()
        )
        res.put(m.node, m.partition, table)


# combined-step cache: one AOT-wrapped program per member-set composition
# (stable stage identities, in canonical order); wrap_step handles per-shape
# signatures underneath, the XLA/AOT disk tiers amortize across processes.
# `_combined_warm` marks (composition, shape) signatures whose program has
# actually been traced/compiled (by a background warm call or an earlier
# wave), so a serving wave never stalls behind a multi-second trace; the
# in-flight set bounds concurrent background compiles to one per signature.
_combined_lock = make_lock("ops.sharedscan._combined_lock")
_combined_cache: Dict[tuple, object] = {}  # guarded-by: _combined_lock
_combined_warm: set = set()  # guarded-by: _combined_lock
_combined_warming: set = set()  # guarded-by: _combined_lock
# test hook: compile the combined program synchronously on first sight
# instead of warming it in the background (deterministic one-launch waves)
SYNC_COMPILE = False


def _combined_ready(sig: tuple) -> bool:
    if SYNC_COMPILE:
        return True
    with _combined_lock:
        return sig in _combined_warm


def _warm_combined(sig: tuple, stages: list, stables: List[str], args) -> None:
    """Trace + compile the composition's combined program OFF the serving
    path (one background thread per signature; XLA compilation releases
    the GIL). Compile-WITHOUT-execute (ISSUE 19 satellite): the warm goes
    through ``step.warm`` — ``jit(...).lower(...).compile()`` under the
    AOT wrapper — so the program never RUNS during warm-up: no output is
    allocated and the wave's shared device buffers are released as soon
    as the trace finishes, closing the transient-HBM accounting gap the
    execute-to-warm approach had. The signature is marked ready for the
    next wave once the executable exists."""
    with _combined_lock:
        if sig in _combined_warm or sig in _combined_warming:
            return
        _combined_warming.add(sig)

    def run() -> None:
        try:
            step = _combined_step(stages, stables)
            step.warm(*args)
            with _combined_lock:
                _combined_warm.add(sig)
        except Exception:
            log.warning("combined-step warm failed", exc_info=True)
        finally:
            with _combined_lock:
                _combined_warming.discard(sig)

    # non-daemon ON PURPOSE: a daemon compile thread racing interpreter
    # teardown aborts in PJRT ("terminate called without an active
    # exception"); non-daemon threads are joined BEFORE finalization, so a
    # process exits cleanly after at most one in-flight warm compile
    threading.Thread(
        target=run, daemon=False, name="sharedscan-warm"
    ).start()


class _AotOwner:
    """Minimal aot_key carrier for aotcache.wrap_step."""

    def __init__(self, aot_key: str) -> None:
        self.aot_key = aot_key


def _combined_step(stages: list, stables: List[str]):
    """One jitted program running every member's unrolled core with its own
    (seg_bucket, cols view, aux, codes) over the SHARED row_valid — the
    member sub-programs are the EXACT solo cores, so each slice of the
    concatenated f32 output is bit-identical to that member's solo stacked
    readback."""
    key = tuple(stables)
    with _combined_lock:
        fn = _combined_cache.get(key)
    if fn is not None:
        return fn
    import jax.numpy as jnp

    from ballista_tpu.ops import aotcache

    cores = [s._unrolled_core() for s in stages]

    def combined(seg_buckets, cols_list, auxs, codes_list, row_valid):
        outs = []
        for core, sb, cols, aux, codes in zip(
            cores, seg_buckets, cols_list, auxs, codes_list
        ):
            outs.append(core(sb, cols, list(aux), codes, row_valid).reshape(-1))
        return jnp.concatenate(outs)

    owner = _AotOwner(
        "sharedscan|"
        + hashlib.sha1("|".join(stables).encode()).hexdigest()
    )
    fn = aotcache.wrap_step(owner, "sharedscan", combined, static_argnums=(0,))
    with _combined_lock:
        if len(_combined_cache) > 64:
            # evicting compiled programs must also forget their READY
            # marks: a warm sig whose program was evicted would otherwise
            # retrace/recompile synchronously inside a serving wave —
            # exactly the stall the warm set exists to prevent
            _combined_cache.clear()
            _combined_warm.clear()
        return _combined_cache.setdefault(key, fn)
