"""Device kernel entry points used by operator dispatch.

hash_aggregate is the headline: whole-pipeline fusion via FusedAggregateStage.
filter_batch is a per-batch lowering used when a filter runs outside a
fusable aggregate pipeline; it returns None (host fallback) for shapes the
device path doesn't support. Projections have no stand-alone device path —
they only pay off fused into a stage (FusedAggregateStage / FactAggregateStage).

This module also owns the CANONICAL DECLINE HELPERS (`decline`,
`host_fallback`): device paths bail to host only through
`raise UnsupportedOnDevice("<reason>")` or these — never a silent
`return None` or an ad-hoc exception — so every decline carries a reason
and the kernels ladder stays enumerable. Enforced by dev/analysis's
decline-discipline pass.
"""

from __future__ import annotations

import functools
import logging
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from ballista_tpu.ops.jaxexpr import ExprCompiler
from ballista_tpu.ops.runtime import (
    ScanDictionaries,
    UnsupportedOnDevice,
    bucket_rows,
    column_to_numpy,
    pad_to,
    readback,
)
from ballista_tpu.utils.locks import make_lock


def decline(reason: str):
    """Canonical raising decline: identical to raising UnsupportedOnDevice
    directly, kept as the named entry point for the ladder."""
    raise UnsupportedOnDevice(reason)


def host_fallback(reason: str) -> None:
    """Canonical Optional-sentinel decline: logs + counts the reason, then
    returns the None the dispatcher maps to the host Arrow path. Use this
    instead of a bare `return None` inside UnsupportedOnDevice handlers so
    declines stay observable (tracing counter + debug log). Inside a
    routing probe the trace buffers with the decision counters, so a
    speculative attempt that declined leaves no phantom fallback trace."""
    from ballista_tpu.ops.runtime import record_decline_trace

    record_decline_trace("device.host_fallback", f"host fallback: {reason}")
    return None


def step_aside(reason: str) -> None:
    """Canonical MID-LADDER decline: one admission path steps aside but the
    dispatcher tries the next rung (e.g. factagg -> mapped rewrite), so the
    query may still run fully on device. Counted separately from
    host_fallback — conflating them would make the device path look
    disengaged on queries that ran on-chip."""
    from ballista_tpu.ops.runtime import record_decline_trace

    record_decline_trace("device.step_aside", f"ladder step-aside: {reason}")
    return None


# -- M:N join admission ------------------------------------------------------
# Bounded-width gather tiers for the device hash join (ops/join.py and the
# SPMD mesh join, parallel/spmd_join.py): duplicate build keys expand each
# probe into up to max-multiplicity matched rows, and the static gather
# width is the smallest tier covering the observed maximum run-length, so
# XLA compiles a bounded set of gather programs (same recompilation-control
# idea as bucket_rows). Shapes past the top tier — or whose padded [probe
# slots x width] materialization would exceed the element cap — step aside
# to the host sort-merge join with a recorded reason.
JOIN_MULTIPLICITY_TIERS = (1, 4, 16, 64, 256)
# padded gather elements (probe slots x width); past this the bounded-width
# materialization + its d2h readback cost more than the host join it
# replaces (2^26 int32 elements = 256 MiB on the wire)
JOIN_GATHER_CAP = 1 << 26


def join_multiplicity_tier(
    max_mult: int, probe_slots: int
) -> Tuple[Optional[int], Optional[str]]:
    """Admission for the M:N bounded-width gather: (tier, None) with the
    smallest static width covering `max_mult`, or (None, reason) when the
    shape exceeds the ladder — callers record the reason (runtime.
    record_join_path) and step aside to the host join."""
    for tier in JOIN_MULTIPLICITY_TIERS:
        if max_mult <= tier:
            # width 1 transfers exactly the one-int32-per-probe plane the
            # pre-M:N kernel always read back uncapped — the cap guards the
            # bounded-width padding amplification, which only exists past
            # width 1 (capping width 1 would regress large unique-key joins
            # to the host for no readback saving)
            if tier > 1 and probe_slots * tier > JOIN_GATHER_CAP:
                return None, (
                    f"M:N gather {probe_slots}x{tier} exceeds the "
                    f"{JOIN_GATHER_CAP}-element cap"
                )
            return tier, None
    return None, (
        f"build-key multiplicity {max_mult} exceeds top tier "
        f"{JOIN_MULTIPLICITY_TIERS[-1]}"
    )


# -- cost-model tier extension (ISSUE 10) ------------------------------------
# The static ladder above stays the cold-start prior AND the hard safety
# cap: a shape it declines may still run on device, but ONLY when the
# measured cost store (ops/costmodel.py) has enough evidence that the
# device gather beats the host join for that shape — and never past the
# hard caps below, which bound the worst case a wrong store can cost.
JOIN_EXTENDED_TIERS = (512, 1024)
JOIN_GATHER_HARD_CAP = JOIN_GATHER_CAP * 4
# predicted device cost must beat the host prediction by this margin:
# close calls stay on the proven static routing
_EXT_MARGIN = 0.75


def join_extended_tier(
    max_mult: int, probe_slots: int, host_units: int
) -> Optional[Tuple[int, float, float]]:
    """Evidence-gated admission past the static ladder: (tier, predicted
    device seconds, predicted host seconds) when the warm cost store says
    the bounded-width gather beats the host join by _EXT_MARGIN — None
    when cold (no evidence = static prior stands), unfavorable, or past
    the hard cap. The static widths are candidates too: a join declined
    purely on the ELEMENT cap (max_mult inside the ladder) re-admits at
    its natural width under the hard cap, not at a 2x-wasteful extended
    width. `host_units` is the host join's work measure (build + probe
    rows)."""
    from ballista_tpu.ops import costmodel

    for tier in JOIN_MULTIPLICITY_TIERS + JOIN_EXTENDED_TIERS:
        if max_mult <= tier:
            if probe_slots * tier > JOIN_GATHER_HARD_CAP:
                return None
            dev = costmodel.predict("join.gather", probe_slots * tier)
            host = costmodel.predict("join.host", host_units, engine="host")
            if dev is None or host is None:
                return None  # cold store: the static ladder is the prior
            if dev < _EXT_MARGIN * host:
                return tier, dev, host
            return None
    return None

# executor task threads run concurrently: lookup/evict/insert must be one
# atomic section or two threads can each build (and pin) the same stage.
# (Tests reach in to clear these between cases — cross-file accesses are
# outside the file-scoped guarded-by check by design.)

_stage_cache_lock = make_lock("ops.kernels._stage_cache_lock")
_stage_cache: Dict[str, object] = {}  # guarded-by: _stage_cache_lock
# pins each cached stage's table source so its id() (part of the cache key
# for memory scans) can never be recycled by a different object
_stage_cache_pins: Dict[str, object] = {}  # guarded-by: _stage_cache_lock
# stable plan identity -> the latest full (mtime-bearing) cache key, so a
# rewritten file's superseded entry can be evicted and its reservations freed
_stage_latest: Dict[str, str] = {}  # guarded-by: _stage_cache_lock
_filter_cache: Dict[tuple, object] = {}


def resolve_stage(exec_node, ctx) -> Tuple[object, str, str, float]:
    """Build-or-fetch the fused device stage for one aggregate node WITHOUT
    running it: the structural-cache half of hash_aggregate, factored out so
    the shared-scan batch executor (ops/sharedscan.py, ISSUE 13) can resolve
    member stages up front and group compatible ones into one launch.

    Returns (stage, key, stable_key, unit_size): `stage` is False when the
    shape permanently declined to the host path (cached verdict included),
    `key` the full mtime-bearing cache key, `stable_key` the mtime-free
    stage identity (the AOT/cost-store key half), and `unit_size` the
    stage's input size in leaf-file bytes or memory-scan rows (the
    stage.run cost-observation units)."""
    from ballista_tpu.ops.stage import FusedAggregateStage

    # AOT program-cache wiring (ISSUE 8): bind the disk tier's directory +
    # chaos injector from this dispatch's config so the stage steps built
    # below resolve through it. The cost model (ISSUE 10) binds beside it:
    # stage runs/compiles/readbacks observed below feed tier selection.
    from ballista_tpu.ops import aotcache, costmodel

    aotcache.configure(ctx.config)
    costmodel.configure(ctx.config)
    # structural cache: identical plan shapes (the common case for repeated
    # queries) share one stage — and with it the jit trace/compile cache.
    # Memory scans carry no identity in their display: include source ids so
    # two in-memory tables with the same shape never collide.
    import os

    from ballista_tpu.physical.scan import MemoryScanExec

    def leaves(node):
        if not node.children():
            yield node
        for c in node.children():
            yield from leaves(c)

    parts = []
    mtimes = []
    pinned = []
    # input-size units for the stage.run cost observation (ISSUE 11
    # satellite): leaf-file bytes (or memory-scan rows). units=1 made the
    # whole-stage rate scale-blind — the first run after a file grew in
    # place predicted the OLD size's seconds and counted one guaranteed
    # gross mispredict; a per-byte rate predicts correctly at any scale.
    unit_size = 0.0
    # persisted-layout eligibility: every leaf's data identity must be a
    # file set with covering mtimes. A shuffle-reader-fed (or otherwise
    # non-file) leaf contributes nothing to the mtime component, so the key
    # would stay constant across data changes and the layout cache could
    # return stale tiles — those stages must never persist.
    file_backed = True
    for leaf in leaves(exec_node):
        if isinstance(leaf, MemoryScanExec):
            parts.append(str(id(leaf.source)))
            pinned.append(leaf.source)
            unit_size += float(sum(
                b.num_rows
                for part in getattr(leaf.source, "partitions", ())
                for b in part
            ))
        elif hasattr(leaf, "source") and hasattr(leaf.source, "files"):
            # file mtimes invalidate the cached stage (and its
            # device-resident columns) when a file is rewritten; they live
            # in a separate key component so the superseded entry can be
            # found and its HBM reservations released
            parts.extend(leaf.source.files)
            for f in leaf.source.files:
                if os.path.exists(f):
                    mtimes.append(str(os.path.getmtime(f)))
                    try:
                        unit_size += float(os.path.getsize(f))
                    except OSError:
                        pass
                else:
                    mtimes.append("0")
                    file_backed = False  # mtime does not cover this leaf
        else:
            file_backed = False
    # config flags participate in the key: a run-time decline under one
    # config must not pin the device path off for another (ADVICE r1). The
    # top-k annotation does too — it changes what a fact-agg stage returns,
    # and it is not part of the aggregate subtree's display.
    flags = (
        f"fv={ctx.config.tpu_fuse_volatile()},dc={ctx.config.device_cache()},"
        f"sk={ctx.config.tpu_sorted_kernel()},"
        f"topk={getattr(exec_node, '_topk_pushdown', None)}"
    )
    # append-only-when-set: ef=False on every key would invalidate every
    # persisted layout entry written before the flag existed
    if getattr(exec_node, "exact_floats", False):
        flags += ",ef=True"
    # batch.size folds into the key the same append-only way (ISSUE 15
    # satellite, PR 13 residue): the persisted layout's tile granularity
    # follows batch size, and keying on it means a warm layout entry is
    # ALWAYS at this dispatch's granularity — which is what makes
    # layout-warm members shared-scan-eligible (the shared batch stream is
    # then row-identical to the member's warm solo stream). The guarantee
    # only holds for entries written under THIS keying scheme, so the
    # layout-cache _FORMAT bump to 5 orphans every pre-keying store (a
    # suffix-less v4 entry could have been written at any batch size).
    from ballista_tpu.config import BALLISTA_BATCH_SIZE, DEFAULT_SETTINGS

    if ctx.batch_size != int(DEFAULT_SETTINGS[BALLISTA_BATCH_SIZE]):
        flags += f",bs={ctx.batch_size}"
    # decorrelated scalar subqueries equality-compare the aggregate result
    # against source values (q2: ps_supplycost = MIN(...)): float MIN/MAX
    # must be the bit-exact stored value. The fused stage delivers exactly
    # that for plain columns via the order-preserving IEEE-754<->int
    # bijection (ops/floatbits.py) — integer min/max on device, inverted on
    # readback, zero rounding — so the ladder runs; the paths that cannot
    # be exact decline individually (factagg.try_build steps aside, the
    # fused stage rejects exact min/max over computed expressions).
    stable = exec_node.display_indent() + "|" + ",".join(parts) + "|" + flags
    key = stable + "|" + ",".join(mtimes)
    with _stage_cache_lock:
        stage = _stage_cache.get(key)
        if stage is None:
            # evict a superseded entry for the same stable plan (file
            # rewritten: new mtimes) and release its HBM-budget reservations
            # — otherwise a long-lived executor leaks budget until
            # everything streams. release marks the old stage retired, so a
            # task thread still inside its run() cannot re-reserve.
            old_key = _stage_latest.get(stable)
            if old_key is not None and old_key != key:
                old = _stage_cache.pop(old_key, None)
                _stage_cache_pins.pop(old_key, None)
                if old not in (None, False):
                    from ballista_tpu.ops.runtime import release_stage_residency

                    release_stage_residency(old)
            _stage_latest[stable] = key
    if stage is None:
        # build OUTSIDE the lock — a slow stage build must not block cache
        # hits for unrelated queries. First insert wins on a racing build.
        try:
            from ballista_tpu.ops.factagg import FactAggregateStage

            from ballista_tpu.ops.mappedscan import try_rewrite_mapped

            # aggregate over a join: try the fact-side pushdown first
            built = FactAggregateStage.try_build(exec_node)
            if (
                built is not None
                and getattr(built, "topk", None) is None
                and getattr(exec_node, "_topk_pushdown", None) is not None
            ):
                # factagg admitted the shape but its epilogue cannot fuse
                # (dim-only grouping, q10: output groups are not fact keys,
                # so its per-key top-k would rank the wrong thing and the
                # member-select readback pays O(members) d2h). A mapped
                # rewrite groups directly by the OUTPUT keys, so the fused
                # stage's lexicographic top-k applies — prefer it when its
                # spec is live, keeping the O(limit) readback.
                rewritten = try_rewrite_mapped(exec_node)
                if rewritten is not None:
                    try:
                        alt = FusedAggregateStage(rewritten)
                        if alt.topk is not None:
                            built = alt
                    except UnsupportedOnDevice:
                        pass
            if built is None:
                # shapes factagg excludes (multi-key fact joins, dim-valued
                # aggregate inputs, fact-column group keys — q7-q9/q12):
                # rewrite the join tree to a mapped fact scan and fuse that
                rewritten = try_rewrite_mapped(exec_node)
                if rewritten is not None:
                    built = FusedAggregateStage(rewritten)
            if built is None:
                built = FusedAggregateStage(exec_node)
        except UnsupportedOnDevice:
            built = False
        # persisted-layout eligibility: only fully file-backed stages
        # (memory-scan keys embed id(), which another process could recycle
        # for different data, and shuffle-fed stages carry no mtimes at all
        # — a false disk hit either way would be silent corruption)
        if built is not False and not pinned and file_backed:
            built.persist_key = key
            # chunk-set delta identity (ISSUE 19): the plan display names the
            # scan DIRECTORY, not the file list, so display+flags is stable
            # across appends — each prepared chunk keys itself under this
            # base plus its own (path, mtime, size, chunk_index), letting a
            # grown file set reuse every existing chunk byte-for-byte.
            chunk_base = exec_node.display_indent() + "|" + flags
            built.chunk_key_base = chunk_base
            inner = getattr(built, "inner", None)
            if inner is not None:
                inner.persist_key = key
                inner.chunk_key_base = chunk_base
        if built is not False:
            # AOT program identity is the STABLE key half (no mtimes):
            # compiled programs depend on plan structure + shapes only
            # (literal codes/tables ride as runtime aux), so a rewritten
            # input file keeps its warm programs; memory-scan id() reuse is
            # harmless here for the same reason (worst case a false hit
            # serves the identical program)
            built.aot_key = stable
            inner = getattr(built, "inner", None)
            if inner is not None:
                inner.aot_key = stable
        with _stage_cache_lock:
            stage = _stage_cache.get(key)
            if stage is None:
                _stage_cache[key] = built
                _stage_cache_pins[key] = pinned
                stage = built
    return stage, key, stable, unit_size


def hash_aggregate(exec_node, partition: int, ctx, keyset=None) -> Optional[pa.Table]:
    """The device's partial states of one partition, or None (host path).
    `keyset` (HashAggregateExec.execute) goes to the fused stage's sorted
    engine, which may then hand back only the groups whose key it holds;
    every other engine returns every group."""
    # bind the AOT disk tier + cost model from THIS dispatch's config
    # BEFORE any path that compiles or observes (the countjoin prescreen
    # included — resolve_stage rebinds idempotently for the ladder below)
    from ballista_tpu.ops import aotcache, costmodel

    aotcache.configure(ctx.config)
    costmodel.configure(ctx.config)
    # shared-scan splice (ISSUE 13): the batched-task executor already ran
    # this node's partition inside one combined device launch — hand its
    # table straight back. The precompute produced EXACTLY what stage.run
    # below would (bit-identity is the batching invariant), so nothing
    # downstream can tell. Checked before the countjoin prescreen on
    # purpose: only scan-rooted stages (join-free row sources) are ever
    # precomputed, and countjoin only matches join shapes, so the two can
    # never claim the same node.
    shared = getattr(ctx, "shared_scan", None)
    if shared is not None:
        hit = shared.take(exec_node, partition)
        if hit is not None:
            from ballista_tpu.ops.runtime import record_routing

            record_routing("batch", "stage")
            return hit
    # COUNT-over-LEFT-join as device membership counting (q13): the
    # per-probe counts plane replaces the join expansion entirely. A cheap
    # shape prescreen — non-matching aggregates fall through to the ladder
    if ctx.config.tpu_device_join():
        from ballista_tpu.ops.countjoin import try_count_left_join

        counted = try_count_left_join(exec_node, partition, ctx)
        if counted is not None:
            return counted
    stage, key, stable, unit_size = resolve_stage(exec_node, ctx)
    if stage is False:
        return None
    try:
        # the run cost is a cost-store observation keyed on stable stage
        # identity (like the AOT cache), and the success is a recorded
        # routing decision — predicted from the stage's own history, so the
        # bench mispredict rate covers the aggregate path too. Units are
        # the stage's input size (file bytes / memory rows), so the learned
        # rate scales with the data instead of memorizing one run's seconds
        # (ISSUE 11 satellite — units=1 mispredicted once per data growth).
        import hashlib

        op = "stage.run|" + hashlib.sha1(stable.encode()).hexdigest()[:12]
        from ballista_tpu.utils import tracing

        # self time: the stage's host work around its programs (a warm
        # prepare, the dimension side, the rank maps)
        with costmodel.timed(op, units=max(1.0, unit_size), routing_op="stage"), \
                tracing.span("runtime.stage", engine=type(stage).__name__):
            from ballista_tpu.ops.stage import FusedAggregateStage

            if keyset is not None and isinstance(stage, FusedAggregateStage):
                out = stage.run(partition, ctx, keyset=keyset)
            else:
                out = stage.run(partition, ctx)
        return out
    except UnsupportedOnDevice:
        # permanently declined: free its pinned device entries and their
        # HBM-budget reservations before dropping the stage. Log WHY once —
        # a silent decline (e.g. tiles just past the HBM budget) reads as
        # "device path ran" in benchmarks when it did not.
        import sys

        reason = f"stage permanently declined: {sys.exc_info()[1]}"
        logging.getLogger("ballista.tpu").warning(
            "device stage permanently declined to host: %s", sys.exc_info()[1]
        )
        from ballista_tpu.ops.runtime import (
            record_routing,
            release_stage_residency,
        )

        release_stage_residency(stage)
        with _stage_cache_lock:
            _stage_cache[key] = False
        record_routing("host", "stage")
        return host_fallback(reason)


def _compile_predicate(predicate, schema: pa.Schema):
    # structural key (an id() key could be recycled after GC and serve a
    # stale compiled predicate)
    key = (str(predicate), tuple(schema.names), tuple(str(t) for t in schema.types))
    hit = _filter_cache.get(key)
    if hit is not None:
        return hit
    try:
        dicts = ScanDictionaries()
        compiler = ExprCompiler(schema, dicts)
        cv = compiler.compile(predicate)
        if cv.kind != "bool":
            decline("non-boolean predicate")  # cold-path: compile-time shape check; the routing decision is recorded where the cached verdict is consumed (filter_batch)
        import jax

        from ballista_tpu.ops.jaxexpr import predicate_fn

        mask_fn = predicate_fn(cv)  # WHERE collapse: NULL -> excluded

        @jax.jit
        def run(cols, aux):
            return mask_fn(cols, aux)

        hit = (compiler, run)
    except UnsupportedOnDevice:
        hit = False
    _filter_cache[key] = hit
    return hit


def filter_batch(batch: pa.RecordBatch, predicate) -> Optional[pa.RecordBatch]:
    """Evaluate the predicate on device, compact on host."""
    import jax.numpy as jnp

    schema = batch.schema
    hit = _compile_predicate(predicate, schema)
    if hit is False:
        return None
    compiler, run = hit
    n = batch.num_rows
    bucket = bucket_rows(n)
    try:
        cols = {}
        for idx, dtype in compiler.used_columns.items():
            d = compiler.dicts.dicts.get(idx)
            npcol = column_to_numpy(batch.column(idx), dtype, d)
            fill = False if npcol.dtype == np.bool_ else 0
            cols[idx] = jnp.asarray(pad_to(npcol, bucket, fill))
    except UnsupportedOnDevice as e:
        from ballista_tpu.ops.runtime import record_routing

        record_routing("host", "filter")
        return host_fallback(f"filter batch lowering: {e}")
    aux = [jnp.asarray(a) for a in compiler.build_aux()]
    # the full boolean mask rides d2h once per batch — account for it
    mask = readback(run(cols, aux))[:n]
    return batch.filter(pa.array(mask))


