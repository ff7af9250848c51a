"""SPMD aggregation stage: Partial -> exchange -> Final as ONE mesh program.

The reference executes a distributed aggregation as independent
per-partition partial tasks, a materialized hash shuffle, and final tasks
(rust/scheduler/src/planner.rs:149-171 + the ShuffleWriter/Reader pair).
The TPU-native restructuring (SURVEY §2.8, §7 step 5): partitions map to
shards of a jax.sharding.Mesh, the partial phase is the fused-stage program
on each shard, and the exchange is lax.psum over the mesh's ICI — no
materialize-then-fetch, one XLA program for the whole
Partial->shuffle->Final pipeline.

Distributed structure (nothing is globally gathered in row space):

  1. per-shard reads — input partition p belongs to mesh shard
     p % n_devices; each shard scans, encodes, and group-codes only its
     own rows (on a multi-host mesh each host would run this for the
     shards it owns — the per-shard decomposition is the multi-host story).
  2. two-pass global key coding — shards exchange only their DISTINCT key
     rows; the union is dense-ranked once (host work proportional to
     distinct-key count, not row count) and each shard remaps its local
     codes through its slice of the ranking. No central row dictionary.
  3. one mesh program — per-shard fused partials, then the exchange:
       G <= 1024: unrolled per-group reductions + psum/pmin/pmax.
       G  > 1024: per-shard sorted chunked-segment tiles (ops/layout.py)
       -> per-chunk partials -> in-program segment fold to dense [G]
       (owners are sorted, V is small) -> psum/pmin/pmax over the mesh.
     Either way ONE compiled program and ONE device->host readback.

SpmdAggregateExec is emitted by the DistributedPlanner (config
`ballista.tpu.spmd_stages` = true) in place of the
HashAggregate(Final) <- Repartition(hash) <- HashAggregate(Partial)
subtree, collapsing what would be two stages + a shuffle into one stage.
The per-shard program is driven by FusedAggregateStage's compiled
filter/value functions — the same expression compiler the single-chip
backend uses — not a hand-written kernel.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional

import numpy as np
import pyarrow as pa

from ballista_tpu.physical.plan import (
    ExecutionPlan,
    Partitioning,
    TaskContext,
    batch_table,
    collect_all,
)

def _rank_rows(columns):
    """Dense-rank the rows of a small key table (the union of per-shard
    distinct keys). Returns (rank per input row [int32], per-column unique
    key arrays in rank order, n_groups). Work is O(K log K) in the number
    of distinct-key candidates, never in the number of data rows."""
    import pyarrow.compute as pc

    from ballista_tpu.ops.stage import dense_rank

    if not columns:
        return np.zeros(0, dtype=np.int32), [], 1
    encoded = []
    for arr in columns:
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        d = arr if isinstance(arr, pa.DictionaryArray) else pc.dictionary_encode(arr)
        encoded.append(
            (d.indices.to_numpy(zero_copy_only=False).astype(np.int64), d)
        )
    inv, first_idx, n_uniq = dense_rank(
        [(codes_i, len(d.dictionary)) for codes_i, d in encoded]
    )
    take = pa.array(first_idx.astype(np.int64))
    uniq_rows = []
    for arr, (_c, d) in zip(columns, encoded):
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if isinstance(arr, pa.DictionaryArray):
            uniq_rows.append(d.dictionary.take(d.indices.take(take)))
        else:
            uniq_rows.append(arr.take(take))
    return inv.astype(np.int32), uniq_rows, n_uniq


def _key_as_i64(a) -> np.ndarray:
    """Key column -> int64 numpy for the multi-host union allgather."""
    from ballista_tpu.ops.runtime import UnsupportedOnDevice

    if isinstance(a, pa.ChunkedArray):
        a = a.combine_chunks()
    if not isinstance(a, pa.Array):
        a = pa.array(a)
    t = a.type
    if pa.types.is_date32(t):
        a = a.cast(pa.int32())
    elif pa.types.is_boolean(t):
        a = a.cast(pa.int8())
    elif not pa.types.is_integer(t):
        raise UnsupportedOnDevice(
            "multi-host key union requires integer-like keys"
        )
    return a.cast(pa.int64()).to_numpy(zero_copy_only=False).astype(np.int64)


def _rebuild_key_arrays(stage, gathered: List[np.ndarray],
                        first_idx: np.ndarray, n_keys: int) -> List[pa.Array]:
    """Group key values in rank order, cast from the int64 wire form back
    to each key expression's Arrow type."""
    gkv = []
    for j in range(n_keys):
        target = stage.group_exprs[j][0].data_type(stage.scan_schema)
        vals = gathered[j][first_idx]
        arr = pa.array(vals)
        if arr.type != target:
            if pa.types.is_date32(target):
                arr = arr.cast(pa.int32()).cast(target)
            elif pa.types.is_boolean(target):
                arr = arr.cast(pa.int8()).cast(target)
            else:
                arr = arr.cast(target)
        gkv.append(arr)
    return gkv


def _np_dtype_for(dtype: pa.DataType) -> np.dtype:
    """The numpy dtype column_to_numpy produces for an Arrow type —
    derived by lowering a ZERO-LENGTH column through column_to_numpy
    itself, so there is one source of truth: an empty host's blocks always
    dtype-match its data-bearing peers' (one shared jit program)."""
    from ballista_tpu.ops.runtime import ColumnDictionary, column_to_numpy

    d = (
        ColumnDictionary()
        if pa.types.is_string(dtype) or pa.types.is_large_string(dtype)
        else None
    )
    return column_to_numpy(pa.array([], type=dtype), dtype, d).dtype


class SpmdAggregateExec(ExecutionPlan):
    """Executes Final(Repartition(Partial(input))) as one mesh program.

    Falls back to executing the wrapped subplan on the host when the stage
    declines to lower (UnsupportedOnDevice: high cardinality, exprs the
    device path declines) or the backend is not tpu — the wrapped subplan
    is the untouched original subtree, so behavior is identical minus the
    fusion. Any other error of the mesh program fails the task.
    """

    def __init__(self, subplan: ExecutionPlan) -> None:
        # subplan = HashAggregateExec(FINAL) over RepartitionExec over
        # HashAggregateExec(PARTIAL); kept whole for serde + fallback
        from ballista_tpu.physical.aggregate import AggregateMode, HashAggregateExec
        from ballista_tpu.physical.repartition import RepartitionExec

        assert isinstance(subplan, HashAggregateExec)
        assert subplan.mode == AggregateMode.FINAL
        self.subplan = subplan
        repart = subplan.input
        assert isinstance(repart, RepartitionExec)
        partial = repart.input
        assert isinstance(partial, HashAggregateExec)
        assert partial.mode == AggregateMode.PARTIAL
        self.final = subplan
        self.partial = partial
        self._stage = None
        self._mesh = None
        self._program = None
        self._program_key = None
        # introspection: "mesh" or "host" after each execute (the dryrun and
        # tests assert the mesh path actually ran, since the host fallback
        # produces identical results)
        self.last_path: Optional[str] = None

    # ------------------------------------------------------------------
    def schema(self) -> pa.Schema:
        return self.subplan.schema()

    def output_partitioning(self) -> Partitioning:
        return Partitioning.unknown(1)

    def children(self) -> List[ExecutionPlan]:
        # the subplan is serialized/traversed whole; no planner recursion
        # into it (it must stay one stage)
        return []

    def with_children(self, children: List[ExecutionPlan]) -> "SpmdAggregateExec":
        assert not children
        return self

    def fmt(self) -> str:
        return "SpmdAggregateExec: partial+exchange+final as one mesh program"

    # ------------------------------------------------------------------
    def _build_mesh(self, ctx: TaskContext):
        from ballista_tpu.parallel.mesh import build_mesh

        if self._mesh is None:
            # a mesh larger than the device count raises: a program that
            # asked for four chips must not quietly answer from one
            self._mesh = build_mesh(ctx.config.mesh_shape() or None)
        return self._mesh

    def fingerprint(self) -> str:
        """Stable short id of the fused subtree, for fallback diagnostics."""
        import hashlib

        def walk(n):
            yield n.fmt()
            for c in n.children():
                yield from walk(c)

        text = "\n".join(walk(self.subplan))
        return hashlib.sha1(text.encode()).hexdigest()[:12]

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        from ballista_tpu.utils import tracing

        assert partition == 0
        if ctx.backend != "tpu":
            yield from self._execute_host(ctx)
            return
        # mesh aggregate cost feeds the same store the single-chip ladder
        # consults (ISSUE 10), keyed on this stage's identity; the decision
        # lands in the routing accumulator either way
        from ballista_tpu.ops import costmodel

        costmodel.configure(ctx.config)
        op = "mesh.agg|" + self.fingerprint()[:12]
        host_op = "mesh.agg.host|" + self.fingerprint()[:12]
        # admission rides the cost model (ISSUE 16 satellite): with BOTH
        # paths warm for this stage shape and the mesh predicted slower
        # (compile + collective overhead on small inputs), decline to the
        # host up front instead of paying the launch to learn it again.
        # Cold on either side → admit, exactly the pre-model ladder; the
        # host run below stays predictive, so a stage that outgrew its
        # host rate grossly mispredicts, re-tiers, and earns the mesh
        # back on its next admission check.
        mesh_pred = costmodel.predict(op, 1.0)
        host_pred = costmodel.predict(host_op, 1.0, engine="host")
        if (
            mesh_pred is not None
            and host_pred is not None
            and mesh_pred > host_pred
        ):
            from ballista_tpu.ops.runtime import record_routing

            record_routing("host", "mesh.agg", mesh_pred, None)
            tracing.incr("spmd.host_declined")
            self.last_path = "host"
            with costmodel.timed(host_op, engine="host"):
                out = collect_all(self.subplan, ctx)
            yield from batch_table(out, ctx.batch_size)
            return
        from ballista_tpu.ops.runtime import UnsupportedOnDevice

        try:
            with costmodel.timed(op, routing_op="mesh.agg"):
                out = self._execute_mesh(ctx)
            self.last_path = "mesh"
            tracing.incr("spmd.mesh")
        except UnsupportedOnDevice as declined:
            # a reasoned decline is the ONLY way to the host: anything else
            # the mesh program raises (an XLA compile error, an exhausted
            # device, a sharding error) fails the task, as the single-chip
            # ladder does (ops/kernels.py::hash_aggregate)
            import logging

            from ballista_tpu.ops.runtime import record_routing

            logging.getLogger("ballista.spmd").info(
                "mesh aggregation declined (stage %s), host subplan: %s",
                self.fingerprint(), declined,
            )
            tracing.incr("spmd.host_fallback")
            record_routing("host", "mesh.agg")
            self.last_path = "host"
            # the forced fallback still warms the host-side rate the
            # admission check above compares against (predictive=False: a
            # run the decline forced must not re-tier on surprise)
            with costmodel.timed(host_op, engine="host", predictive=False):
                out = collect_all(self.subplan, ctx)
        yield from batch_table(out, ctx.batch_size)

    def _execute_host(self, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        """Run the untouched subtree on the host. The Final aggregate above
        the hash Repartition spreads groups over ALL its output partitions —
        this single-partition stage must drain every one of them."""
        yield from batch_table(collect_all(self.subplan, ctx), ctx.batch_size)

    # ------------------------------------------------------------------
    def _execute_mesh(self, ctx: TaskContext) -> pa.Table:
        import jax
        import jax.numpy as jnp

        from ballista_tpu.ops.runtime import UnsupportedOnDevice, bucket_rows
        from ballista_tpu.ops.stage import FusedAggregateStage, MAX_GROUPS

        from ballista_tpu.physical.aggregate import needs_exact_float_minmax

        if needs_exact_float_minmax(self.partial):
            # q2-shape decorrelated MIN(float): the f32 mesh pmin would be
            # equality-joined against exact f64 values — host subplan instead
            raise UnsupportedOnDevice("exact float min/max required")
        if self._stage is None:
            # float_bits=False: the mesh exchange folds rows independently
            # (per-row psum/pmin/pmax collectives), which cannot express the
            # lexicographic hi/lo f64 key-plane pair — this path keeps its
            # documented f32 float min/max semantics (the exact-float decline
            # above already routes q2-shape queries to the host subplan)
            self._stage = FusedAggregateStage(self.partial, float_bits=False)
        stage = self._stage
        mesh = self._build_mesh(ctx)
        n_dev = int(np.prod(list(mesh.shape.values())))
        if jax.process_count() > 1:
            # pod path: per-host shard reads, collective key exchange, the
            # SAME shard_map program over the global mesh
            return self._execute_mesh_multihost(ctx, stage, mesh, n_dev)

        # ---- 1. per-shard reads: each shard scans and group-codes ONLY its
        # own rows. Batches go to the least-loaded shard (batches are finer
        # than partitions, so skewed or few partitions still balance — shard
        # blocks are padded to the largest shard, so balance is wall-time)
        parts = stage.scan.output_partitioning().partition_count()
        shard_batches: List[List[pa.RecordBatch]] = [[] for _ in range(n_dev)]
        shard_rows = [0] * n_dev
        for p in range(parts):
            for b in stage._scan_batches(p, ctx):
                if not b.num_rows:
                    continue
                si = shard_rows.index(min(shard_rows))
                shard_batches[si].append(b)
                shard_rows[si] += b.num_rows
        shards: List[Optional[dict]] = []
        for bs in shard_batches:
            if not bs:
                shards.append(None)  # empty shard: identity contribution
                continue
            t = pa.Table.from_batches(bs).combine_chunks()
            batch = t.to_batches(max_chunksize=t.num_rows)[0]
            codes, kv, g = stage._group_codes(batch)
            shards.append({"batch": batch, "codes": codes, "kv": kv, "g": g})
        live = [d for d in shards if d is not None]
        if not live:
            return self.schema().empty_table()

        # ---- 2. global key coding from per-shard DISTINCTS only
        n_keys = len(stage.group_exprs)
        if n_keys == 0:
            n_groups, gkv = 1, []
            for d in live:
                d["gcodes"] = d["codes"]
        else:
            union_cols = []
            for j in range(n_keys):
                parts_j = []
                for d in live:
                    a = d["kv"][j]
                    parts_j.append(
                        a.combine_chunks() if isinstance(a, pa.ChunkedArray) else a
                    )
                union_cols.append(
                    pa.chunked_array(parts_j).combine_chunks()
                    if len(parts_j) > 1 else parts_j[0]
                )
            inv, gkv, n_groups = _rank_rows(union_cols)
            off = 0
            for d in live:
                mapping = inv[off:off + d["g"]]
                off += d["g"]
                d["gcodes"] = mapping[d["codes"]]
        if n_groups == 0:
            return self.schema().empty_table()

        # ---- 3. lower columns per shard; global int32-sum overflow check
        # (psum adds across shards, so the bound spans ALL rows)
        for d in live:
            d["npcols"] = stage._lower_columns(d["batch"])
        total_n = sum(d["batch"].num_rows for d in live)
        stage._check_int_ranges([d["npcols"] for d in live], total_n)

        aux = [jnp.asarray(a) for a in stage.compiler.build_aux()]
        if n_groups <= MAX_GROUPS:
            counts, outputs = self._run_unrolled_mesh(
                mesh, stage, shards, n_groups, n_dev, aux
            )
        else:
            counts, outputs = self._run_sorted_mesh(
                mesh, stage, shards, n_groups, n_dev, aux
            )
        partial_table = stage._assemble_partial(outputs, counts, gkv, n_groups)
        return self.final._final(partial_table)

    def _execute_mesh_multihost(self, ctx, stage, mesh, n_dev) -> pa.Table:
        """Multi-process mesh execution (jax.distributed): this process
        reads ONLY the partitions its local shards own (multihost.py's
        host-boundary contract), every host ranks the allgathered
        distinct-key union identically, local shard blocks assemble into
        globally-sharded arrays, and the SAME jitted shard_map program the
        single-host path uses runs over the pod mesh. Every decline is
        collective (multihost.agree): a unilateral fallback would leave
        the other hosts blocked inside the program's collectives.

        Scope (collectively enforced): integer/date/bool group keys (the
        key union rides an int64 allgather) and no string columns anywhere
        in the stage (per-host dictionary growth would diverge the aux
        shapes). Both the unrolled (G <= MAX_GROUPS) and the sorted
        chunked-segment (any G) programs run at pod scale. The reference
        reaches multi-node scale with one executor process per node over
        NCCL/MPI; this is the mesh-native equivalent."""
        import jax
        import jax.numpy as jnp

        from ballista_tpu.ops.runtime import UnsupportedOnDevice, bucket_rows
        from ballista_tpu.ops.stage import MAX_GROUPS, dense_rank
        from ballista_tpu.parallel import multihost as mh

        # ---- per-host reads: only partitions owned by local shards ----
        parts = stage.scan.output_partitioning().partition_count()
        my_shards = mh.local_shard_ids(mesh)
        shard_batches = {i: [] for i in my_shards}
        shard_rows = {i: 0 for i in my_shards}
        n_keys = len(stage.group_exprs)
        local: Dict[int, dict] = {}
        ok = True
        my_distinct: List[np.ndarray] = [
            np.zeros(0, dtype=np.int64) for _ in range(n_keys)
        ]
        try:
            if any(
                pa.types.is_string(t) or pa.types.is_large_string(t)
                for t in stage.compiler.used_columns.values()
            ):
                raise UnsupportedOnDevice(
                    "multi-host v1: string columns diverge per-host dictionaries"
                )
            for p in mh.owned_partitions(parts, mesh):
                for b in stage._scan_batches(p, ctx):
                    if not b.num_rows:
                        continue
                    # balance batches among THIS host's own shards only
                    si = min(shard_rows, key=shard_rows.get)
                    shard_batches[si].append(b)
                    shard_rows[si] += b.num_rows
            for si, bs in shard_batches.items():
                if not bs:
                    continue
                t = pa.Table.from_batches(bs).combine_chunks()
                batch = t.to_batches(max_chunksize=t.num_rows)[0]
                codes, kv, g = stage._group_codes(batch)
                local[si] = {"batch": batch, "codes": codes, "kv": kv, "g": g}
            # this host's distinct key tuples as parallel int64 columns
            # (shards in local-iteration order; rows stay tuple-aligned)
            cols_j: List[List[np.ndarray]] = [[] for _ in range(n_keys)]
            for d in local.values():
                for j in range(n_keys):
                    cols_j[j].append(_key_as_i64(d["kv"][j]))
            for j in range(n_keys):
                if cols_j[j]:
                    my_distinct[j] = np.concatenate(cols_j[j])
            for d in local.values():
                d["npcols"] = stage._lower_columns(d["batch"])
        except (UnsupportedOnDevice, MemoryError, OSError, pa.ArrowException):
            # the read/lower fence must catch host-side failures too (a
            # missing file is OSError, an OOM during decode MemoryError, a
            # truncated/corrupt parquet ArrowInvalid — which subclasses
            # ValueError, not OSError): the decline has to be COLLECTIVE,
            # or the healthy peers block forever in the allgather below
            # waiting for this host
            ok = False
        if not mh.agree(ok):
            raise UnsupportedOnDevice("multi-host mesh declined collectively")

        my_rows = sum(d["batch"].num_rows for d in local.values())
        all_rows = mh.allgather_rows(np.array([my_rows], dtype=np.int64))
        if int(all_rows.sum()) == 0:
            return self.schema().empty_table()

        # ---- collective key union; identical ranking on every host ----
        if n_keys == 0:
            n_groups, gkv = 1, []
            for d in local.values():
                d["gcodes"] = d["codes"]
        else:
            gathered = [mh.allgather_rows(c) for c in my_distinct]
            encoded = []
            for col in gathered:
                uniq, inv = np.unique(col, return_inverse=True)
                encoded.append((inv.astype(np.int64), len(uniq)))
            inv_all, first_idx, n_groups = dense_rank(encoded)
            # this host's slice of the gathered ranking
            my_count = sum(d["g"] for d in local.values())
            counts = mh.allgather_rows(
                np.array([my_count], dtype=np.int64)
            )
            pos = int(counts[: jax.process_index()].sum())
            for d in local.values():
                mapping = inv_all[pos: pos + d["g"]]
                pos += d["g"]
                d["gcodes"] = mapping[d["codes"]].astype(np.int32)
            gkv = _rebuild_key_arrays(stage, gathered, first_idx, n_keys)

        # ---- int-overflow check over the GLOBAL row count --------------
        ok = True
        try:
            stage._check_int_ranges(
                [d["npcols"] for d in local.values()],
                max(int(all_rows.sum()), 1),
            )
        except UnsupportedOnDevice:
            ok = False
        if not mh.agree(ok):
            raise UnsupportedOnDevice("multi-host int-range decline")

        if n_groups > MAX_GROUPS:
            # n_groups derives from the SAME gathered union on every host,
            # so the path choice needs no extra agreement
            return self._multihost_sorted(
                ctx, stage, mesh, n_dev, local, gkv, n_groups
            )

        # ---- assemble globally-sharded blocks; run the SAME program ----
        local_max = max(
            [d["batch"].num_rows for d in local.values()], default=1
        )
        S = mh.global_max(int(bucket_rows(local_max)))
        col_ids = sorted(stage.compiler.used_columns)
        aux = [jnp.asarray(a) for a in stage.compiler.build_aux()]
        cols: Dict[int, object] = {}
        for idx in col_ids:
            np_dtype = _np_dtype_for(stage.compiler.used_columns[idx])
            blocks = {}
            for si in my_shards:
                big = np.zeros(S, dtype=np_dtype)
                d = local.get(si)
                if d is not None:
                    npcol = d["npcols"][idx].astype(np_dtype, copy=False)
                    big[: len(npcol)] = npcol
                blocks[si] = big
            cols[idx] = mh.make_sharded(mesh, blocks, S * n_dev, np_dtype)
        codes_blocks, valid_blocks = {}, {}
        for si in my_shards:
            cb = np.zeros(S, dtype=np.int32)
            vb = np.zeros(S, dtype=np.bool_)
            d = local.get(si)
            if d is not None:
                n = d["batch"].num_rows
                cb[:n] = d["gcodes"]
                vb[:n] = True
            codes_blocks[si] = cb
            valid_blocks[si] = vb
        codes_g = mh.make_sharded(mesh, codes_blocks, S * n_dev, np.int32)
        valid_g = mh.make_sharded(mesh, valid_blocks, S * n_dev, np.bool_)

        from ballista_tpu.ops.runtime import readback

        seg = int(bucket_rows(n_groups, 16)) + 1
        program = self._get_program(mesh, stage, seg, set(cols.keys()), len(aux))
        stacked = readback(program(cols, aux, codes_g, valid_g))
        rows = stage._decode_stacked(stacked)
        counts_np = rows[0][:n_groups]
        outputs = [r[:n_groups] for r in rows[1:]]
        partial_table = stage._assemble_partial(outputs, counts_np, gkv, n_groups)
        return self.final._final(partial_table)

    def _multihost_sorted(self, ctx, stage, mesh, n_dev, local, gkv,
                          n_groups) -> pa.Table:
        """Pod path for G > MAX_GROUPS: per-shard sorted chunked-segment
        tiles built host-locally, tile widths (L1) and chunk counts (V)
        unified with collective maxima so every shard's [V_pad, L1] blocks
        stack into one globally-sharded array, then the SAME jitted sorted
        shard_map program (segment fold + psum/pmin/pmax) runs over the
        global mesh — the cardinality-independent layout at pod scale."""
        import jax.numpy as jnp

        from ballista_tpu.ops.layout import SortedSegmentLayout
        from ballista_tpu.ops.runtime import UnsupportedOnDevice, bucket_rows
        from ballista_tpu.parallel import multihost as mh

        my_shards = mh.local_shard_ids(mesh)
        # fallible per-host work is fenced with collective agreement BEFORE
        # the next collective (multihost.py's invariant): a unilateral
        # raise here (oversized shard, MemoryError while materializing)
        # would strand the other hosts inside the collectives below
        ok = True
        layouts: Dict[int, SortedSegmentLayout] = {}
        try:
            for si, d in local.items():
                layouts[si] = SortedSegmentLayout(
                    d["gcodes"], n_groups, min_one_chunk=False
                )
        except (UnsupportedOnDevice, MemoryError):
            ok = False
        if not mh.agree(ok):
            raise UnsupportedOnDevice("multi-host sorted layout decline")
        my_L1 = max((l.L1 for l in layouts.values()), default=8)
        L1 = mh.global_max(my_L1)
        my_V = 1
        col_ids = sorted(stage.compiler.used_columns)
        ok = True
        col_blocks: Dict[int, Dict[int, np.ndarray]] = {}
        clen_blocks: Dict[int, np.ndarray] = {}
        owner_blocks: Dict[int, np.ndarray] = {}
        try:
            for si in list(layouts):
                if layouts[si].L1 != L1:
                    layouts[si] = SortedSegmentLayout(
                        local[si]["gcodes"], n_groups, force_L1=L1,
                        min_one_chunk=False,
                    )
            my_V = max((l.V for l in layouts.values()), default=1)
        except (UnsupportedOnDevice, MemoryError):
            ok = False
        if not mh.agree(ok):
            raise UnsupportedOnDevice("multi-host sorted rebuild decline")
        V_pad = mh.global_max(int(bucket_rows(my_V, 8)))
        G_pad = int(bucket_rows(n_groups, 16))
        ok = True
        try:
            for idx in col_ids:
                np_dtype = _np_dtype_for(stage.compiler.used_columns[idx])
                blocks = {}
                for si in my_shards:
                    big = np.zeros((V_pad, L1), dtype=np_dtype)
                    l = layouts.get(si)
                    if l is not None and l.V:
                        big[: l.V] = l.materialize(
                            local[si]["npcols"][idx].astype(
                                np_dtype, copy=False
                            )
                        )
                    blocks[si] = big
                col_blocks[idx] = blocks
            for si in my_shards:
                cb = np.zeros(V_pad, dtype=np.int16)
                # padding chunks carry identity partials (clen=0); G_pad-1
                # keeps each shard's owner slice sorted
                # (indices_are_sorted=True)
                ob = np.full(V_pad, G_pad - 1, dtype=np.int32)
                l = layouts.get(si)
                if l is not None and l.V:
                    cb[: l.V] = l.clen
                    ob[: l.V] = l.owner
                clen_blocks[si] = cb
                owner_blocks[si] = ob
        except (UnsupportedOnDevice, MemoryError):
            ok = False
        if not mh.agree(ok):
            raise UnsupportedOnDevice("multi-host tile materialization decline")

        aux = [jnp.asarray(a) for a in stage.compiler.build_aux()]
        cols: Dict[int, object] = {}
        for idx in col_ids:
            np_dtype = _np_dtype_for(stage.compiler.used_columns[idx])
            cols[idx] = mh.make_sharded(
                mesh, col_blocks.pop(idx), V_pad * n_dev, np_dtype
            )
        clen_g = mh.make_sharded(mesh, clen_blocks, V_pad * n_dev, np.int16)
        owner_g = mh.make_sharded(mesh, owner_blocks, V_pad * n_dev, np.int32)

        from ballista_tpu.ops.runtime import readback

        program = self._get_sorted_program(
            mesh, stage, G_pad, L1, set(cols.keys()), len(aux)
        )
        stacked = readback(program(cols, aux, clen_g, owner_g))
        rows = stage._decode_stacked(stacked)
        counts_np = rows[0][:n_groups]
        outputs = [r[:n_groups] for r in rows[1:]]
        partial_table = stage._assemble_partial(outputs, counts_np, gkv, n_groups)
        return self.final._final(partial_table)

    def _run_unrolled_mesh(self, mesh, stage, shards, n_groups, n_dev, aux):
        """G <= MAX_GROUPS: per-shard unrolled reductions + psum exchange.
        Shard blocks are padded to a common size and laid out contiguously,
        so shard d's rows live exactly in block d of the sharded arrays."""
        from ballista_tpu.parallel.mesh import put_sharded

        from ballista_tpu.ops.runtime import bucket_rows, readback

        live_ns = [d["batch"].num_rows for d in shards if d is not None]
        S = int(bucket_rows(max(live_ns)))
        total = S * n_dev
        col_ids = sorted(stage.compiler.used_columns)
        cols: Dict[int, object] = {}
        for idx in col_ids:
            ref = next(d["npcols"][idx] for d in shards if d is not None)
            big = np.zeros(total, dtype=ref.dtype)
            for si, d in enumerate(shards):
                if d is not None:
                    npcol = d["npcols"][idx]
                    big[si * S: si * S + len(npcol)] = npcol
            cols[idx] = put_sharded(mesh, big)
        codes_big = np.zeros(total, dtype=np.int32)
        valid_big = np.zeros(total, dtype=np.bool_)
        for si, d in enumerate(shards):
            if d is None:
                continue
            n = d["batch"].num_rows
            codes_big[si * S: si * S + n] = d["gcodes"]
            valid_big[si * S: si * S + n] = True

        seg = int(bucket_rows(n_groups, 16)) + 1  # +1 dump slot
        program = self._get_program(mesh, stage, seg, set(cols.keys()), len(aux))
        stacked = readback(
            program(cols, aux, put_sharded(mesh, codes_big),
                    put_sharded(mesh, valid_big))
        )
        rows = stage._decode_stacked(stacked)
        return rows[0][:n_groups], [r[:n_groups] for r in rows[1:]]

    def _run_sorted_mesh(self, mesh, stage, shards, n_groups, n_dev, aux):
        """G > MAX_GROUPS: per-shard sorted chunked-segment tiles, chunk
        partials folded to dense [G] in-program (sorted segment ops over a
        small V), then psum/pmin/pmax over the mesh. Cardinality-independent:
        device work is O(rows + G), never O(G) serial passes."""
        from ballista_tpu.parallel.mesh import put_sharded

        from ballista_tpu.ops.layout import SortedSegmentLayout
        from ballista_tpu.ops.runtime import bucket_rows, readback

        layouts: List[Optional[SortedSegmentLayout]] = []
        for d in shards:
            layouts.append(
                None if d is None else SortedSegmentLayout(
                    d["gcodes"], n_groups, min_one_chunk=False
                )
            )
        live_layouts = [l for l in layouts if l is not None]
        L1 = max(l.L1 for l in live_layouts)
        for i, (d, l) in enumerate(zip(shards, layouts)):
            if l is not None and l.L1 != L1:
                layouts[i] = SortedSegmentLayout(
                    d["gcodes"], n_groups, force_L1=L1, min_one_chunk=False
                )
        V_pad = int(bucket_rows(max(l.V for l in layouts if l is not None), 8))
        G_pad = int(bucket_rows(n_groups, 16))

        col_ids = sorted(stage.compiler.used_columns)
        cols: Dict[int, object] = {}
        for idx in col_ids:
            ref = next(d["npcols"][idx] for d in shards if d is not None)
            big = np.zeros((n_dev * V_pad, L1), dtype=ref.dtype)
            for si, (d, l) in enumerate(zip(shards, layouts)):
                if d is not None and l.V:
                    big[si * V_pad: si * V_pad + l.V] = l.materialize(
                        d["npcols"][idx]
                    )
            cols[idx] = put_sharded(mesh, big)
        clen_big = np.zeros(n_dev * V_pad, dtype=np.int16)
        # padding chunks carry identity partials (clen=0 -> empty mask), so
        # any segment may absorb them — use G_pad-1 to keep each shard's
        # owner slice SORTED (segment ops run indices_are_sorted=True)
        owner_big = np.full(n_dev * V_pad, G_pad - 1, dtype=np.int32)
        for si, l in enumerate(layouts):
            if l is not None and l.V:
                clen_big[si * V_pad: si * V_pad + l.V] = l.clen
                owner_big[si * V_pad: si * V_pad + l.V] = l.owner

        program = self._get_sorted_program(
            mesh, stage, G_pad, L1, set(cols.keys()), len(aux)
        )
        stacked = readback(
            program(cols, aux, put_sharded(mesh, clen_big),
                    put_sharded(mesh, owner_big))
        )
        rows = stage._decode_stacked(stacked)
        return rows[0][:n_groups], [r[:n_groups] for r in rows[1:]]

    def _get_program(self, mesh, stage, seg: int, col_keys, n_aux: int):
        """shard_map(per-shard fused partials) + psum, jitted once per
        (segment bucket, column set); the mesh is built once per exec."""
        key = (seg, tuple(sorted(col_keys)), n_aux)
        if self._program_key == key:
            return self._program

        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ballista_tpu.ops.stage import jnp_unpack_i32

        core = stage._unrolled_core()
        int_rows = stage._int_rows
        folds = stage._folds
        collectives = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                       "max": jax.lax.pmax}

        def per_shard(cols, aux, codes, row_valid):
            stacked = core(seg, cols, aux, codes, row_valid)
            # the exchange: merge shard partials over ICI instead of a
            # materialized hash shuffle. Rows reduce with their own
            # collective (sum/min/max); int32 rows are hi/lo packed (see
            # stage.py::_stack_rows), so decode -> exact int32 collective
            # -> re-encode.
            outs = []
            p = 0
            for is_int, fold in zip(int_rows, folds):
                red = collectives[fold]
                if is_int:
                    v = red(jnp_unpack_i32(stacked[p], stacked[p + 1]), "data")
                    outs.append((v >> 16).astype(jnp.float32))
                    outs.append((v & 0xFFFF).astype(jnp.float32))
                    p += 2
                else:
                    outs.append(red(stacked[p], "data"))
                    p += 1
            return jnp.stack(outs)

        fn = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(
                {k: P("data") for k in col_keys},
                [P() for _ in range(n_aux)],
                P("data"),
                P("data"),
            ),
            out_specs=P(),
            check_vma=False,
        )
        self._program = jax.jit(fn)
        self._program_key = key
        return self._program

    def _get_sorted_program(self, mesh, stage, G_pad: int, L1: int, col_keys,
                            n_aux: int):
        """shard_map(per-shard tile partials -> sorted segment fold to dense
        [G_pad]) + psum/pmin/pmax exchange, jitted once per (group bucket,
        column set). Chunk owners are sorted within each shard, and V is
        orders of magnitude smaller than the row count, so the in-program
        segment ops stay cheap even though XLA lowers them to scatter."""
        key = ("sorted", G_pad, L1, tuple(sorted(col_keys)), n_aux)
        if self._program_key == key:
            return self._program

        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ballista_tpu.ops.stage import jnp_unpack_i32

        core = stage._sorted_core()
        int_rows = stage._int_rows
        folds = stage._folds
        seg_ops = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
                   "max": jax.ops.segment_max}
        collectives = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                       "max": jax.lax.pmax}

        def per_shard(cols, aux, clen, owner):
            stacked = core(L1, cols, aux, clen)  # [R_packed, V] chunk partials
            outs = []
            p = 0
            for is_int, fold in zip(int_rows, folds):
                if is_int:
                    v = jnp_unpack_i32(stacked[p], stacked[p + 1])
                    p += 2
                else:
                    v = stacked[p]
                    p += 1
                # chunk -> dense group vector (segment identity covers
                # groups this shard never saw), then the mesh exchange
                dense = seg_ops[fold](
                    v, owner, num_segments=G_pad, indices_are_sorted=True
                )
                dense = collectives[fold](dense, "data")
                if is_int:
                    dense = dense.astype(jnp.int32)
                    outs.append((dense >> 16).astype(jnp.float32))
                    outs.append((dense & 0xFFFF).astype(jnp.float32))
                else:
                    outs.append(dense)
            return jnp.stack(outs)

        fn = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(
                {k: P("data") for k in col_keys},
                [P() for _ in range(n_aux)],
                P("data"),
                P("data"),
            ),
            out_specs=P(),
            check_vma=False,
        )
        self._program = jax.jit(fn)
        self._program_key = key
        return self._program
