"""SPMD co-partitioned join: the hash-repartition exchange as ONE mesh
program over ICI.

The reference feeds a partitioned join through two materialized hash
shuffles (RepartitionExec -> ShuffleWriter/Reader pairs,
rust/core/proto/ballista.proto:415-422, rust/scheduler/src/planner.rs:114-148)
and joins partition pairs on the CPU. The TPU-native restructuring (SURVEY
§2.8's RepartitionExec -> lax.all_to_all mapping): key-hash buckets are
exchanged between mesh shards with `lax.all_to_all` inside one shard_map
program, and each shard matches its key range with sort + searchsorted —
the same regular, scatter-free shape the device join kernel uses
(ops/join.py).

What travels over the mesh is (dense key code, row id) per side — the
matching plane. Payload columns do NOT ride the ICI exchange: on a
single-host mesh every payload row is already host-local, so the final
assembly is a zero-copy Arrow take on the matched row-id pairs the program
returns (sending payloads through the chip would add two transfers for
data the host already holds). On a multi-host pod the payload legs ride
the host data plane (Arrow Flight, client/flight.py) exactly like the
reference's shuffle pieces; the ICI program still eliminates the
materialize-sort-merge of the key-matching plane.

Key coding is shared with the host join (physical/joinutil.py): any Arrow
key type, composite keys, nulls -> -1 (never match). Coding is dense, so
bucket ownership `splitmix(code) % n_dev` balances shards and codes fit
int32 for the device sort.

Duplicate build keys run ON the mesh: each shard computes per-probe match
run-lengths with paired searchsorted (side='left'/'right') and materializes
them through a bounded-width gather whose static width is the smallest
admission tier (ops/kernels.py::JOIN_MULTIPLICITY_TIERS) covering the
build side's observed maximum key multiplicity — the same M:N program
shape as the single-chip device join (ops/join.py).

Decline-to-host (the wrapped subplan is the untouched original subtree):
non-INNER/LEFT join types, residual filters, multiplicity past the top
admission tier (steps aside to the inline host join), or any device
error. Every outcome is recorded via runtime.record_join_path so bench's
per-config join counters stay truthful.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import numpy as np
import pyarrow as pa

from ballista_tpu.logical.plan import JoinType
from ballista_tpu.physical.plan import (
    ExecutionPlan,
    Partitioning,
    TaskContext,
    batch_table,
    collect_all,
)
from ballista_tpu.physical.repartition import RepartitionExec, _splitmix64


def _strip_repartition(node: ExecutionPlan) -> ExecutionPlan:
    """The mesh program IS the exchange: read the repartition's input."""
    return node.input if isinstance(node, RepartitionExec) else node


class SpmdJoinExec(ExecutionPlan):
    """Executes HashJoin(Repartition(L), Repartition(R)) as one mesh program.

    Mirrors SpmdAggregateExec's contract: single output partition, the
    wrapped subplan serialized whole (serde + host fallback), `last_path`
    records whether the mesh actually ran.
    """

    def __init__(self, subplan) -> None:
        from ballista_tpu.physical.join import HashJoinExec

        assert isinstance(subplan, HashJoinExec)
        self.subplan = subplan  # the HashJoinExec, kept whole for serde
        self._mesh = None
        self._program = None
        self._program_key = None
        self.last_path: Optional[str] = None

    # ------------------------------------------------------------------
    def schema(self) -> pa.Schema:
        return self.subplan.schema()

    def output_partitioning(self) -> Partitioning:
        return Partitioning.unknown(1)

    def children(self) -> List[ExecutionPlan]:
        return []  # serialized/traversed whole; must stay one stage

    def with_children(self, children: List[ExecutionPlan]) -> "SpmdJoinExec":
        assert not children
        return self

    def fmt(self) -> str:
        on = ", ".join(f"{l} = {r}" for l, r in self.subplan.on)
        return (
            f"SpmdJoinExec: type={self.subplan.join_type.value}, on=[{on}], "
            "all_to_all exchange as one mesh program"
        )

    # ------------------------------------------------------------------
    def _build_mesh(self, ctx: TaskContext):
        from ballista_tpu.parallel.mesh import build_mesh

        if self._mesh is None:
            # raises when the mesh asks for more devices than there are
            self._mesh = build_mesh(ctx.config.mesh_shape() or None)
        return self._mesh

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        from ballista_tpu.utils import tracing

        assert partition == 0
        if ctx.backend != "tpu":
            yield from self._execute_host(ctx)
            return
        from ballista_tpu.ops.runtime import (
            UnsupportedOnDevice,
            record_join_path,
            record_routing,
        )

        declined = None
        try:
            self._inline_host = False
            self._mesh_cost = (None, None)
            out = self._execute_mesh(ctx)
        except UnsupportedOnDevice as e:
            # only a reasoned decline goes to the host join; any other
            # error of the mesh program fails the task (see
            # SpmdAggregateExec.execute)
            declined = e
        if declined is not None:
            tracing.incr("spmd.join_host_fallback")
            record_join_path("host_fallback", f"mesh join: {declined}")
            record_routing("host", "join.mesh")
            self.last_path = "host"
            yield from self._execute_host(ctx)
            return
        self.last_path = "host-inline" if self._inline_host else "mesh"
        tracing.incr(
            "spmd.join_host_inline" if self._inline_host
            else "spmd.join_mesh"
        )
        if not self._inline_host:
            predicted, observed = self._mesh_cost
            record_join_path("device")
            record_routing(
                "device", "join.mesh",
                predicted_s=predicted, observed_s=observed,
            )
        yield from batch_table(out, ctx.batch_size)

    def _execute_host(self, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        yield from batch_table(collect_all(self.subplan, ctx), ctx.batch_size)

    # ------------------------------------------------------------------
    def _execute_mesh(self, ctx: TaskContext) -> pa.Table:
        import jax

        from ballista_tpu.ops.runtime import UnsupportedOnDevice, readback
        from ballista_tpu.parallel.mesh import put_sharded
        from ballista_tpu.physical.joinutil import (
            combined_key_codes,
            take_table,
        )
        from ballista_tpu.physical.joinutil import _refactorize

        if jax.process_count() > 1:
            # pod runs: collect_all below reads HOST-LOCAL rows, but the
            # mesh spans every process — shard_map would feed each host's
            # partial arrays to a global program (wrong results or a hang).
            # The aggregate path has a multihost protocol; the join does
            # not yet — decline to the host join.
            raise UnsupportedOnDevice("mesh join v1 is single-host")

        join = self.subplan
        if join.join_type not in (JoinType.INNER, JoinType.LEFT):
            raise UnsupportedOnDevice(f"mesh join type {join.join_type.value}")
        if join.filter is not None:
            raise UnsupportedOnDevice("mesh join residual filter")

        mesh = self._build_mesh(ctx)
        n_dev = int(np.prod(list(mesh.shape.values())))

        # the mesh replaces the hash exchange: read the repartition inputs
        left = collect_all(_strip_repartition(join.left), ctx)
        right = collect_all(_strip_repartition(join.right), ctx)
        if max(left.num_rows, right.num_rows) >= (1 << 31):
            raise UnsupportedOnDevice("row ids exceed int32")

        lkeys = [n for n, _ in join.on]
        rkeys = [n for _, n in join.on]
        bcodes, pcodes = combined_key_codes(
            [left.column(k) for k in lkeys], [right.column(k) for k in rkeys]
        )
        if left.num_rows == 0 or right.num_rows == 0:
            # no mesh work to do; join inline over what was collected
            return self._host_join_collected(
                left, right, bcodes, pcodes, reason="empty join side"
            )
        hi = max(int(bcodes.max()), int(pcodes.max()))
        if hi >= (1 << 31):
            # dense re-map: distinct count <= row count < 2^31. _refactorize
            # assigns the -1 null sentinel a dense code too — restore it, or
            # null keys would match each other on the mesh
            bnull, pnull = bcodes < 0, pcodes < 0
            bcodes, pcodes, _ = _refactorize(bcodes, pcodes)
            bcodes = np.where(bnull, -1, bcodes)
            pcodes = np.where(pnull, -1, pcodes)
        # build-key multiplicity bounds the static gather width: the staging
        # pass below already touches every code, so the max duplicate count
        # comes from one host bincount-equivalent over the valid build keys
        valid_b = bcodes >= 0
        if valid_b.any():
            _, dup_counts = np.unique(bcodes[valid_b], return_counts=True)
            max_mult = int(dup_counts.max())
        else:
            max_mult = 0

        # ---- host staging: bucket (code, rowid) by key ownership ------
        def stage_side(codes: np.ndarray):
            """Rows -> per-(source shard, dest shard) buckets, padded to a
            common capacity C. Source shard = row % n_dev (each shard would
            read its own partitions on a pod); dest = splitmix(code) % n_dev.
            Returns (codes [n_dev * n_dev*C], rowids same, C)."""
            n = len(codes)
            src = np.arange(n, dtype=np.int64) % n_dev
            dest = (_splitmix64(np.maximum(codes, 0)) % np.uint64(n_dev)).astype(np.int64)
            # bucket sizes per (src, dest)
            flat = src * n_dev + dest
            counts = np.bincount(flat, minlength=n_dev * n_dev)
            C = max(1, int(counts.max()))
            B = n_dev * C
            out_codes = np.full(n_dev * B, -1, dtype=np.int32)
            out_rows = np.full(n_dev * B, -1, dtype=np.int32)
            order = np.argsort(flat, kind="stable")
            sorted_flat = flat[order]
            starts = np.searchsorted(sorted_flat, np.arange(n_dev * n_dev))
            ends = np.searchsorted(sorted_flat, np.arange(n_dev * n_dev), side="right")
            for s in range(n_dev):
                for d in range(n_dev):
                    lo, hi_ = int(starts[s * n_dev + d]), int(ends[s * n_dev + d])
                    rows = order[lo:hi_]
                    base = s * B + d * C
                    out_codes[base: base + len(rows)] = codes[rows]
                    out_rows[base: base + len(rows)] = rows
            return out_codes, out_rows, C

        lc, lr, C_l = stage_side(bcodes)
        pc_, pr, C_p = stage_side(pcodes)

        # admission: smallest static gather width covering the build-key
        # multiplicity; past the ladder the mesh declines to the inline
        # host join (the sides are already collected and coded — no subplan
        # re-execution, no shuffle materialization). host_fallback, not
        # step_aside: the join leaves the device entirely, there is no next
        # device rung — only bench's join_paths kind keeps the admission-
        # tier distinction
        from ballista_tpu.ops import costmodel
        from ballista_tpu.ops.kernels import host_fallback, join_multiplicity_tier

        costmodel.configure(ctx.config)
        width, why = join_multiplicity_tier(max_mult, n_dev * n_dev * C_p)
        if width is None:
            host_fallback(why)
            return self._host_join_collected(
                left, right, bcodes, pcodes, kind="step_aside", reason=why
            )

        # admission rides the cost model (ISSUE 16 satellite): with BOTH
        # the mesh exchange and the inline host join warm for this shape,
        # skip the mesh — and its program compile — when the model says
        # the host wins. Cold on either side → admit, exactly the static
        # ladder above; the mesh path's check_mispredict below keeps its
        # rate honest, and join.host keeps averaging on every inline run,
        # so a side that grows past the host's sweet spot flips back.
        mesh_units = n_dev * n_dev * C_p * width
        mesh_pred = costmodel.predict("join.mesh", mesh_units)
        host_pred = costmodel.predict(
            "join.host", len(bcodes) + len(pcodes), engine="host"
        )
        if (
            mesh_pred is not None
            and host_pred is not None
            and mesh_pred > host_pred
        ):
            return self._host_join_collected(
                left, right, bcodes, pcodes, kind="host_declined",
                reason=(
                    f"cost model: mesh {mesh_pred:.4f}s > "
                    f"host {host_pred:.4f}s"
                ),
            )

        program = self._get_program(
            mesh, n_dev, C_l * n_dev, C_p * n_dev, width,
            want_left_bitmap=join.join_type == JoinType.LEFT,
        )
        # the mesh program's cost lands in the SAME store the single-chip
        # ladder consults (ISSUE 10): one ledger, every device join path.
        # The store is consulted, not just fed — a gross mispredict
        # re-tiers the bucket exactly like the single-chip gather, so the
        # mesh rate tracks the current machine too.
        import time as _time

        predicted = mesh_pred
        t_mesh0 = _time.perf_counter()
        outs = program(
            *(put_sharded(mesh, a) for a in (lc, lr, pc_, pr))
        )
        # the matching plane comes back over d2h: account for it, or the
        # bench readback fields undercount the mesh-join path
        # matched build rows per probe slot [n_dev * B_p, width], -1 = no match
        matched = readback(outs[0], rows=outs[0].shape[0])
        recv_prow = readback(outs[1])  # [n_dev * B_p] int32, -1 = pad
        dt_mesh = _time.perf_counter() - t_mesh0
        costmodel.observe("join.mesh", mesh_units, dt_mesh)
        costmodel.check_mispredict("join.mesh", mesh_units, predicted, dt_mesh)
        # hand predicted/observed back to execute()'s decision record so
        # mesh device decisions count toward the bench mispredict accounting
        self._mesh_cost = (predicted, dt_mesh)

        # flatten probe-slot-major: pad/null slots have all-(-1) rows, so
        # their repeat count is 0 and they vanish from the selection
        hits = matched >= 0
        lidx = matched[hits].astype(np.int64)
        ridx = np.repeat(recv_prow, hits.sum(axis=1)).astype(np.int64)
        left_out = take_table(left, lidx)
        right_out = take_table(right, ridx)
        if join.join_type == JoinType.LEFT:
            lmatched = readback(outs[2])  # bool over exchanged left slots
            recv_lrow = readback(outs[3])
            un = recv_lrow[(recv_lrow >= 0) & ~lmatched].astype(np.int64)
            if len(un):
                left_un = take_table(left, un)
                nulls = pa.table(
                    [pa.nulls(len(un), type=f.type) for f in right.schema],
                    schema=right.schema,
                )
                left_out = pa.concat_tables([left_out, left_un])
                right_out = pa.concat_tables([right_out, nulls])
        cols = list(left_out.columns) + list(right_out.columns)
        return pa.table(cols, schema=self.schema())

    def _host_join_collected(
        self, left: pa.Table, right: pa.Table,
        bcodes: np.ndarray, pcodes: np.ndarray,
        kind: str = "host_fallback", reason: str = "",
    ) -> pa.Table:
        """Vectorized host join over the already-collected sides — the
        decline path for shapes the mesh program cannot take (multiplicity
        past the admission tiers, empty sides). Costs one collect + one
        join pass, like the broadcast join these plans had before SPMD
        co-partitioning; no shuffle materialization, no re-execution."""
        from ballista_tpu.ops import costmodel
        from ballista_tpu.ops.runtime import record_join_path, record_routing
        from ballista_tpu.physical.joinutil import join_indices, take_table

        # every inline-host decline is one host routing decision, whatever
        # the reason — recorded here so no caller can forget it
        record_routing("host", "join.mesh")
        record_join_path(kind, reason or None)
        self._inline_host = True
        how = "inner" if self.subplan.join_type == JoinType.INNER else "left"
        with costmodel.timed("join.host", len(bcodes) + len(pcodes),
                             engine="host", predictive=False):
            li, ri = join_indices(bcodes, pcodes, how)
        lt = take_table(left, li)
        rt = take_table(right, ri)
        return pa.table(
            list(lt.columns) + list(rt.columns), schema=self.schema()
        )

    # ------------------------------------------------------------------
    def _get_program(self, mesh, n_dev: int, B_l: int, B_p: int, width: int,
                     want_left_bitmap: bool):
        """shard_map program, jitted once per (capacities, gather width,
        join shape): all_to_all exchange of (code, rowid) for both sides,
        then per-shard sort + paired searchsorted run-lengths + a
        bounded-width gather (M:N multiplicity). Outputs stay sharded
        (P('data')); every shard owns a disjoint key range, so its matches
        are global."""
        key = (n_dev, B_l, B_p, width, want_left_bitmap)
        if self._program_key == key:
            return self._program

        import jax
        import jax.numpy as jnp
        from ballista_tpu.ops.join import gather_matches, match_runs
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def a2a(x):
            return jax.lax.all_to_all(
                x, "data", split_axis=0, concat_axis=0, tiled=True
            )

        def per_shard(lcode, lrow, pcode, prow):
            # the exchange: every shard sends bucket d of its slice to
            # shard d and receives all buckets it owns — over ICI, no
            # materialized shuffle
            lcode, lrow = a2a(lcode), a2a(lrow)
            pcode, prow = a2a(pcode), a2a(prow)
            order = jnp.argsort(lcode, stable=True)
            sl = lcode[order]
            slrow = lrow[order]
            # shared M:N core (ops/join.py): per-probe run-lengths +
            # bounded-width gather of the matched build row ids
            starts, counts = match_runs(sl, pcode)
            matched = gather_matches(slrow, starts, counts, width)
            outs = [matched, prow]
            if want_left_bitmap:
                # a left slot is matched iff its key occurs among this
                # shard's probe codes — binary search over the sorted probe
                # plane (duplicate-safe, unlike a one-match scatter)
                sp = jnp.sort(pcode)
                lo = jnp.searchsorted(sp, sl, side="left")
                hi = jnp.searchsorted(sp, sl, side="right")
                hit_sorted = (hi > lo) & (sl >= 0)
                lmatched = jnp.zeros(B_l, dtype=bool).at[order].set(hit_sorted)
                outs.extend([lmatched, lrow])
            return tuple(outs)

        fn = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P("data"), P("data"), P("data"), P("data")),
            out_specs=tuple(
                P("data") for _ in range(4 if want_left_bitmap else 2)
            ),
            check_vma=False,
        )
        self._program = jax.jit(fn)
        self._program_key = key
        return self._program
