"""Join operators.

HashJoinExec follows the reference's collect-left build model
(HashJoinExecNode, rust/core/proto/ballista.proto:386-397; serde
rust/core/src/serde/physical_plan/from_proto.rs:176-214): the left child is
collected once as the build side, the right child is probed per-partition.
SEMI/ANTI joins (added beyond the reference's Inner/Left/Right for TPC-H
subquery decorrelation) build on the right and probe left, preserving left
partitioning.
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from ballista_tpu.errors import PlanError
from ballista_tpu.logical.plan import JoinType
from ballista_tpu.physical.joinutil import (
    combined_key_codes,
    int32_key_codes,
    join_indices,
    take_table,
)
from ballista_tpu.physical.plan import (
    ExecutionPlan,
    Partitioning,
    TaskContext,
    batch_table,
    collect_all,
    collect_partition,
)
from ballista_tpu.utils import tracing
from ballista_tpu.utils.locks import make_lock


class HashJoinExec(ExecutionPlan):
    def __init__(
        self,
        left: ExecutionPlan,
        right: ExecutionPlan,
        on: List[Tuple[str, str]],  # (left column name, right column name)
        join_type: JoinType,
        filter=None,  # residual PhysicalExpr over concat(left, right) schema
        partitioned: bool = False,
    ) -> None:
        self.left = left
        self.right = right
        self.on = on
        self.join_type = join_type
        self.filter = filter
        # both inputs hash-co-partitioned on the join keys: each partition
        # pair joins independently (the planner arranges this for outer
        # joins, removing the single-partition probe wall — every key lands
        # in exactly one partition, so per-partition unmatched rows are
        # globally unmatched)
        self.partitioned = partitioned
        if filter is not None and join_type not in (JoinType.SEMI, JoinType.ANTI):
            raise PlanError("join residual filter only supported for SEMI/ANTI")
        if join_type in (JoinType.SEMI, JoinType.ANTI):
            self._schema = left.schema()
        else:
            self._schema = pa.schema(list(left.schema()) + list(right.schema()))
        self._build_lock = make_lock("physical.join._build_lock")
        self._build_table: Optional[pa.Table] = None  # guarded-by: self._build_lock

    def schema(self) -> pa.Schema:
        return self._schema

    def output_partitioning(self) -> Partitioning:
        if self.join_type in (JoinType.SEMI, JoinType.ANTI):
            return self.left.output_partitioning()
        return self.right.output_partitioning()

    def children(self) -> List[ExecutionPlan]:
        return [self.left, self.right]

    def with_children(self, children: List[ExecutionPlan]) -> "HashJoinExec":
        return HashJoinExec(
            children[0], children[1], self.on, self.join_type,
            filter=self.filter, partitioned=self.partitioned,
        )

    # executes an arbitrary child plan while holding the build lock —
    # static call resolution cannot chase plan dispatch, so the reachable
    # lock set is declared (witness-verified)
    # may-acquire: group:exec_substrate
    def _collect_build(self, side: ExecutionPlan, ctx: TaskContext) -> pa.Table:
        with self._build_lock:
            if self._build_table is None:
                self._build_table = collect_all(side, ctx)
            return self._build_table

    def _keyset(self, build: pa.Table) -> dict:
        """What a SEMI join hands an aggregate input grouped exactly by its
        keys (the planner's key-set link, distributed/planner.py): the build
        side's key columns in group order, for this execution alone. The
        join below still decides every row, so the aggregate may return any
        superset of the member groups."""
        from ballista_tpu.physical.aggregate import HashAggregateExec

        left = self.left
        if self.join_type != JoinType.SEMI or not isinstance(left, HashAggregateExec):
            return {}
        groups = left.schema().names[: len(left.group_exprs)]
        right_of = dict(self.on)
        if not groups or sorted(right_of) != sorted(groups) or len(self.on) != len(groups):
            return {}
        return {"keyset": [build.column(right_of[g]) for g in groups]}

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        left_keys = [n for n, _ in self.on]
        right_keys = [n for _, n in self.on]

        if self.join_type in (JoinType.SEMI, JoinType.ANTI):
            # build on RIGHT, probe LEFT partitions
            build = self._collect_build(self.right, ctx)
            probe = collect_partition(self.left, partition, ctx, **self._keyset(build))
            build_keys = [build.column(k) for k in right_keys]
            probe_keys = [probe.column(k) for k in left_keys]
            asked = (self.filter is None and ctx.backend == "tpu"
                     and ctx.config.tpu_device_join())
            if asked:
                # EXISTS / NOT EXISTS as device membership counting (q22):
                # the per-probe counts plane decides kept rows — counts > 0
                # keeps SEMI rows, counts == 0 keeps ANTI rows — exactly
                # the host oracle's semi_right/anti_right selections, so
                # results are bit-identical. Declines (None, with a
                # recorded reason) fall through to the host path.
                from ballista_tpu.ops import aotcache, costmodel
                from ballista_tpu.ops.join import device_membership_counts

                aotcache.configure(ctx.config)
                costmodel.configure(ctx.config)
                bcodes, pcodes = combined_key_codes(build_keys, probe_keys)
                counts = device_membership_counts(*int32_key_codes(bcodes, pcodes))
                if counts is not None:
                    keep = counts > 0 if self.join_type == JoinType.SEMI \
                        else counts == 0
                    out = probe.take(pa.array(np.nonzero(keep)[0]))
                    yield from batch_table(out, ctx.batch_size)
                    return
            with self._host_join(build, probe, declined=asked) as sp:
                if not asked:
                    bcodes, pcodes = combined_key_codes(build_keys, probe_keys)
                if self.filter is None:
                    how = "semi_right" if self.join_type == JoinType.SEMI else "anti_right"
                    keep_idx, _ = join_indices(bcodes, pcodes, how)
                else:
                    keep_idx = self._filtered_semi_indices(build, probe, bcodes, pcodes)
                out = probe.take(pa.array(keep_idx))
                sp.set(out_rows=out.num_rows)
            yield from batch_table(out, ctx.batch_size)
            return

        if self.partitioned:
            build = collect_partition(self.left, partition, ctx)
        else:
            build = self._collect_build(self.left, ctx)
        probe = collect_partition(self.right, partition, ctx)
        device_declined = False
        if (self.join_type == JoinType.INNER and ctx.backend == "tpu"
                and ctx.config.tpu_device_join()):
            # device M:N join: sorted paired binary search + bounded-width
            # gather on TPU, duplicate build keys included; declines (None,
            # always with a recorded reason) fall through to the host join.
            # The cost model (ISSUE 10) rides the config: partial offload,
            # extended tiers, and build-side switching on observed
            # cardinality misestimates — all bit-identical to the host.
            from ballista_tpu.ops import aotcache, costmodel
            from ballista_tpu.ops.join import try_device_inner_join

            aotcache.configure(ctx.config)
            costmodel.configure(ctx.config)
            res = try_device_inner_join(
                build, probe, left_keys, right_keys, config=ctx.config
            )
            if res is not None:
                left_idx, right_idx = res
                left_out = take_table(build, left_idx)
                right_out = take_table(probe, right_idx)
                cols = list(left_out.columns) + list(right_out.columns)
                out = pa.table(cols, schema=self._schema)
                yield from batch_table(out, ctx.batch_size)
                return
            device_declined = True
        with self._host_join(build, probe, declined=device_declined) as sp:
            bcodes, pcodes = combined_key_codes(
                [build.column(k) for k in left_keys],
                [probe.column(k) for k in right_keys],
            )
            how = {
                JoinType.INNER: "inner",
                JoinType.LEFT: "left",
                JoinType.RIGHT: "right",
                JoinType.FULL: "full",
            }[self.join_type]
            if (
                how in ("left", "full")
                and not self.partitioned
                and self.right.output_partitioning().partition_count() > 1
            ):
                raise PlanError(
                    f"{how} join requires co-partitioned inputs or a "
                    "single-partition probe side"
                )
            if device_declined:
                # the host join after a device decline is the device's
                # alternative cost: measure it so tier selection learns what
                # host-wholesale actually costs at this scale
                from ballista_tpu.ops import costmodel

                with costmodel.timed("join.host", len(bcodes) + len(pcodes),
                                     engine="host", predictive=False):
                    left_idx, right_idx = join_indices(bcodes, pcodes, how)
            else:
                left_idx, right_idx = join_indices(bcodes, pcodes, how)
            left_out = take_table(build, left_idx)
            right_out = take_table(probe, right_idx)
            cols = list(left_out.columns) + list(right_out.columns)
            out = pa.table(cols, schema=self._schema)
            sp.set(out_rows=out.num_rows)
        yield from batch_table(out, ctx.batch_size)

    def _host_join(self, build: pa.Table, probe: pa.Table, declined: bool) -> tracing.Span:
        """`executor.host_join`: a join the host answers, from its key codes
        to the rows it takes; `declined` where the device was asked first.
        It ends with the `out_rows` it emitted."""
        return tracing.span("executor.host_join", type=self.join_type.value,
                            build_rows=build.num_rows, probe_rows=probe.num_rows,
                            declined=declined)

    def _filtered_semi_indices(
        self,
        build: pa.Table,
        probe: pa.Table,
        bcodes: np.ndarray,
        pcodes: np.ndarray,
    ) -> np.ndarray:
        """SEMI/ANTI with a residual predicate: expand the inner join on the
        equi keys, evaluate the filter over concat(probe-cols, build-cols),
        keep probe rows with >=1 surviving match (SEMI) or none (ANTI)."""
        import pyarrow.compute as pc

        build_idx, probe_idx = join_indices(bcodes, pcodes, "inner")
        matched = np.zeros(probe.num_rows, dtype=bool)
        if len(probe_idx):
            probe_rows = probe.take(pa.array(probe_idx))
            build_rows = build.take(pa.array(build_idx))
            combined_schema = pa.schema(list(probe.schema) + list(build.schema))
            combined = pa.table(
                list(probe_rows.columns) + list(build_rows.columns),
                schema=combined_schema,
            ).combine_chunks()
            batches = combined.to_batches()
            offset = 0
            for b in batches:
                mask = self.filter.evaluate(b)
                mask_np = pc.fill_null(mask, False).to_numpy(zero_copy_only=False)
                hits = probe_idx[offset: offset + b.num_rows][mask_np.astype(bool)]
                matched[hits] = True
                offset += b.num_rows
        if self.join_type == JoinType.SEMI:
            return np.nonzero(matched)[0]
        return np.nonzero(~matched)[0]

    def fmt(self) -> str:
        on = ", ".join(f"{l} = {r}" for l, r in self.on)
        extra = f", filter={self.filter}" if self.filter is not None else ""
        return f"HashJoinExec: type={self.join_type.value}, on=[{on}]{extra}"


class CrossJoinExec(ExecutionPlan):
    """Cartesian product: left collected as build, right probed per-partition."""

    def __init__(self, left: ExecutionPlan, right: ExecutionPlan) -> None:
        self.left = left
        self.right = right
        self._schema = pa.schema(list(left.schema()) + list(right.schema()))
        self._build_lock = make_lock("physical.join._build_lock")
        self._build_table: Optional[pa.Table] = None  # guarded-by: self._build_lock

    def schema(self) -> pa.Schema:
        return self._schema

    def output_partitioning(self) -> Partitioning:
        return self.right.output_partitioning()

    def children(self) -> List[ExecutionPlan]:
        return [self.left, self.right]

    def with_children(self, children: List[ExecutionPlan]) -> "CrossJoinExec":
        return CrossJoinExec(children[0], children[1])

    # may-acquire: group:exec_substrate
    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        with self._build_lock:
            if self._build_table is None:
                self._build_table = collect_all(self.left, ctx)
            # read under the lock: the unguarded read-after-release here
            # was the ISSUE 14 sweep's first guarded-by finding
            build = self._build_table
        probe = collect_partition(self.right, partition, ctx)
        nb, np_ = build.num_rows, probe.num_rows
        if nb == 0 or np_ == 0:
            return
        left_idx = np.tile(np.arange(nb, dtype=np.int64), np_)
        right_idx = np.repeat(np.arange(np_, dtype=np.int64), nb)
        left_out = build.take(pa.array(left_idx))
        right_out = probe.take(pa.array(right_idx))
        cols = list(left_out.columns) + list(right_out.columns)
        out = pa.table(cols, schema=self._schema)
        yield from batch_table(out, ctx.batch_size)

    def fmt(self) -> str:
        return "CrossJoinExec"
