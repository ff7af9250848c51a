"""Distributed planner: split a physical plan into a DAG of query stages.

Generalizes the reference's rule set (rust/scheduler/src/planner.rs:114-198:
split at MergeExec / final HashAggregate / partition-count change) to one
rule: every exchange operator (RepartitionExec, MergeExec) becomes a stage
boundary — the child pipeline ends in a ShuffleWriterExec, the parent reads
it through UnresolvedShuffleExec until the scheduler substitutes concrete
locations (ref remove_unresolved_shuffles, planner.rs:236-269).

Parallel final aggregation arrives via the physical planner emitting
Partial -> Repartition(hash keys) -> Final, so here the exchange rule covers
the reference's aggregate rule too.

One rule runs on the finished stage DAG: a grouped aggregate whose groups
feed only the non-preserved side of an equi-join gets the other side's keys
ahead of its partial stage (`_link_keysets`).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ballista_tpu.distributed.stages import (
    ShuffleLocation,
    ShuffleReaderExec,
    ShuffleWriterExec,
    UnresolvedShuffleExec,
)
from ballista_tpu.physical.basic import MergeExec
from ballista_tpu.physical.plan import ExecutionPlan
from ballista_tpu.physical.repartition import RepartitionExec


class DistributedPlanner:
    def __init__(self, config=None) -> None:
        self._next_stage_id = 0
        self._config = config
        # joins whose aggregate side the last plan_query_stages narrowed
        self.keyset_links = 0

    def _new_stage_id(self) -> int:
        self._next_stage_id += 1
        return self._next_stage_id

    def plan_query_stages(
        self, job_id: str, plan: ExecutionPlan
    ) -> List[ShuffleWriterExec]:
        """Returns stages in dependency order; the last is the job's root
        (its shuffle output is the query result, one piece per partition)."""
        if self._config is not None and self._config.tpu_spmd():
            plan = self._fuse_spmd_aggregates(plan)
        stages: List[ShuffleWriterExec] = []
        root = self._visit(plan, job_id, stages)
        final = ShuffleWriterExec(job_id, self._new_stage_id(), root, None)
        stages.append(final)
        self.keyset_links = _link_keysets(stages)
        return stages

    def _fuse_spmd_aggregates(self, node: ExecutionPlan) -> ExecutionPlan:
        """Config-gated TPU restructuring (SURVEY §7 step 5):

        - a HashAggregate(Final) <- Repartition(hash) <- HashAggregate(
          Partial) subtree — which the exchange rule below would split into
          two stages plus a materialized shuffle — becomes ONE
          SpmdAggregateExec stage whose exchange is a psum over the mesh;
        - a co-partitionable HashJoin (INNER/LEFT, no residual filter)
          becomes ONE SpmdJoinExec stage whose hash exchange is
          lax.all_to_all over the mesh (SURVEY §2.8's RepartitionExec
          mapping) instead of two materialized shuffles.

        Both keep the untouched subtree inside for serde + host fallback."""
        from ballista_tpu.logical.plan import JoinType
        from ballista_tpu.parallel.spmd_join import SpmdJoinExec
        from ballista_tpu.parallel.spmd_stage import SpmdAggregateExec
        from ballista_tpu.physical.aggregate import AggregateMode, HashAggregateExec
        from ballista_tpu.physical.join import HashJoinExec

        children = [self._fuse_spmd_aggregates(c) for c in node.children()]
        if children:
            node = node.with_children(children)
        if (
            isinstance(node, HashAggregateExec)
            and node.mode == AggregateMode.FINAL
            and isinstance(node.input, RepartitionExec)
            and isinstance(node.input.input, HashAggregateExec)
            and node.input.input.mode == AggregateMode.PARTIAL
        ):
            return SpmdAggregateExec(node)
        if (
            isinstance(node, HashJoinExec)
            and node.partitioned  # only fuse when there IS an exchange pair
            and node.join_type in (JoinType.INNER, JoinType.LEFT)
            and node.filter is None
        ):
            return SpmdJoinExec(node)
        return node

    def _visit(
        self, node: ExecutionPlan, job_id: str, stages: List[ShuffleWriterExec]
    ) -> ExecutionPlan:
        children = [self._visit(c, job_id, stages) for c in node.children()]
        if isinstance(node, RepartitionExec):
            child = children[0]
            stage = ShuffleWriterExec(
                job_id, self._new_stage_id(), child, node.partitioning
            )
            stages.append(stage)
            return UnresolvedShuffleExec(
                stage.stage_id, node.schema(), node.partitioning.partition_count()
            )
        if isinstance(node, MergeExec):
            child = children[0]
            stage = ShuffleWriterExec(job_id, self._new_stage_id(), child, None)
            stages.append(stage)
            reader = UnresolvedShuffleExec(
                stage.stage_id,
                node.schema(),
                child.output_partitioning().partition_count(),
                identity=True,
            )
            return MergeExec(reader)
        if children:
            return node.with_children(children)
        return node


def _link_keysets(stages: List[ShuffleWriterExec]) -> int:
    """Give a grouped aggregate the keys that its one consumer can match.

    For an equi-join (INNER, LEFT, SEMI, ANTI; no residual filter) whose
    right input reads a stage of a FINAL aggregate grouped exactly by the
    right keys, and whose left input reads a stage too, the aggregate's
    PARTIAL stage becomes SEMI(partial, every partition of the left stage):
    right rows whose key the left side lacks never reach that join's
    output, a NULL key matches nothing, and every partial of a dropped key
    goes in every task, so FINAL's states for the kept keys are unchanged.
    The link is plan nodes only, so serde and the scheduler's dependency
    resolution carry it as they carry any plan. Rewrites `stages` in place
    and returns the number of links."""
    at = {s.stage_id: i for i, s in enumerate(stages)}
    readers: Dict[int, int] = {}
    for s in stages:
        for u in find_unresolved_shuffles(s.input):
            readers[u.stage_id] = readers.get(u.stage_id, 0) + 1
    links = 0
    for s in list(stages):
        for join in _hash_joins(s.input):
            link = _keyset_link(join, stages, at, readers)
            if link is not None:
                i, semi = link
                old = stages[i]
                stages[i] = ShuffleWriterExec(
                    old.job_id, old.stage_id, semi, old.shuffle_output_partitioning
                )
                readers[join.left.stage_id] += 1
                links += 1
    return links


def _hash_joins(plan: ExecutionPlan) -> List[ExecutionPlan]:
    from ballista_tpu.physical.join import HashJoinExec

    out = [plan] if isinstance(plan, HashJoinExec) else []
    for c in plan.children():
        out.extend(_hash_joins(c))
    return out


def _keyset_link(join, stages, at, readers) -> Optional[Tuple[int, ExecutionPlan]]:
    """(index of the PARTIAL stage, its new root) where `join` qualifies."""
    from ballista_tpu.logical.plan import JoinType
    from ballista_tpu.physical.aggregate import AggregateMode, HashAggregateExec
    from ballista_tpu.physical.basic import ProjectionExec
    from ballista_tpu.physical.expr import ColumnExpr
    from ballista_tpu.physical.join import HashJoinExec

    left, right = join.left, join.right
    if (
        join.join_type not in (JoinType.INNER, JoinType.LEFT, JoinType.SEMI, JoinType.ANTI)
        or join.filter is not None
        or not isinstance(left, UnresolvedShuffleExec)
        or not isinstance(right, UnresolvedShuffleExec)
        or readers.get(right.stage_id) != 1
    ):
        return None
    # the right keys, traced through rename-carrying projections to the
    # FINAL aggregate's output columns
    node = stages[at[right.stage_id]].input
    cols = [node.schema().get_field_index(r) for _, r in join.on]
    while isinstance(node, ProjectionExec):
        if min(cols) < 0 or not all(isinstance(node.exprs[c][0], ColumnExpr) for c in cols):
            return None
        cols = [node.exprs[c][0].index for c in cols]
        node = node.input
    if not (isinstance(node, HashAggregateExec) and node.mode == AggregateMode.FINAL):
        return None
    k = len(node.group_exprs)
    feed = node.input
    if (
        k == 0
        or sorted(cols) != list(range(k))
        or not isinstance(feed, UnresolvedShuffleExec)
        or readers.get(feed.stage_id) != 1
        # the key set's stage must come first in dependency order
        or at[left.stage_id] > at[feed.stage_id]
    ):
        return None
    partial = stages[at[feed.stage_id]].input
    if not (
        isinstance(partial, HashAggregateExec)
        and partial.mode == AggregateMode.PARTIAL
        and len(partial.group_exprs) == k
    ):
        return None
    names = partial.schema().names
    if any(partial.schema().get_field_index(names[c]) != c for c in cols):
        return None  # a key name the join could not address
    keys = UnresolvedShuffleExec(
        left.stage_id, left.schema(), left.partition_count, identity=left.identity
    )
    on = [(names[c], l) for c, (l, _) in zip(cols, join.on)]
    return at[feed.stage_id], HashJoinExec(partial, keys, on, JoinType.SEMI)


def find_unresolved_shuffles(plan: ExecutionPlan) -> List[UnresolvedShuffleExec]:
    out: List[UnresolvedShuffleExec] = []
    if isinstance(plan, UnresolvedShuffleExec):
        out.append(plan)
    for c in plan.children():
        out.extend(find_unresolved_shuffles(c))
    return out


def remove_unresolved_shuffles(
    plan: ExecutionPlan, locations_by_stage: Dict[int, List[ShuffleLocation]]
) -> ExecutionPlan:
    """Substitute concrete ShuffleReaderExec for each placeholder
    (ref planner.rs:236-269)."""
    if isinstance(plan, UnresolvedShuffleExec):
        locs = locations_by_stage.get(plan.stage_id)
        if locs is None:
            raise KeyError(f"no locations for stage {plan.stage_id}")
        return ShuffleReaderExec(
            locs, plan.schema(), plan.partition_count, identity=plan.identity
        )
    children = [
        remove_unresolved_shuffles(c, locations_by_stage) for c in plan.children()
    ]
    if children:
        return plan.with_children(children)
    return plan
