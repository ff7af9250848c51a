"""Physical plan base classes.

An ExecutionPlan mirrors the reference's (DataFusion's) trait: a schema, an
output partitioning, children, and ``execute(partition)`` yielding Arrow
record batches (reference rust/core/src/execution_plans/query_stage.rs:59-85
shows the passthrough pattern). ``TaskContext`` carries session config and the
kernel backend (cpu Arrow oracle vs. tpu JAX lowering) — the executor-selection
boundary from BASELINE's north star.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional

import pyarrow as pa

from ballista_tpu.config import BallistaConfig
from ballista_tpu.errors import PlanError
from ballista_tpu.utils import tracing


class Partitioning:
    """Output partitioning declaration (reference PhysicalHashRepartition /
    output_partitioning())."""

    def __init__(self, scheme: str, n: int, exprs: Optional[list] = None) -> None:
        assert scheme in ("unknown", "round_robin", "hash")
        self.scheme = scheme
        self.n = n
        self.exprs = exprs or []

    @classmethod
    def unknown(cls, n: int) -> "Partitioning":
        return cls("unknown", n)

    @classmethod
    def round_robin(cls, n: int) -> "Partitioning":
        return cls("round_robin", n)

    @classmethod
    def hash(cls, exprs: list, n: int) -> "Partitioning":
        return cls("hash", n, exprs)

    def partition_count(self) -> int:
        return self.n

    def __repr__(self) -> str:
        if self.scheme == "hash":
            return f"Hash([{', '.join(str(e) for e in self.exprs)}], {self.n})"
        return f"{self.scheme}({self.n})"


class TaskContext:
    """Per-task runtime context: config, kernel backend, shuffle fetcher."""

    def __init__(
        self,
        config: Optional[BallistaConfig] = None,
        shuffle_fetcher=None,
        work_dir: Optional[str] = None,
        job_id: str = "",
        attempt: int = 0,
        executor_id: str = "",
    ) -> None:
        self.config = config or BallistaConfig()
        # shuffle_fetcher: callable(PartitionLocation) -> Iterator[RecordBatch];
        # bound by the executor runtime for ShuffleReaderExec.
        self.shuffle_fetcher = shuffle_fetcher
        self.work_dir = work_dir
        self.job_id = job_id
        # which attempt of the task this context serves: part of the chaos
        # injection key so a retried attempt draws a fresh fault verdict
        self.attempt = attempt
        # which executor runs this task: the HBM-resident exchange registry
        # (ops/exchange.py, ISSUE 16) keys entries per executor, so a
        # StandaloneCluster's co-resident executors never see false "local"
        # hits. Empty (the in-process/local-engine default) disables the
        # exchange tier for this context.
        self.executor_id = executor_id

    @property
    def batch_size(self) -> int:
        return self.config.batch_size()

    @property
    def backend(self) -> str:
        """The kernel backend this task runs on. "tpu" is only ever
        returned once the device behind it is established (ops/device.py):
        every device dispatch branches on this property, so none can run
        on a platform nobody checked."""
        backend = self.config.backend()
        if backend == "tpu":
            from ballista_tpu.ops import device

            device.establish(self.config)
        return backend


_END = object()


def _traced_pulls(execute):
    """`execute` whose every pull runs inside an `executor.operator` span:
    `next()` of the batch iterator in the span (the first pull calls
    `execute` too, and the last, which ends it, gets one as well), the yield
    outside it, so no span is open while the consumer works and the pulls
    of the children nest on the thread's stack."""

    @functools.wraps(execute)
    def pulls(self, *args, **kw):
        it = None
        while True:
            with tracing.span("executor.operator", op=type(self).__name__) as sp:
                if it is None:
                    it = iter(execute(self, *args, **kw))
                batch = next(it, _END)
                sp.set(rows=0 if batch is _END else batch.num_rows)
            if batch is _END:
                return
            yield batch

    return pulls


class ExecutionPlan:
    """Base physical operator. Each subclass's own `execute` is wrapped once,
    when the class is made, by `_traced_pulls`; a class that is its own
    span (`ShuffleWriterExec`, `shuffle.write`) sets `traced_pulls = False`."""

    traced_pulls = True

    def __init_subclass__(cls, **kw) -> None:
        super().__init_subclass__(**kw)
        if "execute" in cls.__dict__ and cls.traced_pulls:
            cls.execute = _traced_pulls(cls.__dict__["execute"])

    def schema(self) -> pa.Schema:
        raise NotImplementedError

    def output_partitioning(self) -> Partitioning:
        return Partitioning.unknown(1)

    def children(self) -> List["ExecutionPlan"]:
        return []

    def with_children(self, children: List["ExecutionPlan"]) -> "ExecutionPlan":
        if children:
            raise PlanError(f"{type(self).__name__} takes no children")
        return self

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        raise NotImplementedError

    # -- display -----------------------------------------------------------
    def fmt(self) -> str:
        return type(self).__name__

    def display_indent(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.fmt()]
        for c in self.children():
            lines.append(c.display_indent(indent + 1))
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.display_indent()


def collect_partition(
    plan: ExecutionPlan, partition: int, ctx: TaskContext, **execute_kw
) -> pa.Table:
    """Drain one partition into a Table (reference utils.rs collect_stream).
    `execute_kw` goes to the plan's own `execute` (a SEMI join hands its
    aggregate input the key set, HashAggregateExec.execute)."""
    batches = list(plan.execute(partition, ctx, **execute_kw))
    if not batches:
        return pa.table(
            {f.name: pa.array([], type=f.type) for f in plan.schema()},
            schema=plan.schema(),
        )
    return pa.Table.from_batches(batches, schema=plan.schema())


def collect_all(plan: ExecutionPlan, ctx: TaskContext) -> pa.Table:
    """Drain every partition (reference executor CollectExec select_all,
    rust/executor/src/collect.rs:70-101)."""
    tables = [
        collect_partition(plan, p, ctx)
        for p in range(plan.output_partitioning().partition_count())
    ]
    return pa.concat_tables(tables)


def batch_table(table: pa.Table, batch_size: int) -> Iterator[pa.RecordBatch]:
    """Re-chunk a table into batches of at most batch_size rows."""
    if table.num_rows == 0:
        yield from table.to_batches()
        return
    for b in table.combine_chunks().to_batches(max_chunksize=batch_size):
        yield b
