"""Serde roundtrip tests — the reference's largest test surface
(rust/core/src/serde/logical_plan/mod.rs roundtrip_test! macro cases and
physical_plan/mod.rs). Equality by display-string comparison, like the
reference's format!-based assertion (mod.rs:43-46)."""

import datetime

import pyarrow as pa
import pytest

from ballista_tpu.datasource import MemoryTableSource
from ballista_tpu.logical import expr as lx
from ballista_tpu.logical import plan as lp
from ballista_tpu.logical.builder import LogicalPlanBuilder
from ballista_tpu.serde.logical import (
    expr_from_proto,
    expr_to_proto,
    plan_from_proto,
    plan_to_proto,
)
from ballista_tpu.logical.expr import col, functions as F, lit

SCHEMA = pa.schema(
    [
        pa.field("a", pa.int64()),
        pa.field("b", pa.float64()),
        pa.field("c", pa.string()),
        pa.field("d", pa.date32()),
    ]
)


def roundtrip_expr(e: lx.Expr):
    msg = expr_to_proto(e)
    data = msg.SerializeToString()
    from ballista_tpu.proto import ballista_pb2 as pb

    decoded = pb.LogicalExprNode()
    decoded.ParseFromString(data)
    e2 = expr_from_proto(decoded)
    assert str(e2) == str(e), f"{e2} != {e}"
    return e2


EXPR_CASES = [
    col("a"),
    lx.Column("x", "t"),
    lit(42),
    lit(3.5),
    lit("hello"),
    lit(True),
    lit(None),
    lx.Literal(datetime.date(1994, 1, 1), pa.date32()),
    lx.Literal(datetime.datetime(1994, 1, 1, 12, 30), pa.timestamp("us")),
    col("a") + lit(1),
    col("a") - lit(1),
    (col("a") * lit(2)) / col("b"),
    col("a") == lit(5),
    (col("a") > lit(1)) & (col("b") < lit(2.0)),
    (col("a") >= lit(1)) | (col("b") <= lit(2.0)),
    ~(col("a") != lit(0)),
    -col("b"),
    col("c").like("%foo%"),
    col("c").not_like("bar%"),
    lx.Like(col("c"), lit("x_%"), True, "\\"),
    col("a").is_null(),
    col("a").is_not_null(),
    col("a").between(lit(1), lit(10)),
    col("a").between(lit(1), lit(10), negated=True),
    col("c").isin(["x", "y"]),
    col("a").isin([1, 2, 3], negated=True),
    lx.Case(None, [(col("a") > lit(0), lit("pos"))], lit("neg")),
    lx.Case(col("a"), [(lit(1), lit("one")), (lit(2), lit("two"))], None),
    col("a").cast(pa.float32()),
    lx.TryCast(col("c"), pa.int64()),
    lx.ScalarFunction("sqrt", [col("b")]),
    lx.ScalarFunction("substring", [col("c"), lit(1), lit(2)]),
    lx.ScalarFunction("extract", [lit("year"), col("d")]),
    F.sum(col("a")),
    F.avg(col("b")),
    F.min(col("a")),
    F.max(col("a")),
    F.count(col("c")),
    F.count(distinct=True),
    lx.AggregateExpr("count", col("c"), distinct=True),
    col("a").sort(ascending=False, nulls_first=True),
    lx.Wildcard(),
]


@pytest.mark.parametrize("e", EXPR_CASES, ids=lambda e: str(e)[:40])
def test_expr_roundtrip(e):
    roundtrip_expr(e)


# scalar edge values, mirroring the reference's ScalarValue matrix
# (rust/core/src/serde/logical_plan/mod.rs:58-920 covers every variant with
# boundary values)
SCALAR_EDGE_CASES = [
    lit(0),
    lit(-1),
    lit(2**63 - 1),
    lit(-(2**63)),
    lit(2**31),          # beyond int32
    lit(0.0),
    lit(-0.0),
    lit(float("inf")),
    lit(float("-inf")),
    lit(float("nan")),
    lit(5e-324),         # smallest subnormal double
    lit(1.7976931348623157e308),
    lit(""),
    lit("unicode ✓ ☃ 日本語"),
    lit("embedded 'quotes' and \"doubles\""),
    lit("newline\nand\ttab"),
    lx.Literal(datetime.date(1970, 1, 1), pa.date32()),
    lx.Literal(datetime.date(1904, 2, 29), pa.date32()),   # pre-epoch leap day
    lx.Literal(datetime.date(2262, 4, 11), pa.date32()),
    lx.Literal(datetime.datetime(1969, 12, 31, 23, 59, 59, 999999),
               pa.timestamp("us")),  # negative epoch micros
    lx.Literal(False, pa.bool_()),
]


@pytest.mark.parametrize("e", SCALAR_EDGE_CASES, ids=lambda e: repr(str(e))[:48])
def test_scalar_edge_roundtrip(e):
    roundtrip_expr(e)


def test_scalar_edge_values_survive_exactly():
    """Beyond display equality: the decoded literal VALUE must be bit-equal
    (display strings can hide float rounding)."""
    import math

    for e in SCALAR_EDGE_CASES:
        msg = expr_to_proto(e)
        from ballista_tpu.proto import ballista_pb2 as pb

        decoded = pb.LogicalExprNode()
        decoded.ParseFromString(msg.SerializeToString())
        e2 = expr_from_proto(decoded)
        v1, v2 = e.value, e2.value
        if isinstance(v1, float) and math.isnan(v1):
            assert math.isnan(v2)
        else:
            assert v1 == v2 and type(v1) is type(v2), (v1, v2)
            if isinstance(v1, float):
                assert math.copysign(1, v1) == math.copysign(1, v2)


def _scan() -> LogicalPlanBuilder:
    table = pa.table(
        {
            "a": pa.array([1, 2, 3], type=pa.int64()),
            "b": pa.array([1.0, 2.0, 3.0]),
            "c": pa.array(["x", "y", "z"]),
            "d": pa.array([datetime.date(2020, 1, 1)] * 3),
        }
    )
    return LogicalPlanBuilder.scan("t", MemoryTableSource.from_table(table, 2))


def roundtrip_plan(plan: lp.LogicalPlan):
    msg = plan_to_proto(plan)
    decoded_bytes = msg.SerializeToString()
    from ballista_tpu.proto import ballista_pb2 as pb

    decoded = pb.LogicalPlanNode()
    decoded.ParseFromString(decoded_bytes)
    p2 = plan_from_proto(decoded)
    assert str(p2) == str(plan)
    assert p2.schema().equals(plan.schema())
    return p2


def _joins():
    left = _scan().alias("l")
    right = _scan().alias("r")
    inner = left.join(
        right,
        [(lx.Column("a", "l"), lx.Column("a", "r"))],
        lp.JoinType.INNER,
    ).build()
    semi = left.join(
        _scan().alias("r2"),
        [(lx.Column("a", "l"), lx.Column("a", "r2"))],
        lp.JoinType.SEMI,
        filter=lx.Column("b", "l") > lit(1.0),
    ).build()
    return inner, semi


# every logical plan the round-trip cases build, by name
LOGICAL_PLANS = {
    "scan_projection_filter": lambda: (
        _scan()
        .filter(col("a") > lit(1))
        .project([col("a"), (col("b") * lit(2.0)).alias("b2")])
        .build()
    ),
    "aggregate_sort_limit": lambda: (
        _scan()
        .aggregate([col("c")], [F.sum(col("a")).alias("s"), F.avg(col("b")).alias("m")])
        .sort([col("s").sort(ascending=False)])
        .limit(5)
        .build()
    ),
    "join_inner": lambda: _joins()[0],
    "join_semi_filtered": lambda: _joins()[1],
    "repartition_distinct": lambda: (
        _scan().repartition_hash([col("a")], 4).distinct().build()
    ),
    "union": lambda: _scan().union([_scan()]).build(),
    "empty": lambda: lp.EmptyRelation(True, pa.schema([pa.field("x", pa.int32())])),
    "create_external_table": lambda: lp.CreateExternalTable(
        "t2", "/tmp/x", "csv", True, SCHEMA
    ),
    "memory_scan": lambda: _scan().build(),
}


@pytest.mark.parametrize("name", [n for n in LOGICAL_PLANS if n != "memory_scan"])
def test_roundtrip_logical(name):
    roundtrip_plan(LOGICAL_PLANS[name]())


def test_roundtrip_memory_scan_preserves_data():
    p2 = roundtrip_plan(LOGICAL_PLANS["memory_scan"]())
    # memory partitions carry actual rows over the wire (IPC)
    assert p2.source.num_partitions() == 2
    total = sum(b.num_rows for part in p2.source.partitions for b in part)
    assert total == 3


def _physical(df_builder):
    from ballista_tpu.engine import ExecutionContext

    ctx = ExecutionContext()
    return ctx.create_physical_plan(df_builder.build())


def _join_sort_limit():
    left = _scan().alias("l")
    right = _scan().alias("r")
    return _physical(
        left.join(right, [(lx.Column("a", "l"), lx.Column("a", "r"))])
        .sort([lx.Column("a", "l").sort()])
        .limit(2)
    )


def _shuffle_writer():
    from ballista_tpu.distributed.stages import ShuffleWriterExec
    from ballista_tpu.physical.expr import ColumnExpr
    from ballista_tpu.physical.plan import Partitioning

    return ShuffleWriterExec(
        "job1", 3, _physical(_scan()), Partitioning.hash([ColumnExpr("a", 0)], 4)
    )


def _shuffle_reader():
    from ballista_tpu.distributed.stages import ShuffleLocation, ShuffleReaderExec

    return ShuffleReaderExec(
        [ShuffleLocation("e1", "h", 50051, "/tmp/x", stage_id=3, map_partition=1)],
        SCHEMA,
        4,
    )


def _unresolved_shuffle():
    from ballista_tpu.distributed.stages import UnresolvedShuffleExec

    return UnresolvedShuffleExec(7, SCHEMA, 2)


def _basic(kind, *args):
    """Remaining node variants (ref from_proto.rs:58-345 covers all 15)."""
    from ballista_tpu.physical import basic
    from ballista_tpu.physical.join import CrossJoinExec
    from ballista_tpu.physical.union import UnionExec

    a = _physical(_scan())
    if kind == "cross_join":
        return CrossJoinExec(a, _physical(_scan()))
    if kind == "union":
        return UnionExec([a, _physical(_scan())])
    if kind == "empty":
        return basic.EmptyExec(args[0], SCHEMA)
    return getattr(basic, kind)(a, *args)


def _repartition(scheme):
    from ballista_tpu.physical.expr import ColumnExpr
    from ballista_tpu.physical.plan import Partitioning
    from ballista_tpu.physical.repartition import RepartitionExec

    part = (Partitioning.hash([ColumnExpr("a", 0)], 8) if scheme == "hash"
            else Partitioning.round_robin(3))
    return RepartitionExec(_physical(_scan()), part)


def _window():
    from ballista_tpu.physical.expr import ColumnExpr
    from ballista_tpu.physical.window import WindowExec, WindowFuncDesc

    return WindowExec(
        _physical(_scan()),
        [
            WindowFuncDesc(
                "row_number", None, [ColumnExpr("c", 2)],
                [(ColumnExpr("a", 0), True)], "rn", pa.int64(),
            ),
            WindowFuncDesc(
                "sum", ColumnExpr("b", 1), [], [(ColumnExpr("a", 0), False)],
                "running", pa.float64(),
            ),
        ],
    )


def _spmd_aggregate():
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.distributed.planner import DistributedPlanner
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.parallel.spmd_stage import SpmdAggregateExec

    ctx = ExecutionContext()
    ctx.register_record_batches(
        "t",
        pa.table({"k": pa.array([1, 2, 1]), "v": pa.array([1.0, 2.0, 3.0])}),
        n_partitions=2,
    )
    df = ctx.table("t").aggregate([col("k")], [F.sum(col("v")).alias("s")])
    phys = ctx.create_physical_plan(df.logical_plan())
    cfg = BallistaConfig({"ballista.tpu.spmd_stages": "true"})
    stages = DistributedPlanner(cfg).plan_query_stages("j", phys)

    def find(n):
        if isinstance(n, SpmdAggregateExec):
            return n
        for c in n.children():
            r = find(c)
            if r is not None:
                return r
        return None

    spmd = next((find(s) for s in stages if find(s) is not None), None)
    assert spmd is not None
    return spmd


# every physical plan the round-trip cases build, by name
PHYSICAL_PLANS = {
    "filter_project": lambda: _physical(
        _scan().filter(col("a") > lit(1)).project([col("a"), col("c")])
    ),
    "aggregate_two_phase": lambda: _physical(
        _scan().aggregate([col("c")], [F.sum(col("a")).alias("s"),
                                       F.avg(col("b")).alias("m"),
                                       F.count(col("a")).alias("n")])
    ),
    "join_sort_limit": _join_sort_limit,
    "shuffle_writer": _shuffle_writer,
    "shuffle_reader": _shuffle_reader,
    "unresolved_shuffle": _unresolved_shuffle,
    "cross_join": lambda: _basic("cross_join"),
    "union": lambda: _basic("union"),
    "coalesce_batches": lambda: _basic("CoalesceBatchesExec", 4096),
    "merge": lambda: _basic("MergeExec"),
    "local_limit": lambda: _basic("LocalLimitExec", 7),
    "empty": lambda: _basic("empty", False),
    "empty_one_row": lambda: _basic("empty", True),
    "repartition_hash": lambda: _repartition("hash"),
    "repartition_round_robin": lambda: _repartition("round_robin"),
    "window": _window,
    "spmd_aggregate": _spmd_aggregate,
}


def roundtrip_physical(plan):
    from ballista_tpu.proto import ballista_pb2 as pb
    from ballista_tpu.serde.physical import (
        phys_plan_from_proto,
        phys_plan_to_proto,
    )

    msg = phys_plan_to_proto(plan)
    decoded = pb.PhysicalPlanNode()
    decoded.ParseFromString(msg.SerializeToString())
    p2 = phys_plan_from_proto(decoded)
    if "mode=final" not in str(plan):
        # FINAL aggregates deserialize with positional placeholder
        # expressions (they never re-evaluate inputs), so display
        # equality is only guaranteed elsewhere
        assert str(p2) == str(plan)
    assert p2.schema().equals(plan.schema())
    return p2


@pytest.mark.parametrize("name", list(PHYSICAL_PLANS))
def test_roundtrip_physical(name):
    plan = PHYSICAL_PLANS[name]()
    p2 = roundtrip_physical(plan)
    if name == "aggregate_two_phase":
        # execution equivalence after roundtrip
        from ballista_tpu.physical.plan import TaskContext, collect_all

        t1 = collect_all(plan, TaskContext()).sort_by("c")
        t2 = collect_all(p2, TaskContext()).sort_by("c")
        assert t1.equals(t2)
    if name == "shuffle_reader":
        # the producing map task's lineage survives the wire: fetch_failed
        # reports name it so the scheduler can recompute the lost partition
        loc = p2.locations[0]
        assert (loc.stage_id, loc.map_partition) == (3, 1)
        assert (loc.executor_id, loc.host, loc.port) == ("e1", "h", 50051)


# -- the codec's memos (ISSUE 27): what is kept is what a fresh pass gives ----

def _ipc_fields(msg):
    """Every Arrow-IPC schema or type a message carries, nested ones too."""
    out = []
    for fd, value in msg.ListFields():
        values = value if fd.is_repeated else [value]
        for v in values:
            if fd.type == fd.TYPE_MESSAGE:
                out += _ipc_fields(v)
            elif fd.name.endswith("_ipc") and fd.name != "partitions_ipc":
                out.append(v)
    return out


def _encoded(kind, name):
    from ballista_tpu.serde.physical import phys_plan_to_proto

    if kind == "logical":
        return plan_to_proto(LOGICAL_PLANS[name]())
    return phys_plan_to_proto(PHYSICAL_PLANS[name]())


ALL_PLANS = [("logical", n) for n in LOGICAL_PLANS] + [
    ("physical", n) for n in PHYSICAL_PLANS]


@pytest.mark.parametrize("kind,name", ALL_PLANS, ids=lambda v: v)
def test_memoised_schema_parse_is_the_unmemoised_parse(kind, name):
    from ballista_tpu.serde import arrow as serde_arrow

    carried = _ipc_fields(_encoded(kind, name))
    assert carried, name
    for data in carried:
        fresh = pa.ipc.read_schema(pa.BufferReader(data))
        for _ in range(2):  # a miss, then a hit
            kept = serde_arrow.schema_from_ipc(data)
            assert kept.equals(fresh, check_metadata=True)
        assert serde_arrow.schema_from_ipc(bytes(data)) is kept
        if len(fresh) == 1:
            assert serde_arrow.dtype_from_ipc(data).equals(
                fresh.field(0).type, check_metadata=True)


def test_a_full_memo_starts_over_and_counts_each_parse(monkeypatch):
    from ballista_tpu.serde import arrow as serde_arrow
    from ballista_tpu.utils import tracing

    monkeypatch.setattr(serde_arrow, "_MEMO_ENTRIES", 2)
    monkeypatch.setattr(serde_arrow, "_schemas", {})
    blobs = [pa.schema([pa.field(f"f{i}", pa.int64())]).serialize().to_pybytes()
             for i in range(3)]
    before = tracing.counters().get("serde.schema_parse", 0)
    for data in blobs + blobs[2:]:
        assert serde_arrow.schema_from_ipc(data).names == [
            pa.ipc.read_schema(pa.BufferReader(data)).names[0]]
        assert len(serde_arrow._schemas) <= 2
    assert tracing.counters()["serde.schema_parse"] - before == 3


METADATA_CASES = {
    "schema_metadata": (SCHEMA, SCHEMA.with_metadata({"origin": "b"})),
    "field_metadata": (
        pa.schema([pa.field("a", pa.int64())]),
        pa.schema([pa.field("a", pa.int64(), metadata={"unit": "rows"})]),
    ),
    "nested_field_metadata": (
        pa.struct([pa.field("a", pa.int64())]),
        pa.struct([pa.field("a", pa.int64(), metadata={"unit": "rows"})]),
    ),
}


@pytest.mark.parametrize("name", list(METADATA_CASES))
def test_equal_schemas_with_other_metadata_encode_to_other_bytes(name):
    """`==` on schemas and types ignores metadata; the wire must not."""
    from ballista_tpu.serde import arrow as serde_arrow

    plain, tagged = METADATA_CASES[name]
    assert plain == tagged and not plain.equals(tagged, check_metadata=True)
    if isinstance(plain, pa.Schema):
        to_ipc, from_ipc = serde_arrow.schema_to_ipc, serde_arrow.schema_from_ipc
    else:
        to_ipc, from_ipc = serde_arrow.dtype_to_ipc, serde_arrow.dtype_from_ipc
    for _ in range(2):  # the second pass meets whatever the first kept
        a, b = to_ipc(plain), to_ipc(tagged)
        assert a != b
        assert from_ipc(a).equals(plain, check_metadata=True)
        assert from_ipc(b).equals(tagged, check_metadata=True)


@pytest.mark.parametrize("name", list(PHYSICAL_PLANS))
def test_a_kept_encode_equals_a_fresh_one_byte_for_byte(name):
    """The scheduler hands every task of a binding the bytes it encoded for
    the first (SchedulerState.task_wire): they are what a fresh
    phys_plan_to_proto of the bound tree gives."""
    from ballista_tpu.proto import ballista_pb2 as pb
    from ballista_tpu.scheduler.kv import MemoryBackend
    from ballista_tpu.scheduler.state import SchedulerState
    from ballista_tpu.serde.physical import phys_plan_to_proto

    s = SchedulerState(MemoryBackend(), "t")
    s.save_stage_plan("j", 9, PHYSICAL_PLANS[name]())
    for p in range(2):  # the stage an UnresolvedShuffleExec(7, ..) reads
        t = pb.TaskStatus()
        t.partition_id.job_id, t.partition_id.stage_id = "j", 7
        t.partition_id.partition_id = p
        t.completed.executor_id, t.completed.path = "e1", f"/w/j/7/{p}"
        s.save_task_status(t)
    bound = s._bound_stage_plan("j", 9, s._ensure_task_index())
    kept, _settings = s.task_wire("j", 9, bound)
    assert s.task_wire("j", 9, bound)[0] is kept and s.plan_encodes == 1
    assert kept == phys_plan_to_proto(bound).SerializeToString()
    assert s._bound_stage_plan("j", 9, s._ensure_task_index()) is bound
