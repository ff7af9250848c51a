"""The key-set link and the select it feeds: a grouped aggregate whose groups
feed only the non-preserved side of an equi-join reads back only the groups
whose keys the other side holds.

The rule (`distributed/planner.py::_link_keysets`) turns the aggregate's
PARTIAL stage into SEMI(partial, every partition of the join's left stage);
the SEMI join hands the aggregate its build side's keys
(`physical/join.py::_keyset`), and the sorted engine takes the member groups'
chunk rows on the device before the readback (`ops/stage.py::_run_keyset`).
Under CPU-jax, on the generator of the cell
`tpch_sf10_subquery_1chip.highcard_agg` at a small scale and on synthetic
tables. A "without" plan is the stage DAG as planned before the rule runs."""

import os
import pathlib
import sys
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

CHIP = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import run  # noqa: E402
from data import tpch8  # noqa: E402

from ballista_tpu.config import BALLISTA_TPU_COALESCE_AGG, BallistaConfig  # noqa: E402
from ballista_tpu.distributed.planner import (  # noqa: E402
    DistributedPlanner,
    find_unresolved_shuffles,
    remove_unresolved_shuffles,
)
from ballista_tpu.distributed.stages import (  # noqa: E402
    ShuffleLocation,
    ShuffleWriterExec,
    UnresolvedShuffleExec,
    read_ipc_file,
    shuffle_output_base,
)
from ballista_tpu.engine.context import ExecutionContext  # noqa: E402
from ballista_tpu.logical.plan import JoinType  # noqa: E402
from ballista_tpu.ops.layout import SortedSegmentLayout  # noqa: E402
from ballista_tpu.ops.stage import GroupKeyIndex  # noqa: E402
from ballista_tpu.physical import expr as px  # noqa: E402
from ballista_tpu.physical.aggregate import AggregateMode, HashAggregateExec  # noqa: E402
from ballista_tpu.physical.join import HashJoinExec  # noqa: E402
from ballista_tpu.physical.plan import TaskContext, collect_partition  # noqa: E402
from ballista_tpu.serde.physical import phys_plan_from_proto, phys_plan_to_proto  # noqa: E402
from ballista_tpu.utils import tracing  # noqa: E402

HIGHCARD = "tpch_sf10_subquery_1chip.highcard_agg"
CELLS = ["tpch_sf10_1chip.scan_agg", "tpch_sf10_1chip.join_topk",
         "tpch_sf10_8tables_1chip.join_deep", HIGHCARD]
OTHERS = ["q1", "q6", "q3", "q10", "q12", "q5", "q7", "q8", "q9", "q2", "q15"]
SCALE = 0.02


def _texts():
    """{text name: sql} of the four cells."""
    out = {}
    for cell in CELLS:
        for text in run.load_cell(cell)["traffic"]["texts"]:
            out[text["name"]] = run._sql(text["sql"])
    return out


def _settings():
    return dict(run.load_cell(HIGHCARD)["config"]["settings"])


def _context(data_dir):
    """The scheduler's planning context (aggregates not coalesced) with the
    cell's settings and every table of `data_dir` registered."""
    cfg = BallistaConfig(_settings()).with_setting(BALLISTA_TPU_COALESCE_AGG, "false")
    ctx = ExecutionContext(cfg)
    for table in sorted(os.listdir(data_dir)):
        ctx.register_parquet(table, os.path.join(data_dir, table))
    return ctx


def _physical(ctx, sql):
    return ctx.create_physical_plan(ctx.sql(sql).logical_plan())


def _unlinked(phys, job="j"):
    """The stage DAG as `plan_query_stages` has it before the rule runs."""
    planner = DistributedPlanner()
    stages = []
    root = planner._visit(phys, job, stages)
    stages.append(ShuffleWriterExec(job, planner._new_stage_id(), root, None))
    return stages


def _linked(phys, job="j"):
    planner = DistributedPlanner()
    stages = planner.plan_query_stages(job, phys)
    return stages, planner.keyset_links


def _reads(stages):
    return {s.stage_id: sorted({u.stage_id for u in find_unresolved_shuffles(s.input)})
            for s in stages}


def _run_stages(stages, work_dir, job):
    """Every stage's tasks in dependency order in this process, through the
    shuffle writer and reader as an executor runs them; the result's rows."""
    ctx = TaskContext(config=BallistaConfig(_settings()), work_dir=str(work_dir), job_id=job)
    located = {}
    for stage in stages:
        plan = remove_unresolved_shuffles(stage, located)
        located[stage.stage_id] = []
        for p in range(plan.output_partitioning().partition_count()):
            plan.execute_shuffle_write(p, ctx)
            base, _ = shuffle_output_base(ctx, job, stage.stage_id, p)
            located[stage.stage_id].append(ShuffleLocation(
                "local", "", 0, base, stage_id=stage.stage_id, map_partition=p))
    batches = [b for loc in located[stages[-1].stage_id]
               for b in read_ipc_file(os.path.join(loc.path, "0.arrow"))]
    return pa.Table.from_batches(batches, schema=stages[-1].schema())


def _rows(table):
    """Order-free, bit-exact form of a table: floats by their bytes."""
    cols = []
    for c in table.columns:
        vals = c.to_pylist()
        if pa.types.is_floating(c.type):
            vals = [None if v is None else np.float64(v).tobytes() for v in vals]
        cols.append(vals)
    return sorted(zip(*cols), key=repr)


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    """The eight tables of the cell's generator at a small scale."""
    d = tmp_path_factory.mktemp("tpch8")
    config = {**run.load_cell(HIGHCARD)["config"], "scale": SCALE}
    tpch8.generate(str(d), config, list(tpch8.TABLES), 2**31 + 11, 1)
    return str(d)


# -- synthetic tables: a LEFT join over an aggregate grouped by two keys ------

# (name, sql); the aggregate side `u` is grouped by (k1, k2)
SYNTHETIC = {
    "left": "select t.id, a.s, a.c from t left join (select k1, k2, sum(v) as s, "
            "count(*) as c from u group by k1, k2) a on t.k1 = a.k1 and t.k2 = a.k2",
    "count": "select id from t where qty >= (select count(*) from u "
             "where u.k1 = t.k1 and u.k2 = t.k2)",
    "sum": "select id from t where qty > (select 0.5 * sum(v) from u "
           "where u.k1 = t.k1 and u.k2 = t.k2)",
}
K1, K2 = 300, 40


def _write_synthetic(d, t_rows):
    """u: 40,000 rows in four files over 12,000 (k1, k2) pairs, three of them
    heavy (hundreds of rows, so a group owns several chunks of the sorted
    layout); t: `t_rows` rows in three files, 5 % NULL k1, keys repeated."""
    rng = np.random.default_rng(7)
    for name, n, files in (("u", 40000, 4), ("t", t_rows, 3)):
        os.makedirs(os.path.join(d, name), exist_ok=True)
        for f in range(files):
            m = n // files
            k1 = rng.integers(0, K1, m)
            k2 = rng.integers(0, K2, m)
            if name == "u":
                heavy = rng.random(m) < 0.06
                k1[heavy], k2[heavy] = rng.integers(0, 3, heavy.sum()), 0
                cols = {"k1": pa.array(k1, pa.int64()), "k2": pa.array(k2, pa.int64()),
                        "v": pa.array(rng.uniform(0, 50, m))}
            else:
                cols = {"k1": pa.array(k1, pa.int64(), mask=rng.random(m) < 0.05),
                        "k2": pa.array(k2, pa.int64()),
                        "id": pa.array(np.arange(f * m, (f + 1) * m), pa.int64()),
                        "qty": pa.array(rng.integers(0, 60, m), pa.int64())}
            pq.write_table(pa.table(cols), os.path.join(d, name, f"part-{f}.parquet"))
    return d


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    """{"few": a t of 600 rows (a task keeps about 5 % of its groups),
    "most": a t of 30,000 (more than half: the full readback)}."""
    return {size: _write_synthetic(str(tmp_path_factory.mktemp(size)), rows)
            for size, rows in (("few", 600), ("most", 30000))}


def _join_of(plan):
    if isinstance(plan, HashJoinExec):
        return plan
    for c in plan.children():
        j = _join_of(c)
        if j is not None:
            return j
    return None


# -- the rule -----------------------------------------------------------------

def test_q20_gains_one_link_and_keeps_its_four_stages(tpch):
    phys = _physical(_context(tpch), _texts()["q20"])
    before = _unlinked(phys)
    stages, links = _linked(phys)
    assert links == 1 and len(stages) == len(before) == 4
    ids = [s.stage_id for s in stages]
    assert ids == [s.stage_id for s in before] and len(set(ids)) == 4
    reads, reads_before = _reads(stages), _reads(before)
    # stage 2, the pair aggregate, now reads stage 1, the forest parts' partsupp
    assert reads_before[2] == [] and reads[2] == [1]
    assert {k: v for k, v in reads.items() if k != 2} == {
        k: v for k, v in reads_before.items() if k != 2}
    for s, b in zip(stages, before):
        if s.stage_id != 2:
            assert s.display_indent() == b.display_indent()
    semi = stages[1].input
    assert isinstance(semi, HashJoinExec) and semi.join_type == JoinType.SEMI
    assert semi.filter is None
    assert semi.left.display_indent() == before[1].input.display_indent()
    assert semi.left.mode == AggregateMode.PARTIAL
    assert [l for l, _ in semi.on] == ["l_partkey", "l_suppkey"]
    assert [r for _, r in semi.on] == ["partsupp.ps_partkey", "partsupp.ps_suppkey"]
    assert isinstance(semi.right, UnresolvedShuffleExec) and semi.right.stage_id == 1
    assert semi.right.partition_count == 8
    assert stages[1].shuffle_output_partitioning is before[1].shuffle_output_partitioning
    assert semi.schema() == before[1].input.schema()


@pytest.mark.parametrize("name", OTHERS)
def test_no_other_text_of_the_four_cells_gains_a_link(name, tpch):
    phys = _physical(_context(tpch), _texts()[name])
    before = _unlinked(phys)
    stages, links = _linked(phys)
    assert links == 0
    assert [s.display_indent() for s in stages] == [s.display_indent() for s in before]


def _synthetic_plan(data_dir, name="left"):
    return _physical(_context(data_dir), SYNTHETIC[name])


@pytest.mark.parametrize("join_type,links", [
    (JoinType.INNER, 1), (JoinType.LEFT, 1), (JoinType.SEMI, 1), (JoinType.ANTI, 1),
    (JoinType.RIGHT, 0), (JoinType.FULL, 0),
], ids=lambda v: v.value if isinstance(v, JoinType) else str(v))
def test_only_a_join_whose_right_side_is_not_preserved_links(join_type, links, synthetic):
    j = _join_of(_synthetic_plan(synthetic["few"]))
    rebuilt = HashJoinExec(j.left, j.right, j.on, join_type, partitioned=j.partitioned)
    assert _linked(rebuilt)[1] == links


def test_an_aggregate_on_the_preserved_side_does_not_link(synthetic):
    j = _join_of(_synthetic_plan(synthetic["few"]))
    swapped = HashJoinExec(j.right, j.left, [(r, l) for l, r in j.on], JoinType.LEFT,
                           partitioned=j.partitioned)
    assert _linked(swapped)[1] == 0


def test_group_keys_that_are_not_the_join_keys_do_not_link(synthetic):
    sql = ("select t.id, a.s from t left join (select k1, k2, sum(v) as s from u "
           "group by k1, k2) a on t.k1 = a.k1")
    assert _linked(_physical(_context(synthetic["few"]), sql))[1] == 0


def test_a_residual_filter_does_not_link(synthetic):
    j = _join_of(_synthetic_plan(synthetic["few"]))
    concat = pa.schema(list(j.left.schema()) + list(j.right.schema()))
    idx = concat.get_field_index("t.id")
    residual = px.BinaryPhysicalExpr(px.ColumnExpr("t.id", idx), "gt",
                                     px.LiteralExpr(0, pa.int64()))
    for filt, links in ((residual, 0), (None, 1)):
        semi = HashJoinExec(j.left, j.right, j.on, JoinType.SEMI, filter=filt,
                            partitioned=j.partitioned)
        assert _linked(semi)[1] == links


@pytest.mark.parametrize("read_twice", [3, 2], ids=["final_stage", "partial_stage"])
def test_an_aggregate_stage_that_another_stage_reads_too_does_not_link(read_twice, tpch):
    """Narrowing a stage that a second consumer reads would narrow that
    consumer's input too: only a stage with one reader links."""
    from ballista_tpu.distributed.planner import _link_keysets

    stages = _unlinked(_physical(_context(tpch), _texts()["q20"]))
    assert _link_keysets(list(stages)) == 1
    twice = stages[read_twice - 1]
    reader = UnresolvedShuffleExec(twice.stage_id, twice.schema(), 8)
    stages.insert(-1, ShuffleWriterExec("j", 99, reader, None))
    assert _link_keysets(stages) == 0


# -- plumbing -------------------------------------------------------------------

def _rebuilt(plan):
    """Every node rebuilt through `with_children`, as each pass over a plan does."""
    children = plan.children()
    return plan.with_children([_rebuilt(c) for c in children]) if children else plan


def _roundtrip(stage):
    return phys_plan_from_proto(phys_plan_to_proto(stage))


@pytest.mark.parametrize("carry", [_rebuilt, _roundtrip], ids=["with_children", "serde"])
def test_the_link_is_plan_nodes_that_survive(carry, tpch):
    stages, links = _linked(_physical(_context(tpch), _texts()["q20"]))
    assert links == 1
    stage = carry(stages[1])
    assert isinstance(stage, ShuffleWriterExec) and stage.stage_id == 2
    semi = stage.input
    assert isinstance(semi, HashJoinExec) and semi.join_type == JoinType.SEMI
    assert isinstance(semi.left, HashAggregateExec) and semi.left.mode == AggregateMode.PARTIAL
    assert isinstance(semi.right, UnresolvedShuffleExec) and semi.right.stage_id == 1
    assert [u.stage_id for u in find_unresolved_shuffles(stage)] == [1]
    assert stage.input.display_indent() == stages[1].input.display_indent()
    # the scheduler binds the reader like any other
    locs = {1: [ShuffleLocation("e", "h", 1, f"/x/{p}", stage_id=1, map_partition=p)
                for p in range(8)]}
    bound = remove_unresolved_shuffles(stage, locs).input.right
    assert len(bound.locations) == 8 and bound.num_partitions == 8


# -- exactness --------------------------------------------------------------------

def _with_and_without(phys, work_dir):
    """The answer of the stage DAG with the rule and as planned before it."""
    stages, links = _linked(phys, job="linked")
    tracing.reset()
    linked = _run_stages(stages, work_dir, "linked")
    counters = tracing.counters()
    plain = _run_stages(_unlinked(phys, job="plain"), work_dir, "plain")
    tracing.reset()
    return links, linked, plain, counters


def test_q20_s_answer_is_the_same_with_and_without_the_link(tpch, tmp_path):
    phys = _physical(_context(tpch), _texts()["q20"])
    links, linked, plain, counters = _with_and_without(phys, tmp_path)
    assert links == 1 and linked.num_rows > 0
    assert linked.equals(plain)
    assert counters["device.keyset_groups_dropped"] > 10 * counters["device.keyset_groups_kept"]


@pytest.mark.parametrize("size", ["few", "most"])
@pytest.mark.parametrize("name", sorted(SYNTHETIC))
def test_a_left_join_over_an_aggregate_is_the_same_with_and_without_the_link(
        name, size, synthetic, tmp_path):
    """NULL keys and repeated keys on the preserved side, a COUNT subquery's
    coalesce, and in `most` a key set that keeps more than half of a task's
    groups, where the full readback runs."""
    phys = _synthetic_plan(synthetic[size], name)
    links, linked, plain, counters = _with_and_without(phys, tmp_path)
    assert links == 1 and linked.num_rows > 0
    assert _rows(linked) == _rows(plain)
    kept, dropped = (counters["device.keyset_groups_kept"],
                     counters["device.keyset_groups_dropped"])
    if size == "few":
        assert 0 < kept < dropped
    else:
        assert dropped == 0 and kept > 4 * 1024


# -- the select ----------------------------------------------------------------------

def _partial_of(data_dir):
    stages, _ = _linked(_synthetic_plan(data_dir))
    semi = next(s.input for s in stages if isinstance(s.input, HashJoinExec))
    return semi.left


def test_the_kept_states_are_the_full_readback_s_bit_for_bit(synthetic):
    """Every member key's states equal the full readback's, and none is
    missing; the set holds the heavy groups (several chunks each), NULLs,
    keys out of range and repeats."""
    agg = _partial_of(synthetic["few"])
    ctx = TaskContext(config=BallistaConfig(_settings()))
    rng = np.random.default_rng(5)
    # (5, K2 + 2) is out of range and packs to where (6, 2) lies
    k1 = np.concatenate([[0, 1, 2, 0, K1 + 5, -3, 5], rng.integers(10, K1, 400)])
    k2 = np.concatenate([[0, 0, 0, 0, 1, 1, K2 + 2], rng.integers(0, K2, 400)])
    keyset = [pa.array(k1, pa.int64(), mask=np.arange(len(k1)) % 17 == 3),
              pa.array(k2, pa.int64())]
    members = {(a, b) for a, b in zip(keyset[0].to_pylist(), keyset[1].to_pylist())
               if a is not None}
    for p in range(4):
        tracing.reset()
        full = collect_partition(agg, p, ctx)
        kept = collect_partition(agg, p, ctx, keyset=keyset)
        counters = tracing.counters()
        want = [r for r in _rows(full) if (r[0], r[1]) in members]
        assert want and _rows(kept) == want
        assert 0 < counters["device.keyset_groups_kept"] < counters["device.keyset_groups_dropped"]
        assert counters["device.groups_out"] == full.num_rows + kept.num_rows
        to_arrow = [s for s in tracing.spans() if s.name == "runtime.to_arrow"]
        assert [s.attrs.get("keyset") for s in to_arrow] == [
            None, counters["device.keyset_groups_kept"]]
        assert to_arrow[1].attrs["groups"] == kept.num_rows
    tracing.reset()


def test_a_layout_subset_folds_as_the_whole_layout_does():
    rng = np.random.default_rng(11)
    codes = np.concatenate([rng.integers(0, 500, 4000), np.zeros(700, np.int64)])
    layout = SortedSegmentLayout(codes, 500)
    assert not layout.one_chunk_per_group
    values = rng.uniform(0, 1, layout.V).astype(np.float32)
    groups = np.array([0, 3, 17, 250, 499])
    chunks, sub = layout.subset(groups)
    assert list(sub.fold_sum(values[chunks])) == list(layout.fold_sum(values)[groups])
    assert list(sub.fold_max(values[chunks])) == list(layout.fold_max(values)[groups])
    assert sub.n_groups == 5 and sub.V == len(chunks) > 5
    none, empty = layout.subset(np.array([], dtype=np.int64))
    assert len(none) == 0 and len(empty.fold_sum(values[none])) == 0


def test_the_group_key_index_finds_members_and_declines_what_it_cannot_pack():
    keys = [pa.array([5, 9, 5, 7], pa.int64()), pa.array([1, 1, 2, 3], pa.int32())]
    index = GroupKeyIndex.build(keys)
    # (4, 4) is out of both ranges, and packs to where (5, 1) lies
    found = index.members([pa.array([5, None, 7, 5, 50, 9, 4], pa.int64()),
                           pa.array([2, 1, 3, 2, 1, 9, 4], pa.int64())])
    assert list(found) == [2, 3]
    assert GroupKeyIndex.build([pa.array(["a", "b"])]) is None
    assert GroupKeyIndex.build([pa.array([0, 31], pa.int32()).cast(pa.date32())]) is None
    assert GroupKeyIndex.build([pa.array([0, 2**40]), pa.array([0, 2**30])]) is None
    assert GroupKeyIndex.build([pa.array([1, None], pa.int64())]) is None
    assert index.members([pa.array([1.5]), pa.array([1])]) is None


def test_wide_composite_codes_become_dense_and_keep_their_equalities():
    from ballista_tpu.physical.joinutil import int32_key_codes

    small = (np.array([3, -1, 7]), np.array([7, 2]))
    assert int32_key_codes(*small) is not None
    assert all(a is b for a, b in zip(int32_key_codes(*small), small))
    left = np.array([2**40, -1, 5, 2**40 + 9, 5])
    right = np.array([5, 2**41, -1, 2**40])
    dl, dr = int32_key_codes(left, right)
    assert dl.max() < 4 and dr.max() < 4
    assert list(dl == -1) == list(left == -1) and list(dr == -1) == list(right == -1)
    for a, b in ((dl, left), (dr, right), (np.concatenate([dl, dr]), np.concatenate([left, right]))):
        assert (a[:, None] == a[None, :]).tolist() == (b[:, None] == b[None, :]).tolist()


def test_a_semi_join_on_a_wide_key_pair_stays_on_the_device():
    """The link's SEMI join is on q20's pair, whose packed range (2 * 10**11
    at scale 10) passes int32: the device counts its members all the same."""
    from ballista_tpu.ops import runtime

    rng = np.random.default_rng(2)
    a = pa.table({"x": pa.array(rng.integers(0, 3_000_000, 2000), pa.int64()),
                  "y": pa.array(rng.integers(0, 1_000_000, 2000), pa.int64()),
                  "id": pa.array(np.arange(2000), pa.int64())})
    pick = rng.choice(2000, 300, replace=False)
    # no NULL: integer keys without one are coded by value, and packed
    b = pa.table({"x": a.column("x").take(pick), "y": a.column("y").take(pick)})
    sql = "select id from a where exists (select 1 from b where b.x = a.x and b.y = a.y)"
    out = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(BallistaConfig({"ballista.executor.backend": backend}))
        ctx.register_record_batches("a", a)
        ctx.register_record_batches("b", b)
        runtime.join_path_stats(reset=True)
        out[backend] = sorted(ctx.sql(sql).collect().column("id").to_pylist())
        paths = runtime.join_path_stats(reset=True)["paths"]
        if backend == "tpu":
            assert paths == {"device": 1}, paths
    assert out["tpu"] == out["cpu"] == sorted(pick.tolist())


# -- served: repeated queries, the span and the counters ---------------------------

@pytest.fixture(scope="module")
def served(tpch):
    """q20 twice, then q2 and q15, through StandaloneCluster + BallistaContext:
    [(text, table, spans, counters)]."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    settings = _settings()
    texts = _texts()
    cluster = StandaloneCluster(n_executors=1, config=BallistaConfig(settings))
    out = []
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        for table in sorted(os.listdir(tpch)):
            ctx.register_parquet(table, os.path.join(tpch, table))
        for name in ("q20", "q20", "q2", "q15"):
            tracing.reset()
            table = ctx.sql(texts[name]).collect()
            time.sleep(0.2)  # the executor's last spans close after the client returns
            out.append((name, table, tracing.spans(), tracing.counters()))
        ctx.close()
    finally:
        cluster.shutdown()
    tracing.reset()
    return out


def test_two_executions_of_q20_in_a_row_give_the_same_answer(served):
    (_, first, _, c1), (_, second, _, c2) = served[0], served[1]
    assert first.num_rows > 0 and first.equals(second)
    for c in (c1, c2):
        assert c["device.keyset_groups_dropped"] > 10 * c["device.keyset_groups_kept"] > 0


def test_the_plan_span_counts_the_links(served):
    for name, _, spans, counters in served:
        plans = [s for s in spans if s.name == "scheduler.plan"]
        assert len(plans) == 1 and plans[0].attrs["keyset_links"] == (name == "q20")
        selects = [s for s in spans if s.name == "runtime.to_arrow" and "keyset" in s.attrs]
        if name == "q20":
            assert sum(s.attrs["keyset"] for s in selects) == counters["device.keyset_groups_kept"]
            assert counters["device.groups_out"] == sum(s.attrs["groups"] for s in selects)
        else:
            assert not selects and not any(k.startswith("device.keyset") for k in counters)
