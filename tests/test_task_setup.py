"""A task's set-up on the executor (ISSUE 27): what is a function of the
stage alone (the decoded, root-checked plan, the job's merged config, the
bound shuffle fetcher) is made once per stage and shared by its tasks, with
confinement, freshness and the lifetime of plan-held state as they were."""

import gc
import os
import threading
import weakref

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.distributed.stages import ShuffleWriterExec, read_ipc_file
from ballista_tpu.executor import execution_loop
from ballista_tpu.executor.execution_loop import PollLoop
from ballista_tpu.logical.plan import JoinType
from ballista_tpu.physical.expr import ColumnExpr
from ballista_tpu.physical.join import HashJoinExec
from ballista_tpu.physical.plan import Partitioning
from ballista_tpu.physical.scan import ParquetScanExec
from ballista_tpu.datasource import ParquetTableSource
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.scheduler.rpc import SchedulerGrpcClient
from ballista_tpu.serde.physical import phys_plan_to_proto
from ballista_tpu.utils import tracing


def _write_table(path, files=8, rows=50, scale=1):
    os.makedirs(path, exist_ok=True)
    for f in range(files):
        ks = [(f * rows + i) % 7 for i in range(rows)]
        pq.write_table(
            pa.table({"k": pa.array(ks, type=pa.int64()),
                      "v": pa.array([scale * (f + i) for i in range(rows)],
                                    type=pa.float64())}),
            os.path.join(path, f"part-{f}.parquet"))
    return str(path)


def _scan_stage(job, table_dir, stage=1, out=2):
    """One stage as the planner lays it out: a scan per file, hash-split."""
    scan = ParquetScanExec(ParquetTableSource(table_dir), None)
    return ShuffleWriterExec(
        job, stage, scan, Partitioning.hash([ColumnExpr("k", 0)], out))


def _tasks(stage_plan, partitions, settings=()):
    wire = phys_plan_to_proto(stage_plan)
    out = []
    for p in range(partitions):
        td = pb.TaskDefinition()
        td.task_id.job_id = stage_plan.job_id
        td.task_id.stage_id = stage_plan.stage_id
        td.task_id.partition_id = p
        td.plan.CopyFrom(wire)
        for k, v in settings:
            td.settings.add(key=k, value=v)
        out.append(td)
    return out


def _loop(work_dir, slots=4, **settings):
    return PollLoop(
        SchedulerGrpcClient("127.0.0.1", 1),
        pb.ExecutorMetadata(id="ex", host="h", port=1),
        str(work_dir), config=BallistaConfig(settings), concurrent_tasks=slots)


def _run(loop, tasks):
    """Run tasks on the loop's slots as pushed tasks are; their statuses by
    partition, and each one's `executor.setup` span."""
    tracing.reset()
    threads = [threading.Thread(target=loop._run_task, args=(td, False, None))
               for td in tasks]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60)
        assert not th.is_alive()
    statuses = {}
    while not loop._finished.empty():
        st = loop._finished.get_nowait()
        statuses[(st.partition_id.job_id, st.partition_id.partition_id)] = st
    setups = [s for s in tracing.spans() if s.name == "executor.setup"]
    assert len(setups) == len(tasks)
    return statuses, setups


def _pieces(status):
    """What a completed task wrote, piece by piece."""
    assert status.WhichOneof("status") == "completed", status
    base = status.completed.path
    return {name: pa.Table.from_batches(list(read_ipc_file(os.path.join(base, name))))
            for name in sorted(os.listdir(base))}


def test_eight_partitions_on_four_slots_decode_once_and_answer_alike(tmp_path):
    table = _write_table(tmp_path / "t")
    tasks = _tasks(_scan_stage("job1", table), 8,
                   settings=[("ballista.batch.size", "4096")])
    loop = _loop(tmp_path / "shared")
    statuses, setups = _run(loop, tasks)
    assert sorted(s.attrs["decoded"] for s in setups) == [False] * 7 + [True]
    assert tracing.counters()["serde.plan_decode"] == 1  # _run reset the log
    assert len(loop._setups) == 1
    # the same tasks, each on an executor that has kept nothing
    for td in tasks:
        p = td.task_id.partition_id
        alone, (setup,) = _run(_loop(tmp_path / f"alone{p}"), [td])
        assert setup.attrs["decoded"] is True
        got, want = _pieces(statuses[("job1", p)]), _pieces(alone[("job1", p)])
        assert list(got) == list(want) == ["0.arrow", "1.arrow"]
        for name in want:
            assert got[name].equals(want[name]), (p, name)
        assert sum(t.num_rows for t in got.values()) == 50


def test_what_names_the_task_is_not_shared(tmp_path):
    """Attempt and partition are the task's: two attempts of one partition
    share the stage's tree and get a context each."""
    table = _write_table(tmp_path / "t", files=2)
    first, = _tasks(_scan_stage("job1", table), 1)
    retry = pb.TaskDefinition()
    retry.CopyFrom(first)
    retry.attempt = 1
    loop = _loop(tmp_path / "w")
    a = loop._member_setup(first)
    b = loop._member_setup(retry)
    assert (a[4], b[4]) == (True, False) and a[2] is b[2]
    assert (a[3].attempt, b[3].attempt) == (0, 1) and a[3] is not b[3]
    assert (a[1].attempt, b[1].attempt) == (0, 1)
    assert a[3].config is b[3].config and a[3].shuffle_fetcher is b[3].shuffle_fetcher
    # other settings are another job's config: nothing of it is shared
    other, = _tasks(_scan_stage("job1", table), 1,
                    settings=[("ballista.batch.size", "1024")])
    c = loop._member_setup(other)
    assert c[4] is True and c[2] is not a[2] and c[3].config.batch_size() == 1024


def test_a_plan_outside_the_data_roots_is_refused_on_every_task(tmp_path):
    allowed = _write_table(tmp_path / "allowed", files=3)
    outside = _write_table(tmp_path / "outside", files=3)
    loop = _loop(tmp_path / "w", **{"ballista.executor.data_roots": allowed})
    statuses, setups = _run(loop, _tasks(_scan_stage("evil", outside), 3))
    for st in statuses.values():
        assert st.WhichOneof("status") == "failed"
        assert "outside configured data roots" in st.failed.error
    assert len(statuses) == 3 and len(loop._setups) == 0
    assert [s.attrs["decoded"] for s in setups] == [True] * 3
    # ... and a plan inside them is kept as any other
    statuses, _setups = _run(loop, _tasks(_scan_stage("good", allowed), 3))
    assert {st.WhichOneof("status") for st in statuses.values()} == {"completed"}
    assert len(loop._setups) == 1


def test_a_second_job_reads_the_file_as_it_is_now(tmp_path):
    """Freshness: nothing decoded is shared across jobs, so each job lists
    the directory and reads the footers anew."""
    table = _write_table(tmp_path / "t", files=2, rows=50)
    loop = _loop(tmp_path / "w")
    statuses, _ = _run(loop, _tasks(_scan_stage("job1", table), 2))
    rows1 = sum(t.num_rows for p in range(2)
                for t in _pieces(statuses[("job1", p)]).values())
    _write_table(tmp_path / "t", files=3, rows=20, scale=100)  # rewritten, and one more
    statuses, setups = _run(loop, _tasks(_scan_stage("job2", table), 3))
    assert sorted(s.attrs["decoded"] for s in setups) == [False, False, True]
    got = [t for p in range(3) for t in _pieces(statuses[("job2", p)]).values()]
    assert (rows1, sum(t.num_rows for t in got)) == (100, 60)
    assert max(v for t in got for v in t.column("v").to_pylist()) == 100 * (2 + 19)


def test_a_displaced_stage_releases_its_join_build_side(tmp_path, monkeypatch):
    monkeypatch.setattr(execution_loop, "_KEPT_STAGES", 2)
    dim = _write_table(tmp_path / "dim", files=1, rows=7)
    fact = _write_table(tmp_path / "fact", files=2)
    join = HashJoinExec(
        ParquetScanExec(ParquetTableSource(dim), None),
        ParquetScanExec(ParquetTableSource(fact), [0]),
        [("k", "k")], JoinType.SEMI)
    loop = _loop(tmp_path / "w")
    statuses, _ = _run(loop, _tasks(ShuffleWriterExec("joinjob", 1, join, None), 1))
    assert statuses[("joinjob", 0)].WhichOneof("status") == "completed"
    (kept,) = loop._setups.values()
    node = kept.plan.input
    assert isinstance(node, HashJoinExec) and node._build_table is not None
    held = weakref.ref(node)
    del kept, node
    for job in ("next1", "next2"):  # two other jobs' stages come in
        _run(loop, _tasks(_scan_stage(job, fact), 1))
    assert [k[0] for k in loop._setups] == ["next1", "next2"]
    gc.collect()
    assert held() is None


def test_many_threads_decode_each_stage_once(tmp_path):
    """Stress, time-bounded: more task threads than cores, three stages'
    tasks interleaved, a short switch interval. One decode a stage, and
    every task gets its own stage's tree."""
    import sys

    table = _write_table(tmp_path / "t", files=2)
    stages = [_scan_stage(f"job{j}", table) for j in range(3)]
    tasks = [td for p in range(12) for st in stages for td in _tasks(st, 1)]
    loop = _loop(tmp_path / "w", slots=64)
    got = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        tracing.reset()
        threads = [threading.Thread(
            target=lambda td=td: got.append((td.task_id.job_id, loop._member_setup(td))))
            for td in tasks]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert len(got) == 36 and tracing.counters()["serde.plan_decode"] == 3
    trees = {}
    for job, (_td, status, plan, ctx, decoded) in got:
        assert plan.job_id == job == ctx.job_id == status.partition_id.job_id
        trees.setdefault(job, set()).add(id(plan))
    assert all(len(ids) == 1 for ids in trees.values())
    assert sum(1 for _job, m in got if m[4]) == 3
