"""Scheduler crash tolerance (ISSUE 6): durable assignment ledger,
crash-safe (atomic) planning writes, and restart reconciliation.

The acceptance run kills the scheduler mid-job (seeded `scheduler.crash`
chaos, keyed on the accepted-status sequence rotated by the restart
generation), restarts a FRESH SchedulerServer on the same SqliteBackend
store, and asserts the job completes bit-identical to the fault-free run —
without re-executing any task an executor still owned (task_retry and
orphan_reassigned stay 0). Torn planning is pinned write-by-write: a crash
between any pair of planning keys leaves NO torn job visible to clients or
assignment, because planning publishes through one atomic put_all whose
commit marker is the queued->running job-status flip."""

import threading
import time

import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.scheduler.kv import MemoryBackend, SqliteBackend
from ballista_tpu.scheduler.state import SchedulerState
from ballista_tpu.utils import tracing
from ballista_tpu.utils.chaos import ChaosInjected, ChaosInjector

# -- durable assignment ledger ----------------------------------------------


def _running_job(s, job="j"):
    running = pb.JobStatus()
    running.running.SetInParent()
    s.save_job_metadata(job, running)


def _meta(i):
    return pb.ExecutorMetadata(id=i, host="h", port=1)


def _pending(job, stage, part, attempt=0):
    t = pb.TaskStatus()
    t.partition_id.job_id = job
    t.partition_id.stage_id = stage
    t.partition_id.partition_id = part
    t.attempt = attempt
    return t


def _stage_plan(s, job="j", stage=1):
    from ballista_tpu.physical.basic import EmptyExec

    s.save_stage_plan(job, stage, EmptyExec(True, pa.schema([("a", pa.int64())])))


def _echo(job, stage, part, attempt):
    e = pb.RunningTaskEcho()
    e.partition_id.job_id = job
    e.partition_id.stage_id = stage
    e.partition_id.partition_id = part
    e.attempt = attempt
    return e


def test_assignment_is_written_through_to_the_kv(tmp_path):
    db = str(tmp_path / "state.db")
    s = SchedulerState(SqliteBackend(db), "t")
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    _stage_plan(s)
    s.save_task_status(_pending("j", 1, 0))
    assert s.assign_next_schedulable_task("e1") is not None
    raw = s.kv.get("/ballista/t/assignments/j/1/0")
    assert raw is not None
    a = pb.Assignment()
    a.ParseFromString(raw)
    assert a.executor_id == "e1" and a.attempt == 0
    # resolving the task clears the durable entry
    done = pb.TaskStatus()
    done.partition_id.CopyFrom(_pending("j", 1, 0).partition_id)
    done.completed.executor_id = "e1"
    done.completed.path = "/x"
    assert s.accept_task_status(done)
    assert s.kv.get("/ballista/t/assignments/j/1/0") is None


def test_restarted_scheduler_readopts_echoed_assignment(tmp_path):
    """The re-adoption path: a fresh SchedulerState on the same store
    reloads the ledger; the owner's attempt-matching echo confirms the
    task (restart_readopted), which is NOT re-executed."""
    db = str(tmp_path / "state.db")
    s1 = SchedulerState(SqliteBackend(db), "t")
    _running_job(s1)
    s1.save_executor_metadata(_meta("e1"))
    _stage_plan(s1)
    s1.save_task_status(_pending("j", 1, 0))
    assert s1.assign_next_schedulable_task("e1") is not None
    del s1  # crash

    tracing.counters("recovery", reset=True)
    s2 = SchedulerState(SqliteBackend(db), "t")
    stats = s2.recover()
    assert stats.get("scheduler_restart") == 1
    assert stats.get("restart_assignment_restored") == 1
    assert stats.get("restart_job_resumed") == 1
    assert ("j", 1, 0) in s2._assigned
    # the owner vouches with the matching attempt: re-adopted, not requeued
    assert s2.reconcile_running_tasks("e1", [_echo("j", 1, 0, 0)]) == 0
    assert s2.get_task_status("j", 1, 0).WhichOneof("status") == "running"
    assert ("j", 1, 0) not in s2._assigned
    assert s2.kv.get("/ballista/t/assignments/j/1/0") is None
    assert tracing.counters("recovery").get("restart_readopted", 0) == 1


def test_restarted_scheduler_requeues_unvouched_assignment(tmp_path):
    """Nobody echoes the reloaded entry within the grace window: the task
    requeues through the normal retry path (fresh attempt + history)."""
    import ballista_tpu.scheduler.state as state_mod

    db = str(tmp_path / "state.db")
    s1 = SchedulerState(SqliteBackend(db), "t")
    _running_job(s1)
    s1.save_executor_metadata(_meta("e1"))
    _stage_plan(s1)
    s1.save_task_status(_pending("j", 1, 0))
    assert s1.assign_next_schedulable_task("e1") is not None
    del s1

    s2 = SchedulerState(SqliteBackend(db), "t")
    s2.recover()
    old = state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS
    state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS = 0.0
    try:
        assert s2.reconcile_running_tasks("e1", []) == 1
    finally:
        state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS = old
    t = s2.get_task_status("j", 1, 0)
    assert t.WhichOneof("status") is None and t.attempt == 1
    assert s2.kv.get("/ballista/t/assignments/j/1/0") is None


def test_stale_attempt_echo_does_not_vouch(tmp_path):
    """An executor still running a SUPERSEDED attempt cannot re-adopt the
    current one: its echo names the old attempt and is ignored."""
    import ballista_tpu.scheduler.state as state_mod

    db = str(tmp_path / "state.db")
    s1 = SchedulerState(SqliteBackend(db), "t")
    _running_job(s1)
    s1.save_executor_metadata(_meta("e1"))
    _stage_plan(s1)
    s1.save_task_status(_pending("j", 1, 0, attempt=2))
    status, _ = s1.assign_next_schedulable_task("e1")
    assert status.attempt == 2
    del s1

    s2 = SchedulerState(SqliteBackend(db), "t")
    s2.recover()
    old = state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS
    state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS = 0.0
    try:
        # attempt-0 echo for an attempt-2 ledger entry: requeued anyway
        assert s2.reconcile_running_tasks("e1", [_echo("j", 1, 0, 0)]) == 1
    finally:
        state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS = old
    assert s2.get_task_status("j", 1, 0).attempt == 3


def test_recover_drops_resolved_ledger_entries(tmp_path):
    """Ledger entries whose task resolved (or was superseded) before the
    crash are discarded on reload, not resurrected."""
    db = str(tmp_path / "state.db")
    s1 = SchedulerState(SqliteBackend(db), "t")
    _running_job(s1)
    s1.save_executor_metadata(_meta("e1"))
    _stage_plan(s1)
    s1.save_task_status(_pending("j", 1, 0))
    assert s1.assign_next_schedulable_task("e1") is not None
    # simulate: the completion wrote but the crash hit before the ledger
    # delete — replay must treat the entry as resolved
    done = pb.TaskStatus()
    done.partition_id.CopyFrom(_pending("j", 1, 0).partition_id)
    done.completed.executor_id = "e1"
    done.completed.path = "/x"
    s1.save_task_status(done)  # raw write, ledger entry left behind
    del s1

    s2 = SchedulerState(SqliteBackend(db), "t")
    s2.recover()
    assert s2._assigned == {}
    assert s2.kv.get("/ballista/t/assignments/j/1/0") is None


# -- crash-safe planning writes ---------------------------------------------


class _CrashAtWrite:
    """Chaos stub that raises on the k-th staged planning write — the
    'crash between each pair of planning keys' probe. Duck-types the one
    injector method the planning path uses. The lease mint (ISSUE 20)
    rides the same commit and counts as one more seam: crashing there
    must be just as invisible as crashing between any other pair."""

    def __init__(self, k):
        self.k = k
        self.calls = 0

    def maybe_fail(self, site, key):
        assert site in ("scheduler.plan_write", "kv.lease")
        self.calls += 1
        if self.calls == self.k:
            raise ChaosInjected(site, key)


def _submit_sales_job(server, n_parts=2):
    from ballista_tpu.logical import col, functions as F
    from ballista_tpu.serde.logical import plan_to_proto
    from ballista_tpu.engine.context import ExecutionContext

    ctx = ExecutionContext()
    ctx.register_record_batches(
        "t", pa.table({"g": ["a", "b", "a", "b"], "v": [1.0, 2.0, 3.0, 4.0]}),
        n_partitions=n_parts,
    )
    df = ctx.table("t").aggregate([col("g")], [F.sum(col("v")).alias("s")])
    params = pb.ExecuteQueryParams()
    params.logical_plan.CopyFrom(plan_to_proto(df.logical_plan()))
    return server.ExecuteQuery(params).job_id


def test_torn_planning_write_leaves_no_job_state_visible(tmp_path):
    """Crash at EVERY staged planning write in turn: the job must stay
    queued with zero planning keys (stages, tasks) visible — the atomic
    put_all never ran — and assignment must hand out nothing."""
    from ballista_tpu.scheduler.server import SchedulerServer

    db = str(tmp_path / "state.db")
    server = SchedulerServer(
        SqliteBackend(db), namespace="t", synchronous_planning=True
    )
    # count the staged writes of an identical healthy plan first
    probe = _CrashAtWrite(k=10**9)
    server.state._chaos = probe
    job_ok = _submit_sales_job(server)
    total_writes = probe.calls
    assert total_writes >= 3  # stage plan(s) + tasks + commit

    for k in range(1, total_writes + 1):
        server.state._chaos = _CrashAtWrite(k)
        with pytest.raises(ChaosInjected):
            _submit_sales_job(server)
        server.state._chaos = None
        # exactly one job planned successfully (the probe); every torn
        # submission left nothing but its queued marker + settings
        tasks = server.state.get_all_tasks()
        assert {t.partition_id.job_id for t in tasks} == {job_ok}
        stage_keys = [
            key for key, _ in server.state.kv.get_prefix("/ballista/t/stages")
        ]
        assert all(f"/{job_ok}/" in key for key in stage_keys)
        torn = [
            key.rsplit("/", 1)[1]
            for key, _ in server.state.kv.get_prefix("/ballista/t/jobs")
        ]
        for job_id in torn:
            if job_id == job_ok:
                continue
            js = server.state.get_job_metadata(job_id)
            assert js.WhichOneof("status") == "queued"
            server.state.synchronize_job_status(job_id)  # must not touch it
            assert server.state.get_job_metadata(job_id).WhichOneof("status") == "queued"
        # nothing assignable beyond the probe job's own tasks
        assigned = server.state.assign_next_schedulable_task("eX")
        if assigned is not None:
            assert assigned[0].partition_id.job_id == job_ok


def test_recover_fails_torn_jobs_cleanly(tmp_path):
    """A restarted scheduler turns uncommitted (queued) jobs into clean
    failures — the client gets 'resubmit', never a hang or a torn run."""
    from ballista_tpu.scheduler.server import SchedulerServer

    db = str(tmp_path / "state.db")
    server = SchedulerServer(
        SqliteBackend(db), namespace="t", synchronous_planning=True
    )
    server.state._chaos = _CrashAtWrite(2)
    with pytest.raises(ChaosInjected):
        _submit_sales_job(server)
    del server  # crash before any retry

    server2 = SchedulerServer(SqliteBackend(db), namespace="t")
    assert server2.recovery_stats.get("torn_job_discarded") == 1
    jobs = list(server2.state.kv.get_prefix("/ballista/t/jobs"))
    assert len(jobs) == 1
    js = pb.JobStatus()
    js.ParseFromString(jobs[0][1])
    assert js.WhichOneof("status") == "failed"
    assert "resubmit" in js.failed.error
    # settings of the torn job were swept too
    assert list(server2.state.kv.get_prefix("/ballista/t/settings")) == []


def test_sqlite_put_all_is_atomic(tmp_path):
    kv = SqliteBackend(str(tmp_path / "kv.db"))
    kv.put("keep", b"old")
    with pytest.raises(Exception):
        # the third item is unbindable: the whole batch must roll back
        kv.put_all([("keep", b"new"), ("a", b"1"), ("bad", object())])
    assert kv.get("keep") == b"old"
    assert kv.get("a") is None
    kv.put_all([("a", b"1"), ("b", b"2")])
    assert kv.get("a") == b"1" and kv.get("b") == b"2"


def test_memory_put_all_and_delete():
    kv = MemoryBackend()
    kv.put_all([("a", b"1"), ("ab", b"2")])
    assert kv.get("a") == b"1" and kv.get("ab") == b"2"
    # exact-key delete must not eat sibling keys sharing the prefix
    kv.delete("a")
    assert kv.get("a") is None and kv.get("ab") == b"2"


def test_sqlite_delete_is_exact_key(tmp_path):
    kv = SqliteBackend(str(tmp_path / "kv.db"))
    kv.put("/a/1/2", b"x")
    kv.put("/a/1/20", b"y")
    kv.delete("/a/1/2")
    assert kv.get("/a/1/2") is None
    assert kv.get("/a/1/20") == b"y"


# -- seeded crash + restart acceptance run ----------------------------------

GROUP_BY_SQL = (
    "select region, sum(amount) as s, count(*) as n from sales "
    "group by region order by region"
)
JOIN_SQL = (
    "select region, sum(amount * bonus) as weighted from sales, regions "
    "where region = name group by region order by region"
)

CLIENT_SETTINGS = {
    "ballista.shuffle.partitions": "4",
    # generous transient-retry budget so clients and executors ride the
    # crash->restart UNAVAILABLE gap instead of surfacing it
    "ballista.rpc.retries": "20",
    "ballista.rpc.backoff_ms": "50",
}


CRASH_RATE = 0.05


def _find_crash_seed():
    """Deterministically scan for a seed where generation 0 crashes the
    scheduler at accepted status 2-4 (mid-job: after planning, during
    execution of the first query's 8 tasks) and generation 1 survives the
    whole run's status horizon (~16 statuses for both queries plus
    redelivered duplicates; 120 is comfortably past it) — pure hashing, no
    cluster involved, so the scan result is stable forever."""
    for seed in range(20000):
        inj = ChaosInjector(seed, rate=CRASH_RATE, sites={"scheduler.crash"})

        def fires_at(gen, horizon):
            for n in range(1, horizon):
                if inj.should_inject("scheduler.crash", f"g{gen}/status{n}"):
                    return n
            return None

        first = fires_at(0, 40)
        if first in (2, 3, 4) and fires_at(1, 120) is None:
            return seed
    pytest.fail("no crash seed found in scan range")


def _register(ctx, sales_table):
    ctx.register_record_batches("sales", sales_table, n_partitions=4)
    ctx.register_record_batches(
        "regions",
        pa.table({"name": ["east", "west", "north"], "bonus": [1.0, 2.0, 3.0]}),
    )


def _run_queries(cluster, sales_table, settings):
    from ballista_tpu.client import BallistaContext

    ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
    _register(ctx, sales_table)
    out = {}
    for name, sql in (("group_by", GROUP_BY_SQL), ("join", JOIN_SQL)):
        out[name] = ctx.sql(sql).collect()
    ctx.close()
    return out


def test_scheduler_crash_and_restart_is_bit_identical(tmp_path, sales_table):
    """ISSUE 6 acceptance: a seeded chaos run crashes the scheduler mid-job
    (after planning: the crash site keys on accepted task statuses);
    a FRESH SchedulerServer restarted on the same SqliteBackend store
    resumes the job from the durable state + assignment ledger and the
    results are bit-identical to the fault-free run. No task an executor
    still owned is re-executed (task_retry == orphan_reassigned == 0)."""
    from ballista_tpu.executor.runtime import StandaloneCluster

    crash_seed = _find_crash_seed()

    clean_cluster = StandaloneCluster(n_executors=2)
    try:
        clean = _run_queries(clean_cluster, sales_table, CLIENT_SETTINGS)
    finally:
        clean_cluster.shutdown()

    cluster_config = BallistaConfig({
        "ballista.chaos.rate": str(CRASH_RATE),
        "ballista.chaos.seed": str(crash_seed),
        "ballista.chaos.sites": "scheduler.crash",
        "ballista.rpc.retries": "20",
        "ballista.rpc.backoff_ms": "50",
    })
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(
        n_executors=2,
        kv=SqliteBackend(str(tmp_path / "sched.db")),
        config=cluster_config,
    )
    # watchdog: restart the scheduler on the same store as soon as the
    # chaos crash fires (an external supervisor's job in a real deployment)
    stop = threading.Event()

    def supervisor():
        while not stop.is_set():
            if cluster.scheduler_impl.crashed:
                cluster.restart_scheduler()
            time.sleep(0.02)

    sup = threading.Thread(target=supervisor, daemon=True)
    sup.start()
    try:
        chaotic = _run_queries(cluster, sales_table, CLIENT_SETTINGS)
    finally:
        stop.set()
        sup.join(timeout=5)
        cluster.shutdown()

    for name in ("group_by", "join"):
        assert chaotic[name].equals(clean[name]), (
            name, chaotic[name].to_pydict(), clean[name].to_pydict(),
        )
    stats = tracing.counters("recovery", reset=True)
    assert stats.get("chaos_scheduler_crash", 0) >= 1, stats
    assert stats.get("scheduler_restart", 0) >= 1, stats
    assert stats.get("restart_job_resumed", 0) >= 1, stats
    # restart reconciliation must NOT have re-executed owned work
    assert stats.get("task_retry", 0) == 0, stats
    assert stats.get("orphan_reassigned", 0) == 0, stats


def test_plan_write_chaos_retries_to_bit_identical(sales_table):
    """scheduler.plan_write armed at a nonzero rate: torn planning attempts
    abort atomically and retry with rotated keys; results stay
    bit-identical to fault-free and the plan_retry counter shows the tears
    actually happened."""
    from ballista_tpu.executor.runtime import StandaloneCluster

    clean_cluster = StandaloneCluster(n_executors=2)
    try:
        clean = _run_queries(clean_cluster, sales_table, CLIENT_SETTINGS)
    finally:
        clean_cluster.shutdown()

    # seed scanned over the PLAN-coordINATE key space the two queries can
    # produce: attempt 0 tears on at least one staged write, attempts 1-3
    # are clean for EVERY candidate key — so planning deterministically
    # converges on the first retry, inside the default budget
    rate = 0.02
    candidates = (
        [f"stage{s}" for s in range(1, 5)]
        + [f"{s}/{p}" for s in range(1, 5) for p in range(4)]
        + ["commit"]
    )

    def _tears(inj, key, attempt):
        return inj.should_inject("scheduler.plan_write", f"{key}@a{attempt}")

    # the tear must land on a key every submission provably produces
    # (stage 1 and its partition 0 exist in any multi-stage job; commit
    # always runs) — a seed tearing only on a key this plan never stages
    # would make plan_retry 0
    always_present = ("stage1", "1/0", "commit")
    seed = next(
        s for s in range(5000)
        if (inj := ChaosInjector(s, rate, sites={"scheduler.plan_write"}))
        and any(_tears(inj, k, 0) for k in always_present)
        and not any(
            _tears(inj, k, a) for k in candidates for a in (1, 2, 3)
        )
    )
    cluster_config = BallistaConfig({
        "ballista.chaos.rate": str(rate),
        "ballista.chaos.seed": str(seed),
        "ballista.chaos.sites": "scheduler.plan_write",
    })
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(n_executors=2, config=cluster_config)
    try:
        chaotic = _run_queries(cluster, sales_table, CLIENT_SETTINGS)
    finally:
        cluster.shutdown()
    for name in ("group_by", "join"):
        assert chaotic[name].equals(clean[name]), name
    stats = tracing.counters("recovery", reset=True)
    assert stats.get("plan_retry", 0) >= 1, stats
