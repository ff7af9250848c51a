"""Fault tolerance & recovery: bounded task retries, lineage-based shuffle
recovery, lost-task rescheduling, scheduler restart resume (checkpointed
state), work-dir GC, transient-RPC backoff.

SURVEY §5 noted the reference has ~~"no retry"~~ — **no longer true of this
port** (ISSUE 5): a failed task is requeued up to
``ballista.shuffle.max_task_retries`` times with per-task executor
blacklisting, a dead executor's completed shuffle outputs are recomputed
via lineage (downstream consumers invalidated, fetch_failed statuses name
the lost location), and only retry exhaustion fails the job — with the full
attempt history in the error."""

import os
import time

import pyarrow as pa
import pytest

from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.scheduler.kv import MemoryBackend, SqliteBackend
from ballista_tpu.scheduler.state import SchedulerState
from ballista_tpu.utils import tracing


def _meta(i, port=1):
    return pb.ExecutorMetadata(id=i, host="h", port=port)


def _task(job, stage, part, status=None, executor="e1"):
    t = pb.TaskStatus()
    t.partition_id.job_id = job
    t.partition_id.stage_id = stage
    t.partition_id.partition_id = part
    if status == "running":
        t.running.executor_id = executor
    elif status == "completed":
        t.completed.executor_id = executor
        t.completed.path = "/x"
    return t


def test_reset_lost_tasks_on_dead_executor():
    s = SchedulerState(MemoryBackend(), "t")
    running = pb.JobStatus()
    running.running.SetInParent()
    s.save_job_metadata("j", running)
    # e1 alive, e2 dead (never registered)
    s.save_executor_metadata(_meta("e1"))
    s.save_task_status(_task("j", 1, 0, "running", "e1"))
    s.save_task_status(_task("j", 1, 1, "running", "e2"))
    s.save_task_status(_task("j", 1, 2, "completed", "e2"))
    n = s.reset_lost_tasks()
    assert n == 2
    statuses = {
        t.partition_id.partition_id: t.WhichOneof("status") for t in s.get_job_tasks("j")
    }
    assert statuses == {0: "running", 1: None, 2: None}


def test_reset_skips_finished_jobs():
    s = SchedulerState(MemoryBackend(), "t")
    done = pb.JobStatus()
    done.completed.SetInParent()
    s.save_job_metadata("j", done)
    s.save_task_status(_task("j", 1, 0, "completed", "gone"))
    assert s.reset_lost_tasks() == 0


def test_scheduler_restart_resumes_from_sqlite(tmp_path):
    """The de-facto checkpoint: job/task/stage state lives in the KV store,
    so a restarted scheduler on a durable backend retains it (ref SURVEY §5
    checkpoint/resume)."""
    db = str(tmp_path / "state.db")
    s1 = SchedulerState(SqliteBackend(db), "t")
    running = pb.JobStatus()
    running.running.SetInParent()
    s1.save_job_metadata("jobA", running)
    s1.save_task_status(_task("jobA", 1, 0, "completed"))
    s1.save_task_status(_task("jobA", 1, 1))
    del s1  # "crash"

    s2 = SchedulerState(SqliteBackend(db), "t")
    assert s2.get_job_metadata("jobA").WhichOneof("status") == "running"
    tasks = s2.get_job_tasks("jobA")
    assert len(tasks) == 2
    assert {t.WhichOneof("status") for t in tasks} == {"completed", None}


def test_end_to_end_recovery_after_executor_death(sales_table):
    """Kill an executor holding work mid-job; the job must still complete on
    the survivor (the reference would lose it)."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster
    from ballista_tpu.scheduler.state import EXECUTOR_LEASE_SECS

    cluster = StandaloneCluster(n_executors=2)
    # shrink lease + check interval so death is detected quickly
    import ballista_tpu.scheduler.state as state_mod

    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    cluster.scheduler_impl.lost_task_check_interval = 0.5
    try:
        ctx = BallistaContext(*cluster.scheduler_addr)
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        # hard-stop one executor (its lease will lapse)
        victim = cluster.executors[0]
        victim.poll_loop.stop()
        time.sleep(1.5)  # lease expiry
        out = ctx.sql(
            "select region, sum(amount) as s from sales group by region order by region"
        ).collect()
        assert out.column("s").to_pylist() == [120.0, 40.0, 145.0]
        ctx.close()
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
        cluster.shutdown()


# -- bounded retries + attempt history (ISSUE 5) ----------------------------

def _running_job(s, job="j"):
    running = pb.JobStatus()
    running.running.SetInParent()
    s.save_job_metadata(job, running)


def test_reset_preserves_attempt_history():
    """A lost-task reset consumes one retry: attempt increments and the
    history names the dead executor."""
    s = SchedulerState(MemoryBackend(), "t")
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    s.save_task_status(_task("j", 1, 0, "completed", "gone"))
    assert s.reset_lost_tasks() == 1
    t = s.get_task_status("j", 1, 0)
    assert t.WhichOneof("status") is None and t.attempt == 1
    assert len(t.history) == 1 and t.history[0].executor_id == "gone"
    assert "shuffle output lost" in t.history[0].error


def test_reset_exhausted_fails_job_with_full_history():
    from ballista_tpu.config import BallistaConfig

    s = SchedulerState(
        MemoryBackend(), "t",
        config=BallistaConfig({"ballista.shuffle.max_task_retries": "1"}),
    )
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    t = _task("j", 1, 0, "completed", "gone")
    t.attempt = 1
    h = t.history.add()
    h.attempt = 0
    h.executor_id = "gone"
    h.error = "earlier loss"
    s.save_task_status(t)
    assert s.reset_lost_tasks() == 0
    js = s.get_job_metadata("j")
    assert js.WhichOneof("status") == "failed"
    # every attempt is listed
    assert "attempt 0 on gone: earlier loss" in js.failed.error
    assert "attempt 1 on gone" in js.failed.error


def test_failed_task_requeues_then_exhausts_listing_every_attempt():
    """The retry fold end to end at the state level: N failures requeue,
    failure N+1 fails the job with all attempts in the error."""
    from ballista_tpu.config import BallistaConfig

    s = SchedulerState(
        MemoryBackend(), "t",
        config=BallistaConfig({"ballista.shuffle.max_task_retries": "2"}),
    )
    _running_job(s)
    for attempt, executor in enumerate(["e1", "e2", "e1"]):
        t = s.get_task_status("j", 1, 0) or _task("j", 1, 0)
        report = pb.TaskStatus()
        report.CopyFrom(t)
        report.failed.error = f"boom{attempt}"
        report.failed.executor_id = executor
        assert s.accept_task_status(report)
        s.synchronize_job_status("j")
        if attempt < 2:
            cur = s.get_task_status("j", 1, 0)
            assert cur.WhichOneof("status") is None
            assert cur.attempt == attempt + 1
            assert s.get_job_metadata("j").WhichOneof("status") == "running"
    js = s.get_job_metadata("j")
    assert js.WhichOneof("status") == "failed"
    for line in ("attempt 0 on e1: boom0", "attempt 1 on e2: boom1",
                 "attempt 2 on e1: boom2"):
        assert line in js.failed.error, js.failed.error


def test_stale_report_from_reset_attempt_is_dropped():
    s = SchedulerState(MemoryBackend(), "t")
    _running_job(s)
    requeued = _task("j", 1, 0)
    requeued.attempt = 2
    s.save_task_status(requeued)
    stale = _task("j", 1, 0, "completed", "e-old")
    stale.attempt = 1  # the attempt the scheduler already reset
    assert not s.accept_task_status(stale)
    assert s.get_task_status("j", 1, 0).WhichOneof("status") is None


def test_assignment_blacklists_last_failing_executor():
    """Attempt N+1 must not land on the executor that failed attempt N —
    unless it is the only one left alive."""
    from ballista_tpu.physical.basic import EmptyExec

    s = SchedulerState(MemoryBackend(), "t")
    _running_job(s)
    s.save_executor_metadata(_meta("e1", 1))
    s.save_executor_metadata(_meta("e2", 2))
    s.save_stage_plan("j", 1, EmptyExec(True, pa.schema([("a", pa.int64())])))
    t = _task("j", 1, 0)
    t.attempt = 1
    h = t.history.add()
    h.attempt = 0
    h.executor_id = "e1"
    h.error = "boom"
    s.save_task_status(t)
    assert s.assign_next_schedulable_task("e1") is None  # blacklisted
    got = s.assign_next_schedulable_task("e2")
    assert got is not None and got[0].running.executor_id == "e2"
    assert got[0].attempt == 1  # attempt rides the assignment

    # sole survivor: with e2 gone, e1 gets it anyway (progress over placement)
    s2 = SchedulerState(MemoryBackend(), "t")
    _running_job(s2)
    s2.save_executor_metadata(_meta("e1", 1))
    s2.save_stage_plan("j", 1, EmptyExec(True, pa.schema([("a", pa.int64())])))
    s2.save_task_status(t)
    got = s2.assign_next_schedulable_task("e1")
    assert got is not None and got[0].running.executor_id == "e1"


# -- lineage-based shuffle recovery (ISSUE 5) -------------------------------

def _two_stage_state(max_retries="3"):
    """Stage 1 (map, 2 partitions) -> stage 2 (reduce) via an
    UnresolvedShuffleExec, as the distributed planner lays jobs out."""
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.distributed.stages import UnresolvedShuffleExec
    from ballista_tpu.physical.basic import EmptyExec

    s = SchedulerState(
        MemoryBackend(), "t",
        config=BallistaConfig({"ballista.shuffle.max_task_retries": max_retries}),
    )
    _running_job(s)
    schema = pa.schema([("a", pa.int64())])
    s.save_stage_plan("j", 1, EmptyExec(True, pa.schema([("a", pa.int64())])))
    s.save_stage_plan("j", 2, UnresolvedShuffleExec(1, schema, 2))
    return s


# -- a stage's plan crosses the wire once (ISSUE 27) ---------------------------

def _codec_counts():
    c = tracing.counters()
    return c.get("serde.plan_decode", 0), c.get("serde.plan_encode", 0)


def _served_two_stage():
    """_two_stage_state behind a SchedulerServer (whose _task_definition is
    what both dispatch paths send), the map stage complete on e1, eight
    reduce tasks pending."""
    from ballista_tpu.distributed.stages import UnresolvedShuffleExec
    from ballista_tpu.physical.basic import EmptyExec
    from ballista_tpu.scheduler.server import SchedulerServer

    srv = SchedulerServer(MemoryBackend(), namespace="t")
    s = srv.state
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    schema = pa.schema([("a", pa.int64())])
    s.save_stage_plan("j", 1, EmptyExec(True, schema))
    s.save_stage_plan("j", 2, UnresolvedShuffleExec(1, schema, 2))
    for m in range(2):
        s.save_task_status(_task("j", 1, m, "completed", "e1"))
    for p in range(8):
        s.save_task_status(_task("j", 2, p))
    return srv, s


def _hand_out(srv, executor="e1"):
    status, plan = srv.state.assign_next_schedulable_task(executor)
    return srv._task_definition(status, plan)


def test_eight_tasks_of_a_stage_cost_one_decode_and_one_encode():
    srv, s = _served_two_stage()
    decoded, encoded = _codec_counts()
    tds = [_hand_out(srv) for _ in range(8)]
    assert [td.task_id.partition_id for td in tds] == list(range(8))
    assert _codec_counts() == (decoded + 1, encoded + 1) and s.plan_encodes == 1
    assert len({td.plan.SerializeToString() for td in tds}) == 1
    locs = tds[0].plan.shuffle_reader.partition_locations
    assert [(l.executor_meta.id, l.path) for l in locs] == [("e1", "/x")] * 2
    # ... and the entry goes with the job's terminal status
    assert set(s._stage_plans["j"]) == {2}
    for st in (1, 2):
        for t in s.get_stage_tasks("j", st):
            if t.WhichOneof("status") != "completed":
                done = _task("j", st, t.partition_id.partition_id, "completed", "e1")
                assert s.accept_task_status(done)
    s.synchronize_job_status("j")
    assert s.get_job_metadata("j").WhichOneof("status") == "completed"
    assert "j" not in s._stage_plans


def _rehome(s, field):
    """Map output 0 is lost and recomputed: what its completed status (or its
    executor's registration) then says in `field` is new."""
    moved = _task("j", 1, 0, "completed", "e1")
    moved.attempt = 1
    if field == "executor":
        s.save_executor_metadata(_meta("e2", 2))
        moved.completed.executor_id = "e2"
    elif field == "host_port":
        s.save_executor_metadata(_meta("e1", 7))
    elif field == "path":
        moved.completed.path = "/y"
    elif field == "storage_uri":
        moved.completed.storage_uri = "/mnt/shuffle/j/1/0"
    elif field == "resident":
        moved.completed.resident = True
    elif field == "nbytes":
        moved.completed.stats.num_bytes = 4096
    s.save_task_status(moved)


@pytest.mark.parametrize(
    "field", ["executor", "host_port", "path", "storage_uri", "resident", "nbytes"])
def test_a_rehomed_upstream_piece_is_in_the_next_task_definition(field):
    """The kept bytes are keyed on every location they carry: after a
    lost-task reset lands a map output elsewhere, the next hand-out binds and
    encodes anew, and while the piece is being recomputed nothing is handed
    out at all."""
    srv, s = _served_two_stage()
    first = _hand_out(srv)
    s.save_task_status(_task("j", 1, 0))  # the reset: completed -> pending
    recompute, _plan = s.assign_next_schedulable_task("e1")
    assert recompute.partition_id.stage_id == 1
    assert s.assign_next_schedulable_task("e1") is None  # the reduce stage waits
    _rehome(s, field)
    _decoded, encoded = _codec_counts()
    second, third = _hand_out(srv), _hand_out(srv)
    assert _codec_counts()[1] == encoded + 1  # one new binding, shared again
    assert second.plan.SerializeToString() == third.plan.SerializeToString()
    assert second.plan.SerializeToString() != first.plan.SerializeToString()
    was = first.plan.shuffle_reader.partition_locations
    now = second.plan.shuffle_reader.partition_locations
    if field != "host_port":  # (e1 serves both pieces)
        assert now[1] == was[1]  # map output 1 never moved
    got = {
        "executor": now[0].executor_meta.id,
        "host_port": now[0].executor_meta.port,
        "path": now[0].path,
        "storage_uri": now[0].storage_uri,
        "resident": now[0].resident,
        "nbytes": now[0].partition_stats.num_bytes,
    }[field]
    want = {"executor": "e2", "host_port": 7, "path": "/y",
            "storage_uri": "/mnt/shuffle/j/1/0", "resident": True,
            "nbytes": 4096}[field]
    assert got == want


def test_a_replanned_stage_row_is_decoded_anew():
    """The decoded tree is keyed on the row's bytes, not on (job, stage)."""
    from ballista_tpu.distributed.stages import UnresolvedShuffleExec

    srv, s = _served_two_stage()
    before = _hand_out(srv)
    s.save_stage_plan("j", 2, UnresolvedShuffleExec(1, pa.schema([("b", pa.int64())]), 2))
    decoded, encoded = _codec_counts()
    after = _hand_out(srv)
    # (the save itself encodes the row once)
    assert _codec_counts() == (decoded + 1, encoded + 1)
    assert after.plan.SerializeToString() != before.plan.SerializeToString()


def test_lineage_completed_map_on_dead_executor_with_running_consumer():
    """Satellite regression (pre-fix-failing): a COMPLETED map task on a
    dead executor while a downstream reduce RUNS on a live executor. Before
    ISSUE 5 the reset put the map back to pending but left the running
    reduce bound to the dead location — its fetch failed, the failed status
    killed the job (reference behavior: in-flight work lost). Now BOTH are
    requeued with the loss recorded, and the job keeps running."""
    s = _two_stage_state()
    s.save_executor_metadata(_meta("e1"))  # alive; e2 never registered = dead
    s.save_task_status(_task("j", 1, 0, "completed", "e2"))  # lost output
    s.save_task_status(_task("j", 1, 1, "completed", "e1"))
    s.save_task_status(_task("j", 2, 0, "running", "e1"))  # live consumer
    n = s.reset_lost_tasks()
    assert n == 2  # the lost map task AND its running consumer
    mt = s.get_task_status("j", 1, 0)
    assert mt.WhichOneof("status") is None and mt.attempt == 1
    rt = s.get_task_status("j", 2, 0)
    assert rt.WhichOneof("status") is None and rt.attempt == 1
    assert "lost" in rt.history[0].error
    # the map output on the LIVE executor is untouched
    assert s.get_task_status("j", 1, 1).WhichOneof("status") == "completed"
    assert s.get_job_metadata("j").WhichOneof("status") == "running"


def test_fetch_failed_recomputes_only_the_lost_map_partition():
    """A reduce task reporting fetch_failed names the lost location; the
    scheduler requeues the reporter AND exactly that map partition."""
    s = _two_stage_state()
    s.save_executor_metadata(_meta("e1"))
    s.save_executor_metadata(_meta("e2", 2))
    s.save_task_status(_task("j", 1, 0, "completed", "e2"))
    s.save_task_status(_task("j", 1, 1, "completed", "e1"))
    report = _task("j", 2, 0)
    report.fetch_failed.error = "connection refused"
    report.fetch_failed.executor_id = "e1"
    report.fetch_failed.map_stage_id = 1
    report.fetch_failed.map_partition_id = 0
    report.fetch_failed.map_executor_id = "e2"
    report.fetch_failed.path = "/work/j/1/0"
    assert s.accept_task_status(report)
    s.synchronize_job_status("j")
    assert s.get_job_metadata("j").WhichOneof("status") == "running"
    # the reporter is requeued with the loss in its history
    rt = s.get_task_status("j", 2, 0)
    assert rt.WhichOneof("status") is None and rt.attempt == 1
    assert "fetch_failed" in rt.history[0].error
    # ONLY map partition 0 (the named one) is recomputed
    assert s.get_task_status("j", 1, 0).WhichOneof("status") is None
    assert s.get_task_status("j", 1, 0).attempt == 1
    assert s.get_task_status("j", 1, 1).WhichOneof("status") == "completed"


def test_orphaned_assignment_is_reconciled():
    """PollWork is retried and not idempotent: if the response carrying an
    assignment is lost, the task sits Running on an executor that never
    heard of it (lease stays fresh — reset_lost_tasks can't help). The
    executor's running_tasks echo lets the scheduler requeue it."""
    import ballista_tpu.scheduler.state as state_mod
    from ballista_tpu.physical.basic import EmptyExec

    s = SchedulerState(MemoryBackend(), "t")
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    s.save_stage_plan("j", 1, EmptyExec(True, pa.schema([("a", pa.int64())])))
    s.save_task_status(_task("j", 1, 0))
    assert s.assign_next_schedulable_task("e1") is not None
    # within the grace period an empty echo is fine (the executor may not
    # have received/started the task yet)
    assert s.reconcile_running_tasks("e1", []) == 0
    assert s.get_task_status("j", 1, 0).WhichOneof("status") == "running"
    old = state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS
    state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS = 0.0
    try:
        assert s.reconcile_running_tasks("e1", []) == 1
    finally:
        state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS = old
    t = s.get_task_status("j", 1, 0)
    assert t.WhichOneof("status") is None and t.attempt == 1
    assert "lost in transit" in t.history[0].error


def test_reconcile_keeps_confirmed_running_tasks():
    import ballista_tpu.scheduler.state as state_mod
    from ballista_tpu.physical.basic import EmptyExec

    s = SchedulerState(MemoryBackend(), "t")
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    s.save_stage_plan("j", 1, EmptyExec(True, pa.schema([("a", pa.int64())])))
    s.save_task_status(_task("j", 1, 0))
    status, _plan = s.assign_next_schedulable_task("e1")
    old = state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS
    state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS = 0.0
    try:
        # a DIFFERENT executor's empty echo must not reclaim e1's task
        assert s.reconcile_running_tasks("e2", []) == 0
        assert s.get_task_status("j", 1, 0).WhichOneof("status") == "running"
        # the owner vouches for the task: nothing reclaimed, stays running
        assert s.reconcile_running_tasks("e1", [status.partition_id]) == 0
        assert s.get_task_status("j", 1, 0).WhichOneof("status") == "running"
    finally:
        state_mod.ORPHANED_ASSIGNMENT_GRACE_SECS = old


# -- transient RPC resilience (ISSUE 5) -------------------------------------

class _FakeGrpcError(Exception):
    def __init__(self, code, detail="go away"):
        self._code = code
        self._detail = detail

    def code(self):
        return self._code

    def details(self):
        return self._detail


def _client_with_stub(stub, retries=3):
    """SchedulerGrpcClient whose PollWork stub is replaced — no server."""
    import grpc

    from ballista_tpu.scheduler.rpc import SchedulerGrpcClient

    c = SchedulerGrpcClient("127.0.0.1", 1, channel=grpc.insecure_channel(
        "127.0.0.1:1"), retries=retries, backoff_s=0.0)
    # stub cache is keyed (endpoint_idx, method) since ISSUE 20; one
    # configured endpoint means every call resolves through index 0
    c._stub_cache[(0, "PollWork")] = stub
    c._stub_cache[(0, "GetFileMetadata")] = stub
    return c


def test_rpc_retries_unavailable_then_succeeds(monkeypatch):
    import grpc

    # grpc.RpcError is the catch target; fake must subclass it
    class Boom(grpc.RpcError, _FakeGrpcError):
        def __init__(self, code):
            _FakeGrpcError.__init__(self, code)

    calls = []

    def stub(params):
        calls.append(1)
        if len(calls) < 3:
            raise Boom(grpc.StatusCode.UNAVAILABLE)
        return pb.PollWorkResult()

    c = _client_with_stub(stub)
    assert c.poll_work(pb.PollWorkParams()) is not None
    assert len(calls) == 3


def test_rpc_retries_cancelled_goaway(monkeypatch):
    """ISSUE 11 regression: a scheduler crash/restart stops its gRPC
    server, which GOAWAYs in-flight unary calls as CANCELLED — the other
    went-away shape, retried like UNAVAILABLE (this client never cancels
    its own unary calls)."""
    import grpc

    class Boom(grpc.RpcError, _FakeGrpcError):
        def __init__(self, code):
            _FakeGrpcError.__init__(self, code)

    calls = []

    def stub(params):
        calls.append(1)
        if len(calls) < 2:
            raise Boom(grpc.StatusCode.CANCELLED)
        return pb.PollWorkResult()

    c = _client_with_stub(stub)
    assert c.poll_work(pb.PollWorkParams()) is not None
    assert len(calls) == 2


def test_rpc_does_not_retry_execution_errors():
    import grpc

    from ballista_tpu.errors import RpcError

    class Boom(grpc.RpcError, _FakeGrpcError):
        def __init__(self):
            _FakeGrpcError.__init__(self, grpc.StatusCode.UNKNOWN, "planner exploded")

    calls = []

    def stub(params):
        calls.append(1)
        raise Boom()

    c = _client_with_stub(stub)
    with pytest.raises(RpcError, match="planner exploded"):
        c.poll_work(pb.PollWorkParams())
    assert len(calls) == 1  # surfaced immediately


def test_rpc_retry_budget_exhausts():
    import grpc

    from ballista_tpu.errors import RpcError

    class Boom(grpc.RpcError, _FakeGrpcError):
        def __init__(self):
            _FakeGrpcError.__init__(self, grpc.StatusCode.UNAVAILABLE)

    calls = []

    def stub(params):
        calls.append(1)
        raise Boom()

    c = _client_with_stub(stub, retries=2)
    with pytest.raises(RpcError):
        c.poll_work(pb.PollWorkParams())
    assert len(calls) == 3  # 1 + 2 retries


def test_get_file_metadata_honors_throttle_hint():
    """Satellite: the scheduler's fail-fast 'too many concurrent metadata
    requests; retry' response is retried with backoff, not surfaced."""
    import grpc

    class Boom(grpc.RpcError, _FakeGrpcError):
        def __init__(self):
            _FakeGrpcError.__init__(
                self, grpc.StatusCode.UNKNOWN,
                "Exception calling application: GetFileMetadata: too many "
                "concurrent metadata requests; retry",
            )

    calls = []

    def stub(params):
        calls.append(1)
        if len(calls) < 3:
            raise Boom()
        return pb.GetFileMetadataResult(num_partitions=7)

    c = _client_with_stub(stub)
    out = c.get_file_metadata(pb.GetFileMetadataParams(path="x", file_type="parquet"))
    assert out.num_partitions == 7 and len(calls) == 3


# -- poll-loop slot handling (ISSUE 5 satellite: TOCTOU fix) ----------------

class _FakeScheduler:
    def __init__(self, tasks=None):
        self.tasks = list(tasks or [])
        self.polls = []

    def poll_work(self, params):
        self.polls.append(params)
        result = pb.PollWorkResult()
        if params.can_accept_task and self.tasks:
            result.task.CopyFrom(self.tasks.pop(0))
        return result


def _poll_loop(scheduler, tmp_path, concurrent_tasks=1):
    from ballista_tpu.executor.execution_loop import PollLoop

    meta = pb.ExecutorMetadata(id="e-test", host="h", port=1)
    return PollLoop(scheduler, meta, str(tmp_path),
                    concurrent_tasks=concurrent_tasks)


def test_poll_once_never_blocks_when_slots_are_full(tmp_path):
    """The TOCTOU fix: with every slot taken, poll_once must report
    can_accept_task=False and return immediately — the old probe/release +
    blocking re-acquire could hang the heartbeat thread here."""
    sched = _FakeScheduler()
    loop = _poll_loop(sched, tmp_path, concurrent_tasks=1)
    assert loop._available.acquire(blocking=False)  # occupy the only slot
    done = []

    def poller():
        loop.poll_once()
        done.append(True)

    import threading

    t = threading.Thread(target=poller, daemon=True)
    t.start()
    t.join(timeout=2.0)
    assert done, "poll_once blocked with all slots taken (heartbeat stall)"
    assert sched.polls[-1].can_accept_task is False


def test_poll_once_hands_held_slot_to_the_task(tmp_path):
    """The slot acquired by the probe is the SAME one the task runs under:
    after receiving a task, no slot remains (concurrent_tasks=1) and the
    next poll advertises can_accept_task=False until the task finishes."""
    task = pb.TaskDefinition()
    task.task_id.job_id = "j"
    task.task_id.stage_id = 1
    sched = _FakeScheduler(tasks=[task])
    loop = _poll_loop(sched, tmp_path, concurrent_tasks=1)
    gate = __import__("threading").Event()

    def fake_run(task, slot_held=True, received_ns=None):
        gate.wait(5)
        loop._available.release()

    loop._run_task = fake_run
    assert loop.poll_once() is True
    assert sched.polls[-1].can_accept_task is True
    # slot is held by the (gated) task thread now, and the in-flight task
    # is echoed so the scheduler can reconcile lost assignments
    loop.poll_once()
    assert sched.polls[-1].can_accept_task is False
    assert [p.job_id for p in sched.polls[-1].running_tasks] == ["j"]
    gate.set()


def test_poll_failure_requeues_drained_statuses(tmp_path):
    """Statuses drained into a failing poll must survive to the next poll —
    losing them would wedge their job forever."""

    class FailingScheduler:
        def poll_work(self, params):
            raise RuntimeError("scheduler unreachable")

    loop = _poll_loop(FailingScheduler(), tmp_path)
    st = pb.TaskStatus()
    st.partition_id.job_id = "j"
    st.completed.executor_id = "e-test"
    loop._finished.put(st)
    with pytest.raises(RuntimeError):
        loop.poll_once()
    assert loop._finished.qsize() == 1  # requeued, not lost


# -- end-to-end lineage recovery (ISSUE 5 acceptance) -----------------------

def test_end_to_end_recovery_after_executor_death_with_lost_outputs(sales_table):
    """Executor killed AFTER its map stage completed: outputs lost while
    downstream reduces run. The job must still complete on the survivor via
    lineage recomputation (fetch_failed -> map recompute, lost-task resets),
    with nonzero recovery counters."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster
    from ballista_tpu.serde.logical import plan_to_proto
    import ballista_tpu.scheduler.state as state_mod

    cluster = StandaloneCluster(n_executors=2)
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    cluster.scheduler_impl.lost_task_check_interval = 0.3
    tracing.counters("recovery", reset=True)
    try:
        ctx = BallistaContext(*cluster.scheduler_addr)
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        df = ctx.sql(
            "select region, sum(amount) as s from sales group by region order by region"
        )
        plan = df.logical_plan()
        params = pb.ExecuteQueryParams()
        params.logical_plan.CopyFrom(plan_to_proto(plan))
        for k, v in ctx.config.explicit_settings().items():
            params.settings.add(key=k, value=v)
        job_id = ctx._client.execute_query(params).job_id

        # wait for the FIRST stage (the maps) to fully complete
        state = cluster.scheduler_impl.state
        deadline = time.time() + 60
        stage1 = []
        while time.time() < deadline:
            tasks = state.get_job_tasks(job_id)
            if tasks:
                first = min(t.partition_id.stage_id for t in tasks)
                stage1 = [t for t in tasks if t.partition_id.stage_id == first]
                if stage1 and all(
                    t.WhichOneof("status") == "completed" for t in stage1
                ):
                    break
            time.sleep(0.02)
        else:
            pytest.fail("map stage did not complete in time")

        # kill an executor that holds completed map outputs — TOTALLY
        # (heartbeat AND data plane), so its outputs really are unreachable
        owners = {t.completed.executor_id for t in stage1}
        victim = next(ex for ex in cluster.executors if ex.id in owners)
        victim.stop()

        status = ctx._wait_for_job(job_id, timeout=120.0)
        tables = [
            ctx._fetch_partition(loc)
            for loc in status.completed.partition_location
        ]
        out = pa.concat_tables(tables).cast(plan.schema())
        assert out.column("s").to_pylist() == [120.0, 40.0, 145.0]

        stats = tracing.counters("recovery")
        recovered = (
            stats.get("fetch_failed", 0)
            + stats.get("map_recomputed", 0)
            + stats.get("lost_task_reset", 0)
            + stats.get("downstream_invalidated", 0)
        )
        assert recovered > 0, f"no recovery events recorded: {stats}"
        assert stats.get("task_retry", 0) > 0, stats
        ctx.close()
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
        cluster.shutdown()


def test_completed_job_with_lost_result_partitions_restarts(sales_table):
    """PR 5 residue (ISSUE 6 satellite): a COMPLETED job whose result
    partitions died with their executor BEFORE the client fetched them was
    never restarted — reset_lost_tasks skips terminal jobs, so the client's
    fetch surfaced an RpcError (pre-fix this test fails exactly there).
    Now the client detects the loss at fetch time (ShuffleFetchError
    against the terminal job), reports it via ReportLostPartition, and the
    scheduler restarts the lost final-stage tasks through the normal
    lineage/retry machinery — the collect returns correct results."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster
    import ballista_tpu.scheduler.state as state_mod

    cluster = StandaloneCluster(n_executors=2)
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    cluster.scheduler_impl.lost_task_check_interval = 0.3
    tracing.counters("recovery", reset=True)
    try:
        ctx = BallistaContext(*cluster.scheduler_addr)
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        df = ctx.sql(
            "select region, sum(amount) as s from sales group by region order by region"
        )
        plan = df.logical_plan()
        job_id = ctx.submit(plan)
        status = ctx._wait_for_job(job_id, timeout=60.0)

        # kill ONE executor holding a result partition — totally (heartbeat
        # AND data plane) — BEFORE anything is fetched; the survivor must
        # recompute its partitions after the fetch-time report
        owners = [
            pl.executor_meta.id for pl in status.completed.partition_location
        ]
        assert owners, "completed job must expose result locations"
        victim = next(ex for ex in cluster.executors if ex.id in owners)
        victim.stop()

        out = ctx._collect_results(job_id, plan.schema(), timeout=120.0)
        assert out.column("s").to_pylist() == [120.0, 40.0, 145.0]

        stats = tracing.counters("recovery")
        assert stats.get("result_partition_restarted", 0) > 0, stats
        assert stats.get("completed_job_restarted", 0) > 0, stats
        assert stats.get("result_fetch_restarted", 0) > 0, stats
        ctx.close()
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
        cluster.shutdown()


def test_restart_completed_job_declines_non_terminal_and_unknown():
    """ReportLostPartition is a no-op (restarted=False) for unknown/failed
    jobs and for executors that hold no final-stage output — the client
    re-raises its fetch error instead of looping. A RUNNING job with a
    completed final-stage task on the named executor DOES restart it
    (ISSUE 8: streaming clients fetch partial_location entries mid-job;
    without the requeue the dead location would be republished on every
    status fold) — and the job status stays running, no flip needed."""
    s = SchedulerState(MemoryBackend(), "t")
    assert s.restart_completed_job("nope", "e1") == 0
    failed = pb.JobStatus()
    failed.failed.error = "x"
    s.save_job_metadata("jf", failed)
    s.save_task_status(_task("jf", 1, 0, "completed", "e1"))
    assert s.restart_completed_job("jf", "e1") == 0  # terminal-failed
    _running_job(s, "jr")
    s.save_task_status(_task("jr", 1, 0, "completed", "e1"))
    assert s.restart_completed_job("jr", "e9") == 0  # e9 holds nothing
    assert s.restart_completed_job("jr", "e1") == 1  # running: requeued
    assert s.get_job_metadata("jr").WhichOneof("status") == "running"
    t = s.get_task_status("jr", 1, 0)
    assert t.WhichOneof("status") is None and t.attempt == 1
    done = pb.JobStatus()
    done.completed.SetInParent()
    s.save_job_metadata("jc", done)
    s.save_task_status(_task("jc", 1, 0, "completed", "e1"))
    s.save_task_status(_task("jc", 2, 0, "completed", "e1"))
    s.save_task_status(_task("jc", 2, 1, "completed", "e2"))
    assert s.restart_completed_job("jc", "e9") == 0  # e9 holds nothing
    assert s.get_job_metadata("jc").WhichOneof("status") == "completed"
    # e1's FINAL-stage task restarts (stage-1 output stays; lineage handles
    # it only if the re-run's fetch actually fails)
    assert s.restart_completed_job("jc", "e1") == 1
    assert s.get_job_metadata("jc").WhichOneof("status") == "running"
    t = s.get_task_status("jc", 2, 0)
    assert t.WhichOneof("status") is None and t.attempt == 1
    assert "result partition lost" in t.history[0].error
    # the untouched final task keeps its completed location
    assert s.get_task_status("jc", 2, 1).WhichOneof("status") == "completed"


def test_work_dir_gc(tmp_path):
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.executor.execution_loop import PollLoop

    loop = PollLoop.__new__(PollLoop)  # no scheduler needed
    loop.work_dir = str(tmp_path)
    loop.config = BallistaConfig()  # the sweep reads the storage root too
    loop.shuffle_ttl_seconds = 0.1
    old = tmp_path / "old_job"
    old.mkdir()
    (old / "1").mkdir()
    time.sleep(0.2)
    fresh = tmp_path / "fresh_job"
    fresh.mkdir()
    removed = loop.gc_work_dir()
    assert removed == 1
    assert not old.exists() and fresh.exists()
