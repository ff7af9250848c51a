"""Replicated control plane (ISSUE 20): lease-sharded job ownership,
fenced (compare-and-swap) writes, peer failover, and ownership redirects.

Unit layer: two hand-built SchedulerStates over ONE shared backend pin the
lease/fencing state machine — mint-with-commit atomicity, renewal, expiry,
adoption running restart recovery scoped to the job, and the deposed
owner's writes rejected whole with no unfenced degradation.

Server layer: in-process SchedulerServer peers pin the RPC-visible
behavior — PollWork's gate-and-partition redirect, the GetJobStatus
ownership hint, and the queued-grace sweep that fails submissions whose
planning replica died before the atomic commit.

E2E layer (the ISSUE 20 acceptance runs): a 3-replica cluster whose job
owner is killed mid-job completes bit-identical to a single-scheduler
fault-free oracle with zero task retries (failover = a peer's scoped
recovery run, not a re-execution); and a paused-then-revived deposed
owner's late writes are rejected without corrupting the adopted job.
"""

import threading
import time

import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.scheduler.kv import MemoryBackend
from ballista_tpu.scheduler.server import SchedulerServer
from ballista_tpu.scheduler.state import SchedulerState
from ballista_tpu.utils import tracing

# -- helpers -----------------------------------------------------------------


def _replica_state(kv, rid, addr, ttl="0.05"):
    cfg = BallistaConfig({"ballista.scheduler.lease_ttl_s": ttl})
    s = SchedulerState(kv, "t", cfg)
    s.replica_id = rid
    s.replica_addr = addr
    return s


def _commit_running(s, job="j"):
    """Commit a minimal 'planned' job the way planning does: the running
    flip rides the same atomic batch that mints the ownership lease."""
    running = pb.JobStatus()
    running.running.SetInParent()
    s.commit_plan_batch(
        job, [(s._key("jobs", job), running.SerializeToString())]
    )


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


# -- lease + fencing state machine (unit) ------------------------------------


def test_lease_minted_atomically_with_plan_commit():
    kv = MemoryBackend()
    a = _replica_state(kv, "a", "127.0.0.1:7001", ttl="5")
    _commit_running(a)
    lease = a.job_lease("j")
    assert lease is not None
    assert lease.replica_id == "a"
    assert lease.fence == 1
    assert lease.addr == "127.0.0.1:7001"
    assert a.owns_job("j") and a.owned_jobs() == ["j"]
    # the fence counter is durable and outlives the lease
    assert kv.get("/ballista/t/leasegen/j") == b"1"
    # a peer racing the same job id loses the expect-absent CAS whole
    b = _replica_state(kv, "b", "127.0.0.1:7002", ttl="5")
    with pytest.raises(RuntimeError, match="lease race"):
        _commit_running(b)
    assert not b.owns_job("j")
    assert a.job_lease("j").replica_id == "a"


def test_renewal_keeps_ownership_against_peers():
    kv = MemoryBackend()
    a = _replica_state(kv, "a", "127.0.0.1:7001")
    b = _replica_state(kv, "b", "127.0.0.1:7002")
    _commit_running(a)
    # heartbeat at ~TTL/2 for several TTLs: the lease never lapses
    for _ in range(6):
        time.sleep(0.02)
        assert a.renew_owned_leases() == 1
    holder = b.ensure_job_writable("j")
    assert holder is not None and holder.replica_id == "a"
    assert not b.owns_job("j")


def test_peer_adopts_after_lease_expiry_with_monotonic_fence():
    kv = MemoryBackend()
    a = _replica_state(kv, "a", "127.0.0.1:7001")
    b = _replica_state(kv, "b", "127.0.0.1:7002")
    _commit_running(a)
    time.sleep(0.1)  # owner stops renewing: replica death
    tracing.counters("recovery", reset=True)
    assert b.ensure_job_writable("j") is None  # adopt-on-demand
    assert b.owns_job("j")
    lease = b.job_lease("j")
    assert lease.replica_id == "b"
    assert lease.fence == 2  # strictly past every fence the dead owner held
    stats = tracing.counters("recovery", reset=True)
    assert stats.get("lease_adopted", 0) == 1, stats


def test_deposed_owner_writes_rejected_whole_without_corruption():
    kv = MemoryBackend()
    a = _replica_state(kv, "a", "127.0.0.1:7001")
    b = _replica_state(kv, "b", "127.0.0.1:7002")
    _commit_running(a)
    time.sleep(0.1)
    assert b.ensure_job_writable("j") is None  # b adopted
    # the deposed-but-alive owner wakes up and writes as if nothing happened
    failed = pb.JobStatus()
    failed.failed.error = "stale verdict from a deposed owner"
    assert a.save_job_metadata("j", failed) is False
    assert a.fence_rejected == 1
    assert not a.owns_job("j")
    # durable truth is untouched: the adopter's running status survives
    assert b.get_job_metadata("j").WhichOneof("status") == "running"
    # deposition is remembered: even after b's lease expires, a's writes
    # never degrade to the unfenced legacy path
    time.sleep(0.1)
    assert a.save_job_metadata("j", failed) is False
    assert b.get_job_metadata("j").WhichOneof("status") == "running"


def test_expired_unclaimed_lease_self_heals():
    """Single-replica servers run no heartbeat thread: their leases expire
    mid-job routinely and the next fenced write re-mints in place."""
    kv = MemoryBackend()
    a = _replica_state(kv, "a", "127.0.0.1:7001")
    _commit_running(a)
    time.sleep(0.1)
    assert a.job_lease("j") is None  # lapsed, nobody claimed it
    tracing.counters("recovery", reset=True)
    running = pb.JobStatus()
    running.running.SetInParent()
    assert a.save_job_metadata("j", running) is True
    lease = a.job_lease("j")
    assert lease.replica_id == "a" and lease.fence == 2
    assert a.owns_job("j")
    assert tracing.counters("recovery", reset=True).get("lease_reminted", 0) == 1


def test_adoption_runs_restart_recovery_scoped_to_the_job():
    """Failover IS restart recovery run by a peer: the adopter reloads the
    dead owner's durable assignment ledger with a fresh grace window, and
    the executor's attempt-matching echo re-adopts the task — no retry."""
    kv = MemoryBackend()
    a = _replica_state(kv, "a", "127.0.0.1:7001")
    _commit_running(a)
    a.save_executor_metadata(_meta("e1"))
    _stage_plan(a)
    a.save_task_status(_pending("j", 1, 0))
    assert a.assign_next_schedulable_task("e1") is not None
    time.sleep(0.1)  # owner dies
    tracing.counters("recovery", reset=True)
    b = _replica_state(kv, "b", "127.0.0.1:7002")
    assert b.ensure_job_writable("j") is None  # adopts + scoped recover
    assert ("j", 1, 0) in b._assigned
    stats = tracing.counters("recovery")
    assert stats.get("restart_job_resumed", 0) == 1, stats
    assert stats.get("restart_assignment_restored", 0) == 1, stats
    # restart_generation untouched: no process died
    assert kv.get("/ballista/t/meta/restart_generation") is None
    # the owner executor vouches: re-adopted, not requeued
    assert b.reconcile_running_tasks("e1", [_echo("j", 1, 0, 0)]) == 0
    assert b.get_task_status("j", 1, 0).WhichOneof("status") == "running"
    assert tracing.counters("recovery", reset=True).get("task_retry", 0) == 0


# -- server-level ownership behavior -----------------------------------------


def test_pollwork_redirects_foreign_statuses_to_the_owner():
    """Gate-and-partition: a poll carrying statuses for a live peer's job
    folds nothing for it, assigns nothing, and aborts UNAVAILABLE naming
    the owner — the executor's retry loop re-homes and re-delivers."""
    kv = MemoryBackend()
    cfg = BallistaConfig({"ballista.scheduler.lease_ttl_s": "5"})
    srv_a = SchedulerServer(
        kv, config=cfg, replica_id="a", advertise_addr="127.0.0.1:7001"
    )
    srv_b = SchedulerServer(
        kv, config=cfg, replica_id="b", advertise_addr="127.0.0.1:7002"
    )
    sa = srv_a.state
    with kv.lock():
        _commit_running(sa)
        sa.save_executor_metadata(_meta("e1"))
        _stage_plan(sa)
        sa.save_task_status(_pending("j", 1, 0))
    done = _pending("j", 1, 0)
    done.completed.executor_id = "e1"
    done.completed.path = "/x"
    tracing.counters("recovery", reset=True)
    params = pb.PollWorkParams(
        metadata=_meta("e1"), can_accept_task=True, task_status=[done]
    )
    with pytest.raises(RuntimeError, match="owned by peer replica 'a'"):
        srv_b.PollWork(params)
    # the foreign completion was NOT folded — the owner's pending task is
    # untouched and no assignment happened on the redirecting replica
    assert sa.get_task_status("j", 1, 0).WhichOneof("status") is None
    assert ("j", 1, 0) not in srv_b.state._assigned
    stats = tracing.counters("recovery", reset=True)
    assert stats.get("ownership_redirected", 0) == 1, stats
    # the owner itself folds the same (idempotent) re-delivery fine
    result = srv_a.PollWork(
        pb.PollWorkParams(metadata=_meta("e1"), task_status=[done])
    )
    assert result is not None
    assert sa.get_task_status("j", 1, 0).WhichOneof("status") == "completed"


def test_get_job_status_carries_owner_hint_on_non_owners():
    kv = MemoryBackend()
    cfg = BallistaConfig({"ballista.scheduler.lease_ttl_s": "5"})
    srv_a = SchedulerServer(
        kv, config=cfg, replica_id="a", advertise_addr="127.0.0.1:7001"
    )
    srv_b = SchedulerServer(
        kv, config=cfg, replica_id="b", advertise_addr="127.0.0.1:7002"
    )
    with kv.lock():
        _commit_running(srv_a.state)
    # any replica answers with KV truth; non-owners add the owner's address
    res_b = srv_b.GetJobStatus(pb.GetJobStatusParams(job_id="j"))
    assert res_b.status.WhichOneof("status") == "running"
    assert res_b.owner_addr == "127.0.0.1:7001"
    res_a = srv_a.GetJobStatus(pb.GetJobStatusParams(job_id="j"))
    assert res_a.status.WhichOneof("status") == "running"
    assert res_a.owner_addr == ""


def test_queued_grace_sweep_fails_dead_planners_jobs_only():
    """A queued job whose planner replica heartbeats stays queued; once the
    heartbeat lapses AND the 2xTTL grace passes, a peer fails it with a CAS
    against the exact queued bytes (racing a resurrected planner's atomic
    commit, exactly one write lands)."""
    kv = MemoryBackend()
    cfg = BallistaConfig({"ballista.scheduler.lease_ttl_s": "0.05"})
    srv_a = SchedulerServer(
        kv, config=cfg, replica_id="a", advertise_addr="127.0.0.1:7001"
    )
    srv_b = SchedulerServer(
        kv, config=cfg, replica_id="b", advertise_addr="127.0.0.1:7002"
    )
    sa = srv_a.state
    with kv.lock():
        queued = pb.JobStatus()
        queued.queued.SetInParent()
        sa.save_job_metadata("jq", queued)
        sa.mark_job_planner("jq")
        sa.replica_heartbeat()
    seen = {}
    with kv.lock():
        assert srv_b._sweep_queued_grace_locked(seen) == 0
    assert "jq" not in seen  # planner heartbeating: no grace clock started
    time.sleep(0.12)  # replica a's heartbeat lapses
    with kv.lock():
        assert srv_b._sweep_queued_grace_locked(seen) == 0  # grace starts
    assert "jq" in seen
    time.sleep(0.12)  # 2xTTL grace elapses
    tracing.counters("recovery", reset=True)
    with kv.lock():
        assert srv_b._sweep_queued_grace_locked(seen) == 1
    st = srv_b.state.get_job_metadata("jq")
    assert st.WhichOneof("status") == "failed"
    assert "replica 'a'" in st.failed.error
    assert tracing.counters("recovery", reset=True).get("queued_grace_failed", 0) == 1
    # terminal: a later sweep has nothing left to do
    with kv.lock():
        assert srv_b._sweep_queued_grace_locked(seen) == 0


# -- acceptance e2e ----------------------------------------------------------

GROUP_SQL = (
    "select region, sum(amount) as s, count(*) as n from sales "
    "group by region order by region"
)
_SETTINGS = {"ballista.shuffle.partitions": "4"}


def _oracle(sales_table):
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=_SETTINGS)
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        out = ctx.sql(GROUP_SQL).collect()
        ctx.close()
        return out
    finally:
        cluster.shutdown()


def _submit_async(ctx, sql):
    """Run collect() on a worker thread; returns (thread, box, errors)."""
    box, errors = {}, []

    def run():
        try:
            box["out"] = ctx.sql(sql).collect()
        except Exception as e:  # surface in the main thread
            errors.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box, errors


def _wait_for(pred, timeout=10.0, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.02)
    pytest.fail(f"timed out waiting for {what}")


def test_three_replica_owner_kill_failover_bit_identical(sales_table):
    """ISSUE 20 acceptance: 3 replicas over one KV, the job's owner is
    killed mid-job (permanently), an idle peer adopts within the lease TTL
    via scoped restart recovery, and the job completes bit-identical to a
    single-scheduler fault-free oracle with zero task retries."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    clean = _oracle(sales_table)
    cfg = BallistaConfig({"ballista.scheduler.lease_ttl_s": "0.3"})
    tracing.counters("recovery", reset=True)
    # no executors yet: the job is guaranteed mid-flight when the owner dies
    cluster = StandaloneCluster(n_executors=0, n_schedulers=3, config=cfg)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings=_SETTINGS,
            endpoints=cluster.scheduler_endpoints,
        )
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        t, box, errors = _submit_async(ctx, GROUP_SQL)
        s0 = cluster.scheduler_impls[0].state
        _wait_for(lambda: s0.owned_jobs(), what="replica 0 planning commit")
        job_id = s0.owned_jobs()[0]
        cluster.kill_scheduler(0)
        peers = cluster.scheduler_impls[1:]
        _wait_for(
            lambda: any(impl.state.owns_job(job_id) for impl in peers),
            what="a peer adopting the orphaned job",
        )
        for _ in range(2):
            cluster._spawn_executor()
        t.join(90)
        assert not t.is_alive(), "failover run never completed"
        assert not errors, errors
        ctx.close()
    finally:
        cluster.shutdown()
    stats = tracing.counters("recovery", reset=True)
    assert box["out"].equals(clean), (
        box["out"].to_pydict(), clean.to_pydict()
    )
    assert stats.get("lease_adopted", 0) >= 1, stats
    assert stats.get("restart_job_resumed", 0) >= 1, stats
    assert stats.get("task_retry", 0) == 0, stats


def test_paused_deposed_owner_late_writes_rejected_e2e(sales_table):
    """ISSUE 20 fencing acceptance: the owner pauses (a long GC pause —
    housekeeping stops renewing, the process stays alive), a peer adopts,
    and the revived owner's late writes are rejected whole: the adopted
    job completes uncorrupted, bit-identical to the oracle."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    clean = _oracle(sales_table)
    cfg = BallistaConfig({"ballista.scheduler.lease_ttl_s": "0.2"})
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(n_executors=0, n_schedulers=2, config=cfg)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings=_SETTINGS,
            endpoints=cluster.scheduler_endpoints,
        )
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        t, box, errors = _submit_async(ctx, GROUP_SQL)
        impl0, impl1 = cluster.scheduler_impls
        _wait_for(lambda: impl0.state.owned_jobs(),
                  what="replica 0 planning commit")
        job_id = impl0.state.owned_jobs()[0]
        impl0.stop_housekeeping()  # the pause: renewals stop, process lives
        _wait_for(lambda: impl1.state.owns_job(job_id),
                  what="the peer adopting the paused owner's job")
        # the owner revives and writes as if it still owned the job
        stale = pb.JobStatus()
        stale.failed.error = "stale verdict from the paused owner"
        with cluster.kv.lock():
            assert impl0.state.save_job_metadata(job_id, stale) is False
        assert impl0.state.fence_rejected >= 1
        # no corruption: the adopter's running status survived the attempt
        assert (
            impl1.state.get_job_metadata(job_id).WhichOneof("status")
            == "running"
        )
        for _ in range(2):
            cluster._spawn_executor()
        t.join(90)
        assert not t.is_alive(), "adopted job never completed"
        assert not errors, errors
        # the job finished under the adopter, untouched by the stale write
        assert (
            impl1.state.get_job_metadata(job_id).WhichOneof("status")
            == "completed"
        )
        ctx.close()
    finally:
        cluster.shutdown()
    stats = tracing.counters("recovery", reset=True)
    assert box["out"].equals(clean), (
        box["out"].to_pydict(), clean.to_pydict()
    )
    assert stats.get("fence_rejected", 0) >= 1, stats
    assert stats.get("task_retry", 0) == 0, stats
