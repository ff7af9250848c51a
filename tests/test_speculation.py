"""Speculative execution (ISSUE 11): cost-model straggler detection,
duplicate attempts through the durable ledger, first-completion-wins, and
per-tenant latency SLOs.

The invariants under test mirror what made PRs 5/6 trustworthy:

- a duplicate attempt is dispatched ONLY through the speculation ledger
  (write-through KV), never by touching the primary's task status;
- first completion wins, whichever attempt it is — the losing sibling's
  report is dropped by the stale-attempt guards and never double-counts
  or clobbers published locations;
- a scheduler crash+restart mid-speculation recovers BOTH attempts (the
  primary from its running status + assignment ledger, the duplicate from
  the speculation ledger) and the owners' echoes re-adopt them;
- fault-free runs with the default thresholds launch nothing;
- results stay bit-identical to the fault-free baseline with speculation
  ON under seeded `task.slow` chaos (end-to-end acceptance here; the
  fuzz slice in test_fuzz_device.py widens the plan space).
"""

import time

import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.ops import costmodel
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.scheduler.kv import MemoryBackend, SqliteBackend
from ballista_tpu.scheduler.state import SchedulerState
from ballista_tpu.utils import tracing
from ballista_tpu.utils.chaos import ChaosInjector

# -- helpers ----------------------------------------------------------------


def _spec_config(**over):
    """Speculation armed with a zero floor + 2x slack so unit tests control
    the trigger purely through the aged watch entry; cost store in-memory
    (dir ""), never touching the repo's on-disk cache."""
    base = {
        "ballista.tpu.cost_model_dir": "",
        "ballista.speculation.min_runtime_ms": "0",
        "ballista.speculation.multiplier": "2",
    }
    base.update(over)
    return BallistaConfig(base)


def _meta(i):
    return pb.ExecutorMetadata(id=i, host="h", port=1)


def _running_job(s, job="j"):
    running = pb.JobStatus()
    running.running.SetInParent()
    s.save_job_metadata(job, running)


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


def _completed(job, stage, part, attempt, executor, speculative=False):
    t = _pending(job, stage, part, attempt)
    t.speculative = speculative
    t.completed.executor_id = executor
    t.completed.path = f"/w/{executor}"
    return t


def _straggling_state(kv=None, config=None, state=None):
    """A state with one RUNNING task on e1 (aged 5s into its watch entry),
    a second live executor e2, and a warm task.run prediction of ~1ms —
    grossly exceeded, so the straggler monitor fires on the next idle
    slot."""
    costmodel.reset()
    s = state or SchedulerState(
        kv or MemoryBackend(), "t", config=config or _spec_config())
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    s.save_executor_metadata(_meta("e2"))
    _stage_plan(s)
    s.save_task_status(_pending("j", 1, 0))
    assert s.assign_next_schedulable_task("e1") is not None
    costmodel.seed(s._task_run_op("j", 1), 1.0, 0.001, engine="task")
    owner, attempt, t0 = s._running_since[("j", 1, 0)]
    s._running_since[("j", 1, 0)] = (owner, attempt, t0 - 5.0)
    return s


SPEC_KEY = "/ballista/t/speculation/j/1/0"


# -- straggler detection + duplicate dispatch -------------------------------


def test_straggler_launches_duplicate_through_the_ledger():
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    got = s.maybe_speculate("e2")
    assert got is not None
    dup, plan = got
    assert dup.attempt == 1 and dup.speculative
    assert plan is not None
    # write-through ledger record: the restart truth for the duplicate
    raw = s.kv.get(SPEC_KEY)
    assert raw is not None
    a = pb.Assignment()
    a.ParseFromString(raw)
    assert a.executor_id == "e2" and a.attempt == 1
    # the PRIMARY's task status is untouched: still running attempt 0 on e1
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "running"
    assert cur.attempt == 0 and cur.running.executor_id == "e1"
    assert tracing.counters("speculation").get("launched") == 1
    # never twice on one task; never back onto the primary's owner
    assert s.maybe_speculate("e2") is None
    assert s.maybe_speculate("e1") is None


def test_cold_model_never_speculates():
    """No prediction -> no speculation: a cold store reproduces
    pre-speculation scheduling exactly."""
    costmodel.reset()
    s = SchedulerState(MemoryBackend(), "t", config=_spec_config())
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    s.save_executor_metadata(_meta("e2"))
    _stage_plan(s)
    s.save_task_status(_pending("j", 1, 0))
    assert s.assign_next_schedulable_task("e1") is not None
    owner, attempt, t0 = s._running_since[("j", 1, 0)]
    s._running_since[("j", 1, 0)] = (owner, attempt, t0 - 300.0)
    assert s.maybe_speculate("e2") is None


def test_default_floor_spares_fresh_tasks():
    """Fault-free runs with default thresholds launch nothing: a task
    younger than ballista.speculation.min_runtime_ms never speculates,
    whatever the model predicts."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state(
        config=_spec_config(**{"ballista.speculation.min_runtime_ms": "500000"})
    )
    assert s.maybe_speculate("e2") is None
    assert tracing.counters("speculation").get("launched", 0) == 0


def test_speculation_disabled_by_config():
    s = _straggling_state(
        config=_spec_config(**{"ballista.speculation": "false"})
    )
    assert s.maybe_speculate("e2") is None


def test_executor_that_failed_an_attempt_is_not_trusted():
    """The tail-latency rescue must not land on an executor that already
    failed an attempt of this task."""
    s = _straggling_state()
    cur = s.get_task_status("j", 1, 0)
    h = cur.history.add()
    h.attempt = 0
    h.executor_id = "e2"
    h.error = "boom"
    s.save_task_status(cur)
    owner, attempt, t0 = s._running_since[("j", 1, 0)]
    s._running_since[("j", 1, 0)] = (owner, attempt, t0 - 5.0)
    assert s.maybe_speculate("e2") is None


# -- first completion wins --------------------------------------------------


def test_duplicate_wins_primary_report_dropped():
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    assert s.maybe_speculate("e2") is not None
    # the duplicate (attempt 1) completes first
    assert s.accept_task_status(_completed("j", 1, 0, 1, "e2", speculative=True))
    assert s.kv.get(SPEC_KEY) is None
    stats = tracing.counters("speculation")
    assert stats.get("won") == 1
    assert stats.get("wasted_seconds", 0) > 0
    # the straggling primary finally reports: dropped as stale, and the
    # winner's published location stands
    tracing.counters("recovery", reset=True)
    assert not s.accept_task_status(_completed("j", 1, 0, 0, "e1"))
    assert tracing.counters("recovery").get("stale_status_dropped") == 1
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "completed"
    assert cur.attempt == 1 and cur.completed.executor_id == "e2"


def test_primary_wins_duplicate_report_dropped():
    """The numeric attempt guard alone would let the higher-numbered
    duplicate clobber the primary's completion — the completion-stands
    guard must drop it."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    assert s.maybe_speculate("e2") is not None
    assert s.accept_task_status(_completed("j", 1, 0, 0, "e1"))
    stats = tracing.counters("speculation")
    assert stats.get("lost") == 1
    assert s.kv.get(SPEC_KEY) is None
    tracing.counters("recovery", reset=True)
    assert not s.accept_task_status(_completed("j", 1, 0, 1, "e2", speculative=True))
    assert tracing.counters("recovery").get("stale_status_dropped") == 1
    cur = s.get_task_status("j", 1, 0)
    assert cur.attempt == 0 and cur.completed.executor_id == "e1"


def test_failed_duplicate_spares_the_primary():
    """A dying duplicate retires the speculation without consuming the
    task's retry budget or touching the primary."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    assert s.maybe_speculate("e2") is not None
    failed = _pending("j", 1, 0, attempt=1)
    failed.speculative = True
    failed.failed.error = "dup died"
    assert not s.accept_task_status(failed)
    assert tracing.counters("speculation").get("failed") == 1
    assert s.kv.get(SPEC_KEY) is None
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "running" and cur.attempt == 0
    # the primary then completes normally
    assert s.accept_task_status(_completed("j", 1, 0, 0, "e1"))


def test_duplicate_fetch_failure_still_recomputes_the_lost_map():
    """Review regression: a duplicate's fetch_failed report is dropped (the
    primary still runs, no retry budget consumed) — but the lineage it
    carries must NOT be: the named lost map output is recomputed now, not
    after the next consumer trips on it a failure round-trip later."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    # a completed upstream map output the duplicate will report lost
    map_done = _completed("j", 0, 0, 0, "em")
    s.save_task_status(map_done)
    assert s.maybe_speculate("e2") is not None
    tracing.counters("recovery", reset=True)
    ff = _pending("j", 1, 0, attempt=1)
    ff.speculative = True
    ff.fetch_failed.executor_id = "e2"
    ff.fetch_failed.error = "connection refused"
    ff.fetch_failed.map_stage_id = 0
    ff.fetch_failed.map_partition_id = 0
    ff.fetch_failed.map_executor_id = "em"
    ff.fetch_failed.path = "/w/em"
    assert not s.accept_task_status(ff)
    assert tracing.counters("speculation").get("failed") == 1
    assert s.kv.get(SPEC_KEY) is None
    # the lost map output was requeued for recompute with the lineage
    assert tracing.counters("recovery").get("map_recomputed") == 1
    mt = s.get_task_status("j", 0, 0)
    assert mt.WhichOneof("status") is None and mt.attempt == 1
    assert mt.history[0].executor_id == "em"
    # the primary is untouched
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "running" and cur.attempt == 0


def test_saturated_tenant_gets_no_speculative_slot():
    """Review regression: the rescue must not grant a tenant past its
    max_inflight quota an extra physical slot — the PR 7 starvation bound
    holds for duplicates too."""
    s = _straggling_state(
        config=_spec_config(**{"ballista.tenant.max_inflight": "1"})
    )
    s.save_job_tenant("j", "alice", 0)
    assert s.maybe_speculate("e2") is None  # alice saturated at 1 in flight
    s2 = _straggling_state(
        config=_spec_config(**{"ballista.tenant.max_inflight": "2"})
    )
    s2.save_job_tenant("j", "alice", 0)
    assert s2.maybe_speculate("e2") is not None  # headroom: rescue allowed


def test_primary_failure_promotes_the_duplicate():
    """The primary dies while its duplicate is in flight: the duplicate IS
    the retry — promoted to the current attempt on its executor, entering
    the normal assignment ledger, consuming no retry budget."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    assert s.maybe_speculate("e2") is not None
    spec_t0 = s._speculative[("j", 1, 0)][2]
    t = s.get_task_status("j", 1, 0)
    assert s.requeue_task(t, "e1", "primary lost", limit=1)
    assert tracing.counters("speculation").get("promoted") == 1
    # the watch clock keeps the duplicate's LAUNCH time: its completion
    # must observe the true duration, not seconds-since-promotion
    assert s._running_since[("j", 1, 0)] == ("e2", 1, spec_t0)
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "running"
    assert cur.attempt == 1 and cur.speculative
    assert cur.running.executor_id == "e2"
    assert len(cur.history) == 1 and cur.history[0].error == "primary lost"
    # speculation record retired into a normal assignment-ledger entry
    assert s.kv.get(SPEC_KEY) is None
    raw = s.kv.get("/ballista/t/assignments/j/1/0")
    assert raw is not None
    a = pb.Assignment()
    a.ParseFromString(raw)
    assert a.executor_id == "e2" and a.attempt == 1
    # the promoted attempt completes like any other
    assert s.accept_task_status(_completed("j", 1, 0, 1, "e2", speculative=True))


def test_lineage_invalidation_retires_instead_of_promoting():
    """Review regression: a requeue caused by the task's UPSTREAM
    locations dying (lineage invalidation / fetch_failed) must NOT promote
    the duplicate — it was bound to the same dead locations; plain requeue
    rebinds fresh ones at the next assignment."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    assert s.maybe_speculate("e2") is not None
    t = s.get_task_status("j", 1, 0)
    assert s.requeue_task(
        t, "e1", "upstream shuffle locations lost mid-run", limit=3,
        promote=False,
    )
    stats = tracing.counters("speculation")
    assert stats.get("promoted", 0) == 0
    assert stats.get("failed") == 1  # the duplicate retired with the reset
    assert s.kv.get(SPEC_KEY) is None
    cur = s.get_task_status("j", 1, 0)
    # pending, numbered PAST the retired duplicate's attempt 1 (ISSUE 15:
    # the retired duplicate may still be running — a same-number requeue
    # would let its late report impersonate the fresh attempt)
    assert cur.WhichOneof("status") is None and cur.attempt == 2


def test_speculative_duplicate_carries_its_primarys_plan_bytes():
    """A duplicate goes through the same binding and the same kept encoding
    as the first attempt (ISSUE 27): same bytes, no second encode."""
    from ballista_tpu.scheduler.server import SchedulerServer

    srv = SchedulerServer(MemoryBackend(), namespace="t", config=_spec_config())
    s = _straggling_state(state=srv.state)
    primary = s.get_task_status("j", 1, 0)
    bound = s._bound_stage_plan("j", 1, s._ensure_task_index())
    td = srv._task_definition(primary, bound)
    dup, dup_plan = s.maybe_speculate("e2")
    td_dup = srv._task_definition(dup, dup_plan)
    assert dup.speculative and td_dup.attempt == td.attempt + 1
    assert td_dup.plan.SerializeToString() == td.plan.SerializeToString()
    assert s.plan_encodes == 1


def test_push_status_suppresses_unchanged_rewrites():
    """Review regression: one push per TRANSITION — synchronize's
    byte-identical running re-writes (one per non-final task completion)
    must not wake every SubscribeJobStatus subscriber."""
    import threading

    from ballista_tpu.scheduler.server import SchedulerServer

    costmodel.reset()
    srv = SchedulerServer(MemoryBackend(), config=_spec_config())
    running = pb.JobStatus()
    running.running.SetInParent()
    srv.state.save_job_metadata("j", running)
    stream = srv.SubscribeJobStatus(pb.GetJobStatusParams(job_id="j"))
    got = []

    def consume():
        for res in stream:
            got.append(res.status.WhichOneof("status"))

    th = threading.Thread(target=consume, daemon=True)
    th.start()
    time.sleep(0.1)
    srv.state.save_job_metadata("j", running)  # identical: suppressed
    srv.state.save_job_metadata("j", running)  # identical: suppressed
    done = pb.JobStatus()
    done.completed.SetInParent()
    srv.state.save_job_metadata("j", done)  # transition: pushed, terminal
    th.join(5)
    assert not th.is_alive()
    assert got == ["running", "completed"], got


def test_redelivered_completion_stays_idempotent():
    """Review regression: the completion-stands guard must NOT drop a
    redelivery of the SAME completion (same attempt, same executor) — a
    scheduler crash between accepting a job's final status and the
    job-status fold makes the executor redeliver it, and dropping it would
    wedge the job in running forever."""
    s = _straggling_state()
    done = _completed("j", 1, 0, 0, "e1")
    assert s.accept_task_status(done)
    # exact redelivery (post-crash requeue): accepted, so the caller
    # re-enters the job into the synchronize set
    assert s.accept_task_status(_completed("j", 1, 0, 0, "e1"))
    # a DIFFERENT completion for the resolved task still drops: another
    # executor's racing report must not clobber the published location
    assert not s.accept_task_status(_completed("j", 1, 0, 0, "e2"))
    cur = s.get_task_status("j", 1, 0)
    assert cur.completed.executor_id == "e1"


def test_promotion_respects_the_retry_budget():
    """Review regression: a primary already AT its final allowed attempt
    must fail the job when it dies — the in-flight duplicate is retired,
    never promoted to attempt numbers past the configured limit."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    assert s.maybe_speculate("e2") is not None
    t = s.get_task_status("j", 1, 0)
    # limit 0: attempt 0 IS the final budgeted attempt
    assert not s.requeue_task(t, "e1", "primary lost", limit=0)
    stats = tracing.counters("speculation")
    assert stats.get("promoted", 0) == 0
    assert stats.get("failed") == 1
    assert s.kv.get(SPEC_KEY) is None  # duplicate record retired with the job


# -- crash + restart recovery -----------------------------------------------


def test_restart_recovers_both_attempts_from_the_ledger(tmp_path):
    """ISSUE 11 acceptance: a scheduler crash mid-speculation recovers the
    primary (assignment ledger + running status) AND the duplicate
    (speculation ledger); the owners' echoes re-adopt both, and the pair
    then resolves through first-completion-wins exactly as if the crash
    never happened."""
    db = str(tmp_path / "state.db")
    s1 = _straggling_state(kv=SqliteBackend(db))
    assert s1.maybe_speculate("e2") is not None
    del s1  # crash with both attempts in flight

    tracing.counters("recovery", reset=True)
    tracing.counters("speculation", reset=True)
    s2 = SchedulerState(SqliteBackend(db), "t", config=_spec_config())
    stats = s2.recover()
    assert stats.get("restart_assignment_restored") == 1
    assert stats.get("restart_speculation_restored") == 1
    assert tracing.counters("speculation").get("restored") == 1
    assert ("j", 1, 0) in s2._assigned
    assert s2.speculation_active(("j", 1, 0), "e2", 1)
    # both owners vouch: nothing requeues, the duplicate is re-adopted
    assert s2.reconcile_running_tasks("e1", [_echo("j", 1, 0, 0)]) == 0
    assert s2.reconcile_running_tasks("e2", [_echo("j", 1, 0, 1)]) == 0
    assert tracing.counters("recovery").get("restart_speculation_readopted") == 1
    # the race resolves normally after the restart: duplicate wins here
    assert s2.accept_task_status(_completed("j", 1, 0, 1, "e2", speculative=True))
    assert not s2.accept_task_status(_completed("j", 1, 0, 0, "e1"))
    cur = s2.get_task_status("j", 1, 0)
    assert cur.attempt == 1 and cur.completed.executor_id == "e2"
    assert s2.kv.get(SPEC_KEY) is None


def test_restart_sweeps_stale_speculation_records(tmp_path):
    """A speculation record whose primary already resolved (or advanced to
    another attempt) is leftover, not live — restart deletes it instead of
    resurrecting a ghost duplicate."""
    db = str(tmp_path / "state.db")
    s1 = _straggling_state(kv=SqliteBackend(db))
    assert s1.maybe_speculate("e2") is not None
    # the primary completes BEFORE the crash... but the crash interleaves
    # with the ledger cleanup: re-write the stale record under the key
    assert s1.accept_task_status(_completed("j", 1, 0, 0, "e1"))
    msg = pb.Assignment(executor_id="e2", attempt=1)
    s1.kv.put(SPEC_KEY, msg.SerializeToString())
    del s1

    s2 = SchedulerState(SqliteBackend(db), "t", config=_spec_config())
    stats = s2.recover()
    assert stats.get("restart_speculation_restored", 0) == 0
    assert s2.kv.get(SPEC_KEY) is None
    assert not s2._speculative


def test_lost_in_transit_duplicate_is_dropped_after_grace():
    """The duplicate has no tasks/ status, so a delivery lost in transit is
    only visible to the speculation ledger: unvouched past the grace
    window, the record is dropped — the primary still runs, nothing
    requeues."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    assert s.maybe_speculate("e2") is not None
    ex, at, t0, vouched, restored = s._speculative[("j", 1, 0)]
    s._speculative[("j", 1, 0)] = (ex, at, t0 - 60.0, vouched, restored)
    # e2 polls with an empty echo: it never received the duplicate
    s.reconcile_running_tasks("e2", [])
    assert tracing.counters("speculation").get("orphaned") == 1
    assert s.kv.get(SPEC_KEY) is None
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "running" and cur.attempt == 0


def test_dead_duplicate_executor_retires_the_speculation():
    """The duplicate's executor lease lapses: the sweep in the straggler
    monitor drops the record (the primary still runs) and the task may
    speculate again onto a live executor."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    assert s.maybe_speculate("e2") is not None
    s.kv.delete("/ballista/t/executors/e2")  # lease gone
    s.save_executor_metadata(_meta("e3"))
    owner, attempt, t0 = s._running_since[("j", 1, 0)]
    s._running_since[("j", 1, 0)] = (owner, attempt, t0 - 5.0)
    got = s.maybe_speculate("e3")
    assert tracing.counters("speculation").get("executor_lost") == 1
    assert got is not None and got[0].attempt == 1
    raw = s.kv.get(SPEC_KEY)
    a = pb.Assignment()
    a.ParseFromString(raw)
    assert a.executor_id == "e3"


# -- per-tenant latency SLOs ------------------------------------------------


def _scan_stage(n_parts=2):
    """A real single-stage plan so assignment can bind it."""
    from ballista_tpu.distributed.planner import DistributedPlanner
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.logical import col

    ctx = ExecutionContext()
    ctx.register_record_batches(
        "t", pa.table({"g": ["a", "b"], "v": [1.0, 2.0]}), n_partitions=n_parts
    )
    df = ctx.table("t").select(col("g"))
    physical = ctx.create_physical_plan(df.logical_plan())
    stages = DistributedPlanner().plan_query_stages("job", physical)
    return stages[0]


def test_tenant_slo_parsing():
    cfg = BallistaConfig({"ballista.tenant.slo_ms": "alice:250, bob:2000"})
    assert cfg.tenant_slos() == {"alice": 250.0, "bob": 2000.0}
    assert BallistaConfig().tenant_slos() == {}
    with pytest.raises(ValueError):
        BallistaConfig({"ballista.tenant.slo_ms": "250"}).tenant_slos()


def test_overdue_tenant_jumps_the_fair_share_order():
    """Deadline-aware admission: pure fair share would hand the idle
    tenant's task out next, but the busy tenant's oldest pending job has
    blown its SLO deadline — it is visited first."""
    costmodel.reset()
    s = SchedulerState(
        MemoryBackend(), "t",
        config=_spec_config(**{"ballista.tenant.slo_ms": "alice:100"}),
    )
    s.save_executor_metadata(_meta("e1"))
    stage_a = _scan_stage(3)
    s.save_job_tenant("aj", "alice", 0, created_at=time.time() - 10.0)
    s.save_stage_plan("aj", stage_a.stage_id, stage_a)
    for p in range(3):
        s.save_task_status(_pending("aj", stage_a.stage_id, p))
    stage_b = _scan_stage(1)
    s.save_job_tenant("bj", "bob", 0)
    s.save_stage_plan("bj", stage_b.stage_id, stage_b)
    s.save_task_status(_pending("bj", stage_b.stage_id, 0))
    tracing.counters("tenancy", reset=True)
    # alice takes the first slot (tie or boost), then the fair-share ratio
    # (1 in flight vs bob's 0) would prefer bob — the blown deadline keeps
    # alice ahead until her pending work drains
    got = [
        s.job_tenant(
            s.assign_next_schedulable_task("e1")[0].partition_id.job_id
        )[0]
        for _ in range(3)
    ]
    assert got == ["alice", "alice", "alice"], got
    # one sustained overdue condition is ONE boost episode, however many
    # admission scans it spans
    assert tracing.counters("tenancy").get("admit_slo_boosted", 0) == 1
    # with no SLO configured the same shape hands bob the second slot
    costmodel.reset()
    s2 = SchedulerState(MemoryBackend(), "t", config=_spec_config())
    s2.save_executor_metadata(_meta("e1"))
    s2.save_job_tenant("aj", "alice", 0, created_at=time.time() - 10.0)
    s2.save_stage_plan("aj", stage_a.stage_id, stage_a)
    for p in range(3):
        s2.save_task_status(_pending("aj", stage_a.stage_id, p))
    s2.save_job_tenant("bj", "bob", 0)
    s2.save_stage_plan("bj", stage_b.stage_id, stage_b)
    s2.save_task_status(_pending("bj", stage_b.stage_id, 0))
    got2 = [
        s2.job_tenant(
            s2.assign_next_schedulable_task("e1")[0].partition_id.job_id
        )[0]
        for _ in range(2)
    ]
    assert got2 == ["alice", "bob"], got2


def test_slo_outcome_counters():
    tracing.counters("speculation", reset=True)
    costmodel.reset()
    s = SchedulerState(
        MemoryBackend(), "t",
        config=_spec_config(**{"ballista.tenant.slo_ms": "alice:100"}),
    )
    s.save_job_tenant("late", "alice", 0, created_at=time.time() - 10.0)
    s._note_job_slo("late")
    s.save_job_tenant("fast", "alice", 0, created_at=time.time())
    s._note_job_slo("fast")
    # no SLO for this tenant: no outcome recorded either way
    s.save_job_tenant("other", "carol", 0, created_at=time.time() - 10.0)
    s._note_job_slo("other")
    # one job is ONE outcome: a re-fold (restart_completed_job after a
    # lost result partition) must not double-count
    s._note_job_slo("late")
    stats = tracing.counters("speculation")
    assert stats.get("slo_misses") == 1
    assert stats.get("slo_met") == 1


# -- whole-stage cost predictions scale with input (PR 10 residue) ----------


def test_stage_run_units_scale_with_input(tmp_path):
    """Pre-fix-failing (ISSUE 11 satellite): stage.run observations must be
    normalized by the stage's input size (memory-scan rows / leaf-file
    bytes), not units=1 — a unit-less rate memorizes one run's seconds and
    guarantees a gross mispredict the first time the same stage shape runs
    at a new scale. Speculation thresholds consume these predictions
    directly."""
    from ballista_tpu.engine import ExecutionContext

    costmodel.reset(clear_dir=True)
    n = 512
    ctx = ExecutionContext(BallistaConfig({
        "ballista.executor.backend": "tpu",
        "ballista.tpu.cost_model_dir": str(tmp_path),
    }))
    ctx.register_record_batches(
        "t",
        pa.table({
            "g": pa.array([f"g{i % 7}" for i in range(n)]),
            "v": pa.array([float(i) for i in range(n)]),
        }),
        n_partitions=1,
    )
    out = ctx.sql("select g, sum(v) as s from t group by g order by g").collect()
    assert out.num_rows == 7
    entries = {
        k: v for k, v in costmodel.snapshot().items()
        if k.startswith("stage.run|")
    }
    assert entries, "no stage.run observation recorded"
    assert any(v["units"] >= n for v in entries.values()), (
        f"stage.run observed with scale-blind units: {entries}"
    )
    costmodel.reset(clear_dir=True)


# -- end-to-end: seeded straggler rescued, bit-identical --------------------


def test_speculation_rescues_seeded_straggler_end_to_end():
    """ISSUE 11 acceptance (cluster-level): a seeded `task.slow` straggler
    in a real 2-executor cluster is rescued by a speculative duplicate —
    the job completes long before the injected delay elapses, the
    duplicate's completion wins, and the result is bit-identical to the
    fault-free run."""
    import numpy as np

    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    rng = np.random.default_rng(1101)
    n = 4000
    table = pa.table({
        "g": pa.array(rng.integers(0, 23, n), type=pa.int64()),
        "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
    })
    sql = "select g, sum(v) as s, count(*) as n from t group by g order by g"
    base_client = {
        "ballista.shuffle.partitions": "2",
        "ballista.cache.results": "false",
        "ballista.tpu.cost_model_dir": "",
    }
    costmodel.reset()
    cluster = StandaloneCluster(
        n_executors=2,
        config=BallistaConfig({
            "ballista.tpu.cost_model_dir": "",
            "ballista.speculation.min_runtime_ms": "150",
            "ballista.speculation.multiplier": "3",
        }),
    )
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=base_client)
        ctx.register_record_batches("t", table, n_partitions=6)
        clean = ctx.sql(sql).collect()
        ctx.close()
        # harvest the executed plan coordinates: chaos verdicts are keyed
        # on (stage, partition, attempt), never job ids, so the clean run's
        # layout predicts the chaos run's exactly
        st = cluster.scheduler_impl.state
        coords = []
        for k, _v in st.kv.get_prefix(st._key("tasks")):
            tail = k.rsplit("/", 3)
            coords.append((int(tail[2]), int(tail[3])))
        by_stage = {}
        for c in coords:
            by_stage.setdefault(c[0], []).append(c)
        # pick a seed injecting EXACTLY one straggler, in a stage with
        # enough fast siblings to warm the prediction past
        # MIN_OBSERVATIONS, whose duplicate (attempt 1) draws fast
        RATE = 0.12
        seed = None
        for cand in range(2000):
            inj = ChaosInjector(cand, RATE, sites=("task.slow",))
            slow = [
                c for c in coords
                if inj.should_inject("task.slow", f"{c[0]}/{c[1]}@a0")
            ]
            if (
                len(slow) == 1
                and len(by_stage[slow[0][0]]) >= costmodel.MIN_OBSERVATIONS + 1
                and not inj.should_inject(
                    "task.slow", f"{slow[0][0]}/{slow[0][1]}@a1"
                )
            ):
                seed = cand
                break
        assert seed is not None, "no qualifying chaos seed in range"
        tracing.counters("speculation", reset=True)
        ctx2 = BallistaContext(
            *cluster.scheduler_addr,
            settings={
                **base_client,
                "ballista.chaos.rate": str(RATE),
                "ballista.chaos.seed": str(seed),
                "ballista.chaos.sites": "task.slow",
                "ballista.chaos.slow_ms": "4000",
            },
        )
        ctx2.register_record_batches("t", table, n_partitions=6)
        t0 = time.perf_counter()
        chaotic = ctx2.sql(sql).collect()
        dt = time.perf_counter() - t0
        ctx2.close()
        assert chaotic.equals(clean), (
            chaotic.to_pydict(), clean.to_pydict(),
        )
        stats = tracing.counters("speculation", reset=True)
        assert stats.get("launched", 0) >= 1, stats
        assert stats.get("won", 0) >= 1, stats
        # the rescue is the point: the job must finish well inside the
        # straggler's injected 4s delay
        assert dt < 3.5, f"speculation did not rescue the tail: {dt:.2f}s"
    finally:
        cluster.shutdown()
        costmodel.reset()


# -- elapsed-ordered straggler heap (ISSUE 13 satellite, PR 11 residue) ------


def test_straggler_heap_agrees_with_linear_scan():
    """The heap-backed candidate walk must return exactly what the old
    linear scan of _running_since would: every running task past the
    speculation floor, most-elapsed first — including entries whose watch
    clocks were re-stamped after their heap push (the reconcile path)."""
    import numpy as np

    cfg = _spec_config(**{"ballista.speculation.min_runtime_ms": "1000"})
    s = SchedulerState(MemoryBackend(), "t", config=cfg)
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    rng = np.random.default_rng(7)
    ages = {}
    for p in range(24):
        t = _pending("j", 1, p)
        t.running.executor_id = "e1"
        s.save_task_status(t)
        # back-date like the promotion re-stamp does: rewrite the watch
        # clock AND push the corrected entry (the superseded heap entry
        # reconciles/dedupes lazily)
        import heapq

        age = float(rng.choice([0.0, 0.2, 0.9, 1.1, 2.5, 7.0, 30.0]))
        owner, attempt, t0 = s._running_since[("j", 1, p)]
        s._running_since[("j", 1, p)] = (owner, attempt, t0 - age)
        heapq.heappush(s._running_heap, (t0 - age, ("j", 1, p)))
        ages[("j", 1, p)] = age
    now = time.monotonic()

    def linear_reference():
        out = [
            k for k, e in s._running_since.items()
            if now - e[2] >= s._spec_floor_s
        ]
        out.sort(key=lambda k: s._running_since[k][2])  # oldest first
        return out

    got = s._straggler_candidates(now)
    assert got == linear_reference(), (got, linear_reference())
    assert got, "the synthetic ages must produce candidates"
    # repeated calls are stable: floor-passing entries re-push on exit
    assert s._straggler_candidates(now) == got
    # resolving a task removes it from candidates (lazy heap invalidation)
    victim = got[0]
    done = _completed(*victim, attempt=0, executor="e1")
    s.save_task_status(done)
    rest = s._straggler_candidates(now)
    assert victim not in rest and rest == [k for k in got if k != victim]


def test_straggler_heap_early_exits_on_young_tasks():
    """An idle slot on a healthy cluster (every running task younger than
    the floor) must not sweep the watch map: the t0-ordered heap walk
    breaks at the first young entry and returns nothing."""
    cfg = _spec_config(**{"ballista.speculation.min_runtime_ms": "60000"})
    s = SchedulerState(MemoryBackend(), "t", config=cfg)
    _running_job(s)
    s.save_executor_metadata(_meta("e1"))
    for p in range(8):
        t = _pending("j", 1, p)
        t.running.executor_id = "e1"
        s.save_task_status(t)
    assert s._straggler_candidates(time.monotonic()) == []
    # the heap survives the walk intact for the next slot
    assert len(s._running_heap) == 8


# -- re-speculation (ISSUE 15 satellite, PR 11 residue) ----------------------


def _age_live_duplicate(s, seconds=5.0, key=("j", 1, 0)):
    ex, at, t0, v, r = s._speculative[key]
    s._speculative[key] = (ex, at, t0 - seconds, v, r)


def test_respeculation_supersedes_straggling_duplicate():
    """A duplicate that ITSELF straggles past the same cost-model threshold
    is superseded by a fresh duplicate on a third executor: the ledger now
    tracks attempt 2, the abandoned attempt 1 lands in the superseded set,
    and the launch count enforces ballista.speculation.max_attempts."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    s.save_executor_metadata(_meta("e3"))
    s.save_executor_metadata(_meta("e4"))
    assert s.maybe_speculate("e2") is not None
    # age the LIVE duplicate's launch clock so it reads as a straggler
    # against the warm ~1ms rate (the judgment is on its own clock)
    _age_live_duplicate(s)
    got = s.maybe_speculate("e3")
    assert got is not None
    dup, _plan = got
    assert dup.attempt == 2 and dup.speculative
    raw = s.kv.get(SPEC_KEY)
    a = pb.Assignment()
    a.ParseFromString(raw)
    assert a.executor_id == "e3" and a.attempt == 2
    assert s._spec_superseded[("j", 1, 0)] == {1}
    assert s._spec_launches[("j", 1, 0)] == 2
    stats = tracing.counters("speculation")
    assert stats.get("launched") == 2 and stats.get("relaunched") == 1
    # bounded: max_attempts=2 (default) — a third launch never happens,
    # however long the second duplicate straggles
    _age_live_duplicate(s)
    assert s.maybe_speculate("e4") is None


def test_respeculation_bounded_by_max_attempts_one():
    """ballista.speculation.max_attempts=1 restores launch-once exactly."""
    s = _straggling_state(
        config=_spec_config(**{"ballista.speculation.max_attempts": "1"})
    )
    s.save_executor_metadata(_meta("e3"))
    assert s.maybe_speculate("e2") is not None
    _age_live_duplicate(s)
    assert s.maybe_speculate("e3") is None


def test_respeculation_waits_for_the_duplicate_floor():
    """The duplicate is judged on ITS OWN clock: a fresh duplicate (under
    the floor) is never superseded even while the primary's elapsed time
    screams straggler."""
    s = _straggling_state(
        config=_spec_config(**{"ballista.speculation.min_runtime_ms": "60000"})
    )
    s.save_executor_metadata(_meta("e3"))
    # age the PRIMARY past the (huge) floor so the first launch fires
    owner, attempt, t0 = s._running_since[("j", 1, 0)]
    s._running_since[("j", 1, 0)] = (owner, attempt, t0 - 120.0)
    assert s.maybe_speculate("e2") is not None
    # the duplicate is brand new: primary still ancient, duplicate under
    # its own floor -> no re-speculation
    assert s.maybe_speculate("e3") is None


def test_superseded_failure_spares_task_and_live_duplicate():
    """An abandoned duplicate's failure touches nothing: no retry budget
    consumed, the primary stays running, and the LIVE successor duplicate
    stays ledgered."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    s.save_executor_metadata(_meta("e3"))
    assert s.maybe_speculate("e2") is not None
    _age_live_duplicate(s)
    assert s.maybe_speculate("e3") is not None
    failed = _pending("j", 1, 0, attempt=1)
    failed.speculative = True
    failed.failed.error = "boom"
    failed.failed.executor_id = "e2"
    assert s.accept_task_status(failed) is False
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "running" and cur.attempt == 0
    a = pb.Assignment()
    a.ParseFromString(s.kv.get(SPEC_KEY))
    assert a.executor_id == "e3" and a.attempt == 2
    stats = tracing.counters("speculation")
    assert stats.get("superseded_failed") == 1
    assert ("j", 1, 0) not in s._spec_superseded  # retired on sight


def test_superseded_completion_still_wins():
    """First completion wins, whoever crosses the line: the ABANDONED
    duplicate finishing first resolves the task, and the whole episode
    (ledger + superseded set) closes."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    s.save_executor_metadata(_meta("e3"))
    assert s.maybe_speculate("e2") is not None
    _age_live_duplicate(s)
    assert s.maybe_speculate("e3") is not None
    done = _completed("j", 1, 0, attempt=1, executor="e2", speculative=True)
    assert s.accept_task_status(done) is True
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "completed"
    assert cur.completed.executor_id == "e2"
    assert s.kv.get(SPEC_KEY) is None
    assert ("j", 1, 0) not in s._spec_superseded
    assert ("j", 1, 0) not in s._spec_launches
    stats = tracing.counters("speculation")
    assert stats.get("superseded_won") == 1
    # review regression: the abandoned duplicate's rescue is a speculative
    # WIN in the effectiveness counters, never a "primary won" loss
    assert stats.get("won") == 1, stats
    assert stats.get("lost", 0) == 0, stats


def test_requeue_numbers_past_every_minted_speculative_attempt():
    """A requeue after re-speculation numbers PAST the highest minted
    duplicate attempt (ledgered AND superseded), so no late report from an
    abandoned attempt can impersonate the fresh one."""
    s = _straggling_state()
    s.save_executor_metadata(_meta("e3"))
    assert s.maybe_speculate("e2") is not None
    _age_live_duplicate(s)
    assert s.maybe_speculate("e3") is not None  # ledger at attempt 2
    t = s.get_task_status("j", 1, 0)
    assert s.requeue_task(t, "e1", "upstream lost", limit=5, promote=False)
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") is None and cur.attempt == 3


def test_primary_failure_promotes_the_respeculated_duplicate():
    """Primary dies while the RE-speculated duplicate runs: the promotion
    path adopts it (attempt 2, on its executor) exactly like a first-round
    duplicate — no retry budget consumed."""
    tracing.counters("speculation", reset=True)
    s = _straggling_state()
    s.save_executor_metadata(_meta("e3"))
    assert s.maybe_speculate("e2") is not None
    _age_live_duplicate(s)
    assert s.maybe_speculate("e3") is not None
    t = s.get_task_status("j", 1, 0)
    assert s.requeue_task(t, "e1", "primary lost", limit=3)
    cur = s.get_task_status("j", 1, 0)
    assert cur.WhichOneof("status") == "running"
    assert cur.attempt == 2 and cur.running.executor_id == "e3"
    assert tracing.counters("speculation").get("promoted") == 1
    # promoted into the ASSIGNMENT ledger; speculation record retired
    assert s.kv.get(SPEC_KEY) is None
    assert s.kv.get("/ballista/t/assignments/j/1/0") is not None


def test_restart_recovers_respeculated_duplicate(tmp_path):
    """A scheduler restart mid-re-speculation restores the ledgered
    attempt-2 duplicate (primary still running attempt 0) and rebuilds the
    launch bound from attempt arithmetic, so the restarted scheduler never
    launches past max_attempts either."""
    kv = SqliteBackend(str(tmp_path / "led.db"))
    s = _straggling_state(kv=kv)
    s.save_executor_metadata(_meta("e3"))
    s.save_executor_metadata(_meta("e4"))
    assert s.maybe_speculate("e2") is not None
    _age_live_duplicate(s)
    assert s.maybe_speculate("e3") is not None
    s2 = SchedulerState(kv, "t", config=_spec_config())
    stats = s2.recover()
    assert stats.get("restart_speculation_restored") == 1, stats
    assert s2._speculative[("j", 1, 0)][0] == "e3"
    assert s2._speculative[("j", 1, 0)][1] == 2
    assert s2._spec_launches[("j", 1, 0)] == 2
    # at the bound: the restarted scheduler refuses a third launch. It has
    # no watch entry until statuses flow — seed one (aged, warm rate) so
    # the monitor WOULD fire if the launch bound did not hold.
    import heapq
    import time as _time

    _age_live_duplicate(s2)
    costmodel.seed(s2._task_run_op("j", 1), 1.0, 0.001, engine="task")
    s2._running_since[("j", 1, 0)] = ("e1", 0, _time.monotonic() - 5.0)
    heapq.heappush(
        s2._running_heap, (s2._running_since[("j", 1, 0)][2], ("j", 1, 0))
    )
    assert s2.maybe_speculate("e4") is None


def test_respeculation_rescues_double_straggler_end_to_end():
    """ISSUE 15 satellite acceptance (cluster-level): a seed where BOTH the
    primary (attempt 0) and the first duplicate (attempt 1) draw slow
    `task.slow` verdicts, while attempt 2 draws fast — the re-speculated
    second duplicate rescues the tail: the job finishes well inside the
    injected delay, a relaunch is counted, and the result is bit-identical
    to the fault-free run. Needs 3 executors: the re-speculation never
    lands on the primary's or the live duplicate's executor."""
    import numpy as np

    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    rng = np.random.default_rng(1103)
    n = 4000
    table = pa.table({
        "g": pa.array(rng.integers(0, 23, n), type=pa.int64()),
        "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
    })
    sql = "select g, sum(v) as s, count(*) as n from t group by g order by g"
    base_client = {
        "ballista.shuffle.partitions": "2",
        "ballista.cache.results": "false",
        "ballista.tpu.cost_model_dir": "",
    }
    costmodel.reset()
    cluster = StandaloneCluster(
        n_executors=3,
        config=BallistaConfig({
            "ballista.tpu.cost_model_dir": "",
            "ballista.speculation.min_runtime_ms": "150",
            "ballista.speculation.multiplier": "3",
            "ballista.speculation.max_attempts": "2",
        }),
    )
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=base_client)
        ctx.register_record_batches("t", table, n_partitions=6)
        clean = ctx.sql(sql).collect()
        ctx.close()
        st = cluster.scheduler_impl.state
        coords = []
        for k, _v in st.kv.get_prefix(st._key("tasks")):
            tail = k.rsplit("/", 3)
            coords.append((int(tail[2]), int(tail[3])))
        by_stage = {}
        for c in coords:
            by_stage.setdefault(c[0], []).append(c)
        # seed injecting EXACTLY one straggler coordinate whose attempts 0
        # AND 1 are both slow and attempt 2 is fast, in a stage with
        # enough fast siblings to warm the prediction
        RATE = 0.12
        seed = None
        for cand in range(4000):
            inj = ChaosInjector(cand, RATE, sites=("task.slow",))
            slow = [
                c for c in coords
                if inj.should_inject("task.slow", f"{c[0]}/{c[1]}@a0")
            ]
            if (
                len(slow) == 1
                and len(by_stage[slow[0][0]]) >= costmodel.MIN_OBSERVATIONS + 1
                and inj.should_inject(
                    "task.slow", f"{slow[0][0]}/{slow[0][1]}@a1"
                )
                and not inj.should_inject(
                    "task.slow", f"{slow[0][0]}/{slow[0][1]}@a2"
                )
            ):
                seed = cand
                break
        assert seed is not None, "no qualifying chaos seed in range"
        tracing.counters("speculation", reset=True)
        ctx2 = BallistaContext(
            *cluster.scheduler_addr,
            settings={
                **base_client,
                "ballista.chaos.rate": str(RATE),
                "ballista.chaos.seed": str(seed),
                "ballista.chaos.sites": "task.slow",
                "ballista.chaos.slow_ms": "8000",
            },
        )
        ctx2.register_record_batches("t", table, n_partitions=6)
        t0 = time.perf_counter()
        chaotic = ctx2.sql(sql).collect()
        dt = time.perf_counter() - t0
        ctx2.close()
        assert chaotic.equals(clean), (
            chaotic.to_pydict(), clean.to_pydict(),
        )
        stats = tracing.counters("speculation", reset=True)
        assert stats.get("launched", 0) >= 2, stats
        assert stats.get("relaunched", 0) >= 1, stats
        assert stats.get("won", 0) >= 1, stats
        # the rescue: both slow attempts carried an 8s injected delay; the
        # re-speculated attempt finishes far inside it
        assert dt < 7.0, f"re-speculation did not rescue the tail: {dt:.2f}s"
    finally:
        cluster.shutdown()
        costmodel.reset()
