"""Randomized crash-recovery property test (ISSUE 18 satellite).

A seeded workload (jobs, tenants, assignments, completions, a speculative
mint, a result-cache publish) drives a SchedulerState; the process is
"killed" at a seeded accepted-status point by abandoning the instance,
and a FRESH SchedulerState recovers over the same store. Every attribute
the durability analyzer classifies `derived(<rebuild-fn>)` in
dev/analysis/durability.toml must rebuild EQUAL to the never-crashed
control's incrementally-maintained copy — the runtime half of the static
recover()-reachability check. The comparator table is asserted to cover
exactly the manifest's derived set, so classifying a new attribute
derived without extending this test fails loudly."""

import pathlib
import random

import pyarrow as pa
import pytest

import tomllib as _toml

from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.scheduler.kv import MemoryBackend
from ballista_tpu.scheduler.state import SchedulerState

REPO = pathlib.Path(__file__).resolve().parent.parent
MANIFEST = REPO / "dev" / "analysis" / "durability.toml"

SEEDS = range(6)


# -- seeded workload ---------------------------------------------------------

def _running_job(s, job):
    running = pb.JobStatus()
    running.running.SetInParent()
    s.save_job_metadata(job, running)


def _pending(job, stage, part):
    t = pb.TaskStatus()
    t.partition_id.job_id = job
    t.partition_id.stage_id = stage
    t.partition_id.partition_id = part
    return t


def _stage_plan(s, job, stage=1):
    from ballista_tpu.physical.basic import EmptyExec

    s.save_stage_plan(job, stage, EmptyExec(True, pa.schema([("a", pa.int64())])))


def _drive(s, seed):
    """Apply the seeded operation sequence up to its crash point (a seeded
    accepted-status count); returns the job ids. Deterministic given the
    seed — the control and nothing else defines the expected state."""
    rng = random.Random(seed)
    jobs = [f"j{i}" for i in range(rng.randint(2, 3))]
    for i, job in enumerate(jobs):
        _running_job(s, job)
        s.save_job_tenant(job, f"tenant{i % 2}", rng.randint(0, 3))
        _stage_plan(s, job)
        for p in range(3):
            s.save_task_status(_pending(job, 1, p))
    for e in ("e1", "e2"):
        s.save_executor_metadata(pb.ExecutorMetadata(id=e, host="h", port=1))
    running = []
    accepted = 0
    crash_at = rng.randint(2, 5)  # the seeded accepted-status crash point
    minted_spec = cached = False
    for _ in range(200):
        if accepted >= crash_at:
            break
        roll = rng.random()
        if roll < 0.5 or not running:
            ex = rng.choice(("e1", "e2"))
            got = s.assign_next_schedulable_task(ex)
            if got is None:
                if not running:
                    break
                continue
            status, _meta = got
            pid = status.partition_id
            key = (pid.job_id, pid.stage_id, pid.partition_id)
            running.append((key, ex, status.attempt))
        elif roll < 0.8:
            key, ex, attempt = running.pop(rng.randrange(len(running)))
            done = pb.TaskStatus()
            done.partition_id.job_id = key[0]
            done.partition_id.stage_id = key[1]
            done.partition_id.partition_id = key[2]
            done.attempt = attempt
            done.completed.executor_id = ex
            done.completed.path = f"/out/{key[0]}/{key[1]}/{key[2]}"
            if s.accept_task_status(done):
                accepted += 1
        elif not minted_spec:
            # mint a speculative duplicate the way maybe_speculate does:
            # launch accounting + the durable spec-ledger write-through
            key, ex, attempt = rng.choice(running)
            other = "e2" if ex == "e1" else "e1"
            s._spec_launches[key] = s._spec_launches.get(key, 0) + 1
            s._spec_put(key, other, attempt + 1)
            minted_spec = True
        elif not cached:
            done_job = pb.JobStatus()
            done_job.completed.SetInParent()
            s.result_cache_put(f"fp{rng.randrange(10)}", done_job.completed)
            cached = True
    return jobs


# -- comparators: one per analyzer-classified derived attribute --------------

def _index_view(idx):
    return {
        "pending": idx.pending,
        "incomplete": idx.incomplete,
        "total": idx.total,
        "running": idx.running,
    }


COMPARATORS = {
    "_task_index": lambda ctl, rec, jobs: (
        _index_view(ctl._ensure_task_index()) == _index_view(rec._task_index)
    ),
    # a timestamp can't equal across processes; rebuilt means re-seeded
    "_task_index_seeded_at": lambda ctl, rec, jobs: (
        rec._task_index_seeded_at > 0
    ),
    "_tenant_cache": lambda ctl, rec, jobs: all(
        rec._tenant_cache.get(j) == ctl._job_tenant_full(j) for j in jobs
    ),
    "_rc_count": lambda ctl, rec, jobs: (
        rec._rc_count == ctl._ensure_rc_count()
    ),
    "_spec_launches": lambda ctl, rec, jobs: (
        rec._spec_launches == ctl._spec_launches
    ),
    # ISSUE 20 generation-stamped read-throughs: recovery must leave the
    # replica tracking the SAME durable epoch the control sees, so the
    # next peer mutation (an epoch bump) re-derives the cached view
    "_plan_epoch_seen": lambda ctl, rec, jobs: (
        ctl._ensure_task_index() is not None
        and rec._plan_epoch_seen == ctl._plan_epoch_seen
    ),
    "_rc_epoch_seen": lambda ctl, rec, jobs: (
        ctl._ensure_rc_count() is not None
        and rec._rc_epoch_seen == ctl._rc_epoch_seen
    ),
}


def _manifest_derived():
    with open(MANIFEST, "rb") as f:
        man = _toml.load(f)
    return {
        key.rsplit(".", 1)[1]
        for key, row in man.get("attrs", {}).items()
        if key.startswith("scheduler.state.SchedulerState.")
        and row.startswith("derived(")
    }


def test_comparators_cover_every_derived_attr():
    """The comparator table and the manifest's derived classification must
    stay in lockstep: a new derived attribute needs a runtime rebuild
    check here, a dropped one needs its comparator retired."""
    assert set(COMPARATORS) == _manifest_derived()


@pytest.mark.parametrize("seed", SEEDS)
def test_derived_state_rebuilds_equal_to_never_crashed_control(seed):
    kv = MemoryBackend()
    control = SchedulerState(kv, "t")
    jobs = _drive(control, seed)
    # crash: the control instance is abandoned mid-flight; a fresh replica
    # recovers from the same store
    replica = SchedulerState(kv, "t")
    stats = replica.recover()
    assert stats.get("scheduler_restart") == 1, stats
    failed = [
        name for name in sorted(COMPARATORS)
        if not COMPARATORS[name](control, replica, jobs)
    ]
    assert failed == [], (
        f"derived attribute(s) did not rebuild to the control state: {failed}"
    )
