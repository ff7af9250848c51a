"""Disaggregated shuffle tier + elastic executor fleet (ISSUE 15).

The invariant under test everywhere: with ballista.shuffle.tier=shared a
piece's home is a PATH, not a process — executor death after map completion
(and graceful scale-in at any time) completes the job with ZERO lineage
recomputes and ZERO task retries, bit-identical to the local tier and to a
fixed fleet. Torn storage writes (shuffle.store chaos) degrade to the
normal retry/lineage ladder, never to a wrong answer; the autoscaler grows
the fleet against the cost-model-predicted backlog and drains it back when
idle.
"""

import os
import time

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.utils import tracing

GROUP_SQL = (
    "select region, sum(amount) as s from sales group by region order by region"
)


@pytest.fixture
def shared_dir(tmp_path):
    d = tmp_path / "shuffle-store"
    d.mkdir()
    return str(d)


def _shared_settings(shared_dir, **over):
    base = {
        "ballista.shuffle.partitions": "4",
        "ballista.cache.results": "false",
        "ballista.shuffle.tier": "shared",
        "ballista.shuffle.dir": shared_dir,
    }
    base.update(over)
    return base


def _local_settings(**over):
    base = {
        "ballista.shuffle.partitions": "4",
        "ballista.cache.results": "false",
    }
    base.update(over)
    return base


# -- config -------------------------------------------------------------------

def test_shared_tier_requires_dir():
    cfg = BallistaConfig({"ballista.shuffle.tier": "shared"})
    with pytest.raises(ValueError, match="ballista.shuffle.dir"):
        cfg.shuffle_storage_root()
    assert BallistaConfig().shuffle_storage_root() == ""
    with pytest.raises(ValueError, match="unknown shuffle tier"):
        BallistaConfig({"ballista.shuffle.tier": "s3"}).shuffle_tier()


# -- writer: shared publish layout + atomic torn-write ------------------------

def _writer(job="jx", stage=2, partitions=2):
    from ballista_tpu.datasource import MemoryTableSource
    from ballista_tpu.distributed.stages import ShuffleWriterExec
    from ballista_tpu.physical.expr import ColumnExpr
    from ballista_tpu.physical.plan import Partitioning
    from ballista_tpu.physical.scan import MemoryScanExec

    t = pa.table({
        "g": pa.array([1, 2, 3, 4, 1, 2], type=pa.int64()),
        "v": pa.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]),
    })
    scan = MemoryScanExec(MemoryTableSource.from_table(t))
    part = Partitioning.hash([ColumnExpr("g", 0)], partitions)
    return ShuffleWriterExec(job, stage, scan, part)


def test_shared_publish_layout_and_counters(shared_dir, tmp_path):
    from ballista_tpu.physical.plan import TaskContext

    w = _writer()
    ctx = TaskContext(
        config=BallistaConfig(_shared_settings(shared_dir)),
        work_dir=str(tmp_path / "work"),
        job_id="jx",
    )
    tracing.counters("shuffle_tier", reset=True)
    stats = w.execute_shuffle_write(0, ctx)
    assert stats.num_rows == 6
    base = os.path.join(shared_dir, "jx", "2", "0")
    pieces = sorted(os.listdir(base))
    assert pieces == ["0.arrow", "1.arrow"], pieces
    # nothing under the work dir, no tmp residue in storage
    assert not os.path.exists(os.path.join(str(tmp_path / "work"), "jx"))
    assert not [p for p in pieces if ".tmp-" in p]
    st = tracing.counters("shuffle_tier", reset=True)
    assert st.get("storage_publish") == 1, st


def test_shuffle_store_write_chaos_tears_publish_atomically(shared_dir, tmp_path):
    """A shuffle.store WRITE verdict fires after the temp pieces closed and
    before any replace: the task attempt fails with NOTHING published (no
    piece, no tmp residue) — degrading to the normal retry ladder."""
    from ballista_tpu.physical.plan import TaskContext
    from ballista_tpu.utils.chaos import ChaosInjected

    w = _writer()
    ctx = TaskContext(
        config=BallistaConfig(_shared_settings(
            shared_dir,
            **{
                "ballista.chaos.rate": "1.0",
                "ballista.chaos.seed": "1",
                "ballista.chaos.sites": "shuffle.store",
            },
        )),
        work_dir=str(tmp_path / "work"),
        job_id="jx",
    )
    tracing.counters("shuffle_tier", reset=True)
    with pytest.raises(ChaosInjected):
        w.execute_shuffle_write(0, ctx)
    base = os.path.join(shared_dir, "jx", "2", "0")
    published = os.listdir(base) if os.path.isdir(base) else []
    assert published == [], published
    st = tracing.counters("shuffle_tier", reset=True)
    assert st.get("storage_publish_torn") == 1, st


# -- reader: storage-first ladder --------------------------------------------

def _reader_for(base, schema, host="", port=0):
    from ballista_tpu.distributed.stages import (
        ShuffleLocation,
        ShuffleReaderExec,
    )

    loc = ShuffleLocation(
        "dead-exec", host, port, base,
        stage_id=2, map_partition=0, storage_uri=base,
    )
    return ShuffleReaderExec([loc], schema, 2)


def test_reader_resolves_storage_first_without_any_peer(shared_dir, tmp_path):
    """A storage-homed piece reads straight from the mount: no fetcher, no
    live producer, no work-dir copy — executor death changed nothing."""
    from ballista_tpu.physical.plan import TaskContext

    w = _writer()
    wctx = TaskContext(
        config=BallistaConfig(_shared_settings(shared_dir)),
        work_dir=str(tmp_path / "work"), job_id="jx",
    )
    w.execute_shuffle_write(0, wctx)
    base = os.path.join(shared_dir, "jx", "2", "0")
    reader = _reader_for(base, w.schema())
    rctx = TaskContext(
        config=BallistaConfig(_shared_settings(shared_dir)),
        work_dir=str(tmp_path / "work2"), job_id="jy",
        shuffle_fetcher=None,
    )
    tracing.counters("shuffle_tier", reset=True)
    rows = sum(b.num_rows for b in reader.execute(0, rctx))
    rows += sum(b.num_rows for b in reader.execute(1, rctx))
    assert rows == 6
    st = tracing.counters("shuffle_tier", reset=True)
    assert st.get("storage_fetch") == 2, st
    assert "storage_fallback_peer" not in st, st


def test_reader_missing_storage_piece_degrades_to_lineage(shared_dir, tmp_path):
    """A storage-homed piece that is NOT in storage (torn away, GC'd) and
    has no live peer surfaces as ShuffleFetchError naming the producing map
    task — the fetch_failed -> lineage-recompute ladder."""
    from ballista_tpu.errors import ShuffleFetchError
    from ballista_tpu.physical.plan import TaskContext

    base = os.path.join(shared_dir, "jx", "2", "0")  # never written
    schema = pa.schema([("g", pa.int64())])
    reader = _reader_for(base, schema)
    rctx = TaskContext(
        config=BallistaConfig(_shared_settings(shared_dir)),
        work_dir=str(tmp_path / "work"), job_id="jy",
    )
    tracing.counters("shuffle_tier", reset=True)
    with pytest.raises(ShuffleFetchError) as ei:
        list(reader.execute(0, rctx))
    assert ei.value.stage_id == 2 and ei.value.map_partition == 0
    st = tracing.counters("shuffle_tier", reset=True)
    assert st.get("storage_fallback_peer") == 1, st


def test_reader_read_chaos_falls_back_then_recovers_lineage(shared_dir, tmp_path):
    """A shuffle.store READ verdict makes a published piece unreadable for
    this attempt: with no peer the reader names the lost map task
    (lineage); a RETRIED attempt (fresh chaos key) reads it fine."""
    from ballista_tpu.errors import ShuffleFetchError
    from ballista_tpu.physical.plan import TaskContext
    from ballista_tpu.utils.chaos import ChaosInjector

    w = _writer()
    wctx = TaskContext(
        config=BallistaConfig(_shared_settings(shared_dir)),
        work_dir=str(tmp_path / "work"), job_id="jx",
    )
    w.execute_shuffle_write(0, wctx)
    base = os.path.join(shared_dir, "jx", "2", "0")
    # seed where attempt 0's read verdict is torn and attempt 1's is not
    seed = None
    for cand in range(500):
        inj = ChaosInjector(cand, 0.5, sites=("shuffle.store",))
        if inj.should_inject(
            "shuffle.store", "r2/0/piece0@a0"
        ) and not inj.should_inject("shuffle.store", "r2/0/piece0@a1"):
            seed = cand
            break
    assert seed is not None
    reader = _reader_for(base, w.schema())
    chaos_settings = _shared_settings(
        shared_dir,
        **{
            "ballista.chaos.rate": "0.5",
            "ballista.chaos.seed": str(seed),
            "ballista.chaos.sites": "shuffle.store",
        },
    )
    from ballista_tpu.physical.plan import TaskContext as TC

    rctx0 = TC(config=BallistaConfig(chaos_settings),
               work_dir=str(tmp_path / "w0"), job_id="jy", attempt=0)
    with pytest.raises(ShuffleFetchError):
        list(reader.execute(0, rctx0))
    rctx1 = TC(config=BallistaConfig(chaos_settings),
               work_dir=str(tmp_path / "w1"), job_id="jy", attempt=1)
    rows = sum(b.num_rows for b in reader.execute(0, rctx1))
    assert rows > 0


# -- scheduler: storage-homed outputs survive their executor ------------------

def _state(config=None):
    from ballista_tpu.scheduler.kv import MemoryBackend
    from ballista_tpu.scheduler.state import SchedulerState

    return SchedulerState(
        MemoryBackend(), "elastic",
        config=config or BallistaConfig({"ballista.tpu.cost_model_dir": ""}),
    )


def _completed_task(job, stage, part, executor, storage_uri=""):
    t = pb.TaskStatus()
    t.partition_id.job_id = job
    t.partition_id.stage_id = stage
    t.partition_id.partition_id = part
    t.completed.executor_id = executor
    t.completed.path = f"/x/{job}/{stage}/{part}"
    if storage_uri:
        t.completed.storage_uri = storage_uri
    return t


def test_reset_lost_tasks_keeps_storage_homed_outputs():
    """The tentpole's core scheduler rule: a COMPLETED task whose output is
    storage-homed survives its executor's death — no requeue, no retry
    budget consumed, no downstream invalidation. The work-dir sibling on
    the same dead executor still requeues (the local-tier contract)."""
    s = _state()
    running = pb.JobStatus()
    running.running.SetInParent()
    s.save_job_metadata("j", running)
    s.save_task_status(_completed_task("j", 1, 0, "dead", storage_uri="/s/j/1/0"))
    s.save_task_status(_completed_task("j", 1, 1, "dead"))
    tracing.counters("recovery", reset=True)
    reset = s.reset_lost_tasks()  # nobody holds a lease: "dead" is dead
    assert reset == 1, reset
    stats = tracing.counters("recovery", reset=True)
    assert stats.get("storage_home_retained") == 1, stats
    assert stats.get("task_retry", 0) == 1, stats
    kept = s.get_task_status("j", 1, 0)
    assert kept.WhichOneof("status") == "completed"
    requeued = s.get_task_status("j", 1, 1)
    assert requeued.WhichOneof("status") is None and requeued.attempt == 1


def test_bound_plan_carries_storage_uri():
    """Locations bound into downstream stage plans carry the path-home, so
    the executing reader resolves storage-first even when the producer's
    metadata is long gone."""
    s = _state()
    from ballista_tpu.distributed.stages import (
        ShuffleReaderExec,
        UnresolvedShuffleExec,
    )

    schema = pa.schema([("g", pa.int64())])
    s.save_stage_plan("j", 2, UnresolvedShuffleExec(1, schema, 2))
    s.save_task_status(_completed_task("j", 1, 0, "gone", storage_uri="/s/j/1/0"))
    idx = s._ensure_task_index()
    bound = s._bound_stage_plan("j", 2, idx)
    assert isinstance(bound, ShuffleReaderExec)
    assert bound.locations[0].storage_uri == "/s/j/1/0"
    assert bound.locations[0].host == ""  # producer gone; storage is home


def test_result_cache_liveness_skips_storage_homed_locations():
    """A cached entry whose partitions are storage-homed stays servable
    after the producing executor retires (the dead-lease invalidation only
    guards work-dir locations)."""
    s = _state()
    completed = pb.CompletedJob()
    pl = completed.partition_location.add()
    pl.executor_meta.id = "retired"
    pl.path = "/s/j/9/0"
    pl.storage_uri = "/s/j/9/0"
    assert s.result_cache_put("fp-storage", completed)
    hit = s.result_cache_lookup("fp-storage")
    assert hit is not None and hit.partition_location[0].storage_uri
    # contrast: a work-dir entry from a dead executor invalidates
    completed2 = pb.CompletedJob()
    pl2 = completed2.partition_location.add()
    pl2.executor_meta.id = "retired"
    pl2.path = "/w/j/9/0"
    assert s.result_cache_put("fp-workdir", completed2)
    assert s.result_cache_lookup("fp-workdir") is None


def test_predicted_backlog_seconds_scales_with_pending():
    """The autoscaling signal: warm task.run rates multiply into the
    pending count; never-observed stages contribute the small cold prior;
    terminal jobs contribute nothing."""
    from ballista_tpu.scheduler.state import BACKLOG_COLD_TASK_SECONDS

    s = _state()
    running = pb.JobStatus()
    running.running.SetInParent()
    s.save_job_metadata("j", running)
    from ballista_tpu.physical.basic import EmptyExec

    schema = pa.schema([("g", pa.int64())])
    s.save_stage_plan("j", 1, EmptyExec(False, schema))
    for p in range(4):
        t = pb.TaskStatus()
        t.partition_id.job_id = "j"
        t.partition_id.stage_id = 1
        t.partition_id.partition_id = p
        s.save_task_status(t)
    cold = s.predicted_backlog_seconds()
    assert cold == pytest.approx(4 * BACKLOG_COLD_TASK_SECONDS)
    # warm the rate: 200ms per task of this stage shape
    for _ in range(8):
        s._observe_task_run("j", 1, 0.2)
    warm = s.predicted_backlog_seconds()
    assert warm == pytest.approx(4 * 0.2, rel=0.2)
    # a failed job's leftover pending tasks stop counting
    failed = pb.JobStatus()
    failed.failed.error = "x"
    s.save_job_metadata("j", failed)
    assert s.predicted_backlog_seconds() == 0.0


# -- e2e: executor death after map completion is a non-event ------------------

def _run_job_kill_owner_prefetch(sales_table, settings):
    """Submit the 2-stage group-by, wait for COMPLETION, then kill an
    executor holding result partitions (and map outputs) — totally
    (heartbeat AND data plane) — BEFORE anything is fetched. Returns
    (result table, recovery stats). On the local tier this is the
    ReportLostPartition-restart scenario; on the shared tier the fetch
    reads storage and nothing restarts."""
    import ballista_tpu.scheduler.state as state_mod
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    cluster = StandaloneCluster(n_executors=2)
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    cluster.scheduler_impl.lost_task_check_interval = 0.3
    tracing.counters("recovery", reset=True)
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        plan = ctx.sql(GROUP_SQL).logical_plan()
        job_id = ctx.submit(plan)
        status = ctx._wait_for_job(job_id, timeout=60.0)
        owners = {
            pl.executor_meta.id
            for pl in status.completed.partition_location
        }
        victim = next(ex for ex in cluster.executors if ex.id in owners)
        victim.stop()
        out = ctx._collect_results(job_id, plan.schema(), timeout=120.0)
        stats = tracing.counters("recovery", reset=True)
        ctx.close()
        return out, stats
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
        cluster.shutdown()


def test_executor_death_after_completion_is_a_nonevent_on_shared_tier(
    sales_table, shared_dir
):
    """ISSUE 15 acceptance: the SAME kill-the-result-owner-before-fetch
    harness that forces a ReportLostPartition restart on the local tier
    (nonzero restarts + task retries, pinned below) completes on the
    shared tier with ZERO recovery events of any kind — the dead
    executor's pieces kept their storage home and the client read them
    from the mount — and results are bit-identical across the tiers."""
    tracing.counters("shuffle_tier", reset=True)
    shared_out, shared_stats = _run_job_kill_owner_prefetch(
        sales_table, _shared_settings(shared_dir)
    )
    tier = tracing.counters("shuffle_tier", reset=True)
    local_out, local_stats = _run_job_kill_owner_prefetch(
        sales_table, _local_settings()
    )
    assert shared_out.equals(local_out), (
        shared_out.to_pydict(), local_out.to_pydict(),
    )
    assert shared_out.column("s").to_pylist() == [120.0, 40.0, 145.0]
    # shared tier: the non-event — no restart, no retry, no lineage
    for event in (
        "task_retry", "map_recomputed", "fetch_failed", "lost_task_reset",
        "downstream_invalidated", "result_partition_restarted",
        "completed_job_restarted", "result_fetch_restarted",
    ):
        assert shared_stats.get(event, 0) == 0, (event, shared_stats)
    assert tier.get("storage_publish", 0) >= 1, tier
    assert tier.get("client_storage_fetch", 0) >= 1, tier
    # local tier, same harness: the loss IS an event (fetch-time restart
    # through lineage, consuming retries)
    assert local_stats.get("result_partition_restarted", 0) > 0, local_stats
    assert local_stats.get("task_retry", 0) > 0, local_stats


def test_executor_death_mid_job_shared_tier_zero_lineage_recompute(
    sales_table, shared_dir
):
    """Executor killed right after its MAP stage completed, while reduces
    run: on the shared tier the surviving reduces read the dead executor's
    map pieces straight from storage — ZERO lineage recomputes (no
    fetch_failed, no map recompute, no downstream invalidation) and the
    completed map outputs are retained (storage_home_retained), with only
    the victim's genuinely in-flight reduces retrying (no tier can save
    running work). The local-tier contrast — nonzero lineage events on
    this exact harness — is pinned by test_fault_tolerance's
    test_end_to_end_recovery_after_executor_death_with_lost_outputs."""
    import ballista_tpu.scheduler.state as state_mod
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    cluster = StandaloneCluster(n_executors=2)
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    cluster.scheduler_impl.lost_task_check_interval = 0.3
    tracing.counters("recovery", reset=True)
    tracing.counters("shuffle_tier", reset=True)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr, settings=_shared_settings(shared_dir)
        )
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        plan = ctx.sql(GROUP_SQL).logical_plan()
        job_id = ctx.submit(plan)
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
        assert all(t.completed.storage_uri for t in stage1), (
            "map outputs not storage-homed"
        )
        owners = {t.completed.executor_id for t in stage1}
        victim = next(ex for ex in cluster.executors if ex.id in owners)
        victim.stop()
        out = ctx._collect_results(job_id, plan.schema(), timeout=120.0)
        assert out.column("s").to_pylist() == [120.0, 40.0, 145.0]
        stats = tracing.counters("recovery", reset=True)
        tier = tracing.counters("shuffle_tier", reset=True)
        # ZERO lineage recomputation: the map outputs never needed it
        assert stats.get("fetch_failed", 0) == 0, stats
        assert stats.get("map_recomputed", 0) == 0, stats
        assert stats.get("downstream_invalidated", 0) == 0, stats
        assert stats.get("storage_home_retained", 0) >= 1, stats
        assert tier.get("storage_fetch", 0) >= 1, tier
        ctx.close()
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
        cluster.shutdown()


# -- e2e: graceful scale-in during a running job ------------------------------

def test_scale_in_during_running_job_bit_identical_zero_retries(
    sales_table, shared_dir
):
    """ISSUE 15 acceptance: gracefully retiring an executor MID-JOB on the
    shared tier (the autoscaler's drain -> stop -> remove mechanism,
    chaos-armed on fleet.scale) is invisible to the job: results are
    bit-identical to a fixed-fleet run and the recovery counters show zero
    task retries — the retiree finished its in-flight work and its
    completed outputs stayed readable from storage."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    settings = _shared_settings(shared_dir)
    # fixed-fleet reference
    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        ref = ctx.sql(GROUP_SQL).collect()
        ctx.close()
    finally:
        cluster.shutdown()

    # elastic run: retire one executor the moment the job is mid-flight.
    # fleet.scale chaos is ARMED (autoscaler evaluations can be torn);
    # the explicit scale_in_one drives the same drain machinery
    # deterministically while the job runs.
    tracing.counters("fleet", reset=True)
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(
        n_executors=2,
        config=BallistaConfig({
            "ballista.fleet.min": "1",
            "ballista.fleet.max": "2",
            "ballista.fleet.interval_s": "0.1",
            "ballista.chaos.rate": "0.3",
            "ballista.chaos.seed": "7",
            "ballista.chaos.sites": "fleet.scale",
        }),
    )
    try:
        shared_dir2 = os.path.join(shared_dir, "scalein")
        os.makedirs(shared_dir2, exist_ok=True)
        settings2 = _shared_settings(shared_dir2)
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings2)
        ctx.register_record_batches("sales", sales_table, n_partitions=4)
        plan = ctx.sql(GROUP_SQL).logical_plan()
        job_id = ctx.submit(plan)
        # wait until the job is actually running (some task started), then
        # scale in while it is in flight
        state = cluster.scheduler_impl.state
        deadline = time.time() + 60
        while time.time() < deadline:
            tasks = state.get_job_tasks(job_id)
            if tasks and any(
                t.WhichOneof("status") in ("running", "completed")
                for t in tasks
            ):
                break
            time.sleep(0.01)
        assert cluster.scale_in_one(timeout=60.0), "scale-in declined"
        status = ctx._wait_for_job(job_id, timeout=120.0)
        tables = [
            ctx._fetch_partition(loc)
            for loc in status.completed.partition_location
        ]
        out = pa.concat_tables(tables).cast(plan.schema())
        ctx.close()
    finally:
        cluster.shutdown()
    assert out.equals(ref), (out.to_pydict(), ref.to_pydict())
    stats = tracing.counters("recovery", reset=True)
    assert stats.get("task_retry", 0) == 0, stats
    assert stats.get("orphan_reassigned", 0) == 0, stats
    fl = tracing.counters("fleet", reset=True)
    assert fl.get("scale_down", 0) >= 1, fl
    assert fl.get("drain_completed", 0) >= 1, fl
    assert cluster.fleet_size() == 1


# -- e2e: autoscaler grows under backlog, drains when idle --------------------

def test_autoscaler_grows_under_backlog_and_drains_idle(shared_dir):
    """The closed loop: a burst of concurrent jobs registers as predicted
    backlog, the fleet grows toward ballista.fleet.max, every job
    completes, and the idle fleet drains back to ballista.fleet.min with
    clean drains (zero retries)."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    rng = np.random.default_rng(5)
    n = 20_000
    table = pa.table({
        "g": pa.array(rng.integers(0, 9, n), type=pa.int64()),
        "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
    })
    sql = "select g, sum(v) as s, count(*) as c from t group by g order by g"
    tracing.counters("fleet", reset=True)
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(
        n_executors=1,
        config=BallistaConfig({
            "ballista.fleet.min": "1",
            "ballista.fleet.max": "3",
            "ballista.fleet.interval_s": "0.1",
            "ballista.fleet.target_backlog_s": "0.05",
        }),
    )
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings=_shared_settings(shared_dir, **{
                "ballista.shuffle.partitions": "8",
            }),
        )
        ctx.register_record_batches("t", table, n_partitions=8)
        ref = ctx.sql(sql).collect()
        jobs = [ctx.submit(ctx.sql(sql).logical_plan()) for _ in range(4)]
        peak = cluster.fleet_size()
        deadline = time.time() + 60
        statuses = []
        while time.time() < deadline:
            peak = max(peak, cluster.fleet_size())
            statuses = [
                ctx._client.get_job_status(
                    pb.GetJobStatusParams(job_id=j)
                ).status
                for j in jobs
            ]
            if all(
                s.WhichOneof("status") in ("completed", "failed")
                for s in statuses
            ):
                break
            time.sleep(0.05)
        assert all(
            s.WhichOneof("status") == "completed" for s in statuses
        ), [s.WhichOneof("status") for s in statuses]
        for j in jobs:
            got = ctx._collect_results(j, ref.schema)
            assert got.equals(ref), j
        # idle: the fleet must drain back to min via graceful drains
        deadline = time.time() + 30
        while time.time() < deadline and cluster.fleet_size() > 1:
            time.sleep(0.1)
        assert cluster.fleet_size() == 1
        ctx.close()
    finally:
        cluster.shutdown()
    fl = tracing.counters("fleet", reset=True)
    assert fl.get("scale_up", 0) >= 1, fl
    assert fl.get("scale_down", 0) >= 1, fl
    assert fl.get("drain_completed", 0) >= fl.get("scale_down", 0), fl
    assert peak > 1, f"fleet never grew (peak {peak})"
    stats = tracing.counters("recovery", reset=True)
    assert stats.get("task_retry", 0) == 0, stats


def test_fleet_scale_chaos_skips_decisions():
    """A fleet.scale verdict tears the scale decision BEFORE any executor
    is touched: the fleet keeps its size that evaluation and the skip is
    counted, never silent."""
    from ballista_tpu.executor.runtime import StandaloneCluster
    from ballista_tpu.utils.chaos import ChaosInjector

    # seed whose FIRST decision verdict is torn (sequence-keyed)
    seed = next(
        s for s in range(200)
        if ChaosInjector(s, 1.0, sites=("fleet.scale",)).should_inject(
            "fleet.scale", "scale1"
        )
    )
    tracing.counters("fleet", reset=True)
    cluster = StandaloneCluster(
        n_executors=2,
        config=BallistaConfig({
            "ballista.fleet.min": "1",
            "ballista.fleet.max": "2",
            # interval long enough that only explicit evaluations run
            "ballista.fleet.interval_s": "3600",
            "ballista.chaos.rate": "1.0",
            "ballista.chaos.seed": str(seed),
            "ballista.chaos.sites": "fleet.scale",
        }),
    )
    try:
        # idle 2-executor cluster above min: the decision is scale-in,
        # torn by chaos -> no action
        assert cluster.autoscale_once() == 0
        assert cluster.fleet_size() == 2
        fl = tracing.counters("fleet", reset=True)
        assert fl.get("scale_chaos_skipped") == 1, fl
        assert fl.get("scale_down", 0) == 0, fl
    finally:
        cluster.shutdown()


# -- security + GC regressions (review findings) ------------------------------

def test_flight_execute_partition_ignores_peer_shuffle_settings(
    sales_table, tmp_path
):
    """Review regression: an unauthenticated Flight peer's per-request
    settings must NOT steer the shuffle WRITE home — the tier/dir come
    from the EXECUTOR's own config (like the scan-root allowlist), so
    ExecutePartition cannot publish .arrow files to an arbitrary host
    path. The hostile settings are simply overridden: the write lands in
    the work dir and the attacker-named directory stays untouched."""
    import socket
    import threading

    from ballista_tpu.client.flight import BallistaClient
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.executor.flight_service import BallistaFlightService

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    work = tmp_path / "work"
    work.mkdir()
    svc = BallistaFlightService(
        f"grpc://0.0.0.0:{port}", str(work), BallistaConfig()
    )
    threading.Thread(target=svc.serve, daemon=True).start()
    try:
        ctx = ExecutionContext()
        ctx.register_record_batches("sales", sales_table, n_partitions=1)
        from ballista_tpu.logical import col, functions as F

        df = ctx.table("sales").aggregate([], [F.sum(col("amount")).alias("s")])
        physical = ctx.create_physical_plan(df.logical_plan())
        evil = str(tmp_path / "exfil")
        client = BallistaClient("127.0.0.1", port)
        results = client.execute_partition(
            "jobsec", 1, [0], physical,
            settings={
                "ballista.shuffle.tier": "shared",
                "ballista.shuffle.dir": evil,
            },
        )
        client.close()
        path, stats = results[0]
        assert stats.num_rows == 1
        assert path.startswith(str(work)), path
        assert not os.path.exists(evil), "peer settings steered the write"
    finally:
        svc.shutdown()


def test_gc_sweeps_shared_storage_root(tmp_path):
    """Review regression: the shuffle TTL sweep covers the executor's
    configured shared storage root beside its work dir — without it the
    shared mount grows without bound (no other component owns the
    pieces)."""
    from ballista_tpu.executor.execution_loop import PollLoop
    from ballista_tpu.scheduler.rpc import SchedulerGrpcClient

    work = tmp_path / "work"
    storage = tmp_path / "storage"
    for root in (work, storage):
        (root / "oldjob" / "1" / "0").mkdir(parents=True)
        (root / "oldjob" / "1" / "0" / "0.arrow").write_bytes(b"x")
    old = time.time() - 7200
    for root in (work, storage):
        os.utime(root / "oldjob", (old, old))
    loop = PollLoop(
        SchedulerGrpcClient("127.0.0.1", 1),
        pb.ExecutorMetadata(id="gc", host="h", port=1),
        str(work),
        config=BallistaConfig(_shared_settings(str(storage))),
    )
    loop.shuffle_ttl_seconds = 3600.0
    removed = loop.gc_work_dir()
    assert removed == 2, removed
    assert not (work / "oldjob").exists()
    assert not (storage / "oldjob").exists()


def test_executor_pinned_tier_ignores_per_job_redirection(sales_table, tmp_path):
    """Review regression (scheduler-dispatch path): an executor whose OWN
    config pins a shuffle tier keeps it — per-job client settings cannot
    redirect the os.replace publish to a client-chosen host path (the
    data_roots discipline applied to writes). An UNCONFIGURED executor
    still honors the per-job opt-in (every other test in this file)."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    pinned = tmp_path / "pinned-store"
    pinned.mkdir()
    evil = tmp_path / "exfil"
    cluster = StandaloneCluster(
        n_executors=1,
        config=BallistaConfig({
            "ballista.shuffle.tier": "shared",
            "ballista.shuffle.dir": str(pinned),
        }),
    )
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={
                "ballista.shuffle.partitions": "2",
                "ballista.cache.results": "false",
                # hostile per-job redirection: must be ignored by the
                # pinned executor (reads still resolve via the PINNED root
                # the scheduler's storage_uri records point into)
                "ballista.shuffle.tier": "shared",
                "ballista.shuffle.dir": str(evil),
            },
        )
        ctx.register_record_batches("sales", sales_table, n_partitions=2)
        out = ctx.sql(GROUP_SQL).collect()
        assert out.column("s").to_pylist() == [120.0, 40.0, 145.0]
        assert not evil.exists(), "per-job settings steered the publish"
        assert os.listdir(pinned), "pinned storage root never used"
        ctx.close()
    finally:
        cluster.shutdown()
