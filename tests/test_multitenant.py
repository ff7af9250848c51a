"""Multi-tenant serving (ISSUE 7): admission control, the plan-fingerprint
result cache, and cross-job artifact sharing.

Three layers, mirroring the subsystem's spread:

- fingerprint units (scheduler/fingerprint.py): the "fully file-backed
  identity" rule applied to whole queries — mtime invalidation by key
  construction, tenant-setting exclusion, unkeyable plans refuse;
- SchedulerState units: durable tenant records, weighted fair-share
  candidate ordering, per-tenant in-flight quotas (the starvation bound),
  result-cache put/lookup/invalidate incl. the chaos-armed put;
- end-to-end standalone-cluster runs: a repeated query served from the
  cache with ZERO executor tasks (counter-asserted), mtime invalidation,
  cache+tenancy surviving a scheduler restart, lost cached partitions
  resubmitting transparently, and seeded chaos on cache.put /
  scheduler.admit staying bit-identical to fault-free.
"""

import logging
import os
import threading
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.client import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.executor.runtime import StandaloneCluster
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.scheduler.fingerprint import plan_fingerprint
from ballista_tpu.scheduler.kv import MemoryBackend, SqliteBackend
from ballista_tpu.scheduler.state import SchedulerState
from ballista_tpu.utils import tracing

logging.getLogger("ballista.executor").setLevel(logging.CRITICAL)


# ---------------------------------------------------------------------------
# fingerprint units
# ---------------------------------------------------------------------------


def _file_plan(path):
    from ballista_tpu.engine import ExecutionContext

    ctx = ExecutionContext()
    ctx.register_parquet("t", path)
    return ctx.sql("select k, sum(v) as s from t group by k order by k"), ctx


@pytest.fixture()
def parquet_file(tmp_path):
    p = str(tmp_path / "t.parquet")
    pq.write_table(
        pa.table({"k": [1, 2, 1, 3], "v": [1.0, 2.0, 3.0, 4.0]}), p
    )
    return p


def test_fingerprint_stable_and_mtime_keyed(parquet_file):
    df, _ = _file_plan(parquet_file)
    plan = df.logical_plan()
    fp1 = plan_fingerprint(plan, {})
    fp2 = plan_fingerprint(plan, {})
    assert fp1 is not None and fp1 == fp2
    # touching the input changes the RESULT key but not the CONTENT key
    # (planning depends on the file list, results on the file bytes)
    os.utime(parquet_file, (time.time() + 5, time.time() + 5))
    fp3 = plan_fingerprint(plan, {})
    assert fp3 is not None
    assert fp3[0] == fp1[0] and fp3[1] != fp1[1]


def test_fingerprint_settings_participate_tenant_excluded(parquet_file):
    df, _ = _file_plan(parquet_file)
    plan = df.logical_plan()
    base = plan_fingerprint(plan, {})
    # result-affecting settings change both keys...
    other = plan_fingerprint(plan, {"ballista.executor.backend": "tpu"})
    assert other is not None and other[0] != base[0] and other[1] != base[1]
    # ...tenancy settings change neither (tenants SHARE cache lines)
    tenanted = plan_fingerprint(
        plan, {"ballista.tenant.name": "alice", "ballista.tenant.priority": "7"}
    )
    assert tenanted == base


def test_fingerprint_memory_tables_content_keyed():
    from ballista_tpu.engine import ExecutionContext

    ctx = ExecutionContext()
    ctx.register_record_batches("m", pa.table({"x": [1, 2, 3]}))
    p1 = plan_fingerprint(ctx.sql("select sum(x) as s from m").logical_plan(), {})
    ctx2 = ExecutionContext()
    ctx2.register_record_batches("m", pa.table({"x": [1, 2, 3]}))
    p2 = plan_fingerprint(ctx2.sql("select sum(x) as s from m").logical_plan(), {})
    assert p1 is not None and p1 == p2  # same content, same identity
    ctx3 = ExecutionContext()
    ctx3.register_record_batches("m", pa.table({"x": [1, 2, 4]}))
    p3 = plan_fingerprint(ctx3.sql("select sum(x) as s from m").logical_plan(), {})
    assert p3 is not None and p3 != p1  # different content, different key


def test_fingerprint_volatile_function_unkeyable(parquet_file):
    """now() makes results depend on WHEN the query runs: such plans must
    never cache (a cached now() comparison would be frozen at the first
    run's clock forever)."""
    from ballista_tpu.engine import ExecutionContext

    ctx = ExecutionContext()
    ctx.register_parquet("t", parquet_file)
    volatile = ctx.sql(
        "select count(*) as n from t where now() > to_timestamp('2000-01-01')"
    ).logical_plan()
    assert plan_fingerprint(volatile, {}) is None
    stable = ctx.sql("select count(*) as n from t").logical_plan()
    assert plan_fingerprint(stable, {}) is not None


def test_fingerprint_missing_file_unkeyable(parquet_file):
    df, _ = _file_plan(parquet_file)
    plan = df.logical_plan()
    assert plan_fingerprint(plan, {}) is not None
    os.unlink(parquet_file)
    assert plan_fingerprint(plan, {}) is None


# ---------------------------------------------------------------------------
# SchedulerState units: tenancy + admission
# ---------------------------------------------------------------------------


def _meta(i, host="h", port=50051):
    return pb.ExecutorMetadata(id=i, host=host, port=port)


def _pending(job, stage, part):
    t = pb.TaskStatus()
    t.partition_id.job_id = job
    t.partition_id.stage_id = stage
    t.partition_id.partition_id = part
    return t


def _running(job, stage, part, executor="e1"):
    t = _pending(job, stage, part)
    t.running.executor_id = executor
    return t


def _scan_stage(n_parts=2):
    """A real single-stage plan so assignment can bind it."""
    from ballista_tpu.distributed.planner import DistributedPlanner
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.logical import col, functions as F

    ctx = ExecutionContext()
    ctx.register_record_batches(
        "t", pa.table({"g": ["a", "b"], "v": [1.0, 2.0]}), n_partitions=n_parts
    )
    df = ctx.table("t").select(col("g"))
    physical = ctx.create_physical_plan(df.logical_plan())
    stages = DistributedPlanner().plan_query_stages("job", physical)
    return stages[0]


def _seed_job(s, job, tenant, priority=0, n_parts=2, stage=None):
    stage = stage if stage is not None else _scan_stage(n_parts)
    s.save_job_tenant(job, tenant, priority)
    s.save_stage_plan(job, stage.stage_id, stage)
    for p in range(n_parts):
        s.save_task_status(_pending(job, stage.stage_id, p))
    return stage


def test_job_tenant_roundtrip_and_restart_durability():
    kv = MemoryBackend()
    s = SchedulerState(kv, "t")
    s.save_job_tenant("j1", "alice", 3)
    assert s.job_tenant("j1") == ("alice", 3)
    assert s.job_tenant("unknown") == ("", 0)
    # a FRESH state over the same store (scheduler restart) reloads it
    s2 = SchedulerState(kv, "t")
    assert s2.job_tenant("j1") == ("alice", 3)


def test_quota_blocks_saturating_tenant():
    """The starvation bound: tenant A at its in-flight quota is skipped and
    tenant B's task is handed out, even though A's job sorts first."""
    kv = MemoryBackend()
    s = SchedulerState(
        kv, "t", config=BallistaConfig({"ballista.tenant.max_inflight": "2"})
    )
    s.save_executor_metadata(_meta("e1"))
    stage = _scan_stage(4)
    _seed_job(s, "aaaa", "hog", n_parts=4, stage=stage)
    # hog saturates its quota (2 in flight) while alone on the cluster
    a1 = s.assign_next_schedulable_task("e1")
    a2 = s.assign_next_schedulable_task("e1")
    assert a1[0].partition_id.job_id == "aaaa"
    assert a2[0].partition_id.job_id == "aaaa"
    # the light tenant arrives: its task is handed out, hog's remaining
    # pending tasks stay queued behind the quota
    _seed_job(s, "zzzz", "light", n_parts=1, stage=_scan_stage(1))
    a3 = s.assign_next_schedulable_task("e1")
    assert a3 is not None and a3[0].partition_id.job_id == "zzzz"
    # light is done; hog stays blocked until its in-flight drains
    assert s.assign_next_schedulable_task("e1") is None
    done = pb.TaskStatus()
    done.CopyFrom(a1[0])
    done.completed.executor_id = "e1"
    done.completed.path = "/x"
    assert s.accept_task_status(done)
    a4 = s.assign_next_schedulable_task("e1")
    assert a4 is not None and a4[0].partition_id.job_id == "aaaa"
    assert tracing.counters("tenancy", reset=True).get("admit_quota_deferred", 0) >= 1


def test_fair_share_prefers_light_tenant():
    """With no quota, the tenant with the smallest in_flight/weight ratio
    is visited first — a busy tenant yields the next slot."""
    kv = MemoryBackend()
    s = SchedulerState(kv, "t")
    s.save_executor_metadata(_meta("e1"))
    _seed_job(s, "aaaa", "busy", n_parts=3, stage=_scan_stage(3))
    _seed_job(s, "zzzz", "idle", n_parts=1, stage=_scan_stage(1))
    a1 = s.assign_next_schedulable_task("e1")
    assert a1[0].partition_id.job_id == "aaaa"  # both idle: name order ties
    # busy now has 1 in flight; idle has 0 -> idle's task goes next even
    # though its job id sorts last
    a2 = s.assign_next_schedulable_task("e1")
    assert a2 is not None and a2[0].partition_id.job_id == "zzzz"
    shares = s.tenant_task_shares()
    assert shares == {"busy": 1, "idle": 1}


def test_weighted_fair_share_ratio():
    """weights alice:4,bob:1 — alice keeps priority until her in-flight is
    4x bob's."""
    kv = MemoryBackend()
    s = SchedulerState(
        kv, "t",
        config=BallistaConfig({"ballista.tenant.weights": "alice:4,bob:1"}),
    )
    s.save_executor_metadata(_meta("e1"))
    _seed_job(s, "aj", "alice", n_parts=6, stage=_scan_stage(6))
    _seed_job(s, "bj", "bob", n_parts=6, stage=_scan_stage(6))
    got = []
    for _ in range(5):
        a = s.assign_next_schedulable_task("e1")
        got.append(s.job_tenant(a[0].partition_id.job_id)[0])
    # 0/4 vs 0/1 ties -> alice (name order); then 1/4 < 0/1 -> ... bob only
    # once alice holds 4x bob's share: a,a,a,a interleaved with bob's first
    assert got.count("alice") == 4 and got.count("bob") == 1, got


def test_priority_orders_jobs_within_tenant():
    kv = MemoryBackend()
    s = SchedulerState(kv, "t")
    s.save_executor_metadata(_meta("e1"))
    _seed_job(s, "aaaa", "alice", priority=0, n_parts=1, stage=_scan_stage(1))
    _seed_job(s, "zzzz", "alice", priority=9, n_parts=1, stage=_scan_stage(1))
    a = s.assign_next_schedulable_task("e1")
    assert a[0].partition_id.job_id == "zzzz"  # high priority first


def test_admission_order_unchanged_without_tenancy():
    """Default config + untenanted jobs reduce to the historical
    (job, str(stage)) candidate order — the PR 2 identity contract."""
    kv = MemoryBackend()
    s = SchedulerState(kv, "t")
    s.save_executor_metadata(_meta("e1"))
    st1 = _scan_stage(1)
    for job in ("jb", "ja", "jc"):
        s.save_stage_plan(job, st1.stage_id, st1)
        s.save_task_status(_pending(job, st1.stage_id, 0))
    picked = [
        s.assign_next_schedulable_task("e1")[0].partition_id.job_id
        for _ in range(3)
    ]
    assert picked == ["ja", "jb", "jc"]


# ---------------------------------------------------------------------------
# SchedulerState units: result cache
# ---------------------------------------------------------------------------


def _completed_job(executor="e1", path="/data/p0"):
    c = pb.CompletedJob()
    pl = c.partition_location.add()
    pl.partition_id.job_id = "j"
    pl.partition_id.stage_id = 1
    pl.executor_meta.CopyFrom(_meta(executor))
    pl.path = path
    return c


def test_result_cache_roundtrip_and_liveness():
    kv = MemoryBackend()
    s = SchedulerState(kv, "t")
    s.save_executor_metadata(_meta("e1"))
    tracing.counters("tenancy", reset=True)
    assert s.result_cache_put("f" * 64, _completed_job())
    hit = s.result_cache_lookup("f" * 64)
    assert hit is not None and hit.cached
    assert hit.partition_location[0].path == "/data/p0"
    # entry referencing an executor with no live lease: invalidated on
    # lookup, entry deleted
    assert s.result_cache_put("a" * 64, _completed_job(executor="gone"))
    assert s.result_cache_lookup("a" * 64) is None
    assert kv.get(s._key("resultcache", "a" * 64)) is None
    stats = tracing.counters("tenancy", reset=True)
    assert stats.get("cache_hit") == 1
    assert stats.get("cache_invalidated") == 1
    assert stats.get("cache_put") == 2


def test_result_cache_put_chaos_torn():
    """rate=1.0 on cache.put: every publish is torn, recorded, and SKIPPED
    — the completion stands, later lookups just miss."""
    kv = MemoryBackend()
    s = SchedulerState(
        kv, "t",
        config=BallistaConfig({
            "ballista.chaos.rate": "1.0",
            "ballista.chaos.sites": "cache.put",
        }),
    )
    s.save_executor_metadata(_meta("e1"))
    tracing.counters("tenancy", reset=True)
    assert not s.result_cache_put("b" * 64, _completed_job())
    assert s.result_cache_lookup("b" * 64) is None
    stats = tracing.counters("tenancy", reset=True)
    assert stats.get("cache_put_torn") == 1
    assert not stats.get("cache_put")


# ---------------------------------------------------------------------------
# end-to-end: standalone cluster
# ---------------------------------------------------------------------------


@pytest.fixture()
def tpath(tmp_path):
    p = str(tmp_path / "t.parquet")
    pq.write_table(
        pa.table(
            {
                "k": pa.array([i % 7 for i in range(500)], type=pa.int64()),
                "v": pa.array([float(i) for i in range(500)]),
            }
        ),
        p,
    )
    return p


def _jobs_of(state):
    out = {}
    for k, _v in state.kv.get_prefix(state._key("jobs")):
        job = k.rsplit("/", 1)[1]
        out[job] = state.get_job_metadata(job)
    return out


def test_cache_hit_zero_tasks_and_mtime_invalidation(tpath):
    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.tenant.name": "dash"},
        )
        ctx.register_parquet("t", tpath)
        tracing.counters("tenancy", reset=True)
        q = "select k, sum(v) as s from t group by k order by k"
        cold = ctx.sql(q).collect()
        warm = ctx.sql(q).collect()
        assert warm.equals(cold)  # bit-identical to cold execution
        st = cluster.scheduler_impl.state
        cached_jobs = [
            j for j, js in _jobs_of(st).items()
            if js.WhichOneof("status") == "completed" and js.completed.cached
        ]
        assert len(cached_jobs) == 1
        # the acceptance counter: a cache-hit job runs ZERO executor tasks
        assert st.get_job_tasks(cached_jobs[0]) == []
        stats = tracing.counters("tenancy", reset=True)
        assert stats.get("cache_hit") == 1 and stats.get("cache_put") == 1
        # touching an input file's mtime invalidates: fresh execution,
        # fresh entry, same bits
        os.utime(tpath, (time.time() + 5, time.time() + 5))
        fresh = ctx.sql(q).collect()
        assert fresh.equals(cold)
        stats = tracing.counters("tenancy", reset=True)
        assert stats.get("cache_hit", 0) == 0 and stats.get("cache_put") == 1
        ctx.close()
    finally:
        cluster.shutdown()


def test_cache_and_tenancy_survive_scheduler_restart(tpath):
    """The cache entry, the tenant record, and admission all live in the KV
    — a restarted scheduler on the same store keeps serving hits."""
    kv = SqliteBackend.temporary()
    cluster = StandaloneCluster(n_executors=1, kv=kv)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr, settings={"ballista.tenant.name": "dash"}
        )
        ctx.register_parquet("t", tpath)
        q = "select k, count(*) as n from t group by k order by k"
        cold = ctx.sql(q).collect()
        cluster.restart_scheduler()
        tracing.counters("tenancy", reset=True)
        warm = ctx.sql(q).collect()
        assert warm.equals(cold)
        assert tracing.counters("tenancy", reset=True).get("cache_hit") == 1
        st = cluster.scheduler_impl.state
        cached = [
            j for j, js in _jobs_of(st).items()
            if js.WhichOneof("status") == "completed" and js.completed.cached
        ]
        assert cached and all(st.job_tenant(j)[0] == "dash" for j in cached)
        ctx.close()
    finally:
        cluster.shutdown()


@pytest.mark.parametrize("how", ["collect", "collect_stream"])
def test_lost_cached_partition_invalidates_and_resubmits(tpath, how):
    """Cached locations outliving their data (executor died under a live
    lease): the fetch fails, ReportLostPartition invalidates the entry and
    fails the cached job, and collect() resubmits transparently — the
    query still returns the right rows. The streaming collect gives the
    same guarantee while it has yielded nothing."""
    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(*cluster.scheduler_addr)
        ctx.register_parquet("t", tpath)
        q = "select k, sum(v) as s from t group by k order by k"
        cold = ctx.sql(q).collect()
        # kill the result-holding executors' data planes without waiting
        # out the 60s lease (the lazy liveness check must NOT catch this)
        st = cluster.scheduler_impl.state
        completed = [
            js for js in _jobs_of(st).values()
            if js.WhichOneof("status") == "completed"
        ]
        owners = {
            pl.executor_meta.id
            for js in completed
            for pl in js.completed.partition_location
        }
        for ex in cluster.executors:
            if ex.id in owners:
                ex.poll_loop.stop()
                ex.flight.shutdown()
        assert len(owners) < len(cluster.executors), (
            "need a surviving executor to re-execute on"
        )
        tracing.counters("tenancy", reset=True)
        if how == "collect":
            again = ctx.sql(q).collect()
        else:
            plan = ctx.sql(q).logical_plan()
            again = pa.Table.from_batches(
                list(ctx.collect_stream(plan)), schema=cold.schema)
        assert again.equals(cold)
        stats = tracing.counters("tenancy", reset=True)
        assert stats.get("cache_hit") == 1  # served stale, then...
        assert stats.get("cache_invalidated", 0) >= 1  # ...invalidated
        assert stats.get("cache_lost_resubmitted") == 1  # ...and resubmitted
        ctx.close()
    finally:
        cluster.shutdown()


def test_starvation_quota_end_to_end(tpath):
    """A saturating tenant cannot block another tenant's job past its
    quota: both jobs complete, and the light tenant's tasks were assigned
    while the hog still had pending work (its share stays bounded)."""
    cluster = StandaloneCluster(
        n_executors=1,
        config=BallistaConfig({"ballista.tenant.max_inflight": "1"}),
        concurrent_tasks=1,
    )
    try:
        hog = BallistaContext(
            *cluster.scheduler_addr,
            settings={
                "ballista.tenant.name": "hog",
                "ballista.shuffle.partitions": "8",
                # distinct per-tenant settings also prove cache isolation
                # is NOT needed for correctness here: different settings,
                # different fingerprints
            },
        )
        light = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.tenant.name": "light"},
        )
        for c in (hog, light):
            c.register_parquet("t", tpath)
        big = "select k, v, count(*) as n from t group by k, v order by k, v limit 5"
        small = "select count(*) as n from t"
        results = {}
        errors = []

        def run(name, c, sql):
            try:
                results[name] = c.sql(sql).collect()
            except Exception as e:  # surface in the main thread
                errors.append((name, e))

        th = threading.Thread(target=run, args=("hog", hog, big))
        tl = threading.Thread(target=run, args=("light", light, small))
        th.start()
        tl.start()
        th.join(120)
        tl.join(120)
        assert not errors, errors
        assert results["light"].column("n").to_pylist() == [500]
        assert results["hog"].num_rows == 5
        shares = cluster.scheduler_impl.state.tenant_task_shares()
        assert shares.get("hog", 0) >= 1 and shares.get("light", 0) >= 1
        hog.close()
        light.close()
    finally:
        cluster.shutdown()


def _admit_seed(rate=0.35):
    """A seed whose FIRST admission verdict injects (deterministic scan,
    like the chaos suite's seed picks)."""
    from ballista_tpu.utils.chaos import ChaosInjector

    for seed in range(200):
        inj = ChaosInjector(seed, rate, ["scheduler.admit"])
        if inj.should_inject("scheduler.admit", "admit1"):
            return seed
    raise AssertionError("no injecting seed in range")


def test_admit_chaos_bit_identical(tpath):
    """Seeded chaos on scheduler.admit: the faulted PollWork aborts before
    the Running flip, the executor retries, and the run stays bit-identical
    to fault-free."""
    q = "select k, sum(v) as s, count(*) as n from t group by k order by k"
    outs = {}
    for chaos in (False, True):
        cfg = None
        if chaos:
            cfg = BallistaConfig({
                "ballista.chaos.rate": "0.35",
                "ballista.chaos.seed": str(_admit_seed()),
                "ballista.chaos.sites": "scheduler.admit",
            })
        cluster = StandaloneCluster(n_executors=2, config=cfg)
        try:
            ctx = BallistaContext(*cluster.scheduler_addr)
            ctx.register_parquet("t", tpath)
            tracing.counters("recovery", reset=True)
            outs[chaos] = ctx.sql(q).collect()
            if chaos:
                assert tracing.counters("recovery", reset=True).get("chaos_injected", 0) > 0
            ctx.close()
        finally:
            cluster.shutdown()
    assert outs[True].equals(outs[False])


def test_cache_put_chaos_bit_identical(tpath):
    """rate=1.0 on cache.put cluster-wide: every publish torn, zero hits,
    every repeat re-executes — and the results stay bit-identical."""
    cluster = StandaloneCluster(
        n_executors=2,
        config=BallistaConfig({
            "ballista.chaos.rate": "1.0",
            "ballista.chaos.sites": "cache.put",
        }),
    )
    try:
        ctx = BallistaContext(*cluster.scheduler_addr)
        ctx.register_parquet("t", tpath)
        tracing.counters("tenancy", reset=True)
        q = "select k, sum(v) as s from t group by k order by k"
        a = ctx.sql(q).collect()
        b = ctx.sql(q).collect()
        assert a.equals(b)
        stats = tracing.counters("tenancy", reset=True)
        assert stats.get("cache_put_torn", 0) >= 2
        assert stats.get("cache_hit", 0) == 0
        ctx.close()
    finally:
        cluster.shutdown()


_CRASH_RATE = 0.05


def _crash_seed():
    """A seed that crashes the FIRST scheduler life early (g0, accepted
    status 1-4: while the first job's tasks are being admitted/executed)
    and lets the restarted life (g1) survive the whole run's status
    horizon — the deterministic-scan idiom from test_scheduler_restart."""
    from ballista_tpu.utils.chaos import ChaosInjector

    for seed in range(20000):
        inj = ChaosInjector(seed, _CRASH_RATE, ["scheduler.crash"])

        def fires_at(gen, horizon):
            for n in range(1, horizon):
                if inj.should_inject("scheduler.crash", f"g{gen}/status{n}"):
                    return n
            return None

        if fires_at(0, 5) is not None and fires_at(1, 120) is None:
            return seed
    raise AssertionError("no suitable crash seed in range")


def test_scheduler_crash_mid_admission_bit_identical(tmp_path):
    """ISSUE 7 acceptance: a seeded scheduler crash while a tenanted job is
    being admitted/executed, restarted on the same durable store, stays
    bit-identical to fault-free — and the repeated query afterwards is
    served from the (durable) result cache."""
    # a 2-file table: the scan gets 2 partitions, so the job is a real
    # 2-stage plan with enough task statuses for the seeded crash to land
    # mid-execution (a 1-partition scan collapses to a single task)
    tdir = tmp_path / "t"
    tdir.mkdir()
    for i in range(2):
        pq.write_table(
            pa.table({
                "k": pa.array([j % 7 for j in range(250)], type=pa.int64()),
                "v": pa.array([float(j + i * 250) for j in range(250)]),
            }),
            str(tdir / f"part{i}.parquet"),
        )
    tpath = str(tdir)
    q = "select k, sum(v) as s, count(*) as n from t group by k order by k"

    clean_cluster = StandaloneCluster(n_executors=2)
    try:
        cctx = BallistaContext(*clean_cluster.scheduler_addr)
        cctx.register_parquet("t", tpath)
        clean = cctx.sql(q).collect()
        cctx.close()
    finally:
        clean_cluster.shutdown()

    cluster = StandaloneCluster(
        n_executors=2,
        kv=SqliteBackend(str(tmp_path / "sched.db")),
        config=BallistaConfig({
            "ballista.chaos.rate": str(_CRASH_RATE),
            "ballista.chaos.seed": str(_crash_seed()),
            "ballista.chaos.sites": "scheduler.crash",
            "ballista.rpc.retries": "20",
            "ballista.rpc.backoff_ms": "50",
        }),
    )
    stop = threading.Event()

    def supervisor():
        while not stop.is_set():
            if cluster.scheduler_impl.crashed:
                cluster.restart_scheduler()
            time.sleep(0.02)

    sup = threading.Thread(target=supervisor, daemon=True)
    sup.start()
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={
                "ballista.tenant.name": "dash",
                "ballista.rpc.retries": "20",
            },
        )
        ctx.register_parquet("t", tpath)
        tracing.counters("recovery", reset=True)
        tracing.counters("tenancy", reset=True)
        first = ctx.sql(q).collect()
        second = ctx.sql(q).collect()
        ctx.close()
    finally:
        stop.set()
        sup.join(timeout=5)
        cluster.shutdown()
    assert first.equals(clean) and second.equals(clean)
    stats = tracing.counters("recovery", reset=True)
    assert stats.get("chaos_scheduler_crash", 0) >= 1, stats
    assert stats.get("scheduler_restart", 0) >= 1, stats
    # the repeat rode the durable cache entry written after the restart
    assert tracing.counters("tenancy", reset=True).get("cache_hit", 0) >= 1


def test_plan_cache_shares_physical_plans(tpath):
    """Cross-job artifact sharing: with the result cache off (forcing the
    second submission to really plan + execute), the second identical query
    reuses the first's physical plan — and the results agree."""
    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.results": "false"},
        )
        ctx.register_parquet("t", tpath)
        tracing.counters("tenancy", reset=True)
        q = "select k, max(v) as m from t group by k order by k"
        a = ctx.sql(q).collect()
        b = ctx.sql(q).collect()
        assert a.equals(b)
        stats = tracing.counters("tenancy", reset=True)
        assert stats.get("plan_cache_hit") == 1
        assert stats.get("cache_hit", 0) == 0
        ctx.close()
    finally:
        cluster.shutdown()


def test_cross_tenant_cache_sharing(tpath):
    """N tenants running the same dashboard query execute it once: the
    fingerprint excludes tenant identity, so tenant B hits tenant A's
    entry."""
    cluster = StandaloneCluster(n_executors=2)
    try:
        q = "select k, sum(v) as s from t group by k order by k"
        outs = []
        tracing.counters("tenancy", reset=True)
        for tenant in ("alice", "bob", "carol"):
            ctx = BallistaContext(
                *cluster.scheduler_addr,
                settings={"ballista.tenant.name": tenant},
            )
            ctx.register_parquet("t", tpath)
            outs.append(ctx.sql(q).collect())
            ctx.close()
        assert outs[0].equals(outs[1]) and outs[1].equals(outs[2])
        stats = tracing.counters("tenancy", reset=True)
        assert stats.get("cache_hit") == 2  # bob and carol rode alice's run
        assert stats.get("cache_put") == 1
    finally:
        cluster.shutdown()
