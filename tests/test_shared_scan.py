"""Shared-scan multi-query execution (ISSUE 13): one upload, one launch,
N queries.

The invariant under test everywhere: a batched execution is BIT-IDENTICAL
to solo execution for every member query — same backend, same to_pylist —
whatever the batch composition, the evidence gate's verdict, chaos at the
formation site, or a member's (or executor's) mid-batch death. Counters
prove the sharing actually happened (batches_formed / batched_stages /
uploads_saved / launches_saved), and every decline is visible, never
silent.

Determinism harness: clusters start with ZERO executors, the distinct
queries are submitted concurrently and PLAN while nothing can pull work,
then one executor starts — so every compatible stage task is co-pending at
first dispatch and batch formation is deterministic rather than a race.
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.client import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.executor.runtime import BallistaExecutor, StandaloneCluster
from ballista_tpu.ops import costmodel
from ballista_tpu.ops.runtime import routing_stats
from ballista_tpu.utils import tracing
from ballista_tpu.utils.chaos import ChaosInjector

QUERIES = [
    "select g, sum(v) as s, count(*) as c from t group by g order by g",
    "select g, min(q) as mn, max(q) as mx from t where v > 0 "
    "group by g order by g",
    "select g, sum(q) as sq from t where q < 30 group by g order by g",
]
# device column `s` is a STRING filter input: its stage grows a per-stage
# dictionary, so it must never join a shared upload (string GROUP keys —
# `g` above — stay host-side and batch fine)
STRING_FILTER_QUERY = (
    "select g, count(*) as c from t where s <> 'x1' group by g order by g"
)


@pytest.fixture(scope="module")
def table_path(tmp_path_factory):
    rng = np.random.default_rng(42)
    n = 40_000
    t = pa.table({
        "g": pa.array([f"k{v}" for v in rng.integers(0, 6, n)]),
        "s": pa.array([f"x{v}" for v in rng.integers(0, 4, n)]),
        "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
        "q": pa.array(rng.integers(1, 50, n), type=pa.int64()),
        "d": pa.array(
            rng.integers(8000, 12000, n), type=pa.int32()
        ).cast(pa.date32()),
    })
    path = str(tmp_path_factory.mktemp("sharedscan") / "t.parquet")
    pq.write_table(t, path)
    return path


def _client_settings(**over):
    base = {
        "ballista.executor.backend": "tpu",
        "ballista.cache.results": "false",
        "ballista.shuffle.partitions": "2",
        # the scan-per-query regime shared-scan exists for: with device
        # residency on, a warm member rightly degrades to its resident solo
        # run (pinned by test_resident_members_degrade_to_solo below) and
        # repeated suite queries would never batch
        "ballista.tpu.device_cache": "false",
    }
    base.update(over)
    return base


def _run_sequential(path, queries, client_settings=None, cluster_config=None):
    """Reference harness: one client, queries one at a time (nothing can
    co-pend, so nothing batches)."""
    cluster = StandaloneCluster(n_executors=1, config=cluster_config)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings=client_settings or _client_settings(),
        )
        ctx.register_parquet("t", path)
        out = [ctx.sql(q).collect().to_pydict() for q in queries]
        ctx.close()
        return out
    finally:
        cluster.shutdown()


def _run_concurrent(
    path, queries, client_settings=None, cluster_config=None,
    per_query_settings=None, plan_delay=1.5, executors=1,
    mid_flight=None, join_timeout=120,
):
    """Deterministic-batching harness: submit every query concurrently
    against a cluster with NO executors, wait for planning, then start the
    executor(s) — all compatible stage tasks are co-pending at first
    dispatch. `per_query_settings[i]` overlays query i's client settings;
    `mid_flight(cluster)` runs shortly after the executors start (executor
    -death injection)."""
    cluster = StandaloneCluster(n_executors=0, config=cluster_config)
    results = [None] * len(queries)
    errors = []
    try:
        def submit(i):
            try:
                settings = dict(client_settings or _client_settings())
                if per_query_settings and per_query_settings[i]:
                    settings.update(per_query_settings[i])
                c = BallistaContext(*cluster.scheduler_addr, settings=settings)
                c.register_parquet("t", path)
                results[i] = c.sql(queries[i]).collect().to_pydict()
                c.close()
            except Exception as e:  # surfaced by the caller's assert
                errors.append(f"q{i}: {e!r}")

        threads = [
            threading.Thread(target=submit, args=(i,))
            for i in range(len(queries))
        ]
        for th in threads:
            th.start()
        time.sleep(plan_delay)
        for i in range(executors):
            ex = BallistaExecutor(
                "127.0.0.1", cluster.port,
                config=cluster.config, executor_id=f"late-{i}",
            )
            ex.start()
            cluster.executors.append(ex)
        if mid_flight is not None:
            mid_flight(cluster)
        for th in threads:
            th.join(join_timeout)
        alive = [th for th in threads if th.is_alive()]
        assert not alive, f"clients hung: {len(alive)} (errors: {errors})"
    finally:
        cluster.shutdown()
    assert not errors, errors
    return results


# -- batched == solo bit-identity + the sharing counters --------------------

def test_batched_bit_identical_to_solo(table_path, monkeypatch):
    """Concurrent distinct queries batch into ONE shared-scan launch
    (SYNC_COMPILE pins the deterministic one-launch path) and every
    member's result is BIT-identical to its solo run on the same backend;
    the counters prove one upload and one launch served N stages."""
    from ballista_tpu.ops import sharedscan

    monkeypatch.setattr(sharedscan, "SYNC_COMPILE", True)
    solo = _run_sequential(table_path, QUERIES)
    tracing.counters("shared_scan", reset=True)
    routing_stats(reset=True)
    batched = _run_concurrent(table_path, QUERIES)
    stats = tracing.counters("shared_scan", reset=True)
    routing = routing_stats(reset=True)
    for q, got, want in zip(QUERIES, batched, solo):
        assert got == want, (q, got, want)
    assert stats.get("batches_formed", 0) >= 1, stats
    assert stats.get("batched_stages", 0) >= 2, stats
    assert stats.get("shared_groups", 0) >= 1, stats
    assert stats.get("uploads_saved", 0) >= 1, stats
    assert stats.get("launches_saved", 0) >= 1, stats
    # spliced members are visible routing decisions, not silent shortcuts
    assert routing["engines"].get("batch", 0) >= 2, routing


def test_cold_composition_falls_back_to_member_launches(table_path):
    """A composition whose combined program is not compiled yet must NOT
    stall the wave behind a multi-second trace: the members run their own
    jitted steps over the SHARED upload (uploads still saved, results
    bit-identical) while the one-launch program warms in the background."""
    solo = _run_sequential(table_path, QUERIES)
    tracing.counters("shared_scan", reset=True)
    batched = _run_concurrent(table_path, QUERIES)
    stats = tracing.counters("shared_scan", reset=True)
    for q, got, want in zip(QUERIES, batched, solo):
        assert got == want, (q, got, want)
    assert stats.get("batches_formed", 0) >= 1, stats
    assert stats.get("uploads_saved", 0) >= 1, stats
    # cold compositions took the per-member fallback (or finished warming
    # mid-run and switched — either way the wave never traced inline)
    assert (
        stats.get("warm_fallback_launches", 0) >= 1
        or stats.get("launches_saved", 0) >= 1
    ), stats


def test_shared_scan_off_forms_no_batches(table_path):
    cfg = BallistaConfig({"ballista.shared_scan": "false"})
    tracing.counters("shared_scan", reset=True)
    out = _run_concurrent(
        table_path, QUERIES,
        client_settings=_client_settings(**{"ballista.shared_scan": "false"}),
        cluster_config=cfg,
    )
    assert all(o is not None for o in out)
    assert tracing.counters("shared_scan", reset=True) == {}


def test_resident_members_degrade_to_solo(table_path):
    """With device residency ON and the member stages already warm, a
    batched dispatch degrades every member to its resident solo run —
    re-scanning what HBM already holds would undo the residency tier —
    and results stay bit-identical."""
    resident = _client_settings(**{"ballista.tpu.device_cache": "true"})
    solo = _run_sequential(table_path, QUERIES, client_settings=resident)
    tracing.counters("shared_scan", reset=True)
    out = _run_concurrent(table_path, QUERIES, client_settings=resident)
    stats = tracing.counters("shared_scan", reset=True)
    for q, got, want in zip(QUERIES, out, solo):
        assert got == want, (q, got, want)
    # the scheduler may form batches (it cannot see executor residency);
    # the executor's precompute hands every warm member back
    assert stats.get("shared_groups", 0) == 0, stats
    assert stats.get("uploads_saved", 0) == 0, stats


# -- evidence gate ----------------------------------------------------------

def test_evidence_gate_declines_predicted_slow_batches(table_path):
    """With warm solo task.run rates and a stage.batch rate that predicts
    the batch SLOWER than the members' solo sum, formation dispatches solo
    — recorded (batch_gate_solo + a routing decision), never silent — and
    results are unchanged. Re-seeding the batch rate fast re-enables
    batching: the gate is evidence, not a switch."""
    # warm the scheduler-observed task.run rates past MIN_OBSERVATIONS:
    # 4 sequential runs of each shape = 4 completions per stage-1 op (the
    # in-memory cost store is process-global and pinned to dir "")
    _run_sequential(table_path, QUERIES * 4)
    assert any(
        k.startswith("task.run|") for k in costmodel.snapshot()
    ), "warm pass recorded no task.run rates"
    for k in (2.0, 4.0, 8.0):
        costmodel.seed("stage.batch", k, 1e6, engine="task")
    solo = _run_sequential(table_path, QUERIES)
    tracing.counters("shared_scan", reset=True)
    gated = _run_concurrent(table_path, QUERIES)
    stats = tracing.counters("shared_scan", reset=True)
    for q, got, want in zip(QUERIES, gated, solo):
        assert got == want, (q, got, want)
    assert stats.get("batches_formed", 0) == 0, stats
    assert stats.get("batch_gate_solo", 0) >= 1, stats
    # favorable evidence: batching resumes
    for k in (2.0, 4.0, 8.0):
        costmodel.seed("stage.batch", k, 1e-6, engine="task")
    tracing.counters("shared_scan", reset=True)
    fast = _run_concurrent(table_path, QUERIES)
    stats = tracing.counters("shared_scan", reset=True)
    for q, got, want in zip(QUERIES, fast, solo):
        assert got == want, (q, got, want)
    assert stats.get("batches_formed", 0) >= 1, stats


# -- mixed compatible/incompatible groups -----------------------------------

def test_mixed_compatibility_batches_only_compatible_members(table_path):
    """A member whose stage reads a string device column cannot share the
    upload (per-stage dictionaries); it degrades to solo while the
    compatible members still batch — and everyone's result is exactly its
    solo result."""
    queries = QUERIES + [STRING_FILTER_QUERY]
    solo = _run_sequential(table_path, queries)
    tracing.counters("shared_scan", reset=True)
    batched = _run_concurrent(table_path, queries)
    stats = tracing.counters("shared_scan", reset=True)
    for q, got, want in zip(queries, batched, solo):
        assert got == want, (q, got, want)
    assert stats.get("batches_formed", 0) >= 1, stats
    assert stats.get("member_ineligible", 0) >= 1, stats


# -- scheduler.batch chaos --------------------------------------------------

def test_chaos_torn_batch_formation_degrades_to_solo(table_path):
    """scheduler.batch chaos at rate 1.0 tears EVERY formation before any
    sibling's Running flip: everything dispatches solo (nothing written,
    nothing torn) and results stay bit-identical."""
    solo = _run_sequential(table_path, QUERIES)
    chaos_cfg = BallistaConfig({
        "ballista.chaos.rate": "1.0",
        "ballista.chaos.seed": "7",
        "ballista.chaos.sites": "scheduler.batch",
    })
    tracing.counters("shared_scan", reset=True)
    tracing.counters("recovery", reset=True)
    out = _run_concurrent(table_path, QUERIES, cluster_config=chaos_cfg)
    stats = tracing.counters("shared_scan", reset=True)
    rec = tracing.counters("recovery", reset=True)
    for q, got, want in zip(QUERIES, out, solo):
        assert got == want, (q, got, want)
    assert stats.get("batches_formed", 0) == 0, stats
    assert stats.get("batch_chaos_solo", 0) >= 1, stats
    assert rec.get("chaos_injected", 0) >= 1, rec


# -- member failure isolation -----------------------------------------------

def test_member_failure_spares_batch_siblings(table_path):
    """One member's task.execute chaos (attempt 0 faulted, attempt 1 clean,
    armed via that job's OWN settings) fails the member alone: its retry
    completes and every batch sibling's result is bit-identical to solo."""
    # find a seed that faults exactly the batchable stage-1 task's first
    # attempt and nothing else the faulted job runs (stage 2 has
    # shuffle.partitions=2 tasks)
    seed = None
    for cand in range(500):
        inj = ChaosInjector(cand, 0.25, sites=("task.execute",))
        if (
            inj.should_inject("task.execute", "1/0@a0")
            and not inj.should_inject("task.execute", "1/0@a1")
            and not any(
                inj.should_inject("task.execute", f"2/{p}@a0")
                for p in range(2)
            )
        ):
            seed = cand
            break
    assert seed is not None
    solo = _run_sequential(table_path, QUERIES)
    per_query = [None] * len(QUERIES)
    per_query[1] = {
        "ballista.chaos.rate": "0.25",
        "ballista.chaos.seed": str(seed),
        "ballista.chaos.sites": "task.execute",
    }
    tracing.counters("shared_scan", reset=True)
    tracing.counters("recovery", reset=True)
    out = _run_concurrent(
        table_path, QUERIES, per_query_settings=per_query,
    )
    stats = tracing.counters("shared_scan", reset=True)
    rec = tracing.counters("recovery", reset=True)
    for q, got, want in zip(QUERIES, out, solo):
        assert got == want, (q, got, want)
    assert rec.get("task_retry", 0) >= 1, rec
    assert stats.get("batches_formed", 0) >= 1, stats


def test_executor_death_mid_batch_recovers_bit_identical(table_path):
    """The executor dies WHILE a shared-scan batch runs on it (one member
    slowed by task.slow keeps the batch in flight): every member's task
    requeues through the normal lease machinery onto the replacement
    executor and completes bit-identical to solo — a batched dispatch is N
    ordinary in-flight tasks to every recovery path."""
    import ballista_tpu.scheduler.state as state_mod

    solo = _run_sequential(table_path, QUERIES)
    per_query = [None] * len(QUERIES)
    per_query[0] = {
        # rate 1.0: EVERY attempt of this job's tasks sleeps, keeping the
        # batch mid-flight when the victim dies (retries sleep too — the
        # join timeout absorbs them)
        "ballista.chaos.rate": "1.0",
        "ballista.chaos.seed": "3",
        "ballista.chaos.sites": "task.slow",
        "ballista.chaos.slow_ms": "2500",
    }

    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0

    def kill_victim(cluster):
        cluster.scheduler_impl.lost_task_check_interval = 0.5
        time.sleep(0.8)  # the batch is dispatched and sleeping in a member
        victim = cluster.executors[0]
        victim.poll_loop.stop()
        victim.flight.shutdown()
        time.sleep(1.5)  # lease expiry
        ex = BallistaExecutor(
            "127.0.0.1", cluster.port,
            config=cluster.config, executor_id="survivor",
        )
        ex.start()
        cluster.executors.append(ex)

    tracing.counters("shared_scan", reset=True)
    tracing.counters("recovery", reset=True)
    try:
        out = _run_concurrent(
            table_path, QUERIES, per_query_settings=per_query,
            mid_flight=kill_victim, join_timeout=180,
        )
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
    stats = tracing.counters("shared_scan", reset=True)
    rec = tracing.counters("recovery", reset=True)
    for q, got, want in zip(QUERIES, out, solo):
        assert got == want, (q, got, want)
    assert stats.get("batches_formed", 0) >= 1, stats
    assert rec.get("lost_task_reset", 0) >= 1, rec


# -- fuzz slice: concurrent distinct queries over shared tables -------------

_FUZZ_AGGS = [
    "sum(v)", "count(*)", "min(q)", "max(q)", "sum(q)", "min(d)", "max(d)",
    "avg(v)",
]
_FUZZ_PREDS = ["v > 0", "q < 25", "d >= date '1995-01-01'", "v < 50 and q > 5"]


def _fuzz_queries(qrng, k=3):
    out = []
    for _ in range(k):
        key = str(qrng.choice(["g", "s", "g, s"]))
        picks = list(qrng.choice(
            _FUZZ_AGGS, size=int(qrng.integers(1, 4)), replace=False
        ))
        sel = ", ".join([key] + [f"{a} as a{i}" for i, a in enumerate(picks)])
        sql = f"select {sel} from t"
        if qrng.random() < 0.6:
            sql += " where " + str(qrng.choice(_FUZZ_PREDS))
        out.append(sql + f" group by {key} order by {key}")
    return out


@pytest.mark.parametrize("seed", range(2))
def test_fuzz_concurrent_shared_scan(tmp_path, seed):
    """Fuzz slice (ISSUE 13): random concurrent DISTINCT aggregate queries
    over one shared table, batched dispatch ON, compared bit-exactly
    against the sequential (never-batched) run of the same cluster shape.
    Own rng streams (22000+ data, 23000+ queries), so every baseline
    stream in test_fuzz_device.py stays byte-identical."""
    rng = np.random.default_rng(22000 + seed)
    qrng = np.random.default_rng(23000 + seed)
    n = int(rng.integers(5_000, 30_000))
    t = pa.table({
        "g": pa.array([f"k{v}" for v in rng.integers(0, 8, n)]),
        "s": pa.array([f"x{v}" for v in rng.integers(0, 3, n)]),
        "v": pa.array(np.round(rng.uniform(-1000, 1000, n), 2)),
        "q": pa.array(rng.integers(1, 100, n), type=pa.int64()),
        "d": pa.array(
            rng.integers(8000, 12000, n), type=pa.int32()
        ).cast(pa.date32()),
    })
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)
    queries = _fuzz_queries(qrng)
    solo = _run_sequential(path, queries)
    batched = _run_concurrent(path, queries)
    for q, got, want in zip(queries, batched, solo):
        assert got == want, (q, got, want)


# -- weighted fair-share sibling ordering (ISSUE 14 satellite) ---------------

def test_form_shared_batch_fair_share_sibling_order():
    """PR 13 residue: sibling selection must honor the same smallest
    in_flight/weight fair-share key assignment uses — a heavy tenant with
    many co-pending compatible stages can no longer fill every sibling
    slot of a batch while a lighter tenant has compatible work. Pre-fix,
    candidates were visited in KV insertion order, so the heavy tenant's
    (earlier-submitted) jobs consumed all max_batch-1 slots."""
    from ballista_tpu.proto import ballista_pb2 as pb
    from ballista_tpu.scheduler.kv import MemoryBackend
    from ballista_tpu.scheduler.state import SchedulerState

    state = SchedulerState(
        MemoryBackend(), "fairshare",
        config=BallistaConfig({
            "ballista.shared_scan.max_batch": "4",  # 3 sibling slots
            "ballista.tpu.cost_model_dir": "",
        }),
    )

    def add_job(job_id, tenant):
        running = pb.JobStatus()
        running.running.SetInParent()
        state.save_job_metadata(job_id, running)
        state.save_job_tenant(job_id, tenant, 0)
        st = pb.TaskStatus()
        st.partition_id.job_id = job_id
        st.partition_id.stage_id = 1
        st.partition_id.partition_id = 0
        state.save_task_status(st)

    # the heavy tenant submits FIRST (insertion order favored it pre-fix)
    # and already has 4 running tasks in flight; the light tenant has none
    for j in ("h1", "h2", "h3", "h4"):
        add_job(j, "heavy")
    add_job("l1", "light")
    for i in range(4):
        run = pb.TaskStatus()
        run.partition_id.job_id = "h-running"
        run.partition_id.stage_id = 9
        run.partition_id.partition_id = i
        run.running.executor_id = "e-other"
        state.save_task_status(run)
    state.save_job_tenant("h-running", "heavy", 0)
    rj = pb.JobStatus()
    rj.running.SetInParent()
    state.save_job_metadata("h-running", rj)

    # primary already assigned (another heavy job)
    primary = pb.TaskStatus()
    primary.partition_id.job_id = "h0"
    primary.partition_id.stage_id = 1
    primary.partition_id.partition_id = 0
    primary.running.executor_id = "e1"
    state.save_job_tenant("h0", "heavy", 0)
    pj = pb.JobStatus()
    pj.running.SetInParent()
    state.save_job_metadata("h0", pj)

    # unit harness: every candidate stage is scan-compatible and binds
    sig = ("ParquetScanExec", ("f.parquet",), False, 1)
    state._cached_stage_signature = lambda j, s: sig
    state._bound_stage_plan = lambda j, s, idx: object()

    out = state.form_shared_batch(primary, object(), "e1")
    members = [st.partition_id.job_id for st, _plan in out]
    assert len(members) == 3
    # the light tenant's job MUST hold a slot (pre-fix: ['h1','h2','h3'])
    assert "l1" in members, members
    # and the re-ranking interleaves rather than draining one tenant:
    # light (0 in flight) first, then heavy's fair share
    assert members[0] == "l1", members


def test_batch_siblings_carry_their_own_stages_kept_bytes(table_path):
    """ISSUE 27: a shared-scan sibling's TaskDefinition goes through the same
    binding and the same kept encoding as a first attempt of its stage: one
    encode a member stage, and the bytes a fresh encode of the bound tree
    gives."""
    from ballista_tpu.distributed.planner import DistributedPlanner
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.proto import ballista_pb2 as pb
    from ballista_tpu.scheduler.kv import MemoryBackend
    from ballista_tpu.scheduler.server import SchedulerServer
    from ballista_tpu.serde.physical import phys_plan_to_proto

    costmodel.reset()
    srv = SchedulerServer(
        MemoryBackend(), namespace="t",
        config=BallistaConfig({"ballista.tpu.cost_model_dir": ""}))
    s = srv.state
    s.save_executor_metadata(pb.ExecutorMetadata(id="e1", host="h", port=1))
    ctx = ExecutionContext()
    ctx.register_parquet("t", table_path)
    stage_of = {}
    for job, query in zip("abc", QUERIES):
        physical = ctx.create_physical_plan(ctx.sql(query).logical_plan())
        stage = DistributedPlanner().plan_query_stages(job, physical)[0]
        running = pb.JobStatus()
        running.running.SetInParent()
        s.save_job_metadata(job, running)
        s.save_stage_plan(job, stage.stage_id, stage)
        pending = pb.TaskStatus()
        pending.partition_id.job_id, pending.partition_id.stage_id = job, stage.stage_id
        s.save_task_status(pending)
        stage_of[job] = stage.stage_id
    status, plan = s.assign_next_schedulable_task("e1")
    siblings = s.form_shared_batch(status, plan, "e1")
    assert sorted(st.partition_id.job_id for st, _p in siblings) == ["b", "c"]
    members = [srv._task_definition(status, plan)] + [
        srv._task_definition(st, p) for st, p in siblings]
    assert s.plan_encodes == 3
    for td in members:
        job = td.task_id.job_id
        kept = s._stage_plans[job][stage_of[job]]
        bound = s._bound_stage_plan(job, stage_of[job], s._ensure_task_index())
        assert bound is kept.bound
        assert td.plan.SerializeToString() == kept.wire
        assert kept.wire == phys_plan_to_proto(bound).SerializeToString()
        assert td.plan.shuffle_writer.job_id == job
    assert s.plan_encodes == 3


# -- layout-warm members are shared-scan-eligible (ISSUE 15 satellite) -------

def test_layout_warm_member_batches_bit_identical(table_path, tmp_path):
    """PR 13 residue: batch.size now folds into the stage/persist key, so a
    persisted-layout-WARM member is shared-scan-eligible — the warm layout
    is guaranteed to be at this dispatch's batch granularity, making the
    shared batch stream row-identical to the member's layout-cache solo
    run. Pre-fix, any member with a persist key and a configured layout dir
    silently degraded to solo. Warm the persisted layouts with a sequential
    pass, then batch concurrently on the SAME layout dir: batches must
    form and every member must be bit-identical to its warm solo run."""
    layout_dir = str(tmp_path / "layouts")
    warm = _client_settings(
        **{"ballista.tpu.layout_cache_dir": layout_dir}
    )
    # sequential warm pass: persists each member stage's layout
    solo = _run_sequential(table_path, QUERIES, client_settings=warm)
    import os

    assert os.path.isdir(layout_dir) and os.listdir(layout_dir), (
        "warm pass persisted no layout entries — the regression test "
        "would not exercise the layout-warm path"
    )
    tracing.counters("shared_scan", reset=True)
    batched = _run_concurrent(table_path, QUERIES, client_settings=warm)
    stats = tracing.counters("shared_scan", reset=True)
    for q, got, want in zip(QUERIES, batched, solo):
        assert got == want, (q, got, want)
    # the whole point: layout-warm members now group and share the scan
    assert stats.get("batches_formed", 0) >= 1, stats
    assert stats.get("shared_groups", 0) >= 1, stats
    assert stats.get("uploads_saved", 0) >= 1, stats
