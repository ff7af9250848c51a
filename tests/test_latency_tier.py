"""Low-latency serving tier (ISSUE 8): push dispatch, the persistent AOT
program cache, streaming result collect, and their satellites.

Four layers, mirroring the subsystem's spread:

- push-dispatch units: pump credit bounds, stale-attempt rejection, the
  per-partition completion notifications on the running job status;
- AOT cache units (ops/aotcache.py): disk roundtrip, corrupted /
  fingerprint-mismatched artifact fallback (reason recorded), the
  `aot.load` chaos site, prewarm;
- end-to-end standalone-cluster runs: push-dispatched queries with ZERO
  poll dispatches, stream drop -> poll fallback -> re-subscribe, a warm
  AOT tier answering with ZERO fresh traces, streaming collect bit-equal
  to buffered, mid-fetch loss routing through ReportLostPartition, and
  seeded `scheduler.push` chaos staying bit-identical to fault-free;
- result-cache eviction (PR 7 residue): size bound LRU-by-last-hit, TTL,
  restart survival of the eviction order.
"""

import logging
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.client import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.executor.runtime import StandaloneCluster
from ballista_tpu.ops import aotcache
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.scheduler.kv import MemoryBackend, SqliteBackend
from ballista_tpu.scheduler.server import SchedulerServer, _PushSubscriber
from ballista_tpu.scheduler.state import SchedulerState
from ballista_tpu.utils import tracing

logging.getLogger("ballista.executor").setLevel(logging.CRITICAL)


@pytest.fixture()
def tpath(tmp_path):
    """3-file parquet table: multi-partition scans, so plans really have
    a shuffle stage and multiple tasks per stage."""
    d = tmp_path / "t"
    d.mkdir()
    for part in range(3):
        rows = range(part * 200, (part + 1) * 200)
        pq.write_table(
            pa.table(
                {
                    "k": pa.array([i % 7 for i in rows], type=pa.int64()),
                    "v": pa.array([float(i) * 0.5 for i in rows]),
                }
            ),
            str(d / f"part-{part}.parquet"),
        )
    return str(d)


def _wait_for(predicate, timeout=10.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# push-dispatch units
# ---------------------------------------------------------------------------


def _server_with_job(tpath, **extra):
    """Synchronous-planning scheduler over a memory store with one planned
    2-stage job and a registered executor — the pump unit-test bed."""
    server = SchedulerServer(
        MemoryBackend(),
        config=BallistaConfig({"ballista.cache.results": "false",
                               "ballista.shuffle.partitions": "4", **extra}),
        synchronous_planning=True,
    )
    server.state.save_executor_metadata(
        pb.ExecutorMetadata(id="e1", host="h", port=1)
    )
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.serde.logical import plan_to_proto

    ectx = ExecutionContext()
    ectx.register_parquet("t", tpath)
    plan = ectx.sql("select k, sum(v) as s from t group by k").logical_plan()
    params = pb.ExecuteQueryParams()
    params.logical_plan.CopyFrom(plan_to_proto(plan))
    job_id = server.ExecuteQuery(params).job_id
    return server, job_id


def test_pump_respects_credit_and_frees_on_status(tpath):
    server, job_id = _server_with_job(tpath)
    sub = _PushSubscriber("e1", slots=2)
    with server._push_mu:
        server._subscribers["e1"] = sub
    with server.state.kv.lock():
        n = server._pump_pushes()
    # credit bound: only `slots` pushed even though stage 1 has more tasks
    assert n == 2 and sub.queue.qsize() == 2
    assert len(sub.outstanding) == 2
    with server.state.kv.lock():
        assert server._pump_pushes() == 0  # saturated
    # a terminal status for one pushed task frees its credit (the PollWork
    # resolution path); the next pump refills
    td = sub.queue.get_nowait()
    st = pb.TaskStatus()
    st.partition_id.CopyFrom(td.task_id)
    st.attempt = td.attempt
    st.completed.executor_id = "e1"
    st.completed.path = "/x"
    poll = pb.PollWorkParams(metadata=pb.ExecutorMetadata(id="e1", host="h", port=1))
    poll.task_status.add().CopyFrom(st)
    server.PollWork(poll)
    assert len(sub.outstanding) == 2  # one resolved, one refilled by pump


def test_stale_attempt_push_rejected(tpath):
    """A pushed task requeued behind the executor's back (attempt bumped):
    the executor's late report with the OLD attempt is dropped, and the
    pump's credit re-verification frees the stale entry."""
    server, job_id = _server_with_job(tpath)
    st = server.state
    sub = _PushSubscriber("e1", slots=1)
    with server._push_mu:
        server._subscribers["e1"] = sub
    with st.kv.lock():
        assert server._pump_pushes() == 1
    td = sub.queue.get_nowait()
    pid = td.task_id
    tracing.counters("recovery", reset=True)
    # the task is requeued (e.g. orphan reconciliation) -> attempt 1
    with st.kv.lock():
        cur = st.get_task_status(pid.job_id, pid.stage_id, pid.partition_id)
        assert st.requeue_task(cur, "e1", "requeued under test", limit=3)
    # the executor finishes the STALE attempt and reports it
    late = pb.TaskStatus()
    late.partition_id.CopyFrom(pid)
    late.attempt = td.attempt
    late.completed.executor_id = "e1"
    late.completed.path = "/stale"
    with st.kv.lock():
        assert not st.accept_task_status(late)
    assert tracing.counters("recovery", reset=True).get("stale_status_dropped") == 1
    # pump re-verification: the stale outstanding entry no longer matches
    # the KV (attempt moved on), so its credit frees and the retry pushes
    with st.kv.lock():
        assert server._pump_pushes() == 1
    refetched = sub.queue.get_nowait()
    assert refetched.attempt == td.attempt + 1


def test_push_chaos_kills_stream_and_leaves_assignment(tpath):
    """rate=1.0 on scheduler.push: the delivery is torn AFTER the Running
    flip — the subscriber dies with it and the task stays Running in the
    ledger (the orphaned-assignment machinery owns recovery from there)."""
    server, job_id = _server_with_job(
        tpath,
        **{"ballista.chaos.rate": "1.0",
           "ballista.chaos.sites": "scheduler.push"},
    )
    sub = _PushSubscriber("e1", slots=2)
    with server._push_mu:
        server._subscribers["e1"] = sub
    tracing.counters("recovery", reset=True)
    with server.state.kv.lock():
        assert server._pump_pushes() == 0
    assert sub.closed.is_set()
    # nothing delivered: the queue holds only the close() sentinel
    assert sub.queue.get_nowait() is None and sub.queue.qsize() == 0
    assert tracing.counters("recovery", reset=True).get("chaos_push_torn") == 1
    # the assignment stands (Running, in the durable ledger), exactly like
    # a PollWork response lost in transit
    running = [
        t for t in server.state.get_job_tasks(job_id)
        if t.WhichOneof("status") == "running"
    ]
    assert len(running) == 1
    assert len(server.state._assigned) == 1


def test_partial_location_published_per_completed_partition(tpath):
    """synchronize_job_status publishes final-stage completions on the
    RUNNING status (the streaming client's per-partition notification)."""
    server, job_id = _server_with_job(tpath)
    st = server.state
    tasks = st.get_job_tasks(job_id)
    final_stage = max(t.partition_id.stage_id for t in tasks)
    finals = sorted(
        (t for t in tasks if t.partition_id.stage_id == final_stage),
        key=lambda t: t.partition_id.partition_id,
    )
    assert len(finals) >= 2
    done = pb.TaskStatus()
    done.partition_id.CopyFrom(finals[1].partition_id)
    done.completed.executor_id = "e1"
    done.completed.path = "/p1"
    with st.kv.lock():
        st.accept_task_status(done)
        st.synchronize_job_status(job_id)
    js = st.get_job_metadata(job_id)
    assert js.WhichOneof("status") == "running"
    locs = list(js.running.partial_location)
    assert [pl.partition_id.partition_id for pl in locs] == [
        finals[1].partition_id.partition_id
    ]
    assert locs[0].path == "/p1" and locs[0].executor_meta.id == "e1"


# ---------------------------------------------------------------------------
# AOT program-cache units
# ---------------------------------------------------------------------------


class _Owner:
    def __init__(self, key):
        self.aot_key = key


def _wrapped(tmp_path, key="stage-A", chaos=None):
    cfg = {"ballista.tpu.aot_cache": str(tmp_path / "aot")}
    if chaos:
        cfg.update(chaos)
    aotcache.configure(BallistaConfig(cfg))

    import jax.numpy as jnp

    def core(n, cols, aux):
        return jnp.stack(
            [jnp.sum(jnp.where(cols[0] == g, cols[1], 0.0)) for g in range(n)]
        ) + aux[0]

    return aotcache.wrap_step(_Owner(key), "unit", core, static_argnums=(0,))


def _args():
    import jax.numpy as jnp

    return (
        3,
        {0: jnp.asarray(np.arange(16, dtype=np.int32) % 3),
         1: jnp.asarray(np.arange(16, dtype=np.float32))},
        [jnp.asarray(np.float32(1.0))],
    )


def test_aot_roundtrip_disk_hit_and_prewarm(tmp_path):
    aotcache.reset(clear_disk_dir=True)
    step = _wrapped(tmp_path)
    tracing.counters("serving", reset=True)
    out1 = np.asarray(step(*_args()))
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_trace") == 1 and s.get("aot_saved") == 1
    np.testing.assert_array_equal(out1, np.asarray(step(*_args())))
    assert tracing.counters("serving", reset=True).get("compile_hit_memory") == 1
    # cold process: fresh wrapper + empty memory map -> disk hit, same bits
    aotcache.reset()
    step2 = _wrapped(tmp_path)
    out2 = np.asarray(step2(*_args()))
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_hit_disk") == 1 and not s.get("compile_trace")
    np.testing.assert_array_equal(out1, out2)
    # prewarm: artifacts compile BEFORE any call; the call is a memory hit
    aotcache.reset()
    n = aotcache.prewarm(
        BallistaConfig({"ballista.tpu.aot_cache": str(tmp_path / "aot")})
    )
    assert n == 1
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_prewarmed") == 1
    step3 = _wrapped(tmp_path)
    out3 = np.asarray(step3(*_args()))
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_hit_memory") == 1 and not s.get("compile_trace")
    np.testing.assert_array_equal(out1, out3)


def test_warm_compiles_without_execute(tmp_path):
    """ISSUE 19 satellite: ``step.warm(...)`` compiles (and persists) a
    signature WITHOUT running the program — the later real call neither
    traces nor compiles, and a cold process warms straight from the
    persisted artifact."""
    import jax.numpy as jnp

    aotcache.reset(clear_disk_dir=True)
    traces = {"n": 0}

    def core(n, cols, aux):
        traces["n"] += 1
        return jnp.stack(
            [jnp.sum(jnp.where(cols[0] == g, cols[1], 0.0)) for g in range(n)]
        ) + aux[0]

    aotcache.configure(
        BallistaConfig({"ballista.tpu.aot_cache": str(tmp_path / "aot")})
    )
    step = aotcache.wrap_step(
        _Owner("warm-A"), "unit", core, static_argnums=(0,)
    )
    tracing.counters("serving", reset=True)
    assert step.warm(*_args()) is True
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_warmed") == 1 and s.get("aot_saved") == 1
    assert not s.get("compile_trace")
    warm_traces = traces["n"]
    assert warm_traces >= 1  # the warm itself traced (a compile happened)
    # the real call: memory-map hit + jit executable-cache hit — NO retrace
    out = np.asarray(step(*_args()))
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_hit_memory") == 1 and not s.get("compile_trace")
    assert traces["n"] == warm_traces  # compile-without-execute held: the
    # signature was never traced again after the warm
    # a second warm finds the signature already resolvable
    assert step.warm(*_args()) is False
    # cold process: the artifact the warm persisted serves a disk warm
    aotcache.reset()
    step2 = aotcache.wrap_step(
        _Owner("warm-A"), "unit", core, static_argnums=(0,)
    )
    tracing.counters("serving", reset=True)
    assert step2.warm(*_args()) is True
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_hit_disk") == 1 and not s.get("compile_warmed")
    np.testing.assert_array_equal(out, np.asarray(step2(*_args())))


def test_aot_shape_and_stage_keyed(tmp_path):
    """A different shape bucket or a different stage identity is a
    different program — no false sharing."""
    import jax.numpy as jnp

    aotcache.reset(clear_disk_dir=True)
    step = _wrapped(tmp_path)
    tracing.counters("serving", reset=True)
    step(*_args())
    wide = (
        3,
        {0: jnp.asarray(np.arange(32, dtype=np.int32) % 3),
         1: jnp.asarray(np.arange(32, dtype=np.float32))},
        [jnp.asarray(np.float32(1.0))],
    )
    step(*wide)  # new shape bucket -> fresh trace
    other = _wrapped(tmp_path, key="stage-B")
    other(*_args())  # new stage identity -> fresh trace
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_trace") == 3 and not s.get("compile_hit_memory")


def test_aot_corrupted_artifact_falls_back(tmp_path):
    aotcache.reset(clear_disk_dir=True)
    step = _wrapped(tmp_path)
    out1 = np.asarray(step(*_args()))
    [entry] = aotcache.manifest_entries(str(tmp_path / "aot"))
    blob_path = aotcache._blob_path(str(tmp_path / "aot"), entry["key"])
    with open(blob_path, "rb") as f:
        payload = f.read()
    header, _, _blob = payload.partition(b"\n")
    with open(blob_path, "wb") as f:
        f.write(header + b"\n" + b"garbage-not-a-program")
    aotcache.reset()
    step2 = _wrapped(tmp_path)
    tracing.counters("serving", reset=True)
    out2 = np.asarray(step2(*_args()))
    s = tracing.counters("serving", reset=True)
    assert s.get("aot_load_error") == 1  # reason recorded
    assert s.get("compile_trace") == 1  # fell back to a fresh compile
    np.testing.assert_array_equal(out1, out2)


def test_aot_fingerprint_mismatch_falls_back(tmp_path):
    """An artifact written by a different jax/jaxlib/backend is rejected
    by its header before deserialization is even attempted."""
    import json

    aotcache.reset(clear_disk_dir=True)
    step = _wrapped(tmp_path)
    out1 = np.asarray(step(*_args()))
    [entry] = aotcache.manifest_entries(str(tmp_path / "aot"))
    blob_path = aotcache._blob_path(str(tmp_path / "aot"), entry["key"])
    with open(blob_path, "rb") as f:
        _header, _, blob = f.read().partition(b"\n")
    with open(blob_path, "wb") as f:
        f.write(json.dumps(
            {"fingerprint": "v0|jax0.0.0|jaxlib0.0.0|tpu", "name": "unit"}
        ).encode() + b"\n" + blob)
    aotcache.reset()
    step2 = _wrapped(tmp_path)
    tracing.counters("serving", reset=True)
    out2 = np.asarray(step2(*_args()))
    s = tracing.counters("serving", reset=True)
    assert s.get("aot_load_error") == 1 and s.get("compile_trace") == 1
    np.testing.assert_array_equal(out1, out2)
    # prewarm skips it the same way
    aotcache.reset()
    tracing.counters("serving", reset=True)
    assert aotcache.prewarm(
        BallistaConfig({"ballista.tpu.aot_cache": str(tmp_path / "aot")})
    ) == 0
    assert tracing.counters("serving", reset=True).get("aot_load_error") == 1


def test_aot_load_chaos_torn(tmp_path):
    """rate=1.0 on aot.load: every disk load is torn deterministically and
    falls back to a fresh compile — results identical, reason recorded."""
    aotcache.reset(clear_disk_dir=True)
    step = _wrapped(tmp_path)
    out1 = np.asarray(step(*_args()))
    aotcache.reset()
    step2 = _wrapped(
        tmp_path,
        chaos={"ballista.chaos.rate": "1.0",
               "ballista.chaos.sites": "aot.load"},
    )
    tracing.counters("serving", reset=True)
    out2 = np.asarray(step2(*_args()))
    s = tracing.counters("serving", reset=True)
    assert s.get("aot_load_error") == 1 and s.get("compile_trace") == 1
    np.testing.assert_array_equal(out1, out2)


def test_aot_bypasses_without_key_or_dir(tmp_path):
    """No aot_key (stage built outside the dispatcher) or no cache dir:
    the wrapper is a plain jit passthrough — no counters, no files."""
    aotcache.reset(clear_disk_dir=True)
    step = _wrapped(tmp_path, key=None)
    tracing.counters("serving", reset=True)
    step(*_args())
    assert tracing.counters("serving", reset=True) == {}
    assert aotcache.manifest_entries(str(tmp_path / "aot")) == []


# ---------------------------------------------------------------------------
# end-to-end: standalone cluster
# ---------------------------------------------------------------------------


def test_push_dispatch_e2e_zero_poll(tpath):
    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.results": "false"},
        )
        ctx.register_parquet("t", tpath)
        tracing.counters("serving", reset=True)
        q = "select k, sum(v) as s from t group by k order by k"
        first = ctx.sql(q).collect()
        again = ctx.sql(q).collect()
        assert again.equals(first)
        s = tracing.counters("serving", reset=True)
        assert s.get("dispatch_push", 0) > 0
        assert s.get("dispatch_poll", 0) == 0, s
        assert s.get("task_pushed") == s.get("dispatch_push")
        ctx.close()
    finally:
        cluster.shutdown()


def test_stream_drop_poll_fallback_then_resubscribe(tpath):
    """Stream loss -> polls pull work (automatic fallback) -> re-subscribe
    resumes push. The scheduler's push gate stands in for a mid-rollout
    scheduler that cannot stream."""
    cluster = StandaloneCluster(n_executors=1)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.results": "false"},
        )
        ctx.register_parquet("t", tpath)
        q = "select k, count(*) as n from t group by k order by k"
        base = ctx.sql(q).collect()
        ex = cluster.executors[0]
        # kill the stream AND refuse re-subscription
        cluster.scheduler_impl.push_enabled = False
        ex.poll_loop._cancel_push()
        assert _wait_for(lambda: not ex.poll_loop._stream_ok.is_set())
        tracing.counters("serving", reset=True)
        out = ctx.sql(q).collect()
        s = tracing.counters("serving", reset=True)
        assert out.equals(base)
        assert s.get("dispatch_poll", 0) > 0, s
        assert s.get("dispatch_push", 0) == 0
        # scheduler allows streams again: the executor's subscribe loop
        # reconnects by itself and dispatch returns to push
        cluster.scheduler_impl.push_enabled = True
        assert _wait_for(lambda: ex.poll_loop._stream_ok.is_set())
        tracing.counters("serving", reset=True)
        out2 = ctx.sql(q).collect()
        s = tracing.counters("serving", reset=True)
        assert out2.equals(base)
        assert s.get("dispatch_push", 0) > 0
        assert s.get("dispatch_poll", 0) == 0, s
        ctx.close()
    finally:
        cluster.shutdown()


def test_idle_poll_backoff_decays_and_snaps_back(tpath):
    """Satellite: with a healthy stream the heartbeat decays toward
    idle_poll_max_s; a stream drop snaps it back to 250ms."""
    cluster = StandaloneCluster(
        n_executors=1,
        config=BallistaConfig({"ballista.executor.idle_poll_max_s": "0.6"}),
    )
    try:
        ex = cluster.executors[0]
        loop = ex.poll_loop
        assert _wait_for(lambda: loop._stream_ok.is_set())
        assert _wait_for(
            lambda: loop._poll_interval > 0.25, timeout=15.0
        ), "interval never decayed"
        with loop._mu:
            assert loop._poll_interval <= 0.6 + 1e-9
        cluster.scheduler_impl.push_enabled = False
        loop._cancel_push()
        assert _wait_for(lambda: not loop._stream_ok.is_set())
        # next loop iteration resets to the 250ms floor
        assert _wait_for(
            lambda: abs(loop._poll_interval - 0.25) < 1e-9, timeout=10.0
        )
    finally:
        cluster.shutdown()


def test_aot_warm_push_query_zero_trace_zero_poll(tmp_path, tpath):
    """The acceptance path: with prewarm on and push dispatch enabled, a
    repeated small query runs with ZERO fresh traces (compile-hit counter)
    and ZERO poll-dispatched tasks (push counter)."""
    from ballista_tpu.ops import kernels

    aot_dir = str(tmp_path / "aot")
    settings = {
        "ballista.executor.backend": "tpu",
        "ballista.cache.results": "false",
        "ballista.tpu.aot_cache": aot_dir,
        "ballista.tpu.layout_cache_dir": str(tmp_path / "layouts"),
    }
    q = "select k, sum(v) as s, count(*) as n from t group by k order by k"

    def clear_stage_caches():
        with kernels._stage_cache_lock:
            kernels._stage_cache.clear()
            kernels._stage_cache_pins.clear()
            kernels._stage_latest.clear()

    aotcache.reset(clear_disk_dir=True)
    clear_stage_caches()
    cluster = StandaloneCluster(n_executors=1, config=BallistaConfig(settings))
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        ctx.register_parquet("t", tpath)
        cold = ctx.sql(q).collect()  # traces + persists the programs
        assert tracing.counters("serving", reset=True).get("compile_trace", 0) > 0
        warm = ctx.sql(q).collect()
        s = tracing.counters("serving", reset=True)
        assert warm.equals(cold)
        assert s.get("compile_trace", 0) == 0, s
        assert s.get("compile_hit_memory", 0) > 0
        assert s.get("dispatch_poll", 0) == 0 and s.get("dispatch_push", 0) > 0
        ctx.close()
    finally:
        cluster.shutdown()
    # a COLD executor with prewarm on: first query, zero trace, zero poll
    aotcache.reset()
    clear_stage_caches()
    cluster = StandaloneCluster(
        n_executors=1,
        config=BallistaConfig({**settings, "ballista.tpu.prewarm": "true"}),
    )
    try:
        prewarmed = tracing.counters("serving", reset=True)
        assert prewarmed.get("compile_prewarmed", 0) > 0
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        ctx.register_parquet("t", tpath)
        first = ctx.sql(q).collect()
        s = tracing.counters("serving", reset=True)
        assert first.equals(cold)
        assert s.get("compile_trace", 0) == 0, s
        assert s.get("dispatch_poll", 0) == 0 and s.get("dispatch_push", 0) > 0
        ctx.close()
    finally:
        cluster.shutdown()


def test_streaming_collect_bit_equality(tpath):
    """Streaming collect (and the raw batch generator) deliver bits
    identical to the buffered path — including a multi-partition final
    stage, where batches must assemble in partition order regardless of
    completion order."""
    cluster = StandaloneCluster(n_executors=2)
    try:
        # no global sort: the final stage keeps its shuffle partitioning,
        # so results really stream partition-by-partition
        q = "select k, sum(v) as s, count(*) as n from t group by k"
        buf_ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.results": "false",
                      "ballista.shuffle.partitions": "4"},
        )
        buf_ctx.register_parquet("t", tpath)
        buffered = buf_ctx.sql(q).collect()
        st_ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.results": "false",
                      "ballista.shuffle.partitions": "4",
                      "ballista.client.stream_results": "true"},
        )
        st_ctx.register_parquet("t", tpath)
        streamed = st_ctx.sql(q).collect()
        assert streamed.equals(buffered)
        # raw generator: same rows, same order
        batches = list(
            st_ctx.collect_stream(st_ctx.sql(q).logical_plan())
        )
        tbl = pa.Table.from_batches(
            batches, schema=batches[0].schema
        ).cast(buffered.schema)
        assert tbl.equals(buffered)
        buf_ctx.close()
        st_ctx.close()
    finally:
        cluster.shutdown()


def test_streaming_lost_partition_recovers(tpath):
    """Mid-fetch loss on the streaming path routes through
    ReportLostPartition + re-poll: the job restarts the lost final-stage
    tasks and the stream completes with the recomputed bits. Same death
    harness as the buffered-path test in test_fault_tolerance (total
    executor death + shortened lease so lineage can reschedule)."""
    import ballista_tpu.scheduler.state as state_mod

    cluster = StandaloneCluster(n_executors=2)
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    cluster.scheduler_impl.lost_task_check_interval = 0.3
    try:
        settings = {"ballista.cache.results": "false",
                    "ballista.client.stream_results": "true"}
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        ctx.register_parquet("t", tpath)
        q = "select k, sum(v) as s from t group by k order by k"
        plan = ctx.sql(q).logical_plan()
        baseline = ctx.collect(plan)
        # run to completion, then kill an owning executor COMPLETELY so the
        # streaming fetch hits dead locations
        job_id = ctx.submit(plan)
        st = cluster.scheduler_impl.state

        def completed():
            js = st.get_job_metadata(job_id)
            return js is not None and js.WhichOneof("status") == "completed"

        assert _wait_for(completed, timeout=60.0)
        js = st.get_job_metadata(job_id)
        owners = {pl.executor_meta.id
                  for pl in js.completed.partition_location}
        victim = next(
            ex for ex in cluster.executors if ex.id in owners
        )
        victim.stop()
        tracing.counters("recovery", reset=True)
        out = ctx._collect_results(job_id, plan.schema(), timeout=120)
        assert out.equals(baseline)
        rec = tracing.counters("recovery", reset=True)
        assert rec.get("result_fetch_restarted", 0) >= 1
        assert rec.get("result_partition_restarted", 0) >= 1
        ctx.close()
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
        cluster.shutdown()


def _chaos_push_run(tpath, rate, seed):
    cluster = StandaloneCluster(
        n_executors=2,
        config=BallistaConfig({
            "ballista.chaos.rate": str(rate),
            "ballista.chaos.seed": str(seed),
            "ballista.chaos.sites": "scheduler.push",
        }),
    )
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.results": "false"},
        )
        ctx.register_parquet("t", tpath)
        out = ctx.collect(
            ctx.sql(
                "select k, sum(v) as s, count(*) as n from t "
                "group by k order by k"
            ).logical_plan(),
            timeout=90,
        )
        ctx.close()
        return out
    finally:
        cluster.shutdown()


@pytest.mark.slow
def test_push_chaos_bit_identical(tpath):
    """Seeded scheduler.push chaos: torn deliveries kill the stream with
    the assignment already written — recovery (orphan-grace requeue +
    re-subscribe + poll fallback) must deliver bits identical to the
    fault-free run. The seed is scanned so the run provably injects."""
    fault_free = _chaos_push_run(tpath, 0.0, 0)
    for seed in range(20):
        tracing.counters("recovery", reset=True)
        tracing.counters("serving", reset=True)
        out = _chaos_push_run(tpath, 0.4, seed)
        assert out.equals(fault_free), f"seed {seed} diverged"
        rec = tracing.counters("recovery", reset=True)
        if rec.get("chaos_push_torn"):
            assert tracing.counters("serving", reset=True).get("push_stream_drop", 0) >= 1
            return
    pytest.fail("no seed in range injected a scheduler.push fault")


# ---------------------------------------------------------------------------
# result-cache eviction (PR 7 residue)
# ---------------------------------------------------------------------------


def _completed(path, executor="e1"):
    c = pb.CompletedJob()
    pl = c.partition_location.add()
    pl.path = path
    pl.executor_meta.id = executor
    return c


def _reg(st, executor="e1"):
    st.save_executor_metadata(
        pb.ExecutorMetadata(id=executor, host="h", port=1)
    )


def test_result_cache_eviction_lru_by_last_hit():
    st = SchedulerState(
        MemoryBackend(), "t",
        config=BallistaConfig({"ballista.cache.results.max_entries": "3"}),
    )
    _reg(st)
    tracing.counters("tenancy", reset=True)
    for i in range(3):
        assert st.result_cache_put(f"fp{i}", _completed(f"/p{i}"))
        time.sleep(0.01)
    # hit fp0: it becomes the MOST recent; fp1 (never hit, oldest created)
    # is now the LRU victim
    assert st.result_cache_lookup("fp0") is not None
    assert st.result_cache_put("fp3", _completed("/p3"))
    present = [
        i for i in range(4)
        if st.kv.get(st._key("resultcache", f"fp{i}")) is not None
    ]
    assert present == [0, 2, 3], present
    assert tracing.counters("tenancy", reset=True).get("cache_evicted") == 1


def test_result_cache_ttl_expiry():
    st = SchedulerState(
        MemoryBackend(), "t",
        config=BallistaConfig({"ballista.cache.results.ttl_s": "0.05"}),
    )
    _reg(st)
    assert st.result_cache_put("fpx", _completed("/x"))
    assert st.result_cache_lookup("fpx") is not None  # fresh: still a hit
    time.sleep(0.1)
    tracing.counters("tenancy", reset=True)
    assert st.result_cache_lookup("fpx") is None
    stats = tracing.counters("tenancy", reset=True)
    assert stats.get("cache_expired") == 1
    assert st.kv.get(st._key("resultcache", "fpx")) is None


def test_result_cache_eviction_order_survives_restart():
    """last_hit lives in the KV value: a restarted scheduler on the same
    store evicts in the same order the dead one would have."""
    kv = SqliteBackend.temporary()
    st = SchedulerState(
        kv, "t",
        config=BallistaConfig({"ballista.cache.results.max_entries": "2"}),
    )
    _reg(st)
    assert st.result_cache_put("a", _completed("/a"))
    time.sleep(0.01)
    assert st.result_cache_put("b", _completed("/b"))
    time.sleep(0.01)
    assert st.result_cache_lookup("a") is not None  # a outranks b now
    st2 = SchedulerState(
        kv, "t",
        config=BallistaConfig({"ballista.cache.results.max_entries": "2"}),
    )
    assert st2.result_cache_put("c", _completed("/c"))
    present = [
        fp for fp in ("a", "b", "c")
        if kv.get(st2._key("resultcache", fp)) is not None
    ]
    assert present == ["a", "c"], present


def test_result_cache_unbounded_when_disabled():
    st = SchedulerState(
        MemoryBackend(), "t",
        config=BallistaConfig({"ballista.cache.results.max_entries": "0",
                               "ballista.cache.results.ttl_s": "0"}),
    )
    _reg(st)
    for i in range(8):
        assert st.result_cache_put(f"fp{i}", _completed(f"/p{i}"))
    assert all(
        st.kv.get(st._key("resultcache", f"fp{i}")) is not None
        for i in range(8)
    )
