"""The span recorder (utils/tracing.py) and the spans of the served path."""

import hashlib
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.utils import locks, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_log():
    tracing.reset()
    yield
    tracing.reset()


def _span(name, start, end, sid, parent=0, job="j"):
    """A hand-made closed span: ids and times given, nothing recorded."""
    s = tracing.Span(name, job=job)
    s.start_ns, s.end_ns, s.id, s.parent = start, end, sid, parent
    return s


def test_a_span_has_name_times_thread_parent_and_ids():
    with tracing.span("outer", job="j1") as outer:
        with tracing.span("inner", stage=2, partition=5, bytes=7) as inner:
            inner.set(via="x")
    log = {s.name: s for s in tracing.spans()}
    assert list(log) == ["inner", "outer"]  # closed in that order
    o, i = log["outer"], log["inner"]
    assert 0 < o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    assert o.parent == 0 and i.parent == o.id and i.id != o.id
    assert o.tid == i.tid == threading.get_ident()
    assert (i.job, i.stage, i.partition) == ("j1", 2, 5)  # the job is the parent's
    assert (o.job, o.stage, o.partition) == ("j1", None, None)
    assert i.attrs == {"bytes": 7, "via": "x"} and i.seconds >= 0


def test_a_job_set_inside_the_block_reaches_later_children():
    with tracing.span("client.collect") as root:
        with tracing.span("client.submit") as sub:
            sub.job = "abc"
        root.job = sub.job
        with tracing.span("client.wait"):
            pass
    jobs = {s.name: s.job for s in tracing.spans()}
    assert jobs == {"client.submit": "abc", "client.wait": "abc", "client.collect": "abc"}


def test_two_threads_never_adopt_each_others_parent():
    inside = threading.Event()
    done = threading.Event()

    def other():
        with tracing.span("other.root", job="b"):
            inside.set()
            done.wait(5)

    t = threading.Thread(target=other)
    t.start()
    assert inside.wait(5)
    with tracing.span("mine.root", job="a"):  # opened while other.root is open
        with tracing.span("mine.child"):
            pass
    done.set()
    t.join()
    log = {s.name: s for s in tracing.spans()}
    assert log["mine.root"].parent == 0 and log["other.root"].parent == 0
    assert log["mine.child"].parent == log["mine.root"].id
    assert log["mine.child"].job == "a" and log["other.root"].tid != log["mine.root"].tid


def test_an_explicit_parent_joins_a_worker_thread_to_the_request():
    out = []
    with tracing.span("executor.execute", job="j", stage=1, partition=3) as task:
        t = threading.Thread(
            target=lambda: out.append(tracing.span("shuffle.fetch", parent=task).__enter__()))
        t.start()
        t.join()
    child = out[0]
    assert child.parent == task.id and (child.job, child.stage, child.partition) == ("j", 1, 3)
    assert tracing.current() is None


def test_a_helper_thread_adopts_the_span_it_works_for():
    """`pipelined_map`'s reader pulls the stage's scan on its own thread:
    what the pull opens is the stage's child, and the thread is left clean."""
    from ballista_tpu.ops.runtime import pipelined_map

    def scan():
        for i in range(3):
            with tracing.span("runtime.dim_build", rows=i):
                pass
            yield i

    left = []
    with tracing.span("runtime.stage", job="j", stage=2, partition=1) as stage:
        assert list(pipelined_map(scan(), lambda x: x * 2, workers=2)) == [0, 2, 4]

        def helper():
            with tracing.adopt(stage):
                with tracing.span("adopted.child"):
                    pass
            with tracing.adopt(None):  # nothing to work for: a root, as before
                with tracing.span("adopted.none"):
                    pass
            left.append(tracing.current())

        t = threading.Thread(target=helper)
        t.start()
        t.join()
    log = tracing.spans()
    builds = [s for s in log if s.name == "runtime.dim_build"]
    assert len(builds) == 3 and all(s.tid != stage.tid for s in builds)
    for s in builds + [s for s in log if s.name == "adopted.child"]:
        assert s.parent == stage.id and (s.job, s.stage, s.partition) == ("j", 2, 1)
    assert [s.parent for s in log if s.name == "adopted.none"] == [0]
    assert left == [None] and tracing.current() is None
    # the stage's self time is what no child covers, on whichever thread
    assert tracing.by_name(log)["runtime.stage"][2] <= stage.seconds


def test_record_keeps_an_interval_whose_ends_lie_elsewhere():
    t0 = tracing.now_ns()
    s = tracing.record("scheduler.queue", t0, t0 + 5_000_000, job="j", stage=1,
                       partition=0, via="push")
    assert tracing.spans() == [s]
    assert s.seconds == pytest.approx(0.005) and s.attrs == {"via": "push"}
    assert tracing.current() is None  # a record opens nothing
    backwards = tracing.record("x", t0, t0 - 10)
    assert backwards.end_ns == backwards.start_ns  # never a negative length


@pytest.mark.parametrize("children,own_ms", [
    ([], 100.0),
    ([(10, 30)], 80.0),
    ([(10, 30), (20, 50)], 60.0),        # overlapping children: their union
    ([(10, 30), (60, 130)], 40.0),       # a child that outlives its parent is clipped
])
def test_self_time_is_duration_less_what_the_children_cover(children, own_ms):
    ms = 1_000_000
    log = [_span("parent", 0, 100 * ms, 1)]
    log += [_span("child", a * ms, b * ms, 10 + i, parent=1)
            for i, (a, b) in enumerate(children)]
    count, total, own = tracing.by_name(log)["parent"]
    assert (count, total) == (1, pytest.approx(0.1))
    assert own * 1e3 == pytest.approx(own_ms)


def test_covered_s_is_the_union_inside_the_given_intervals():
    ms = 1_000_000
    log = [_span("a", 0, 10 * ms, 1), _span("b", 5 * ms, 20 * ms, 2),
           _span("c", 40 * ms, 50 * ms, 3), _span("d", 90 * ms, 120 * ms, 4)]
    assert tracing.covered_s(log, [(0, 100 * ms)]) == pytest.approx(0.040)
    assert tracing.covered_s(log, [(8 * ms, 45 * ms)]) == pytest.approx(0.017)
    assert tracing.covered_s(log, [(0, 5 * ms), (95 * ms, 200 * ms)]) == pytest.approx(0.030)
    assert tracing.covered_s([], [(0, 100 * ms)]) == 0.0
    assert [s.name for s in tracing.leaves(
        [_span("p", 0, 9, 1), _span("q", 1, 2, 2, parent=1)])] == ["q"]


def test_the_ring_is_bounded_and_counts_what_it_dropped(monkeypatch):
    monkeypatch.setattr(tracing, "RING", 8)
    tracing.reset()  # a ring of the patched size
    for i in range(20):
        with tracing.span(f"s{i}"):
            pass
    assert [s.name for s in tracing.spans()] == [f"s{i}" for i in range(12, 20)]
    assert tracing.counters()["tracing.dropped"] == 12
    tracing.reset()
    assert tracing.drained()["counters"]["tracing.dropped"] == 12
    assert "tracing.dropped" not in tracing.counters()
    monkeypatch.undo()
    tracing.reset()
    assert tracing.RING >= 2 * 83_000  # twice today's largest window (scan_agg's)


def test_reset_keeps_what_it_clears_until_the_next_reset():
    tracing.incr("device.host_fallback", 2)
    with tracing.span("first", job="j"):
        pass
    tracing.reset()
    assert tracing.spans() == [] and tracing.counters() == {}
    kept = tracing.drained()
    assert [s.name for s in kept["spans"]] == ["first"]
    assert kept["counters"] == {"device.host_fallback": 2}
    with tracing.span("second"):
        pass
    assert [s.name for s in tracing.drained()["spans"]] == ["first"]  # not yet
    tracing.reset()
    assert [s.name for s in tracing.drained()["spans"]] == ["second"]
    assert tracing.drained()["counters"] == {}


def test_a_family_is_read_and_reset_alone():
    tracing.incr("recovery.task_retry")
    tracing.incr("recovery.rpc_retry", 3)
    tracing.incr("recovery_x.other")  # a longer name is another family
    tracing.incr("device.host_fallback", 2)
    tracing.incr("serde.plan_decode")
    assert tracing.counters("recovery") == {"task_retry": 1, "rpc_retry": 3}
    assert tracing.counters("tenancy") == {}
    assert tracing.counters("recovery", reset=True) == {"task_retry": 1, "rpc_retry": 3}
    assert tracing.counters("recovery") == {}
    assert tracing.counters() == {
        "recovery_x.other": 1, "device.host_fallback": 2, "serde.plan_decode": 1}


def test_incr_from_many_threads_loses_no_count_and_sums_floats():
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(10_000):
                tracing.incr("speculation.launched")
                tracing.incr("speculation.wasted_seconds", 0.25)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert tracing.counters("speculation") == {"launched": 80_000, "wasted_seconds": 20_000.0}


def test_reset_drains_a_family_like_any_other_counter():
    tracing.incr("serving.dispatch_push", 17)
    tracing.incr("device.map_rows", 5)
    tracing.reset()
    assert tracing.counters("serving") == {} and tracing.counters() == {}
    assert tracing.drained()["counters"] == {"serving.dispatch_push": 17, "device.map_rows": 5}


def test_timeline_orders_by_start_and_indents_by_parent():
    with tracing.span("client.collect", job="j9"):
        with tracing.span("client.submit"):
            pass
        with tracing.span("client.wait", via="push"):
            time.sleep(0.002)
    with tracing.span("elsewhere", job="other"):
        pass
    lines = tracing.timeline("j9").splitlines()
    assert [ln.split(" ms ", 1)[1].split()[0] for ln in lines] == [
        "client.collect", "client.submit", "client.wait"]
    assert lines[0].lstrip().startswith("0.000 ms client.collect")
    assert " ms   client.wait" in lines[2] and "via=push" in lines[2]
    assert tracing.timeline("nobody") == "no span of job 'nobody'"
    assert not hasattr(tracing, "report")


def test_closing_a_span_under_the_kv_lock_records_no_edge():
    from ballista_tpu.scheduler.kv import MemoryBackend

    was_on = locks.witness_enabled()  # BALLISTA_LOCK_WITNESS=1 runs keep theirs
    if not was_on:
        locks.reset_witness()
        locks.enable_witness()
    try:
        before = set(locks.witness_edges())
        kv = MemoryBackend()
        with kv.lock():
            with tracing.span("scheduler.status", job="j"):
                pass
            tracing.record("scheduler.queue", 1, 2, job="j")
        assert set(locks.witness_edges()) == before  # no lock taken, so no edge
        assert not any("tracing" in src or "tracing" in dst
                       for src, dst in locks.witness_edges())
    finally:
        if not was_on:
            locks.disable_witness()
            locks.reset_witness()


def test_a_client_process_does_not_start_importing_jax():
    code = ("import sys\n"
            "from ballista_tpu.utils import tracing\n"
            "with tracing.span('client.collect', job='j'):\n"
            "    pass\n"
            "assert len(tracing.spans()) == 1 and 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT, timeout=120)


def test_a_span_during_another_threads_import_of_jax(monkeypatch):
    """`jax` is in sys.modules from the first line of an `import jax` on
    another thread, long before `jax.profiler` exists: a span opened
    meanwhile records, annotates nothing and leaves the stack clean."""
    import types

    monkeypatch.setattr(tracing, "_annotate", None)  # as in a process that has not found it yet
    monkeypatch.setitem(sys.modules, "jax", types.ModuleType("jax"))
    tracing.reset()
    with tracing.span("executor.task", job="j") as s:
        assert tracing.current() is s
    assert tracing.current() is None
    assert [x.name for x in tracing.spans()] == ["executor.task"]
    # the submodule object is there, its names are not yet
    sys.modules["jax"].profiler = types.ModuleType("jax.profiler")
    with tracing.span("executor.task", job="j"):
        pass
    assert tracing.current() is None and len(tracing.spans()) == 2
    assert tracing._annotate is None  # asked again by the next span


def test_a_failing_annotation_leaves_no_span_on_the_stack(monkeypatch):
    import types

    def refuse(name, **ids):
        raise RuntimeError("no annotation")

    refuse.is_enabled = lambda: True
    stub = types.ModuleType("jax")
    stub.profiler = types.SimpleNamespace(TraceAnnotation=refuse)
    monkeypatch.setattr(tracing, "_annotate", None)
    monkeypatch.setitem(sys.modules, "jax", stub)
    with pytest.raises(RuntimeError):
        with tracing.span("executor.task", job="j"):
            pass
    assert tracing.current() is None


# -- the served path ------------------------------------------------------------

SETTINGS = {"ballista.executor.backend": "tpu", "ballista.cache.results": "false"}

# every span name that a two-stage aggregate over parquet files can reach on
# one executor: `runtime.upload` needs a persisted layout, which is not on this
# plan's path
SERVED = {
    "client.collect", "client.submit", "client.wait", "client.fetch",
    "scheduler.execute_query", "scheduler.plan", "scheduler.plan.commit",
    "scheduler.queue", "scheduler.assign", "scheduler.status",
    "executor.receive", "executor.task", "executor.setup", "executor.execute",
    "executor.report", "shuffle.write", "shuffle.fetch", "flight.do_get",
    "runtime.stage", "runtime.launch", "runtime.readback", "runtime.device_wait",
    "runtime.to_arrow",
}
# the host's steps inside a task: an operator's pull, a shuffle write's parts
STEPS = {"executor.operator", "shuffle.partition", "shuffle.ipc", "shuffle.publish"}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One SQL text through StandaloneCluster + BallistaContext under
    CPU-jax, warm: (the job's spans, the whole log, the table)."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.executor.runtime import StandaloneCluster

    d = tmp_path_factory.mktemp("t")
    for part in range(3):
        rows = range(part * 400, (part + 1) * 400)
        pq.write_table(pa.table({
            "k": pa.array([i % 7 for i in rows], type=pa.int64()),
            "v": pa.array([float(i) * 0.5 for i in rows])}),
            str(d / f"part-{part}.parquet"))
    cluster = StandaloneCluster(n_executors=1, config=BallistaConfig(SETTINGS))
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=SETTINGS)
        ctx.register_parquet("t", str(d))
        sql = "select k, sum(v) as s, count(*) as n from t group by k"
        ctx.sql(sql).collect()
        tracing.reset()
        table = ctx.sql(sql).collect()
        time.sleep(0.3)  # the executor's last spans close after the client returns
        log = tracing.spans()
        ctx.close()
    finally:
        cluster.shutdown()
    root = [s for s in log if s.name == "client.collect"]
    assert len(root) == 1
    return [s for s in log if s.job == root[0].job], log, table


def test_a_served_query_yields_every_span_its_plan_can_reach(served):
    mine, log, table = served
    assert sorted(table.column("k").to_pylist()) == list(range(7))
    names = {s.name for s in mine}
    assert SERVED <= names, sorted(SERVED - names)
    assert not {s.name for s in log if s.job is None and s.name in SERVED}
    for s in mine:
        assert s.start_ns <= s.end_ns, s
    tasked = [s for s in mine if s.name in (
        "scheduler.queue", "executor.receive", "executor.task", "executor.setup",
        "executor.execute", "executor.report", "shuffle.write", "shuffle.fetch",
        "runtime.stage", "runtime.launch")]
    assert all(s.stage is not None and s.partition is not None for s in tasked)
    done = [s for s in mine if s.name == "scheduler.status" and s.attrs.get("job_done")]
    assert len(done) == 1 and done[0].attrs["notified_ns"] >= done[0].start_ns
    wait = next(s for s in mine if s.name == "client.wait")
    assert wait.attrs["via"] in ("push", "poll")
    launch = next(s for s in mine if s.name == "runtime.launch")
    assert launch.attrs["program"] and launch.attrs.get("tier") in (None, "memory", "disk", "trace")


def test_a_copy_stands_apart_from_the_wait_for_its_program(served):
    """Every `runtime.readback` syncs its producer first, as its child
    `runtime.device_wait`: the copy's self time holds no program's run."""
    mine, _log, _table = served
    copies = [s for s in mine if s.name == "runtime.readback"]
    waits = {s.parent: s for s in mine if s.name == "runtime.device_wait"}
    assert copies and all(c.id in waits for c in copies)
    for c in copies:
        w = waits[c.id]
        assert c.start_ns <= w.start_ns <= w.end_ns <= c.end_ns
        assert c.attrs["bytes"] > 0


def test_a_fetch_lies_where_the_read_happened(served):
    """`shuffle.fetch` is a real interval of its task: inside the task's
    `executor.execute`, on the thread that read (a pool thread for a piece
    read whole, the task's own for a streamed one, which says `streamed`)."""
    mine, _log, _table = served
    by_id = {s.id: s for s in mine}
    fetches = [s for s in mine if s.name == "shuffle.fetch"]
    assert fetches
    for f in fetches:
        assert f.attrs["via"] in ("local", "flight", "storage", "resident")
        assert f.attrs["bytes"] > 0 and f.seconds > 0
        up = by_id[f.parent]
        while up.name != "executor.execute":
            up = by_id[up.parent]
        assert up.start_ns <= f.start_ns and f.end_ns <= up.end_ns
        assert (f.tid == up.tid) == bool(f.attrs.get("streamed"))


def test_the_request_s_spans_lie_inside_client_collect(served):
    mine, _log, _table = served
    root = next(s for s in mine if s.name == "client.collect")
    # what the executor closes after its last status left may end later
    late = {"executor.task", "executor.report", "scheduler.status", "scheduler.assign"}
    for s in mine:
        assert s.start_ns >= root.start_ns, s
        if s.name not in late:
            assert s.end_ns <= root.end_ns, s
    # a q1 at SF=10 closes about a hundred: three partitions stay well under.
    # Beside them, one span an operator's pull and a shuffle write's steps:
    # 57 on this plan (30 pulls of six tasks, 27 steps)
    steps = [s for s in mine if s.name in STEPS]
    assert len(mine) - len(steps) < 120, len(mine) - len(steps)
    assert len(steps) < 70, len(steps)


def test_untraced_share_of_a_served_query_is_small(served):
    mine, _log, _table = served
    root = next(s for s in mine if s.name == "client.collect")
    work = [s for s in mine if s.name != "client.wait"]
    covered = tracing.covered_s(tracing.leaves(work), [(root.start_ns, root.end_ns)])
    share = 100.0 * (1.0 - covered / root.seconds)
    # read 7.9 % to 41.8 % over ten runs on the sandbox's CPU (PR 26): a task's
    # own host work outside any leaf, and gRPC between the process's threads
    assert 0.0 <= share < 75.0, share


def test_spans_lie_on_a_host_plane_of_a_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.span("client.collect", job="jq"):
            with tracing.span("runtime.readback", stage=1, partition=2):
                np.asarray(jax.numpy.arange(8) + 1)
    finally:
        jax.profiler.stop_trace()
    files = []
    for base, _dirs, names in os.walk(tmp_path):
        files += [os.path.join(base, n) for n in names if n.endswith(".xplane.pb")]
    assert len(files) == 1
    found = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("client.collect", "runtime.readback"):
                    found[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"client.collect", "runtime.readback"}
    outer, inner = found["client.collect"], found["runtime.readback"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    assert outer[2].get("job") == "jq" and str(inner[2].get("partition")) == "2"


def test_wrap_step_names_the_module_and_keeps_the_aot_key(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.tree_util import tree_flatten

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.ops import aotcache

    class Owner:
        aot_key = "stage-key"

    def core(n, x):
        return x * n

    aotcache.reset(clear_disk_dir=True)
    base = str(tmp_path / "aot")
    aotcache.configure(BallistaConfig({"ballista.tpu.aot_cache": base}))
    try:
        step = aotcache.wrap_step(Owner(), "factagg_topk", core, static_argnums=(0,))
        x = jnp.arange(4, dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(step(3, x)), [0, 3, 6, 9])
        launch = [s for s in tracing.spans() if s.name == "runtime.launch"]
        assert [s.attrs for s in launch] == [{"program": "factagg_topk", "tier": "trace"}]
        # the key as the parent commit computes it: the name is part of `sig`
        # already, and nothing else of the naming may reach it
        leaves, treedef = tree_flatten((x,))
        sig = (f"factagg_topk|s{[(0, repr(3))]!r}|{treedef}"
               f"|{[(tuple(a.shape), str(a.dtype)) for a in leaves]!r}")
        want = hashlib.sha256(
            f"{aotcache.fingerprint()}|stage-key|{sig}".encode()).hexdigest()
        assert [e["key"] for e in aotcache.manifest_entries(base)] == [want]
    finally:
        aotcache.reset(clear_disk_dir=True)
        aotcache.configure(BallistaConfig({}))
    text = jax.jit(aotcache._named("factagg_topk", core), static_argnums=(0,)).lower(
        3, x).as_text(debug_info=True)
    assert "module @jit_factagg_topk" in text and "factagg_topk/" in text


# -- the host's steps inside a task -----------------------------------------------

def _scan(n_batches=2, rows=4, partitions=1):
    from ballista_tpu.datasource import MemoryTableSource
    from ballista_tpu.physical.scan import MemoryScanExec

    t = pa.Table.from_batches([pa.record_batch({
        "k": pa.array([(b * rows + i) % 3 for i in range(rows)], type=pa.int64()),
        "v": pa.array([float(b * rows + i) for i in range(rows)])})
        for b in range(n_batches)])
    src = MemoryTableSource(t.schema, [t.to_batches()] + [[] for _ in range(partitions - 1)])
    return MemoryScanExec(src)


def _three_operators():
    from ballista_tpu.physical.basic import FilterExec, ProjectionExec
    from ballista_tpu.physical.expr import ColumnExpr, LiteralExpr

    class AtLeast:  # v >= 1.0, so the first batch loses a row and none is empty
        def evaluate(self, batch):
            import pyarrow.compute as pc

            return pc.greater_equal(batch.column(1), LiteralExpr(1.0, pa.float64()).scalar())

    scan = _scan()
    return ProjectionExec(FilterExec(scan, AtLeast()), [(ColumnExpr("v", 1), "v")])


def _pulls(log):
    return [(s.attrs["op"], s.attrs["rows"]) for s in log if s.name == "executor.operator"]


def test_three_operators_open_one_span_a_pull_the_last_pull_included():
    from ballista_tpu.physical.plan import TaskContext

    with tracing.span("executor.execute", job="j", stage=3, partition=0):
        out = list(_three_operators().execute(0, TaskContext()))
    assert [b.num_rows for b in out] == [3, 4]
    log = tracing.spans()
    pulls = _pulls(log)
    # two batches and the pull that ends the iterator, for each of the three
    assert sorted(pulls) == sorted([("MemoryScanExec", 4), ("MemoryScanExec", 4),
                                    ("MemoryScanExec", 0), ("FilterExec", 3),
                                    ("FilterExec", 4), ("FilterExec", 0),
                                    ("ProjectionExec", 3), ("ProjectionExec", 4),
                                    ("ProjectionExec", 0)])
    by_id = {s.id: s for s in log}
    for s in log:
        if s.name != "executor.operator":
            continue
        assert (s.job, s.stage, s.partition) == ("j", 3, 0)  # from the thread's stack
        parent = by_id[s.parent]
        want = {"ProjectionExec": "executor.execute", "FilterExec": "ProjectionExec",
                "MemoryScanExec": "FilterExec"}[s.attrs["op"]]
        assert parent.attrs.get("op", parent.name) == want
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns


def test_no_span_is_open_across_a_yield():
    from ballista_tpu.physical.plan import TaskContext

    with tracing.span("consumer", job="j") as consumer:
        it = _three_operators().execute(0, TaskContext())
        stack = list(tracing._local.stack)
        for _ in range(2):
            next(it)
            assert tracing._local.stack == stack and tracing.current() is consumer
        with pytest.raises(StopIteration):
            next(it)
        assert tracing._local.stack == stack
    assert tracing.current() is None
    # an iterator left half read leaves no span open either
    it = _three_operators().execute(0, TaskContext())
    next(it)
    assert tracing.current() is None
    it.close()


def test_an_operators_self_time_excludes_its_child():
    from ballista_tpu.physical.plan import ExecutionPlan, TaskContext

    class Slow(ExecutionPlan):
        def execute(self, partition, ctx):
            for i in range(2):
                time.sleep(0.03)
                yield pa.record_batch({"x": [i]})

    class Thin(ExecutionPlan):
        def __init__(self, child):
            self.child = child

        def execute(self, partition, ctx):
            for b in self.child.execute(partition, ctx):
                time.sleep(0.005)
                yield b

    assert len(list(Thin(Slow()).execute(0, TaskContext()))) == 2
    log = tracing.spans()
    children = {}
    for s in log:
        children.setdefault(s.parent, []).append(s)

    def own_ms(op):
        spans = [s for s in log if s.attrs.get("op") == op]
        return 1e3 * sum(s.seconds - tracing.covered_s(children.get(s.id, ()),
                                                        [(s.start_ns, s.end_ns)])
                         for s in spans)

    assert 60 <= own_ms("Slow") < 200
    assert 10 <= own_ms("Thin") < 40  # its own two sleeps, none of its child's


def _join_inputs():
    from ballista_tpu.datasource import MemoryTableSource
    from ballista_tpu.physical.scan import MemoryScanExec

    build = pa.table({"bk": pa.array([1, 2, 2, 5], type=pa.int64()),
                      "bv": pa.array([10.0, 20.0, 21.0, 50.0])})
    probe = pa.table({"pk": pa.array([2, 3, 5, 5, 7], type=pa.int64()),
                      "pv": pa.array([1.0, 2.0, 3.0, 4.0, 5.0])})
    return (MemoryScanExec(MemoryTableSource.from_table(build)),
            MemoryScanExec(MemoryTableSource.from_table(probe)))


def test_a_left_join_on_the_host_opens_one_host_join_and_a_device_join_none():
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.logical.plan import JoinType
    from ballista_tpu.ops import runtime
    from ballista_tpu.physical.join import HashJoinExec
    from ballista_tpu.physical.plan import TaskContext, collect_partition

    left, right = _join_inputs()
    out = collect_partition(HashJoinExec(left, right, [("bk", "pk")], JoinType.LEFT),
                            0, TaskContext())
    joins = [s for s in tracing.spans() if s.name == "executor.host_join"]
    assert len(joins) == 1 and out.num_rows == 5  # 2 twice, 5 twice, 1 unmatched
    assert joins[0].attrs == {"type": "left", "build_rows": 4, "probe_rows": 5,
                              "declined": False, "out_rows": out.num_rows}
    by_id = {s.id: s for s in tracing.spans()}
    assert by_id[joins[0].parent].attrs["op"] == "HashJoinExec"

    tracing.reset()
    runtime.join_path_stats(reset=True)
    ctx = TaskContext(config=BallistaConfig({"ballista.executor.backend": "tpu"}))
    left, right = _join_inputs()
    inner = collect_partition(HashJoinExec(left, right, [("bk", "pk")], JoinType.INNER), 0, ctx)
    assert inner.num_rows == 4
    assert runtime.join_path_stats(reset=True)["paths"].get("device", 0) >= 1
    assert not [s for s in tracing.spans() if s.name == "executor.host_join"]
    assert [s for s in tracing.spans() if s.name == "runtime.join"]


@pytest.mark.parametrize("hashed", [True, False], ids=["hash", "pass_through"])
def test_a_shuffle_write_opens_its_steps_under_shuffle_write(tmp_path, hashed):
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.distributed.stages import ShuffleWriterExec
    from ballista_tpu.physical.expr import ColumnExpr
    from ballista_tpu.physical.plan import Partitioning, TaskContext

    part = Partitioning.hash([ColumnExpr("k", 0)], 3) if hashed else None
    writer = ShuffleWriterExec("jw", 1, _scan(n_batches=3), part)
    ctx = TaskContext(config=BallistaConfig({}), work_dir=str(tmp_path), job_id="jw",
                      executor_id="e1")  # an executor's task: the resident exchange too
    stats = writer.execute_shuffle_write(0, ctx)
    log = tracing.spans()
    (write,) = [s for s in log if s.name == "shuffle.write"]
    steps = [s for s in log if s.name.startswith("shuffle.") and s is not write]
    assert steps and all(s.parent == write.id for s in steps)
    assert all((s.job, s.stage, s.partition) == ("jw", 1, 0) for s in steps)
    names = {s.name for s in steps}
    assert names == ({"shuffle.partition", "shuffle.ipc", "shuffle.publish"} if hashed
                     else {"shuffle.ipc", "shuffle.publish"})  # nothing to partition
    ipc = [s for s in steps if s.name == "shuffle.ipc"]
    assert sum(s.attrs["bytes"] for s in ipc) == write.attrs["bytes"] == stats.num_bytes > 0
    # each batch's writes, the opening, and the close with the publish
    assert len(ipc) == 3 + 2
    if hashed:
        cuts = [s for s in steps if s.name == "shuffle.partition"]
        assert [s.attrs["rows"] for s in cuts] == [4, 4, 4]
        assert all(1 <= s.attrs["pieces"] <= 3 for s in cuts)
    (publish,) = [s for s in steps if s.name == "shuffle.publish"]
    assert publish.attrs["kept"] is True
    # the plan's pulls lie under the write too, none inside a step
    pulls = [s for s in log if s.name == "executor.operator"]
    assert len(pulls) == 4 and all(s.parent == write.id for s in pulls)


def test_an_annotation_carries_the_span_id(monkeypatch):
    import types

    made = []

    class Annotation:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    Annotation.is_enabled = staticmethod(lambda: True)
    stub = types.ModuleType("jax")
    stub.profiler = types.SimpleNamespace(TraceAnnotation=Annotation)
    monkeypatch.setattr(tracing, "_annotate", None)
    monkeypatch.setitem(sys.modules, "jax", stub)
    with tracing.span("executor.task", job="j", stage=2) as outer:
        with tracing.span("executor.operator", op="X") as inner:
            pass
    assert made == [("executor.task", {"span": outer.id, "job": "j", "stage": 2}),
                    ("executor.operator", {"span": inner.id, "job": "j", "stage": 2})]


def test_a_served_query_yields_the_host_steps_of_its_tasks(served):
    mine, _log, _table = served
    names = {s.name for s in mine}
    assert STEPS <= names, sorted(STEPS - names)
    for s in mine:
        if s.name in STEPS:
            assert s.stage is not None and s.partition is not None, s
    by_id = {s.id: s for s in mine}
    for s in mine:
        if s.name in ("shuffle.partition", "shuffle.ipc", "shuffle.publish"):
            assert by_id[s.parent].name == "shuffle.write"
        if s.name == "runtime.stage":  # the engines are an operator's children
            assert by_id[s.parent].name == "executor.operator"


# -- every span on the device's clock (dev/span_report.py) ---------------------------

def test_an_idle_gap_goes_to_the_innermost_span_a_record_included():
    """A synthetic trace and log with a known offset between the clocks:
    two annotated spans give it, and the `record()`, never annotated, still
    holds the gap it lies over."""
    from dev import span_report

    ms, off = 1_000_000, 7_000_000_000
    log = [_span("executor.task", 0, 100 * ms, 1),
           _span("executor.operator", 10 * ms, 40 * ms, 2, parent=1),
           _span("scheduler.queue", 50 * ms, 70 * ms, 3)]  # a record(): no annotation
    annotated = {1: off + 300, 2: 10 * ms + off - 300}  # the trace's starts, 0.3 us apart
    got, residuals = span_report.clock_offset(annotated, log)
    assert got == off and sorted(abs(r) for r in residuals) == [300, 300]
    spans = [(s.start_ns + got, s.end_ns + got, s.name) for s in log]
    busy = [(off, off + 5 * ms), (off + 45 * ms, off + 50 * ms), (off + 70 * ms, off + 100 * ms)]
    queries = [(off, off + 100 * ms, "bench.query:q1")]
    by_span, by_text, gaps = span_report.attribute_idle(
        busy, (off, off + 100 * ms), queries, spans)
    assert gaps == [(off + 5 * ms, off + 45 * ms), (off + 50 * ms, off + 70 * ms)]
    want = {"executor.task": 0.010, "executor.operator": 0.030, "scheduler.queue": 0.020}
    assert dict(by_span) == pytest.approx(want)
    assert {k: dict(v) for k, v in by_text.items()} == {"q1": pytest.approx(want)}


def test_a_trace_gives_back_each_annotated_span_by_id(tmp_path):
    import jax

    from dev import span_report

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(20):
            with tracing.span("executor.task", job="jc"):
                with tracing.span("shuffle.ipc"):
                    np.asarray(jax.numpy.arange(4) + i)
    finally:
        jax.profiler.stop_trace()
    tracing.record("scheduler.queue", 1, 2, job="jc")  # after the session: no annotation
    log = tracing.spans()
    files = [os.path.join(b, n) for b, _d, ns in os.walk(tmp_path) for n in ns
             if n.endswith(".xplane.pb")]
    trace = span_report.read_xplane(files[0])
    assert set(trace["annotated"]) == {s.id for s in log if s.name != "scheduler.queue"}
    off, residuals = span_report.clock_offset(trace["annotated"], log)
    assert len(residuals) == 40
    assert span_report._quantile([abs(r) for r in residuals], 0.5) < 50_000  # ns
