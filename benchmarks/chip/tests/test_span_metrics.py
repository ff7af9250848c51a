"""The eleven per-layer metrics that read the program's spans
(span_log.py and its readers under layer_metrics/): a traced rehearsal of
each cell reports all of them, and the three cases span_log tells apart."""

import json
import math

import pytest

import lastline
import run
import span_log

MARK = "REHEARSAL under CPU-jax, no result and no device metric: "
SPAN_METRICS = [
    "client.submit_ms", "client.notify_ms", "client.fetch_ms",
    "scheduler.plan_ms", "scheduler.queue_ms", "executor.dispatch_ms",
    "executor.report_ms", "shuffle.write_ms", "shuffle.fetch_ms",
    "runtime.readback_ms", "host.untraced_share",
]


def test_the_eleven_are_declared_with_a_reader_each():
    declared = {m["name"]: m for m in run._json(
        run.os.path.join(run.ROOT, "BENCHMARK.json"))["per_layer"]}
    readers = run.layer_readers()
    for name in SPAN_METRICS:
        assert declared[name]["source"] == "program_span" and name in readers
        assert "workloads" not in declared[name]
    assert list(declared)[-11:] == SPAN_METRICS  # appended, nothing moved


@pytest.mark.parametrize("workload", ["tpch_sf10_1chip.scan_agg",
                                      "tpch_sf10_1chip.join_topk"])
def test_a_traced_rehearsal_reports_all_eleven(capsys, workload):
    args = run.parse(["--workload", workload, "--seed", "2147483659",
                      "--seconds", "2", "--trace", "1"])
    assert run.execute(args, rehearsal={"scale": 0.01}) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith(MARK)
    line = json.loads(last[len(MARK):])
    spec = run.load_cell(workload)
    expected = [(m["name"], m["unit"]) for m in spec["per_layer"]]
    assert lastline.validate(line, expected, True) == []
    for name in SPAN_METRICS:
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0.0, (name, value)
    assert 0.0 <= line["metrics"]["host.untraced_share"]["value"] <= 100.0
    # every layer the cell's plans reach did some work
    for name in ("client.submit_ms", "client.fetch_ms", "scheduler.plan_ms",
                 "scheduler.queue_ms", "executor.dispatch_ms", "shuffle.write_ms"):
        assert line["metrics"][name]["value"] > 0.0, name


def _spans(tracing, names):
    tracing.reset()
    for name in names:
        with tracing.span(name, job="j"):
            pass
    tracing.reset()  # the log just written is now the drained one


def test_a_window_with_no_client_collect_span_reads_none_everywhere():
    from ballista_tpu.utils import tracing

    _spans(tracing, ["scheduler.plan", "executor.task"])
    facts = {"window": {"completed": 3}}
    readers = run.layer_readers()
    assert [readers[n].read(facts) for n in SPAN_METRICS] == [None] * 11
    tracing.reset()


def test_a_ring_that_dropped_spans_reads_none_everywhere(monkeypatch):
    from ballista_tpu.utils import tracing

    monkeypatch.setattr(tracing, "RING", 4)
    tracing.reset()
    _spans(tracing, ["client.collect"] * 6)
    assert tracing.drained()["counters"]["tracing.dropped"] == 2
    facts = {"window": {"completed": 6}}
    readers = run.layer_readers()
    assert [readers[n].read(facts) for n in SPAN_METRICS] == [None] * 11
    monkeypatch.undo()
    tracing.reset()


def test_a_program_without_the_recorder_reads_nothing_traced(monkeypatch):
    """The parent of the PR that brought the spans, under this benchmark's
    files: its traced line has to pass lastline.validate all the same."""
    from ballista_tpu.utils import tracing

    monkeypatch.delattr(tracing, "drained")
    facts = {"window": {"completed": 3}}
    readers = run.layer_readers()
    got = {n: readers[n].read(facts) for n in SPAN_METRICS}
    assert got.pop("host.untraced_share") == 100.0
    assert set(got.values()) == {0.0}
    assert span_log.window({"window": {"completed": 0}}) is None


def test_sums_are_per_completed_query_and_a_layer_without_work_reads_zero():
    from ballista_tpu.utils import tracing

    tracing.reset()
    with tracing.span("client.collect", job="a") as root:
        t0 = root.start_ns
    tracing.record("client.submit", t0, t0 + 4_000_000, job="a")
    tracing.record("client.wait", t0, t0 + 30_000_000, job="a")
    tracing.record("scheduler.status", t0, t0 + 20_000_000, job="a",
                   job_done=True, notified_ns=t0 + 18_000_000)
    tracing.reset()
    facts = {"window": {"completed": 2}}
    readers = run.layer_readers()
    assert readers["client.submit_ms"].read(facts) == pytest.approx(2.0)
    assert readers["client.notify_ms"].read(facts) == pytest.approx(12.0)
    assert readers["shuffle.fetch_ms"].read(facts) == 0.0
    tracing.reset()


def test_the_wait_for_the_device_is_no_part_of_the_copy():
    from ballista_tpu.utils import tracing

    tracing.reset()
    with tracing.span("client.collect", job="a") as root:
        t0 = root.start_ns
    copy = tracing.record("runtime.readback", t0, t0 + 10_000_000, job="a")
    tracing.record("runtime.device_wait", t0, t0 + 7_000_000, parent=copy)
    tracing.record("runtime.to_arrow", t0 + 10_000_000, t0 + 15_000_000, job="a")
    tracing.reset()
    read = run.layer_readers()["runtime.readback_ms"].read
    assert read({"window": {"completed": 1}}) == pytest.approx(3.0 + 5.0)
    tracing.reset()
