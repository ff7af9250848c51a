"""The five readers of the host's steps inside a task (`executor.operator`,
`executor.host_join`, `shuffle.partition`, `shuffle.ipc`, `shuffle.publish`):
0.0 on a window whose log has none of their spans (a program from before
them), the sum per completed query with them."""

import pytest

import run

MS = 1_000_000

# (metric, span, ms of the span, ms of a child, ms the metric reads of it)
CASES = [
    ("executor.operators_ms", "executor.operator", 10, 6, 4),  # self time
    ("engines.host_join_ms", "executor.host_join", 10, 0, 10),
    ("shuffle.partition_ms", "shuffle.partition", 10, 0, 10),
    ("shuffle.ipc_ms", "shuffle.ipc", 10, 0, 10),
    ("shuffle.publish_ms", "shuffle.publish", 10, 4, 10),  # its uploads included
]


def test_the_five_are_declared_together_with_a_reader_each():
    declared = run._json(run.os.path.join(run.ROOT, "BENCHMARK.json"))["per_layer"]
    names = [m["name"] for m in declared]
    at = names.index(CASES[0][0])  # appended together; later entries may follow
    assert names[at:at + 5] == [c[0] for c in CASES]
    readers = run.layer_readers()
    for m in declared[at:at + 5]:
        assert (m["source"], m["better"], m["moves"]) == (
            "program_span", "lower", "queries_per_min")
        assert "workloads" not in m and readers[m["name"]].UNIT == m["unit"] == "ms/query"
        assert readers[m["name"]].LAYER == m["layer"]


def _window(tracing, names_and_ms):
    tracing.reset()
    with tracing.span("client.collect", job="a") as root:
        t0 = root.start_ns
    for name, ms, child_ms in names_and_ms:
        s = tracing.record(name, t0, t0 + ms * MS, job="a", stage=1, partition=0)
        if child_ms:
            tracing.record("runtime.stage", t0, t0 + child_ms * MS, parent=s)
    tracing.reset()  # the log just written is now the drained one


@pytest.mark.parametrize("metric,span,ms,child_ms,reads", CASES, ids=[c[0] for c in CASES])
def test_a_reader_reads_nothing_without_its_span_and_the_sum_with_it(
        metric, span, ms, child_ms, reads):
    from ballista_tpu.utils import tracing

    read = run.layer_readers()[metric].read
    facts = {"window": {"completed": 2}}
    _window(tracing, [("shuffle.write", 30, 0)])
    assert read(facts) == 0.0
    _window(tracing, [(span, ms, child_ms), (span, ms, child_ms), ("shuffle.write", 30, 0)])
    assert read(facts) == pytest.approx(2 * reads / 2)  # two spans, two queries
    tracing.reset()
