"""`executor.plan_decodes` (layer_metrics/executor.plan_decodes.py): the
`executor.setup` spans of the window that ran the plan codec, a query."""

import pytest

import run
import span_log

NAME = "executor.plan_decodes"


def _read(facts):
    return run.layer_readers()[NAME].read(facts)


def _window(tracing, setups):
    """One query's spans: a root and one `executor.setup` per entry of
    `setups`, each with the attributes given."""
    tracing.reset()
    with tracing.span("client.collect", job="a"):
        for attrs in setups:
            with tracing.span("executor.setup", job="a", **attrs):
                pass
    tracing.reset()  # the log just written is now the drained one


def test_it_is_declared_last_with_its_reader():
    declared = run._json(run.os.path.join(run.ROOT, "BENCHMARK.json"))["per_layer"]
    assert declared[-1] == {
        "name": NAME, "unit": "count/query", "better": "lower",
        "source": "program_span", "layer": "Executor", "moves": "queries_per_min"}
    reader = run.layer_readers()[NAME]
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        NAME, "count/query", "Executor", "queries_per_min", "program_span")


@pytest.mark.parametrize("setups,completed,want", [
    # a program from before the attribute: every task decoded
    ([{}] * 17 + [{}] * 9, 2, 13.0),
    # a window of hits: q1's three stages and q6's two
    ([{"decoded": True}] * 3 + [{"decoded": False}] * 14
     + [{"decoded": True}] * 2 + [{"decoded": False}] * 7, 2, 2.5),
    # a set-up that failed before it knew says True: counted
    ([{"decoded": True}, {"decoded": False}, {}], 1, 2.0),
    # no task at all (a cached answer): nothing decoded
    ([], 4, 0.0),
], ids=["no_attribute", "hits", "mixed", "no_tasks"])
def test_it_counts_the_setups_that_did_not_say_false(setups, completed, want):
    from ballista_tpu.utils import tracing

    _window(tracing, setups)
    assert _read({"window": {"completed": completed}}) == pytest.approx(want)
    tracing.reset()


def test_a_broken_recorder_reads_none(monkeypatch):
    from ballista_tpu.utils import tracing

    # no `client.collect` in the window
    tracing.reset()
    with tracing.span("executor.setup", job="a"):
        pass
    tracing.reset()
    assert _read({"window": {"completed": 3}}) is None
    # spans fell out of the ring
    monkeypatch.setattr(tracing, "RING", 4)
    tracing.reset()
    _window(tracing, [{}] * 6)
    assert tracing.drained()["counters"]["tracing.dropped"] == 3
    assert _read({"window": {"completed": 1}}) is None
    monkeypatch.undo()
    # nothing completed
    assert _read({"window": {"completed": 0}}) is None
    tracing.reset()


def test_a_program_without_the_recorder_reads_nothing_traced(monkeypatch):
    from ballista_tpu.utils import tracing

    monkeypatch.delattr(tracing, "drained")
    assert span_log.window({"window": {"completed": 3}}) is span_log.ABSENT
    assert _read({"window": {"completed": 3}}) == 0.0
