"""The validator accepts a good traced and untraced line and refuses each
of the ways PR 22's line was wrong."""

import copy

import pytest

import lastline

E2E = [("queries_per_min", "queries/min"), ("query_p90_s", "s"), ("setup_s", "s")]
LAYER = [("programs.busy_ms", "ms/query"), ("device.idle_share", "%")]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 4873969664}
COMPARED = {"rel_err_max": {"value": 3e-7, "limit": 2e-5}}


def untraced():
    return lastline.build(
        correct=True, attempted=90, failed=0,
        metrics={"queries_per_min": (120.5, "queries/min"),
                 "query_p90_s": (0.61, "s"), "setup_s": (48.2, "s")},
        device=DEVICE, compared=COMPARED)


def traced():
    return lastline.build(
        correct=True, attempted=90, failed=0,
        metrics={"programs.busy_ms": (31.5, "ms/query"),
                 "device.idle_share": (91.2, "%")},
        device={**DEVICE, "window_s": 4.0, "busy_s": 0.35},
        breakdown={"device_ops": [["fusion.1", 0.2]], "idle_gaps": [["idle during q1", 0.1]]},
        compared=COMPARED)


def test_good_lines_pass_and_compared_comes_last():
    assert lastline.validate(untraced(), E2E, trace=False) == []
    assert lastline.validate(traced(), LAYER, trace=True) == []
    assert list(untraced())[-1] == "compared"
    assert list(traced())[-1] == "compared"
    assert "\n" not in lastline.render(traced())


def _break(line, path, value):
    line = copy.deepcopy(line)
    d = line
    for k in path[:-1]:
        d = d[k]
    if value is KeyError:
        del d[path[-1]]
    else:
        d[path[-1]] = value
    return line


@pytest.mark.parametrize("path,value,says", [
    (("metrics", "device.idle_share"), KeyError, "missing"),
    (("device", "busy_s"), 0.0, "busy_s"),
    (("device", "busy_s"), 4.5, "busy_s"),
    (("device", "busy_s"), KeyError, "busy_s"),
    (("device", "window_s"), KeyError, "window_s"),
    (("metrics", "programs.busy_ms", "unit"), "milliseconds/query", "unit"),
    (("metrics", "programs.busy_ms", "unit"), "ms per query", "unit"),
    (("metrics", "programs.busy_ms", "value"), float("nan"), "finite"),
    (("metrics", "programs.busy_ms", "value"), None, "finite"),
    (("metrics", "programs.busy_ms"), 31.5, "value, unit"),
    (("device", "memory_peak_bytes"), None, "memory_peak_bytes"),
    (("device", "count"), 0, "count"),
    (("correct",), "true", "boolean"),
    (("failed",), 91, "failed"),
    (("device",), KeyError, "'device'"),
])
def test_each_fault_of_a_traced_line_is_refused(path, value, says):
    faults = lastline.validate(_break(traced(), path, value), LAYER, trace=True)
    assert faults and any(says in f for f in faults), faults


def test_a_bad_metric_name_is_refused():
    line = traced()
    line["metrics"]["busy ms,per/query"] = {"value": 1.0, "unit": "ms"}
    faults = lastline.validate(line, LAYER, trace=True)
    assert any("busy ms,per/query" in f for f in faults)


def test_an_untraced_line_needs_every_end_to_end_metric():
    line = _break(untraced(), ("metrics", "setup_s"), KeyError)
    assert any("setup_s" in f for f in lastline.validate(line, E2E, trace=False))
    # and does not need busy_s
    assert lastline.validate(untraced(), E2E, trace=False) == []
