"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (a rehearsal under CPU-jax at a tiny scale), an
answer is altered where it is produced, and `correct` has to come out false.
A one-chip served query path can have only that fault of the contract's list
(no training state, no batch mean, no exchange between chips)."""

import json

import pyarrow as pa
import pytest

import run

MARK = "REHEARSAL under CPU-jax, no result and no device metric: "


def _drive(capsys, workload="tpch_sf10_1chip.scan_agg", trace=0):
    args = run.parse(["--workload", workload, "--seed", "2147483659",
                      "--seconds", "2", "--trace", str(trace)])
    assert run.execute(args, rehearsal={"scale": 0.01}) == 0
    out = capsys.readouterr()
    last = out.out.strip().splitlines()[-1]
    assert last.startswith(MARK)
    return json.loads(last[len(MARK):]), out.err


def _tamper(monkeypatch, alter, after=7):
    """Every collect() from call `after` on (past the six of the warm-up)
    returns alter(table)."""
    from ballista_tpu.client.context import BallistaDataFrame

    real = BallistaDataFrame.collect
    calls = {"n": 0}

    def collect(self):
        table = real(self)
        calls["n"] += 1
        return alter(table) if calls["n"] >= after else table

    monkeypatch.setattr(BallistaDataFrame, "collect", collect)


def _scale_last_float(table: pa.Table) -> pa.Table:
    i = max(i for i, f in enumerate(table.schema) if pa.types.is_floating(f.type))
    col = pa.compute.multiply(table.column(i), 1.0 + 1e-3)
    return table.set_column(i, table.schema[i].name, col)


def _drop_a_row(table: pa.Table) -> pa.Table:
    return table.slice(0, table.num_rows - 1) if table.num_rows > 1 else table


def test_a_sound_run_is_correct_and_says_what_it_compared(capsys):
    line, err = _drive(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "compared"
    assert line["compared"]["rel_err_max"]["value"] < line["compared"]["rel_err_max"]["limit"]
    tail = err.strip().splitlines()[-3:]
    assert all(t.startswith("compared ") and "limit=" in t for t in tail)


@pytest.mark.parametrize("alter,number", [
    (_scale_last_float, "rel_err_max"),
    (_drop_a_row, "exact_mismatches"),
])
def test_an_altered_answer_is_not_correct(capsys, monkeypatch, alter, number):
    _tamper(monkeypatch, alter)
    line, _err = _drive(capsys)
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"]


def test_a_query_that_fails_is_counted_and_not_correct(capsys, monkeypatch):
    def boom(table):
        raise RuntimeError("planted")

    _tamper(monkeypatch, boom, after=8)
    line, _err = _drive(capsys)
    assert line["correct"] is False and line["failed"] >= 1
    assert line["compared"]["answers_missing"]["value"] == line["failed"]


def test_the_traced_line_passes_the_same_validator(capsys):
    line, _err = _drive(capsys, trace=1)
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert "programs.hbm_roofline" in line["metrics"]
    assert "queries_per_min" not in line["metrics"]
