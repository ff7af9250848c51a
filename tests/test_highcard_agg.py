"""TPC-H's aggregate subqueries (q2, q15, q20) as the benchmark's cell
`tpch_sf10_subquery_1chip.highcard_agg` sends them: the cell's own generator,
texts, reference and comparison, through the served path under CPU-jax at a
tiny scale; the span a device join reports, the counter of the rows a device
aggregate hands the host, and the two per-layer readers over them."""

import os
import pathlib
import sys
import time

import pytest

CHIP = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import compare  # noqa: E402
import run  # noqa: E402
from data import tpch8  # noqa: E402
from reference import tpch_subquery  # noqa: E402
from reference.tpch import _day, load  # noqa: E402

from ballista_tpu.utils import tracing  # noqa: E402

CELL = "tpch_sf10_subquery_1chip.highcard_agg"
TEXTS = ["q2", "q15", "q20"]
SCALE = 0.02


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def data(cell, tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch8")
    tables = sorted({t for text in cell["traffic"]["texts"] for t in text["reads"]})
    config = {**cell["config"], "scale": SCALE}
    return str(d), tpch8.generate(str(d), config, tables, 2**31 + 11, 1)


@pytest.fixture(scope="module")
def served(cell, data):
    """Every text of the cell twice through StandaloneCluster +
    BallistaContext: {text: {"table", "cold", "warm"}}, a log being the
    spans and counters of one execution."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.executor.runtime import StandaloneCluster
    from ballista_tpu.ops import runtime

    data_dir, rows = data
    settings = dict(cell["config"]["settings"])
    cluster = StandaloneCluster(n_executors=1, config=BallistaConfig(settings))
    out = {}
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        for table in rows:
            ctx.register_parquet(table, os.path.join(data_dir, table))
        for text in cell["traffic"]["texts"]:
            sql, logs = run._sql(text["sql"]), []
            for _ in range(2):
                tracing.reset()
                runtime.join_path_stats(reset=True)
                table = ctx.sql(sql).collect()
                time.sleep(0.2)  # the executor's last spans close after the client returns
                logs.append({"spans": tracing.spans(), "counters": tracing.counters(),
                             "join_paths": runtime.join_path_stats(reset=True)["paths"]})
            out[text["name"]] = {"table": table, "cold": logs[0], "warm": logs[1]}
        ctx.close()
    finally:
        cluster.shutdown()
    tracing.reset()
    return out


def test_the_cell_names_the_three_texts_and_its_own_modules(cell):
    traffic = cell["traffic"]
    assert [t["name"] for t in traffic["texts"]] == TEXTS
    assert cell["config"]["generator"] == "tpch8"
    assert cell["config"]["reference"] == "tpch_subquery"
    assert {t["reference_module"] for t in traffic["texts"]} == {"tpch_subquery"}
    assert cell["config"]["reduced"] == ["scale"] and cell["cell"]["chips"] == 1
    assert (traffic["warmup_rounds"], traffic["trace_rounds"]) == (3, 3)
    assert not any("sort_by" in t for t in traffic["texts"])
    sibling = run.load_cell("tpch_sf10_8tables_1chip.join_deep")["config"]
    for key in ("scale", "files_per_table", "executors", "settings", "fresh_dirs",
                "guarantees", "limits"):
        assert cell["config"][key] == sibling[key], key
    for text in traffic["texts"]:
        chip = (CHIP / "queries" / text["sql"]).read_text()
        assert chip == (CHIP.parent / "tpch" / "queries" / f"{text['name']}.sql").read_text()


@pytest.mark.parametrize("name", TEXTS)
def test_the_served_answer_is_the_reference_s(name, cell, data, served):
    text = next(t for t in cell["traffic"]["texts"] if t["name"] == name)
    want = tpch_subquery.run(text["reference"], data[0], run.reads_of(text))
    assert len(want) > 0
    verdict = compare.compare_window(
        [{"text": name, "table": served[name]["table"]}], {name: want},
        run.sort_keys([text]), 0, cell["config"]["limits"])
    assert verdict["correct"], (verdict["compared"], verdict["notes"])


@pytest.mark.parametrize("name", TEXTS)
def test_a_warm_execution_hands_the_host_nothing(name, served):
    warm = served[name]["warm"]
    assert warm["counters"].get("device.host_fallback", 0) == 0
    assert set(warm["join_paths"]) <= {"device"}
    stages = [sp for sp in warm["spans"] if sp.name == "runtime.stage"]
    assert stages and all(sp.attrs["engine"] for sp in stages)


@pytest.mark.parametrize("name", TEXTS)
def test_a_device_join_is_a_span_with_its_rows(name, served):
    warm = served[name]["warm"]
    joins = [s for s in warm["spans"] if s.name == "runtime.join"]
    probes = [s for s in joins if s.attrs["path"] != "encode"]
    # one probe batch a device join path, and its path is the one counted
    assert probes and len(probes) == sum(warm["join_paths"].values())
    for s in joins:
        assert s.job is not None and s.stage is not None and s.partition is not None
        assert s.attrs["build_rows"] >= 0 and s.attrs["probe_rows"] >= 0
    for s in probes:
        assert s.attrs["path"] == "device" and s.attrs["out_rows"] >= 0
    assert sum(s.attrs["out_rows"] for s in probes) > 0
    # what the join launches, reads back and flattens lies inside its span
    by_id = {s.id: s for s in warm["spans"]}
    inside = {s.name for s in warm["spans"]
              if by_id.get(s.parent) is not None and by_id[s.parent].name == "runtime.join"}
    assert {"runtime.launch", "runtime.readback"} <= inside
    flattened = [s for s in warm["spans"]
                 if s.name == "runtime.to_arrow" and s.attrs.get("engine") == "join"]
    assert all(by_id[s.parent].name == "runtime.join" for s in flattened)


@pytest.mark.parametrize("name", TEXTS)
def test_every_probe_batch_says_how_it_found_its_runs(name, served):
    """ISSUE 34: the texts' keys are single integers of a dense range, so a
    probe reads the position table wherever the range is not long for it;
    the two counters sum to the probe rows of the probe spans."""
    warm = served[name]["warm"]
    probes = [s for s in warm["spans"]
              if s.name == "runtime.join" and s.attrs["path"] != "encode"]
    assert {s.attrs["method"] for s in probes} <= {"table", "search"}
    assert any(s.attrs["method"] == "table" for s in probes)
    for method in ("table", "search"):
        assert warm["counters"].get(f"device.join_{method}_probes", 0) == sum(
            s.attrs["probe_rows"] for s in probes if s.attrs["method"] == method)
    assert all(s.attrs["entries"] >= 1024 for s in probes)


@pytest.mark.parametrize("name", TEXTS)
def test_groups_out_is_the_sum_of_the_stage_results_rows(name, served):
    for log in (served[name]["cold"], served[name]["warm"]):
        handed = [s.attrs["groups"] for s in log["spans"]
                  if s.name == "runtime.to_arrow" and s.attrs.get("engine") != "join"]
        assert handed and log["counters"]["device.groups_out"] == sum(handed)
        assert not any("groups" in s.attrs for s in log["spans"]
                       if s.name == "runtime.to_arrow" and s.attrs.get("engine") == "join")


def test_q20_hands_over_no_fewer_groups_than_the_year_s_pairs(served, data):
    """q20's inner aggregate holds a group a (l_partkey, l_suppkey) pair of
    1994 at least (the partial aggregates of several tasks may each hold a
    pair), and since the key-set link it hands over only those its LEFT
    join can match: what it kept and dropped add up to no fewer than the
    year's pairs, and what reached the host is the kept. q15's whole result
    still reaches the host, a group a supplier, twice."""
    li = load(data[0], {"lineitem": ["l_partkey", "l_suppkey", "l_shipdate"]})["lineitem"]
    days = li.l_shipdate
    year = li[(days >= _day("1994-01-01")) & (days < _day("1995-01-01"))]
    pairs = len(year.drop_duplicates(["l_partkey", "l_suppkey"]))
    assert pairs > 1000
    q20 = served["q20"]["warm"]["counters"]
    kept, dropped = q20["device.keyset_groups_kept"], q20["device.keyset_groups_dropped"]
    assert kept + dropped >= pairs and 0 < kept < pairs / 10
    assert q20["device.groups_out"] <= kept
    quarter = li[(days >= _day("1996-01-01")) & (days < _day("1996-04-01"))]
    assert (served["q15"]["warm"]["counters"]["device.groups_out"]
            >= 2 * quarter.l_suppkey.nunique())


def _window(joins, groups):
    """One query's log, drained: a root, a `runtime.join` per entry of
    `joins` (seconds) and `groups` rows counted as handed over."""
    tracing.reset()
    with tracing.span("client.collect", job="a"):
        pass
    t = tracing.now_ns()
    for seconds in joins:
        tracing.record("runtime.join", t, t + int(seconds * 1e9), job="a", path="device")
    if groups:
        tracing.incr("device.groups_out", groups)
    counters = {k: v for k, v in tracing.counters().items() if k.startswith("device.")}
    tracing.reset()
    return counters


@pytest.mark.parametrize("joins,groups,completed,want_ms,want_groups", [
    ([0.25, 0.75], 9_000_000, 2, 500.0, 4_500_000.0),
    ([], 4, 1, 0.0, 4.0),      # a text with no device join
    ([], 0, 4, 0.0, 0.0),      # the parent's program: no span, no counter
], ids=["joins_and_groups", "no_join", "neither"])
def test_the_two_readers_read_a_window_with_and_without_them(
        joins, groups, completed, want_ms, want_groups):
    readers = run.layer_readers()
    counters = _window(joins, groups)
    facts = {"window": {"completed": completed, "counters": counters}}
    assert readers["engines.join_ms"].read(facts) == pytest.approx(want_ms)
    assert readers["engines.groups_out"].read(facts) == pytest.approx(want_groups)
    tracing.reset()


def test_the_two_readers_are_declared_with_the_layer_they_move(cell):
    declared = {m["name"]: m for m in cell["per_layer"]}
    readers = run.layer_readers()
    for name, unit, source in (("engines.groups_out", "count/query", "program_counter"),
                               ("engines.join_ms", "ms/query", "program_span")):
        r, m = readers[name], declared[name]
        assert (r.NAME, r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["name"], m["unit"], m["layer"], m["moves"], m["source"])
        assert (m["layer"], m["unit"], m["source"], m["better"]) == (
            "device engines", unit, source, "lower")
        assert "workloads" not in m
    # a window that completed nothing, or a recorder with no root span, reads nothing
    tracing.reset()
    tracing.reset()
    nothing = {"window": {"completed": 0, "counters": {}}}
    assert readers["engines.groups_out"].read(nothing) is None
    assert readers["engines.join_ms"].read(nothing) is None
    assert readers["engines.join_ms"].read({"window": {"completed": 2, "counters": {}}}) is None
