"""The deep joins of TPC-H (q5, q7, q8, q9) as the benchmark's cell
`tpch_sf10_8tables_1chip.join_deep` sends them: the cell's own generator,
texts, reference and comparison, through the served path under CPU-jax at a
tiny scale; the span and the counter the dimension side reports, and the two
per-layer readers over them."""

import hashlib
import os
import pathlib
import sys
import time

import numpy as np
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

CHIP = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import compare  # noqa: E402
import run  # noqa: E402
from data import tpch8  # noqa: E402
from reference import tpch_deep  # noqa: E402

from ballista_tpu.utils import tracing  # noqa: E402

CELL = "tpch_sf10_8tables_1chip.join_deep"
TEXTS = ["q5", "q7", "q8", "q9"]
SCALE = 0.01


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


def _generate(out_dir, cell, scale, tables=tpch8.TABLES, workers=1, seed=7):
    config = {**cell["config"], "scale": scale}
    return tpch8.generate(str(out_dir), config, list(tables), seed, workers)


@pytest.fixture(scope="module")
def data(cell, tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch8")
    return str(d), _generate(d, cell, SCALE)


@pytest.fixture(scope="module")
def served(cell, data):
    """Every text of the cell twice through StandaloneCluster +
    BallistaContext: {text: {"table", "cold", "warm", "engines"}}, a log
    being the spans and counters of one execution."""
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
            runtime.routing_stats(reset=True)
            for _ in range(2):
                tracing.reset()
                table = ctx.sql(sql).collect()
                time.sleep(0.2)  # the executor's last spans close after the client returns
                logs.append({"spans": tracing.spans(), "counters": tracing.counters()})
            out[text["name"]] = {
                "table": table, "cold": logs[0], "warm": logs[1],
                "engines": runtime.routing_stats(reset=True)["engines"],
                "fallbacks": sum(log["counters"].get("device.host_fallback", 0)
                                 for log in logs)}
        ctx.close()
    finally:
        cluster.shutdown()
    tracing.reset()
    return out


def test_the_cell_names_the_four_texts_and_its_own_modules(cell):
    assert [t["name"] for t in cell["traffic"]["texts"]] == TEXTS
    assert cell["config"]["generator"] == "tpch8"
    assert {t["reference_module"] for t in cell["traffic"]["texts"]} == {"tpch_deep"}
    assert cell["config"]["reduced"] == ["scale"] and cell["cell"]["chips"] == 1
    for text in cell["traffic"]["texts"]:
        chip = (CHIP / "queries" / text["sql"]).read_text()
        assert chip == (CHIP.parent / "tpch" / "queries" / f"{text['name']}.sql").read_text()


@pytest.mark.parametrize("name", TEXTS)
def test_the_served_answer_is_the_reference_s(name, cell, data, served):
    text = next(t for t in cell["traffic"]["texts"] if t["name"] == name)
    want = tpch_deep.run(text["reference"], data[0], run.reads_of(text))
    assert len(want) > 0
    verdict = compare.compare_window(
        [{"text": name, "table": served[name]["table"]}], {name: want},
        run.sort_keys([text]), 0, cell["config"]["limits"])
    assert verdict["correct"], (verdict["compared"], verdict["notes"])


@pytest.mark.parametrize("name", TEXTS)
def test_a_device_engine_answers_and_the_host_none(name, served):
    s = served[name]
    assert s["engines"].get("device", 0) >= 2 and not s["engines"].get("host")
    assert s["fallbacks"] == 0
    stages = [sp for sp in s["warm"]["spans"] if sp.name == "runtime.stage"]
    assert stages and all(sp.attrs["engine"] for sp in stages)


@pytest.mark.parametrize("name,engine", [
    ("q5", "factagg"), ("q7", "mapped"), ("q8", "mapped"), ("q9", "mapped")])
def test_the_dimension_side_is_a_span_under_its_stage(name, engine, served):
    cold = served[name]["cold"]
    builds = [s for s in cold["spans"] if s.name == "runtime.dim_build"]
    assert builds and {s.attrs["engine"] for s in builds} == {engine}
    by_id = {s.id: s for s in cold["spans"]}
    for s in builds:
        assert s.attrs["attachments"] >= 1 and s.job is not None
        # a child of the stage, on whichever thread the scan is pulled
        up = by_id.get(s.parent)
        while up is not None and up.name != "runtime.stage":
            up = by_id.get(up.parent)
        assert up is not None, s
    assert max(s.attrs.get("rows", 0) for s in builds) > 0


def test_map_rows_counts_the_fact_rows_extended_and_a_warm_query_none(served, data):
    lineitem = data[1]["lineitem"]
    for name in ("q7", "q8", "q9"):
        cold, warm = served[name]["cold"], served[name]["warm"]
        gathers = [s for s in cold["spans"]
                   if s.name == "runtime.dim_build" and "fact_rows" in s.attrs]
        assert cold["counters"]["device.map_rows"] == sum(
            s.attrs["fact_rows"] * s.attrs["attachments"] for s in gathers)
        # the maps are resident: nothing is gathered again
        assert "device.map_rows" not in warm["counters"]
        assert not [s for s in warm["spans"] if s.name == "runtime.dim_build"]
    # q9 joins on no filter of the fact: every line, five attachments
    assert served["q9"]["cold"]["counters"]["device.map_rows"] == 5 * lineitem
    # q5's static supplier map is gathered once per line; its rank map (the
    # coupling value at each order rank) is built by the cold query and kept
    # with the prepared partition: a warm q5 still opens the span, around two
    # reads of what is kept
    cold, warm = served["q5"]["cold"], served["q5"]["warm"]
    assert cold["counters"]["device.map_rows"] == lineitem
    assert cold["counters"]["device.rank_map_build"] == 1
    builds = [s for s in warm["spans"] if s.name == "runtime.dim_build"]
    assert builds and all(s.attrs["cached"] is True for s in builds)
    assert warm["counters"]["device.rank_map_hit"] == 1
    assert "device.rank_map_build" not in warm["counters"]


@pytest.mark.parametrize("name", TEXTS)
def test_every_mapped_row_is_answered_one_of_the_two_ways(name, served):
    """PR 32: by position table or by a search of sorted keys; q5's supplier
    map (ops/factagg.py) still searches, and a warm query counts neither."""
    cold, warm = served[name]["cold"]["counters"], served[name]["warm"]["counters"]
    assert (cold.get("device.map_dense_rows", 0) + cold.get("device.map_sorted_rows", 0)
            == cold["device.map_rows"])
    assert ("device.map_dense_rows" in cold) == (name != "q5")
    assert "device.map_dense_rows" not in warm and "device.map_sorted_rows" not in warm
    gathers = [s for s in served[name]["cold"]["spans"]
               if s.name == "runtime.dim_build" and s.attrs.get("engine") == "mapped"
               and "fact_rows" in s.attrs]
    assert cold.get("device.map_dense_rows", 0) == sum(
        s.attrs["fact_rows"] * s.attrs["dense"] for s in gathers)


def _window(builds, launches):
    """One query's log, drained: a root, `launches` programs and a
    `runtime.dim_build` per entry of `builds` (seconds)."""
    tracing.reset()
    with tracing.span("client.collect", job="a"):
        with tracing.span("runtime.stage", job="a"):
            for _ in range(launches):
                with tracing.span("runtime.launch", job="a"):
                    pass
    t = tracing.now_ns()
    for seconds in builds:
        tracing.record("runtime.dim_build", t, t + int(seconds * 1e9), job="a",
                       engine="mapped")
    tracing.reset()


@pytest.mark.parametrize("builds,launches,completed,want_ms,want_launches", [
    ([0.25, 0.75], 8, 2, 500.0, 4.0),
    ([], 3, 1, 0.0, 3.0),      # the maps are resident: a number, and it is 0
    ([], 0, 4, 0.0, 0.0),      # a window of host answers
], ids=["with_builds", "resident", "no_device_work"])
def test_the_two_readers_read_a_window_with_and_without_the_spans(
        builds, launches, completed, want_ms, want_launches):
    readers = run.layer_readers()
    _window(builds, launches)
    facts = {"window": {"completed": completed}}
    assert readers["engines.dim_build_ms"].read(facts) == pytest.approx(want_ms)
    assert readers["runtime.launches"].read(facts) == pytest.approx(want_launches)
    tracing.reset()


def test_the_two_readers_are_declared_with_the_layers_they_move(cell):
    declared = {m["name"]: m for m in cell["per_layer"]}
    readers = run.layer_readers()
    for name, layer, unit in (("engines.dim_build_ms", "device engines", "ms/query"),
                              ("runtime.launches", "device runtime", "count/query")):
        r, m = readers[name], declared[name]
        assert (r.NAME, r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["name"], m["unit"], m["layer"], m["moves"], m["source"])
        assert (m["layer"], m["unit"], m["better"]) == (layer, unit, "lower")
        assert "workloads" not in m
    # a broken recorder (no root span in the window) reads nothing
    tracing.reset()
    tracing.reset()
    assert readers["runtime.launches"].read({"window": {"completed": 2}}) is None
    assert readers["engines.dim_build_ms"].read({"window": {"completed": 2}}) is None


# -- the generator -------------------------------------------------------------

@pytest.mark.parametrize("scale", [0.01, 0.05])
def test_row_counts_are_the_specification_s(scale, cell, tmp_path):
    small = [t for t in tpch8.TABLES if t != "lineitem"]
    rows = _generate(tmp_path, cell, scale, tables=small)
    assert rows == {
        "part": int(200_000 * scale), "supplier": int(10_000 * scale),
        "partsupp": int(800_000 * scale), "customer": int(150_000 * scale),
        "orders": int(1_500_000 * scale), "nation": 25, "region": 5}
    for table, n in rows.items():
        assert pq.read_table(str(tmp_path / table)).num_rows == n


def test_every_line_s_part_and_supplier_is_a_row_of_partsupp(data):
    d, _rows = data
    li = pq.read_table(os.path.join(d, "lineitem"), columns=["l_partkey", "l_suppkey"])
    ps = pq.read_table(os.path.join(d, "partsupp"), columns=["ps_partkey", "ps_suppkey"])
    n_supp = pq.read_table(os.path.join(d, "supplier")).num_rows
    pairs = ps["ps_partkey"].to_numpy() * (n_supp + 1) + ps["ps_suppkey"].to_numpy()
    assert len(np.unique(pairs)) == len(pairs) == 4 * pq.read_table(
        os.path.join(d, "part"), columns=["p_partkey"]).num_rows
    lines = li["l_partkey"].to_numpy() * (n_supp + 1) + li["l_suppkey"].to_numpy()
    assert np.isin(lines, pairs).all()


def test_the_domains_the_texts_select_on(data):
    d, rows = data
    part = pq.read_table(os.path.join(d, "part"))
    green = pc.sum(pc.match_substring(part["p_name"], "green")).as_py() / part.num_rows
    assert 0.04 <= green <= 0.07
    words = [name.split(" ") for name in part["p_name"].to_pylist()]
    assert all(len(w) == 5 == len(set(w)) and set(w) <= set(tpch8.COLOURS) for w in words)
    assert len(tpch8.COLOURS) == 92
    types = set(part["p_type"].to_pylist())
    assert "ECONOMY ANODIZED STEEL" in types and len(types) <= 150
    cost = pq.read_table(os.path.join(d, "partsupp"))["ps_supplycost"].to_numpy()
    assert 1.0 <= cost.min() and cost.max() <= 1000.0
    supplier = pq.read_table(os.path.join(d, "supplier"))
    assert set(supplier["s_nationkey"].to_pylist()) <= set(range(25))
    region = pq.read_table(os.path.join(d, "region"))
    assert region["r_name"].to_pylist() == [
        "AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
    nation = pq.read_table(os.path.join(d, "nation")).to_pandas().set_index("n_name")
    assert nation.n_regionkey[["BRAZIL", "FRANCE", "GERMANY", "CHINA"]].tolist() == [1, 3, 3, 2]


def test_the_files_are_the_same_for_one_and_four_workers(cell, tmp_path):
    def digests(workers):
        d = tmp_path / f"w{workers}"
        _generate(d, cell, SCALE, workers=workers, seed=2**31 + 5)
        return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
                for p in sorted(d.rglob("*.parquet"))}

    one = digests(1)
    assert len(one) == 6 * 8 + 2 and one == digests(4)
