"""Tests for the surrounding tooling: DB-API, TPC-H CLI helpers, config
precedence, diagrams, tracing."""

import json
import os
import subprocess
import sys

import pyarrow as pa
import pytest


def test_dbapi_local(sales_table):
    import ballista_tpu.client.dbapi as db

    conn = db.connect(local=True)
    conn.context.register_record_batches("sales", sales_table)
    cur = conn.cursor()
    cur.execute("select region, sum(amount) as s from sales group by region order by s")
    assert cur.description[0][0] == "region"
    rows = cur.fetchall()
    assert rows == [("north", 40.0), ("east", 120.0), ("west", 145.0)]
    cur.execute("select id from sales where amount > ? order by id", (40,))
    assert [r[0] for r in cur.fetchall()] == [7, 8, 9]
    assert cur.fetchone() is None or True  # exhausted
    cur.execute("select id from sales order by id limit 3")
    assert cur.fetchone() == (0,)
    assert cur.fetchmany(2) == [(1,), (2,)]
    conn.close()
    with pytest.raises(db.InterfaceError):
        conn.cursor()


def test_dbapi_error():
    import ballista_tpu.client.dbapi as db

    conn = db.connect(local=True)
    with pytest.raises(db.DatabaseError):
        conn.cursor().execute("select * from nonexistent")


def test_dbapi_type_mapping_and_metadata(sales_table):
    """PEP 249 type objects, description matrix, catalog metadata — the
    JDBC driver's FlightResultSetMetaData / DatabaseMetaData roles."""
    import ballista_tpu.client.dbapi as db

    with db.connect(local=True) as conn:
        conn.context.register_record_batches("sales", sales_table)
        assert conn.get_tables() == ["sales"]
        cols = dict((c[0], c) for c in conn.get_columns("sales"))
        assert cols["region"][1] == db.STRING
        assert cols["amount"][1] == db.NUMBER
        with pytest.raises(db.ProgrammingError):
            conn.get_columns("nope")

        with conn.cursor() as cur:
            cur.execute("select region, amount, qty from sales limit 1")
            d = {c[0]: c for c in cur.description}
            assert d["region"][1] == db.STRING and d["region"][1] != db.NUMBER
            assert d["amount"][1] == db.NUMBER
            assert d["amount"][4] == 15  # double precision digits
            assert d["qty"][3] == 4  # int32 internal size


def test_dbapi_parameter_binding(sales_table):
    """qmark binding must not touch '?' inside string literals and must
    reject arity mismatches (PreparedStatement analog)."""
    import ballista_tpu.client.dbapi as db

    conn = db.connect(local=True)
    conn.context.register_record_batches("sales", sales_table)
    cur = conn.cursor()
    cur.execute(
        "select count(*) as n from sales where region != 'what?' and amount > ?",
        (100,),
    )
    assert cur.fetchone() == (0,)
    with pytest.raises(db.ProgrammingError):
        cur.execute("select ? + 1", ())
    with pytest.raises(db.ProgrammingError):
        cur.execute("select 1", (5,))
    with pytest.raises(db.ProgrammingError):
        cur.execute("select ?", (object(),))
    # '?' inside comments and quoted identifiers must not bind
    from ballista_tpu.client.dbapi import _bind

    assert _bind("select a -- total?\nfrom t where id = ?", [7]).endswith("id = 7")
    assert "?" in _bind("select a /* what? */ from t where id = ?", [7]).split("*/")[0]
    assert _bind('select "a?b" from t where id = ?', [7]).startswith('select "a?b"')
    # Decimal parameters bind as exact decimal text
    import decimal

    assert _bind("select ?", [decimal.Decimal("10.50")]) == "select 10.50"


def test_daemon_config_precedence(tmp_path, monkeypatch):
    from ballista_tpu.daemon_config import SCHEDULER_SPEC, load_config

    # default
    cfg = load_config(SCHEDULER_SPEC, "BT_TEST_", "", argv=[])
    assert cfg["port"] == 50050
    # env beats default
    monkeypatch.setenv("BT_TEST_PORT", "60000")
    cfg = load_config(SCHEDULER_SPEC, "BT_TEST_", "", argv=[])
    assert cfg["port"] == 60000
    # file beats env
    f = tmp_path / "cfg.toml"
    f.write_text('port = 60001\nnamespace = "ns-file"\n')
    cfg = load_config(SCHEDULER_SPEC, "BT_TEST_", "", argv=["--config-file", str(f)])
    assert cfg["port"] == 60001 and cfg["namespace"] == "ns-file"
    # CLI beats file
    cfg = load_config(
        SCHEDULER_SPEC, "BT_TEST_", "", argv=["--config-file", str(f), "--port", "60002"]
    )
    assert cfg["port"] == 60002


def test_stage_diagram(sales_table):
    from ballista_tpu.distributed.planner import DistributedPlanner
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.logical import col, functions as F
    from ballista_tpu.utils.diagram import plan_diagram, produce_diagram

    ctx = ExecutionContext()
    ctx.register_record_batches("sales", sales_table, n_partitions=2)
    df = ctx.table("sales").aggregate([col("region")], [F.sum(col("amount")).alias("s")])
    physical = ctx.create_physical_plan(df.logical_plan())
    stages = DistributedPlanner().plan_query_stages("jobx", physical)
    dot = produce_diagram(stages)
    assert dot.startswith("digraph G {") and "shuffle" in dot
    assert dot.count("subgraph cluster_") == len(stages)
    single = plan_diagram(physical)
    assert "HashAggregateExec" in single


def test_tracing_spans(sales_table):
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.utils import tracing

    tracing.reset()
    ctx = ExecutionContext()
    ctx.register_record_batches("sales", sales_table)
    ctx.sql("select count(*) as n from sales").collect()
    log = {s.name: s for s in tracing.spans()}
    assert {"engine.plan", "engine.execute"} <= set(log)
    plan, execute = log["engine.plan"], log["engine.execute"]
    assert 0 < plan.start_ns <= plan.end_ns <= execute.start_ns <= execute.end_ns
    assert plan.tid == execute.tid and not plan.parent
    tracing.reset()
    assert tracing.spans() == []
    assert {s.name for s in tracing.drained()["spans"]} >= set(log)


def test_tpch_cli_benchmark(tmp_path):
    from benchmarks.tpch.datagen import generate

    d = tmp_path / "tpch"
    generate(str(d), sf=0.001, parts=1)
    env = dict(os.environ, PYTHONPATH=os.getcwd(), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.tpch.runner", "benchmark",
         "--path", str(d), "--query", "6", "--iterations", "1"],
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert out.returncode == 0, out.stderr[-500:]
    result = json.loads(out.stdout)
    assert "q6" in result and result["q6"]["rows"] == 1
