"""The cell `tpch_sf10_8tables_1chip.join_deep`: its plain reference against
answers computed by hand on tables of a few dozen rows, the control one
precision below, and a traced rehearsal that ends with a validated line."""

import datetime
import json
import math
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import lastline
import run
from data.tpch import NATIONS
from reference import tpch_deep

CELL = "tpch_sf10_8tables_1chip.join_deep"
MARK = "REHEARSAL under CPU-jax, no result and no device metric: "

FRANCE, GERMANY, BRAZIL, CHINA, INDIA, USA = 6, 7, 2, 18, 8, 24
STEEL = "ECONOMY ANODIZED STEEL"

# (s_suppkey, s_nationkey)
SUPPLIER = [(1, FRANCE), (2, GERMANY), (3, BRAZIL), (4, CHINA), (5, INDIA)]
# (c_custkey, c_nationkey)
CUSTOMER = [(1, GERMANY), (2, FRANCE), (3, BRAZIL), (4, CHINA), (5, USA), (6, INDIA)]
# (p_partkey, p_name, p_type)
PART = [(1, "almond green blue red pink", STEEL),
        (2, "red blue pink tan sky", STEEL),
        (3, "forest green snow tan sky", "STANDARD PLATED TIN")]
# (ps_partkey, ps_suppkey, ps_supplycost): no row for part 3 of supplier 4
PARTSUPP = [(1, 1, 10.0), (1, 2, 20.0), (1, 3, 30.0), (3, 1, 5.0), (3, 3, 15.0),
            (3, 5, 25.0), (2, 2, 1.0), (2, 4, 2.0), (2, 3, 3.0), (2, 5, 4.0)]
# (o_orderkey, o_custkey, o_orderdate)
ORDERS = [(1, 1, "1995-03-01"), (2, 2, "1996-07-01"), (3, 3, "1995-06-01"),
          (4, 4, "1994-05-01"), (5, 5, "1996-02-01"), (6, 6, "1994-12-31"),
          (7, 4, "1995-01-01"), (8, 1, "1997-01-01")]
# (l_orderkey, l_partkey, l_suppkey, l_quantity, l_extendedprice, l_discount, l_shipdate)
LINEITEM = [
    (1, 1, 1, 10.0, 1000.0, 0.10, "1995-04-01"),  # q7 FRANCE->GERMANY 1995: 900; q9 FRANCE 1995: 900-100
    (1, 2, 2, 5.0, 2000.0, 0.00, "1995-04-02"),   # q7: GERMANY->GERMANY, out
    (2, 1, 2, 2.0, 4000.0, 0.50, "1996-12-31"),   # q7 GERMANY->FRANCE 1996: 2000 (last day in); q9 GERMANY 1996: 2000-40
    (2, 3, 1, 1.0, 500.0, 0.00, "1997-01-01"),    # q7: shipped too late; q9 FRANCE 1996: 500-5
    (3, 1, 3, 4.0, 1000.0, 0.00, "1995-07-01"),   # q8 1995 BRAZIL 1000; q9 BRAZIL 1995: 1000-120
    (3, 2, 4, 3.0, 3000.0, 0.00, "1995-07-02"),   # q8 1995 other 3000
    (5, 2, 3, 2.0, 500.0, 0.20, "1996-03-01"),    # q8 1996 BRAZIL 400
    (5, 3, 3, 2.0, 999.0, 0.00, "1996-03-01"),    # q8: not the type; q9 BRAZIL 1996: 999-30
    (5, 1, 1, 1.0, 1600.0, 0.00, "1996-03-05"),   # q8 1996 other 1600; q9 FRANCE 1996: 1600-10
    (4, 2, 4, 1.0, 1000.0, 0.10, "1994-06-01"),   # q5 CHINA 900
    (4, 2, 5, 1.0, 700.0, 0.00, "1994-06-01"),    # q5: customer CHINA, supplier INDIA, out
    (6, 3, 5, 2.0, 2000.0, 0.25, "1995-01-15"),   # q5 INDIA 1500 (ordered 1994-12-31); q9 INDIA 1994: 1500-50
    (7, 2, 4, 1.0, 5000.0, 0.00, "1995-02-01"),   # q5: ordered 1995-01-01, out
    (8, 1, 2, 1.0, 100.0, 0.00, "1997-02-01"),    # q9 GERMANY 1997: 100-20
    (1, 3, 4, 1.0, 100.0, 0.00, "1995-05-01"),    # q9: no partsupp row, out
]

WANT = {
    "q5": {"n_name": ["INDIA", "CHINA"], "revenue": [1500.0, 900.0]},
    "q7": {"supp_nation": ["FRANCE", "GERMANY"], "cust_nation": ["GERMANY", "FRANCE"],
           "l_year": [1995, 1996], "revenue": [900.0, 2000.0]},
    "q8": {"o_year": [1995, 1996], "mkt_share": [1000.0 / 4000.0, 400.0 / 2000.0]},
    "q9": {"nation": ["BRAZIL", "BRAZIL", "FRANCE", "FRANCE", "GERMANY", "GERMANY", "INDIA"],
           "o_year": [1996, 1995, 1996, 1995, 1997, 1996, 1994],
           "sum_profit": [969.0, 880.0, 495.0 + 1590.0, 800.0, 80.0, 1960.0, 1450.0]},
}


def _dates(values):
    return pa.array([datetime.date.fromisoformat(v) for v in values], type=pa.date32())


@pytest.fixture(scope="module")
def by_hand(tmp_path_factory):
    d = tmp_path_factory.mktemp("by_hand")
    s, c, p, ps, o, li = (list(zip(*rows)) for rows in (
        SUPPLIER, CUSTOMER, PART, PARTSUPP, ORDERS, LINEITEM))
    tables = {
        "region": {"r_regionkey": list(range(5)),
                   "r_name": ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]},
        "nation": {"n_nationkey": list(range(25)), "n_name": [n for n, _ in NATIONS],
                   "n_regionkey": [r for _, r in NATIONS]},
        "supplier": {"s_suppkey": s[0], "s_nationkey": s[1]},
        "customer": {"c_custkey": c[0], "c_nationkey": c[1]},
        "part": {"p_partkey": p[0], "p_name": p[1], "p_type": p[2]},
        "partsupp": {"ps_partkey": ps[0], "ps_suppkey": ps[1], "ps_supplycost": ps[2]},
        "orders": {"o_orderkey": o[0], "o_custkey": o[1], "o_orderdate": _dates(o[2])},
        "lineitem": {"l_orderkey": li[0], "l_partkey": li[1], "l_suppkey": li[2],
                     "l_quantity": li[3], "l_extendedprice": li[4], "l_discount": li[5],
                     "l_shipdate": _dates(li[6])},
    }
    for name, columns in tables.items():
        os.makedirs(d / name)
        pq.write_table(pa.table(columns), str(d / name / "part-000.parquet"))
    return str(d)


def _texts():
    return {t["name"]: t for t in run.load_cell(CELL)["traffic"]["texts"]}


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_reference_gives_the_answer_computed_by_hand(name, by_hand):
    text = _texts()[name]
    got = tpch_deep.run(text["reference"], by_hand, run.reads_of(text))
    want = WANT[name]
    assert list(got.columns) == list(want)
    for column, values in want.items():
        if isinstance(values[0], float):
            assert got[column].tolist() == pytest.approx(values, rel=1e-12), column
        else:
            assert got[column].tolist() == values, column


@pytest.mark.parametrize("name", sorted(WANT))
def test_one_precision_below_keeps_the_keys_and_moves_the_floats(name, by_hand):
    text = _texts()[name]
    full = tpch_deep.run(text["reference"], by_hand, run.reads_of(text))
    low = tpch_deep.run(text["reference"], by_hand, run.reads_of(text), precision="bf16")
    floats = [c for c in full.columns if full[c].dtype.kind == "f"]
    assert floats and list(low.columns) == list(full.columns)
    for column in full.columns:
        if column not in floats:
            assert low[column].tolist() == full[column].tolist()
    # 969, 1450 and 2085 are no bfloat16 numbers; q8's 0.2 neither
    if name in ("q8", "q9"):
        assert any(low[c].tolist() != full[c].tolist() for c in floats)


def test_the_traffic_file_names_each_text_s_columns_by_their_types():
    widths = run._json(os.path.join(run.CHIP, "peaks.json"))["logical_width_bytes"]
    texts = _texts()
    assert list(texts) == ["q5", "q7", "q8", "q9"]
    for text in texts.values():
        sql = run._sql(text["sql"])
        for table, cols in text["reads"].items():
            for column, kind in cols.items():
                assert column in sql and kind in widths, (text["name"], column)
    kinds = {c: k for t in texts.values() for cols in t["reads"].values()
             for c, k in cols.items()}
    assert (kinds["p_name"], kinds["n_name"], kinds["p_type"], kinds["r_name"]) == (
        "carried_string", "carried_string", "code", "code")
    assert kinds["ps_supplycost"] == kinds["l_quantity"] == "decimal"
    assert kinds["o_orderdate"] == kinds["l_shipdate"] == "date"
    # q9: six lineitem columns of 4 bytes over 60M rows are most of its floor
    rows = {"lineitem": 60_000_000, "orders": 15_000_000, "partsupp": 8_000_000,
            "part": 2_000_000, "supplier": 100_000, "nation": 25}
    assert run.floor_bytes(texts["q9"], rows, widths) == (
        60_000_000 * 24 + 15_000_000 * 8 + 8_000_000 * 12 + 2_000_000 * 8
        + 100_000 * 8 + 25 * 8)


def test_a_traced_rehearsal_ends_with_a_validated_line(capsys):
    args = run.parse(["--workload", CELL, "--seed", "7", "--seconds", "3", "--trace", "1"])
    assert run.execute(args, rehearsal={"scale": 0.01}) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith(MARK)
    line = json.loads(last[len(MARK):])
    spec = run.load_cell(CELL)
    expected = [(m["name"], m["unit"]) for m in spec["per_layer"]]
    assert lastline.validate(line, expected, True) == []
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 12
    assert line["compared"]["rel_err_max"]["value"] <= 2e-5
    metrics = line["metrics"]
    assert metrics["engines.host_answers"]["value"] == 0
    assert metrics["runtime.window_compiles"]["value"] == 0
    for name in ("engines.dim_build_ms", "runtime.launches"):
        assert math.isfinite(metrics[name]["value"]) and metrics[name]["value"] >= 0.0
    # every text launches at least one program in every query
    assert metrics["runtime.launches"]["value"] >= 1.0
