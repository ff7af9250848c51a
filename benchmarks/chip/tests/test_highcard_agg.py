"""The cell `tpch_sf10_subquery_1chip.highcard_agg`: its plain reference
against answers computed by hand on tables of a few dozen rows, the control
one precision below, and a traced rehearsal that ends with a validated line
holding the two metrics the cell brought."""

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
from reference import tpch_subquery

CELL = "tpch_sf10_subquery_1chip.highcard_agg"
MARK = "REHEARSAL under CPU-jax, no result and no device metric: "
LIMIT = 2e-5

FRANCE, GERMANY, UK, CANADA, BRAZIL = 6, 7, 23, 3, 2

# (s_suppkey, s_nationkey, s_acctbal); 1 and 6 tie on s_acctbal
SUPPLIER = [(1, FRANCE, 4321.77), (2, GERMANY, 987.65), (3, CANADA, 100.0),
            (4, CANADA, 200.0), (5, BRAZIL, 5000.0), (6, UK, 4321.77),
            (7, CANADA, 300.0), (8, CANADA, 400.0)]
# (p_partkey, p_name, p_type, p_size)
PART = [(1, "forest green blue red pink", "STANDARD POLISHED BRASS", 15),
        (2, "forest blue pink tan sky", "SMALL PLATED BRASS", 15),
        (3, "red forest snow tan sky", "ECONOMY ANODIZED STEEL", 15),  # forest is not first; no BRASS
        (4, "almond blue snow tan sky", "LARGE BRUSHED BRASS", 14),    # q2: the wrong size
        (5, "forest red snow tan sky", "PROMO BURNISHED BRASS", 15)]   # q2: no European supplier
# (ps_partkey, ps_suppkey, ps_availqty, ps_supplycost)
PARTSUPP = [
    (1, 1, 100, 10.0),  # q2: part 1's minimum in EUROPE, twice
    (1, 2, 50, 10.0),
    (1, 5, 10, 5.0),    # q2: cheaper, and BRAZIL
    (1, 3, 20, 7.0),    # q2: cheaper, and CANADA; q20: 20 = 0.5 * 40, out
    (2, 6, 30, 20.0),   # q2: part 2's minimum in EUROPE
    (2, 1, 5, 25.0),
    (2, 3, 11, 1.0),    # q20: 11 = 0.5 * 22, out
    (2, 4, 10, 2.0),    # q20: 10 > 0.5 * 10, supplier 4 in
    (3, 2, 5, 1.0),
    (3, 8, 1000, 9.0),  # q20: stock enough, of a part that is no forest part
    (4, 1, 5, 1.0),
    (5, 5, 5, 3.0),
    (5, 3, 9, 4.0),     # q20: no line of 1994, a NULL sum, out
    (5, 4, 12, 8.0),    # q20: its one line shipped 1995-01-01, out
    (5, 7, 8, 6.0),     # q20: 8 > 0.5 * 15, supplier 7 in
]
# (l_partkey, l_suppkey, l_quantity, l_extendedprice, l_discount, l_shipdate)
LINEITEM = [
    (1, 3, 15.0, 10.0, 0.0, "1994-01-01"),    # q20: the first day in
    (1, 3, 25.0, 10.0, 0.0, "1994-12-31"),    # q20: the last day in; 40 in all
    (2, 3, 22.0, 10.0, 0.0, "1994-06-01"),
    (2, 4, 10.0, 10.0, 0.0, "1994-03-01"),
    (2, 4, 50.0, 10.0, 0.0, "1995-01-01"),    # q20: the next year, not in the sum
    (5, 4, 1.0, 10.0, 0.0, "1995-01-01"),
    (1, 1, 2.0, 10.0, 0.0, "1994-05-05"),     # q20: stock enough, and FRANCE
    (5, 7, 15.0, 10.0, 0.0, "1994-07-07"),
    (3, 8, 1.0, 10.0, 0.0, "1994-07-07"),
    (1, 2, 1.0, 901.0, 0.0, "1996-01-01"),    # q15: supplier 2, 901 + 100
    (3, 2, 1.0, 100.0, 0.0, "1996-03-31"),
    (1, 5, 1.0, 2002.0, 0.5, "1996-02-15"),   # q15: supplier 5, 1001 too: a tie for the maximum
    (1, 1, 1.0, 500.0, 0.0, "1996-02-01"),    # q15: supplier 1, 500
    (1, 1, 1.0, 9999.0, 0.0, "1996-04-01"),   # q15: a day late
    (1, 1, 1.0, 9999.0, 0.0, "1995-12-31"),   # q15: a day early
]


def _name(k):
    return f"Supplier#{k:09d}"


def _address(k):
    return f"Addr#{k:09d}"


def _phone(k):
    return f"{10 + k}-989-741-2988"


WANT = {
    # s_acctbal desc, then n_name: FRANCE before UNITED KINGDOM
    "q2": {"s_acctbal": [4321.77, 4321.77, 987.65],
           "s_name": [_name(1), _name(6), _name(2)],
           "n_name": ["FRANCE", "UNITED KINGDOM", "GERMANY"],
           "p_partkey": [1, 2, 1],
           "p_mfgr": ["Manufacturer#1", "Manufacturer#2", "Manufacturer#1"],
           "s_address": [_address(1), _address(6), _address(2)],
           "s_phone": [_phone(1), _phone(6), _phone(2)],
           "s_comment": ["comment 1", "comment 6", "comment 2"]},
    "q15": {"s_suppkey": [2, 5], "s_name": [_name(2), _name(5)],
            "s_address": [_address(2), _address(5)], "s_phone": [_phone(2), _phone(5)],
            "total_revenue": [1001.0, 1001.0]},
    "q20": {"s_name": [_name(4), _name(7)], "s_address": [_address(4), _address(7)]},
}


def _dates(values):
    return pa.array([datetime.date.fromisoformat(v) for v in values], type=pa.date32())


@pytest.fixture(scope="module")
def by_hand(tmp_path_factory):
    d = tmp_path_factory.mktemp("by_hand")
    s, p, ps, li = (list(zip(*rows)) for rows in (SUPPLIER, PART, PARTSUPP, LINEITEM))
    tables = {
        "region": {"r_regionkey": list(range(5)),
                   "r_name": ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]},
        "nation": {"n_nationkey": list(range(25)), "n_name": [n for n, _ in NATIONS],
                   "n_regionkey": [r for _, r in NATIONS]},
        "supplier": {"s_suppkey": s[0], "s_name": [_name(k) for k in s[0]],
                     "s_address": [_address(k) for k in s[0]], "s_nationkey": s[1],
                     "s_phone": [_phone(k) for k in s[0]], "s_acctbal": s[2],
                     "s_comment": [f"comment {k}" for k in s[0]]},
        "part": {"p_partkey": p[0], "p_name": p[1],
                 "p_mfgr": [f"Manufacturer#{k}" for k in p[0]], "p_type": p[2],
                 "p_size": pa.array(p[3], type=pa.int32())},
        "partsupp": {"ps_partkey": ps[0], "ps_suppkey": ps[1],
                     "ps_availqty": pa.array(ps[2], type=pa.int32()),
                     "ps_supplycost": ps[3]},
        "lineitem": {"l_partkey": li[0], "l_suppkey": li[1], "l_quantity": li[2],
                     "l_extendedprice": li[3], "l_discount": li[4],
                     "l_shipdate": _dates(li[5])},
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
    got = tpch_subquery.run(text["reference"], by_hand, run.reads_of(text))
    want = WANT[name]
    assert list(got.columns) == list(want)
    for column, values in want.items():
        if isinstance(values[0], float):
            assert got[column].tolist() == pytest.approx(values, rel=1e-12), column
        else:
            assert got[column].tolist() == values, column


@pytest.mark.parametrize("name,column", [("q2", "s_acctbal"), ("q15", "total_revenue")])
def test_one_precision_below_moves_the_float_past_ten_times_the_limit(name, column, by_hand):
    """The control rests on q2 and q15: 4321.77 and 987.65 are no bfloat16
    numbers, nor are 901, 2002 and 1001."""
    text = _texts()[name]
    full = tpch_subquery.run(text["reference"], by_hand, run.reads_of(text))
    low = tpch_subquery.run(text["reference"], by_hand, run.reads_of(text), precision="bf16")
    assert list(low.columns) == list(full.columns) and len(low) == len(full)
    for c in full.columns:
        if full[c].dtype.kind != "f":
            assert low[c].tolist() == full[c].tolist(), c
    err = ((low[column] - full[column]).abs() / full[column].abs()).max()
    assert err > 10 * LIMIT


def test_one_precision_below_leaves_q20_as_it_is(by_hand):
    """Keys, strings and sums of whole numbers that bfloat16 holds exactly:
    q20 cannot tell the precisions apart."""
    text = _texts()["q20"]
    full = tpch_subquery.run(text["reference"], by_hand, run.reads_of(text))
    low = tpch_subquery.run(text["reference"], by_hand, run.reads_of(text), precision="bf16")
    assert low.equals(full)


def test_the_traffic_file_names_each_text_s_columns_by_their_types():
    widths = run._json(os.path.join(run.CHIP, "peaks.json"))["logical_width_bytes"]
    spec = run.load_cell(CELL)
    texts = _texts()
    assert list(texts) == ["q2", "q15", "q20"]
    assert {k: spec["traffic"][k] for k in run.GENERATOR} == run.GENERATOR
    for text in texts.values():
        sql = run._sql(text["sql"])
        assert "sort_by" not in text and "order by" in sql
        for table, cols in text["reads"].items():
            assert f" {table}" in sql
            for column, kind in cols.items():
                assert column in sql and kind in widths, (text["name"], column)
    # every column a text names is in its reads
    for name, columns in (
            ("q2", "s_acctbal s_name n_name p_partkey p_mfgr s_address s_phone s_comment "
                   "ps_partkey s_suppkey ps_suppkey p_size p_type s_nationkey n_nationkey "
                   "n_regionkey r_regionkey r_name ps_supplycost"),
            ("q15", "s_suppkey s_name s_address s_phone l_suppkey l_extendedprice "
                    "l_discount l_shipdate"),
            ("q20", "s_name s_address s_suppkey ps_suppkey ps_partkey p_partkey p_name "
                    "ps_availqty l_quantity l_partkey l_suppkey l_shipdate s_nationkey "
                    "n_nationkey n_name")):
        named = {c for cols in texts[name]["reads"].values() for c in cols}
        assert named == set(columns.split()), name
    kinds = {c: k for t in texts.values() for cols in t["reads"].values()
             for c, k in cols.items()}
    assert (kinds["p_name"], kinds["p_type"], kinds["r_name"], kinds["s_comment"]) == (
        "carried_string", "code", "code", "carried_string")
    assert kinds["ps_supplycost"] == kinds["l_quantity"] == kinds["s_acctbal"] == "decimal"
    assert kinds["ps_availqty"] == kinds["p_size"] == "integer"
    assert kinds["l_shipdate"] == "date"
    # q20: four lineitem columns of 4 bytes over 60M rows are most of its floor
    rows = {"lineitem": 60_000_000, "partsupp": 8_000_000, "part": 2_000_000,
            "supplier": 100_000, "nation": 25}
    assert run.floor_bytes(texts["q20"], rows, widths) == (
        60_000_000 * 16 + 8_000_000 * 12 + 2_000_000 * 8 + 100_000 * 16 + 25 * 8)


def test_a_traced_rehearsal_ends_with_a_validated_line(capsys):
    args = run.parse(["--workload", CELL, "--seed", str(2**31 + 13), "--seconds", "3",
                      "--trace", "1"])
    assert run.execute(args, rehearsal={"scale": 0.02}) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith(MARK)
    line = json.loads(last[len(MARK):])
    spec = run.load_cell(CELL)
    expected = [(m["name"], m["unit"]) for m in spec["per_layer"]]
    assert lastline.validate(line, expected, True) == []
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 9
    assert line["compared"]["rel_err_max"]["value"] <= LIMIT
    metrics = line["metrics"]
    assert metrics["engines.host_answers"]["value"] == 0
    assert metrics["runtime.window_compiles"]["value"] == 0
    for name in ("engines.groups_out", "engines.join_ms"):
        assert math.isfinite(metrics[name]["value"]) and metrics[name]["value"] > 0.0
    # q20 hands the host a group a pair of the year, q15 a group a supplier twice
    assert metrics["engines.groups_out"]["value"] >= 1000
