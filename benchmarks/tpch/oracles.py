"""Independent pandas oracles for all 22 TPC-H queries.

One function per query, `qN(tables) -> pd.DataFrame`, where `tables` maps
table name -> pandas DataFrame (dates as python `datetime.date`). These are
hand-derived from the TPC-H specification text, independent of this
framework's planner/operators — the correctness role the reference assigns
to its Spark comparison harness (spark/benchmarks/.../Main.scala:45-195)
and to the expected-q1 table in rust/benchmarks/tpch/README.md:73-84,
extended here to the full query list with programmatic assertions.

Scalar aggregate queries (q6, q14, q17, q19) return a one-row frame whose
value is NaN when the SQL result would be NULL (aggregate over zero rows).

Shared by tests/test_tpch.py (tiny-SF assertions) and benchmarks/compare.py
(cross-engine validation at benchmark SF).
"""

from __future__ import annotations

from typing import Dict

import numpy as np
import pandas as pd


def _date(s: str):
    return pd.Timestamp(s).date()


def _years(col):
    return pd.to_datetime(col).dt.year


def q1(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li = t["lineitem"]
    d = li[li.l_shipdate <= _date("1998-09-02")]
    disc = d.l_extendedprice * (1 - d.l_discount)
    return (
        d.assign(disc_price=disc, charge=disc * (1 + d.l_tax))
        .groupby(["l_returnflag", "l_linestatus"], as_index=False)
        .agg(
            sum_qty=("l_quantity", "sum"),
            sum_base_price=("l_extendedprice", "sum"),
            sum_disc_price=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            avg_qty=("l_quantity", "mean"),
            avg_price=("l_extendedprice", "mean"),
            avg_disc=("l_discount", "mean"),
            count_order=("l_quantity", "size"),
        )
        .sort_values(["l_returnflag", "l_linestatus"])
        .reset_index(drop=True)
    )


def q2(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    eu_n = t["nation"].merge(
        t["region"][t["region"].r_name == "EUROPE"],
        left_on="n_regionkey", right_on="r_regionkey",
    )
    eu_s = t["supplier"].merge(eu_n, left_on="s_nationkey", right_on="n_nationkey")
    eu_ps = t["partsupp"].merge(eu_s, left_on="ps_suppkey", right_on="s_suppkey")
    min_cost = eu_ps.groupby("ps_partkey").ps_supplycost.min()
    p = t["part"]
    sel = p[(p.p_size == 15) & p.p_type.str.endswith("BRASS")]
    j = eu_ps.merge(sel, left_on="ps_partkey", right_on="p_partkey")
    j = j[j.ps_supplycost == j.ps_partkey.map(min_cost)]
    return (
        j[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr",
           "s_address", "s_phone", "s_comment"]]
        .sort_values(
            ["s_acctbal", "n_name", "s_name", "p_partkey"],
            ascending=[False, True, True, True],
        )
        .head(100)
        .reset_index(drop=True)
    )


def q3(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    cut = _date("1995-03-15")
    j = (
        c[c.c_mktsegment == "BUILDING"]
        .merge(o[o.o_orderdate < cut], left_on="c_custkey", right_on="o_custkey")
        .merge(li[li.l_shipdate > cut], left_on="o_orderkey", right_on="l_orderkey")
    )
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    return (
        j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"], as_index=False)
        .agg(revenue=("rev", "sum"))
        [["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
        .sort_values(["revenue", "o_orderdate"], ascending=[False, True])
        .head(10)
        .reset_index(drop=True)
    )


def q4(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    o, li = t["orders"], t["lineitem"]
    lo, hi = _date("1993-07-01"), _date("1993-10-01")
    ok = li[li.l_commitdate < li.l_receiptdate].l_orderkey.unique()
    d = o[(o.o_orderdate >= lo) & (o.o_orderdate < hi) & o.o_orderkey.isin(ok)]
    return (
        d.groupby("o_orderpriority", as_index=False)
        .agg(order_count=("o_orderkey", "size"))
        .sort_values("o_orderpriority")
        .reset_index(drop=True)
    )


def q5(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    lo, hi = _date("1994-01-01"), _date("1995-01-01")
    j = (
        t["customer"]
        .merge(t["orders"], left_on="c_custkey", right_on="o_custkey")
        .merge(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
        .merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .merge(t["nation"], left_on="s_nationkey", right_on="n_nationkey")
        .merge(t["region"], left_on="n_regionkey", right_on="r_regionkey")
    )
    j = j[
        (j.c_nationkey == j.s_nationkey)
        & (j.r_name == "ASIA")
        & (j.o_orderdate >= lo)
        & (j.o_orderdate < hi)
    ]
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    return (
        j.groupby("n_name", as_index=False)
        .agg(revenue=("rev", "sum"))
        .sort_values("revenue", ascending=False)
        .reset_index(drop=True)
    )


def q6(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li = t["lineitem"]
    lo, hi = _date("1994-01-01"), _date("1995-01-01")
    d = li[
        (li.l_shipdate >= lo)
        & (li.l_shipdate < hi)
        & (li.l_discount >= 0.05)
        & (li.l_discount <= 0.07)
        & (li.l_quantity < 24)
    ]
    rev = np.nan if d.empty else float((d.l_extendedprice * d.l_discount).sum())
    return pd.DataFrame({"revenue": [rev]})


def q7(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    lo, hi = _date("1995-01-01"), _date("1996-12-31")
    li = t["lineitem"]
    j = (
        t["supplier"]
        .merge(li[(li.l_shipdate >= lo) & (li.l_shipdate <= hi)],
               left_on="s_suppkey", right_on="l_suppkey")
        .merge(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
        .merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
        .merge(t["nation"].add_prefix("n1_"), left_on="s_nationkey",
               right_on="n1_n_nationkey")
        .merge(t["nation"].add_prefix("n2_"), left_on="c_nationkey",
               right_on="n2_n_nationkey")
    )
    pair = (
        ((j.n1_n_name == "FRANCE") & (j.n2_n_name == "GERMANY"))
        | ((j.n1_n_name == "GERMANY") & (j.n2_n_name == "FRANCE"))
    )
    j = j[pair]
    return (
        j.assign(
            supp_nation=j.n1_n_name,
            cust_nation=j.n2_n_name,
            l_year=_years(j.l_shipdate),
            volume=j.l_extendedprice * (1 - j.l_discount),
        )
        .groupby(["supp_nation", "cust_nation", "l_year"], as_index=False)
        .agg(revenue=("volume", "sum"))
        .sort_values(["supp_nation", "cust_nation", "l_year"])
        .reset_index(drop=True)
    )


def q8(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    lo, hi = _date("1995-01-01"), _date("1996-12-31")
    o, p = t["orders"], t["part"]
    j = (
        p[p.p_type == "ECONOMY ANODIZED STEEL"]
        .merge(t["lineitem"], left_on="p_partkey", right_on="l_partkey")
        .merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .merge(o[(o.o_orderdate >= lo) & (o.o_orderdate <= hi)],
               left_on="l_orderkey", right_on="o_orderkey")
        .merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
        .merge(t["nation"].add_prefix("n1_"), left_on="c_nationkey",
               right_on="n1_n_nationkey")
        .merge(t["region"][t["region"].r_name == "AMERICA"],
               left_on="n1_n_regionkey", right_on="r_regionkey")
        .merge(t["nation"].add_prefix("n2_"), left_on="s_nationkey",
               right_on="n2_n_nationkey")
    )
    j = j.assign(
        o_year=_years(j.o_orderdate),
        volume=j.l_extendedprice * (1 - j.l_discount),
    )
    j = j.assign(bra=j.volume.where(j.n2_n_name == "BRAZIL", 0.0))
    return (
        j.groupby("o_year", as_index=False)
        .agg(bra=("bra", "sum"), vol=("volume", "sum"))
        .assign(mkt_share=lambda d: d.bra / d.vol)
        [["o_year", "mkt_share"]]
        .sort_values("o_year")
        .reset_index(drop=True)
    )


def q9(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    p = t["part"]
    j = (
        p[p.p_name.str.contains("green")]
        .merge(t["lineitem"], left_on="p_partkey", right_on="l_partkey")
        .merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .merge(
            t["partsupp"],
            left_on=["l_suppkey", "l_partkey"],
            right_on=["ps_suppkey", "ps_partkey"],
        )
        .merge(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
        .merge(t["nation"], left_on="s_nationkey", right_on="n_nationkey")
    )
    j = j.assign(
        nation=j.n_name,
        o_year=_years(j.o_orderdate),
        amount=j.l_extendedprice * (1 - j.l_discount)
        - j.ps_supplycost * j.l_quantity,
    )
    return (
        j.groupby(["nation", "o_year"], as_index=False)
        .agg(sum_profit=("amount", "sum"))
        .sort_values(["nation", "o_year"], ascending=[True, False])
        .reset_index(drop=True)
    )


def q10(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    lo, hi = _date("1993-10-01"), _date("1994-01-01")
    j = (
        t["customer"]
        .merge(t["orders"], left_on="c_custkey", right_on="o_custkey")
        .merge(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
        .merge(t["nation"], left_on="c_nationkey", right_on="n_nationkey")
    )
    j = j[(j.o_orderdate >= lo) & (j.o_orderdate < hi) & (j.l_returnflag == "R")]
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    return (
        j.groupby(
            ["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
             "c_address", "c_comment"],
            as_index=False,
        )
        .agg(revenue=("rev", "sum"))
        [["c_custkey", "c_name", "revenue", "c_acctbal", "n_name", "c_address",
          "c_phone", "c_comment"]]
        .sort_values("revenue", ascending=False)
        .head(20)
        .reset_index(drop=True)
    )


def q11(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    de = (
        t["partsupp"]
        .merge(t["supplier"], left_on="ps_suppkey", right_on="s_suppkey")
        .merge(t["nation"][t["nation"].n_name == "GERMANY"],
               left_on="s_nationkey", right_on="n_nationkey")
    )
    de = de.assign(v=de.ps_supplycost * de.ps_availqty)
    per_part = de.groupby("ps_partkey", as_index=False).agg(value=("v", "sum"))
    w = per_part[per_part.value > de.v.sum() * 0.0001]
    # ORDER BY value desc leaves ties unordered; break them on the key so the
    # oracle is deterministic (callers re-sort `got` the same way)
    return (
        w.sort_values(["value", "ps_partkey"], ascending=[False, True])
        .reset_index(drop=True)
    )


def q12(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    o, li = t["orders"], t["lineitem"]
    lo, hi = _date("1994-01-01"), _date("1995-01-01")
    j = o.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j = j[
        j.l_shipmode.isin(["MAIL", "SHIP"])
        & (j.l_commitdate < j.l_receiptdate)
        & (j.l_shipdate < j.l_commitdate)
        & (j.l_receiptdate >= lo)
        & (j.l_receiptdate < hi)
    ]
    high = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"]).astype(int)
    return (
        j.assign(h=high, l=1 - high)
        .groupby("l_shipmode", as_index=False)
        .agg(high_line_count=("h", "sum"), low_line_count=("l", "sum"))
        .sort_values("l_shipmode")
        .reset_index(drop=True)
    )


def q13(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    c, o = t["customer"], t["orders"]
    o_sel = o[~o.o_comment.str.contains("special.*requests", regex=True)]
    j = c.merge(o_sel, left_on="c_custkey", right_on="o_custkey", how="left")
    per_cust = j.groupby("c_custkey", as_index=False).agg(
        c_count=("o_orderkey", "count")
    )
    return (
        per_cust.groupby("c_count", as_index=False)
        .agg(custdist=("c_count", "size"))
        [["c_count", "custdist"]]
        .sort_values(["custdist", "c_count"], ascending=[False, False])
        .reset_index(drop=True)
    )


def q14(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li, p = t["lineitem"], t["part"]
    lo, hi = _date("1995-09-01"), _date("1995-10-01")
    j = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi)].merge(
        p, left_on="l_partkey", right_on="p_partkey"
    )
    rev = j.l_extendedprice * (1 - j.l_discount)
    total = float(rev.sum())
    if j.empty or total == 0.0:
        return pd.DataFrame({"promo_revenue": [np.nan]})
    promo = float(rev.where(j.p_type.str.startswith("PROMO"), 0.0).sum())
    return pd.DataFrame({"promo_revenue": [100.0 * promo / total]})


def q15(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li, s = t["lineitem"], t["supplier"]
    lo, hi = _date("1996-01-01"), _date("1996-04-01")
    d = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi)]
    rev = (
        d.assign(r=d.l_extendedprice * (1 - d.l_discount))
        .groupby("l_suppkey", as_index=False)
        .agg(total_revenue=("r", "sum"))
    )
    top = rev[rev.total_revenue == rev.total_revenue.max()]
    return (
        s.merge(top, left_on="s_suppkey", right_on="l_suppkey")
        [["s_suppkey", "s_name", "s_address", "s_phone", "total_revenue"]]
        .sort_values("s_suppkey")
        .reset_index(drop=True)
    )


def q16(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    bad = t["supplier"][
        t["supplier"].s_comment.str.contains("Customer.*Complaints", regex=True)
    ].s_suppkey
    p = t["part"]
    sel = p[
        (p.p_brand != "Brand#45")
        & ~p.p_type.str.startswith("MEDIUM POLISHED")
        & p.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])
    ]
    j = t["partsupp"].merge(sel, left_on="ps_partkey", right_on="p_partkey")
    j = j[~j.ps_suppkey.isin(bad)]
    return (
        j.groupby(["p_brand", "p_type", "p_size"], as_index=False)
        .agg(supplier_cnt=("ps_suppkey", "nunique"))
        .sort_values(
            ["supplier_cnt", "p_brand", "p_type", "p_size"],
            ascending=[False, True, True, True],
        )
        .reset_index(drop=True)
    )


def q17(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li, p = t["lineitem"], t["part"]
    sel = p[(p.p_brand == "Brand#23") & (p.p_container == "MED BOX")]
    j = li.merge(sel, left_on="l_partkey", right_on="p_partkey")
    avg_by_part = li.groupby("l_partkey").l_quantity.mean()
    thresh = j.l_partkey.map(avg_by_part) * 0.2
    d = j[j.l_quantity < thresh]
    val = np.nan if d.empty else float(d.l_extendedprice.sum()) / 7.0
    return pd.DataFrame({"avg_yearly": [val]})


def q18(t: Dict[str, pd.DataFrame], threshold: float = 300) -> pd.DataFrame:
    qty = t["lineitem"].groupby("l_orderkey").l_quantity.sum()
    big = qty[qty > threshold].index
    o = t["orders"]
    j = (
        t["customer"]
        .merge(o[o.o_orderkey.isin(big)], left_on="c_custkey", right_on="o_custkey")
        .merge(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
    )
    return (
        j.groupby(
            ["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"],
            as_index=False,
        )
        .agg(sum_qty=("l_quantity", "sum"))
        .sort_values(["o_totalprice", "o_orderdate"], ascending=[False, True])
        .head(100)
        .reset_index(drop=True)
    )


def q19(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li, p = t["lineitem"], t["part"]
    j = li.merge(p, left_on="l_partkey", right_on="p_partkey")
    c1 = (
        (j.p_brand == "Brand#12")
        & j.p_container.isin(["SM CASE", "SM BOX", "SM PACK", "SM PKG"])
        & (j.l_quantity >= 1) & (j.l_quantity <= 11)
        & (j.p_size >= 1) & (j.p_size <= 5)
    )
    c2 = (
        (j.p_brand == "Brand#23")
        & j.p_container.isin(["MED BAG", "MED BOX", "MED PKG", "MED PACK"])
        & (j.l_quantity >= 10) & (j.l_quantity <= 20)
        & (j.p_size >= 1) & (j.p_size <= 10)
    )
    c3 = (
        (j.p_brand == "Brand#34")
        & j.p_container.isin(["LG CASE", "LG BOX", "LG PACK", "LG PKG"])
        & (j.l_quantity >= 20) & (j.l_quantity <= 30)
        & (j.p_size >= 1) & (j.p_size <= 15)
    )
    common = j.l_shipmode.isin(["AIR", "AIR REG"]) & (
        j.l_shipinstruct == "DELIVER IN PERSON"
    )
    d = j[(c1 | c2 | c3) & common]
    val = np.nan if d.empty else float((d.l_extendedprice * (1 - d.l_discount)).sum())
    return pd.DataFrame({"revenue": [val]})


def q20(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    lo, hi = _date("1994-01-01"), _date("1995-01-01")
    li = t["lineitem"]
    d = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi)]
    half = d.groupby(["l_partkey", "l_suppkey"]).l_quantity.sum() * 0.5
    forest = t["part"][t["part"].p_name.str.startswith("forest")].p_partkey
    ps = t["partsupp"][t["partsupp"].ps_partkey.isin(forest)]
    key = list(zip(ps.ps_partkey, ps.ps_suppkey))
    thresh = pd.Series([half.get(k, np.nan) for k in key], index=ps.index)
    ok = ps[ps.ps_availqty > thresh]  # NaN threshold -> row drops, like SQL NULL
    s = t["supplier"].merge(
        t["nation"][t["nation"].n_name == "CANADA"],
        left_on="s_nationkey", right_on="n_nationkey",
    )
    return (
        s[s.s_suppkey.isin(ok.ps_suppkey)][["s_name", "s_address"]]
        .sort_values("s_name")
        .reset_index(drop=True)
    )


def q21(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li = t["lineitem"]
    l1 = li[li.l_receiptdate > li.l_commitdate]
    suppliers_per_order = li.groupby("l_orderkey").l_suppkey.nunique()
    late_suppliers_per_order = l1.groupby("l_orderkey").l_suppkey.nunique()
    j = (
        t["supplier"]
        .merge(t["nation"][t["nation"].n_name == "SAUDI ARABIA"],
               left_on="s_nationkey", right_on="n_nationkey")
        .merge(l1, left_on="s_suppkey", right_on="l_suppkey")
        .merge(t["orders"][t["orders"].o_orderstatus == "F"],
               left_on="l_orderkey", right_on="o_orderkey")
    )
    multi = j.l_orderkey.map(suppliers_per_order) > 1
    only_late = j.l_orderkey.map(late_suppliers_per_order) == 1
    j = j[multi & only_late]
    return (
        j.groupby("s_name", as_index=False)
        .agg(numwait=("s_name", "size"))
        .sort_values(["numwait", "s_name"], ascending=[False, True])
        .head(100)
        .reset_index(drop=True)
    )


def q22(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    c, o = t["customer"], t["orders"]
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    cc = c.assign(cntrycode=c.c_phone.str[:2])
    sel = cc[cc.cntrycode.isin(codes)]
    avg_bal = sel[sel.c_acctbal > 0.0].c_acctbal.mean()
    no_orders = ~sel.c_custkey.isin(o.o_custkey.unique())
    d = sel[(sel.c_acctbal > avg_bal) & no_orders]
    return (
        d.groupby("cntrycode", as_index=False)
        .agg(numcust=("c_custkey", "size"), totacctbal=("c_acctbal", "sum"))
        .sort_values("cntrycode")
        .reset_index(drop=True)
    )


ORACLES = {f"q{i}": globals()[f"q{i}"] for i in range(1, 23)}


# -- running an oracle on a data set on disk --------------------------------
# The served-path smoke (chip_smoke.py) checks every answer at benchmark
# scale, where handing an oracle all eight tables whole does not fit a host:
# each oracle gets only the columns it reads.


def oracle_columns(name: str) -> Dict[str, list]:
    """table -> the columns `ORACLES[name]` reads, found by matching the
    function's source against the TPC-H schema. Every column of a TPC-H
    table carries its table's prefix, so a name cannot be mistaken for
    another table's."""
    import inspect
    import re

    from benchmarks.tpch.schema import TPCH_TABLES, get_tpch_schema

    words = set(re.findall(r"\b[a-z]{1,2}_[a-z]+\b", inspect.getsource(ORACLES[name])))
    out = {}
    for table in TPCH_TABLES:
        cols = [c for c in get_tpch_schema(table).names if c in words]
        if cols:
            out[table] = cols
    return out


def run_on_dir(name: str, data_dir: str) -> pd.DataFrame:
    """`ORACLES[name]` over the parquet data set at `data_dir` (the layout
    datagen.generate writes). Top-level and of plain arguments so a worker
    process can run it; touches pandas and pyarrow only."""
    import os

    import pyarrow.parquet as pq

    tables = {
        table: pq.read_table(os.path.join(data_dir, table), columns=cols).to_pandas()
        for table, cols in oracle_columns(name).items()
    }
    return ORACLES[name](tables)


def compare_frames(got: pd.DataFrame, want: pd.DataFrame, rtol: float) -> Dict[str, object]:
    """Hold an engine's answer to the oracle's. Non-float columns must be
    equal; float columns must agree within `rtol` (relative, and absolute
    for values near zero). Raises AssertionError on any mismatch, else
    returns {"exact": [columns equal bit for bit], "max_rel_err": the
    largest relative error among the others (0.0 when all were exact)}."""
    assert len(got) == len(want), f"row count {len(got)} != {len(want)}"
    assert list(got.columns) == list(want.columns), (
        list(got.columns), list(want.columns))
    exact, worst = [], 0.0
    for c in want.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        if not np.issubdtype(w.dtype, np.floating):
            assert list(g) == list(w), f"column {c}: {g[:5]} != {w[:5]}"
            exact.append(c)
            continue
        g, w = g.astype(float), w.astype(float)
        if np.array_equal(g, w, equal_nan=True):
            exact.append(c)
            continue
        np.testing.assert_allclose(g, w, rtol=rtol, atol=rtol, err_msg=f"column {c}")
        denom = np.maximum(np.abs(w), 1e-300)
        worst = max(worst, float(np.nanmax(np.abs(g - w) / denom)))
    return {"exact": exact, "max_rel_err": worst}
