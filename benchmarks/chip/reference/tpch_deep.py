"""Plain reference for the deep joins of TPC-H (q5, q7, q8, q9): pandas over
the generated parquet files, each answer written from the specification's
query text (2.4.5, 2.4.7-2.4.9) with the validation parameters.

Every table is cut down by its own predicates, and by the keys that
survived on the other side of its join, before it is merged: at SF=10 no
merge sees more than a few million rows. float64 throughout. Imports
nothing of the program and takes nothing it has made. Years come back as
whole numbers.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np
import pandas as pd

from reference.lowprec import lower
from reference.tpch import _day, load


def _year(days: pd.Series) -> np.ndarray:
    """extract(year from d) of days since 1970-01-01."""
    return (days.to_numpy().astype("datetime64[D]").astype("datetime64[Y]")
            .astype(np.int64) + 1970)


def _nations(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    return t["nation"].astype({"n_name": str})


def _in_region(t: Dict[str, pd.DataFrame], name: str) -> pd.DataFrame:
    """The nations of region `name`."""
    r = t["region"]
    keys = r[r.r_name == name].r_regionkey
    n = _nations(t)
    return n[n.n_regionkey.isin(keys)]


def _volume(j: pd.DataFrame) -> pd.Series:
    return j.l_extendedprice * (1 - j.l_discount)


def q5(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    n = _in_region(t, "ASIA")[["n_nationkey", "n_name"]]
    c, s, o, li = t["customer"], t["supplier"], t["orders"], t["lineitem"]
    c = c[c.c_nationkey.isin(n.n_nationkey)]
    s = s[s.s_nationkey.isin(n.n_nationkey)]
    o = o[(o.o_orderdate >= _day("1994-01-01")) & (o.o_orderdate < _day("1995-01-01"))]
    o = o[o.o_custkey.isin(c.c_custkey)]
    li = li[li.l_orderkey.isin(o.o_orderkey) & li.l_suppkey.isin(s.s_suppkey)]
    j = (
        li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
        .merge(s, left_on=["l_suppkey", "c_nationkey"],
               right_on=["s_suppkey", "s_nationkey"])
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
    )
    return (
        j.assign(rev=_volume(j))
        .groupby("n_name", as_index=False)
        .agg(revenue=("rev", "sum"))
        .sort_values("revenue", ascending=False)
        .reset_index(drop=True)
    )


def q7(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    n = _nations(t)
    n = n[n.n_name.isin(["FRANCE", "GERMANY"])][["n_nationkey", "n_name"]]
    s, c, o, li = t["supplier"], t["customer"], t["orders"], t["lineitem"]
    s = s[s.s_nationkey.isin(n.n_nationkey)].merge(
        n.rename(columns={"n_name": "supp_nation"}),
        left_on="s_nationkey", right_on="n_nationkey")
    c = c[c.c_nationkey.isin(n.n_nationkey)].merge(
        n.rename(columns={"n_name": "cust_nation"}),
        left_on="c_nationkey", right_on="n_nationkey")
    li = li[(li.l_shipdate >= _day("1995-01-01")) & (li.l_shipdate <= _day("1996-12-31"))]
    li = li[li.l_suppkey.isin(s.s_suppkey)]
    o = o[o.o_custkey.isin(c.c_custkey) & o.o_orderkey.isin(li.l_orderkey)]
    j = (
        li.merge(s[["s_suppkey", "supp_nation"]], left_on="l_suppkey", right_on="s_suppkey")
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c[["c_custkey", "cust_nation"]], left_on="o_custkey", right_on="c_custkey")
    )
    j = j[((j.supp_nation == "FRANCE") & (j.cust_nation == "GERMANY"))
          | ((j.supp_nation == "GERMANY") & (j.cust_nation == "FRANCE"))]
    return (
        j.assign(l_year=_year(j.l_shipdate), volume=_volume(j))
        .groupby(["supp_nation", "cust_nation", "l_year"], as_index=False)
        .agg(revenue=("volume", "sum"))
        .sort_values(["supp_nation", "cust_nation", "l_year"])
        .reset_index(drop=True)
    )


def q8(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    n1 = _in_region(t, "AMERICA")
    n2 = _nations(t)[["n_nationkey", "n_name"]]
    p, s, c, o, li = t["part"], t["supplier"], t["customer"], t["orders"], t["lineitem"]
    p = p[p.p_type == "ECONOMY ANODIZED STEEL"]
    c = c[c.c_nationkey.isin(n1.n_nationkey)]
    li = li[li.l_partkey.isin(p.p_partkey)]
    o = o[(o.o_orderdate >= _day("1995-01-01")) & (o.o_orderdate <= _day("1996-12-31"))]
    o = o[o.o_orderkey.isin(li.l_orderkey) & o.o_custkey.isin(c.c_custkey)]
    j = (
        li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
        .merge(n2, left_on="s_nationkey", right_on="n_nationkey")
    )
    volume = _volume(j)
    j = j.assign(o_year=_year(j.o_orderdate), volume=volume,
                 brazil=volume.where(j.n_name == "BRAZIL", 0.0))
    return (
        j.groupby("o_year", as_index=False)
        .agg(brazil=("brazil", "sum"), volume=("volume", "sum"))
        .assign(mkt_share=lambda d: d.brazil / d.volume)
        [["o_year", "mkt_share"]]
        .sort_values("o_year")
        .reset_index(drop=True)
    )


def q9(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    n = _nations(t)[["n_nationkey", "n_name"]]
    p, s, ps, o, li = t["part"], t["supplier"], t["partsupp"], t["orders"], t["lineitem"]
    p = p[p.p_name.str.contains("green", regex=False)]
    li = li[li.l_partkey.isin(p.p_partkey)]
    ps = ps[ps.ps_partkey.isin(p.p_partkey)]
    o = o[o.o_orderkey.isin(li.l_orderkey)]
    j = (
        li.merge(ps, left_on=["l_partkey", "l_suppkey"],
                 right_on=["ps_partkey", "ps_suppkey"])
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
    )
    return (
        j.assign(nation=j.n_name, o_year=_year(j.o_orderdate),
                 amount=_volume(j) - j.ps_supplycost * j.l_quantity)
        .groupby(["nation", "o_year"], as_index=False)
        .agg(sum_profit=("amount", "sum"))
        .sort_values(["nation", "o_year"], ascending=[True, False])
        .reset_index(drop=True)
    )


ANSWERS = {"q5": q5, "q7": q7, "q8": q8, "q9": q9}


def run(name: str, data_dir: str, reads: Dict[str, List[str]],
        precision: str = "f64") -> pd.DataFrame:
    """The answer to text `name` over the files at `data_dir`. `reads` is
    the traffic file's list of the columns the text names. Top-level and of
    plain arguments: it runs in a worker process."""
    tables = {k: lower(v, precision) for k, v in load(data_dir, reads).items()}
    return lower(ANSWERS[name](tables), precision)
