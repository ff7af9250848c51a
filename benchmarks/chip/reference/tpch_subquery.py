"""Plain reference for TPC-H's aggregate subqueries (q2, q15, q20): pandas
over the generated parquet files, each answer written from the
specification's query text (2.4.2, 2.4.15, 2.4.20) with the validation
parameters.

A subquery is computed as it reads: the correlated minimum of q2 and the
correlated sum of q20 as a `groupby` over the rows the inner block selects,
merged back on the correlation columns (a part or a pair with no such row
has a NULL there and fails the comparison: an inner merge); q15's scalar as
a `max`; an `IN` as an `isin`. Every table is cut down by its own
predicates, and the inner block by the keys the outer block can ask it for,
before it is grouped. float64 throughout. Imports nothing of the program and
takes nothing it has made. Column names and order are the select list's,
rows in the ORDER BY's order.
"""

from __future__ import annotations

from typing import Dict, List

import pandas as pd

from reference.lowprec import lower
from reference.tpch import _day, load
from reference.tpch_deep import _in_region


def q2(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    n = _in_region(t, "EUROPE")[["n_nationkey", "n_name"]]
    s = t["supplier"]
    s = s[s.s_nationkey.isin(n.n_nationkey)]
    p = t["part"]
    p = p[(p.p_size == 15) & p.p_type.astype(str).str.endswith("BRASS")]
    # partsupp x supplier x nation x region of both blocks: a European
    # supplier's offers
    ps = t["partsupp"]
    ps = ps[ps.ps_suppkey.isin(s.s_suppkey)]
    # the subquery: min(ps_supplycost) of those offers, per the p_partkey it
    # is correlated on
    cheapest = (ps.groupby("ps_partkey", as_index=False)
                .agg(min_cost=("ps_supplycost", "min")))
    j = (
        p[["p_partkey", "p_mfgr"]]
        .merge(ps, left_on="p_partkey", right_on="ps_partkey")
        .merge(cheapest, on="ps_partkey")
    )
    j = (
        j[j.ps_supplycost == j.min_cost]
        .merge(s, left_on="ps_suppkey", right_on="s_suppkey")
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
    )
    return (
        j[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr", "s_address",
           "s_phone", "s_comment"]]
        .astype({k: str for k in ("s_name", "p_mfgr", "s_address", "s_phone", "s_comment")})
        .sort_values(["s_acctbal", "n_name", "s_name", "p_partkey"],
                     ascending=[False, True, True, True])
        .head(100)
        .reset_index(drop=True)
    )


def q15(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    s, li = t["supplier"], t["lineitem"]
    li = li[(li.l_shipdate >= _day("1996-01-01")) & (li.l_shipdate < _day("1996-04-01"))]
    # revenue0, and the same rows again as revenue1
    revenue = (
        li.assign(rev=li.l_extendedprice * (1 - li.l_discount))
        .groupby("l_suppkey", as_index=False)
        .agg(total_revenue=("rev", "sum"))
        .rename(columns={"l_suppkey": "supplier_no"})
    )
    top = revenue[revenue.total_revenue == revenue.total_revenue.max()]
    return (
        s.merge(top, left_on="s_suppkey", right_on="supplier_no")
        [["s_suppkey", "s_name", "s_address", "s_phone", "total_revenue"]]
        .astype({k: str for k in ("s_name", "s_address", "s_phone")})
        .sort_values("s_suppkey")
        .reset_index(drop=True)
    )


def q20(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    s, n, ps, p, li = (t[k] for k in ("supplier", "nation", "partsupp", "part", "lineitem"))
    forest = p[p.p_name.astype(str).str.startswith("forest")].p_partkey
    ps = ps[ps.ps_partkey.isin(forest)]
    li = li[(li.l_shipdate >= _day("1994-01-01")) & (li.l_shipdate < _day("1995-01-01"))]
    li = li[li.l_partkey.isin(ps.ps_partkey)]
    # the innermost subquery: sum(l_quantity) of the year's lines, per the
    # (ps_partkey, ps_suppkey) it is correlated on
    shipped = (li.groupby(["l_partkey", "l_suppkey"], as_index=False)
               .agg(quantity=("l_quantity", "sum")))
    j = ps.merge(shipped, left_on=["ps_partkey", "ps_suppkey"],
                 right_on=["l_partkey", "l_suppkey"])
    excess = j[j.ps_availqty > 0.5 * j.quantity].ps_suppkey
    canada = n[n.n_name == "CANADA"].n_nationkey
    s = s[s.s_suppkey.isin(excess) & s.s_nationkey.isin(canada)]
    return (
        s[["s_name", "s_address"]]
        .astype({"s_name": str, "s_address": str})
        .sort_values("s_name")
        .reset_index(drop=True)
    )


ANSWERS = {"q2": q2, "q15": q15, "q20": q20}


def run(name: str, data_dir: str, reads: Dict[str, List[str]],
        precision: str = "f64") -> pd.DataFrame:
    """The answer to text `name` over the files at `data_dir`. `reads` is
    the traffic file's list of the columns the text names. Top-level and of
    plain arguments: it runs in a worker process."""
    tables = {k: lower(v, precision) for k, v in load(data_dir, reads).items()}
    return lower(ANSWERS[name](tables), precision)
