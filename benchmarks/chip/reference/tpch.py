"""Plain reference for the TPC-H texts the cells send: pandas over the
generated parquet files, hand-derived from the specification's query text.

Copied from benchmarks/tpch/oracles.py (q1, q3, q6, q10, q12), which the
program's tests import. Changed for benchmark scale: each table is read with
only the columns its query names, strings as categoricals and dates as day
numbers, and a join filters its inputs before it merges them (q10 merged the
three big tables whole and peaked near 21 GB at SF=10). Imports nothing of
the program and takes nothing it has made. Dates come back as days since
1970-01-01.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

from reference.lowprec import lower


def _day(s: str) -> int:
    return int((np.datetime64(s) - np.datetime64("1970-01-01")).astype(np.int64))


def q1(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li = t["lineitem"]
    d = li[li.l_shipdate <= _day("1998-09-02")]
    disc = d.l_extendedprice * (1 - d.l_discount)
    return (
        d.assign(disc_price=disc, charge=disc * (1 + d.l_tax))
        .groupby(["l_returnflag", "l_linestatus"], as_index=False, observed=True)
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
        .astype({"l_returnflag": str, "l_linestatus": str})
        .sort_values(["l_returnflag", "l_linestatus"])
        .reset_index(drop=True)
    )


def q3(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    cut = _day("1995-03-15")
    j = (
        c[c.c_mktsegment == "BUILDING"][["c_custkey"]]
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


def q6(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    li = t["lineitem"]
    d = li[
        (li.l_shipdate >= _day("1994-01-01"))
        & (li.l_shipdate < _day("1995-01-01"))
        & (li.l_discount >= 0.05)
        & (li.l_discount <= 0.07)
        & (li.l_quantity < 24)
    ]
    rev = np.nan if d.empty else float((d.l_extendedprice * d.l_discount).sum())
    return pd.DataFrame({"revenue": [rev]})


def q10(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    c, o, li, n = t["customer"], t["orders"], t["lineitem"], t["nation"]
    o = o[(o.o_orderdate >= _day("1993-10-01")) & (o.o_orderdate < _day("1994-01-01"))]
    li = li[li.l_returnflag == "R"]
    j = o.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    # c_custkey is customer's key: it alone decides the group, the other
    # grouping columns follow from it
    rev = j.groupby("o_custkey", as_index=False).agg(revenue=("rev", "sum"))
    out = (
        rev.merge(c, left_on="o_custkey", right_on="c_custkey")
        .merge(n, left_on="c_nationkey", right_on="n_nationkey")
    )
    return (
        out[["c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
             "c_address", "c_phone", "c_comment"]]
        .astype({k: str for k in ("c_name", "n_name", "c_address", "c_phone",
                                  "c_comment")})
        .sort_values("revenue", ascending=False)
        .head(20)
        .reset_index(drop=True)
    )


def q12(t: Dict[str, pd.DataFrame]) -> pd.DataFrame:
    o, li = t["orders"], t["lineitem"]
    li = li[
        li.l_shipmode.isin(["MAIL", "SHIP"])
        & (li.l_commitdate < li.l_receiptdate)
        & (li.l_shipdate < li.l_commitdate)
        & (li.l_receiptdate >= _day("1994-01-01"))
        & (li.l_receiptdate < _day("1995-01-01"))
    ]
    j = o.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    high = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"]).astype(np.int64)
    return (
        j.assign(h=high, l=1 - high)
        .groupby("l_shipmode", as_index=False, observed=True)
        .agg(high_line_count=("h", "sum"), low_line_count=("l", "sum"))
        .astype({"l_shipmode": str})
        .sort_values("l_shipmode")
        .reset_index(drop=True)
    )


ANSWERS = {"q1": q1, "q3": q3, "q6": q6, "q10": q10, "q12": q12}


def load(data_dir: str, reads: Dict[str, List[str]]) -> Dict[str, pd.DataFrame]:
    """{table: frame of the columns in `reads`}: strings as categoricals,
    dates as days since 1970-01-01."""
    out = {}
    for table, cols in reads.items():
        t = pq.read_table(os.path.join(data_dir, table), columns=list(cols))
        for i, f in enumerate(t.schema):
            if pa.types.is_date32(f.type):
                t = t.set_column(i, f.name, t.column(i).cast(pa.int32()))
            elif pa.types.is_string(f.type):
                t = t.set_column(i, f.name, t.column(i).dictionary_encode())
        out[table] = t.to_pandas()
    return out


def run(name: str, data_dir: str, reads: Dict[str, List[str]],
        precision: str = "f64") -> pd.DataFrame:
    """The answer to text `name` over the files at `data_dir`. `reads` is
    the traffic file's list of the columns the text names. Top-level and of
    plain arguments: it runs in a worker process."""
    tables = {k: lower(v, precision) for k, v in load(data_dir, reads).items()}
    return lower(ANSWERS[name](tables), precision)
