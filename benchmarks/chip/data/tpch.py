"""TPC-H tables from a seed (dbgen-lite): the benchmark's own copy.

Copied from benchmarks/tpch/datagen.py (the program's tests import that one,
so later PRs may change it): dbgen's row counts, key relations and value
domains, not bit-identical to dbgen. Differences from the original, all for
set-up time: only the tables a cell lists are generated; a large table is cut
into `files` key ranges, one job and one file each; every (table, range) has
a random stream of its own, so `lineitem` needs only the order dates and not
the whole `orders` chunk; flag columns are dictionary takes, not numpy string
arrays. numpy and pyarrow only: a worker never imports JAX.
"""

from __future__ import annotations

import os
from typing import Dict, List, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from data.common import rng as _rng
from data.common import run_jobs

NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
COMMENT_WORDS = [
    "carefully", "quickly", "furiously", "slyly", "blithely", "special",
    "requests", "packages", "deposits", "accounts", "instructions", "pending",
    "unusual", "express", "regular", "ironic", "final", "bold", "silent",
    "even", "daring", "brave", "quiet", "complaints", "theodolites",
]

_EPOCH = np.datetime64("1970-01-01")
START = int((np.datetime64("1992-01-01") - _EPOCH).astype(np.int32))
END = int((np.datetime64("1998-08-02") - _EPOCH).astype(np.int32))
_CUTOFF = int((np.datetime64("1995-06-17") - _EPOCH).astype(np.int32))

# rows of each table at scale factor 1 (TPC-H specification, 4.2.5);
# lineitem follows from orders (1 to 7 lines each)
ROWS_AT_SF1 = {"customer": 150_000, "orders": 1_500_000, "part": 200_000,
               "supplier": 10_000}
LARGE = ("lineitem", "orders", "customer")
TABLES = LARGE + ("nation",)

_f = pa.field
SCHEMAS = {
    "customer": pa.schema([
        _f("c_custkey", pa.int64()), _f("c_name", pa.string()),
        _f("c_address", pa.string()), _f("c_nationkey", pa.int64()),
        _f("c_phone", pa.string()), _f("c_acctbal", pa.float64()),
        _f("c_mktsegment", pa.string()), _f("c_comment", pa.string()),
    ]),
    "orders": pa.schema([
        _f("o_orderkey", pa.int64()), _f("o_custkey", pa.int64()),
        _f("o_orderstatus", pa.string()), _f("o_totalprice", pa.float64()),
        _f("o_orderdate", pa.date32()), _f("o_orderpriority", pa.string()),
        _f("o_clerk", pa.string()), _f("o_shippriority", pa.int32()),
        _f("o_comment", pa.string()),
    ]),
    "lineitem": pa.schema([
        _f("l_orderkey", pa.int64()), _f("l_partkey", pa.int64()),
        _f("l_suppkey", pa.int64()), _f("l_linenumber", pa.int32()),
        _f("l_quantity", pa.float64()), _f("l_extendedprice", pa.float64()),
        _f("l_discount", pa.float64()), _f("l_tax", pa.float64()),
        _f("l_returnflag", pa.string()), _f("l_linestatus", pa.string()),
        _f("l_shipdate", pa.date32()), _f("l_commitdate", pa.date32()),
        _f("l_receiptdate", pa.date32()), _f("l_shipinstruct", pa.string()),
        _f("l_shipmode", pa.string()), _f("l_comment", pa.string()),
    ]),
    "nation": pa.schema([
        _f("n_nationkey", pa.int64()), _f("n_name", pa.string()),
        _f("n_regionkey", pa.int64()), _f("n_comment", pa.string()),
    ]),
}


def _take(pool: List[str], idx: np.ndarray) -> pa.Array:
    return pa.DictionaryArray.from_arrays(
        pa.array(idx, type=pa.int32()), pa.array(pool)).cast(pa.string())


def _comments(rng: np.random.Generator, n: int) -> pa.Array:
    w = [_take(COMMENT_WORDS, rng.integers(0, len(COMMENT_WORDS), n))
         for _ in range(3)]
    return pc.binary_join_element_wise(w[0], w[1], w[2], " ")


def _numbered(prefix: str, keys: np.ndarray) -> pa.Array:
    digits = pc.utf8_lpad(pa.array(keys).cast(pa.string()), 9, "0")
    return pc.binary_join_element_wise(
        pa.scalar(prefix + "#"), digits, "")


def _order_dates(seed: int, k: int, n: int) -> np.ndarray:
    """o_orderdate of key range k: a stream of its own, because `lineitem`
    derives its dates from it in a cell that never writes `orders`."""
    return _rng(seed, "o_orderdate", k).integers(START, END - 121, n).astype(np.int32)


def gen_nation() -> pa.Table:
    return pa.table({
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": pa.array([n for n, _ in NATIONS]),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int64),
        "n_comment": pa.array(["" for _ in NATIONS]),
    }, schema=SCHEMAS["nation"])


def gen_customer(sf: float, seed: int, k: int, lo: int, n: int) -> pa.Table:
    rng = _rng(seed, "customer", k)
    keys = np.arange(lo + 1, lo + n + 1, dtype=np.int64)
    nk = rng.integers(0, 25, n).astype(np.int64)
    phone = pc.binary_join_element_wise(
        pa.array(10 + nk).cast(pa.string()), pa.scalar("-467-109-8538"), "")
    return pa.table({
        "c_custkey": keys,
        "c_name": _numbered("Customer", keys),
        "c_address": _numbered("Addr", keys),
        "c_nationkey": nk,
        "c_phone": phone,
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "c_mktsegment": _take(SEGMENTS, rng.integers(0, len(SEGMENTS), n)),
        "c_comment": _comments(rng, n),
    }, schema=SCHEMAS["customer"])


def gen_orders(sf: float, seed: int, k: int, lo: int, n: int) -> pa.Table:
    rng = _rng(seed, "orders", k)
    n_cust = max(1, int(ROWS_AT_SF1["customer"] * sf))
    keys = np.arange(lo + 1, lo + n + 1, dtype=np.int64)
    # dbgen: only two thirds of the customers have orders
    ck = (rng.integers(0, max(1, n_cust * 2 // 3), n) * 3 % n_cust) + 1
    clerks = rng.integers(1, max(2, int(1000 * sf) + 1), n).astype(np.int64)
    return pa.table({
        "o_orderkey": keys,
        "o_custkey": ck.astype(np.int64),
        "o_orderstatus": _take(["O", "F", "P"], rng.integers(0, 3, n)),
        "o_totalprice": np.round(rng.uniform(850.0, 560_000.0, n), 2),
        "o_orderdate": pa.array(_order_dates(seed, k, n), type=pa.date32()),
        "o_orderpriority": _take(PRIORITIES, rng.integers(0, 5, n)),
        "o_clerk": _numbered("Clerk", clerks),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": _comments(rng, n),
    }, schema=SCHEMAS["orders"])


def gen_lineitem(sf: float, seed: int, k: int, lo: int, n: int) -> pa.Table:
    """The lines of the orders of key range k (`lo`, `n` are order keys)."""
    rng = _rng(seed, "lineitem", k)
    n_part = max(1, int(ROWS_AT_SF1["part"] * sf))
    n_supp = max(1, int(ROWS_AT_SF1["supplier"] * sf))
    okeys = np.arange(lo + 1, lo + n + 1, dtype=np.int64)
    lines_per = rng.integers(1, 8, n)
    lok = np.repeat(okeys, lines_per)
    lod = np.repeat(_order_dates(seed, k, n), lines_per)
    rows = len(lok)
    first = np.repeat(np.concatenate(([0], np.cumsum(lines_per)[:-1])), lines_per)
    linenumber = np.arange(rows, dtype=np.int64) - first + 1
    pk = rng.integers(1, n_part + 1, rows).astype(np.int64)
    # dbgen: one of the part's four suppliers
    sk = ((pk + rng.integers(0, 4, rows) * (n_supp // 4 + 1)) % n_supp) + 1
    qty = rng.integers(1, 51, rows).astype(np.float64)
    extprice = np.round(qty * (900 + (pk % 1000) / 10 + 100 * (pk % 10)), 2)
    ship = lod + rng.integers(1, 122, rows).astype(np.int32)
    commit = lod + rng.integers(30, 91, rows).astype(np.int32)
    receipt = ship + rng.integers(1, 31, rows).astype(np.int32)
    # R or A where the line was received by the cut-off, else N
    flag = np.where(receipt <= _CUTOFF, rng.integers(0, 2, rows), 2)
    status = (ship > _CUTOFF).astype(np.int32)
    return pa.table({
        "l_orderkey": lok,
        "l_partkey": pk,
        "l_suppkey": sk,
        "l_linenumber": linenumber.astype(np.int32),
        "l_quantity": qty,
        "l_extendedprice": extprice,
        "l_discount": np.round(rng.integers(0, 11, rows) / 100.0, 2),
        "l_tax": np.round(rng.integers(0, 9, rows) / 100.0, 2),
        "l_returnflag": _take(["R", "A", "N"], flag),
        "l_linestatus": _take(["F", "O"], status),
        "l_shipdate": pa.array(ship, type=pa.date32()),
        "l_commitdate": pa.array(commit, type=pa.date32()),
        "l_receiptdate": pa.array(receipt, type=pa.date32()),
        "l_shipinstruct": _take(INSTRUCTIONS, rng.integers(0, 4, rows)),
        "l_shipmode": _take(SHIPMODES, rng.integers(0, 7, rows)),
        "l_comment": _comments(rng, rows),
    }, schema=SCHEMAS["lineitem"])


_GEN = {"customer": gen_customer, "orders": gen_orders, "lineitem": gen_lineitem}


def _ranges(total: int, files: int) -> List[Tuple[int, int, int]]:
    """(k, lo, n) for `files` key ranges of `total` keys: a function of the
    configuration alone, never of the worker count."""
    files = max(1, min(files, total))
    step = -(-total // files)
    return [(k, k * step, min(step, total - k * step))
            for k in range(files) if total - k * step > 0]


def write_range(out_dir: str, table: str, sf: float, seed: int,
                k: int, lo: int, n: int) -> int:
    """Generate one key range of one table and write its file; returns the
    rows written. Top-level and of plain arguments: it runs in a worker."""
    t = _GEN[table](sf, seed, k, lo, n)
    pq.write_table(t, os.path.join(out_dir, table, f"part-{k:03d}.parquet"))
    return t.num_rows


def generate(out_dir: str, config: Dict[str, object], tables: Sequence[str],
             seed: int, workers: int) -> Dict[str, int]:
    """Write `tables` under `out_dir` (one directory each) at the
    configuration's scale; returns {table: rows}. The files are the same for
    any worker count."""
    sf = float(config["scale"])
    files = int(config["files_per_table"])
    unknown = [t for t in tables if t not in TABLES]
    if unknown:
        raise ValueError(f"tpch generator has no table {unknown}; it has {TABLES}")
    rows = {t: 0 for t in tables}
    jobs = []
    for table in LARGE:  # the longest jobs first
        if table not in tables:
            continue
        os.makedirs(os.path.join(out_dir, table), exist_ok=True)
        base = "orders" if table == "lineitem" else table
        total = max(1, int(ROWS_AT_SF1[base] * sf))
        jobs += [(out_dir, table, sf, seed, k, lo, n)
                 for k, lo, n in _ranges(total, files)]
    if "nation" in tables:
        os.makedirs(os.path.join(out_dir, "nation"), exist_ok=True)
        pq.write_table(gen_nation(), os.path.join(out_dir, "nation", "part-000.parquet"))
        rows["nation"] = 25
    for job, n in zip(jobs, run_jobs(write_range, jobs, workers)):
        rows[job[1]] += n
    return rows
