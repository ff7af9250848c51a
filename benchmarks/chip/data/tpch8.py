"""All eight TPC-H tables from a seed (dbgen-lite): `customer`, `orders`,
`lineitem`, `nation` are data/tpch.py's, file for file; `part`, `supplier`,
`partsupp`, `region` are written here.

What the texts select on is the specification's (rev 3.0.1, 4.2.3, 4.2.5):
row counts; `p_name` five different words of the 92 colours; `p_type` one of
the 150 three-word types; `s_nationkey` uniform over 25; `ps_supplycost`
1.00 to 1000.00; four `partsupp` rows a part, whose `ps_suppkey` are the four
suppliers data/tpch.py draws a line's `l_suppkey` from; `region` and
`n_regionkey` as the specification's tables. A stream per (table, key
range), so the files are the same for any worker count. numpy and pyarrow
only: a worker never imports JAX.
"""

from __future__ import annotations

import os
from typing import Dict, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from data import tpch
from data.common import rng as _rng
from data.common import run_jobs
from data.tpch import _comments, _numbered, _ranges, _take

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
COLOURS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod", "green",
    "grey", "honeydew", "hot", "indian", "ivory", "khaki", "lace",
    "lavender", "lawn", "lemon", "light", "lime", "linen", "magenta", "maroon",
    "medium", "metallic", "midnight", "mint", "misty", "moccasin", "navajo",
    "navy", "olive", "orange", "orchid", "pale", "papaya", "peach", "peru",
    "pink", "plum", "powder", "puff", "purple", "red", "rose", "rosy", "royal",
    "saddle", "salmon", "sandy", "seashell", "sienna", "sky", "slate", "smoke",
    "snow", "spring", "steel", "tan", "thistle", "tomato", "turquoise",
    "violet", "wheat", "white", "yellow",
]
TYPE_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINERS = [f"{a} {b}"
              for a in ["SM", "LG", "MED", "JUMBO", "WRAP"]
              for b in ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]]
NAME_WORDS = 5

# cut into `files_per_table` key ranges (partsupp's are ranges of part keys)
RANGED = ("partsupp", "part", "supplier")
TABLES = tpch.TABLES + RANGED + ("region",)

_f = pa.field
SCHEMAS = {
    "part": pa.schema([
        _f("p_partkey", pa.int64()), _f("p_name", pa.string()),
        _f("p_mfgr", pa.string()), _f("p_brand", pa.string()),
        _f("p_type", pa.string()), _f("p_size", pa.int32()),
        _f("p_container", pa.string()), _f("p_retailprice", pa.float64()),
        _f("p_comment", pa.string()),
    ]),
    "supplier": pa.schema([
        _f("s_suppkey", pa.int64()), _f("s_name", pa.string()),
        _f("s_address", pa.string()), _f("s_nationkey", pa.int64()),
        _f("s_phone", pa.string()), _f("s_acctbal", pa.float64()),
        _f("s_comment", pa.string()),
    ]),
    "partsupp": pa.schema([
        _f("ps_partkey", pa.int64()), _f("ps_suppkey", pa.int64()),
        _f("ps_availqty", pa.int32()), _f("ps_supplycost", pa.float64()),
        _f("ps_comment", pa.string()),
    ]),
    "region": pa.schema([
        _f("r_regionkey", pa.int64()), _f("r_name", pa.string()),
        _f("r_comment", pa.string()),
    ]),
}


def _rows(table: str, sf: float) -> int:
    return max(1, int(tpch.ROWS_AT_SF1[table] * sf))


def _different_words(rng: np.random.Generator, n: int, pool: int, k: int) -> np.ndarray:
    """[n, k] indices into a pool, the k of a row all different: the j-th is
    drawn from the pool less the j before it."""
    picks = np.empty((n, k), dtype=np.int64)
    for j in range(k):
        x = rng.integers(0, pool - j, n)
        for earlier in np.sort(picks[:, :j], axis=1).T:
            x += x >= earlier
        picks[:, j] = x
    return picks


def gen_region() -> pa.Table:
    return pa.table({
        "r_regionkey": np.arange(len(REGIONS), dtype=np.int64),
        "r_name": pa.array(REGIONS),
        "r_comment": pa.array(["" for _ in REGIONS]),
    }, schema=SCHEMAS["region"])


def gen_supplier(sf: float, seed: int, k: int, lo: int, n: int) -> pa.Table:
    rng = _rng(seed, "supplier", k)
    keys = np.arange(lo + 1, lo + n + 1, dtype=np.int64)
    nk = rng.integers(0, 25, n).astype(np.int64)
    phone = pc.binary_join_element_wise(
        pa.array(10 + nk).cast(pa.string()), pa.scalar("-989-741-2988"), "")
    return pa.table({
        "s_suppkey": keys,
        "s_name": _numbered("Supplier", keys),
        "s_address": _numbered("Addr", keys),
        "s_nationkey": nk,
        "s_phone": phone,
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "s_comment": _comments(rng, n),
    }, schema=SCHEMAS["supplier"])


def gen_part(sf: float, seed: int, k: int, lo: int, n: int) -> pa.Table:
    rng = _rng(seed, "part", k)
    keys = np.arange(lo + 1, lo + n + 1, dtype=np.int64)
    words = _different_words(rng, n, len(COLOURS), NAME_WORDS)
    name = pc.binary_join_element_wise(
        *[_take(COLOURS, words[:, j]) for j in range(NAME_WORDS)], " ")
    m = rng.integers(1, 6, n)
    brand = pc.binary_join_element_wise(
        pa.scalar("Brand#"), pa.array(m * 10 + rng.integers(1, 6, n)).cast(pa.string()), "")
    mfgr = pc.binary_join_element_wise(
        pa.scalar("Manufacturer#"), pa.array(m).cast(pa.string()), "")
    ptype = pc.binary_join_element_wise(
        _take(TYPE_1, rng.integers(0, len(TYPE_1), n)),
        _take(TYPE_2, rng.integers(0, len(TYPE_2), n)),
        _take(TYPE_3, rng.integers(0, len(TYPE_3), n)), " ")
    return pa.table({
        "p_partkey": keys,
        "p_name": name,
        "p_mfgr": mfgr,
        "p_brand": brand,
        "p_type": ptype,
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": _take(CONTAINERS, rng.integers(0, len(CONTAINERS), n)),
        # the price data/tpch.py builds l_extendedprice from
        "p_retailprice": np.round(900 + (keys % 1000) / 10 + 100 * (keys % 10), 2),
        "p_comment": _comments(rng, n),
    }, schema=SCHEMAS["part"])


def gen_partsupp(sf: float, seed: int, k: int, lo: int, n: int) -> pa.Table:
    """Four rows for each part of key range k (`lo`, `n` are part keys)."""
    rng = _rng(seed, "partsupp", k)
    n_supp = _rows("supplier", sf)
    pk = np.repeat(np.arange(lo + 1, lo + n + 1, dtype=np.int64), 4)
    j = np.tile(np.arange(4, dtype=np.int64), n)
    # the four suppliers data/tpch.py's gen_lineitem draws l_suppkey from
    sk = ((pk + j * (n_supp // 4 + 1)) % n_supp) + 1
    rows = len(pk)
    return pa.table({
        "ps_partkey": pk,
        "ps_suppkey": sk,
        "ps_availqty": rng.integers(1, 10_000, rows).astype(np.int32),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, rows), 2),
        "ps_comment": _comments(rng, rows),
    }, schema=SCHEMAS["partsupp"])


_GEN = {"part": gen_part, "supplier": gen_supplier, "partsupp": gen_partsupp}


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
        raise ValueError(f"tpch8 generator has no table {unknown}; it has {TABLES}")
    rows = tpch.generate(out_dir, config, [t for t in tables if t in tpch.TABLES],
                         seed, workers)
    jobs = []
    for table in RANGED:  # the longest jobs first
        if table not in tables:
            continue
        os.makedirs(os.path.join(out_dir, table), exist_ok=True)
        total = _rows("part" if table == "partsupp" else table, sf)
        jobs += [(out_dir, table, sf, seed, k, lo, n)
                 for k, lo, n in _ranges(total, files)]
        rows[table] = 0
    if "region" in tables:
        os.makedirs(os.path.join(out_dir, "region"), exist_ok=True)
        pq.write_table(gen_region(), os.path.join(out_dir, "region", "part-000.parquet"))
        rows["region"] = len(REGIONS)
    for job, n in zip(jobs, run_jobs(write_range, jobs, workers)):
        rows[job[1]] += n
    return rows
