"""Mapped fact scan (ops/mappedscan.py): aggregate-over-join shapes factagg
excludes — multi-key fact joins (q7-q9) and dim-valued aggregate inputs /
fact-column group keys (q12) — rewritten to Aggregate(MappedScanExec) and
fused on the device. Reference executes these as join-materialize +
hash-aggregate (rust/core/src/serde/physical_plan/from_proto.rs:176-214)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.engine import ExecutionContext
from ballista_tpu.ops import kernels, mappedscan
from ballista_tpu.ops.mappedscan import Attachment, MappedScanExec
from ballista_tpu.ops.stage import FusedAggregateStage
from ballista_tpu.physical.plan import ExecutionPlan, Partitioning, TaskContext


@pytest.fixture(autouse=True)
def _fresh():
    kernels._stage_cache.clear()
    kernels._stage_cache_pins.clear()
    kernels._stage_latest.clear()
    yield


def _mapped_stages():
    return [
        s for s in kernels._stage_cache.values()
        if isinstance(s, FusedAggregateStage)
        and isinstance(s.scan, MappedScanExec)
    ]


def _write(tmp_path, name, table):
    p = tmp_path / f"{name}.parquet"
    pq.write_table(table, str(p))
    return str(p)


def _star(tmp_path, n_fact=30_000, n_dim=800, missing=50, seed=7):
    """Fact + dim where `missing` fact keys have NO dim row (inner join
    must drop those rows) + a second-level dim keyed on a DIM column."""
    rng = np.random.default_rng(seed)
    fact = pa.table(
        {
            "fk": pa.array(rng.integers(0, n_dim + missing, n_fact),
                           type=pa.int64()),
            "mode": pa.array([f"m{i % 5}" for i in range(n_fact)]),
            "amount": pa.array(rng.uniform(0, 100, n_fact)),
        }
    )
    dim = pa.table(
        {
            "dk": pa.array(np.arange(n_dim), type=pa.int64()),
            "prio": pa.array([f"p{i % 3}" for i in range(n_dim)]),
            "regionkey": pa.array(np.arange(n_dim, dtype=np.int64) % 7),
        }
    )
    region = pa.table(
        {
            "rk": pa.array(np.arange(7), type=pa.int64()),
            "rname": pa.array([f"region-{i}" for i in range(7)]),
        }
    )
    return (
        _write(tmp_path, "fact", fact),
        _write(tmp_path, "dim", dim),
        _write(tmp_path, "region", region),
        fact,
    )


def _ctx(backend, paths):
    ctx = ExecutionContext(
        BallistaConfig({"ballista.executor.backend": backend})
    )
    for name, p in paths.items():
        ctx.register_parquet(name, p)
    return ctx


Q_DIM_VALUED = """
    select mode,
           sum(case when prio = 'p0' then 1 else 0 end) as c0,
           sum(amount) as s
    from dim, fact
    where dk = fk
    group by mode
    order by mode
"""

# table order puts the fact join innermost, so region attaches through the
# dim-mapped `regionkey` column (a CHAINED attachment); the dim-valued
# aggregate input keeps factagg (which would otherwise claim this q10-like
# shape) out of the way
Q_CHAINED = """
    select rname, count(*) as c, sum(amount * (1 + regionkey)) as s
    from dim, fact, region
    where dk = fk and rk = regionkey
    group by rname
    order by rname
"""


def _run_both(paths, sql):
    out = {}
    for backend in ("tpu", "cpu"):
        out[backend] = _ctx(backend, paths).sql(sql).collect()
    return out["tpu"], out["cpu"]


def test_dim_valued_aggregate_inputs(tmp_path):
    """q12 shape: fact-column group key + aggregate over a dim string."""
    fp, dp, rp, _ = _star(tmp_path)
    t, c = _run_both({"fact": fp, "dim": dp}, Q_DIM_VALUED)
    assert _mapped_stages(), "mapped rewrite did not engage"
    assert t.column("mode").to_pylist() == c.column("mode").to_pylist()
    assert t.column("c0").to_pylist() == c.column("c0").to_pylist()
    np.testing.assert_allclose(
        t.column("s").to_numpy(), c.column("s").to_numpy(), rtol=1e-4
    )


def test_chained_attachment_and_membership(tmp_path):
    """q7 shape: a second dim keyed on a column the FIRST dim attached;
    fact rows with no dim match must drop (inner-join membership)."""
    fp, dp, rp, fact = _star(tmp_path)
    t, c = _run_both({"fact": fp, "dim": dp, "region": rp}, Q_CHAINED)
    assert _mapped_stages(), "mapped rewrite did not engage"
    assert t.column("rname").to_pylist() == c.column("rname").to_pylist()
    assert t.column("c").to_pylist() == c.column("c").to_pylist()
    # membership really dropped the missing-key rows
    assert sum(t.column("c").to_pylist()) < fact.num_rows
    np.testing.assert_allclose(
        t.column("s").to_numpy(), c.column("s").to_numpy(), rtol=1e-4
    )


def test_composite_key_attachment(tmp_path):
    """q9 shape: dim unique on a two-column key; out-of-range second
    components must not alias into other tuples."""
    rng = np.random.default_rng(3)
    n = 20_000
    fact = pa.table(
        {
            "k1": pa.array(rng.integers(0, 40, n), type=pa.int64()),
            # includes values beyond the dim's k2 range (0..19)
            "k2": pa.array(rng.integers(0, 30, n), type=pa.int64()),
            "v": pa.array(rng.uniform(0, 10, n)),
        }
    )
    dim_rows = [(a, b) for a in range(40) for b in range(20)]
    dim = pa.table(
        {
            "d1": pa.array([a for a, _ in dim_rows], type=pa.int64()),
            "d2": pa.array([b for _, b in dim_rows], type=pa.int64()),
            "cost": pa.array(
                [float(a * 100 + b) for a, b in dim_rows]
            ),
        }
    )
    paths = {
        "fact": _write(tmp_path, "fact", fact),
        "dim": _write(tmp_path, "dim", dim),
    }
    sql = (
        "select k1, sum(v * cost) as sc from dim, fact "
        "where d1 = k1 and d2 = k2 group by k1 order by k1"
    )
    t, c = _run_both(paths, sql)
    assert _mapped_stages(), "mapped rewrite did not engage"
    assert t.column("k1").to_pylist() == c.column("k1").to_pylist()
    np.testing.assert_allclose(
        t.column("sc").to_numpy(), c.column("sc").to_numpy(), rtol=1e-4
    )


def test_duplicate_dim_keys_fall_back_correctly(tmp_path):
    """A non-unique dim key multiplies rows; the mapped stage must decline
    at prepare and the host path must produce the multiplied result."""
    fact = pa.table(
        {
            "fk": pa.array([1, 1, 2], type=pa.int64()),
            "mode": pa.array(["a", "a", "b"]),
            "amount": pa.array([1.0, 2.0, 4.0]),
        }
    )
    dim = pa.table(
        {
            "dk": pa.array([1, 1, 2], type=pa.int64()),  # dup key 1
            "prio": pa.array(["p0", "p1", "p0"]),
        }
    )
    paths = {
        "fact": _write(tmp_path, "fact", fact),
        "dim": _write(tmp_path, "dim", dim),
    }
    sql = (
        "select mode, count(*) as c, sum(amount) as s from dim, fact "
        "where dk = fk group by mode order by mode"
    )
    t, c = _run_both(paths, sql)
    assert t.column("c").to_pylist() == c.column("c").to_pylist() == [4, 1]
    assert t.column("s").to_pylist() == c.column("s").to_pylist()


def test_null_fact_keys_drop(tmp_path):
    fact = pa.table(
        {
            "fk": pa.array([1, None, 2, None], type=pa.int64()),
            "mode": pa.array(["a", "a", "b", "b"]),
            "amount": pa.array([1.0, 2.0, 4.0, 8.0]),
        }
    )
    dim = pa.table(
        {
            "dk": pa.array([1, 2], type=pa.int64()),
            "prio": pa.array(["p0", "p1"]),
        }
    )
    paths = {
        "fact": _write(tmp_path, "fact", fact),
        "dim": _write(tmp_path, "dim", dim),
    }
    sql = (
        "select mode, sum(amount) as s from dim, fact "
        "where dk = fk group by mode order by mode"
    )
    t, c = _run_both(paths, sql)
    assert t.column("s").to_pylist() == c.column("s").to_pylist() == [1.0, 4.0]


def test_tpch_q7_q12_device_path(tmp_path):
    """The real TPC-H q7/q12 (and q8/q9 composite shapes) engage the mapped
    device path and match the host backend."""
    from benchmarks.tpch.datagen import generate, register_all

    d = tmp_path / "tpch"
    generate(str(d), sf=0.02, parts=1)
    results = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(
            BallistaConfig({"ballista.executor.backend": backend})
        )
        register_all(ctx, str(d))
        results[backend] = {}
        for q in ("q7", "q9", "q12"):
            sql = open(f"benchmarks/tpch/queries/{q}.sql").read()
            results[backend][q] = ctx.sql(sql).collect()
    assert len(_mapped_stages()) >= 3, "mapped rewrite did not engage"
    for q in ("q7", "q9", "q12"):
        t, c = results["tpu"][q], results["cpu"][q]
        assert t.num_rows == c.num_rows, q
        for name in t.schema.names:
            tv, cv = t.column(name).to_pylist(), c.column(name).to_pylist()
            if t.schema.field(name).type in (pa.float64(), pa.float32()):
                np.testing.assert_allclose(tv, cv, rtol=1e-3, err_msg=q)
            else:
                assert tv == cv, (q, name)


def test_multifile_fact_as_build_side(tmp_path):
    """The framework drives the join's PROBE-side partition count; with a
    multi-file fact on the BUILD side and a single-file dim probe, the
    rewritten stage must stripe every fact partition over the driven ones
    (missing the stride silently dropped all but one fact file)."""
    rng = np.random.default_rng(9)
    fdir = tmp_path / "factdir"
    fdir.mkdir()
    parts = []
    for p in range(3):
        n = 5000 + p * 100
        t = pa.table(
            {
                "fk": pa.array(rng.integers(0, 200, n), type=pa.int64()),
                "mode": pa.array([f"m{i % 4}" for i in range(n)]),
                "amount": pa.array(rng.uniform(0, 10, n)),
            }
        )
        pq.write_table(t, str(fdir / f"part-{p}.parquet"))
        parts.append(t)
    dim = pa.table(
        {
            "dk": pa.array(np.arange(200), type=pa.int64()),
            "prio": pa.array([f"p{i % 3}" for i in range(200)]),
        }
    )
    paths = {"fact": str(fdir), "dim": _write(tmp_path, "dim", dim)}
    # "from fact, dim" puts the multi-partition fact on the BUILD side
    sql = (
        "select mode, sum(case when prio = 'p1' then amount else 0 end) as s,"
        " count(*) as c from fact, dim where fk = dk "
        "group by mode order by mode"
    )
    t, c = _run_both(paths, sql)
    assert _mapped_stages(), "mapped rewrite did not engage"
    # counts cover ALL three fact files, not just partition 0
    assert sum(c.column("c").to_pylist()) == sum(p.num_rows for p in parts)
    assert t.column("c").to_pylist() == c.column("c").to_pylist()
    np.testing.assert_allclose(
        t.column("s").to_numpy(), c.column("s").to_numpy(), rtol=1e-4
    )


def test_float_min_equality_consumer_stays_exact(tmp_path):
    """TPC-H q2 shape: a decorrelated MIN(float) subquery whose result is
    equality-joined back against the source column. The device computes
    f32; the rounded min would match nothing — the rewrite must decline
    float MIN/MAX so the exact host value flows into the join."""
    rng = np.random.default_rng(21)
    n, nk = 8000, 400
    fact = pa.table(
        {
            "fk": pa.array(rng.integers(0, nk, n), type=pa.int64()),
            # 2-decimal "decimal" values: not exactly representable in f32
            "cost": pa.array(np.round(rng.uniform(1, 1000, n), 2)),
        }
    )
    dim = pa.table(
        {
            "dk": pa.array(np.arange(nk), type=pa.int64()),
            "attr": pa.array([f"a{i % 9}" for i in range(nk)]),
        }
    )
    paths = {
        "fact": _write(tmp_path, "fact", fact),
        "dim": _write(tmp_path, "dim", dim),
    }
    sql = (
        "select fk, cost from dim, fact where dk = fk and cost = ("
        "  select min(cost) from dim d2, fact f2 "
        "  where d2.dk = f2.fk and f2.fk = fact.fk"
        ") order by fk"
    )
    t, c = _run_both(paths, sql)
    assert c.num_rows >= nk  # sanity: the oracle finds every group's min
    assert t.num_rows == c.num_rows
    assert t.column("cost").to_pylist() == c.column("cost").to_pylist()


def test_semi_and_anti_membership(tmp_path):
    """q4 shape: EXISTS/NOT EXISTS become membership-only attachments —
    no columns, no uniqueness requirement, null fact keys follow SQL
    (never match; ANTI keeps them)."""
    fact = pa.table(
        {
            "fk": pa.array([1, 1, 2, 3, None, 5], type=pa.int64()),
            "mode": pa.array(["a", "b", "a", "b", "a", "b"]),
            "amount": pa.array([1.0, 2.0, 4.0, 8.0, 16.0, 32.0]),
        }
    )
    # duplicate + null keys on the membership side are fine
    sub = pa.table(
        {
            "sk": pa.array([1, 1, 3, None], type=pa.int64()),
            "x": pa.array([0.0, 1.0, 2.0, 3.0]),
        }
    )
    paths = {
        "fact": _write(tmp_path, "fact", fact),
        "sub": _write(tmp_path, "sub", sub),
    }
    for op, expected_s in (
        ("in", [1.0 + 2.0 + 8.0]),        # fk in (1, 3)
        ("not in", [4.0 + 32.0]),          # fk = 2, 5 (null fk never matches
                                           # EXISTS; NOT EXISTS keeps it —
                                           # but SQL [NOT] IN via EXISTS
                                           # decorrelation keeps nulls out)
    ):
        sql = (
            "select sum(amount) as s from fact "
            f"where fk {op} (select sk from sub where sk is not null)"
        )
        t, c = _run_both(paths, sql)
        assert c.column("s").to_pylist() == expected_s, op  # hand oracle
        assert t.column("s").to_pylist() == c.column("s").to_pylist(), op


def test_tpch_q4_device_path(tmp_path):
    from benchmarks.tpch.datagen import generate, register_all

    d = tmp_path / "tpch"
    generate(str(d), sf=0.02, parts=1)
    res = {}
    for backend in ("tpu", "cpu"):
        kernels._stage_cache.clear()
        ctx = ExecutionContext(
            BallistaConfig({"ballista.executor.backend": backend})
        )
        register_all(ctx, str(d))
        res[backend] = ctx.sql(
            open("benchmarks/tpch/queries/q4.sql").read()
        ).collect()
        if backend == "tpu":
            assert _mapped_stages(), "q4 did not engage the mapped path"
    t, c = res["tpu"], res["cpu"]
    assert t.column("o_orderpriority").to_pylist() == \
        c.column("o_orderpriority").to_pylist()
    assert t.column("order_count").to_pylist() == \
        c.column("order_count").to_pylist()


def test_composite_semi_keys_with_nulls(tmp_path):
    """Composite EXISTS keys whose dim side has nulls in DIFFERENT rows
    with equal per-column null counts: tuples must stay row-aligned (a
    per-column drop_null zipped phantom tuples)."""
    fact = pa.table(
        {
            "k1": pa.array([1, 3, 7], type=pa.int64()),
            "k2": pa.array([10, 20, 30], type=pa.int64()),
            "amount": pa.array([1.0, 2.0, 4.0]),
        }
    )
    sub = pa.table(
        {
            "s1": pa.array([1, None, 3], type=pa.int64()),
            "s2": pa.array([10, 20, None], type=pa.int64()),
        }
    )
    paths = {
        "fact": _write(tmp_path, "fact", fact),
        "sub": _write(tmp_path, "sub", sub),
    }
    # only (1, 10) is a fully-valid dim tuple -> only amount=1.0 survives
    sql = (
        "select sum(amount) as s from fact where exists ("
        "  select 1 from sub where s1 = k1 and s2 = k2)"
    )
    t, c = _run_both(paths, sql)
    assert c.column("s").to_pylist() == [1.0]
    assert t.column("s").to_pylist() == [1.0]


# ---------------------------------------------------------------------------
# how a map finds a key's dimension row (PR 32): by position table where the
# packed key range fits MAX_POSITION_ENTRIES, else a search of the sorted
# keys with the batch's needles in key order. Both against a plain reference
# that shares no code with _extend: a dict from key tuple to dimension row.
# ---------------------------------------------------------------------------


class _Rows(ExecutionPlan):
    """A table as a one-partition plan, in small batches."""

    def __init__(self, table, batch_rows=64):
        self.table, self.batch_rows = table, batch_rows

    def schema(self):
        return self.table.schema

    def output_partitioning(self):
        return Partitioning.unknown(1)

    def children(self):
        return []

    def with_children(self, children):
        return self

    def fmt(self):
        return "_Rows"

    def execute(self, partition, ctx):
        yield from self.table.to_batches(max_chunksize=self.batch_rows)


def _reference_extend(fact, atts):
    """(member, {mapped column: values}) by dict lookups, row by row. A row
    an inner attachment drops carries the values of the smallest key's row
    (what the gather leaves under a cleared `__member`); a later attachment
    keyed on a mapped column looks that value up, as the scan does."""
    lookups = []
    for dim, fact_keys, dim_keys, kind in atts:
        rows = dim.to_pylist()
        keyed = {tuple(r[k] for k in dim_keys): r for r in rows
                 if None not in [r[k] for k in dim_keys]}
        lookups.append((fact_keys, kind, keyed, keyed[min(keyed)] if keyed else None))
    member, mapped = [], {}
    for r in fact.to_pylist():
        keep = True
        for fact_keys, kind, keyed, smallest in lookups:
            key = tuple(r[k] for k in fact_keys)
            found = keyed.get(key)  # a null component is in no dict key
            if kind == "anti":
                keep = keep and found is None
            else:
                keep = keep and found is not None
            if kind == "inner":
                r.update(found if found is not None else smallest)
        member.append(int(keep))
        for name, v in r.items():
            if name not in fact.schema.names:
                mapped.setdefault(name, []).append(v)
    return member, mapped


def _ints(values):
    return pa.array(values, type=pa.int64())


def _single_key_case(dim_keys, fact_keys):
    dim = pa.table({"dk": _ints(dim_keys),
                    "dv": pa.array([f"v{k}" for k in dim_keys]),
                    "dn": _ints([k * 10 for k in dim_keys])})
    fact = pa.table({"fk": _ints(fact_keys),
                     "x": pa.array(np.arange(len(fact_keys), dtype=np.float64))})
    return fact, [(dim, ["fk"], ["dk"], "inner")]


def _two_column_case(span):
    """A dimension unique on (a, b) with each component `span` wide: fact
    pairs that hit, pairs whose components each exist but never together,
    and pairs out of range on one side."""
    rng = np.random.default_rng(11)
    a = rng.choice(span, 40, replace=False)
    b = rng.choice(span, 40, replace=False)
    dim = pa.table({"da": _ints(a), "db": _ints(b),
                    "cost": pa.array(rng.uniform(1, 9, 40))})
    pick = rng.integers(0, 40, 300)
    fa, fb = a[pick], b[pick]
    fb = np.where(np.arange(300) % 5 == 0, b[(pick + 1) % 40], fb)  # no such pair
    fa = np.where(np.arange(300) % 7 == 0, span + 3, fa)  # above the range
    fact = pa.table({"fa": _ints(fa), "fb": _ints(fb)})
    return fact, [(dim, ["fa", "fb"], ["da", "db"], "inner")]


def _chained_case():
    """A second dimension keyed on a column the first one maps (q7, q9's
    nation through s_nationkey): a row the first drops looks up the
    smallest key's value, a row the second drops stays dropped."""
    dim = pa.table({"dk": _ints([5, 3, 9, 7]), "rk": _ints([2, 0, 1, 4])})
    region = pa.table({"r": _ints([0, 1, 2]), "rname": pa.array(["zero", "one", "two"])})
    fact = pa.table({"fk": _ints([3, 5, 7, 9, 4, 3, 11, 5])})
    return fact, [(dim, ["fk"], ["dk"], "inner"), (region, ["rk"], ["r"], "inner")]


def _membership_case(kind, sub_keys):
    sub = pa.table({"sk": _ints(sub_keys)})
    fact = pa.table({"fk": _ints([1, 1, 2, 3, None, 5, 8, 0]),
                     "x": pa.array(np.arange(8, dtype=np.float64))})
    return fact, [(sub, ["fk"], ["sk"], kind)]


_SCRAMBLED = [40, 7, 23, 15, 2, 31, 11, 19]
# name -> (case, MAX_POSITION_ENTRIES for the case or None, maps answered by position)
EXTEND_CASES = {
    "dense_single_key": (_single_key_case(list(range(10, 90)), list(range(100)) * 3), None, 1),
    # the same keys with the bound below their range of 80
    "single_key_beyond_the_bound": (
        _single_key_case(list(range(10, 90)), list(range(100)) * 3), 79, 0),
    "two_column_key_dense": (_two_column_case(60), None, 1),
    # 10**6 x 10**6 packed values: no position table at the real bound
    "two_column_key_wide": (_two_column_case(10 ** 6), None, 0),
    "chained_through_a_mapped_column": (_chained_case(), None, 2),
    "chained_beyond_the_bound": (_chained_case(), 1, 0),
    "null_fact_keys": (_single_key_case([4, 2, 6], [2, None, 6, None, 4, 2]), None, 1),
    "null_fact_keys_beyond_the_bound": (
        _single_key_case([4, 2, 6], [2, None, 6, None, 4, 2]), 2, 0),
    # below, above and between the dimension's keys
    "fact_keys_outside_and_between": (
        _single_key_case([10, 20, 30], [5, 10, 15, 20, 25, 30, 35, -1, 10 ** 12]), None, 1),
    "fact_keys_outside_and_between_beyond_the_bound": (
        _single_key_case([10, 20, 30], [5, 10, 15, 20, 25, 30, 35, -1, 10 ** 12]), 20, 0),
    "semi": (_membership_case("semi", [1, 1, 3, 8]), None, 1),
    "semi_beyond_the_bound": (_membership_case("semi", [1, 1, 3, 8]), 7, 0),
    "anti": (_membership_case("anti", [1, 1, 3, 8]), None, 1),
    "anti_beyond_the_bound": (_membership_case("anti", [1, 1, 3, 8]), 7, 0),
    "semi_with_an_empty_dimension": (_membership_case("semi", []), None, 1),
    "dimension_not_in_key_order": (_single_key_case(_SCRAMBLED, sorted(_SCRAMBLED) * 2 + [3]), None, 1),
    "dimension_not_in_key_order_beyond_the_bound": (
        _single_key_case(_SCRAMBLED, sorted(_SCRAMBLED) * 2 + [3]), 38, 0),
}


def _mapped_scan(fact, atts):
    return MappedScanExec(_Rows(fact), [
        Attachment(_Rows(dim), fact_keys, dim_keys, kind=kind)
        for dim, fact_keys, dim_keys, kind in atts])


@pytest.mark.parametrize("name", EXTEND_CASES)
def test_extend_against_a_dict_reference(name, monkeypatch):
    (fact, atts), bound, want_dense = EXTEND_CASES[name]
    if bound is not None:
        monkeypatch.setattr(mappedscan, "MAX_POSITION_ENTRIES", bound)
    scan = _mapped_scan(fact, atts)
    maps = scan._ensure_maps(TaskContext())
    assert sum(m["pos"] is not None for m in maps) == want_dense
    got = pa.Table.from_batches(
        [scan._extend(b, maps) for b in fact.to_batches(max_chunksize=64)])
    assert got.schema == scan.schema()
    for field in fact.schema:  # the fact's own columns pass through
        assert got.column(field.name).equals(fact.column(field.name))
    member, mapped = _reference_extend(fact, atts)
    assert got.column("__member").to_pylist() == member
    assert set(mapped) == set(got.schema.names) - set(fact.schema.names) - {"__member"}
    # every row, dropped rows too: those carry the smallest key's values
    for column, want in mapped.items():
        assert got.column(column).to_pylist() == want, column
    # the case is one: some rows stay and some go (none stays on an empty dimension)
    assert sum(member) < len(member)
    assert (sum(member) > 0) == all(dim.num_rows for dim, *_ in atts)


def test_the_two_ways_sum_to_map_rows_and_the_span_says_dense():
    """One scan with a map of each way: `device.map_dense_rows` and
    `.map_sorted_rows` count fact rows x attachments answered by position
    table and by search, and every per-batch span carries `dense`."""
    from ballista_tpu.utils import tracing

    fact, (narrow,) = _two_column_case(60)
    _, (wide,) = _two_column_case(10 ** 6)
    # the wide dimension's columns renamed so that both attach to one fact
    wide_dim = wide[0].rename_columns(["wa", "wb", "wcost"])
    atts = [narrow, (wide_dim, ["fa", "fb"], ["wa", "wb"], "inner"),
            (pa.table({"sk": _ints([1, 2])}), ["fa"], ["sk"], "semi")]
    scan = _mapped_scan(fact, atts)
    tracing.reset()
    out = pa.Table.from_batches(list(scan.execute(0, TaskContext())))
    counters = tracing.counters()
    spans = [s for s in tracing.spans() if s.name == "runtime.dim_build"]
    tracing.reset()
    assert out.num_rows == fact.num_rows == 300
    assert counters["device.map_rows"] == 3 * 300
    assert counters["device.map_dense_rows"] == 2 * 300
    assert counters["device.map_sorted_rows"] == 1 * 300
    assert len(spans) == 1 + 5 and all(s.attrs["dense"] == 2 for s in spans)
    gathers = [s for s in spans if "fact_rows" in s.attrs]
    assert sum(s.attrs["fact_rows"] * s.attrs["dense"] for s in gathers) == 600


# ---------------------------------------------------------------------------
# the unrolled engine gathers a scan partition's batches into entries of
# bounded padding: a cached parquet read hands a file over in chunks
# (131,072 rows, or a row group) that to_batches never merges, and run()
# launches, reads back and assembles a partial table once an entry
# ---------------------------------------------------------------------------


def _chunked_star(tmp_path, n_files=2, rows=4000, group_rows=1000, units_max=1000,
                  fk_max=850, seed=5):
    """A fact of `n_files` files whose row groups of `group_rows` a cached
    read hands over as that many batches, and an 800-row dimension (fact
    keys from `fk_max` on match none)."""
    rng = np.random.default_rng(seed)
    fdir = tmp_path / "fact"
    fdir.mkdir()
    for f in range(n_files):
        t = pa.table({
            "fk": pa.array(rng.integers(0, fk_max, rows), type=pa.int64()),
            "g": pa.array(np.arange(rows, dtype=np.int64) // 2),
            "units": pa.array(rng.integers(0, units_max + 1, rows), type=pa.int64()),
            "amount": pa.array(rng.uniform(-50, 100, rows)),
        })
        pq.write_table(t, str(fdir / f"part-{f}.parquet"), row_group_size=group_rows)
    dim = pa.table({"dk": pa.array(np.arange(800), type=pa.int64()),
                    "prio": pa.array([f"p{i % 3}" for i in range(800)])})
    return {"fact": str(fdir), "dim": _write(tmp_path, "dim", dim)}


def _run_traced(paths, sql, settings=()):
    """(tpu answer, cpu answer, the tpu run's counters, its `runtime.launch`
    spans) with fresh layout directories, so the stage prepares here."""
    from ballista_tpu.utils import tracing

    out = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(BallistaConfig({
            "ballista.executor.backend": backend,
            "ballista.tpu.layout_cache_dir": "", **dict(settings)}))
        for name, p in paths.items():
            ctx.register_parquet(name, p)
        tracing.reset()
        out[backend] = ctx.sql(sql).collect()
        if backend == "tpu":
            counters = tracing.counters()
            launches = [s for s in tracing.spans() if s.name == "runtime.launch"]
    tracing.reset()
    return out["tpu"], out["cpu"], counters, launches


def _prepared(stage):
    return list(stage._device_cache.values())


def test_one_entry_and_one_launch_a_scan_file(tmp_path):
    """Two files of four row groups each, about 4,000 rows a file, within an
    eighth of their 4,096-row bucket: the unrolled stage holds one entry a
    file and launches once a file, and answers as the host does for a
    float SUM, an integer SUM, a COUNT, float MIN / MAX (the bijection) and
    a dimension-valued group key (the dimension-valued input keeps the fact
    aggregate, which would take a dimension key alone, out of the way)."""
    paths = _chunked_star(tmp_path)
    sql = ("select prio, sum(amount) as s, sum(units) as u, count(*) as c, "
           "min(amount) as lo, max(amount) as hi, "
           "sum(case when prio = 'p2' then amount else 0 end) as s2 "
           "from dim, fact where dk = fk group by prio order by prio")
    t, c, counters, launches = _run_traced(paths, sql)
    (stage,) = _mapped_stages()
    prepared = _prepared(stage)
    assert {p["kind"] for p in prepared} == {"batches"}
    assert sum(len(p["entries"]) for p in prepared) == 2
    assert len(launches) == 2
    assert counters["device.gathered_batches_in"] == 8
    assert counters["device.gathered_batches_out"] == 2
    assert not counters.get("device.host_fallback")
    assert t.column("prio").to_pylist() == c.column("prio").to_pylist() == ["p0", "p1", "p2"]
    for name in ("u", "c"):
        assert t.column(name).to_pylist() == c.column(name).to_pylist(), name
    for name in ("lo", "hi"):
        for a, b in zip(t.column(name).to_pylist(), c.column(name).to_pylist()):
            assert np.float64(a).tobytes() == np.float64(b).tobytes(), name
    for name in ("s", "s2"):
        np.testing.assert_allclose(t.column(name).to_numpy(), c.column(name).to_numpy(),
                                   rtol=1e-4, err_msg=name)


def test_an_integer_sum_past_the_int32_bound_is_cut_not_declined(tmp_path):
    """Each 1,000-row group of `units` (at most 10**6) passes the int32 sum
    bound and a whole 5,000-row file does not: the file's batches are
    gathered while the bound holds, two at a time (2,147 rows at most), and
    the stage stays on the device with the exact sum."""
    paths = _chunked_star(tmp_path, rows=5000, units_max=10 ** 6)
    sql = ("select prio, sum(units) as u, "
           "sum(case when prio = 'p0' then amount else 0 end) as s "
           "from dim, fact where dk = fk group by prio order by prio")
    t, c, counters, launches = _run_traced(paths, sql)
    (stage,) = _mapped_stages()
    entries = [e for p in _prepared(stage) for e in p["entries"]]
    pieces = sorted(int(np.asarray(e["row_valid"]).sum()) for e in entries)
    assert pieces == sorted([2000, 2000, 1000] * 2)
    assert len(launches) == 6
    assert counters["device.gathered_batches_in"] == 10
    assert counters["device.gathered_batches_out"] == 6
    assert not counters.get("device.host_fallback")
    assert t.column("u").to_pylist() == c.column("u").to_pylist()
    np.testing.assert_allclose(t.column("s").to_numpy(), c.column("s").to_numpy(), rtol=1e-4)


def test_a_gathered_batch_past_max_groups_reaches_the_sorted_engine(tmp_path):
    """500 groups a row group, 2,000 a file: the gathered batch passes the
    unrolled engine's MAX_GROUPS, and the stage prepares sorted instead,
    with the host's answer."""
    from ballista_tpu.ops.stage import MAX_GROUPS

    paths = _chunked_star(tmp_path)
    sql = ("select g, sum(case when prio = 'p1' then amount else 0 end) as s, "
           "count(*) as c from dim, fact where dk = fk group by g order by g")
    t, c, counters, _ = _run_traced(paths, sql)
    assert 2000 > MAX_GROUPS > 500
    (stage,) = _mapped_stages()
    assert {p["kind"] for p in _prepared(stage)} == {"sorted"}
    assert not counters.get("device.host_fallback")
    assert t.column("g").to_pylist() == c.column("g").to_pylist()
    assert t.column("c").to_pylist() == c.column("c").to_pylist()
    np.testing.assert_allclose(t.column("s").to_numpy(), c.column("s").to_numpy(),
                               rtol=1e-4, atol=1e-9)


def test_gathered_batches_renumber_groups_and_bound_the_entry():
    """Two batches whose batch-local group codes differ: the entry numbers
    the groups that occur, batch by batch in the order of their batch-local
    codes, joins the lowered columns, and
    takes no batch of another partition, past the row limit or past the
    int32 sum bound."""
    from ballista_tpu.ops.stage import _GatheredBatches

    held = _GatheredBatches(part=3)
    # batch 1: keys (a, x), (a, y), (b, x) by codes 0..2; (b, y) = 3 occurs not
    held.add(3, {0: np.array([1, 2, 3], dtype=np.int32)},
             np.array([2, 0, 0], dtype=np.int32),
             [pa.array(["a", "a", "b", "b"]), pa.array(["x", "y", "x", "y"])], 4,
             {"v": 1000})
    # batch 2: its own codes, (b, x) again and (c, z) new
    held.add(2, {0: np.array([4, 5], dtype=np.int32)},
             np.array([1, 0], dtype=np.int32),
             [pa.array(["c", "b"]), pa.array(["z", "x"])], 2, {"v": 600})
    ((rows, cols, codes, key_values, n_groups),) = held.entries()
    assert n_groups == 3 and rows == held.rows == 5
    assert cols[0].tolist() == [1, 2, 3, 4, 5]
    keys = list(zip(*(kv.to_pylist() for kv in key_values)))
    assert keys == [("a", "x"), ("b", "x"), ("c", "z")]
    assert [keys[c] for c in codes] == [("b", "x"), ("a", "x"), ("a", "x"),
                                        ("b", "x"), ("c", "z")]
    assert held.fits(3, 595, {}, limit=600)
    assert not held.fits(4, 1, {}, limit=600)  # another scan partition
    assert not held.fits(3, 596, {}, limit=600)  # past the batch size
    assert not held.fits(3, 596, {"v": 10 ** 6}, limit=10 ** 6)  # past v's 600
    assert not held.fits(3, 96, {"w": 100}, limit=10 ** 6)  # past the batch's own


def test_a_memory_scan_gathers_its_batches_into_one_entry():
    """A memory table's five 200-row batches become one entry and one
    launch, with the host's answer."""
    from ballista_tpu.utils import tracing

    rng = np.random.default_rng(3)
    batches = [pa.record_batch({"k": pa.array(rng.integers(0, 4, 200), type=pa.int64()),
                                "v": pa.array(rng.uniform(0, 1, 200))})
               for _ in range(5)]
    table = pa.Table.from_batches(batches)
    ctx = ExecutionContext(BallistaConfig({"ballista.executor.backend": "tpu"}))
    ctx.register_record_batches("t", table)
    tracing.reset()
    got = ctx.sql("select k, sum(v) as s from t group by k order by k").collect()
    launches = [s for s in tracing.spans() if s.name == "runtime.launch"]
    counters = tracing.counters()
    tracing.reset()
    stages = [s for s in kernels._stage_cache.values() if isinstance(s, FusedAggregateStage)]
    assert [len(p["entries"]) for s in stages for p in _prepared(s)] == [1]
    assert len(launches) == 1
    assert counters["device.gathered_batches_in"] == 5
    assert counters["device.gathered_batches_out"] == 1
    want = table.group_by("k").aggregate([("v", "sum")]).sort_by("k")
    assert got.column("k").to_pylist() == want.column("k").to_pylist()
    np.testing.assert_allclose(got.column("s").to_numpy(),
                               want.column("v_sum").to_numpy(), rtol=1e-5)


def _device_bytes(entries):
    """What _prepare_partition counts against the HBM budget for `entries`:
    the padded columns (and their lookup tables), the codes and row_valid."""
    total = 0
    for e in entries:
        for col in e["cols"].values():
            total += sum(a.nbytes for a in (col if isinstance(col, tuple) else (col,)))
        total += 3 * len(e["row_valid"])
    return total


def test_a_file_just_past_a_power_of_two_pads_a_little_and_stays_resident(
        tmp_path, monkeypatch):
    """A file of nine 1,024-row groups and a 100-row one, 9,316 rows: as one
    entry it would pad to 16,384 rows. Gathered with every bucket past the
    least one subject to the padding bound (the bound's scale shrunk to
    this file's), it is an entry of 8,192 rows and one of 1,124 (2,048
    padded), 10% padding; under a budget that holds that layout with a
    quarter to spare, and not a 16,384-row entry, the stage stays on the
    device with the host's answers."""
    from ballista_tpu.ops import runtime
    from ballista_tpu.ops import stage as stage_mod
    from ballista_tpu.ops.stage import _tight_runs

    monkeypatch.setattr(stage_mod, "_WHOLE_BUCKET_ROWS", 1024)

    paths = _chunked_star(tmp_path, n_files=1, rows=9 * 1024 + 100, group_rows=1024,
                          fk_max=800)
    # a dimension-valued input keeps the fact aggregate out of the way
    sql = ("select prio, sum(units) as u, count(*) as c, "
           "sum(case when prio = 'p2' then amount else 0 end) as s "
           "from dim, fact where dk = fk group by prio order by prio")
    t, c, counters, launches = _run_traced(paths, sql)
    (stage,) = _mapped_stages()
    entries = [e for p in _prepared(stage) for e in p["entries"]]
    assert [int(np.asarray(e["row_valid"]).sum()) for e in entries] == [8192, 1124]
    assert [len(e["row_valid"]) for e in entries] == [8192, 2048]
    assert counters["device.gathered_batches_in"] == 10
    assert counters["device.gathered_batches_out"] == 2
    # a budget with a quarter to spare over these entries (16,384 padded
    # rows in one entry would take 1.6 times their bytes), and no pin of
    # another test's stages in it
    budget = _device_bytes(entries) * 5 // 4
    kernels._stage_cache.clear()
    kernels._stage_cache_pins.clear()
    runtime.reset_residency()
    t, c, counters, launches = _run_traced(
        paths, sql, {"ballista.tpu.hbm_budget_bytes": str(budget)})
    (stage,) = _mapped_stages()
    assert {p["kind"] for p in _prepared(stage)} == {"batches"}
    assert len(launches) == 2 and not counters.get("device.host_fallback")
    for name in ("prio", "u", "c"):
        assert t.column(name).to_pylist() == c.column(name).to_pylist(), name
    np.testing.assert_allclose(t.column("s").to_numpy(), c.column("s").to_numpy(), rtol=1e-4)
    # the runs themselves: a 131,072-row chunk file just past 2^23 rows
    chunks = [131072] * 68 + [87104]
    runs = _tight_runs(chunks)
    assert [sum(chunks[lo:hi]) for lo, hi in runs] == [2 ** 23, 2 ** 19, 87104]
    assert _tight_runs([1000] * 5) == [(0, 4), (4, 5)]  # 5,120 rows padded, not 8,192
    assert _tight_runs([600, 600]) == [(0, 2)]  # two entries would pad as much: no cut
    assert _tight_runs([3, 2]) == [(0, 2)]  # under the least bucket: no cut


def test_a_gathered_run_of_a_small_bucket_stays_one_entry():
    """Padding is bounded where it costs device memory: a run whose bucket
    is at most _WHOLE_BUCKET_ROWS stays one entry (58 filtered chunks of
    670 rows, 38,860 rows in a 65,536-row bucket: one launch, not three),
    and a larger one is cut (a file of 131,072-row chunks just past 2^23
    rows: 2^23 and a tail within its 2^20-row bucket)."""
    from ballista_tpu.ops.stage import _WHOLE_BUCKET_ROWS, _tight_runs

    assert _WHOLE_BUCKET_ROWS == 2 ** 20
    assert _tight_runs([670] * 58) == [(0, 58)]
    assert _tight_runs([131072] * 4 + [87104]) == [(0, 5)]  # 611,392 rows in 2^20
    assert _tight_runs([131072] * 8 + [87104]) == [(0, 8), (8, 9)]  # 2^20 + 2^17, not 2^21
    chunks = [131072] * 68 + [87104]
    runs = _tight_runs(chunks)
    assert [sum(chunks[lo:hi]) for lo, hi in runs] == [2 ** 23, 4 * 131072 + 87104]
