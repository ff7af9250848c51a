"""Fact-side aggregation pushdown (ops/factagg.py): Aggregate over a PK-FK
join runs as host-dim + device fact partials + (optional) device top-k."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.engine import ExecutionContext
from ballista_tpu.ops import kernels


@pytest.fixture
def star(tmp_path):
    """Fact table (20k rows, 3k distinct keys) + dim table (unique key)."""
    rng = np.random.default_rng(5)
    nf, nk = 20_000, 3000
    fact = pa.table(
        {
            "fk": pa.array(rng.integers(0, nk, nf), type=pa.int64()),
            "amount": pa.array(np.round(rng.uniform(1, 500, nf), 2)),
            "disc": pa.array(np.round(rng.uniform(0, 0.1, nf), 3)),
            "flag": pa.array(rng.integers(0, 2, nf), type=pa.int64()),
        }
    )
    dim = pa.table(
        {
            "dk": pa.array(np.arange(nk), type=pa.int64()),
            "attr": pa.array([f"grp-{i % 37}" for i in range(nk)]),
            "region": pa.array([f"r{i % 5}" for i in range(nk)]),
        }
    )
    pq.write_table(fact, str(tmp_path / "fact.parquet"))
    pq.write_table(dim, str(tmp_path / "dim.parquet"))
    return tmp_path


def _ctx(backend, star, settings=None):
    ctx = ExecutionContext(
        BallistaConfig({"ballista.executor.backend": backend, **(settings or {})})
    )
    ctx.register_parquet("fact", str(star / "fact.parquet"))
    ctx.register_parquet("dim", str(star / "dim.parquet"))
    return ctx


Q_TOPK = """
    select fk, sum(amount * (1 - disc)) as rev, attr
    from dim, fact
    where dk = fk and flag = 1
    group by fk, attr
    order by rev desc
    limit 15
"""

Q_FULL = """
    select fk, sum(amount) as s, count(amount) as c, avg(amount) as a, attr
    from dim, fact
    where dk = fk
    group by fk, attr
    order by fk
"""


def _factagg_stages():
    from ballista_tpu.ops.factagg import FactAggregateStage

    return [
        s for s in kernels._stage_cache.values()
        if isinstance(s, FactAggregateStage)
    ]


def test_topk_pushdown_matches_host(star):
    kernels._stage_cache.clear()
    t = _ctx("tpu", star).sql(Q_TOPK).collect()
    h = _ctx("host", star).sql(Q_TOPK).collect()
    assert t.column("fk").to_pylist() == h.column("fk").to_pylist()
    assert t.column("attr").to_pylist() == h.column("attr").to_pylist()
    np.testing.assert_allclose(
        t.column("rev").to_numpy(), h.column("rev").to_numpy(), rtol=1e-4
    )
    stages = _factagg_stages()
    assert stages and stages[0].topk is not None, "top-k epilogue not engaged"


def test_full_select_matches_host(star):
    kernels._stage_cache.clear()
    t = _ctx("tpu", star).sql(Q_FULL).collect()
    h = _ctx("host", star).sql(Q_FULL).collect()
    assert t.num_rows == h.num_rows  # keys present in fact (~3000)
    assert t.num_rows > 2900
    assert t.column("fk").to_pylist() == h.column("fk").to_pylist()
    assert t.column("attr").to_pylist() == h.column("attr").to_pylist()
    assert t.column("c").to_pylist() == h.column("c").to_pylist()
    np.testing.assert_allclose(
        t.column("s").to_numpy(), h.column("s").to_numpy(), rtol=1e-4, atol=1e-3
    )
    np.testing.assert_allclose(
        t.column("a").to_numpy(), h.column("a").to_numpy(), rtol=1e-4, atol=1e-4
    )
    stages = _factagg_stages()
    assert stages and stages[0].topk is None  # member-select path


def test_duplicate_dim_keys_fall_back_to_host(star, tmp_path):
    """A dim side with duplicate join keys multiplies fact rows; the
    pushdown must decline and the host join must produce the answer."""
    rng = np.random.default_rng(6)
    dim2 = pa.table(
        {
            "dk": pa.array(np.concatenate([np.arange(3000), [0, 1, 2]]),
                           type=pa.int64()),
            "attr": pa.array([f"a{i}" for i in range(3003)]),
        }
    )
    pq.write_table(dim2, str(tmp_path / "dim2.parquet"))
    sql = """
        select fk, sum(amount) as s, attr from dim2, fact
        where dk = fk group by fk, attr order by fk, attr
    """
    outs = {}
    for backend in ("tpu", "host"):
        ctx = _ctx(backend, star)
        ctx.register_parquet("dim2", str(tmp_path / "dim2.parquet"))
        outs[backend] = ctx.sql(sql).collect()
    assert outs["tpu"].column("fk").to_pylist() == outs["host"].column("fk").to_pylist()
    np.testing.assert_allclose(
        outs["tpu"].column("s").to_numpy(), outs["host"].column("s").to_numpy(),
        rtol=1e-4, atol=1e-3,
    )


def test_no_match_keys_empty_result(star):
    sql = """
        select fk, sum(amount) as s from dim, fact
        where dk = fk and dk > 100000 group by fk
    """
    t = _ctx("tpu", star).sql(sql).collect()
    assert t.num_rows == 0


def test_topk_over_integer_sum(star):
    """ORDER BY SUM(int_col) LIMIT k: the device score must decode BOTH
    packed halves — ranking by the hi half alone collapses sums below 65536
    into ties (review regression)."""
    kernels._stage_cache.clear()
    sql = """
        select fk, sum(flag) as nf from dim, fact
        where dk = fk group by fk order by nf desc limit 10
    """
    t = _ctx("tpu", star).sql(sql).collect()
    h = _ctx("host", star).sql(sql).collect()
    assert t.column("nf").to_pylist() == h.column("nf").to_pylist()
    stages = _factagg_stages()
    assert stages and stages[0].topk is not None


def test_nested_dim_joins_group_by_dim_only(star, tmp_path):
    """q10 shape: the fact is nested under TWO dim joins and the group keys
    are all dim attributes (no fact key) — many fact keys fold into one
    output group, so factagg's per-key top-k must never rank it. The ladder
    now prefers the mapped rewrite here when ITS fused epilogue is live
    (it groups directly by the output keys, so the O(limit) readback is
    sound); either way the answer must match the host."""
    rng = np.random.default_rng(9)
    # dimA: dk -> ck (FK into dimB); dimB: ck -> cattr. group by cattr only.
    dimA = pa.table(
        {
            "dk": pa.array(np.arange(3000), type=pa.int64()),
            "ck": pa.array(rng.integers(0, 50, 3000), type=pa.int64()),
        }
    )
    dimB = pa.table(
        {
            "ck2": pa.array(np.arange(50), type=pa.int64()),
            "cattr": pa.array([f"c{i}" for i in range(50)]),
        }
    )
    pq.write_table(dimA, str(tmp_path / "dimA.parquet"))
    pq.write_table(dimB, str(tmp_path / "dimB.parquet"))
    sql = """
        select cattr, sum(amount) as s, count(*) as n
        from dimB, dimA, fact
        where ck2 = ck and dk = fk
        group by cattr
        order by s desc
        limit 12
    """
    kernels._stage_cache.clear()
    outs = {}
    for backend in ("tpu", "host"):
        ctx = _ctx(backend, star)
        ctx.register_parquet("dimA", str(tmp_path / "dimA.parquet"))
        ctx.register_parquet("dimB", str(tmp_path / "dimB.parquet"))
        outs[backend] = ctx.sql(sql).collect()
    t, h = outs["tpu"], outs["host"]
    np.testing.assert_allclose(
        t.column("s").to_numpy(), h.column("s").to_numpy(), rtol=1e-4
    )
    assert t.column("n").to_pylist() == h.column("n").to_pylist()
    assert t.column("cattr").to_pylist() == h.column("cattr").to_pylist()
    from ballista_tpu.ops.factagg import FactAggregateStage

    stages = [s for s in kernels._stage_cache.values() if s]
    assert stages, "device path did not engage"
    if isinstance(stages[0], FactAggregateStage):
        # factagg served it: per-key top-k must be OFF (dim-only grouping
        # would rank per-fact-key partials, the wrong quantity)
        assert stages[0].topk is None
    else:
        # the mapped rewrite won the ladder precisely because its fused
        # top-k ranks the OUTPUT groups
        assert stages[0].topk is not None


def test_planner_annotates_topk(star):
    ctx = _ctx("host", star)
    df = ctx.sql(Q_TOPK)
    plan = ctx.create_physical_plan(df.logical_plan())
    from ballista_tpu.physical.aggregate import HashAggregateExec

    def find(node):
        if isinstance(node, HashAggregateExec):
            return node
        for c in node.children():
            r = find(c)
            if r is not None:
                return r
        return None

    agg = find(plan)
    assert agg is not None
    tk = getattr(agg, "_topk_pushdown", None)
    assert tk == {
        "agg_index": 0, "descending": True, "k": 15, "strict": False,
        # multi-key extension: the resolved sort-key prefix and whether it
        # covers the whole ORDER BY (ops/stage.py's fused epilogue)
        "keys": [{"agg_index": 0, "descending": True}], "covered": True,
    }


def test_topk_int_sum_f32_collapse_boundary(tmp_path):
    """Integer SUM scores rank as f32 on device; above 2^24 distinct sums
    collapse into false ties (ADVICE r2). A collapse run spanning the
    candidate-pool boundary must fall back to the host plan, not silently
    return a smaller true sum."""
    import pyarrow.parquet as pq

    base = 1 << 25  # f32 ulp here is 4: base and base+1 collapse
    G = 4000
    sums = np.full(G, base, dtype=np.int64)
    sums[:5] = base + 1000 * (np.arange(5) + 1)  # distinct in f32
    # true 6th-largest f32-ties the base crowd; its HIGH index keeps it out
    # of the (index-stable) device top-k unless the tie check fires
    sums[G - 1] = base + 1
    rng = np.random.default_rng(0)
    fact = pa.table(
        {
            "fk": pa.array(np.arange(G), type=pa.int64()),
            "amount": pa.array(sums, type=pa.int64()),
            # incompressible filler so the fact file outweighs the dim file
            # (fact selection picks the largest scan chain)
            "pad1": pa.array(rng.uniform(0, 1, G)),
            "pad2": pa.array(rng.uniform(0, 1, G)),
            "pad3": pa.array(rng.uniform(0, 1, G)),
        }
    )
    dim = pa.table({"dk": pa.array(np.arange(G), type=pa.int64()),
                    "attr": pa.array([f"a{i}" for i in range(G)])})
    pq.write_table(fact, str(tmp_path / "fact.parquet"))
    pq.write_table(dim, str(tmp_path / "dim.parquet"))
    kernels._stage_cache.clear()
    sql = """
        select fk, sum(amount) as s, attr from dim, fact
        where dk = fk group by fk, attr order by s desc limit 10
    """
    # unit level: the device stage builds, runs the top-k path, and DECLINES
    # on the collapsed tie at the pool boundary instead of returning rows
    from ballista_tpu.ops.factagg import FactAggregateStage
    from ballista_tpu.ops.runtime import UnsupportedOnDevice
    from ballista_tpu.physical.aggregate import HashAggregateExec
    from ballista_tpu.physical.plan import TaskContext

    ctx = _ctx("tpu", tmp_path)
    cfg = ctx.config
    phys = ctx.create_physical_plan(ctx.sql(sql).logical_plan())

    def find_agg(n):
        if isinstance(n, HashAggregateExec):
            return n
        for c in n.children():
            r = find_agg(c)
            if r is not None:
                return r
        return None

    stage = FactAggregateStage(find_agg(phys))
    assert stage.topk is not None
    tctx = TaskContext(config=cfg, work_dir=str(tmp_path), job_id="t")
    with pytest.raises(UnsupportedOnDevice, match="tie at candidate boundary"):
        stage.run(0, tctx)

    # end to end the decline lands on the host plan: values match exactly.
    # The top-6 values are unique ints; equal-sum tail rows may tiebreak on
    # any key, so compare the VALUE lists.
    t = ctx.sql(sql).collect()
    h = _ctx("host", tmp_path).sql(sql).collect()
    assert t.column("s").to_pylist() == h.column("s").to_pylist()
    assert (base + 1) in t.column("s").to_pylist()


@pytest.fixture
def coupled_star(tmp_path):
    """q5-shaped schema: fact joins a secondary dim on a fact column, with
    an attribute coupling between primary and secondary dims."""
    rng = np.random.default_rng(11)
    n_orders, n_supp, nf = 900, 50, 24_000
    orders = pa.table(
        {
            "o_key": pa.array(np.arange(n_orders), type=pa.int64()),
            "o_flag": pa.array(rng.integers(0, 2, n_orders), type=pa.int64()),
            "c_nat": pa.array(rng.integers(0, 8, n_orders), type=pa.int64()),
        }
    )
    supplier = pa.table(
        {
            "s_key": pa.array(np.arange(n_supp), type=pa.int64()),
            "s_nat": pa.array(rng.integers(0, 8, n_supp), type=pa.int64()),
        }
    )
    nation = pa.table(
        {
            "nat_key": pa.array(np.arange(8), type=pa.int64()),
            "nat_name": pa.array([f"nation-{i}" for i in range(8)]),
            "nat_region": pa.array([i % 2 for i in range(8)], type=pa.int64()),
        }
    )
    fact = pa.table(
        {
            "f_okey": pa.array(rng.integers(0, n_orders, nf), type=pa.int64()),
            "f_skey": pa.array(rng.integers(0, n_supp, nf), type=pa.int64()),
            "amount": pa.array(np.round(rng.uniform(1, 100, nf), 2)),
        }
    )
    pq.write_table(fact, str(tmp_path / "fact.parquet"))
    pq.write_table(orders, str(tmp_path / "orders.parquet"))
    pq.write_table(supplier, str(tmp_path / "supplier.parquet"))
    pq.write_table(nation, str(tmp_path / "nation.parquet"))
    return tmp_path


Q_COUPLED = """
    select nat_name, sum(amount) as rev
    from orders, fact, supplier, nation
    where o_key = f_okey and f_skey = s_key and c_nat = s_nat
      and s_nat = nat_key and nat_region = 1 and o_flag = 1
    group by nat_name
    order by nat_name
"""


def _coupled_ctx(backend, star, settings=None):
    ctx = ExecutionContext(
        BallistaConfig({"ballista.executor.backend": backend, **(settings or {})})
    )
    for t in ("fact", "orders", "supplier", "nation"):
        ctx.register_parquet(t, str(star / f"{t}.parquet"))
    return ctx


def test_coupled_secondary_dim_matches_host(coupled_star):
    """q5 shape: upper join keyed on a fact column with a primary<->secondary
    attribute coupling runs per-class on device (static mapped column)."""
    kernels._stage_cache.clear()
    t = _coupled_ctx("tpu", coupled_star).sql(Q_COUPLED).collect()
    h = _coupled_ctx("cpu", coupled_star).sql(Q_COUPLED).collect()
    stages = _factagg_stages()
    assert stages and stages[0].secondary is not None
    assert t.column("nat_name").to_pylist() == h.column("nat_name").to_pylist()
    np.testing.assert_allclose(
        np.array(t.column("rev").to_pylist()),
        np.array(h.column("rev").to_pylist()), rtol=1e-4,
    )


def test_coupled_secondary_impure_filter_falls_back(coupled_star):
    """A secondary-side filter that is NOT a pure function of the coupling
    attribute (here: on s_key itself) invalidates the static map — the
    stage must decline and the host fallback must stay correct."""
    sql = Q_COUPLED.replace("and o_flag = 1", "and o_flag = 1 and s_key < 25")
    kernels._stage_cache.clear()
    t = _coupled_ctx("tpu", coupled_star).sql(sql).collect()
    h = _coupled_ctx("cpu", coupled_star).sql(sql).collect()
    assert t.column("nat_name").to_pylist() == h.column("nat_name").to_pylist()
    np.testing.assert_allclose(
        np.array(t.column("rev").to_pylist()),
        np.array(h.column("rev").to_pylist()), rtol=1e-4,
    )


def test_semi_join_folds_into_membership(tmp_path):
    """q18 shape: a SEMI join above the fact's inner join folds whole into
    the dim-plan membership and the aggregation stays on device."""
    rng = np.random.default_rng(17)
    n_orders, nf = 600, 18_000
    orders = pa.table(
        {
            "o_key": pa.array(np.arange(n_orders), type=pa.int64()),
            "o_name": pa.array([f"o{i}" for i in range(n_orders)]),
        }
    )
    fact = pa.table(
        {
            "f_okey": pa.array(rng.integers(0, n_orders, nf), type=pa.int64()),
            "qty": pa.array(np.round(rng.uniform(1, 50, nf), 2)),
        }
    )
    pq.write_table(fact, str(tmp_path / "fact.parquet"))
    pq.write_table(orders, str(tmp_path / "orders.parquet"))
    sql = """
        select o_name, o_key, sum(qty) as s
        from orders, fact
        where o_key = f_okey
          and o_key in (select f_okey from fact group by f_okey
                        having sum(qty) > 800)
        group by o_name, o_key
        order by o_key
    """
    outs = {}
    for backend in ("tpu", "cpu"):
        kernels._stage_cache.clear()
        ctx = ExecutionContext(BallistaConfig({"ballista.executor.backend": backend}))
        ctx.register_parquet("fact", str(tmp_path / "fact.parquet"))
        ctx.register_parquet("orders", str(tmp_path / "orders.parquet"))
        outs[backend] = ctx.sql(sql).collect()
        if backend == "tpu":
            stages = _factagg_stages()
            assert stages, "device stage did not build for the semi fold"
    t, h = outs["tpu"], outs["cpu"]
    assert t.num_rows == h.num_rows > 0
    assert t.column("o_key").to_pylist() == h.column("o_key").to_pylist()
    np.testing.assert_allclose(
        np.array(t.column("s").to_pylist()),
        np.array(h.column("s").to_pylist()), rtol=1e-4,
    )


def test_fact_partitions_differ_from_driven_partitions(tmp_path):
    """A single-partition probe side with a multi-partition fact build side
    plans a SINGLE aggregate with NO merge — the fact stage must stripe
    every fact file into its one driven partition (reading only file p was
    a silent 1/N-of-the-data bug). Also covers the inverse shape (more
    probe partitions than fact files)."""
    import numpy as np
    import pyarrow.parquet as pq

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.engine import ExecutionContext

    rng = np.random.default_rng(11)
    n = 40_000
    (tmp_path / "sales").mkdir()
    for p in range(4):
        t = pa.table({
            "cust": rng.integers(0, 500, n // 4),
            "amount": rng.uniform(1, 1000, n // 4),
        })
        pq.write_table(t, str(tmp_path / "sales" / f"part-{p}.parquet"))
    (tmp_path / "cust").mkdir()
    pq.write_table(
        pa.table({"c_id": np.arange(500)}), str(tmp_path / "cust" / "p0.parquet")
    )
    (tmp_path / "cust8").mkdir()
    for p in range(8):
        pq.write_table(
            pa.table({"c_id": np.arange(500)}).slice(p * 63, 63),
            str(tmp_path / "cust8" / f"part-{p}.parquet"),
        )

    full = pq.read_table(str(tmp_path / "sales")).to_pandas()
    want = full.groupby("cust").amount.sum().sort_index()
    topw = full.groupby("cust").amount.sum().nlargest(5)

    from ballista_tpu.ops import kernels, runtime
    from ballista_tpu.ops.factagg import FactAggregateStage

    kernels._stage_cache.clear()
    kernels._stage_cache_pins.clear()
    kernels._stage_latest.clear()
    runtime.reset_residency()
    for dim, probe_parts in (("cust", 1), ("cust8", 8)):
        for backend in ("cpu", "tpu"):
            ctx = ExecutionContext(
                BallistaConfig({"ballista.executor.backend": backend})
            )
            ctx.register_parquet("sales", str(tmp_path / "sales"))
            ctx.register_parquet(dim, str(tmp_path / dim))
            out = (
                ctx.sql(
                    f"select cust, sum(amount) as rev from sales, {dim} "
                    "where c_id = cust group by cust"
                )
                .collect().to_pandas().set_index("cust").rev.sort_index()
            )
            np.testing.assert_allclose(
                out.to_numpy(), want.to_numpy(), rtol=1e-4,
                err_msg=f"{backend}/{dim}",
            )
            top = ctx.sql(
                f"select cust, sum(amount) as rev from sales, {dim} "
                "where c_id = cust group by cust order by rev desc limit 5"
            ).collect().to_pandas()
            assert list(top.cust) == list(topw.index), (backend, dim)
    # the device fact-agg path must have RUN with striped fact reads (a
    # silent host fallback would also produce matching results)
    ran = [
        s for s in kernels._stage_cache.values()
        if isinstance(s, FactAggregateStage) and s._prepared
    ]
    assert ran, "device fact-agg stage did not run"
    assert any(s.inner.scan_stride is not None for s in ran)


def test_date_minmax_through_factagg(tmp_path):
    """MIN/MAX over a fact-side date32 column through the fact-agg pushdown
    (the partial assembly crashed casting double -> date32 before the
    shared state_column helper)."""
    rng = np.random.default_rng(8)
    nf, nk = 20_000, 2000
    fact = pa.table(
        {
            "fk": pa.array(rng.integers(0, nk, nf), type=pa.int64()),
            "amount": pa.array(rng.uniform(1, 100, nf)),
            "ship": pa.array(
                rng.integers(8000, 12000, nf), type=pa.int32()
            ).cast(pa.date32()),
        }
    )
    dim = pa.table(
        {
            "dk": pa.array(np.arange(nk), type=pa.int64()),
            "attr": pa.array([f"a{i % 11}" for i in range(nk)]),
        }
    )
    pq.write_table(fact, str(tmp_path / "fact.parquet"))
    pq.write_table(dim, str(tmp_path / "dim.parquet"))
    kernels._stage_cache.clear()
    res = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(
            BallistaConfig({"ballista.executor.backend": backend})
        )
        ctx.register_parquet("fact", str(tmp_path / "fact.parquet"))
        ctx.register_parquet("dim", str(tmp_path / "dim.parquet"))
        res[backend] = ctx.sql(
            "select fk, min(ship) as mn, max(ship) as mx, attr "
            "from dim, fact where dk = fk group by fk, attr order by fk"
        ).collect()
    assert _factagg_stages(), "fact-agg stage not engaged"
    t, c = res["tpu"], res["cpu"]
    assert t.column("mn").to_pylist() == c.column("mn").to_pylist()
    assert t.column("mx").to_pylist() == c.column("mx").to_pylist()


# -- rank maps kept with the prepared partition --------------------------
# A stage's rank maps (which fact key ranks the dimension side holds, the
# dimension row or coupling value at each) are a pure function of the
# prepared partition and the dimension side: built by the first query, kept
# inside the partition's pinned entry, served to every later one.

PARTS = 3


@pytest.fixture
def star_parts(tmp_path):
    """The star as directories of PARTS files each: with the aggregates not
    coalesced, one partial aggregate (and one prepared partition) a file."""
    rng = np.random.default_rng(5)
    nf, nk = 8_000, 3000
    (tmp_path / "fact").mkdir()
    (tmp_path / "dim").mkdir()
    for p in range(PARTS):
        pq.write_table(
            pa.table({
                "fk": pa.array(rng.integers(0, nk, nf), type=pa.int64()),
                "amount": pa.array(np.round(rng.uniform(1, 500, nf), 2)),
            }),
            str(tmp_path / "fact" / f"part-{p}.parquet"),
        )
        keys = np.arange(nk)[p::PARTS]
        pq.write_table(
            pa.table({
                "dk": pa.array(keys, type=pa.int64()),
                "attr": pa.array([f"grp-{i % 37}" for i in keys]),
            }),
            str(tmp_path / "dim" / f"part-{p}.parquet"),
        )
    return tmp_path


def _parts_ctx(backend, star, settings=None):
    ctx = ExecutionContext(BallistaConfig({
        "ballista.executor.backend": backend,
        "ballista.tpu.coalesce_aggregates": "false", **(settings or {})}))
    ctx.register_parquet("fact", str(star / "fact"))
    ctx.register_parquet("dim", str(star / "dim"))
    return ctx


def _rewrite_star_dim(star):
    """Every third key leaves the dimension, the others change groups."""
    keys = np.arange(3000)[np.arange(3000) % 3 != 1]
    pq.write_table(
        pa.table({
            "dk": pa.array(keys, type=pa.int64()),
            "attr": pa.array([f"new-{i % 11}" for i in keys]),
            "region": pa.array([f"r{i % 5}" for i in keys]),
        }),
        str(star / "dim.parquet"),
    )
    return star / "dim.parquet"


def _rewrite_parts_dim(star):
    keys = np.arange(3000)[1::PARTS][::2]
    pq.write_table(
        pa.table({
            "dk": pa.array(keys, type=pa.int64()),
            "attr": pa.array([f"new-{i % 11}" for i in keys]),
        }),
        str(star / "dim" / "part-1.parquet"),
    )
    return star / "dim" / "part-1.parquet"


def _rewrite_coupled_orders(star):
    """The primary side's coupling values and its filter column redrawn."""
    rng = np.random.default_rng(99)
    pq.write_table(
        pa.table({
            "o_key": pa.array(np.arange(900), type=pa.int64()),
            "o_flag": pa.array(rng.integers(0, 2, 900), type=pa.int64()),
            "c_nat": pa.array(rng.integers(0, 8, 900), type=pa.int64()),
        }),
        str(star / "orders.parquet"),
    )
    return star / "orders.parquet"


# path -> (fixture, context, text, partitions, exact columns, float columns,
#          the rewrite of a DIMENSION file that changes the answer)
_RANK_MAP_PATHS = {
    "coupled_secondary": ("coupled_star", _coupled_ctx, Q_COUPLED, 1,
                          ["nat_name"], ["rev"], _rewrite_coupled_orders),
    "topk": ("star", _ctx, Q_TOPK, 1, ["fk", "attr"], ["rev"], _rewrite_star_dim),
    "member_select": ("star_parts", _parts_ctx, Q_FULL.replace("avg(amount) as a, ", ""),
                      PARTS, ["fk", "attr", "c"], ["s"], _rewrite_parts_dim),
}


def _fresh_stages():
    from ballista_tpu.ops import runtime

    kernels._stage_cache.clear()
    kernels._stage_cache_pins.clear()
    kernels._stage_latest.clear()
    runtime.reset_residency()


def _traced(ctx, sql):
    """(table, counters, the `cached` of each runtime.dim_build that says)."""
    from ballista_tpu.utils import tracing

    tracing.reset()
    table = ctx.sql(sql).collect()
    cached = [s.attrs["cached"] for s in tracing.spans()
              if s.name == "runtime.dim_build" and "cached" in s.attrs]
    counters = tracing.counters()
    tracing.reset()
    return table, counters, cached


def _the_stage(path):
    (stage,) = _factagg_stages()
    assert (stage.secondary is not None) == (path == "coupled_secondary")
    assert (stage.topk is not None) == (path == "topk")
    return stage


def _assert_same_answer(got, want, exact, floats):
    assert got.num_rows == want.num_rows > 0
    for name in exact:
        assert got.column(name).to_pylist() == want.column(name).to_pylist(), name
    for name in floats:
        np.testing.assert_allclose(
            np.array(got.column(name).to_pylist()),
            np.array(want.column(name).to_pylist()), rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("path", list(_RANK_MAP_PATHS))
def test_rank_maps_are_built_by_the_first_query_and_serve_the_later_ones(path, request):
    fixture, make_ctx, sql, parts, exact, floats, _ = _RANK_MAP_PATHS[path]
    star = request.getfixturevalue(fixture)
    _fresh_stages()
    ctx = make_ctx("tpu", star)
    (first, counters, cached), *later = [_traced(ctx, sql) for _ in range(3)]
    stage = _the_stage(path)
    assert sorted(stage._prepared) == list(range(parts))
    assert counters.get("device.rank_map_build") == parts
    assert "device.rank_map_hit" not in counters
    assert cached.count(False) >= parts
    for table, counters, cached in later:
        assert counters.get("device.rank_map_hit") == parts
        assert "device.rank_map_build" not in counters
        assert cached and all(cached)
        # the same program over the same arguments: column for column
        assert table.equals(first)
    _assert_same_answer(first, make_ctx("cpu", star).sql(sql).collect(), exact, floats)


@pytest.mark.parametrize("path", list(_RANK_MAP_PATHS))
def test_nothing_is_kept_with_the_device_cache_off(path, request):
    from ballista_tpu.ops import runtime

    fixture, make_ctx, sql, parts, exact, floats, _ = _RANK_MAP_PATHS[path]
    star = request.getfixturevalue(fixture)
    _fresh_stages()
    kept = make_ctx("tpu", star).sql(sql).collect()
    _fresh_stages()
    ctx = make_ctx("tpu", star, {"ballista.tpu.device_cache": "false"})
    for _ in range(2):
        table, counters, cached = _traced(ctx, sql)
        assert counters.get("device.rank_map_build") == parts
        assert "device.rank_map_hit" not in counters
        assert cached and not any(cached)
        assert table.equals(kept)
    stage = _the_stage(path)
    assert not stage._prepared and stage._dim_cache is None
    assert runtime.resident_bytes() == 0


@pytest.mark.parametrize("path", list(_RANK_MAP_PATHS))
def test_a_rewritten_dimension_file_is_answered_from_the_new_rows(path, request):
    import os

    from ballista_tpu.ops import runtime

    fixture, make_ctx, sql, parts, exact, floats, rewrite = _RANK_MAP_PATHS[path]
    star = request.getfixturevalue(fixture)
    _fresh_stages()
    ctx = make_ctx("tpu", star)
    before = [_traced(ctx, sql)[0] for _ in range(2)][-1]
    old = _the_stage(path)
    assert all("rank_maps" in ent for ent in old._prepared.values())

    changed = rewrite(star)
    stamp = os.path.getmtime(changed) + 2  # past any clock's granularity
    os.utime(changed, (stamp, stamp))
    want = make_ctx("cpu", star).sql(sql).collect()
    table, counters, _ = _traced(make_ctx("tpu", star), sql)
    _assert_same_answer(table, want, exact, floats)
    assert not table.equals(before), "the rewrite did not change the answer"
    # the stage was superseded whole: no map of the old rows is reachable
    assert old._retired and not old._prepared
    new = _the_stage(path)
    assert new is not old
    assert counters.get("device.rank_map_build") == parts
    assert "device.rank_map_hit" not in counters
    assert runtime.resident_bytes() == sum(
        runtime.entry_device_bytes(ent) for ent in new._prepared.values())


@pytest.mark.parametrize("how", ["evicted", "released"])
@pytest.mark.parametrize("path", list(_RANK_MAP_PATHS))
def test_the_maps_go_with_their_partition_and_are_built_again(path, how, request):
    from ballista_tpu.ops import runtime

    fixture, make_ctx, sql, parts, exact, floats, _ = _RANK_MAP_PATHS[path]
    star = request.getfixturevalue(fixture)
    _fresh_stages()
    ctx = make_ctx("tpu", star)
    first = [_traced(ctx, sql)[0] for _ in range(2)][-1]
    stage = _the_stage(path)
    held = runtime.resident_bytes()
    assert held == sum(runtime.entry_device_bytes(e) for e in stage._prepared.values())
    assert sum(runtime.entry_device_bytes(e["rank_maps"])
               for e in stage._prepared.values()) > 0
    if how == "evicted":
        # another stage asks for all of a budget this one fills
        runtime.make_headroom(object(), held, held)
    else:
        runtime.release_stage_residency(stage)
    assert runtime.resident_bytes() == 0 and not stage._prepared
    table, counters, _ = _traced(ctx, sql)
    assert counters.get("device.rank_map_build") == parts
    assert "device.rank_map_hit" not in counters
    assert table.equals(first)
    if how == "evicted":
        assert runtime.resident_bytes() == held
        assert _traced(ctx, sql)[1].get("device.rank_map_hit") == parts
    else:
        # a retired stage pins nothing again
        assert runtime.resident_bytes() == 0


def test_concurrent_first_queries_keep_one_set_of_maps_a_partition(star_parts):
    """Queries that meet on a stage nobody has run yet may each build a
    partition's maps: every one answers the same, and what stays reserved is
    one set a partition."""
    import threading

    from ballista_tpu.ops import runtime

    _, make_ctx, sql, parts, *_ = _RANK_MAP_PATHS["member_select"]
    _fresh_stages()
    tables, errors = [], []

    def client():
        try:
            ctx = make_ctx("tpu", star_parts)
            tables.extend(ctx.sql(sql).collect() for _ in range(3))
        except Exception as e:  # the assertion below reports it
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    assert len(tables) == 12 and all(t.equals(tables[0]) for t in tables)
    stage = _the_stage("member_select")
    assert sorted(stage._prepared) == list(range(parts))
    assert all("rank_maps" in ent for ent in stage._prepared.values())
    assert runtime.resident_bytes() == sum(
        runtime.entry_device_bytes(ent) for ent in stage._prepared.values())
