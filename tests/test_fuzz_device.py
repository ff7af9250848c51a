"""Seeded randomized differential testing: random aggregation (and
aggregate-over-join) queries run on BOTH backends and must agree.

The q2 regression (f32 device MIN feeding an equality join) was caught by
a broad differential sweep, not by the targeted suites — this keeps a
deterministic slice of that sweep in CI. Ints compare exactly; floats at
the documented f32 device tolerance."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.engine import ExecutionContext
from ballista_tpu.ops import kernels
from ballista_tpu.utils import tracing


def _fresh():
    from ballista_tpu.ops.runtime import reset_residency

    kernels._stage_cache.clear()
    kernels._stage_cache_pins.clear()
    kernels._stage_latest.clear()
    reset_residency()


def _extrema_floats(rng, n):
    """Adversarial float-extrema column: negative-heavy full-mantissa
    doubles with ±0, subnormals, and (on some seeds) NaN — the NaN tables
    must DECLINE the device min/max path (Arrow's host min/max skips NaN)
    and still agree across backends."""
    v = rng.uniform(-1e9, 1e3, n) + rng.uniform(0, 1e-6, n)
    v[rng.integers(0, n, max(1, n // 500))] = -0.0
    v[rng.integers(0, n, max(1, n // 500))] = 0.0
    v[rng.integers(0, n, max(1, n // 700))] = 5e-324  # subnormal
    v[rng.integers(0, n, max(1, n // 700))] = -5e-324
    if rng.random() < 0.4:
        v[rng.integers(0, n, max(1, n // 1000))] = np.nan
    return v


def _null_heavy_strings(rng, n):
    """~45% null string column (its own rng stream, like fx): nulls ride the
    device as -1 dictionary codes and every code predicate must apply SQL
    three-valued logic to them (ops/runtime.py::column_to_numpy)."""
    vals = rng.integers(0, 7, n)
    nulls = rng.random(n) < 0.45
    return pa.array(
        [None if isnull else f"x{v}" for v, isnull in zip(vals, nulls)],
        type=pa.string(),
    )


def _random_table(rng, n):
    cols = {
        "i8": pa.array(rng.integers(-100, 100, n), type=pa.int64()),
        "ibig": pa.array(rng.integers(-10**8, 10**8, n), type=pa.int64()),
        "f": pa.array(np.round(rng.uniform(-1000, 1000, n), 2)),
        # fx and sn draw from their own rngs so the baseline columns (and
        # every query the original stream generates) stay byte-identical
        "fx": pa.array(_extrema_floats(np.random.default_rng(n ^ 0xF10A7), n)),
        "sn": _null_heavy_strings(np.random.default_rng(n ^ 0x5EED), n),
        "g": pa.array(rng.integers(0, rng.integers(2, 3000), n),
                      type=pa.int64()),
        "s": pa.array([f"tag{v}" for v in rng.integers(0, 9, n)]),
        "d": pa.array(rng.integers(8000, 12000, n), type=pa.int32()).cast(
            pa.date32()
        ),
    }
    return pa.table(cols)


# exact aggregates (the True flags) are bit-identical across backends —
# ints stay int32/int64 end to end, float MIN/MAX travels the
# order-preserving bijection — so they may RANK an ORDER BY ... LIMIT
# epilogue (a tolerance-only aggregate ranking the boundary could select
# different rows per backend and that would be a false alarm, not a bug)
_AGGS = [
    ("sum(i8)", True), ("sum(ibig)", True), ("sum(f)", False),
    ("count(*)", True), ("count(f)", True),
    ("min(i8)", True), ("max(ibig)", True), ("min(d)", True),
    ("max(d)", True), ("avg(f)", False), ("avg(i8)", False),
    ("sum(f * (1 - 0.1))", False),
    ("sum(case when i8 > 0 then f else 0 end)", False),
    ("min(f)", True), ("max(f)", True), ("min(fx)", True),
    ("max(fx)", True),
]
# the original generator draws from this prefix of _AGGS (keeping the
# baseline rng stream byte-identical: compile-heavy query shapes stay the
# ones the suite always had); the float-extrema tail joins via the
# epilogue generator's own stream
_N_BASE_AGGS = 13
_PREDS = [
    "i8 > 0", "f < 250.5", "s <> 'tag3'", "s in ('tag1', 'tag2', 'tag7')",
    "d >= date '1995-01-01'", "i8 between -50 and 50",
    "s like 'tag%'", "i8 > 0 and f < 0", "i8 < -90 or f > 900",
]
# null-heavy string predicates (ROADMAP fuzzer slice): selected by their
# OWN rng stream so the baseline queries stay byte-identical. Every shape
# exercises SQL three-valued logic over the -1 null code on device: the
# WHERE collapse must drop NULL rows for =/<>/LIKE/IN, and IS [NOT] NULL
# is the explicit code test.
_NULLSTR_PREDS = [
    "sn is null", "sn is not null", "sn = 'x1'", "sn <> 'x2'",
    "sn like 'x%'", "sn in ('x1', 'x3', 'x5')",
    "sn is null or sn = 'x2'", "sn is not null and sn <> 'x4'",
]


def _random_query(rng, erng, nrng=None):
    """Base query from `rng` (UNCHANGED baseline stream), ORDER BY + LIMIT
    epilogue decisions from the separate `erng`, null-string predicate
    injection from `nrng` — so the base workload stays identical to the
    seed suite's."""
    keys = list(rng.choice(["g", "s", "d"], size=rng.integers(0, 3),
                           replace=False))
    n_aggs = rng.integers(1, 5)
    picks = list(rng.choice(_N_BASE_AGGS, size=n_aggs, replace=False))
    epilogue = erng.random() < 0.5
    if epilogue and erng.random() < 0.5:
        # swap one pick for a float-extrema min/max — only on epilogue
        # queries, which the annotation routes through the vectorized
        # sorted core (no fresh unrolled-core compiles beyond baseline's)
        picks[int(erng.integers(0, len(picks)))] = int(
            erng.integers(_N_BASE_AGGS, len(_AGGS))
        )
    aggs = [f"{_AGGS[p][0]} as a{i}" for i, p in enumerate(picks)]
    sel = ", ".join(keys + aggs)
    sql = f"select {sel} from t"
    if rng.random() < 0.7:
        sql += f" where {rng.choice(_PREDS)}"
    if nrng is not None and nrng.random() < 0.5:
        p = str(nrng.choice(_NULLSTR_PREDS))
        conj = "and" if nrng.random() < 0.7 else "or"
        sql += f" {conj} ({p})" if " where " in sql else f" where ({p})"
    if not keys:
        return sql
    sql += " group by " + ", ".join(keys)
    exact = [f"a{i}" for i, p in enumerate(picks) if _AGGS[p][1]]
    if exact and epilogue:
        # ORDER BY ... LIMIT epilogue over exact ranking keys, ties
        # included (counts/coarse sums collide constantly at these group
        # cardinalities). The trailing group keys make the order total, so
        # a fused device top-k must either match the host selection or
        # detect the boundary tie and fall back — either way bit-equal.
        ranks = [
            f"{a}{' desc' if erng.random() < 0.5 else ''}"
            for a in erng.choice(exact, size=erng.integers(1, len(exact) + 1),
                                 replace=False)
        ]
        sql += " order by " + ", ".join(ranks + keys)
        sql += f" limit {erng.integers(1, 60)}"
    else:
        sql += " order by " + ", ".join(keys)
    return sql


def _compare(t, c, sql):
    assert t.num_rows == c.num_rows, sql
    assert t.schema.names == c.schema.names, sql
    for name in t.schema.names:
        a, b = t.column(name).to_pylist(), c.column(name).to_pylist()
        if a and isinstance(
            next((x for x in a if x is not None), None), float
        ):
            an = np.array([np.nan if x is None else x for x in a], dtype=float)
            bn = np.array([np.nan if x is None else x for x in b], dtype=float)
            np.testing.assert_allclose(
                an, bn, rtol=1e-3, atol=1e-3, equal_nan=True,
                err_msg=f"{sql} :: {name}",
            )
        else:
            assert a == b, f"{sql} :: {name}"


@pytest.mark.parametrize("seed", range(12))
def test_fuzz_aggregates(tmp_path, seed):
    rng = np.random.default_rng(1000 + seed)
    _fresh()
    table = _random_table(rng, int(rng.integers(1_000, 40_000)))
    path = str(tmp_path / "t.parquet")
    pq.write_table(table, path)
    ctxs = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(
            BallistaConfig({"ballista.executor.backend": backend})
        )
        ctx.register_parquet("t", path)
        ctxs[backend] = ctx
    erng = np.random.default_rng(5000 + seed)
    nrng = np.random.default_rng(9000 + seed)
    for _ in range(4):
        sql = _random_query(rng, erng, nrng)
        _compare(ctxs["tpu"].sql(sql).collect(),
                 ctxs["cpu"].sql(sql).collect(), sql)


@pytest.mark.parametrize("seed", range(8))
def test_fuzz_aggregate_over_join(tmp_path, seed):
    """Random star joins through the factagg/mapped admission machinery."""
    rng = np.random.default_rng(2000 + seed)
    _fresh()
    nk = int(rng.integers(50, 2000))
    nf = int(rng.integers(2_000, 30_000))
    missing = int(rng.integers(0, nk // 4 + 1))
    fact = pa.table(
        {
            "fk": pa.array(rng.integers(0, nk + missing, nf),
                           type=pa.int64()),
            "v": pa.array(np.round(rng.uniform(0, 500, nf), 2)),
            "q": pa.array(rng.integers(1, 50, nf), type=pa.int64()),
            "m": pa.array([f"m{x}" for x in rng.integers(0, 6, nf)]),
        }
    )
    dim = pa.table(
        {
            "dk": pa.array(np.arange(nk), type=pa.int64()),
            "attr": pa.array([f"g{i % rng.integers(2, 40)}"
                              for i in range(nk)]),
            "w": pa.array(rng.integers(0, 10, nk), type=pa.int64()),
        }
    )
    pq.write_table(fact, str(tmp_path / "fact.parquet"))
    pq.write_table(dim, str(tmp_path / "dim.parquet"))
    ctxs = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(
            BallistaConfig({"ballista.executor.backend": backend})
        )
        ctx.register_parquet("fact", str(tmp_path / "fact.parquet"))
        ctx.register_parquet("dim", str(tmp_path / "dim.parquet"))
        ctxs[backend] = ctx

    group = rng.choice(["fk", "attr", "m", "fk, attr", "attr, m"])
    _JOIN_AGGS = [("sum(v)", False), ("count(*)", True), ("sum(q)", True),
                  ("avg(v)", False), ("sum(v * q)", False),
                  ("sum(case when attr <> 'g1' then v else 0 end)", False),
                  ("sum(w)", True), ("min(q)", True), ("max(q)", True)]
    picks = list(rng.choice(len(_JOIN_AGGS), size=rng.integers(1, 4),
                            replace=False))
    sel = ", ".join([group] + [f"{_JOIN_AGGS[p][0]} as a{i}"
                               for i, p in enumerate(picks)])
    sql = f"select {sel} from dim, fact where dk = fk"
    if rng.random() < 0.6:
        sql += " and " + str(rng.choice(
            ["v > 100", "q < 25", "m <> 'm3'", "w > 2"]
        ))
    sql += f" group by {group}"
    exact = [f"a{i}" for i, p in enumerate(picks) if _JOIN_AGGS[p][1]]
    if exact and rng.random() < 0.5:
        # Sort+Limit epilogue through the factagg/mapped top-k machinery
        # (ties included; trailing group keys make the order total)
        rank = f"{rng.choice(exact)}{' desc' if rng.random() < 0.5 else ''}"
        sql += f" order by {rank}, {group} limit {rng.integers(1, 40)}"
    else:
        sql += f" order by {group}"
    _compare(ctxs["tpu"].sql(sql).collect(),
             ctxs["cpu"].sql(sql).collect(), sql)


def _dup_key_build(rng, shape: str):
    """Build-side key column with controlled duplicate-key structure.

    Shapes (ROADMAP "outer joins with duplicate keys" fuzzer slice):
    - zipf: Zipf-skewed duplicate counts clipped inside the admission tiers
      (the heaviest device-admissible skew);
    - all_dup: every row carries ONE key (multiplicity == num_rows);
    - monster: mostly-unique keys plus one key duplicated past the top
      tier, forcing the step-aside path (results must still be exact);
    - uniform: modest uniform duplication (the common case)."""
    from ballista_tpu.ops.kernels import JOIN_MULTIPLICITY_TIERS

    top = JOIN_MULTIPLICITY_TIERS[-1]
    nk = int(rng.integers(30, 400))
    if shape == "zipf":
        counts = np.minimum(rng.zipf(1.5, nk), top)
        keys = np.repeat(np.arange(nk, dtype=np.int64), counts)
    elif shape == "all_dup":
        keys = np.full(int(rng.integers(2, min(top, 150))), 7, dtype=np.int64)
    elif shape == "monster":
        keys = np.concatenate([
            np.arange(nk, dtype=np.int64),
            np.full(top + int(rng.integers(1, 50)), 3, dtype=np.int64),
        ])
    else:  # uniform
        keys = np.repeat(
            np.arange(nk, dtype=np.int64), rng.integers(1, 6, nk)
        )
    rng.shuffle(keys)
    return keys


@pytest.mark.parametrize("seed", range(8))
def test_fuzz_duplicate_key_joins(tmp_path, seed):
    """Differential duplicate-key join sweep: the M:N device kernel (INNER,
    build side with duplicate keys) and the host LEFT join must agree with
    the cpu backend bit-for-bit — multiplicity, order, and null padding
    included. Own rng streams (12000+/13000+ seeds), so every baseline
    generator above stays byte-identical."""
    rng = np.random.default_rng(12000 + seed)
    prng = np.random.default_rng(13000 + seed)
    _fresh()
    shape = str(rng.choice(["zipf", "all_dup", "monster", "uniform"]))
    bkeys = _dup_key_build(rng, shape)
    nb = len(bkeys)
    # ~5% null build keys (nulls must never match, not even each other)
    bnull = rng.random(nb) < 0.05
    build = pa.table(
        {
            "bk": pa.array(
                [None if isnull else int(v) for v, isnull in zip(bkeys, bnull)],
                type=pa.int64(),
            ),
            "bv": pa.array(np.round(rng.uniform(-100, 100, nb), 3)),
            "bs": pa.array([f"b{v % 11}" for v in range(nb)]),
        }
    )
    np_rows = int(prng.integers(500, 8000))
    pkeys = prng.integers(-1, int(bkeys.max()) + 20, np_rows)
    probe = pa.table(
        {
            "pk": pa.array(
                [None if v < 0 else int(v) for v in pkeys], type=pa.int64()
            ),
            "pv": pa.array(np.round(prng.uniform(0, 50, np_rows), 3)),
        }
    )
    how = str(rng.choice(["inner", "left"]))
    out = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(
            BallistaConfig({"ballista.executor.backend": backend})
        )
        ctx.register_record_batches("b", build, n_partitions=1)
        ctx.register_record_batches("p", probe, n_partitions=1)
        df = ctx.table("b").join(ctx.table("p"), ["bk"], ["pk"], how=how)
        out[backend] = df.collect()
    assert out["tpu"].schema == out["cpu"].schema, (shape, how)
    assert out["tpu"].to_pylist() == out["cpu"].to_pylist(), (shape, how)


def _distributed_fuzz_queries(qrng, k=2):
    """Random 2-stage (partial agg -> shuffle -> final agg) queries from the
    dedicated 15000+ stream. Aggregates restricted to orders the
    distributed fold computes deterministically under retries (it does for
    all of them — partials are per-partition and partitioning is by hash)."""
    aggs = ["sum(v)", "count(*)", "min(q)", "max(q)", "sum(q)"]
    out = []
    for _ in range(k):
        key = str(qrng.choice(["g", "s", "g, s"]))
        picks = list(qrng.choice(aggs, size=int(qrng.integers(1, 4)),
                                 replace=False))
        sel = ", ".join([key] + [f"{a} as a{i}" for i, a in enumerate(picks)])
        sql = f"select {sel} from t"
        if qrng.random() < 0.5:
            sql += " where " + str(qrng.choice(
                ["v > 0", "q < 30", "s <> 't2'", "g % 7 <> 3"]
            ))
        out.append(sql + f" group by {key} order by {key}")
    return out


def _run_distributed(table, queries, client_settings, cluster_config=None,
                     lost_task_check_interval=None):
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    cluster = StandaloneCluster(n_executors=2, config=cluster_config)
    if lost_task_check_interval is not None:
        # a run that kills an executor: until the lost-task check sees the
        # expired lease, every failed duplicate of a dead primary's task is
        # speculated again at once, a poll each. At the default 5 s that is
        # 390-430 polls of the survivor, whose death seed holds for 400.
        cluster.scheduler_impl.lost_task_check_interval = lost_task_check_interval
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=client_settings)
        ctx.register_record_batches("t", table, n_partitions=4)
        out = [ctx.sql(sql).collect() for sql in queries]
        ctx.close()
        return out
    finally:
        cluster.shutdown()


@pytest.mark.parametrize("seed", range(2))
def test_fuzz_distributed_two_stage_chaos(seed):
    """ROADMAP fuzzer slice (ISSUE 6 satellite): random 2-stage plans
    through the REAL scheduler + executors, run fault-free and then with
    the PR 5/6 chaos sites armed at a seeded nonzero rate — task faults,
    fetch faults, scheduler KV-write faults, and torn planning writes must
    all recover to BIT-IDENTICAL results. Own rng streams (14000+ data,
    15000+ queries), so every baseline stream above stays byte-identical."""
    from ballista_tpu.config import BallistaConfig

    rng = np.random.default_rng(14000 + seed)
    qrng = np.random.default_rng(15000 + seed)
    _fresh()
    n = int(rng.integers(2_000, 8_000))
    table = pa.table(
        {
            "g": pa.array(rng.integers(0, 50, n), type=pa.int64()),
            "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
            "q": pa.array(rng.integers(1, 50, n), type=pa.int64()),
            "s": pa.array([f"t{x}" for x in rng.integers(0, 5, n)]),
        }
    )
    queries = _distributed_fuzz_queries(qrng)

    clean = _run_distributed(
        table, queries, {"ballista.shuffle.partitions": "4"}
    )
    # executor-side sites ride the per-job client settings; scheduler-side
    # sites (kv.put, scheduler.plan_write) arm through the cluster config
    chaos_client = {
        "ballista.shuffle.partitions": "4",
        "ballista.chaos.rate": "0.05",
        "ballista.chaos.seed": str(70 + seed),
        "ballista.chaos.sites": "task.execute,flight.fetch",
        "ballista.shuffle.max_task_retries": "5",
    }
    chaos_cluster = BallistaConfig({
        "ballista.chaos.rate": "0.02",
        "ballista.chaos.seed": str(70 + seed),
        "ballista.chaos.sites": "kv.put,scheduler.plan_write",
        "ballista.shuffle.max_task_retries": "5",
    })
    tracing.counters("recovery", reset=True)
    chaotic = _run_distributed(table, queries, chaos_client, chaos_cluster)
    stats = tracing.counters("recovery", reset=True)
    for sql, c, t in zip(queries, clean, chaotic):
        assert t.equals(c), (sql, t.to_pydict(), c.to_pydict())
    assert stats.get("chaos_injected", 0) > 0, stats


@pytest.mark.parametrize("seed", range(2))
def test_fuzz_concurrent_submission_cache(seed):
    """Multi-tenant fuzz slice (ISSUE 7 satellite): N concurrent tenant
    clients replay a Zipf-repeated random query mix against ONE cluster
    with the result cache armed; every result — cache-served or cold —
    must be bit-identical to a cache-disabled sequential baseline, and the
    Zipf repetition must actually produce hits. Own rng streams (16000+
    data, 17000+ queries/replay), so every baseline stream above stays
    byte-identical."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    rng = np.random.default_rng(16000 + seed)
    qrng = np.random.default_rng(17000 + seed)
    _fresh()
    n = int(rng.integers(2_000, 6_000))
    table = pa.table(
        {
            "g": pa.array(rng.integers(0, 40, n), type=pa.int64()),
            "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
            "q": pa.array(rng.integers(1, 50, n), type=pa.int64()),
            "s": pa.array([f"t{x}" for x in rng.integers(0, 5, n)]),
        }
    )
    queries = _distributed_fuzz_queries(qrng, k=4)
    # Zipf-repeated replay schedules, drawn BEFORE any threading so the
    # schedule is a pure function of the seed
    n_tenants = 4
    schedules = [
        [int(z - 1) % len(queries)
         for z in qrng.zipf(1.6, size=int(qrng.integers(4, 7)))]
        for _ in range(n_tenants)
    ]
    cold = _run_distributed(
        table, queries,
        {"ballista.cache.results": "false", "ballista.shuffle.partitions": "4"},
    )
    cluster = StandaloneCluster(n_executors=2)
    try:
        tracing.counters("tenancy", reset=True)
        results = {}
        errors = []

        def replay(i):
            try:
                ctx = BallistaContext(
                    *cluster.scheduler_addr,
                    settings={
                        "ballista.tenant.name": f"tenant{i}",
                        "ballista.shuffle.partitions": "4",
                    },
                )
                ctx.register_record_batches("t", table, n_partitions=4)
                results[i] = [
                    (qi, ctx.sql(queries[qi]).collect())
                    for qi in schedules[i]
                ]
                ctx.close()
            except Exception as e:  # surface in the main thread
                errors.append((i, e))

        import threading

        threads = [
            threading.Thread(target=replay, args=(i,))
            for i in range(n_tenants)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not errors, errors
        for i in range(n_tenants):
            for qi, got in results[i]:
                assert got.equals(cold[qi]), (
                    i, queries[qi], got.to_pydict(), cold[qi].to_pydict()
                )
        stats = tracing.counters("tenancy", reset=True)
        total = sum(len(s) for s in schedules)
        assert stats.get("cache_hit", 0) > 0, (stats, schedules)
        assert stats.get("cache_hit", 0) + stats.get("cache_miss", 0) >= total
    finally:
        cluster.shutdown()


@pytest.mark.parametrize("seed", range(4))
def test_fuzz_float_extrema_minmax(tmp_path, seed):
    """Dedicated float-extrema sweep: MIN/MAX over NaN/±0/subnormal/
    negative-heavy doubles must agree across backends — bit-exactly when
    the device path runs (the bijection), and via the host fallback when
    NaN forces the decline. High-cardinality groups keep this on the
    vectorized sorted core."""
    rng = np.random.default_rng(8000 + seed)
    _fresh()
    n = int(rng.integers(5_000, 30_000))
    fx = _extrema_floats(rng, n)
    table = pa.table(
        {
            "g": pa.array(rng.integers(0, 2000, n), type=pa.int64()),
            "fx": pa.array(fx),
            "q": pa.array(rng.integers(1, 50, n), type=pa.int64()),
        }
    )
    path = str(tmp_path / "t.parquet")
    pq.write_table(table, path)
    ctxs = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(
            BallistaConfig({"ballista.executor.backend": backend})
        )
        ctx.register_parquet("t", path)
        ctxs[backend] = ctx
    queries = [
        "select min(fx) as mn, max(fx) as mx from t",
        "select g, min(fx) as mn, max(fx) as mx from t group by g order by g",
        ("select g, min(fx) as mn, count(*) as c from t where q < 40 "
         "group by g order by mn, g limit 25"),
    ]
    for sql in queries:
        t = ctxs["tpu"].sql(sql).collect().to_pydict()
        c = ctxs["cpu"].sql(sql).collect().to_pydict()
        assert set(t) == set(c), sql
        for name in t:
            for a, b in zip(t[name], c[name]):
                if isinstance(a, float) and isinstance(b, float):
                    # bit-exact modulo the documented ±0 collapse
                    assert (a == b == 0.0) or (
                        np.float64(a).tobytes() == np.float64(b).tobytes()
                    ), (sql, name, a, b)
                else:
                    assert a == b, (sql, name, a, b)


@pytest.mark.parametrize("seed", range(2))
def test_fuzz_speculation_straggler(seed):
    """Speculation fuzz slice (ISSUE 11 satellite): random 2-stage plans
    through the REAL scheduler + executors under seeded `task.slow` chaos
    with speculation ARMED (aggressive thresholds, predictions warmed by
    the fault-free pass — the task.run op is job-independent, so the clean
    run's durations predict the chaos run's). The straggler site never
    corrupts work, and first-completion-wins must never double-count it:
    results are BIT-IDENTICAL to the fault-free baseline whatever the
    duplicate/primary race does. Own rng streams (20000+ data, 21000+
    queries), so every baseline stream above stays byte-identical."""
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.ops import costmodel

    rng = np.random.default_rng(20000 + seed)
    qrng = np.random.default_rng(21000 + seed)
    _fresh()
    costmodel.reset()
    n = int(rng.integers(2_000, 8_000))
    table = pa.table(
        {
            "g": pa.array(rng.integers(0, 50, n), type=pa.int64()),
            "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
            "q": pa.array(rng.integers(1, 50, n), type=pa.int64()),
            "s": pa.array([f"t{x}" for x in rng.integers(0, 5, n)]),
        }
    )
    queries = _distributed_fuzz_queries(qrng)
    # the in-memory cost store (dir "") is process-global: the clean pass
    # warms the task.run rates the chaos pass's straggler monitor predicts
    # from — every config (cluster AND per-job) pins the same dir so no
    # configure() rebind drops the store between the two passes
    spec_cluster = BallistaConfig({
        "ballista.tpu.cost_model_dir": "",
        "ballista.speculation.min_runtime_ms": "100",
        "ballista.speculation.multiplier": "2",
    })
    base_client = {
        "ballista.shuffle.partitions": "4",
        "ballista.cache.results": "false",
        "ballista.tpu.cost_model_dir": "",
    }
    clean = _run_distributed(table, queries, base_client, spec_cluster)
    chaos_client = {
        **base_client,
        "ballista.chaos.rate": "0.2",
        "ballista.chaos.seed": str(90 + seed),
        "ballista.chaos.sites": "task.slow",
        "ballista.chaos.slow_ms": "2000",
    }
    tracing.counters("recovery", reset=True)
    tracing.counters("speculation", reset=True)
    chaotic = _run_distributed(table, queries, chaos_client, spec_cluster)
    rec = tracing.counters("recovery", reset=True)
    spec = tracing.counters("speculation", reset=True)
    costmodel.reset()
    for sql, c, t in zip(queries, clean, chaotic):
        assert t.equals(c), (sql, t.to_pydict(), c.to_pydict())
    assert rec.get("chaos_slow_injected", 0) > 0, rec
    assert spec.get("launched", 0) >= 1, (spec, rec)


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_routing(tmp_path, seed):
    """Adaptive-execution replay (ISSUE 10): the duplicate-key join sweep
    re-run with the cost model forced cold, warm, off, and fed seeded
    ADVERSARIAL cost entries (absurd rates both directions). Routing may
    differ — device, split, extended tier, host — but results must be
    bit-identical in every configuration: the cost model changes where a
    partition runs, never what it returns. Own rng streams (18000+ data,
    19000+ probe/adversary), so every baseline stream above stays
    byte-identical."""
    from ballista_tpu.ops import costmodel
    from ballista_tpu.ops.kernels import JOIN_EXTENDED_TIERS

    rng = np.random.default_rng(18000 + seed)
    prng = np.random.default_rng(19000 + seed)
    _fresh()
    costmodel.reset(clear_dir=True)
    shape = str(rng.choice(["zipf", "all_dup", "monster", "uniform"]))
    bkeys = _dup_key_build(rng, shape)
    nb = len(bkeys)
    bnull = rng.random(nb) < 0.05
    build = pa.table({
        "bk": pa.array(
            [None if isnull else int(v) for v, isnull in zip(bkeys, bnull)],
            type=pa.int64(),
        ),
        "bv": pa.array(np.round(rng.uniform(-100, 100, nb), 3)),
    })
    np_rows = int(prng.integers(500, 6000))
    pkeys = prng.integers(-1, int(bkeys.max()) + 20, np_rows)
    probe = pa.table({
        "pk": pa.array(
            [None if v < 0 else int(v) for v in pkeys], type=pa.int64()
        ),
        "pv": pa.array(np.round(prng.uniform(0, 50, np_rows), 3)),
    })

    def run(backend, model, store_dir):
        ctx = ExecutionContext(BallistaConfig({
            "ballista.executor.backend": backend,
            "ballista.tpu.cost_model": model,
            "ballista.tpu.cost_model_dir": store_dir,
        }))
        ctx.register_record_batches("b", build, n_partitions=1)
        ctx.register_record_batches("p", probe, n_partitions=1)
        df = ctx.table("b").join(ctx.table("p"), ["bk"], ["pk"], how="inner")
        return df.collect().to_pylist()

    store = str(tmp_path / "costs")
    try:
        baseline = run("cpu", "false", "")
        out_off = run("tpu", "false", "")
        out_cold = run("tpu", "true", store)
        costmodel.flush()
        costmodel.reset()  # fresh-process simulation: reload from disk
        out_warm = run("tpu", "true", store)
        # adversarial entries: absurd rates in a prng-chosen direction,
        # covering every op the join ladder predicts from. The run MUST
        # keep the same store dir — a dir change in configure() clears the
        # in-memory store and would silently wipe the seeds
        fast, slow = (1e-12, 100.0)
        if prng.random() < 0.5:
            fast, slow = slow, fast
        for tier in JOIN_EXTENDED_TIERS:
            costmodel.seed("join.gather", 4096 * tier, fast)
        costmodel.seed("join.gather", 4096, fast)
        costmodel.seed("join.host", nb + np_rows, slow, engine="host")
        assert costmodel.snapshot(), "adversarial seeds must be installed"
        out_adv = run("tpu", "true", store)
        assert costmodel.snapshot(), "seeds were wiped before the run"
        assert baseline == out_off == out_cold == out_warm == out_adv, (
            shape, seed,
        )
    finally:
        costmodel.reset(clear_dir=True)
        _fresh()


@pytest.mark.parametrize("seed", range(2))
def test_fuzz_shared_tier_chaos(seed, tmp_path):
    """Shared-tier fuzz slice (ISSUE 15 satellite): random 2-stage plans on
    the SHARED shuffle tier under seeded shuffle.store chaos (torn storage
    publishes retry; torn storage reads degrade down the peer/lineage
    ladder) PLUS a deterministic mid-run executor death — results must be
    bit-identical to the LOCAL-tier fault-free baseline. Own rng streams
    (24000+ data, 25000+ queries), so every baseline stream above stays
    byte-identical."""
    import time as _time

    import ballista_tpu.scheduler.state as state_mod
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.utils.chaos import ChaosInjector

    rng = np.random.default_rng(24000 + seed)
    qrng = np.random.default_rng(25000 + seed)
    _fresh()
    n = int(rng.integers(2_000, 8_000))
    table = pa.table(
        {
            "g": pa.array(rng.integers(0, 50, n), type=pa.int64()),
            "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
            "q": pa.array(rng.integers(1, 50, n), type=pa.int64()),
            "s": pa.array([f"t{x}" for x in rng.integers(0, 5, n)]),
        }
    )
    queries = _distributed_fuzz_queries(qrng)

    clean = _run_distributed(
        table, queries, {"ballista.shuffle.partitions": "4"}
    )

    # deterministic executor death: local-0 dies within its first polls,
    # local-1 survives the whole run (pure hashing, stable forever)
    death_seed = None
    for cand in range(2000):
        inj = ChaosInjector(cand, 0.005, sites={"executor.death"})

        def death_poll(eid, horizon):
            for k in range(1, horizon):
                if inj.should_inject("executor.death", f"{eid}/poll{k}"):
                    return k
            return None

        d0 = death_poll("local-0", 17)
        if d0 is not None and 4 <= d0 and death_poll("local-1", 400) is None:
            death_seed = cand
            break
    assert death_seed is not None, "no death seed in scan range"

    shared = str(tmp_path / f"store{seed}")
    chaos_client = {
        "ballista.shuffle.partitions": "4",
        "ballista.shuffle.tier": "shared",
        "ballista.shuffle.dir": shared,
        # this slice exercises the STORAGE ladder under torn publishes —
        # the ISSUE 16 residency registry would satisfy same-executor
        # reads before the ladder (and shift the poll cadence the death
        # seed was scanned for); test_fuzz_exchange_chaos owns the
        # exchange-on chaos story
        "ballista.tpu.exchange": "false",
        "ballista.chaos.rate": "0.05",
        "ballista.chaos.seed": str(170 + seed),
        "ballista.chaos.sites": "shuffle.store",
        "ballista.shuffle.max_task_retries": "5",
    }
    chaos_cluster = BallistaConfig({
        "ballista.chaos.rate": "0.005",
        "ballista.chaos.seed": str(death_seed),
        "ballista.chaos.sites": "executor.death",
        "ballista.shuffle.max_task_retries": "5",
    })
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    tracing.counters("recovery", reset=True)
    tracing.counters("shuffle_tier", reset=True)
    try:
        chaotic = _run_distributed(table, queries, chaos_client, chaos_cluster,
                                   lost_task_check_interval=0.3)
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
    stats = tracing.counters("recovery", reset=True)
    tier = tracing.counters("shuffle_tier", reset=True)
    for sql, c, t in zip(queries, clean, chaotic):
        assert t.equals(c), (sql, t.to_pydict(), c.to_pydict())
    assert stats.get("chaos_injected", 0) > 0, stats
    assert stats.get("chaos_executor_death", 0) >= 1, stats
    assert tier.get("storage_publish", 0) > 0, tier
    assert tier.get("storage_fetch", 0) > 0, tier


@pytest.mark.parametrize("seed", range(2))
def test_fuzz_exchange_chaos(seed):
    """HBM-resident exchange fuzz slice (ISSUE 16 satellite): random
    2-stage plans run fault-free with the exchange OFF (pure authoritative
    piece ladder — the oracle), then with the exchange ON under seeded
    exchange.evict chaos (consume-time registry probes torn) PLUS a
    deterministic mid-run executor death (the registry dies with its
    executor). The residency tier is pure acceleration: every loss
    degrades to the ladder, so results must be bit-identical. Own rng
    streams (26000+ data, 27000+ queries), so every baseline stream above
    stays byte-identical."""
    import ballista_tpu.scheduler.state as state_mod
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.ops import exchange
    from ballista_tpu.utils.chaos import ChaosInjector

    rng = np.random.default_rng(26000 + seed)
    qrng = np.random.default_rng(27000 + seed)
    _fresh()
    n = int(rng.integers(2_000, 8_000))
    table = pa.table(
        {
            "g": pa.array(rng.integers(0, 50, n), type=pa.int64()),
            "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
            "q": pa.array(rng.integers(1, 50, n), type=pa.int64()),
            "s": pa.array([f"t{x}" for x in rng.integers(0, 5, n)]),
        }
    )
    queries = _distributed_fuzz_queries(qrng)

    clean = _run_distributed(
        table, queries,
        {"ballista.shuffle.partitions": "4",
         "ballista.tpu.exchange": "false"},
    )

    # deterministic executor death: local-0 dies within its first polls,
    # local-1 survives the whole run (pure hashing, stable forever)
    death_seed = None
    for cand in range(2000):
        inj = ChaosInjector(cand, 0.005, sites={"executor.death"})

        def death_poll(eid, horizon):
            for k in range(1, horizon):
                if inj.should_inject("executor.death", f"{eid}/poll{k}"):
                    return k
            return None

        d0 = death_poll("local-0", 17)
        if d0 is not None and 4 <= d0 and death_poll("local-1", 400) is None:
            death_seed = cand
            break
    assert death_seed is not None, "no death seed in scan range"

    chaos_client = {
        "ballista.shuffle.partitions": "4",
        "ballista.chaos.rate": "0.3",
        "ballista.chaos.seed": str(190 + seed),
        "ballista.chaos.sites": "exchange.evict",
        "ballista.shuffle.max_task_retries": "5",
    }
    chaos_cluster = BallistaConfig({
        "ballista.chaos.rate": "0.005",
        "ballista.chaos.seed": str(death_seed),
        "ballista.chaos.sites": "executor.death",
        "ballista.shuffle.max_task_retries": "5",
    })
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    exchange.reset()
    tracing.counters("exchange", reset=True)
    tracing.counters("recovery", reset=True)
    try:
        chaotic = _run_distributed(table, queries, chaos_client, chaos_cluster,
                                   lost_task_check_interval=0.3)
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
    stats = tracing.counters("recovery", reset=True)
    ex = tracing.counters("exchange", reset=True)
    for sql, c, t in zip(queries, clean, chaotic):
        assert t.equals(c), (sql, t.to_pydict(), c.to_pydict())
    assert stats.get("chaos_injected", 0) > 0, stats
    assert stats.get("chaos_executor_death", 0) >= 1, stats
    # the registry was exercised AND torn: publishes happened, at least
    # one probe lost its entry to chaos, and the reads that missed walked
    # the ladder instead of failing the task
    assert ex.get("published", 0) > 0, ex
    assert ex.get("evicted_chaos", 0) >= 1, ex


# ---------------------------------------------------------------------------
# ISSUE 19: incremental execution under randomized appends
# ---------------------------------------------------------------------------


def _delta_fuzz_queries(qrng):
    """Randomized advancement-shaped aggregations plus one deliberately
    INELIGIBLE member set (a float sum must decline, never mis-fold)."""
    queries = []
    for _ in range(3):
        members = ["count(*) as c"]
        if qrng.integers(0, 2):
            members.append("sum(v) as sv")
        if qrng.integers(0, 2):
            members.append("min(v) as mn")
        if qrng.integers(0, 2):
            members.append("max(v) as mx")
        keys = "g, h" if qrng.integers(0, 2) else "g"
        thr = int(qrng.integers(-8, 2))
        queries.append(
            f"select {keys}, {', '.join(members)} from t where w > {thr} "
            f"group by {keys} order by {keys}"
        )
    queries.append(
        "select g, sum(f) as sf, count(*) as c from t "
        "group by g order by g"
    )
    return queries


@pytest.mark.parametrize("seed", range(3))
def test_fuzz_delta_append(tmp_path, seed):
    """ROADMAP fuzzer slice (ISSUE 19): randomized eligible and ineligible
    aggregations over a parquet set that GROWS mid-stream, with the result
    cache advancing on the appends — fault-free and with every advanced
    publish torn by cache.advance chaos. Every configuration must be
    bit-identical to a cold full run over the grown set; the ineligible
    member set (float sum) must decline, never mis-fold. Own rng streams
    (28000+ data, 29000+ queries), so every baseline stream above stays
    byte-identical."""
    import os

    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    rng = np.random.default_rng(28000 + seed)
    qrng = np.random.default_rng(29000 + seed)
    d = str(tmp_path / "grow")
    os.makedirs(d)

    def write_part(i):
        n = int(rng.integers(1_000, 4_000))
        pq.write_table(pa.table({
            "g": pa.array(rng.integers(0, 9, n), type=pa.int64()),
            "h": pa.array(rng.integers(0, 3, n), type=pa.int64()),
            "v": pa.array(rng.integers(-100, 100, n), type=pa.int64()),
            "w": pa.array(rng.integers(-10, 10, n), type=pa.int64()),
            "f": pa.array(rng.random(n), type=pa.float64()),
        }), os.path.join(d, f"part-{i}.parquet"))

    write_part(0)
    write_part(1)
    queries = _delta_fuzz_queries(qrng)
    next_part = [2]

    def run_grow(cluster_config=None):
        """Cold pass over the current set, append one NEW file (never a
        rewrite — a moved identity is a correct probe miss, not a delta),
        advanced pass over the grown set."""
        cluster = StandaloneCluster(n_executors=2, config=cluster_config)
        try:
            ctx = BallistaContext(*cluster.scheduler_addr, settings={
                "ballista.cache.advance": "true",
            })
            ctx.register_parquet("t", d)
            for sql in queries:
                ctx.sql(sql).collect()
            write_part(next_part[0])
            next_part[0] += 1
            ctx.register_parquet("t", d)
            grown = [ctx.sql(sql).collect() for sql in queries]
            truth_ctx = BallistaContext(*cluster.scheduler_addr, settings={
                "ballista.cache.results": "false",
            })
            truth_ctx.register_parquet("t", d)
            truth = [truth_ctx.sql(sql).collect() for sql in queries]
            ctx.close()
            truth_ctx.close()
            return grown, truth
        finally:
            cluster.shutdown()

    tracing.counters("delta", reset=True)
    grown, truth = run_grow()
    stats = tracing.counters("delta", reset=True)
    for sql, g, t in zip(queries, grown, truth):
        assert g.equals(t), (sql, g.to_pydict(), t.to_pydict())
    # the eligible shapes advanced; the float-sum shape declined loudly
    assert stats.get("advance_hits", 0) >= 1, stats
    assert stats.get("advance_declined", 0) >= 1, stats

    # every advanced publish torn: all declines, still bit-identical. The
    # chaos pass's cold queries hit the first pass's (shared content-key)
    # cache entries; its append then forces a NEW advancement attempt
    # whose publish the chaos site tears.
    tracing.counters("delta", reset=True)
    chaos_grown, chaos_truth = run_grow(BallistaConfig({
        "ballista.chaos.rate": "1.0",
        "ballista.chaos.seed": str(70 + seed),
        "ballista.chaos.sites": "cache.advance",
    }))
    stats = tracing.counters("delta", reset=True)
    for sql, g, t in zip(queries, chaos_grown, chaos_truth):
        assert g.equals(t), (sql, g.to_pydict(), t.to_pydict())
    assert stats.get("advance_hits", 0) == 0, stats
    assert stats.get("advance_declined", 0) >= 1, stats


@pytest.mark.parametrize("seed", range(2))
def test_fuzz_replica_failover(seed):
    """ROADMAP fuzzer slice (ISSUE 20 satellite): random 2-stage plans
    against a 2-replica control plane with the ``scheduler.lease`` chaos
    site armed (torn renewal rounds lapse owned leases early, so peers
    adopt live jobs) PLUS a seeded hard kill of replica 0 partway through
    the query stream. Every query must come back BIT-IDENTICAL to the
    fault-free single-scheduler oracle. Chaos verdicts on renewal rounds
    are timing-dependent (rounds tick on the wall clock), so this slice
    asserts results, not injection counters — the deterministic owner
    kill is the headline. Own rng streams (30000+ data, 31000+ queries),
    so every baseline stream above stays byte-identical."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.executor.runtime import StandaloneCluster

    rng = np.random.default_rng(30000 + seed)
    qrng = np.random.default_rng(31000 + seed)
    _fresh()
    n = int(rng.integers(2_000, 6_000))
    table = pa.table(
        {
            "g": pa.array(rng.integers(0, 50, n), type=pa.int64()),
            "v": pa.array(np.round(rng.uniform(-100, 100, n), 2)),
            "q": pa.array(rng.integers(1, 50, n), type=pa.int64()),
            "s": pa.array([f"t{x}" for x in rng.integers(0, 5, n)]),
        }
    )
    queries = _distributed_fuzz_queries(qrng, k=3)
    kill_after = int(rng.integers(1, len(queries)))

    oracle = _run_distributed(
        table, queries, {"ballista.shuffle.partitions": "4"}
    )

    _fresh()
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(
        n_executors=2,
        n_schedulers=2,
        config=BallistaConfig({
            "ballista.scheduler.lease_ttl_s": "0.3",
            "ballista.chaos.rate": "0.25",
            "ballista.chaos.seed": str(90 + seed),
            "ballista.chaos.sites": "scheduler.lease",
        }),
    )
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.shuffle.partitions": "4"},
            endpoints=cluster.scheduler_endpoints,
        )
        ctx.register_record_batches("t", table, n_partitions=4)
        got = []
        for i, sql in enumerate(queries):
            if i == kill_after:
                cluster.kill_scheduler(0)
            got.append(ctx.sql(sql).collect())
        ctx.close()
    finally:
        cluster.shutdown()

    for sql, g, o in zip(queries, got, oracle):
        assert g.equals(o), (seed, kill_after, sql,
                             g.to_pydict(), o.to_pydict())
    stats = tracing.counters("recovery", reset=True)
    # the survivor finished every post-kill query without a single task
    # re-execution: failover is a control-plane event, not a data redo
    assert stats.get("task_retry", 0) == 0, stats
