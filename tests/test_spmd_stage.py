"""SPMD stage execution through the REAL distributed planner: a
Partial -> hash exchange -> Final aggregation collapses into one
SpmdAggregateExec stage whose exchange is a psum over the 8-device mesh
(config ballista.tpu.spmd_stages)."""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.client import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.distributed.planner import DistributedPlanner
from ballista_tpu.engine import ExecutionContext
from ballista_tpu.executor.runtime import StandaloneCluster
from ballista_tpu.logical import col, functions as F
from ballista_tpu.parallel.spmd_stage import SpmdAggregateExec

SPMD_SETTINGS = {
    "ballista.executor.backend": "tpu",
    "ballista.tpu.spmd_stages": "true",
    "ballista.tpu.mesh": "data:8",
}


def _sales(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "region": pa.array(
                np.array(["east", "west", "north", "south"])[
                    rng.integers(0, 4, n)
                ]
            ),
            "amount": pa.array(rng.uniform(0, 100, n)),
            "qty": pa.array(rng.integers(1, 50, n), type=pa.int64()),
        }
    )


def _physical(table, settings):
    ctx = ExecutionContext(BallistaConfig(settings))
    ctx.register_record_batches("sales", table, n_partitions=4)
    df = ctx.table("sales").aggregate(
        [col("region")],
        [F.sum(col("amount")).alias("s"), F.count(col("qty")).alias("c"),
         F.min(col("amount")).alias("mn"), F.sum(col("qty")).alias("sq")],
    )
    return ctx, ctx.create_physical_plan(df.logical_plan())


def test_planner_fuses_partial_final_into_one_stage():
    table = _sales()
    _, phys = _physical(table, SPMD_SETTINGS)
    cfg = BallistaConfig(SPMD_SETTINGS)

    fused = DistributedPlanner(cfg).plan_query_stages("job", phys)
    plain = DistributedPlanner().plan_query_stages("job", phys)

    def nodes(plan):
        yield plan
        for c in plan.children():
            yield from nodes(c)

    fused_types = [type(n).__name__ for s in fused for n in nodes(s)]
    assert "SpmdAggregateExec" in fused_types
    # the exchange stage disappeared: one stage instead of two
    assert len(fused) == len(plain) - 1


def test_spmd_exec_serde_roundtrip():
    from ballista_tpu.serde.physical import phys_plan_from_proto, phys_plan_to_proto

    table = _sales()
    cfg = BallistaConfig(SPMD_SETTINGS)
    _, phys = _physical(table, SPMD_SETTINGS)
    stages = DistributedPlanner(cfg).plan_query_stages("job", phys)
    spmd = None
    for s in stages:
        def find(n):
            if isinstance(n, SpmdAggregateExec):
                return n
            for c in n.children():
                r = find(c)
                if r is not None:
                    return r
            return None
        spmd = spmd or find(s)
    assert spmd is not None
    back = phys_plan_from_proto(phys_plan_to_proto(spmd))
    assert isinstance(back, SpmdAggregateExec)
    assert back.schema() == spmd.schema()


def test_mesh_program_matches_host():
    """The mesh program's result equals the plain host aggregation."""
    from ballista_tpu.physical.plan import TaskContext

    table = _sales()
    cfg = BallistaConfig(SPMD_SETTINGS)
    ctx, phys = _physical(table, SPMD_SETTINGS)
    stages = DistributedPlanner(cfg).plan_query_stages("job", phys)

    def find(n):
        if isinstance(n, SpmdAggregateExec):
            return n
        for c in n.children():
            r = find(c)
            if r is not None:
                return r
        return None

    spmd = next(s for s in (find(st) for st in stages) if s is not None)
    tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="t")
    out = pa.Table.from_batches(list(spmd.execute(0, tctx))).sort_by("region")
    # the host fallback would produce identical rows; require the mesh path
    assert spmd.last_path == "mesh"

    host = (
        table.group_by("region")
        .aggregate([("amount", "sum"), ("qty", "count"), ("amount", "min"),
                    ("qty", "sum")])
        .sort_by("region")
    )
    assert out.column("region").to_pylist() == host.column("region").to_pylist()
    assert out.column("c").to_pylist() == host.column("qty_count").to_pylist()
    assert out.column("sq").to_pylist() == host.column("qty_sum").to_pylist()
    np.testing.assert_allclose(
        out.column("s").to_numpy(), host.column("amount_sum").to_numpy(),
        rtol=1e-4,
    )
    np.testing.assert_allclose(
        out.column("mn").to_numpy(), host.column("amount_min").to_numpy(),
        rtol=1e-6, atol=1e-6,
    )


def _find_spmd(stages):
    def find(n):
        if isinstance(n, SpmdAggregateExec):
            return n
        for c in n.children():
            r = find(c)
            if r is not None:
                return r
        return None

    return next(s for s in (find(st) for st in stages) if s is not None)


def _run_spmd(table, group_cols, aggs, n_partitions=4, settings=SPMD_SETTINGS):
    from ballista_tpu.physical.plan import TaskContext

    cfg = BallistaConfig(settings)
    ctx = ExecutionContext(cfg)
    ctx.register_record_batches("t", table, n_partitions=n_partitions)
    df = ctx.table("t").aggregate([col(c) for c in group_cols], aggs)
    phys = ctx.create_physical_plan(df.logical_plan())
    spmd = _find_spmd(DistributedPlanner(cfg).plan_query_stages("job", phys))
    tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="t")
    out = pa.Table.from_batches(list(spmd.execute(0, tctx)))
    return spmd, out


def test_mesh_high_cardinality_takes_mesh_path():
    """>=100k groups run the sorted chunked-segment mesh path (per-shard
    reads + in-program segment fold + psum), matching the host oracle —
    the unrolled path's 1024-group ceiling does not apply to the mesh."""
    rng = np.random.default_rng(7)
    N, G = 300_000, 130_000
    table = pa.table(
        {
            "k": pa.array(rng.integers(0, G, N).astype(np.int64)),
            "v": pa.array(rng.uniform(0, 100, N)),
            "q": pa.array(rng.integers(1, 50, N).astype(np.int64)),
        }
    )
    spmd, out = _run_spmd(
        table, ["k"],
        [F.sum(col("v")).alias("s"), F.count(col("q")).alias("c"),
         F.min(col("v")).alias("mn"), F.sum(col("q")).alias("sq")],
        n_partitions=5,  # 5 partitions over 8 shards: empty shards included
    )
    assert spmd.last_path == "mesh"
    ora = (
        table.group_by("k")
        .aggregate([("v", "sum"), ("q", "count"), ("v", "min"), ("q", "sum")])
        .sort_by("k")
    )
    got = out.sort_by("k")
    assert got.num_rows == ora.num_rows > 100_000
    np.testing.assert_array_equal(
        got.column("k").to_numpy(), ora.column("k").to_numpy()
    )
    np.testing.assert_array_equal(
        got.column("c").to_numpy(), ora.column("q_count").to_numpy()
    )
    np.testing.assert_array_equal(
        got.column("sq").to_numpy(), ora.column("q_sum").to_numpy()
    )
    np.testing.assert_allclose(
        got.column("s").to_numpy(), ora.column("v_sum").to_numpy(), rtol=1e-4
    )
    np.testing.assert_allclose(
        got.column("mn").to_numpy(), ora.column("v_min").to_numpy(), rtol=1e-6
    )


def test_mesh_multi_column_key():
    """Composite group keys get globally-consistent codes from the
    per-shard-distincts union ranking."""
    rng = np.random.default_rng(11)
    n = 6000
    table = pa.table(
        {
            "region": pa.array(
                np.array(["east", "west", "north", "south"])[rng.integers(0, 4, n)]
            ),
            "tier": pa.array(rng.integers(0, 7, n).astype(np.int64)),
            "amount": pa.array(rng.uniform(0, 100, n)),
        }
    )
    spmd, out = _run_spmd(
        table, ["region", "tier"],
        [F.sum(col("amount")).alias("s"), F.count(col("amount")).alias("c")],
        n_partitions=6,
    )
    assert spmd.last_path == "mesh"
    ora = (
        table.group_by(["region", "tier"])
        .aggregate([("amount", "sum"), ("amount", "count")])
        .sort_by([("region", "ascending"), ("tier", "ascending")])
    )
    got = out.sort_by([("region", "ascending"), ("tier", "ascending")])
    assert got.column("region").to_pylist() == ora.column("region").to_pylist()
    assert got.column("tier").to_pylist() == ora.column("tier").to_pylist()
    assert got.column("c").to_pylist() == ora.column("amount_count").to_pylist()
    np.testing.assert_allclose(
        got.column("s").to_numpy(), ora.column("amount_sum").to_numpy(),
        rtol=1e-4,
    )


def test_mesh_skewed_run_lengths_unify_tile_width():
    """One shard holds a single hot group (long runs -> large L1) while the
    rest are high-cardinality (L1=8): shards must rebuild their layouts to
    one shared tile width before stacking (the force_L1 branch)."""
    rng = np.random.default_rng(13)
    # first half: ONE mega-group (its shard sees a 0 percentile over the
    # group grid -> L1=8); second half: every group 1..1100 at count 16
    # (-> L1=16). The shards must agree on a tile width, so at least one
    # rebuilds with force_L1.
    G = 1100  # > 1024: the sorted mesh path
    mega = np.zeros(G * 32, dtype=np.int64)
    dense = np.tile(np.arange(1, G + 1, dtype=np.int64), 32)
    keys = np.concatenate([mega, dense])
    table = pa.table(
        {
            "k": pa.array(keys),
            "v": pa.array(rng.uniform(0, 10, len(keys))),
        }
    )
    spmd, out = _run_spmd(
        table, ["k"],
        [F.sum(col("v")).alias("s"), F.count(col("v")).alias("c")],
        n_partitions=2,
    )
    assert spmd.last_path == "mesh"
    ora = (
        table.group_by("k").aggregate([("v", "sum"), ("v", "count")]).sort_by("k")
    )
    got = out.sort_by("k")
    assert got.num_rows == ora.num_rows > 1024  # sorted mesh path
    np.testing.assert_array_equal(
        got.column("c").to_numpy(), ora.column("v_count").to_numpy()
    )
    np.testing.assert_allclose(
        got.column("s").to_numpy(), ora.column("v_sum").to_numpy(), rtol=1e-4
    )


def test_mesh_fewer_partitions_than_devices():
    """Empty shards contribute the identity; results stay exact."""
    table = _sales(n=500, seed=5)
    spmd, out = _run_spmd(
        table, ["region"],
        [F.sum(col("qty")).alias("sq"), F.max(col("amount")).alias("mx")],
        n_partitions=2,  # 6 of 8 shards empty
    )
    assert spmd.last_path == "mesh"
    ora = (
        table.group_by("region")
        .aggregate([("qty", "sum"), ("amount", "max")])
        .sort_by("region")
    )
    got = out.sort_by("region")
    assert got.column("sq").to_pylist() == ora.column("qty_sum").to_pylist()
    np.testing.assert_allclose(
        got.column("mx").to_numpy(), ora.column("amount_max").to_numpy(),
        rtol=1e-6,
    )


def test_mesh_readback_recorded():
    """Multi-chip readback accounting (ISSUE 3): the mesh aggregate's d2h
    result transfer must flow through record_readback on BOTH programs —
    unrolled (G <= 1024) and sorted (G > 1024) — so readback_stats
    does not undercount pod runs."""
    from ballista_tpu.ops.runtime import readback_stats

    # unrolled mesh program
    readback_stats(reset=True)
    table = _sales(n=3000, seed=21)
    spmd, out = _run_spmd(
        table, ["region"],
        [F.sum(col("amount")).alias("s"), F.count(col("qty")).alias("c")],
    )
    assert spmd.last_path == "mesh"
    s = readback_stats(reset=True)
    assert s["readbacks"] >= 1
    assert s["rows"] > 0 and s["bytes"] > 0

    # sorted mesh program (G > MAX_GROUPS)
    rng = np.random.default_rng(23)
    n, G = 60_000, 5_000
    big = pa.table(
        {
            "k": pa.array(rng.integers(0, G, n).astype(np.int64)),
            "v": pa.array(rng.uniform(0, 10, n)),
        }
    )
    spmd, out = _run_spmd(
        big, ["k"], [F.sum(col("v")).alias("s"), F.count(col("v")).alias("c")]
    )
    assert spmd.last_path == "mesh"
    assert out.num_rows > 1024  # the sorted path actually ran
    s = readback_stats(reset=True)
    assert s["readbacks"] >= 1
    assert s["rows"] >= out.num_rows  # padded group axis covers every group
    assert s["bytes"] > 0


def test_mesh_join_readback_recorded():
    """The SPMD mesh join reads its matching plane back over d2h — those
    transfers must be accounted too (they were the unrecorded sites ISSUE 3
    calls out in parallel/spmd_join.py)."""
    import pyarrow.parquet as pq  # noqa: F401  (parity with other suites)

    from ballista_tpu.ops.runtime import readback_stats
    from ballista_tpu.parallel.spmd_join import SpmdJoinExec
    from ballista_tpu.physical.plan import TaskContext

    rng = np.random.default_rng(29)
    n_b, n_p = 500, 4000
    build = pa.table(
        {
            "bk": pa.array(np.arange(n_b).astype(np.int64)),
            "bv": pa.array(rng.uniform(0, 1, n_b)),
        }
    )
    probe = pa.table(
        {
            "pk": pa.array(rng.integers(0, n_b + 50, n_p).astype(np.int64)),
            "pv": pa.array(rng.uniform(0, 1, n_p)),
        }
    )
    cfg = BallistaConfig(SPMD_SETTINGS)
    ctx = ExecutionContext(cfg)
    ctx.register_record_batches("b", build, n_partitions=2)
    ctx.register_record_batches("p", probe, n_partitions=3)
    df = ctx.table("b").join(ctx.table("p"), ["bk"], ["pk"], how="inner")
    phys = ctx.create_physical_plan(df.logical_plan())
    stages = DistributedPlanner(cfg).plan_query_stages("job", phys)

    def find(n):
        if isinstance(n, SpmdJoinExec):
            return n
        for c in n.children():
            r = find(c)
            if r is not None:
                return r
        return None

    spmd = next((find(st) for st in stages if find(st) is not None), None)
    assert spmd is not None, "planner did not emit SpmdJoinExec"
    readback_stats(reset=True)
    tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="j")
    out = pa.Table.from_batches(list(spmd.execute(0, tctx)))
    assert spmd.last_path == "mesh"
    s = readback_stats(reset=True)
    assert s["readbacks"] >= 2  # matched row ids + probe row ids at minimum
    assert s["rows"] > 0 and s["bytes"] > 0
    # sanity: the join itself is right
    ora = build.join(probe, keys="bk", right_keys="pk", join_type="inner")
    assert out.num_rows == ora.num_rows


def _sales_spmd_exec(cfg):
    table = _sales(n=800, seed=9)
    ctx = ExecutionContext(cfg)
    ctx.register_record_batches("t", table, n_partitions=3)
    df = ctx.table("t").aggregate(
        [col("region")], [F.sum(col("amount")).alias("s")]
    )
    phys = ctx.create_physical_plan(df.logical_plan())
    return table, _find_spmd(
        DistributedPlanner(cfg).plan_query_stages("job", phys)
    )


def test_mesh_error_fails_the_task(monkeypatch):
    """Only a reasoned decline goes to the host. Any other error inside the
    mesh program (an XLA compile error, an exhausted device, a sharding
    error) fails the task: on the chip a host answer would hide it."""
    from ballista_tpu.physical.plan import TaskContext
    from ballista_tpu.utils import tracing

    cfg = BallistaConfig(SPMD_SETTINGS)
    _table, spmd = _sales_spmd_exec(cfg)

    def boom(ctx):
        raise RuntimeError("injected mesh failure")

    monkeypatch.setattr(spmd, "_execute_mesh", boom)
    tracing.reset()
    tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="t")
    with pytest.raises(RuntimeError, match="injected mesh failure"):
        list(spmd.execute(0, tctx))
    c = tracing.counters()
    assert c.get("spmd.host_fallback") is None
    assert c.get("spmd.mesh") is None


def test_mesh_decline_runs_host_subplan_and_is_counted(monkeypatch):
    from ballista_tpu.ops.runtime import UnsupportedOnDevice
    from ballista_tpu.physical.plan import TaskContext
    from ballista_tpu.utils import tracing

    cfg = BallistaConfig(SPMD_SETTINGS)
    table, spmd = _sales_spmd_exec(cfg)

    def decline(ctx):
        raise UnsupportedOnDevice("injected decline")

    monkeypatch.setattr(spmd, "_execute_mesh", decline)
    tracing.reset()
    tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="t")
    out = pa.Table.from_batches(list(spmd.execute(0, tctx)))
    assert spmd.last_path == "host"
    c = tracing.counters()
    assert c.get("spmd.host_fallback") == 1
    assert c.get("spmd.mesh") is None
    ora = table.group_by("region").aggregate([("amount", "sum")]).sort_by("region")
    got = out.sort_by("region")
    np.testing.assert_allclose(
        got.column("s").to_numpy(), ora.column("amount_sum").to_numpy(),
        rtol=1e-4,
    )


def test_mesh_larger_than_device_count_is_an_error():
    """The mesh is never shrunk to fit: asking for more devices than the
    process has fails the task (the suite runs on 8 virtual devices)."""
    from ballista_tpu.physical.plan import TaskContext

    cfg = BallistaConfig({**SPMD_SETTINGS, "ballista.tpu.mesh": "data:16"})
    _table, spmd = _sales_spmd_exec(cfg)
    tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="t")
    with pytest.raises(ValueError, match="needs 16 devices"):
        list(spmd.execute(0, tctx))


def test_distributed_spmd_end_to_end(sales_table):
    """Full path: BallistaContext -> scheduler -> DistributedPlanner(spmd) ->
    executor runs the mesh program -> client fetches the result."""
    cluster = StandaloneCluster(
        n_executors=1, config=BallistaConfig(SPMD_SETTINGS)
    )
    try:
        host, port = cluster.scheduler_addr
        c = BallistaContext(host, port, settings=SPMD_SETTINGS)
        c.register_record_batches("sales", sales_table, n_partitions=3)
        out = (
            c.table("sales")
            .aggregate([col("region")], [F.sum(col("amount")).alias("total"),
                                         F.count(col("id")).alias("n")])
            .sort(col("region").sort())
            .collect()
        )
        assert out.column("region").to_pylist() == ["east", "north", "west"]
        assert out.column("total").to_pylist() == [120.0, 40.0, 145.0]
        assert out.column("n").to_pylist() == [4, 2, 4]
        c.close()
    finally:
        cluster.shutdown()


def test_admission_declines_mesh_when_model_prefers_host(tmp_path):
    """Mesh admission rides the cost model (ISSUE 16 satellite): with BOTH
    the mesh and host rates warm for this stage shape and the mesh
    predicted slower, execute() routes to the host subplan up front (no
    mesh launch) — last_path == "host", identical rows. Re-seeding the
    model mesh-cheap flips the same node back to the mesh."""
    from ballista_tpu.ops import costmodel
    from ballista_tpu.physical.plan import TaskContext
    from ballista_tpu.utils import tracing

    table = _sales()
    settings = {
        **SPMD_SETTINGS,
        "ballista.tpu.cost_model": "true",
        "ballista.tpu.cost_model_dir": str(tmp_path / "costs"),
    }
    cfg = BallistaConfig(settings)
    ctx, phys = _physical(table, settings)
    stages = DistributedPlanner(cfg).plan_query_stages("job", phys)

    def find(n):
        if isinstance(n, SpmdAggregateExec):
            return n
        for c in n.children():
            r = find(c)
            if r is not None:
                return r
        return None

    spmd = next(s for s in (find(st) for st in stages) if s is not None)
    fp = spmd.fingerprint()[:12]
    costmodel.reset(clear_dir=True)
    costmodel.configure(cfg)
    try:
        costmodel.seed("mesh.agg|" + fp, 1.0, 10.0)
        costmodel.seed("mesh.agg.host|" + fp, 1.0, 1e-4, engine="host")
        declined_before = tracing.counters().get("spmd.host_declined", 0)
        tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="t")
        host_out = pa.Table.from_batches(
            list(spmd.execute(0, tctx))
        ).sort_by("region")
        assert spmd.last_path == "host"
        assert (
            tracing.counters().get("spmd.host_declined", 0)
            == declined_before + 1
        )

        # inverse seeding (seed replaces the bucket history) re-admits the
        # mesh on the very next execute — and the rows cannot move
        costmodel.seed("mesh.agg|" + fp, 1.0, 1e-6)
        costmodel.seed("mesh.agg.host|" + fp, 1.0, 10.0, engine="host")
        mesh_out = pa.Table.from_batches(
            list(spmd.execute(0, tctx))
        ).sort_by("region")
        assert spmd.last_path == "mesh"
        # summation ORDER differs between paths: exact on every column but
        # the float sum, which gets the same tolerance the mesh-vs-host
        # equivalence test uses
        for name in ("region", "c", "sq"):
            assert (mesh_out.column(name).to_pylist()
                    == host_out.column(name).to_pylist())
        np.testing.assert_allclose(
            mesh_out.column("s").to_numpy(), host_out.column("s").to_numpy(),
            rtol=1e-4,
        )
        np.testing.assert_allclose(
            mesh_out.column("mn").to_numpy(), host_out.column("mn").to_numpy(),
            rtol=1e-6, atol=1e-6,
        )
    finally:
        costmodel.reset(clear_dir=True)


def test_admission_stays_mesh_while_host_rate_is_cold(tmp_path):
    """A warm mesh rate alone must NOT decline: the gate needs both sides
    warm, so the cold-start behavior is exactly the pre-model ladder."""
    from ballista_tpu.ops import costmodel
    from ballista_tpu.physical.plan import TaskContext

    table = _sales()
    settings = {
        **SPMD_SETTINGS,
        "ballista.tpu.cost_model": "true",
        "ballista.tpu.cost_model_dir": str(tmp_path / "costs"),
    }
    cfg = BallistaConfig(settings)
    ctx, phys = _physical(table, settings)
    stages = DistributedPlanner(cfg).plan_query_stages("job", phys)

    def find(n):
        if isinstance(n, SpmdAggregateExec):
            return n
        for c in n.children():
            r = find(c)
            if r is not None:
                return r
        return None

    spmd = next(s for s in (find(st) for st in stages) if s is not None)
    costmodel.reset(clear_dir=True)
    costmodel.configure(cfg)
    try:
        # arbitrarily slow mesh, but no host observation → admit
        costmodel.seed("mesh.agg|" + spmd.fingerprint()[:12], 1.0, 1e9)
        tctx = TaskContext(config=cfg, work_dir="/tmp", job_id="t")
        list(spmd.execute(0, tctx))
        assert spmd.last_path == "mesh"
    finally:
        costmodel.reset(clear_dir=True)
