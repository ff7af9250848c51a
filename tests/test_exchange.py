"""HBM-resident cross-stage exchange tier (ISSUE 16).

The invariant under test everywhere: registering shuffle pieces in the
executor's in-memory exchange registry is PURE acceleration — the Arrow
piece on disk/shared storage stays the authoritative home, so eviction
(budget or chaos), executor death, stale attempts, and scheduler GC all
degrade silently down the storage -> Flight peer -> lineage ladder with
bit-identical results and zero extra task retries. The scheduler's
locality preference and the shared-store GC ride the same hints and must
never outrank fair-share order or break completed-job restarts.
"""

import os

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.ops import costmodel, exchange
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.utils import tracing

GROUP_SQL = (
    "select region, sum(amount) as s from sales group by region order by region"
)


@pytest.fixture(autouse=True)
def _clean_registry():
    exchange.reset()
    tracing.counters("exchange", reset=True)
    yield
    exchange.reset()
    tracing.counters("exchange", reset=True)


@pytest.fixture
def cm(tmp_path):
    """Cost model bound to a throwaway store (module-global, like the
    registry itself)."""
    costmodel.reset(clear_dir=True)
    cfg = BallistaConfig({
        "ballista.tpu.cost_model": "true",
        "ballista.tpu.cost_model_dir": str(tmp_path / "costs"),
    })
    costmodel.configure(cfg)
    yield cfg
    costmodel.reset(clear_dir=True)


def _batch(n, fill=1):
    return pa.record_batch({"v": pa.array([fill] * n, type=pa.int64())})


# -- registry unit behavior ---------------------------------------------------

def test_publish_resolve_roundtrip_and_counters():
    b = _batch(8)
    kept = exchange.publish(
        "e1", "job", 2, 0, 0, [b], b.schema, attempt=0,
        path="/w/job/2/0/0.arrow", budget=1 << 20,
    )
    assert kept
    hit = exchange.resolve("e1", "job", 2, 0, 0)
    assert hit is not None
    batches, nbytes = hit
    assert batches[0].equals(b) and nbytes == b.nbytes
    # keyed per executor: a peer in the SAME process must never see it
    assert exchange.resolve("e2", "job", 2, 0, 0) is None
    # path-keyed lookup (the Flight service's view)
    schema, pbatches, _ = exchange.resolve_path("/w/job/2/0/0.arrow")
    assert schema == b.schema and pbatches[0].equals(b)
    assert exchange.resident_bytes() == b.nbytes
    assert exchange.stage_resident("e1", "job", 2, 0)
    assert not exchange.stage_resident("e1", "job", 2, 1)
    s = tracing.counters("exchange", reset=True)
    assert s.get("published") == 1 and s.get("publish_bytes") == b.nbytes


def test_publish_rejects_over_budget_piece():
    b = _batch(100)
    assert not exchange.publish(
        "e1", "j", 1, 0, 0, [b], b.schema, attempt=0, path="/p",
        budget=b.nbytes - 1,
    )
    assert exchange.resolve("e1", "j", 1, 0, 0) is None
    assert tracing.counters("exchange", reset=True).get("skipped_budget") == 1


def test_budget_eviction_is_cost_gated_by_size(cm):
    """Cold model: predicted savings are bytes-proportional, so a small
    incomer cannot displace a bigger victim — but a bigger incomer evicts
    the smaller LRU entry."""
    big, small = _batch(100), _batch(25)
    budget = big.nbytes + small.nbytes - 8  # either alone fits, both don't
    assert exchange.publish("e1", "j", 1, 0, 0, [big], big.schema, 0,
                            "/p/big", budget)
    # smaller incomer: victim's predicted saving exceeds the incomer's
    assert not exchange.publish("e1", "j", 1, 1, 0, [small], small.schema, 0,
                                "/p/small", budget)
    assert exchange.resolve("e1", "j", 1, 0, 0) is not None
    assert tracing.counters("exchange", reset=True).get("skipped_budget") == 1
    # bigger incomer displaces the smaller resident
    exchange.reset()
    assert exchange.publish("e1", "j", 1, 1, 0, [small], small.schema, 0,
                            "/p/small", budget)
    assert exchange.publish("e1", "j", 1, 0, 0, [big], big.schema, 0,
                            "/p/big", budget)
    assert exchange.resolve("e1", "j", 1, 1, 0) is None
    assert exchange.resolve("e1", "j", 1, 0, 0) is not None
    assert tracing.counters("exchange", reset=True).get("evicted_budget") == 1


def test_budget_eviction_prices_at_observed_rates(cm):
    """The keep/evict decision consults the cost model's OBSERVED h2d +
    readback rates, not just sizes: a small entry whose bucket observed
    pathologically slow transfers outprices a byte-bigger incomer."""
    big, small = _batch(100), _batch(25)
    # the small entry's bucket transfers at a crawl; the big one's is fast
    costmodel.seed("h2d", float(small.nbytes), 10.0)
    costmodel.seed("readback", float(small.nbytes), 10.0)
    costmodel.seed("h2d", float(big.nbytes), 1e-6)
    costmodel.seed("readback", float(big.nbytes), 1e-6)
    budget = big.nbytes + small.nbytes - 8
    assert exchange.publish("e1", "j", 1, 1, 0, [small], small.schema, 0,
                            "/p/small", budget)
    # byte-bigger incomer now LOSES: evicting the slow-bucket entry would
    # forfeit more predicted transfer seconds than the incomer saves
    assert not exchange.publish("e1", "j", 1, 0, 0, [big], big.schema, 0,
                                "/p/big", budget)
    assert exchange.resolve("e1", "j", 1, 1, 0) is not None
    assert tracing.counters("exchange", reset=True).get("skipped_budget") == 1


def test_republish_newest_attempt_wins():
    """Speculation promotion / retry re-publish: the registry keeps exactly
    one entry per piece and the NEWEST attempt's batches (any attempt's
    output is bit-identical — the repo invariant — so serving it is
    always sound; the attempt is tracked for exactly this pin)."""
    b0, b1 = _batch(8, fill=1), _batch(8, fill=1)
    assert exchange.publish("e1", "j", 1, 0, 0, [b0], b0.schema, 0,
                            "/p/a0", 1 << 20)
    assert exchange.attempt_of("e1", "j", 1, 0, 0) == 0
    assert exchange.publish("e1", "j", 1, 0, 0, [b1], b1.schema, 2,
                            "/p/a2", 1 << 20)
    assert exchange.attempt_of("e1", "j", 1, 0, 0) == 2
    # the stale attempt's path no longer resolves; the new one does
    assert exchange.resolve_path("/p/a0") is None
    assert exchange.resolve_path("/p/a2") is not None
    assert exchange.resident_bytes() == b1.nbytes


def test_tenant_budget_enforced_before_global(cm):
    """ISSUE 19 satellite: a tenant at its residency cap evicts ITS OWN
    LRU entries (cost-gated, like the global policy) and can never
    displace another tenant's bytes to fit itself — the per-tenant ledger
    follows every publish and drop."""
    a1, a2, b1 = _batch(50), _batch(50), _batch(50)
    budget = 1 << 20  # the global cap never binds in this test
    t_budget = a1.nbytes + 8  # one piece per tenant fits, two do not
    assert exchange.publish("e1", "j", 1, 0, 0, [a1], a1.schema, 0, "/a1",
                            budget, tenant="alice", tenant_budget=t_budget)
    assert exchange.publish("e1", "j", 1, 1, 0, [b1], b1.schema, 0, "/b1",
                            budget, tenant="bob", tenant_budget=t_budget)
    assert exchange.tenant_resident_bytes("alice") == a1.nbytes
    assert exchange.tenant_resident_bytes("bob") == b1.nbytes
    # alice's second piece (equal saving): evicts HER LRU piece, not bob's
    assert exchange.publish("e1", "j", 1, 2, 0, [a2], a2.schema, 0, "/a2",
                            budget, tenant="alice", tenant_budget=t_budget)
    assert exchange.resolve("e1", "j", 1, 0, 0) is None  # a1 evicted
    assert exchange.resolve("e1", "j", 1, 1, 0) is not None  # bob intact
    assert exchange.resolve("e1", "j", 1, 2, 0) is not None
    assert exchange.tenant_resident_bytes("alice") == a2.nbytes
    assert exchange.tenant_resident_bytes("bob") == b1.nbytes
    s = tracing.counters("exchange", reset=True)
    assert s.get("evicted_tenant_budget") == 1, s
    assert not s.get("evicted_budget"), s


def test_tenant_budget_cost_gate_keeps_warmer_own_entry(cm):
    """Within one tenant the same cost gate applies: a smaller incomer
    whose predicted saving trails its own bigger resident's is skipped
    rather than evicting it."""
    big, small = _batch(100), _batch(25)
    t_budget = big.nbytes + 8
    assert exchange.publish("e1", "j", 1, 0, 0, [big], big.schema, 0,
                            "/big", 1 << 20,
                            tenant="alice", tenant_budget=t_budget)
    assert not exchange.publish("e1", "j", 1, 1, 0, [small], small.schema, 0,
                                "/small", 1 << 20,
                                tenant="alice", tenant_budget=t_budget)
    assert exchange.resolve("e1", "j", 1, 0, 0) is not None
    assert exchange.tenant_resident_bytes("alice") == big.nbytes
    s = tracing.counters("exchange", reset=True)
    assert s.get("skipped_budget") == 1, s
    # a single piece bigger than the tenant cap is rejected outright
    assert not exchange.publish("e1", "j", 1, 2, 0, [big], big.schema, 0,
                                "/big2", 1 << 20,
                                tenant="bob", tenant_budget=big.nbytes - 1)
    assert exchange.tenant_resident_bytes("bob") == 0


def test_tenant_budget_plumbed_from_job_settings():
    """End-to-end: ballista.tenant.residency_budget_bytes rides the job's
    settings into the executor's capture — an over-cap tenant's pieces
    are skipped (ladder reads, correct result), an uncapped run keeps
    registering."""
    t = _sales()
    capped_out, capped_stats, _ = _run_cluster(t, {
        "ballista.tenant.name": "alice",
        "ballista.tenant.residency_budget_bytes": "1",
    })
    plain_out, plain_stats, _ = _run_cluster(t, {})
    assert capped_out.equals(plain_out)
    assert capped_stats.get("published", 0) == 0, capped_stats
    assert capped_stats.get("skipped_budget", 0) >= 1, capped_stats
    assert plain_stats.get("published", 0) >= 1, plain_stats


def test_evict_and_evict_job():
    b = _batch(4)
    exchange.publish("e1", "ja", 1, 0, 0, [b], b.schema, 0, "/pa", 1 << 20)
    exchange.publish("e1", "jb", 1, 0, 0, [b], b.schema, 0, "/pb", 1 << 20)
    assert exchange.evict("e1", "ja", 1, 0, 0)
    assert not exchange.evict("e1", "ja", 1, 0, 0)
    assert exchange.evict_job("jb") == 1
    assert exchange.resident_bytes() == 0


# -- scheduler locality preference --------------------------------------------

def _state(config=None):
    from ballista_tpu.scheduler.kv import MemoryBackend
    from ballista_tpu.scheduler.state import SchedulerState

    return SchedulerState(
        MemoryBackend(), "exch",
        config=config or BallistaConfig({"ballista.tpu.cost_model_dir": ""}),
    )


def _identity_reader(residents):
    """Identity ShuffleReaderExec whose map outputs live on the executors
    named in `residents` (executor_id, resident, nbytes) triples."""
    from ballista_tpu.distributed.stages import ShuffleLocation, ShuffleReaderExec

    locs = [
        ShuffleLocation(eid, "h", 1, f"/x/{i}", stage_id=1, map_partition=i,
                        resident=res, nbytes=nb)
        for i, (eid, res, nb) in enumerate(residents)
    ]
    schema = pa.schema([("v", pa.int64())])
    return ShuffleReaderExec(locs, schema, len(locs), identity=True)


def test_locality_order_prefers_resident_partitions():
    """Partitions whose resident inputs live on THIS executor come first,
    biggest predicted saving first; everything else keeps the pinned
    sorted-by-str order (and an executor with nothing resident sees
    exactly that baseline order)."""
    st = _state()
    plan = _identity_reader([
        ("e1", False, 100), ("e2", True, 100),
        ("e1", True, 10_000_000), ("e1", True, 100),
    ])
    parts = {0, 1, 2, 3}
    ordered, preferred = st._locality_partition_order(plan, parts, "e1")
    assert preferred == {2, 3}
    assert ordered[0] == 2  # 10 MB resident beats 100 B resident
    assert ordered[1] == 3
    assert ordered[2:] == [0, 1]  # non-resident tail keeps baseline order
    base, none_pref = st._locality_partition_order(plan, parts, "e9")
    assert none_pref == set()
    assert base == sorted(parts, key=str)


def test_locality_order_is_uniform_for_hash_readers():
    """A non-identity reader consumes a slice of EVERY map output — no
    partition is more local than another, so the order stays the baseline."""
    from ballista_tpu.distributed.stages import ShuffleLocation, ShuffleReaderExec

    st = _state()
    locs = [
        ShuffleLocation("e1", "h", 1, "/x/0", stage_id=1, map_partition=0,
                        resident=True, nbytes=1000),
    ]
    plan = ShuffleReaderExec(locs, pa.schema([("v", pa.int64())]), 4,
                             identity=False)
    ordered, preferred = st._locality_partition_order(plan, {0, 1, 2, 3}, "e1")
    assert preferred == set()
    assert ordered == sorted({0, 1, 2, 3}, key=str)


# -- scheduler-led shared-store GC --------------------------------------------

def _completed_task(job, stage, part, storage_uri=""):
    t = pb.TaskStatus()
    t.partition_id.job_id = job
    t.partition_id.stage_id = stage
    t.partition_id.partition_id = part
    t.completed.executor_id = "e1"
    t.completed.path = storage_uri or f"/w/{job}/{stage}/{part}"
    t.completed.storage_uri = storage_uri
    return t


def test_gc_shared_store_job_sweeps_by_terminal_kind(tmp_path):
    root = tmp_path / "store"
    tasks = []
    for stage in (1, 2, 3):
        base = root / "jobc" / str(stage) / "0"
        base.mkdir(parents=True)
        (base / "0.arrow").write_bytes(b"x")
        tasks.append(_completed_task("jobc", stage, 0, str(base)))
    st = _state()
    tracing.counters("shuffle_tier", reset=True)
    # completed: intermediates sweep, the final stage stays for the client
    assert st._gc_shared_store_job("jobc", 3, tasks) == 2
    assert sorted(os.listdir(root / "jobc")) == ["3"]
    # failed: everything releases, the emptied job dir prunes with it
    assert st._gc_shared_store_job("jobc", None, tasks) == 1
    assert not (root / "jobc").exists()
    assert tracing.counters("shuffle_tier", reset=True).get("gc_stage_swept") == 3
    # work-dir-homed tasks (empty storage_uri) are never the scheduler's
    assert st._gc_shared_store_job(
        "jobl", None, [_completed_task("jobl", 1, 0)]
    ) == 0
    # a uri whose tail does not spell the task's own plan coordinates
    # never steers a delete (hostile or corrupt report)
    evil = tmp_path / "elsewhere"
    evil.mkdir()
    assert st._gc_shared_store_job(
        "jobc", None, [_completed_task("jobc", 1, 0, str(evil))]
    ) == 0
    assert evil.exists()


def test_result_cache_delete_sweeps_cached_final_stage(tmp_path):
    """Every way an entry leaves the cache releases its storage-homed
    result pieces: explicit invalidation and LRU eviction both sweep the
    job dir (the intermediates went at job completion)."""
    root = tmp_path / "store"
    cfg = BallistaConfig({
        "ballista.cache.results.max_entries": "1",
    })
    st = _state(cfg)

    def put(fp, job):
        base = root / job / "3" / "0"
        base.mkdir(parents=True)
        (base / "0.arrow").write_bytes(b"x")
        done = pb.CompletedJob()
        pl = done.partition_location.add()
        pl.partition_id.job_id = job
        pl.partition_id.stage_id = 3
        pl.partition_id.partition_id = 0
        pl.path = str(base)
        pl.storage_uri = str(base)
        assert st.result_cache_put(fp, done)

    tracing.counters("shuffle_tier", reset=True)
    put("fp-a", "joba")
    st.result_cache_invalidate("fp-a")
    assert not (root / "joba").exists()
    # LRU eviction (cap 1): inserting fp-c evicts fp-b and sweeps its job
    put("fp-b", "jobb")
    put("fp-c", "jobc")
    assert not (root / "jobb").exists()
    assert (root / "jobc").exists()
    assert tracing.counters("shuffle_tier", reset=True).get("gc_result_swept") == 2


# -- end to end ---------------------------------------------------------------

def _sales(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "region": pa.array(
            np.array(["east", "west", "north", "south"])[rng.integers(0, 4, n)]
        ),
        "amount": pa.array(rng.uniform(0, 100, n)),
    })


def _run_cluster(table, settings, n_executors=1):
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    exchange.reset()
    tracing.counters("exchange", reset=True)
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(n_executors=n_executors)
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings={
            "ballista.shuffle.partitions": "4",
            "ballista.cache.results": "false",
            **settings,
        })
        ctx.register_record_batches("sales", table, n_partitions=4)
        out = ctx.sql(GROUP_SQL).collect()
        ctx.close()
    finally:
        cluster.shutdown()
    return out, tracing.counters("exchange", reset=True), tracing.counters("recovery", reset=True)


def test_same_executor_consumer_skips_reupload_bit_identical():
    """ISSUE 16 acceptance: on a single-executor 2-stage run the reduce
    side resolves every local map piece from the registry (zero decode,
    zero h2d) — and the result is bit-identical to the exchange-off run."""
    t = _sales()
    on_out, on_stats, on_rec = _run_cluster(t, {})
    off_out, off_stats, _ = _run_cluster(t, {"ballista.tpu.exchange": "false"})
    assert on_out.equals(off_out)
    assert on_stats.get("published", 0) >= 1, on_stats
    assert on_stats.get("reupload_skipped", 0) >= 1, on_stats
    assert on_stats.get("h2d_bytes_saved", 0) > 0, on_stats
    assert on_rec.get("task_retry", 0) == 0, on_rec
    assert off_stats == {}, off_stats


def test_exchange_evict_chaos_degrades_to_ladder_zero_retries():
    """Every consume-time probe torn by exchange.evict chaos (rate 1.0):
    entries are dropped at the seam and every read walks the authoritative
    piece ladder — bit-identical to the exchange-off run, ZERO task
    retries, zero lineage events (the loss of a residency entry is not a
    data loss)."""
    t = _sales()
    chaos_out, cs, cr = _run_cluster(t, {
        "ballista.chaos.rate": "1.0",
        "ballista.chaos.seed": "5",
        "ballista.chaos.sites": "exchange.evict",
    })
    plain_out, _, _ = _run_cluster(t, {"ballista.tpu.exchange": "false"})
    assert chaos_out.equals(plain_out)
    assert cs.get("evicted_chaos", 0) >= 1, cs
    assert cs.get("reupload_skipped", 0) == 0, cs
    assert cs.get("miss", 0) >= 1, cs
    assert cr.get("chaos_injected", 0) >= 1, cr
    for event in ("task_retry", "fetch_failed", "map_recomputed"):
        assert cr.get(event, 0) == 0, (event, cr)


def test_executor_death_with_resident_only_consumer_recovers():
    """The registry dies with its executor: a consumer whose inputs were
    resident ONLY on the dead executor must recover through the ordinary
    Flight/lineage ladder (stale `resident` hints on completed tasks are
    advisory, never load-bearing) — results stay correct."""
    import ballista_tpu.scheduler.state as state_mod
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    exchange.reset()
    tracing.counters("recovery", reset=True)
    cluster = StandaloneCluster(n_executors=2)
    old_lease = state_mod.EXECUTOR_LEASE_SECS
    state_mod.EXECUTOR_LEASE_SECS = 1.0
    cluster.scheduler_impl.lost_task_check_interval = 0.3
    try:
        t = _sales()
        ctx = BallistaContext(*cluster.scheduler_addr, settings={
            "ballista.shuffle.partitions": "4",
            "ballista.cache.results": "false",
        })
        ctx.register_record_batches("sales", t, n_partitions=4)
        plan = ctx.sql(GROUP_SQL).logical_plan()
        job_id = ctx.submit(plan)
        status = ctx._wait_for_job(job_id, timeout=60.0)
        owners = {
            pl.executor_meta.id
            for pl in status.completed.partition_location
        }
        victim = next(ex for ex in cluster.executors if ex.id in owners)
        # the victim's registry entries die with it — drop them explicitly
        # too, mirroring a real process death inside this shared process
        victim.stop()
        exchange.reset()
        out = ctx._collect_results(job_id, plan.schema(), timeout=120.0)
        ctx.close()
        expected = (
            t.group_by("region").aggregate([("amount", "sum")])
            .rename_columns(["region", "s"]).sort_by("region")
        )
        got = out.sort_by("region")
        assert got.column("region").to_pylist() == expected.column(
            "region").to_pylist()
        np.testing.assert_allclose(
            got.column("s").to_pylist(), expected.column("s").to_pylist()
        )
        stats = tracing.counters("recovery", reset=True)
        assert stats.get("result_partition_restarted", 0) > 0, stats
    finally:
        state_mod.EXECUTOR_LEASE_SECS = old_lease
        cluster.shutdown()


def test_terminal_gc_sweeps_intermediates_on_shared_tier(tmp_path):
    """End to end: a completed shared-tier job leaves only its final stage
    in the store (the client fetch still works), intermediates swept at
    the terminal transition."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.executor.runtime import StandaloneCluster

    shared = tmp_path / "store"
    shared.mkdir()
    tracing.counters("shuffle_tier", reset=True)
    cluster = StandaloneCluster(n_executors=1)
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings={
            "ballista.shuffle.partitions": "4",
            "ballista.cache.results": "false",
            "ballista.shuffle.tier": "shared",
            "ballista.shuffle.dir": str(shared),
        })
        ctx.register_record_batches("sales", _sales(), n_partitions=4)
        out = ctx.sql(GROUP_SQL).collect()
        ctx.close()
    finally:
        cluster.shutdown()
    assert out.num_rows == 4
    jobs = os.listdir(shared)
    assert len(jobs) == 1, jobs
    stages = os.listdir(shared / jobs[0])
    assert len(stages) == 1, stages  # only the final stage survives
    tier = tracing.counters("shuffle_tier", reset=True)
    assert tier.get("gc_stage_swept", 0) >= 1, tier
