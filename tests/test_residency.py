"""HBM residency accounting: LRU eviction replaces first-come streaming.

When the budget fills, the least-recently-touched pins of OTHER stages are
evicted (their stages re-prepare on next touch); an entry that cannot fit
even after eviction streams. First-come residency would have made every
query after the budget filled stream per iteration — fatal for the SF=100
suite where one stage's lineitem residency is most of the chip.
"""

import numpy as np
import pytest

from ballista_tpu.ops import runtime


class _FakeStage:
    def __init__(self):
        self._device_cache = {}


@pytest.fixture(autouse=True)
def _clean_residency():
    runtime.reset_residency()
    yield
    runtime.reset_residency()


def test_lru_evicts_oldest_other_stage():
    a, b, c = _FakeStage(), _FakeStage(), _FakeStage()
    budget = 100
    assert runtime.reserve_and_pin(a, 0, {"x": 1}, a._device_cache, 40, budget)
    assert runtime.reserve_and_pin(b, 0, {"x": 2}, b._device_cache, 40, budget)
    runtime.touch_residency(a, 0)  # a is now more recent than b
    # c needs 40: evicting b (oldest) suffices; a must survive
    assert runtime.reserve_and_pin(c, 0, {"x": 3}, c._device_cache, 40, budget)
    assert 0 in a._device_cache
    assert 0 not in b._device_cache, "LRU victim must be dropped"
    assert 0 in c._device_cache
    assert runtime.resident_bytes() == 80


def test_own_partitions_never_victims():
    a = _FakeStage()
    budget = 100
    assert runtime.reserve_and_pin(a, 0, {"x": 1}, a._device_cache, 60, budget)
    # a second partition of the SAME stage must not evict the first; it
    # simply fails to pin (streams per query)
    assert not runtime.reserve_and_pin(a, 1, {"x": 2}, a._device_cache, 60, budget)
    assert 0 in a._device_cache and 1 not in a._device_cache
    assert runtime.resident_bytes() == 60


def test_oversized_entry_streams_without_evicting():
    a, b = _FakeStage(), _FakeStage()
    budget = 100
    assert runtime.reserve_and_pin(a, 0, {"x": 1}, a._device_cache, 50, budget)
    # b can NEVER fit: it must stream without disturbing a's pin (an
    # eviction sweep here would repeat on every one of b's queries)
    assert not runtime.reserve_and_pin(b, 0, {"x": 2}, b._device_cache, 150, budget)
    assert runtime.resident_bytes() == 50
    assert 0 in a._device_cache


def test_huge_victim_not_evicted_for_small_need():
    """Evicting a pin much larger than the request costs more re-upload
    than the newcomer streaming ever would (A/B alternation thrash)."""
    a, b = _FakeStage(), _FakeStage()
    budget = 100
    assert runtime.reserve_and_pin(a, 0, {"x": 1}, a._device_cache, 95, budget)
    # b needs 10; the only victim holds 95 > 4x10 — b streams, a survives
    assert not runtime.reserve_and_pin(b, 0, {"x": 2}, b._device_cache, 10, budget)
    assert 0 in a._device_cache
    assert runtime.resident_bytes() == 95


def test_multi_victim_eviction_plan():
    a, b, c = _FakeStage(), _FakeStage(), _FakeStage()
    budget = 100
    assert runtime.reserve_and_pin(a, 0, {"x": 1}, a._device_cache, 30, budget)
    assert runtime.reserve_and_pin(b, 0, {"x": 2}, b._device_cache, 30, budget)
    # c needs 80: both victims (60 total <= 4x80) go, oldest first
    assert runtime.reserve_and_pin(c, 0, {"x": 3}, c._device_cache, 80, budget)
    assert 0 not in a._device_cache and 0 not in b._device_cache
    assert runtime.resident_bytes() == 80


def test_release_stage_clears_lru_bookkeeping():
    a = _FakeStage()
    assert runtime.reserve_and_pin(a, 0, {"x": 1}, a._device_cache, 10, 100)
    runtime.release_stage_residency(a)
    assert runtime.resident_bytes() == 0
    assert not runtime._pinned and not runtime._last_used
    # retired stages refuse new pins
    assert not runtime.reserve_and_pin(a, 0, {"x": 1}, a._device_cache, 10, 100)


def _device(n):
    import jax.numpy as jnp

    return jnp.zeros(n, dtype=jnp.int8)  # n device bytes


def test_attach_to_pinned_grows_the_reservation_and_goes_with_the_entry():
    a = _FakeStage()
    ent = {"x": 1}
    assert runtime.reserve_and_pin(a, 0, ent, a._device_cache, 40, 100)
    assert runtime.attach_to_pinned(a, 0, ent, a._device_cache, "m", {"d": _device(30)}, 100)
    assert runtime.resident_bytes() == 70 and runtime._reservations[(id(a), 0)] == 70
    # a second builder of the same thing reserves it once; a smaller one shrinks it
    assert runtime.attach_to_pinned(a, 0, ent, a._device_cache, "m", {"d": _device(30)}, 100)
    assert runtime.resident_bytes() == 70
    assert runtime.attach_to_pinned(a, 0, ent, a._device_cache, "m", {"d": _device(10)}, 100)
    assert runtime.resident_bytes() == 50 and ent["m"]["d"].nbytes == 10
    runtime.release_stage_residency(a)
    assert runtime.resident_bytes() == 0 and not runtime._reservations


@pytest.mark.parametrize("case", ["not_pinned", "another_entry", "evicted", "no_room"])
def test_attach_to_pinned_keeps_nothing_where_the_entry_is_not_kept(case):
    a, b = _FakeStage(), _FakeStage()
    ent = {"x": 1}
    if case != "not_pinned":
        assert runtime.reserve_and_pin(a, 0, ent, a._device_cache, 40, 100)
    target = {"x": 2} if case == "another_entry" else ent
    if case == "evicted":
        assert runtime.reserve_and_pin(b, 0, {"y": 1}, b._device_cache, 80, 100)
        assert 0 not in a._device_cache
    held = runtime.resident_bytes()
    size = 70 if case == "no_room" else 10  # 40 + 70 > 100, and its own pin is no victim
    assert not runtime.attach_to_pinned(a, 0, target, a._device_cache, "m", {"d": _device(size)}, 100)
    assert "m" not in target and runtime.resident_bytes() == held


def test_attach_to_pinned_evicts_another_stage_s_oldest_pin_for_room():
    a, b = _FakeStage(), _FakeStage()
    ent = {"x": 1}
    assert runtime.reserve_and_pin(b, 0, {"y": 1}, b._device_cache, 50, 100)
    assert runtime.reserve_and_pin(a, 0, ent, a._device_cache, 40, 100)
    assert runtime.attach_to_pinned(a, 0, ent, a._device_cache, "m", {"d": _device(30)}, 100)
    assert 0 not in b._device_cache and runtime.resident_bytes() == 70


def test_racing_builders_of_one_value_reserve_it_once():
    """Task threads of two queries on one partition may both miss and both
    attach what they built: the reservation holds it once, whoever wins, and
    an eviction between two attaches leaves nothing behind."""
    import sys
    import threading

    a, b = _FakeStage(), _FakeStage()
    ent = {"x": 1}
    assert runtime.reserve_and_pin(a, 0, ent, a._device_cache, 40, 100)
    value = {"d": _device(30)}
    kept = []

    def build():
        for _ in range(200):
            kept.append(runtime.attach_to_pinned(a, 0, ent, a._device_cache, "m", dict(value), 100))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=build) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert len(kept) == 16 * 200 and all(kept)
    assert runtime.resident_bytes() == 70 == runtime._reservations[(id(a), 0)]
    # evicted for another stage: the later attach keeps nothing
    assert runtime.reserve_and_pin(b, 0, {"y": 1}, b._device_cache, 90, 100)
    assert not runtime.attach_to_pinned(a, 0, ent, a._device_cache, "m", dict(value), 100)
    assert runtime.resident_bytes() == 90


def test_a_factagg_partition_s_reservation_counts_its_rank_maps(tmp_path):
    """What a fact aggregate keeps on the device beside its tiles (PR 29:
    the rank maps built by the first query) is inside the partition's
    reservation: the budget sees it, and a release gives it back."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.ops import kernels
    from ballista_tpu.ops.factagg import FactAggregateStage

    rng = np.random.default_rng(3)
    pq.write_table(
        pa.table({"fk": pa.array(rng.integers(0, 2000, 30_000), type=pa.int64()),
                  "v": pa.array(rng.uniform(0, 10, 30_000))}),
        str(tmp_path / "fact.parquet"))
    pq.write_table(
        pa.table({"dk": pa.array(np.arange(2000), type=pa.int64()),
                  "attr": pa.array([f"a{i % 7}" for i in range(2000)])}),
        str(tmp_path / "dim.parquet"))
    kernels._stage_cache.clear()
    kernels._stage_latest.clear()
    ctx = ExecutionContext(BallistaConfig({"ballista.executor.backend": "tpu"}))
    ctx.register_parquet("fact", str(tmp_path / "fact.parquet"))
    ctx.register_parquet("dim", str(tmp_path / "dim.parquet"))
    sql = "select fk, sum(v) as s, attr from dim, fact where dk = fk group by fk, attr order by fk"
    tiles = None
    for _ in range(2):
        ctx.sql(sql).collect()
        (stage,) = [s for s in kernels._stage_cache.values()
                    if isinstance(s, FactAggregateStage)]
        (ent,) = stage._prepared.values()
        maps = runtime.entry_device_bytes(ent["rank_maps"])
        tiles = tiles or runtime.entry_device_bytes(ent) - maps
        assert maps > 0
        assert runtime._reservations[(id(stage), 0)] == tiles + maps
        assert runtime.resident_bytes() == tiles + maps
    runtime.release_stage_residency(stage)
    assert runtime.resident_bytes() == 0


def test_stage_past_budget_declines_to_host(tmp_path):
    """A stage whose tiles cannot fit the HBM budget must decline BEFORE
    device allocation (host fallback), not OOM the chip — and results stay
    correct via the host path."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.engine import ExecutionContext

    rng = np.random.default_rng(2)
    n = 60_000
    t = pa.table(
        {
            "k": pa.array(rng.choice(["x", "y", "z"], n)),
            "v": pa.array(rng.uniform(0, 1e6, n)),  # high-card: stays f32
        }
    )
    pq.write_table(t, tmp_path / "t.parquet")
    results = {}
    for budget in ("32", str(1 << 30)):  # 32 bytes: nothing fits
        ctx = ExecutionContext(
            BallistaConfig(
                {
                    "ballista.executor.backend": "tpu",
                    "ballista.tpu.hbm_budget_bytes": budget,
                }
            )
        )
        ctx.register_parquet("t", str(tmp_path))
        out = ctx.sql(
            "select k, sum(v) as s, count(*) as c from t group by k order by k"
        ).collect()
        results[budget] = out.to_pydict()
    assert results["32"]["k"] == results[str(1 << 30)]["k"]
    assert results["32"]["c"] == results[str(1 << 30)]["c"]
    np.testing.assert_allclose(
        results["32"]["s"], results[str(1 << 30)]["s"], rtol=1e-5
    )


def test_eviction_preserves_running_consumers():
    """An evicted entry's arrays stay alive for a thread already holding
    them (Python references) — eviction only drops the cache slot."""
    a, b = _FakeStage(), _FakeStage()
    arr = np.arange(8)
    assert runtime.reserve_and_pin(a, 0, {"arr": arr}, a._device_cache, 60, 100)
    held = a._device_cache[0]["arr"]  # a task thread's reference
    assert runtime.reserve_and_pin(b, 0, {"x": 1}, b._device_cache, 60, 100)
    assert 0 not in a._device_cache
    np.testing.assert_array_equal(held, np.arange(8))


def test_two_real_stages_under_pressure_reach_steady_state(tmp_path):
    """Two real parquet-backed sorted stages alternating
    under a budget that fits either but not both. The thrash guards must
    converge: after one thrash cycle the cooldown pins a survivor and the
    other stage streams — NOT the A,B,A,B full re-prepare ping-pong plain
    LRU would give. Prepares (each one h2d upload on this path) are counted
    per stage; results must stay correct throughout."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.engine import ExecutionContext
    from ballista_tpu.ops import kernels
    from ballista_tpu.ops.stage import FusedAggregateStage

    rng = np.random.default_rng(11)
    n, g = 120_000, 2500  # >1024 groups: the sorted (one-upload) path
    for name, seed in (("ta", 1), ("tb", 2)):
        r = np.random.default_rng(seed)
        pq.write_table(
            pa.table(
                {
                    "k": pa.array(r.integers(0, g, n), type=pa.int64()),
                    "v": pa.array(r.uniform(-10, 10, n)),
                }
            ),
            str(tmp_path / f"{name}.parquet"),
        )

    def make_ctx(budget):
        ctx = ExecutionContext(
            BallistaConfig(
                {
                    "ballista.executor.backend": "tpu",
                    "ballista.tpu.hbm_budget_bytes": str(budget),
                }
            )
        )
        for name in ("ta", "tb"):
            ctx.register_parquet(name, str(tmp_path / f"{name}.parquet"))
        return ctx

    def q(t):
        return f"select k, sum(v) as s from {t} group by k order by k"

    # size the stages with an unconstrained run
    kernels._stage_cache.clear()
    runtime.reset_residency()
    big = make_ctx(1 << 30)
    oracle = {t: big.sql(q(t)).collect() for t in ("ta", "tb")}
    per_stage = runtime.resident_bytes() / 2
    assert per_stage > 0
    budget = int(per_stage * 1.25)  # fits either stage, not both

    kernels._stage_cache.clear()
    runtime.reset_residency()

    prepares = {}
    orig = FusedAggregateStage._prepare_partition_sorted

    def counting(self, partition, ctx):
        prepares[id(self)] = prepares.get(id(self), 0) + 1
        return orig(self, partition, ctx)

    FusedAggregateStage._prepare_partition_sorted = counting
    try:
        ctx = make_ctx(budget)
        history = []
        for cycle in range(4):
            for t in ("ta", "tb"):
                out = ctx.sql(q(t)).collect()
                assert out.equals(oracle[t]), f"cycle {cycle} {t} wrong"
            history.append(dict(prepares))
    finally:
        FusedAggregateStage._prepare_partition_sorted = orig

    assert runtime.resident_bytes() <= budget
    # steady state by cycle 3: exactly one stage re-prepares per cycle (the
    # streamer), the survivor stays pinned with zero further prepares
    deltas = []
    for c in (2, 3):
        d = {
            sid: history[c][sid] - history[c - 1][sid]
            for sid in history[c]
        }
        deltas.append(sorted(d.values()))
    assert deltas == [[0, 1], [0, 1]], (
        f"expected survivor+streamer steady state, got per-cycle prepare "
        f"deltas {deltas} (history {history})"
    )
    # and the ping-pong phase was bounded: no stage prepared more than twice
    # before steady state plus once per later cycle
    assert max(history[-1].values()) <= 2 + 2
