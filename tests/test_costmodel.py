"""Adaptive execution (ISSUE 10): the measured cost model and its three
consumers.

Contract under test, in order of importance:

1. **Bit-identity** — the cost model changes WHERE a partition runs,
   never what it returns. Every routing outcome here (extended tier,
   partial-offload split, build-side swap, skew re-plan) is asserted
   bit-identical to the host oracle.
2. **Cold-start safety** — a cold, corrupt, or fingerprint-mismatched
   store reproduces the pre-adaptive static routing exactly.
3. **Honest accounting** — every decision lands in the routing
   accumulator, predictions carry their observations, and the mispredict
   accounting sums (mispredicts <= predictions; rate = m/p).
"""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.engine import ExecutionContext
from ballista_tpu.ops import costmodel, kernels
from ballista_tpu.ops.join import device_join_indices, try_device_inner_join
from ballista_tpu.ops.kernels import (
    JOIN_EXTENDED_TIERS,
    JOIN_GATHER_HARD_CAP,
    JOIN_MULTIPLICITY_TIERS,
    join_extended_tier,
)
from ballista_tpu.ops.runtime import (
    bucket_rows,
    join_path_stats,
    record_routing,
    reset_residency,
    routing_stats,
)
from ballista_tpu.physical.joinutil import join_indices
from ballista_tpu.utils import tracing

TOP_TIER = JOIN_MULTIPLICITY_TIERS[-1]


def _fresh():
    kernels._stage_cache.clear()
    kernels._stage_cache_pins.clear()
    kernels._stage_latest.clear()
    reset_residency()
    routing_stats(reset=True)
    join_path_stats(reset=True)


@pytest.fixture
def cm(tmp_path):
    """Cost model bound to a throwaway persisted store, drained routing
    accumulators, and guaranteed post-test reset (the module is process-
    global state, like the stage cache)."""
    _fresh()
    costmodel.reset(clear_dir=True)
    cfg = BallistaConfig({
        "ballista.tpu.cost_model": "true",
        "ballista.tpu.cost_model_dir": str(tmp_path / "costs"),
    })
    costmodel.configure(cfg)
    yield cfg
    costmodel.reset(clear_dir=True)
    _fresh()


# -- store: roundtrip, corruption, fingerprint -------------------------------

def test_store_roundtrip(cm, tmp_path):
    """Observations survive flush + reset (a simulated fresh process
    lazily reloads the persisted entries and predicts from them)."""
    for _ in range(costmodel.MIN_OBSERVATIONS):
        costmodel.observe("op.x", 1024, 0.010)
    costmodel.flush()
    assert (tmp_path / "costs" / "costs.json").exists()
    costmodel.reset()  # fresh process: in-memory store gone, dir kept
    costmodel.configure(cm)
    p = costmodel.predict("op.x", 1024)
    assert p is not None and abs(p - 0.010) < 1e-9


def test_store_corruption_starts_empty(cm, tmp_path):
    d = tmp_path / "costs"
    d.mkdir(parents=True, exist_ok=True)
    (d / "costs.json").write_text("{definitely not json")
    routing_stats(reset=True)
    assert costmodel.predict("op.x", 64) is None
    assert costmodel.snapshot() == {}
    ev = routing_stats(reset=True)["events"]
    assert ev.get("cost_store_corrupt") == 1


def test_store_fingerprint_mismatch_ignored(cm, tmp_path):
    """A store written by a different jax/jaxlib/backend stack must never
    steer this one: ignored wholesale, reason recorded."""
    d = tmp_path / "costs"
    d.mkdir(parents=True, exist_ok=True)
    (d / "costs.json").write_text(json.dumps({
        "format": 1, "fingerprint": "cm1|some-other-stack",
        "entries": {"op.x|device|b64": {"s": 1.0, "units": 64, "n": 99}},
    }))
    routing_stats(reset=True)
    assert costmodel.predict("op.x", 64) is None
    assert routing_stats(reset=True)["events"].get(
        "cost_store_fingerprint_mismatch") == 1


def test_flush_merges_other_writers(cm, tmp_path):
    """Last-writer-wins per KEY, not per file: another process's entries
    for keys we never touched survive our flush."""
    costmodel.seed("ours", 64, 0.001)
    costmodel.flush()
    blob = json.loads((tmp_path / "costs" / "costs.json").read_text())
    blob["entries"]["theirs|device|b64"] = {"s": 0.5, "units": 64, "n": 8}
    (tmp_path / "costs" / "costs.json").write_text(json.dumps(blob))
    costmodel.observe("ours", 64, 0.001)  # dirty again
    costmodel.flush()
    merged = json.loads((tmp_path / "costs" / "costs.json").read_text())
    assert "theirs|device|b64" in merged["entries"]
    assert "ours|device|b64" in merged["entries"]


# -- prediction: buckets, priors, forgetting, retier -------------------------

def test_cold_predict_is_none(cm):
    assert costmodel.predict("never.seen", 1000) is None


def test_exact_bucket_preferred_over_global(cm):
    costmodel.seed("op.y", 64, 0.001)       # 64-bucket: ~1.6e-5 s/unit
    costmodel.seed("op.y", 4096, 0.400)     # 4096-bucket: ~1e-4 s/unit
    p_small = costmodel.predict("op.y", 64)
    p_big = costmodel.predict("op.y", 4096)
    assert abs(p_small - 0.001) < 1e-9
    assert abs(p_big - 0.400) < 1e-9
    # an unseen bucket falls back to the op-global rate (non-None)
    assert costmodel.predict("op.y", 1 << 20) is not None


def test_prediction_needs_min_observations(cm):
    costmodel.observe("op.z", 128, 0.002)  # n=1 < MIN_OBSERVATIONS
    assert costmodel.predict("op.z", 128) is None


def test_exponential_forgetting_bounds_history(cm):
    for _ in range(200):
        costmodel.observe("op.f", 256, 0.001)
    entry = costmodel.snapshot()["op.f|device|b256"]
    # history halves at saturation: n can never run away to 200
    assert entry["n"] <= 2 * 32 + 1


def test_retier_replaces_history(cm):
    costmodel.seed("op.r", 512, 10.0)  # absurdly slow prior
    costmodel.retier("op.r", 512, 0.001)
    p = costmodel.predict("op.r", 512)
    assert p is not None and p < 0.01
    assert routing_stats(reset=True)["events"].get("retier") == 1


def test_check_mispredict_is_symmetric(cm):
    """The canonical check re-tiers on gross deviation in EITHER
    direction (an over-predicted rate suppressing admission is as wrong
    as an under-predicted one admitting too much)."""
    assert not costmodel.check_mispredict("op.c", 64, None, 1.0)
    assert not costmodel.check_mispredict("op.c", 64, 0.010, 0.011)
    assert costmodel.check_mispredict("op.c", 64, 0.001, 0.010)  # slower
    assert costmodel.predict("op.c", 64) == pytest.approx(0.010)
    assert costmodel.check_mispredict("op.c", 64, 0.100, 0.002)  # faster
    assert costmodel.predict("op.c", 64) == pytest.approx(0.002)
    assert routing_stats(reset=True)["events"].get("retier") == 2


def test_disabled_model_noops():
    costmodel.reset(clear_dir=True)
    costmodel.observe("op.off", 64, 1.0)
    assert costmodel.predict("op.off", 64) is None
    assert costmodel.snapshot() == {}


# -- routing accumulator accounting ------------------------------------------

def test_routing_accounting_sums(cm):
    routing_stats(reset=True)
    record_routing("device", "join", 0.010, 0.011)   # fine
    record_routing("device", "join", 0.001, 0.010)   # 10x over: mispredict
    record_routing("host", "join", 0.030, 0.002)     # 15x under: mispredict
    record_routing("split", "join")                  # no prediction
    s = routing_stats(reset=True)
    assert s["engines"] == {"device": 2, "host": 1, "split": 1}
    assert s["predictions"] == 3 and s["mispredicts"] == 2
    assert s["mispredict_rate"] == pytest.approx(2 / 3)
    assert s["predictions"] <= sum(s["engines"].values())
    assert abs(s["predicted_s"] - 0.041) < 1e-9
    assert abs(s["observed_s"] - 0.023) < 1e-9
    # reset drained everything
    s2 = routing_stats()
    assert not s2["engines"] and s2["predictions"] == 0


# -- tier selection units ----------------------------------------------------

def _warm_extended(probe_slots, host_units, dev_s=1e-4, host_s=10.0):
    """Seed the store so the 512 gather looks cheap and the host join
    expensive for the given shape."""
    costmodel.seed("join.gather", probe_slots * JOIN_EXTENDED_TIERS[0], dev_s)
    costmodel.seed("join.host", host_units, host_s, engine="host")


def test_extended_tier_cold_store_declines(cm):
    assert join_extended_tier(TOP_TIER + 10, 1024, 100_000) is None


def test_extended_tier_warm_store_admits(cm):
    _warm_extended(1024, 100_000)
    got = join_extended_tier(TOP_TIER + 10, 1024, 100_000)
    assert got is not None
    tier, dev, host = got
    assert tier == JOIN_EXTENDED_TIERS[0]
    assert dev < 0.75 * host


def test_extended_tier_unfavorable_evidence_declines(cm):
    _warm_extended(1024, 100_000, dev_s=10.0, host_s=1e-4)
    assert join_extended_tier(TOP_TIER + 10, 1024, 100_000) is None


def test_extended_tier_hard_cap_is_absolute(cm):
    """No store, however warm, admits past the hard cap — it bounds the
    worst case a wrong (or adversarial) store can cost."""
    slots = JOIN_GATHER_HARD_CAP // JOIN_EXTENDED_TIERS[0] + 1
    _warm_extended(slots, 100_000)
    assert join_extended_tier(TOP_TIER + 10, slots, 100_000) is None


def test_extended_tier_multiplicity_past_top_extended(cm):
    _warm_extended(1024, 100_000)
    assert join_extended_tier(JOIN_EXTENDED_TIERS[-1] + 1, 1024,
                              100_000) is None


def test_extended_tier_readmits_cap_decline_at_natural_width(cm):
    """A join declined purely on the ELEMENT cap (multiplicity inside the
    static ladder) re-admits at its natural static width under the hard
    cap — not at a 2x-wasteful extended width."""
    from ballista_tpu.ops.kernels import JOIN_GATHER_CAP

    slots = JOIN_GATHER_CAP // TOP_TIER + 1  # past the element cap at 256
    assert slots * TOP_TIER <= JOIN_GATHER_HARD_CAP
    costmodel.seed("join.gather", slots * TOP_TIER, 1e-4)
    costmodel.seed("join.host", 500_000, 10.0, engine="host")
    got = join_extended_tier(TOP_TIER - 6, slots, 500_000)
    assert got is not None and got[0] == TOP_TIER


# -- partial offload: split at the tier boundary -----------------------------

def _skewed_join(monster_mult=TOP_TIER + 60, tail=1500, n_probe=3000, seed=3):
    """Build with ONE monster key past the top static tier + a unique
    tail; probes guaranteed to hit the monster."""
    rng = np.random.default_rng(seed)
    build = np.concatenate([
        np.arange(tail, dtype=np.int64),
        np.full(monster_mult, tail // 2, dtype=np.int64),
    ])
    rng.shuffle(build)
    probe = np.concatenate([
        rng.integers(-1, tail + 50, n_probe - 2).astype(np.int64),
        np.full(2, tail // 2, dtype=np.int64),
    ])
    return build, probe


def _assert_oracle_equal(res, build, probe):
    assert res is not None
    build_idx, probe_idx, counts = res
    bi, pi = join_indices(build, probe, "inner")
    assert build_idx.tolist() == bi.tolist()
    assert probe_idx.tolist() == pi.tolist()
    np.testing.assert_array_equal(counts, np.bincount(pi, minlength=len(probe)))


def test_partial_offload_bit_equality(cm):
    """The acceptance shape: a join just past a static tier boundary runs
    SPLIT (device prefix + host remainder, merged) instead of wholesale
    host — bit-identical to the host oracle, decision recorded."""
    build, probe = _skewed_join()
    res = device_join_indices(build, probe, config=cm)
    _assert_oracle_equal(res, build, probe)
    s = routing_stats(reset=True)
    assert s["engines"].get("split") == 1
    assert s["events"].get("split") == 1
    assert join_path_stats(reset=True)["paths"].get("split") == 1


def test_partial_offload_without_config_keeps_static_contract(cm):
    """Direct callers that pass no config get the pre-adaptive ladder
    exactly: the same shape steps aside wholesale."""
    build, probe = _skewed_join()
    join_path_stats(reset=True)
    assert device_join_indices(build, probe) is None
    assert join_path_stats(reset=True)["paths"] == {"step_aside": 1}


def test_partial_offload_model_off_keeps_static_contract(cm):
    build, probe = _skewed_join()
    off = BallistaConfig({"ballista.tpu.cost_model": "false"})
    join_path_stats(reset=True)
    assert device_join_indices(build, probe, config=off) is None
    assert join_path_stats(reset=True)["paths"] == {"step_aside": 1}


def test_partial_offload_broad_duplication_not_split(cm):
    """Dozens of distinct hot keys is broad duplication, not skew — the
    split escape must not engage (host-wholesale handles it)."""
    rng = np.random.default_rng(9)
    hot_keys = np.arange(24, dtype=np.int64)  # > _SPLIT_MAX_HOT_KEYS
    build = np.concatenate([
        np.repeat(hot_keys, TOP_TIER + 10),
        np.arange(100, 400, dtype=np.int64),
    ])
    rng.shuffle(build)
    probe = np.concatenate([
        np.repeat(hot_keys, 2),
        rng.integers(0, 400, 500).astype(np.int64),
    ])
    join_path_stats(reset=True)
    assert device_join_indices(build, probe, config=cm) is None
    assert join_path_stats(reset=True)["paths"] == {"step_aside": 1}


# -- extended admission e2e + mispredict-driven re-tiering -------------------

def test_warm_store_runs_previously_declined_shape(cm):
    """ISSUE 10 acceptance: with a warm cost store, a multiplicity-300
    join the static ladder declines runs ON DEVICE at an extended tier,
    bit-identical to the host oracle."""
    build, probe = _skewed_join(monster_mult=300)
    probe_slots = bucket_rows(len(probe), 16)
    _warm_extended(probe_slots, len(build) + len(probe))
    join_path_stats(reset=True)
    res = device_join_indices(build, probe, config=cm)
    _assert_oracle_equal(res, build, probe)
    s = routing_stats(reset=True)
    assert s["engines"].get("device") == 1
    assert join_path_stats(reset=True)["paths"].get("device") == 1


def test_mispredict_retier_pulls_admission_back(cm):
    """An over-eager store admits an extended tier once; the gross
    mispredict REPLACES the bucket's history with the observed cost, and
    the very next decision for the shape falls back to the static
    ladder."""
    # 20 distinct hot keys: NOT a split candidate, so the post-retier
    # decision is a clean step-aside, not a split
    hot = np.repeat(np.arange(20, dtype=np.int64), 300)
    build = np.concatenate([hot, np.arange(100, 1100, dtype=np.int64)])
    rng = np.random.default_rng(11)
    rng.shuffle(build)
    probe = np.concatenate([
        np.arange(20, dtype=np.int64),
        rng.integers(0, 1100, 800).astype(np.int64),
    ])
    probe_slots = bucket_rows(len(probe), 16)
    # absurdly fast gather prior + a host prior slow enough to admit but
    # fast enough that the REAL gather cost loses to it after the retier
    costmodel.seed("join.gather", probe_slots * JOIN_EXTENDED_TIERS[0], 1e-9)
    costmodel.seed("join.host", len(build) + len(probe), 0.002, engine="host")
    res = device_join_indices(build, probe, config=cm)
    _assert_oracle_equal(res, build, probe)
    s = routing_stats(reset=True)
    assert s["engines"].get("device") == 1
    assert s["events"].get("retier", 0) >= 1
    assert s["mispredicts"] >= 1
    # the store now predicts the REAL gather cost (compile included),
    # which loses to the seeded host rate: static ladder again
    join_path_stats(reset=True)
    assert device_join_indices(build, probe, config=cm) is None
    assert join_path_stats(reset=True)["paths"] == {"step_aside": 1}


# -- runtime re-planning: build-side swap ------------------------------------

def test_build_side_swap_bit_identity(cm):
    """A planned build side 4x+ larger than the probe swaps sides on
    device (sort the smaller plane); the restored probe-major order is
    bit-identical to the unswapped run and the host oracle."""
    rng = np.random.default_rng(13)
    build = pa.table({"bk": pa.array(np.arange(9000), type=pa.int64())})
    pk = rng.integers(0, 9500, 400)
    probe = pa.table({"pk": pa.array(pk, type=pa.int64())})
    routing_stats(reset=True)
    swapped = try_device_inner_join(build, probe, ["bk"], ["pk"], config=cm)
    assert routing_stats(reset=True)["events"].get("join_build_swapped") == 1
    plain = try_device_inner_join(build, probe, ["bk"], ["pk"])
    assert swapped is not None and plain is not None
    np.testing.assert_array_equal(swapped[0], plain[0])
    np.testing.assert_array_equal(swapped[1], plain[1])


def test_failed_build_swap_records_one_decision(cm):
    """A speculative swap whose swapped shape declines must not leak its
    probe's host decline into the counters — only the planned-side
    attempt's outcome lands, so one join counts exactly one decision.
    The tracing counters must agree: an uncommitted probe's declines
    leave no phantom device.host_fallback/step_aside trace either."""
    rng = np.random.default_rng(17)
    # planned build: unique keys, > 4x the probe -> the swap triggers;
    # swapped build (= the probe) has 20 hot keys x 300 — multiplicity
    # past the top tier AND too many distinct hot keys to split, so the
    # swapped ladder declines and the planned sides run on device
    build = pa.table({"bk": pa.array(np.arange(25_000), type=pa.int64())})
    pk = np.repeat(np.arange(20, dtype=np.int64), 300)
    rng.shuffle(pk)
    probe = pa.table({"pk": pa.array(pk, type=pa.int64())})
    routing_stats(reset=True)
    join_path_stats(reset=True)
    trace_before = tracing.counters()
    res = try_device_inner_join(build, probe, ["bk"], ["pk"], config=cm)
    assert res is not None
    bi, pi = join_indices(np.arange(25_000), pk, "inner")
    np.testing.assert_array_equal(res[0], bi)
    np.testing.assert_array_equal(res[1], pi)
    s = routing_stats(reset=True)
    assert s["engines"] == {"device": 1}
    assert "join_build_swapped" not in s["events"]
    assert join_path_stats(reset=True)["paths"] == {"device": 1}
    trace_after = tracing.counters()
    for name in ("device.host_fallback", "device.step_aside"):
        assert trace_after.get(name, 0) == trace_before.get(name, 0), name


# -- runtime re-planning: general skew handler -------------------------------

def test_skew_split_plan_units():
    from ballista_tpu.ops.stage import SKEW_MAX_DOMINANT, skew_split_plan

    # one monster group among small tails: split exactly the monster
    codes = np.sort(np.concatenate([
        np.arange(3000), np.full(2049, 1500),
    ])).astype(np.int64)
    plan = skew_split_plan(codes, 3000)
    assert plan is not None
    L1, n_dom = plan
    assert n_dom == 1 and L1 <= 16  # tail runs are 1-2 rows
    # uniformly huge groups: nothing to split, not skew
    broad = np.repeat(np.arange(66, dtype=np.int64), 17_000)
    assert skew_split_plan(broad, 66) is None
    assert skew_split_plan(np.zeros(10, dtype=np.int64), 1) is None


def _skewed_topk_table(seed=17, n_small=3000, monster=2049):
    rng = np.random.default_rng(seed)
    g = np.concatenate([np.arange(n_small), np.full(monster, n_small)])
    return pa.table({
        "g": pa.array(g, type=pa.int64()),
        "v": pa.array(rng.uniform(-1e9, 1e9, len(g))
                      + rng.uniform(0, 1e-6, len(g))),
    })


@pytest.mark.parametrize("model", ["true", "false"])
def test_skew_replan_e2e_bit_equality(tmp_path, model):
    """q10's monster-group shape through the engine: with the cost model
    on, the failed one-chunk cover re-plans to the tail cover + in-program
    segment fold (skew_replan recorded); off keeps the default chunking.
    Bit-equal to the host either way."""
    _fresh()
    t = _skewed_topk_table()
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)
    out = {}
    for backend in ("tpu", "cpu"):
        ctx = ExecutionContext(BallistaConfig({
            "ballista.executor.backend": backend,
            "ballista.tpu.cost_model": model,
        }))
        ctx.register_parquet("t", path)
        sql = ("select g, min(v) mn, max(v) mx, count(*) c from t "
               "group by g order by mn, g limit 15")
        out[backend] = ctx.sql(sql).collect()
    got, want = out["tpu"].to_pydict(), out["cpu"].to_pydict()
    assert got["g"] == want["g"] and got["c"] == want["c"]
    for col in ("mn", "mx"):
        for a, b in zip(got[col], want[col]):
            assert np.float64(a).tobytes() == np.float64(b).tobytes()
    replans = routing_stats(reset=True)["events"].get("skew_replan", 0)
    if model == "true":
        assert replans >= 1
    else:
        assert replans == 0
    _fresh()


# -- chunked double-buffered h2d upload --------------------------------------

def test_upload_array_chunked_bit_identity(cm, monkeypatch):
    import jax.numpy as jnp

    from ballista_tpu.ops import runtime

    monkeypatch.setattr(runtime, "_H2D_MIN_CHUNKED", 1 << 12)
    monkeypatch.setattr(runtime, "_H2D_CHUNK_BYTES", 1 << 10)
    arr = np.arange(4096, dtype=np.int64).reshape(512, 8)
    routing_stats(reset=True)
    up = runtime.upload_array(arr)
    np.testing.assert_array_equal(np.asarray(up), np.asarray(jnp.asarray(arr)))
    assert routing_stats(reset=True)["events"].get("h2d_chunked") == 1
    # per-chunk timings landed in the cost store as h2d observations
    h2d = [k for k in costmodel.snapshot() if k.startswith("h2d|")]
    assert h2d, "chunked upload recorded no h2d observations"
    # small arrays keep the plain single dispatch
    small = np.arange(16, dtype=np.int64)
    routing_stats(reset=True)
    np.testing.assert_array_equal(np.asarray(runtime.upload_array(small)),
                                  small)
    assert not routing_stats(reset=True)["events"].get("h2d_chunked")
    # cost model OFF restores the single-put path exactly (no chunk copy,
    # no transient HBM peak), whatever the array size
    costmodel.reset(clear_dir=True)
    routing_stats(reset=True)
    np.testing.assert_array_equal(np.asarray(runtime.upload_array(arr)), arr)
    assert not routing_stats(reset=True)["events"].get("h2d_chunked")


def test_h2d_chunk_size_tuned_from_observed_rates(cm, monkeypatch):
    """ISSUE 13 satellite (PR 10 residue): the per-chunk h2d transfer size
    follows the cost store's observed per-bucket rates — the best warm
    bucket wins, a cold store keeps the static default — and the pick is
    surfaced as h2d_chunk_bytes in routing stats. Bit-identical by
    construction (chunking never changes the concatenated bytes)."""
    import jax.numpy as jnp

    from ballista_tpu.ops import runtime

    monkeypatch.setattr(runtime, "_H2D_MIN_CHUNKED", 1 << 12)
    monkeypatch.setattr(runtime, "_H2D_CHUNK_BYTES", 1 << 10)
    monkeypatch.setattr(runtime, "_H2D_CHUNK_CANDIDATES", (1 << 9, 1 << 11))
    # cold store: the static default stands
    assert runtime._h2d_chunk_bytes() == 1 << 10
    # warm rates: the 2 KiB bucket observed much faster per byte
    costmodel.seed("h2d", float(1 << 9), 1.0)
    costmodel.seed("h2d", float(1 << 11), 0.1)
    assert runtime._h2d_chunk_bytes() == 1 << 11
    arr = np.arange(8192, dtype=np.int64).reshape(1024, 8)
    routing_stats(reset=True)
    up = runtime.upload_array(arr)
    np.testing.assert_array_equal(np.asarray(up), np.asarray(jnp.asarray(arr)))
    rs = routing_stats(reset=True)
    assert rs["events"].get("h2d_chunked") == 1
    assert rs["h2d_chunk_bytes"] == 1 << 11
    # flipping the observed rates flips the pick
    costmodel.seed("h2d", float(1 << 9), 0.001)
    assert runtime._h2d_chunk_bytes() == 1 << 9
    # a bucket below MIN_OBSERVATIONS never competes, however fast it looks
    costmodel.seed("h2d", float(1 << 9), 1000.0)        # warm but terrible
    costmodel.seed("h2d", float(1 << 11), 0.0001, n=1)  # fast but unproven
    assert runtime._h2d_chunk_bytes() == 1 << 9


# -- AOT disk tier for the device-join programs (PR 8 residue) ---------------

def test_join_programs_aot_disk_tier(tmp_path):
    """The runs kernel + gather program reload from the AOT disk tier in a
    cold process (compile_hit_disk, zero fresh traces), bit-identically."""
    from ballista_tpu.ops import aotcache
    from ballista_tpu.ops import join as jmod

    aotcache.reset(clear_disk_dir=True)
    aotcache.configure(BallistaConfig({
        "ballista.tpu.aot_cache": str(tmp_path / "aot"),
    }))
    jmod._runs_kernel.cache_clear()
    jmod._gather_kernel.cache_clear()
    build = np.repeat(np.arange(50, dtype=np.int64), 3)
    probe = np.arange(-5, 60, dtype=np.int64)
    tracing.counters("serving", reset=True)
    first = device_join_indices(build, probe)
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_trace", 0) >= 2  # runs + gather traced fresh
    assert s.get("aot_saved", 0) >= 2
    # cold process: fresh wrappers + empty memory map -> disk hits
    aotcache.reset()
    jmod._runs_kernel.cache_clear()
    jmod._gather_kernel.cache_clear()
    second = device_join_indices(build, probe)
    s = tracing.counters("serving", reset=True)
    assert s.get("compile_hit_disk", 0) >= 2, s
    assert not s.get("compile_trace"), s
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)
    aotcache.reset(clear_disk_dir=True)
    aotcache.configure(BallistaConfig({}))


# -- adversarial store entries never change results --------------------------

def test_adversarial_store_entries_bit_identity(cm):
    """A poisoned store (absurd rates both directions) may mis-route, but
    every route is bit-identical to the oracle — the invariant the fuzz
    slice sweeps at scale."""
    build, probe = _skewed_join(monster_mult=TOP_TIER + 100)
    for dev_s, host_s in ((1e-12, 100.0), (100.0, 1e-12)):
        costmodel.reset()
        costmodel.configure(cm)
        probe_slots = bucket_rows(len(probe), 16)
        costmodel.seed("join.gather",
                       probe_slots * JOIN_EXTENDED_TIERS[0], dev_s)
        costmodel.seed("join.host", len(build) + len(probe), host_s,
                       engine="host")
        res = device_join_indices(build, probe, config=cm)
        if res is not None:
            _assert_oracle_equal(res, build, probe)
        else:
            # declined to host: the caller's host join IS the oracle
            pass
