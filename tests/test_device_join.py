"""Device join kernel vs host join oracle."""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.engine import ExecutionContext
from ballista_tpu.ops.join import device_join_indices, device_membership_counts
from ballista_tpu.ops.runtime import bucket_rows


def test_device_join_indices_basic():
    build = np.array([10, 3, 7, 1], dtype=np.int64)
    probe = np.array([7, 7, 2, 10, 1], dtype=np.int64)
    build_idx, probe_idx, counts = device_join_indices(build, probe)
    assert counts.tolist() == [1, 1, 0, 1, 1]
    assert build_idx.tolist() == [2, 2, 0, 3]
    assert probe_idx.tolist() == [0, 1, 3, 4]


def test_device_join_expands_duplicates():
    """The retired unique-build-key decline: duplicate build keys expand to
    their full multiplicity, probe-major, build rows in original order."""
    build = np.array([5, 5, 6], dtype=np.int64)
    probe = np.array([5, 6, 5], dtype=np.int64)
    build_idx, probe_idx, counts = device_join_indices(build, probe)
    assert counts.tolist() == [2, 1, 2]
    assert build_idx.tolist() == [0, 1, 2, 0, 1]
    assert probe_idx.tolist() == [0, 0, 1, 2, 2]


def test_device_join_null_probe_keys():
    build = np.array([1, 2, 3], dtype=np.int64)
    probe = np.array([2, -1, 3], dtype=np.int64)  # -1 = null code
    _, probe_idx, counts = device_join_indices(build, probe)
    assert counts.tolist() == [1, 0, 1]
    assert probe_idx.tolist() == [0, 2]


@pytest.mark.parametrize("n", [1000, 5000])
def test_device_join_vs_host_random(n):
    rng = np.random.default_rng(3)
    build = rng.permutation(n * 2)[:n].astype(np.int64)  # unique
    probe = rng.integers(0, n * 2, n * 3).astype(np.int64)
    build_idx, probe_idx, counts = device_join_indices(build, probe)
    lookup = {int(k): i for i, k in enumerate(build)}
    hits = {int(p): int(b) for b, p in zip(build_idx, probe_idx)}
    for j in range(len(probe)):
        want = lookup.get(int(probe[j]), None)
        assert counts[j] == (0 if want is None else 1)
        if want is not None:
            assert hits[j] == want


def _tpch_join_sql():
    return (
        "select o_orderkey, c_name, o_totalprice from orders, customer "
        "where o_custkey = c_custkey and o_totalprice > 100000 "
        "order by o_totalprice desc limit 10"
    )


def test_tpu_backend_join_matches_cpu(tmp_path_factory):
    from benchmarks.tpch.datagen import generate, register_all

    d = str(tmp_path_factory.mktemp("tpch_join"))
    generate(d, sf=0.002, parts=2)
    out = {}
    for backend in ("cpu", "tpu"):
        ctx = ExecutionContext(BallistaConfig({"ballista.executor.backend": backend}))
        register_all(ctx, d)
        out[backend] = ctx.sql(_tpch_join_sql()).collect().to_pylist()
    assert out["cpu"] == out["tpu"]


# ---------------------------------------------------------------------------
# membership counting (ISSUE 7 satellite: the q13/q22 device path)
# ---------------------------------------------------------------------------


def test_device_membership_counts_matches_host_oracle():
    """The counts-only plane: per-probe run-lengths bit-equal to the host
    join_indices counts, nulls (code -1) on both sides included."""
    from ballista_tpu.ops.join import device_membership_counts
    from ballista_tpu.physical.joinutil import join_indices

    rng = np.random.default_rng(11)
    build = rng.integers(0, 40, 300).astype(np.int64)
    build[rng.integers(0, 300, 20)] = -1  # null build keys never match
    probe = rng.integers(0, 60, 500).astype(np.int64)
    probe[rng.integers(0, 500, 30)] = -1
    counts = device_membership_counts(build, probe)
    assert counts is not None
    # host oracle counts via the inner join's probe_idx multiplicities
    _b, p = join_indices(build, probe, "inner")
    want = np.bincount(p, minlength=len(probe)) if len(p) else np.zeros(len(probe), int)
    assert counts.tolist() == want.tolist()
    assert all(counts[probe < 0] == 0)


def _both_backends(tables, sql):
    out = {}
    for backend in ("cpu", "tpu"):
        ctx = ExecutionContext(BallistaConfig({"ballista.executor.backend": backend}))
        for name, t in tables.items():
            ctx.register_record_batches(name, t, n_partitions=1)
        out[backend] = ctx.sql(sql).collect().to_pylist()
    return out


def _count_join_tables(with_nulls=False):
    rng = np.random.default_rng(23)
    n_c, n_o = 200, 1500
    cust = pa.table({
        "c_id": pa.array(np.arange(n_c), type=pa.int64()),
        "c_grp": pa.array(rng.integers(0, 9, n_c), type=pa.int64()),
    })
    oid = rng.integers(0, 5000, n_o)
    okey = rng.integers(0, int(n_c * 1.3), n_o)  # some point past customers
    orders = {
        "o_id": pa.array(oid, type=pa.int64()),
        "o_cust": pa.array(okey, type=pa.int64()),
    }
    if with_nulls:
        # nulls in the COUNTED column (COUNT must skip them) and in the
        # join key (never matches)
        null_at = rng.random(n_o) < 0.15
        orders["o_id"] = pa.array(
            [None if m else int(v) for v, m in zip(oid, null_at)],
            type=pa.int64(),
        )
        key_null = rng.random(n_o) < 0.1
        orders["o_cust"] = pa.array(
            [None if m else int(v) for v, m in zip(okey, key_null)],
            type=pa.int64(),
        )
    return {"cust": cust, "orders": pa.table(orders)}


@pytest.mark.parametrize("with_nulls", [False, True])
def test_count_over_left_join_device_matches_cpu(with_nulls):
    """q13's shape: COUNT(right column) grouped by left keys over a LEFT
    join routes through the per-probe counts plane — tpu == cpu
    bit-equality (counts are exact ints), including NULL counted values
    and NULL join keys."""
    from ballista_tpu.utils import tracing

    sql = (
        "select c_grp, cnt, count(*) as dist from ("
        "  select c_id, c_grp, count(o_id) as cnt from cust "
        "  left outer join orders on c_id = o_cust group by c_id, c_grp"
        ") sub group by c_grp, cnt order by c_grp, cnt"
    )
    tracing.reset()
    out = _both_backends(_count_join_tables(with_nulls), sql)
    assert out["cpu"] == out["tpu"]
    assert tracing.counters().get("device.count_join", 0) >= 1


def test_anti_join_membership_device_matches_cpu():
    """q22's NOT EXISTS: the ANTI join keeps rows off counts == 0 on
    device, bit-identical to the host anti_right selection."""
    from ballista_tpu.ops.runtime import join_path_stats

    tables = _count_join_tables()
    sql = (
        "select c_grp, count(*) as n from cust where not exists ("
        "  select * from orders where o_cust = c_id"
        ") group by c_grp order by c_grp"
    )
    join_path_stats(reset=True)
    out = _both_backends(tables, sql)
    assert out["cpu"] == out["tpu"]
    assert join_path_stats(reset=True).get("paths", {}).get("device", 0) >= 1


def test_q13_q22_device_engaged_on_tpch(tmp_path_factory):
    """The ROADMAP carry-over struck for real: q13 and q22 run their
    membership counting on the device path (counter-asserted) and stay
    bit-identical to the cpu backend on real TPC-H data."""
    import pathlib

    from benchmarks.tpch.datagen import generate, register_all
    from ballista_tpu.utils import tracing

    d = str(tmp_path_factory.mktemp("tpch_q13"))
    generate(d, sf=0.002, parts=2)
    qdir = pathlib.Path(__file__).parent.parent / "benchmarks" / "tpch" / "queries"
    out = {}
    for backend in ("cpu", "tpu"):
        ctx = ExecutionContext(BallistaConfig({"ballista.executor.backend": backend}))
        register_all(ctx, d)
        tracing.reset()
        out[backend] = {
            q: ctx.sql((qdir / f"{q}.sql").read_text()).collect().to_pylist()
            for q in ("q13", "q22")
        }
        if backend == "tpu":
            assert tracing.counters().get("device.count_join", 0) >= 1
    # counts are ints and q22's sum is exact over these rows: bit-equality
    assert out["cpu"]["q13"] == out["tpu"]["q13"]
    assert out["cpu"]["q22"] == out["tpu"]["q22"]


# ---------------------------------------------------------------------------
# the runs program's two look-ups (ISSUE 34): a position table where the key
# range is dense, the paired search elsewhere
# ---------------------------------------------------------------------------


def _dup(rng, n_keys, hi, k):
    """`n_keys` distinct codes of [0, hi], each 1..k times, shuffled."""
    keys = rng.choice(hi + 1, size=n_keys, replace=False)
    build = np.repeat(keys, rng.integers(1, k + 1, n_keys)).astype(np.int64)
    rng.shuffle(build)
    return build


def _case_small_runs(rng):
    return _dup(rng, 300, 999, 3), rng.integers(0, 1000, 700).astype(np.int64)


def _case_top_tier(rng):
    from ballista_tpu.ops.kernels import JOIN_MULTIPLICITY_TIERS

    build = _dup(rng, 12, 40, JOIN_MULTIPLICITY_TIERS[-1])
    build = np.concatenate([build, np.full(JOIN_MULTIPLICITY_TIERS[-1], 41)])
    return build, rng.integers(0, 45, 90).astype(np.int64)


def _case_nulls_both_sides(rng):
    build, probe = _case_small_runs(rng)
    build[rng.integers(0, len(build), 40)] = -1
    probe[rng.integers(0, len(probe), 60)] = -1
    return build, probe


def _case_probe_outside_build_range(rng):
    # build codes in [200, 300], probes below, inside and above
    return _dup(rng, 60, 100, 4) + 200, rng.integers(0, 5000, 800).astype(np.int64)


def _case_power_of_two_planes(rng):
    # no pad slot on either side, and the top code of the range present
    build = np.concatenate([_dup(rng, 200, 1022, 1), [1023] * 56])
    return build.astype(np.int64), rng.integers(0, 1024, 2048).astype(np.int64)


def _case_build_of_one_row(rng):
    return np.array([7], dtype=np.int64), rng.integers(0, 12, 50).astype(np.int64)


def _case_all_build_null(rng):
    return np.full(20, -1, dtype=np.int64), rng.integers(-1, 30, 40).astype(np.int64)


_RUNS_CASES = [
    _case_small_runs, _case_top_tier, _case_nulls_both_sides,
    _case_probe_outside_build_range, _case_power_of_two_planes,
    _case_build_of_one_row, _case_all_build_null,
]


def _case_id(case):
    return case.__name__[len("_case_"):]


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("case", _RUNS_CASES, ids=_case_id)
def test_table_runs_is_match_runs_bit_for_bit(case, seed):
    """`counts` everywhere and `starts` wherever a probe has a match, over
    the padded planes the programs see (pad codes on both sides)."""
    import jax.numpy as jnp

    from ballista_tpu.ops.join import _PAD_CODE, match_runs, table_runs
    from ballista_tpu.ops.runtime import pad_to

    build, probe = case(np.random.default_rng(seed))
    b = jnp.asarray(pad_to(build.astype(np.int32), bucket_rows(len(build), 16), _PAD_CODE))
    p = jnp.asarray(pad_to(probe.astype(np.int32), bucket_rows(len(probe), 16), -1))
    starts, counts = (np.asarray(a) for a in match_runs(b[jnp.argsort(b, stable=True)], p))
    for entries in (bucket_rows(int(max(build.max(), probe.max())) + 1), 1 << 14):
        starts_t, counts_t = (np.asarray(a) for a in table_runs(b, p, entries))
        assert starts_t.dtype == counts_t.dtype == np.int32
        np.testing.assert_array_equal(counts_t, counts)
        np.testing.assert_array_equal(starts_t[counts > 0], starts[counts > 0])


@pytest.fixture(params=["table", "search"])
def method(request, monkeypatch):
    """Both look-ups over the same inputs: no range is dense once the cap is 0."""
    from ballista_tpu.ops import join as jmod

    if request.param == "search":
        monkeypatch.setattr(jmod, "_TABLE_MAX_ENTRIES", 0)
    return request.param


@pytest.mark.parametrize("case", _RUNS_CASES[:-1], ids=_case_id)
def test_both_entries_equal_the_host_oracle_by_either_method(case, method):
    from ballista_tpu.physical.joinutil import join_indices
    from ballista_tpu.utils import tracing

    build, probe = case(np.random.default_rng(5))
    bi_o, pi_o = join_indices(build, probe, "inner")
    tracing.reset()
    build_idx, probe_idx, counts = device_join_indices(build, probe)
    members = device_membership_counts(build, probe)
    assert tracing.counters("device") == {f"join_{method}_probes": 2 * len(probe)}
    np.testing.assert_array_equal(build_idx, bi_o)
    np.testing.assert_array_equal(probe_idx, pi_o)
    want = np.bincount(pi_o, minlength=len(probe))
    np.testing.assert_array_equal(counts, want)
    np.testing.assert_array_equal(members, want)


def _probe_spans():
    from ballista_tpu.utils import tracing

    return [s for s in tracing.spans()
            if s.name == "runtime.join" and s.attrs.get("path") != "encode"]


@pytest.mark.parametrize("top,want", [(4095, "table"), (4096, "search")],
                         ids=["range_at_the_cap", "range_past_the_cap"])
def test_the_cap_on_the_table_s_entries_decides(top, want, monkeypatch):
    from ballista_tpu.ops import join as jmod
    from ballista_tpu.physical.joinutil import join_indices
    from ballista_tpu.utils import tracing

    monkeypatch.setattr(jmod, "_TABLE_MAX_ENTRIES", 4096)
    rng = np.random.default_rng(top)
    build = np.concatenate([_dup(rng, 500, top - 1, 2), [top, top]])
    probe = np.concatenate([rng.integers(0, top + 1, 900), [top]]).astype(np.int64)
    tracing.reset()
    build_idx, probe_idx, _ = device_join_indices(build, probe)
    (span,) = _probe_spans()
    assert (span.attrs["method"], span.attrs["path"]) == (want, "device")
    assert span.attrs["entries"] == (4096 if want == "table" else 8192)
    assert tracing.counters("device") == {f"join_{want}_probes": len(probe)}
    bi_o, pi_o = join_indices(build, probe, "inner")
    np.testing.assert_array_equal(build_idx, bi_o)
    np.testing.assert_array_equal(probe_idx, pi_o)


@pytest.mark.parametrize("what", ["range", "build"])
def test_a_long_table_or_build_stays_away_from_a_short_probe(what):
    """The second bound, at the module's own constant: a prefix sum over 2M
    entries, or a scatter of a long build, to spare 1,024 probe slots their
    searches; the same build under enough probes reads the table."""
    from ballista_tpu.ops import join as jmod
    from ballista_tpu.utils import tracing

    rng = np.random.default_rng(9)
    per_slot = jmod._TABLE_SLOTS_PER_PROBE_SLOT
    top, rows = ((1 << 21) - 1, 3000) if what == "range" else (1023, 1024 * per_slot + 1)
    build = rng.integers(0, top + 1, rows).astype(np.int64)
    build[:2] = top
    slots = bucket_rows(rows, 16) if what == "build" else top + 1
    short = rng.integers(0, top + 1, 1000).astype(np.int64)
    enough = rng.integers(0, top + 1, slots // per_slot - 5).astype(np.int64)
    tracing.reset()
    device_membership_counts(build, short)
    device_membership_counts(build, enough)
    assert [(s.attrs["method"], s.attrs["entries"]) for s in _probe_spans()] == [
        ("search", top + 1), ("table", top + 1)]
    assert tracing.counters("device") == {"join_search_probes": len(short),
                                          "join_table_probes": len(enough)}


def test_a_wide_composite_key_keeps_the_search_and_the_counters_add_up():
    """A dense single key reads the table, two packed columns of 40,000 values
    each (a range of 1.6e9) search; the two counters sum to the probe rows of
    every probe span, and a decline counts under neither."""
    from ballista_tpu.ops.join import try_device_inner_join
    from ballista_tpu.utils import tracing

    rng = np.random.default_rng(4)
    build, probe = (pa.table({c: pa.array(rng.integers(0, 40_000, n)) for c in "ab"})
                    for n in (300, 2000))
    tracing.reset()
    assert try_device_inner_join(build, probe, ["a"], ["a"]) is not None
    assert try_device_inner_join(build, probe, ["a", "b"], ["a", "b"]) is not None
    assert device_membership_counts(np.arange(50), np.arange(70)) is not None
    assert device_membership_counts(np.arange(50), np.empty(0, np.int64)) is None
    spans = _probe_spans()
    assert [s.attrs.get("method") for s in spans] == ["table", "search", "table", None]
    assert spans[1].attrs["entries"] == 1 << 31 and spans[3].attrs["path"] == "host_fallback"
    counters = tracing.counters()
    assert counters["device.join_table_probes"] == 2000 + 70
    assert counters["device.join_search_probes"] == 2000
    assert sum(s.attrs["probe_rows"] for s in spans) == 2000 + 2000 + 70


def test_the_two_runs_programs_are_named_and_compile_once_a_shape(tmp_path, monkeypatch):
    from ballista_tpu.ops import aotcache
    from ballista_tpu.ops import join as jmod
    from ballista_tpu.utils import tracing

    aotcache.reset(clear_disk_dir=True)
    aotcache.configure(BallistaConfig({"ballista.tpu.aot_cache": str(tmp_path / "aot")}))
    jmod._runs_kernel.cache_clear()
    jmod._gather_kernel.cache_clear()
    try:
        rng = np.random.default_rng(8)
        build = _dup(rng, 100, 900, 2)
        tracing.reset()
        device_join_indices(build, rng.integers(0, 901, 400).astype(np.int64))
        monkeypatch.setattr(jmod, "_TABLE_MAX_ENTRIES", 0)
        device_join_indices(build, rng.integers(0, 901, 400).astype(np.int64))
        monkeypatch.undo()
        launched = [s.attrs["program"] for s in tracing.spans() if s.name == "runtime.launch"]
        assert launched[0::2] == ["join_runs_table", "join_runs"]
        assert launched[1] == launched[3] and launched[1].startswith("join_gather_w")
        first = tracing.counters("serving", reset=True)
        assert first["compile_trace"] == 3 and first["aot_saved"] == 3
        # the same buckets again, other rows: nothing compiles, by either method
        device_join_indices(build[:-3], rng.integers(0, 1000, 300).astype(np.int64))
        monkeypatch.setattr(jmod, "_TABLE_MAX_ENTRIES", 0)
        device_join_indices(build[:-3], rng.integers(0, 1000, 300).astype(np.int64))
        again = tracing.counters("serving", reset=True)
        assert not again.get("compile_trace") and not again.get("compile_hit_disk"), again
    finally:
        aotcache.reset(clear_disk_dir=True)
        aotcache.configure(BallistaConfig({}))
        jmod._runs_kernel.cache_clear()
        jmod._gather_kernel.cache_clear()
