"""Persisted device-layout cache (ops/layout_cache.py): a fresh process
skips the O(N log N) host prepare (parquet decode, encode, rank, sort,
materialize) and goes straight to the h2d transfer.

Scan-side analog of the reference's materialize-before-consume discipline
(rust/executor/src/flight_service.rs:104-126)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.engine import ExecutionContext
from ballista_tpu.ops import kernels


def _reset_stage_caches():
    """Simulate a fresh process: drop the in-memory stage cache and its HBM
    reservations so the next query rebuilds stages from scratch."""
    from ballista_tpu.ops.runtime import release_stage_residency, reset_residency

    for stage in kernels._stage_cache.values():
        if stage not in (None, False):
            release_stage_residency(stage)
    kernels._stage_cache.clear()
    kernels._stage_cache_pins.clear()
    kernels._stage_latest.clear()
    reset_residency()


@pytest.fixture(autouse=True)
def _fresh_caches():
    _reset_stage_caches()
    yield
    _reset_stage_caches()


def _ctx(cache_dir):
    return ExecutionContext(
        BallistaConfig(
            {
                "ballista.executor.backend": "tpu",
                "ballista.tpu.layout_cache_dir": str(cache_dir),
            }
        )
    )


def _make_table(n=60_000, g=3000, seed=0):
    """>1024 groups forces the sorted chunked-segment path (the persisted
    one); includes a string column so the dictionary snapshot is exercised."""
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "k": pa.array(rng.integers(0, g, n), type=pa.int64()),
            "v": pa.array(rng.uniform(-100, 100, n).astype(np.float64)),
            "s": pa.array(
                [f"tag{i}" for i in rng.integers(0, 7, n)], type=pa.string()
            ),
        }
    )


QUERY = (
    "select k, sum(v) as sv, count(v) as c, min(v) as mn, max(v) as mx "
    "from t where s <> 'tag3' group by k order by k"
)


def _run(path, cache_dir):
    ctx = _ctx(cache_dir)
    ctx.register_parquet("t", path)
    return ctx.sql(QUERY).collect()


def test_warm_start_hits_without_parquet_decode(tmp_path, monkeypatch):
    path = str(tmp_path / "t.parquet")
    pq.write_table(_make_table(), path)
    cache = tmp_path / "layouts"

    cold = _run(path, cache)
    entries = [p for p in cache.rglob("meta.json")]
    assert entries, "cold run persisted no layout entry"

    _reset_stage_caches()

    # a warm start must not touch the parquet data pages at stage-prepare
    # time (registration/planning may still read metadata)
    real_read = pq.read_table

    def _no_decode(*a, **kw):
        raise AssertionError("parquet decode on a warm start")

    monkeypatch.setattr(pq, "read_table", _no_decode)
    try:
        warm = _run(path, cache)
    finally:
        monkeypatch.setattr(pq, "read_table", real_read)
    assert warm.equals(cold)


def test_rewritten_file_misses(tmp_path):
    path = str(tmp_path / "t.parquet")
    pq.write_table(_make_table(seed=0), path)
    cache = tmp_path / "layouts"
    first = _run(path, cache)

    # rewrite with different data: the mtime-bearing stage key changes, so
    # the stale entry must miss and results must reflect the new file
    import os
    import time

    pq.write_table(_make_table(seed=1), path)
    os.utime(path, (time.time() + 5, time.time() + 5))
    _reset_stage_caches()
    second = _run(path, cache)
    assert not second.equals(first)

    # oracle for the new data
    host = ExecutionContext(
        BallistaConfig({"ballista.executor.backend": "cpu"})
    )
    host.register_parquet("t", path)
    expected = host.sql(QUERY).collect()
    sv = second.column("sv").to_numpy()
    ev = expected.column("sv").to_numpy()
    assert second.column("k").equals(expected.column("k"))
    np.testing.assert_allclose(sv, ev, rtol=1e-3)  # f32 device accumulation


def test_disabled_dir_persists_nothing(tmp_path):
    path = str(tmp_path / "t.parquet")
    pq.write_table(_make_table(), path)
    ctx = ExecutionContext(
        BallistaConfig(
            {
                "ballista.executor.backend": "tpu",
                "ballista.tpu.layout_cache_dir": "",
            }
        )
    )
    ctx.register_parquet("t", path)
    ctx.sql(QUERY).collect()
    assert not list(tmp_path.rglob("meta.json"))


def test_dictionary_prefix_refusal():
    """adopt_dict_snapshot must refuse when a live dictionary conflicts with
    the snapshot — persisted tiles bake the snapshot's codes."""
    from ballista_tpu.ops.layout_cache import (
        adopt_dict_snapshot,
        pack_dict_snapshot,
    )
    from ballista_tpu.ops.runtime import ScanDictionaries

    src = ScanDictionaries()
    d = src.for_column(0)
    d.encode(pa.array(["a", "b", "c"]))
    meta, arrays = pack_dict_snapshot(src)

    # live dict is a prefix -> adopts, codes extend
    ok = ScanDictionaries()
    ok.for_column(0).encode(pa.array(["a"]))
    assert adopt_dict_snapshot(ok, meta, arrays)
    assert len(ok.for_column(0)) == 3

    # live dict conflicts at position 0 -> refuses
    bad = ScanDictionaries()
    bad.for_column(0).encode(pa.array(["b"]))
    assert not adopt_dict_snapshot(bad, meta, arrays)

    # live dict longer than the snapshot -> refuses
    longer = ScanDictionaries()
    longer.for_column(0).encode(pa.array(["a", "b", "c", "d"]))
    assert not adopt_dict_snapshot(longer, meta, arrays)


def test_arrow_roundtrip_types():
    """Group key values of awkward Arrow types survive the IPC packing."""
    from ballista_tpu.ops.layout_cache import (
        pack_arrow_arrays,
        unpack_arrow_arrays,
    )
    import datetime

    arrays = [
        pa.array(["x", None, "z"]),
        pa.array([datetime.date(1994, 1, 1), datetime.date(1995, 2, 2), None]),
        pa.array([1.5, 2.5, 3.5]),
    ]
    out = unpack_arrow_arrays(pack_arrow_arrays(arrays))
    assert len(out) == 3
    for a, b in zip(arrays, out):
        assert a.equals(b)
    assert unpack_arrow_arrays(pack_arrow_arrays([])) == []


def test_factagg_warm_start(tmp_path, monkeypatch):
    """The fact-agg (aggregate-over-join) path flows through the same
    persisted prepare; its warm start must skip the fact-side parquet decode
    and reproduce the cold results (including the top-k epilogue)."""
    rng = np.random.default_rng(5)
    nf, nk = 20_000, 3000
    fact = pa.table(
        {
            "fk": pa.array(rng.integers(0, nk, nf), type=pa.int64()),
            "amount": pa.array(np.round(rng.uniform(1, 500, nf), 2)),
            "flag": pa.array(rng.integers(0, 2, nf), type=pa.int64()),
        }
    )
    dim = pa.table(
        {
            "dk": pa.array(np.arange(nk), type=pa.int64()),
            "attr": pa.array([f"grp-{i % 37}" for i in range(nk)]),
        }
    )
    pq.write_table(fact, str(tmp_path / "fact.parquet"))
    pq.write_table(dim, str(tmp_path / "dim.parquet"))
    cache = tmp_path / "layouts"
    q = (
        "select fk, sum(amount) as rev, attr from dim, fact "
        "where dk = fk and flag = 1 group by fk, attr "
        "order by rev desc limit 15"
    )

    def run():
        ctx = _ctx(cache)
        ctx.register_parquet("fact", str(tmp_path / "fact.parquet"))
        ctx.register_parquet("dim", str(tmp_path / "dim.parquet"))
        return ctx.sql(q).collect()

    cold = run()
    from ballista_tpu.ops.factagg import FactAggregateStage

    assert any(
        isinstance(s, FactAggregateStage)
        for s in kernels._stage_cache.values()
    ), "fact-agg stage not engaged; test would not cover its warm start"
    assert list(cache.rglob("meta.json")), "no persisted entry"
    _reset_stage_caches()

    # the fact-side decode must not run on the warm start; the (small) dim
    # side is re-read per process, so only fail on the fact file
    real_read = pq.read_table

    def _guard(path, *a, **kw):
        if "fact" in str(path):
            raise AssertionError("fact-side parquet decode on a warm start")
        return real_read(path, *a, **kw)

    monkeypatch.setattr(pq, "read_table", _guard)
    try:
        warm = run()
    finally:
        monkeypatch.setattr(pq, "read_table", real_read)
    assert warm.equals(cold)


def test_disk_hit_pins_into_device_cache(tmp_path):
    """A disk-loaded entry must be pinned like a freshly built one: inserted
    into the stage's _device_cache and reserved in the residency ledger —
    an unpinned hit would re-read the multi-GB entry from disk per query and
    hold device arrays the HBM bookkeeping never accounted for."""
    from ballista_tpu.ops.runtime import resident_bytes
    from ballista_tpu.ops.stage import FusedAggregateStage

    path = str(tmp_path / "t.parquet")
    pq.write_table(_make_table(), path)
    cache = tmp_path / "layouts"
    _run(path, cache)
    _reset_stage_caches()
    assert resident_bytes() == 0

    _run(path, cache)
    stages = [
        s for s in kernels._stage_cache.values()
        if isinstance(s, FusedAggregateStage)
    ]
    assert stages and 0 in stages[0]._device_cache
    assert stages[0]._device_cache[0]["kind"] == "sorted"
    assert resident_bytes() > 0


def test_batches_path_warm_start(tmp_path, monkeypatch):
    """Low-cardinality stages (the unrolled batches path — q1/q6 shapes)
    persist too: at SF=100 their full-scan decode is minutes of host time
    per fresh process."""
    rng = np.random.default_rng(4)
    n = 80_000
    table = pa.table(
        {
            "g": pa.array([f"grp{i % 5}" for i in rng.integers(0, 5, n)]),
            "v": pa.array(rng.uniform(-10, 10, n)),
            "w": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
        }
    )
    path = str(tmp_path / "t.parquet")
    pq.write_table(table, path)
    cache = tmp_path / "layouts"
    q = ("select g, sum(v) as sv, count(*) as c, sum(w) as sw from t "
         "where v > -5 group by g order by g")

    def run():
        ctx = _ctx(cache)
        ctx.register_parquet("t", path)
        return ctx.sql(q).collect()

    cold = run()
    import json as _json

    metas = [_json.load(open(p)) for p in cache.rglob("meta.json")]
    # ISSUE 19: parquet-backed batches persist per-chunk delta entries
    # (one per (path, mtime, size, chunk_index)), not one whole-set blob
    assert any(m.get("kind") == "chunk" for m in metas), metas
    _reset_stage_caches()

    real_read = pq.read_table

    def _no_decode(*a, **kw):
        raise AssertionError("parquet decode on a warm start")

    monkeypatch.setattr(pq, "read_table", _no_decode)
    try:
        warm = run()
    finally:
        monkeypatch.setattr(pq, "read_table", real_read)
    assert warm.equals(cold)
