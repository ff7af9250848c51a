"""Chunk-set delta layout cache (ISSUE 19 tentpole A): parquet-backed
batch prepares persist one entry per (path, mtime, size, chunk_index)
beneath the mtime-free chunk_key_base, so a query over files ∪ {new}
re-prepares only the new file's chunks and loads every existing tile
byte-for-byte — plus the mid-append fail-closed bugfix (a file whose
identity moves between the stat and the read must not poison the store)."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.engine import ExecutionContext
from ballista_tpu.ops import kernels
from ballista_tpu.utils import tracing


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
    tracing.counters("delta", reset=True)
    yield
    _reset_stage_caches()
    tracing.counters("delta", reset=True)


def _ctx(cache_dir):
    return ExecutionContext(
        BallistaConfig(
            {
                "ballista.executor.backend": "tpu",
                "ballista.tpu.layout_cache_dir": str(cache_dir),
                # several chunks per file so per-chunk addressing is real
                "ballista.batch.size": "4096",
            }
        )
    )


def _part(seed, n=10_000):
    """Low-cardinality shape -> the unrolled batches (chunked) path."""
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "g": pa.array([f"grp{i}" for i in rng.integers(0, 5, n)]),
            "v": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
            "w": pa.array(rng.uniform(-10, 10, n)),
        }
    )


QUERY = (
    "select g, sum(v) as sv, count(*) as c, min(v) as mn from t "
    "where w > -5 group by g order by g"
)


def _run(data_dir, cache_dir):
    ctx = _ctx(cache_dir)
    ctx.register_parquet("t", str(data_dir))
    return ctx.sql(QUERY).collect()


def test_append_reprepares_only_new_chunks(tmp_path, monkeypatch):
    data = tmp_path / "data"
    data.mkdir()
    pq.write_table(_part(0), str(data / "part-0.parquet"))
    pq.write_table(_part(1), str(data / "part-1.parquet"))
    cache = tmp_path / "layouts"

    _run(data, cache)
    cold = tracing.counters("delta", reset=True)
    assert cold.get("chunks_prepared", 0) >= 2, cold
    assert cold.get("chunks_reused", 0) == 0, cold

    # append one file; the grown set must re-prepare ONLY its chunks
    pq.write_table(_part(2), str(data / "part-2.parquet"))
    _reset_stage_caches()

    from ballista_tpu.ops.stage import FusedAggregateStage

    real = FusedAggregateStage._read_scan_file

    def _guard(self, path, ctx):
        if "part-2" not in str(path):
            raise AssertionError(f"re-read of existing file {path}")
        return real(self, path, ctx)

    monkeypatch.setattr(FusedAggregateStage, "_read_scan_file", _guard)
    try:
        grown = _run(data, cache)
    finally:
        monkeypatch.setattr(FusedAggregateStage, "_read_scan_file", real)
    warm = tracing.counters("delta", reset=True)
    assert warm.get("chunks_reused", 0) >= cold["chunks_prepared"], warm
    assert warm.get("chunks_prepared", 0) >= 1, warm
    assert warm.get("bytes_reprepared_saved", 0) > 0, warm

    # bit-identity: the advanced prepare must equal a cold full run over
    # the grown set (fresh process, empty layout store)
    _reset_stage_caches()
    cold_grown = _run(data, tmp_path / "layouts-cold")
    assert grown.equals(cold_grown)


def test_warm_set_reuses_every_chunk(tmp_path, monkeypatch):
    """Unchanged file set: the second fresh process loads everything and
    never touches the parquet data pages at prepare time."""
    data = tmp_path / "data"
    data.mkdir()
    pq.write_table(_part(3), str(data / "part-0.parquet"))
    cache = tmp_path / "layouts"
    first = _run(data, cache)
    tracing.counters("delta", reset=True)
    _reset_stage_caches()

    from ballista_tpu.ops.stage import FusedAggregateStage

    def _no_read(self, path, ctx):
        raise AssertionError("parquet decode on a warm chunk set")

    real = FusedAggregateStage._read_scan_file
    monkeypatch.setattr(FusedAggregateStage, "_read_scan_file", _no_read)
    try:
        warm = _run(data, cache)
    finally:
        monkeypatch.setattr(FusedAggregateStage, "_read_scan_file", real)
    stats = tracing.counters("delta", reset=True)
    assert stats.get("chunks_reused", 0) >= 1, stats
    assert stats.get("chunks_prepared", 0) == 0, stats
    assert warm.equals(first)


def test_midappend_write_fails_closed(tmp_path):
    """ISSUE 19 bugfix regression: a writer whose file identity moved
    between the pre-read stat and the read must DECLINE the save — the
    decoded bytes may not be the state the identity describes, and
    persisting them poisons the entry for any process that fingerprints at
    the old identity. Pre-fix, this test fails with grp sums from the
    appended data served against the original file."""
    data = tmp_path / "data"
    data.mkdir()
    path = str(data / "part-0.parquet")
    t1 = _part(7)
    pq.write_table(t1, path)
    st1 = os.stat(path)
    cache = tmp_path / "layouts"

    t2 = pa.concat_tables([t1, _part(8, n=4_096)])

    from ballista_tpu.ops.stage import FusedAggregateStage

    real = FusedAggregateStage._read_scan_file

    def _mid_append(self, p, ctx):
        # the append lands after the prepare statted the file but before
        # (equivalently: during) the read — the read sees the NEW bytes
        pq.write_table(t2, p)
        return real(self, p, ctx)

    FusedAggregateStage._read_scan_file = _mid_append
    try:
        _run(data, cache)
    finally:
        FusedAggregateStage._read_scan_file = real
    stats = tracing.counters("delta", reset=True)
    assert stats.get("save_declined_midappend", 0) >= 1, stats

    # another process raced the same window: it fingerprinted at the OLD
    # identity and the file it reads is the OLD state (simulated by
    # restoring the original bytes + mtime). It must NOT be served the
    # torn writer's tiles.
    pq.write_table(t1, path)
    os.utime(path, (st1.st_atime, st1.st_mtime))
    assert os.stat(path).st_size == st1.st_size  # deterministic writer
    _reset_stage_caches()
    got = _run(data, cache)

    host = ExecutionContext(BallistaConfig({"ballista.executor.backend": "cpu"}))
    host.register_parquet("t", str(data))
    expected = host.sql(QUERY).collect()
    assert got.column("g").equals(expected.column("g"))
    assert got.column("sv").to_pylist() == expected.column("sv").to_pylist()
    assert got.column("c").to_pylist() == expected.column("c").to_pylist()


def test_tampered_chunk_identity_misses(tmp_path):
    """Load-side belt: an entry whose stamped identity does not match the
    identity its key was computed from is refused, and the file
    re-prepares (fail closed, never serve)."""
    import json

    data = tmp_path / "data"
    data.mkdir()
    pq.write_table(_part(9), str(data / "part-0.parquet"))
    cache = tmp_path / "layouts"
    first = _run(data, cache)
    tracing.counters("delta", reset=True)

    metas = list(cache.rglob("meta.json"))
    assert metas
    for mp in metas:
        m = json.load(open(mp))
        if m.get("kind") == "chunk":
            m["ident"] = [m["ident"][0], "0.0", 0]
            json.dump(m, open(mp, "w"))
    _reset_stage_caches()
    again = _run(data, cache)
    stats = tracing.counters("delta", reset=True)
    assert stats.get("chunks_reused", 0) == 0, stats
    assert stats.get("chunks_prepared", 0) >= 1, stats
    assert again.equals(first)
