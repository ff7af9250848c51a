"""Result-cache advancement (ISSUE 19, tentpole B): appending a file to a
cached query's chunk set serves the new result by folding delta partials
into the cached aggregate state instead of recomputing from scratch.

Covers the full acceptance surface:

- end-to-end advancement on append: advance_hits >= 1 and the advanced
  result is BIT-IDENTICAL to a cold full run over the grown set;
- the advanced entry is self-contained (state inline in the KV value):
  a third submission is a plain cache hit with zero executor tasks, and
  the entry keeps serving across a scheduler restart on a durable store;
- cache.advance chaos (torn publish): the advancement declines and falls
  back to a FULL recompute — never a silent wrong answer;
- ineligible shapes (float sums are order-sensitive) decline loudly via
  the advance_declined counter and still return correct results.
"""

import os
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ballista_tpu.client import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.executor.runtime import StandaloneCluster
from ballista_tpu.scheduler.kv import SqliteBackend
from ballista_tpu.utils import tracing

# the canonical advancement-eligible shape: filter below the aggregate,
# order-insensitive members only (int sum / count / min), sort on the
# full group key so merged output lands in a deterministic row order
QUERY = (
    "select g, sum(v) as sv, count(*) as c, min(v) as mn "
    "from t where w > -5 group by g order by g"
)


def _write_part(d: str, i: int, n: int = 200) -> str:
    rng = np.random.default_rng(100 + i)
    path = os.path.join(d, f"part-{i}.parquet")
    pq.write_table(
        pa.table(
            {
                "g": pa.array(rng.integers(0, 7, n), type=pa.int64()),
                "v": pa.array(rng.integers(-50, 50, n), type=pa.int64()),
                "w": pa.array(rng.integers(-10, 10, n), type=pa.int64()),
                "f": pa.array(rng.random(n), type=pa.float64()),
            }
        ),
        path,
    )
    return path


@pytest.fixture()
def tdir():
    with tempfile.TemporaryDirectory() as d:
        _write_part(d, 0)
        _write_part(d, 1)
        yield d


def _cold_truth(cluster, d: str, query: str = QUERY) -> pa.Table:
    """Ground truth: a full run over the current file set with the result
    cache disabled, so nothing cached can leak into the reference."""
    ctx = BallistaContext(
        *cluster.scheduler_addr,
        settings={"ballista.cache.results": "false"},
    )
    try:
        ctx.register_parquet("t", d)
        return ctx.sql(query).collect()
    finally:
        ctx.close()


def _cached_jobs(state):
    out = []
    for k, _v in state.kv.get_prefix(state._key("jobs")):
        job = k.rsplit("/", 1)[1]
        js = state.get_job_metadata(job)
        if js.WhichOneof("status") == "completed" and js.completed.cached:
            out.append(job)
    return out


def test_advance_on_append_bit_identical(tdir):
    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.advance": "true"},
        )
        ctx.register_parquet("t", tdir)
        tracing.counters("delta", reset=True)
        cold = ctx.sql(QUERY).collect()
        # grow the chunk set and re-register so the client re-discovers it
        _write_part(tdir, 2)
        ctx.register_parquet("t", tdir)
        advanced = ctx.sql(QUERY).collect()
        stats = tracing.counters("delta", reset=True)
        assert stats.get("advance_hits") == 1, stats
        # the acceptance bar: advanced result == cold full run, byte for byte
        truth = _cold_truth(cluster, tdir)
        assert advanced.equals(truth)
        assert not advanced.equals(cold)  # the append actually changed rows
        # the advanced entry is a first-class cache line: a third submission
        # is a plain hit served inline, with ZERO executor tasks
        tracing.counters("tenancy", reset=True)
        third = ctx.sql(QUERY).collect()
        assert third.equals(truth)
        assert tracing.counters("tenancy", reset=True).get("cache_hit") == 1
        st = cluster.scheduler_impl.state
        hits = _cached_jobs(st)
        assert hits and all(st.get_job_tasks(j) == [] for j in hits)
        ctx.close()
    finally:
        cluster.shutdown()


def test_advanced_entry_survives_scheduler_restart(tdir):
    """Advanced entries carry their state INLINE in the KV value, so they
    need no live executor and no scheduler memory: a restarted scheduler
    on the same durable store keeps serving the advanced result."""
    kv = SqliteBackend.temporary()
    cluster = StandaloneCluster(n_executors=1, kv=kv)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.advance": "true"},
        )
        ctx.register_parquet("t", tdir)
        tracing.counters("delta", reset=True)
        ctx.sql(QUERY).collect()
        _write_part(tdir, 2)
        ctx.register_parquet("t", tdir)
        advanced = ctx.sql(QUERY).collect()
        assert tracing.counters("delta", reset=True).get("advance_hits") == 1
        cluster.restart_scheduler()
        tracing.counters("tenancy", reset=True)
        again = ctx.sql(QUERY).collect()
        assert again.equals(advanced)
        assert tracing.counters("tenancy", reset=True).get("cache_hit") == 1
        ctx.close()
    finally:
        cluster.shutdown()


def test_advance_chaos_torn_publish_falls_back(tdir):
    """cache.advance chaos fires BEFORE any KV write of the advanced
    entry: the advancement declines, the query falls back to a full
    recompute, and the answer is still bit-identical — a torn publish is
    a performance event, never a correctness event."""
    cfg = BallistaConfig(
        {
            "ballista.chaos.seed": "19",
            "ballista.chaos.rate": "1.0",
            "ballista.chaos.sites": "cache.advance",
        }
    )
    cluster = StandaloneCluster(n_executors=2, config=cfg)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.advance": "true"},
        )
        ctx.register_parquet("t", tdir)
        tracing.counters("delta", reset=True)
        ctx.sql(QUERY).collect()
        _write_part(tdir, 2)
        ctx.register_parquet("t", tdir)
        result = ctx.sql(QUERY).collect()
        stats = tracing.counters("delta", reset=True)
        assert stats.get("advance_hits", 0) == 0, stats
        assert stats.get("advance_declined", 0) >= 1, stats
        assert result.equals(_cold_truth(cluster, tdir))
        ctx.close()
    finally:
        cluster.shutdown()


def test_float_sum_declines_to_full_recompute(tdir):
    """Float sums are order-sensitive (fp addition does not associate), so
    advancement cannot guarantee bit-identity: the fold spec declines,
    the decline is COUNTED (never silent), and the full recompute serves
    the correct rows."""
    q = "select g, sum(f) as sf, count(*) as c from t group by g order by g"
    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.advance": "true"},
        )
        ctx.register_parquet("t", tdir)
        tracing.counters("delta", reset=True)
        ctx.sql(q).collect()
        _write_part(tdir, 2)
        ctx.register_parquet("t", tdir)
        result = ctx.sql(q).collect()
        stats = tracing.counters("delta", reset=True)
        assert stats.get("advance_hits", 0) == 0, stats
        assert stats.get("advance_declined", 0) >= 1, stats
        assert result.equals(_cold_truth(cluster, tdir, q))
        ctx.close()
    finally:
        cluster.shutdown()


def test_shrunk_or_rewritten_set_never_advances(tdir):
    """Advancement requires a STRICT superset with untouched base files:
    rewriting an existing file (same path, new mtime) must miss the probe
    entirely — changed history is a full recompute, not a fold."""
    cluster = StandaloneCluster(n_executors=2)
    try:
        ctx = BallistaContext(
            *cluster.scheduler_addr,
            settings={"ballista.cache.advance": "true"},
        )
        ctx.register_parquet("t", tdir)
        tracing.counters("delta", reset=True)
        ctx.sql(QUERY).collect()
        # rewrite part-0 with different rows AND add part-2: the base fact
        # set no longer holds, so the probe must find nothing
        rng = np.random.default_rng(999)
        pq.write_table(
            pa.table(
                {
                    "g": pa.array(rng.integers(0, 7, 150), type=pa.int64()),
                    "v": pa.array(rng.integers(-50, 50, 150), type=pa.int64()),
                    "w": pa.array(rng.integers(-10, 10, 150), type=pa.int64()),
                    "f": pa.array(rng.random(150), type=pa.float64()),
                }
            ),
            os.path.join(tdir, "part-0.parquet"),
        )
        _write_part(tdir, 2)
        ctx.register_parquet("t", tdir)
        result = ctx.sql(QUERY).collect()
        assert tracing.counters("delta", reset=True).get("advance_hits", 0) == 0
        assert result.equals(_cold_truth(cluster, tdir))
        ctx.close()
    finally:
        cluster.shutdown()
