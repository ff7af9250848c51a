#!/usr/bin/env bash
# Integration test driver (ref dev/integration-tests.sh + rust/benchmarks/tpch/run.sh):
# generate TPC-H data, start a cluster, run the reference's integration query
# set (q1, q3, q5, q6, q10, q12) through a real scheduler + executors.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="$PWD:${PYTHONPATH:-}"

DATA=${DATA:-/tmp/ballista-tpu-it}
SF=${SF:-0.01}

# strict static-analysis gate FIRST: the device-path invariants (readback
# accounting, tracer hygiene, dtype narrowing, lock discipline, decline
# ladder) and the scheduler durability contract (KV write-through,
# recover() coverage, replica-coherence classification — ISSUE 18) are
# machine-checked before anything executes — a violation fails the tier
# in seconds instead of surfacing as a wrong bench number later.
# --jobs 8 (ISSUE 15 satellite, PR 14 residue): per-file analysis fans out
# over a process pool — 5.2s -> 1.6s cold on a 24-core box — with output
# and cache semantics identical to serial (pinned by
# tests/test_lockorder.py::test_jobs_parallel_matches_serial_and_caches).
python -m dev.analysis --jobs 8 ballista_tpu/

[ -d "$DATA/lineitem" ] || python -m benchmarks.tpch.runner datagen --sf "$SF" --out "$DATA" --parts 2

python - <<'PY'
import os, pathlib, sys
sys.path.insert(0, os.getcwd())
from ballista_tpu.client import BallistaContext
from ballista_tpu.executor.runtime import StandaloneCluster
from benchmarks.tpch.datagen import register_all

data = os.environ.get("DATA", "/tmp/ballista-tpu-it")
cluster = StandaloneCluster(n_executors=2)
ctx = BallistaContext(*cluster.scheduler_addr)
register_all(ctx, data)
for q in (1, 3, 5, 6, 10, 12):
    sql = pathlib.Path(f"benchmarks/tpch/queries/q{q}.sql").read_text()
    out = ctx.sql(sql).collect()
    print(f"q{q}: OK ({out.num_rows} rows)")
cluster.shutdown()
print("integration tests passed")
PY

# cross-engine comparison on the same data: hand-written pyarrow
# implementations validate the CI query set (the reference's Spark
# comparison role); host engine only — CI has no chip
python -m benchmarks.compare --data "$DATA" \
    --queries q1 q3 q5 q6 q10 q12 --iterations 1 --engines host pyarrow --strict

# strict gate on the fused Sort+Limit epilogue, the float-bits bijection,
# and the M:N join multiplicity kernel: these modules are the bit-exactness
# contract for the O(limit) readback, q2's device path, and duplicate-key
# joins staying on device — a regression here must fail the tier loudly,
# not vanish into a silent host fallback
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_floatbits.py tests/test_topk_epilogue.py \
    tests/test_join_multiplicity.py

# strict gate on failure recovery (ISSUE 5): bounded retries with attempt
# history, lineage-based shuffle recovery (fetch_failed -> map recompute),
# the poll-loop TOCTOU fix, transient-RPC backoff, and the seeded chaos
# acceptance runs. Chaos verdicts are pure functions of (seed, site,
# plan-coordinate key) — no wall-clock or RNG flake by construction — and
# the chaos runs must stay bit-identical to the fault-free runs.
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_chaos.py tests/test_fault_tolerance.py

# strict gate on scheduler crash tolerance (ISSUE 6): the durable
# assignment ledger + restart reconciliation (seeded scheduler.crash +
# restart on the same SqliteBackend store, bit-identical, no owned task
# re-executed), torn-planning-write atomicity, the fetch-time restart of
# completed jobs with lost result partitions, and the distributed fuzz
# slice with the chaos sites folded in.
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_scheduler_restart.py \
    "tests/test_fuzz_device.py::test_fuzz_distributed_two_stage_chaos"

# strict gate on multi-tenant serving (ISSUE 7): weighted fair-share
# admission with per-tenant in-flight quotas (the starvation bound), the
# plan-fingerprint result cache (zero-task cache hits, mtime invalidation,
# restart durability, lost-cached-partition resubmission), chaos-armed
# cache.put / scheduler.admit staying bit-identical to fault-free, and the
# concurrent-submission fuzz slice (N tenant clients, Zipf-repeated mix,
# cache-hit results bit-identical to cold execution).
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_multitenant.py \
    "tests/test_fuzz_device.py::test_fuzz_concurrent_submission_cache"

# strict gate on the low-latency serving tier (ISSUE 8): push dispatch
# (zero poll-dispatched tasks on a healthy stream; drop -> poll fallback ->
# re-subscribe; stale-attempt rejection), the persistent AOT program cache
# (roundtrip, corrupted/version-mismatched artifact fallback, prewarm,
# aot.load chaos), streaming collect bit-equal to buffered incl. lost-
# partition recovery, seeded scheduler.push chaos bit-identical to
# fault-free, adaptive idle-poll backoff, and result-cache eviction.
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_latency_tier.py

# strict gate on adaptive execution (ISSUE 10): the measured cost model —
# store roundtrip/corruption/fingerprint-mismatch safety, evidence-gated
# extended-tier admission with the static ladder as cold-start prior and
# hard cap, partial-offload splits bit-identical to the host oracle,
# mispredict-driven re-tiering, the general skew handler, build-side
# swapping, the chunked h2d upload, the device-join AOT disk tier, and
# the routing fuzz slice (cold / warm / off / adversarial store entries,
# results bit-identical in every configuration).
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_costmodel.py \
    "tests/test_fuzz_device.py::test_fuzz_routing"

# adaptive-execution bench smoke (ISSUE 10): the skewed join past the
# static ladder must SPLIT at the tier boundary instead of declining
# wholesale, results bit-identical across cold/warm/off, and the routing
# block's mispredict accounting must sum (mispredicts <= predictions <=
# total decisions; rate == mispredicts/predictions).
JAX_PLATFORMS=cpu BENCH_ROUTING_ONLY=1 python bench.py \
    > /tmp/_ballista_routing_smoke.json
python - /tmp/_ballista_routing_smoke.json <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))["routing"]
assert rec is not None, "routing smoke returned no record"
assert rec["bit_identical"], "routing changed results"
assert rec["splits"] >= 1, f"no partial-offload split: {rec}"
assert rec["engines"].get("split", 0) >= 1, rec
total = sum(rec["engines"].values())
assert 0 <= rec["mispredicts"] <= rec["predictions"] <= total, rec
want = rec["mispredicts"] / rec["predictions"] if rec["predictions"] else 0.0
assert abs(rec["mispredict_rate"] - want) < 1e-4, rec
assert rec["events"].get("split", 0) == rec["splits"], rec
assert rec["skew_replans"] == rec["events"].get("skew_replan", 0), rec
print("routing smoke OK:", {k: rec[k] for k in
                            ("engines", "mispredict_rate", "splits")})
PY

# strict gate on speculative execution (ISSUE 11): cost-model straggler
# detection launching duplicates through the durable speculation ledger,
# first-completion-wins in both directions (the losing sibling's report
# dropped by the stale guards, never double-counted), primary-failure
# promotion of the in-flight duplicate, scheduler crash+restart recovering
# BOTH attempts from the ledger, deadline-aware (SLO) admission, the
# scale-normalized stage.run units, the end-to-end seeded-straggler
# rescue, and the speculation fuzz slice (random 2-stage plans under
# task.slow chaos, bit-identical to fault-free).
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_speculation.py \
    "tests/test_fuzz_device.py::test_fuzz_speculation_straggler"

# speculation bench smoke (ISSUE 11): seeded task.slow chaos in the
# closed-loop latency harness (multi-process client driver) — p99 with
# speculation ON must land STRICTLY below OFF, results bit-identical to
# the fault-free baseline in both modes, counters emitted, and the
# fault-free warm passes must launch nothing.
JAX_PLATFORMS=cpu BENCH_SPECULATION_ONLY=1 BENCH_SPEC_DURATION=4 \
    BENCH_SPEC_SLOW_MS=800 python bench.py > /tmp/_ballista_spec_smoke.json
python - /tmp/_ballista_spec_smoke.json <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))["speculation"]
assert rec is not None, "speculation scenario returned no record"
assert rec["bit_identical"], "speculation changed results"
on, off = rec["on"], rec["off"]
assert on["p99_ms"] < off["p99_ms"], (
    f"speculation ON p99 {on['p99_ms']}ms not below OFF {off['p99_ms']}ms")
assert on["speculation"].get("launched", 0) > 0, on
assert on["speculation"].get("won", 0) >= 1, on
assert off["speculation"].get("launched", 0) == 0, off
# fault-free runs launch nothing: both modes' warm passes stayed silent
assert on["warm_launched"] == 0 and off["warm_launched"] == 0, rec
print("speculation smoke OK:",
      {"on_p99_ms": on["p99_ms"], "off_p99_ms": off["p99_ms"],
       "p99_speedup": rec["p99_speedup"],
       "counters": on["speculation"]})
PY

# strict gate on shared-scan multi-query execution (ISSUE 13): batched
# dispatch bit-identical to solo on the same backend (evidence gate on/off,
# mixed compatible/incompatible groups, scheduler.batch chaos, one member's
# failure sparing its siblings, a mid-batch executor death, and the
# concurrent-distinct-queries fuzz slice), plus the straggler heap and the
# tuned h2d chunk size riding the same tier via their own suites above.
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_shared_scan.py

# shared-scan bench smoke (ISSUE 13): concurrent distinct aggregate queries
# over one table on a saturated single-slot cluster — batches must form,
# at least one member upload must be SAVED by the shared scan, and every
# batched result must be bit-identical to the never-batched reference.
JAX_PLATFORMS=cpu BENCH_SHAREDSCAN_ONLY=1 BENCH_SS_DURATION=6 \
    BENCH_SS_TENANTS=1,4 python bench.py > /tmp/_ballista_ss_smoke.json
python - /tmp/_ballista_ss_smoke.json <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))["shared_scan"]
assert rec is not None, "shared-scan scenario returned no record"
assert rec["bit_identical"], "shared-scan batching changed results"
by = {r["tenants"]: r for r in rec["sweep"]}
assert 4 in by, rec
ss = by[4]["shared_scan"]
assert ss.get("batches_formed", 0) >= 1, rec
assert ss.get("batched_stages", 0) >= 2, rec
assert ss.get("uploads_saved", 0) >= 1, rec
# solo tenants must never batch
assert by.get(1, {}).get("shared_scan", {}) == {}, rec
print("shared-scan smoke OK:",
      {"qps": {t: r["qps"] for t, r in by.items()},
       "counters": ss})
PY

# strict gate on the disaggregated shuffle tier + elastic fleet (ISSUE 15):
# shared-storage piece publish (atomic tmp-then-replace, shuffle.store
# write chaos tearing nothing visible), the storage-first reader ladder
# (storage -> Flight peer -> fetch_failed/lineage), executor death after
# map/job completion as a NON-EVENT (zero retries, zero lineage recomputes,
# vs nonzero on the local tier in the same harness), graceful
# scale-in-during-a-running-job bit-identical with zero retries, the
# backlog-driven autoscaler (grow under load, drain when idle), and the
# shared-tier fuzz slice (random 2-stage plans under shuffle.store +
# executor.death chaos, bit-identical to the local-tier fault-free
# baseline).
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_elastic_shuffle.py \
    "tests/test_fuzz_device.py::test_fuzz_shared_tier_chaos"

# elastic-fleet bench smoke (ISSUE 15): a burst of concurrent jobs on the
# shared tier against an autoscaled cluster — the fleet must GROW under
# the injected (cost-model-predicted) backlog, drain back to min when
# idle, fetch shuffle pieces from storage, and complete every job
# bit-identical with zero task retries.
JAX_PLATFORMS=cpu BENCH_ELASTIC_ONLY=1 python bench.py \
    > /tmp/_ballista_elastic_smoke.json
python - /tmp/_ballista_elastic_smoke.json <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))["elastic"]
assert rec is not None, "elastic scenario returned no record"
assert rec["bit_identical"], "elastic fleet changed results"
assert rec["fleet_peak"] > rec["fleet_min"], f"fleet never grew: {rec}"
assert rec["fleet_final"] == rec["fleet_min"], f"fleet never drained: {rec}"
assert rec["backlog_ms_peak"] > 0, rec
assert rec["task_retries"] == 0, rec
fl, tier = rec["fleet"], rec["shuffle_tier"]
assert fl.get("scale_up", 0) >= 1 and fl.get("scale_down", 0) >= 1, fl
assert fl.get("drain_completed", 0) >= fl.get("scale_down", 0), fl
assert tier.get("storage_publish", 0) > 0, tier
assert tier.get("storage_fetch", 0) > 0, tier
print("elastic smoke OK:",
      {"fleet_peak": rec["fleet_peak"], "fleet_final": rec["fleet_final"],
       "backlog_ms_peak": rec["backlog_ms_peak"],
       "storage_fetch": tier.get("storage_fetch"),
       "peer_fetch": tier.get("peer_fetch", 0)})
PY

# scale-in chaos e2e under the dynamic lock witness (ISSUE 15 satellite):
# the graceful drain/retire path — autoscaler decision machinery included,
# fleet.scale chaos armed — runs with every project lock asserting the
# declared order at acquisition time. Hard asserts: the test's own
# bit-identity + zero-retry contract, ZERO order violations, and ZERO
# runtime edges the static analyzer missed.
rm -f /tmp/_ballista_witness_elastic.json.*
JAX_PLATFORMS=cpu BALLISTA_LOCK_WITNESS=1 \
    BALLISTA_LOCK_WITNESS_OUT=/tmp/_ballista_witness_elastic.json \
    python -m pytest -q -p no:cacheprovider \
    "tests/test_elastic_shuffle.py::test_scale_in_during_running_job_bit_identical_zero_retries"
# env-armed dumps are per-process (<OUT>.<pid>, ISSUE 18 satellite): pass
# every dump and the edge sets merge before the static diff
WITNESS_ARGS=()
for f in /tmp/_ballista_witness_elastic.json.*; do
    WITNESS_ARGS+=(--check-witness "$f")
done
python -m dev.analysis "${WITNESS_ARGS[@]}" ballista_tpu

# strict gate on the concurrency analyzer (ISSUE 14): lock-order graph
# construction, cycle detection, manifest round-trip + enforcement
# semantics, the atomicity (check-then-act) sub-check, the dynamic lock
# witness (edge recording, inversion assert with both stacks, plan-tree
# nesting), the witness-vs-static diff, and --jobs parallel analysis with
# cache-identical deterministic output. (The lint run at the top of this
# script is the self-run acceptance gate: zero cycles, every edge declared
# in dev/analysis/lockorder.toml, suppressions within budget.)
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_lockorder.py

# strict gate on the durability analyzer (ISSUE 18): replica-coherence
# classification coverage (every SchedulerState/server attribute durable /
# derived / ephemeral), durable-mutation KV write-through, derived-rebuild
# reachability from recover(), attempt-guard discipline, ephemeral
# budgets, manifest agreement — plus the randomized crash-recovery
# property test (kill at a seeded accepted-status point, restart, every
# analyzer-classified derived attribute rebuilds equal to the
# never-crashed control).
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_durability_analysis.py tests/test_durability_recovery.py

# witness smoke (ISSUE 14): one seeded chaos e2e — executor death mid-run
# plus a scheduler restart on the same store — under
# ballista.debug.lock_witness=1. Hard asserts: the death and the restart
# actually happened, ZERO declared-order violations were recorded at the
# moment of acquisition, and `--check-witness` reports ZERO runtime edges
# the static analyzer missed (stale declared-but-never-witnessed edges are
# reported, not fatal — one short run cannot visit every code path).
JAX_PLATFORMS=cpu python - <<'PY'
import os, sys, tempfile
sys.path.insert(0, os.getcwd())
import numpy as np, pyarrow as pa, pyarrow.parquet as pq
import ballista_tpu.scheduler.state as state_mod
from ballista_tpu.client import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.executor.runtime import StandaloneCluster
from ballista_tpu.ops.runtime import recovery_stats
from ballista_tpu.utils import locks
from ballista_tpu.utils.chaos import ChaosInjector

def find_death_seed():
    for seed in range(2000):
        inj = ChaosInjector(seed, rate=0.005, sites={"executor.death"})
        def death_poll(eid, horizon):
            for n in range(1, horizon):
                if inj.should_inject("executor.death", f"{eid}/poll{n}"):
                    return n
            return None
        d0 = death_poll("local-0", 17)
        if d0 is not None and 4 <= d0 and death_poll("local-1", 400) is None:
            return seed
    raise SystemExit("no death seed in scan range")

tmp = tempfile.mkdtemp()
rng = np.random.default_rng(7)
n = 5000
pq.write_table(pa.table({
    "g": pa.array([f"k{v}" for v in rng.integers(0, 5, n)]),
    "v": pa.array(rng.integers(0, 100, n), type=pa.int64()),
}), os.path.join(tmp, "t.parquet"))
locks.reset_witness(); locks.enable_witness()
state_mod.EXECUTOR_LEASE_SECS = 1.0
recovery_stats(reset=True)
cluster = StandaloneCluster(n_executors=2, config=BallistaConfig({
    "ballista.debug.lock_witness": "1",
    "ballista.chaos.rate": "0.005",
    "ballista.chaos.seed": str(find_death_seed()),
    "ballista.chaos.sites": "executor.death",
    "ballista.rpc.retries": "20",
}))
cluster.scheduler_impl.lost_task_check_interval = 0.3
import time
ctx = BallistaContext(*cluster.scheduler_addr,
                      settings={"ballista.cache.results": "false"})
ctx.register_parquet("t", os.path.join(tmp, "t.parquet"))
sql = "select g, sum(v) as s, count(*) as c from t group by g order by g"
first = ctx.sql(sql).collect()
deadline = time.time() + 10
while time.time() < deadline and not recovery_stats().get("chaos_executor_death"):
    time.sleep(0.1)
cluster.restart_scheduler()
second = ctx.sql(sql).collect()
assert first.to_pydict() == second.to_pydict(), "restart changed results"
ctx.close(); cluster.shutdown()
stats = recovery_stats(reset=True)
assert stats.get("chaos_executor_death", 0) >= 1, stats
assert stats.get("scheduler_restart", 0) >= 1, stats
violations = locks.witness_violations()
assert violations == [], f"lock-order violations at runtime: {violations}"
out = "/tmp/_ballista_witness.json"
rec = locks.dump(out)
assert rec["edges"], "witness saw no edges - not armed?"
print("witness smoke: %d runtime edge(s), 0 violations -> %s"
      % (len(rec["edges"]), out))
PY
# the cross-check: exit 1 on any runtime edge the static analyzer missed
python -m dev.analysis --check-witness /tmp/_ballista_witness.json ballista_tpu

# latency harness smoke (ISSUE 8): tiny QPS, 2s budget per level — the
# p50/p99 + time-to-first-batch + dispatch/compile-counter pipeline is
# exercised end-to-end on CPU images even though the absolute numbers only
# mean something on chip. The jq-less assertion: the harness must emit a
# non-null latency record with zero poll dispatches and a warm compile-hit
# rate of 1.0.
JAX_PLATFORMS=cpu BENCH_LATENCY_ONLY=1 BENCH_LAT_DURATION=2 \
    BENCH_LAT_CLIENTS=1 python bench.py > /tmp/_ballista_lat_smoke.json
python - /tmp/_ballista_lat_smoke.json <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))["latency"]
assert rec is not None, "latency harness returned no record"
assert rec["sweep"], "empty QPS sweep"
for row in rec["sweep"]:
    for f in ("qps", "p50_ms", "p95_ms", "p99_ms", "ttfb_p50_ms"):
        assert f in row, f"sweep row missing {f}"
assert rec["dispatch_poll"] == 0, f"poll-dispatched tasks: {rec}"
assert rec["dispatch_push"] > 0, f"no push dispatches: {rec}"
assert rec["compile_trace"] == 0, f"warm sweep traced: {rec}"
assert rec["compile_hit_rate"] == 1.0, rec
print("latency smoke OK:", rec["sweep"][0])
PY

# HBM-resident exchange bench smoke (ISSUE 16): the 2-stage aggregation
# must actually SKIP re-uploads on the same-executor consume path
# (registry hits, not ladder reads), stay bit-identical to the
# exchange-off oracle, and degrade to the ladder with zero task retries
# when every consume-time probe is torn by seeded exchange.evict chaos.
JAX_PLATFORMS=cpu BENCH_EXCHANGE_ONLY=1 python bench.py \
    > /tmp/_ballista_exchange_smoke.json
python - /tmp/_ballista_exchange_smoke.json <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))["exchange"]
assert rec is not None, "exchange scenario returned no record"
assert rec["bit_identical"], "exchange tier changed results"
assert rec["reupload_skipped"] >= 1, rec
assert rec["h2d_bytes_saved"] > 0, rec
assert rec["off_stats_empty"], "exchange-off run touched the registry"
assert rec["task_retries"] == 0, rec
ch = rec["chaos"]
assert ch["evicted_chaos"] >= 1, ch
assert ch["injected"] >= 1, ch
assert ch["task_retries"] == 0, "registry loss caused task retries"
print("exchange smoke OK:",
      {"reupload_skipped": rec["reupload_skipped"],
       "h2d_bytes_saved": rec["h2d_bytes_saved"],
       "d2h_bytes_saved": rec["d2h_bytes_saved"],
       "chaos_evicted": ch["evicted_chaos"],
       "digest": rec["digest"]})
PY

# incremental-execution bench smoke (ISSUE 19): appending a file to a
# cached query's chunk set must (a) reload every existing chunk's tiles
# from the persisted layout store, (b) serve the new result by FOLDING
# delta partials into the cached aggregate state — strictly faster than a
# cold full run over the grown set and bit-identical to it, (c) decline
# to a full recompute when every advanced publish is torn by seeded
# cache.advance chaos, and (d) keep serving the advanced entry as a plain
# cache hit across a scheduler restart on a durable KV.
JAX_PLATFORMS=cpu BENCH_DELTA_ONLY=1 python bench.py \
    > /tmp/_ballista_delta_smoke.json
python - /tmp/_ballista_delta_smoke.json <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))["delta"]
assert rec is not None, "delta scenario returned no record"
assert rec["bit_identical"], "incremental execution changed results"
assert rec["chunks_reused"] >= 1, rec
assert rec["advance_hits"] >= 1, rec
assert rec["advance_ms"] < rec["cold_ms"], (
    f"advancement not faster than cold: {rec}")
ch = rec["chaos"]
assert ch["advance_hits"] == 0, "torn publish still served an advance"
assert ch["advance_declined"] >= 1, ch
assert rec["restart_advanced"] and rec["restart_cache_hit"], rec
print("delta smoke OK:",
      {"advance_ms": rec["advance_ms"], "cold_ms": rec["cold_ms"],
       "chunks_reused": rec["chunks_reused"],
       "advance_hits": rec["advance_hits"], "digest": rec["digest"]})
PY

# replicated control-plane bench smoke (ISSUE 20): closed-loop admission
# from 4 client processes, homed round-robin, against one scheduler and
# then two lease-sharded replicas over the same KV. Two replicas must
# admit strictly more completed queries per second, and the union of
# result digests must be IDENTICAL across both configs — the throughput
# win never rides a correctness regression.
JAX_PLATFORMS=cpu BENCH_REPLICA_ONLY=1 BENCH_REPLICA_DURATION=4 \
    python bench.py > /tmp/_ballista_replica_smoke.json
python - /tmp/_ballista_replica_smoke.json <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))["replica"]
assert rec is not None, "replica scenario returned no record"
assert rec["digests_identical"], "replicated admission changed results"
assert rec["n_digests"] >= 1, rec
assert rec["two"]["qps"] > rec["one"]["qps"], (
    f"2-replica admission not faster than 1-replica: {rec}")
print("replica smoke OK:",
      {"one_qps": rec["one"]["qps"], "two_qps": rec["two"]["qps"],
       "speedup": rec["speedup"], "n_digests": rec["n_digests"]})
PY

# full tier-1 under the dynamic lock witness (ISSUE 16 satellite): every
# fast test — the exchange registry, scheduler GC, chaos ladders, SPMD
# admission included — runs with each project lock asserting the declared
# order at acquisition, then --check-witness fails the tier on any runtime
# edge the static analyzer missed. This is the broadest coverage the
# witness gets: the targeted smokes above arm single paths; this lane arms
# everything tier-1 reaches.
rm -f /tmp/_ballista_witness_t1.json.*
JAX_PLATFORMS=cpu BALLISTA_LOCK_WITNESS=1 \
    BALLISTA_LOCK_WITNESS_OUT=/tmp/_ballista_witness_t1.json \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly
# tier-1 forks executor/cluster worker processes: each dumped its own
# <OUT>.<pid> witness; merge them all before the cross-check so an edge
# seen by ANY process counts against the static graph
WITNESS_ARGS=()
for f in /tmp/_ballista_witness_t1.json.*; do
    WITNESS_ARGS+=(--check-witness "$f")
done
python -m dev.analysis "${WITNESS_ARGS[@]}" ballista_tpu
