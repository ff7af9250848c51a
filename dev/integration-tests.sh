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
# in seconds instead of surfacing as a wrong number later.
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

# strict gate on shared-scan multi-query execution (ISSUE 13): batched
# dispatch bit-identical to solo on the same backend (evidence gate on/off,
# mixed compatible/incompatible groups, scheduler.batch chaos, one member's
# failure sparing its siblings, a mid-batch executor death, and the
# concurrent-distinct-queries fuzz slice), plus the straggler heap and the
# tuned h2d chunk size riding the same tier via their own suites above.
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_shared_scan.py

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
from ballista_tpu.utils import locks, tracing
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
tracing.counters("recovery", reset=True)
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
while time.time() < deadline and not tracing.counters("recovery").get("chaos_executor_death"):
    time.sleep(0.1)
cluster.restart_scheduler()
second = ctx.sql(sql).collect()
assert first.to_pydict() == second.to_pydict(), "restart changed results"
ctx.close(); cluster.shutdown()
stats = tracing.counters("recovery", reset=True)
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
