"""Bring-up smoke: the served TPC-H path, once, on the chip.

    python chip_smoke.py                       # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --dry-run --sf 0.01

Generates TPC-H from the seed (several parts, so scans are multi-partition
and plans have a real shuffle stage), starts a `StandaloneCluster` (gRPC
scheduler + one `BallistaExecutor(backend=tpu)` + Flight, all in THIS
process: a chip belongs to one process) and runs the reference's integration
set q1, q3, q5, q6, q10, q12 through `BallistaContext`, cold, warm, and once
more after dropping JAX's in-memory executables so that the persistent
compile cache has to answer. One query also runs through the in-process
`ExecutionContext`. With four or more devices the same queries run again as
mesh programs (`ballista.tpu.spmd_stages`) and through four co-resident
executors. Every answer is held to the independent pandas oracle
(benchmarks/tpch/oracles.py) on the same data, and every query has to show
that the device did the work.

What it prints are smoke observations, not benchmark numbers: one reading
each, taken while oracle workers share the host's cores. The last line of
stdout is one JSON object, `{"ok": true, "device": {...}}`. Any failed phase
makes the exit code non-zero. Without a TPU the script refuses (exit 3, no
result) unless it is asked for a CPU dry run in so many words: `--dry-run`
together with `JAX_PLATFORMS=cpu` — a dry run checks the control flow and
the answers and prints no time.

Worker processes (data generation, oracles) import numpy, pandas and pyarrow
only; this is the one process that touches JAX.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import logging
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))

QUERIES = ("q1", "q3", "q5", "q6", "q10", "q12")
# queries whose cold run sends a join through the device join kernel
# (ops/join.py) and records it in join_path_stats(). q12's join is folded
# into the fused fact-aggregate stage and records no join path, and a warm
# run reuses the membership its stage prepared when cold: both show under
# CPU-jax as well. What no run may show is a join that left the device.
COLD_DEVICE_JOINS = ("q3", "q5", "q10")
# slowest oracle first (q10 and q5 join the three big tables unfiltered)
ORACLE_ORDER = ("q10", "q5", "q12", "q1", "q3", "q6")
# engines recorded by runtime.record_routing that mean "a device program ran"
DEVICE_ENGINES = ("device", "split", "batch")
# device aggregation accumulates in f32 by design; the oracle is f64. Same
# bound tests/test_tpch.py holds the tpu backend to.
FLOAT_RTOL = 5e-4


class _DeclineLog(logging.Handler):
    """Collects the reasons the device path gives when it declines
    (kernels.host_fallback / step_aside log them at DEBUG, the mesh execs at
    INFO), so a failed check can say why and not only how often."""

    def __init__(self) -> None:
        super().__init__(logging.DEBUG)
        self.messages: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if "fallback" in msg or "step-aside" in msg or "declined" in msg:
            self.messages.append(msg)

    def drain(self) -> List[str]:
        out, self.messages = self.messages, []
        return out


class _CompileLog:
    """JAX's own compile events: seconds inside backend compilation (cache
    retrievals included) and persistent-cache hits and misses."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def drain(self) -> Dict[str, float]:
        out = {"compile_s": self.seconds, "cache_hits": self.hits,
               "cache_misses": self.misses}
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


def _drain_counters(declines: _DeclineLog) -> Dict[str, object]:
    from ballista_tpu.ops import runtime
    from ballista_tpu.utils import tracing

    counters = tracing.counters()
    tracing.reset()
    return {
        "engines": runtime.routing_stats(reset=True)["engines"],
        "readback": runtime.readback_stats(reset=True),
        "join_paths": runtime.join_path_stats(reset=True),
        "prepares": runtime.ingest_stats(reset=True).get("prepares", 0),
        "counters": {
            k: v for k, v in counters.items()
            if k.startswith(("device.", "spmd."))
        },
        "declines": declines.drain(),
    }


def _device_checks(name: str, obs: Dict[str, object], mesh: bool,
                   cold: bool = False) -> List[str]:
    """Why this run of `name` does not show that the device did the work
    (empty when it does)."""
    problems = []
    engines = obs["engines"]
    if not any(engines.get(e) for e in DEVICE_ENGINES):
        problems.append(f"no device engine in routing_stats: {engines}")
    if obs["readback"]["readbacks"] < 1:
        problems.append("no device readback")
    counters = obs["counters"]
    if mesh:
        # the mesh leg is held to the mesh counters only. Its joins may
        # leave the mesh with a reason that does not depend on the platform:
        # a build-key multiplicity past the gather tiers (q5 and q10 at
        # SF=10 join on keys of 25 values), or the cost model finding the
        # mesh join slower than the host join once both were measured.
        bad = {k: v for k, v in counters.items()
               if k.startswith("spmd.") and "host_fallback" in k}
        if bad:
            problems.append(f"mesh program fell back to the host: {bad} "
                            f"{obs['declines']}")
        return problems
    paths = obs["join_paths"]["paths"]
    if cold and name in COLD_DEVICE_JOINS and not paths.get("device"):
        problems.append(f"no join ran on the device: {obs['join_paths']}")
    if any(p != "device" for p in paths):
        problems.append(f"a join left the device: {obs['join_paths']}")
    # this smoke at SF=10 under explicit CPU-jax shows no host fallback (PR
    # 21's dry run), so one that shows here appeared only on the chip
    if counters.get("device.host_fallback"):
        problems.append(
            f"{counters['device.host_fallback']} device.host_fallback: "
            f"{[m for m in obs['declines'] if 'host fallback' in m]}")
    return problems


def _memory(devices) -> List[Dict[str, object]]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_limit": stats.get("bytes_limit"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_in_use": stats.get("bytes_in_use")})
    return out


def _brief(e: BaseException) -> str:
    """An XLA error can run to hundreds of lines (a compile-time OOM lists
    every allocation); the tool shows only the end of the output."""
    text = f"{type(e).__name__}: {e}"
    return text if len(text) <= 1200 else text[:1200] + " [...]"


def _sql(name: str) -> str:
    with open(os.path.join(HERE, "benchmarks", "tpch", "queries", f"{name}.sql")) as f:
        return f.read()


def _served_leg(label: str, *, data_dir: str, settings: Dict[str, str],
                n_executors: int, passes: tuple, mesh: bool,
                declines: _DeclineLog, compiles: _CompileLog,
                dry: bool) -> Dict[str, object]:
    """The six queries through BallistaContext -> gRPC scheduler ->
    BallistaExecutor -> shuffle -> Flight fetch. Returns {"answers": {query:
    pandas frame of the last pass}, "runs": {pass: {query: observation}},
    "failures": [...]}."""
    import jax

    from ballista_tpu.client import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.executor.runtime import StandaloneCluster
    from benchmarks.tpch.datagen import register_all

    failures: List[str] = []
    runs: Dict[str, Dict[str, object]] = {}
    answers = {}
    cluster = StandaloneCluster(
        n_executors=n_executors, config=BallistaConfig(settings)
    )
    try:
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        try:
            register_all(ctx, data_dir)
            _drain_counters(declines)
            compiles.drain()
            for pass_name in passes:
                if pass_name == "recompiled":
                    # drop every in-memory executable: the next launch of
                    # each program has to come from the persistent cache
                    jax.clear_caches()
                runs[pass_name] = {}
                for name in QUERIES:
                    t0 = time.perf_counter()
                    try:
                        table = ctx.sql(_sql(name)).collect()
                    except Exception as e:  # the other queries still run
                        failures.append(
                            f"{label}/{pass_name}/{name}: {_brief(e)}")
                        print(f"[{label}] {pass_name} {name} FAILED: "
                              f"{_brief(e)}", flush=True)
                        _drain_counters(declines)
                        compiles.drain()
                        continue
                    seconds = time.perf_counter() - t0
                    runs[pass_name][name] = _observe(
                        label, pass_name, name, table, seconds, mesh=mesh,
                        declines=declines, compiles=compiles, dry=dry,
                        failures=failures)
                    answers[name] = table.to_pandas()
        finally:
            ctx.close()
    finally:
        cluster.shutdown()
    return {"answers": answers, "runs": runs, "failures": failures}


def _observe(label: str, pass_name: str, name: str, table, seconds: float, *,
             mesh: bool, declines: _DeclineLog, compiles: _CompileLog,
             dry: bool, failures: List[str]) -> Dict[str, object]:
    """What one run of one query showed: counters drained, checked against
    the device rules, printed. A dry run keeps no time of any kind."""
    import jax

    obs = _drain_counters(declines)
    obs.update(compiles.drain())
    obs["rows"] = table.num_rows
    if dry:
        del obs["compile_s"]
    else:
        obs["seconds"] = seconds
        if len(jax.devices()) > 1:
            obs["peaks"] = [m["peak_bytes_in_use"] for m in _memory(jax.devices())]
    for p in _device_checks(name, obs, mesh, pass_name == "cold"):
        failures.append(f"{label}/{pass_name}/{name}: {p}")
    rb = obs["readback"]
    print(
        f"[{label}] {pass_name:10s} {name:4s} "
        + ("(dry run: no time)" if dry else
           f"{seconds:.3f}s compile_s={obs['compile_s']:.2f}")
        + f" rows={obs['rows']} engines={obs['engines']} "
        f"readbacks={rb['readbacks']} readback_rows={rb['rows']} "
        f"readback_bytes={rb['bytes']} join_paths={obs['join_paths']['paths']}"
        # a cost-model reason quotes the seconds it measured: not in a dry run
        f"{'' if dry else obs['join_paths']['reasons'] or ''} "
        f"prepares={obs['prepares']} "
        f"cache_hits={obs['cache_hits']} cache_misses={obs['cache_misses']} "
        f"counters={obs['counters']}"
        + (f" peak_bytes_by_device={obs['peaks']}" if "peaks" in obs else ""),
        flush=True,
    )
    return obs


def _compare(label: str, answers: Dict[str, object], oracle_frames: Dict[str, object],
             failures: List[str]) -> Dict[str, object]:
    from benchmarks.tpch.oracles import compare_frames

    out = {}
    for name, got in answers.items():
        try:
            verdict = compare_frames(got, oracle_frames[name], FLOAT_RTOL)
        except AssertionError as e:
            failures.append(f"{label}/{name}: answer differs from the oracle: {e}")
            continue
        out[name] = verdict
        print(f"[{label}] oracle {name}: exact={verdict['exact']} "
              f"max_rel_err_of_the_rest={verdict['max_rel_err']:.3g}", flush=True)
    return out


def _memory_line(memory: List[Dict[str, object]]) -> str:
    return " ".join(
        f"dev{m['id']}: peak={m['peak_bytes_in_use']} in_use={m['bytes_in_use']}"
        for m in memory)


def _mesh_leg(settings: Dict[str, str], devices, report: Dict[str, object],
              failures: List[str], legs: Dict[str, object]) -> Dict[str, object]:
    """The six queries as mesh programs over four devices
    (ballista.tpu.spmd_stages, mesh data:4). Returns the answers."""
    mesh = _served_leg(
        "mesh4",
        settings={**settings, "ballista.tpu.spmd_stages": "true",
                  "ballista.tpu.mesh": "data:4"},
        n_executors=1, passes=("cold", "warm"), mesh=True, **legs,
    )
    failures.extend(mesh["failures"])
    report["mesh4"] = mesh["runs"]
    seen: Dict[str, int] = {}
    for per_query in mesh["runs"].values():
        for o in per_query.values():
            for k, v in o["counters"].items():
                seen[k] = seen.get(k, 0) + v
    print(f"mesh4 counters: {seen}")
    if not (seen.get("spmd.mesh", 0) >= 1 and seen.get("spmd.join_mesh", 0) >= 1):
        failures.append(f"mesh4: spmd.mesh / spmd.join_mesh did not both run: {seen}")
    memory = _memory(devices)
    report["memory_after_mesh4"] = memory
    print(f"mesh4 per-device memory (inputs are placed shard by shard: no "
          f"device may hold the whole table): {_memory_line(memory)}")
    # q1 is the leg's first query and a pure mesh program: its line above
    # shows the four peaks side by side before anything else has run (q6
    # has no exchange and stays a one-device stage, on device 0)
    if any(m["peak_bytes_in_use"] == 0 for m in memory[1:4]):
        failures.append(f"mesh4: a device of the mesh received nothing: {memory}")
    return mesh["answers"]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=10.0,
                    help="TPC-H scale factor (10 = one chip's share of the "
                         "SF=100 / v5e-8 deployment)")
    ap.add_argument("--parts", type=int, default=8,
                    help="files per large table = scan partitions")
    ap.add_argument("--seed", type=int, default=20260728)
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU dry run; needs JAX_PLATFORMS=cpu as well")
    ap.add_argument("--deadline", type=int, default=1150,
                    help="dump every thread's stack and exit after this "
                         "many seconds (the driver allows 1200)")
    args = ap.parse_args(argv)

    if not (os.path.isdir(os.path.join(HERE, "ballista_tpu"))
            and os.path.isdir(os.path.join(HERE, "benchmarks"))):
        print("chip_smoke: not inside a checkout of the repository "
              "(ballista_tpu/ and benchmarks/ must sit beside this file)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    faulthandler.dump_traceback_later(args.deadline, exit=True)

    # -- the device, established before anything else ----------------------
    from ballista_tpu.ops import device

    try:
        info = device.establish()
        import jax

        devices = jax.devices()
    except Exception as e:  # no backend at all, or one nobody asked for
        print(f"chip_smoke: no device: {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    dry = info.platform != "tpu"
    if dry and not args.dry_run:
        print(f"chip_smoke: JAX found no accelerator (platform="
              f"{info.platform}); a CPU dry run must be asked for with "
              f"--dry-run", file=sys.stderr)
        return 3
    if args.dry_run and not dry:
        print("chip_smoke: --dry-run on a machine with a TPU; run it without",
              file=sys.stderr)
        return 2
    if dry:
        print("=== CPU DRY RUN: control flow and answers only; nothing "
              "below is a device metric ===")
    print(f"platform={info.platform} device_kind={info.device_kind} "
          f"count={info.count} bytes_limit={info.bytes_limit}")
    print(f"compile_cache_dir={device.compile_cache_dir()} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'not set'})")
    print(f"scale: TPC-H SF={args.sf:g}, {args.parts} parts, seed {args.seed}")

    declines = _DeclineLog()
    for logger in ("ballista.tpu", "ballista.spmd"):
        logging.getLogger(logger).setLevel(logging.DEBUG)
        logging.getLogger(logger).addHandler(declines)
    compiles = _CompileLog()

    failures: List[str] = []
    report: Dict[str, object] = {
        "device": {"platform": info.platform, "kind": info.device_kind,
                   "count": info.count},
        "dry_run": dry, "sf": args.sf, "parts": args.parts, "seed": args.seed,
    }
    run_dir = tempfile.mkdtemp(prefix="ballista-smoke-")
    pool = None
    try:
        # -- data, then the oracles beside the device run -------------------
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor

        from benchmarks.tpch import oracles
        from benchmarks.tpch.datagen import generate

        data_dir = os.path.join(run_dir, "tpch")
        t0 = time.perf_counter()
        generate(data_dir, sf=args.sf, parts=args.parts, seed=args.seed,
                 workers=min(8, os.cpu_count() or 1))
        print(f"datagen: {time.perf_counter() - t0:.1f}s host seconds "
              f"(set-up)", flush=True)
        # q10's oracle peaks near 21 GB at SF=10: one worker at a time
        # unless the host has room for three
        host_gib = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE") / 2**30
        pool = ProcessPoolExecutor(
            max_workers=3 if host_gib >= 96 else 1,
            mp_context=multiprocessing.get_context("spawn"),
            max_tasks_per_child=1,  # an oracle's frames die with its process
        )
        oracle_futures = {
            name: pool.submit(oracles.run_on_dir, name, data_dir)
            for name in ORACLE_ORDER
        }

        from ballista_tpu.native import get_lib

        print(f"shuffle partitioner: "
              f"{'C++ (built by this run)' if get_lib() is not None else 'numpy'}")

        cache_dir = os.path.join(run_dir, "cache")
        settings = {
            "ballista.executor.backend": "tpu",
            # the bench's device batch: one fused launch per scan partition
            "ballista.batch.size": "16777216",
            # every pass has to execute: a repeated query must not be
            # answered from the scheduler's result cache
            "ballista.cache.results": "false",
            # nothing prepared by an earlier run (under CPU-jax, say) may
            # be served to this one
            "ballista.tpu.layout_cache_dir": os.path.join(cache_dir, "layouts"),
            "ballista.tpu.aot_cache": os.path.join(cache_dir, "aot"),
            "ballista.tpu.cost_model_dir": os.path.join(cache_dir, "costmodel"),
        }

        legs = dict(data_dir=data_dir, declines=declines, compiles=compiles,
                    dry=dry)
        # -- four chips first: per-device peak memory is a high-water mark
        # that cannot be reset, so the mesh leg has to run while the devices
        # are still empty to show what it alone put on each
        mesh_answers = {}
        if info.count >= 4:
            mesh_answers = _mesh_leg(settings, devices, report, failures, legs)
        else:
            print(f"four-chip leg skipped: {info.count} device(s)")

        # -- the served path on one executor --------------------------------
        served = _served_leg(
            "served", settings=settings, n_executors=1,
            passes=("cold", "warm", "recompiled"), mesh=False, **legs,
        )
        failures.extend(served["failures"])
        report["served"] = served["runs"]
        totals = {
            p: {k: sum(o.get(k, 0) for o in per_query.values())
                for k in ("compile_s", "cache_hits", "cache_misses")}
            for p, per_query in served["runs"].items()
        }
        if not dry:
            print("compile seconds: "
                  + " ".join(f"{p}={t['compile_s']:.1f}" for p, t in totals.items()))
        print("persistent compile cache: "
              + " ".join(f"{p}: {t['cache_hits']} hits/{t['cache_misses']} misses"
                         for p, t in totals.items()))
        if totals.get("recompiled", {}).get("cache_hits", 0) < 1:
            failures.append("recompiled pass shows no persistent compile-cache hit")
        report["memory_after_served"] = _memory(devices)
        print(f"memory after served leg: {_memory_line(report['memory_after_served'])}")

        # -- one query through the in-process ExecutionContext --------------
        from ballista_tpu.config import BallistaConfig
        from ballista_tpu.engine import ExecutionContext
        from benchmarks.tpch.datagen import register_all

        local = ExecutionContext(BallistaConfig(settings))
        register_all(local, data_dir)
        t0 = time.perf_counter()
        local_q1 = local.sql(_sql("q1")).collect()
        report["in_process"] = {"q1": _observe(
            "in-process", "warm", "q1", local_q1, time.perf_counter() - t0,
            mesh=False, declines=declines, compiles=compiles, dry=dry,
            failures=failures)}

        # -- four co-resident executors: where do their arrays land? --------
        if info.count >= 4:
            # nothing names a device, so four executors (threads over one
            # process-global stage cache) put everything on the default
            # one. Stated as a fact of this run: devices 1..3 must still
            # show the mesh leg's peak and nothing in use.
            four = _served_leg(
                "executors4", settings=settings, n_executors=4,
                passes=("warm",), mesh=False, **legs,
            )
            failures.extend(four["failures"])
            memory = _memory(devices)
            report["executors4"] = {"runs": four["runs"], "memory": memory}
            print(f"memory after four executors served the six queries "
                  f"(arrays of every executor land on device 0): "
                  f"{_memory_line(memory)}")

        # -- the answers against the oracle ---------------------------------
        t0 = time.perf_counter()
        oracle_frames = {n: f.result() for n, f in oracle_futures.items()}
        print(f"oracles: waited {time.perf_counter() - t0:.1f}s more host "
              f"seconds after the device legs", flush=True)
        report["oracle"] = {
            "served": _compare("served", served["answers"], oracle_frames, failures),
            "in_process": _compare(
                "in-process", {"q1": local_q1.to_pandas()}, oracle_frames, failures),
        }
        if mesh_answers:
            report["oracle"]["mesh4"] = _compare(
                "mesh4", mesh_answers, oracle_frames, failures)
    except Exception as e:
        import traceback

        traceback.print_exc(limit=8)
        failures.append(_brief(e))
    finally:
        if pool is not None:
            pool.shutdown(wait=True, cancel_futures=True)
        shutil.rmtree(run_dir, ignore_errors=True)
        faulthandler.cancel_dump_traceback_later()

    report["ok"] = not failures
    report["failures"] = failures
    out_dir = os.path.join(HERE, "chiprun_out")
    try:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump(report, f, indent=1, default=str)
    except OSError as e:
        print(f"chip_smoke: report not written: {e}", file=sys.stderr)
    for f in failures:
        print(f"FAILED: {f}")
    summary = {"ok": not failures, "device": report["device"]}
    if dry:
        summary["dry_run"] = True
    if failures:
        summary["failures"] = len(failures)
    print(json.dumps(summary), flush=True)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
