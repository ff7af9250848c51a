"""The benchmark's command: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json; everything that
belongs to it is found by name: the configuration's file, the traffic mix
`traffic/<traffic>.json`, the generator `data/<generator>.py`, the plain
reference `reference/<reference>.py`, the texts under `queries/`, one reader
per per-layer metric under `layer_metrics/`. Adding a cell, a configuration
or a metric adds files and entries and edits none (README.md).

What the window drives is `BallistaContext(...).sql(text).collect()` against
a `StandaloneCluster` with one TPU executor, all in this process, which holds
the chip: client -> gRPC scheduler -> planner -> executor -> device engines
-> shuffle -> Flight fetch -> Arrow table. Worker processes (data, reference)
import no JAX. The last line of stdout is the result object, validated before
it is printed (lastline.py); without a TPU the run fails and prints none.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as this file can see it

import argparse
import contextlib
import faulthandler
import gc
import importlib
import importlib.util
import json
import multiprocessing
import os
import shutil
import statistics
import sys
import tempfile
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, List, Optional, Tuple

CHIP = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(CHIP))
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)  # workers inherit it: data.*, reference.*

import compare  # noqa: E402
import lastline  # noqa: E402
import trace_reduce  # noqa: E402


# Every thread's stack is dumped and the run ends after this many seconds
# (the first run of a checkout, which compiles, may take 1200).
DEADLINE_S = 1150

# The one load generator there is: a later mix that asks for another one is
# refused, never run as this one under the new cell's name.
GENERATOR = {"loop": "closed", "clients": 1, "order": "round_robin"}


class Refused(Exception):
    """The run cannot start; no result is printed."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> Dict[str, object]:
    """The cell's entry, its configuration and its traffic, by name."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def reports(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if reports(m)]
    moved = {m["name"] for m in end_to_end}
    traffic = _json(os.path.join(CHIP, "traffic", cell["traffic"] + ".json"))
    asked = {k: traffic.get(k) for k in GENERATOR}
    if asked != GENERATOR:
        raise Refused(f"traffic {cell['traffic']!r} asks for {asked}; the generator "
                      f"drives {GENERATOR} only (another loop is a benchmark PR)")
    return {
        "cell": cell,
        "config": _json(os.path.join(ROOT, entry["file"])),
        "traffic": traffic,
        "end_to_end": end_to_end,
        "per_layer": [m for m in bench["per_layer"]
                      if reports(m) and m["moves"] in moved],
    }


def layer_readers() -> Dict[str, object]:
    """{metric name: module} for every reader under layer_metrics/."""
    out = {}
    d = os.path.join(CHIP, "layer_metrics")
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".py"):
            continue
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + fn[:-3].replace(".", "_").replace("-", "_"),
            os.path.join(d, fn))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[mod.NAME] = mod
    return out


class CompileLog:
    """JAX's own compile events: seconds inside backend compilation (cache
    retrievals included) and persistent-cache hits and misses. Copied from
    chip_smoke.py."""

    def __init__(self) -> None:
        import jax

        self.seconds, self.hits, self.misses = 0.0, 0, 0
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


class GcLog:
    """Seconds this process's Python garbage collector ran, from its own
    callbacks: a stall inside the window that is a collection shows here."""

    def __init__(self) -> None:
        self.pauses: List[float] = []
        self._t = 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def drain(self) -> str:
        p, self.pauses = self.pauses, []
        return (f"collections={len(p)} total={sum(p):.3f}s "
                f"longest={max(p, default=0.0):.3f}s")


def drain_counters() -> Dict[str, object]:
    """The program's counts since the last drain (chip_smoke._drain_counters)."""
    from ballista_tpu.ops import runtime
    from ballista_tpu.utils import tracing

    counters = tracing.counters()
    tracing.reset()
    return {
        "engines": runtime.routing_stats(reset=True)["engines"],
        "readback": runtime.readback_stats(reset=True),
        "join_paths": runtime.join_path_stats(reset=True),
        "prepares": runtime.ingest_stats(reset=True).get("prepares", 0),
        "counters": {k: v for k, v in counters.items()
                     if k.startswith(("device.", "spmd."))},
    }


def floor_bytes(text: dict, rows: Dict[str, int], widths: Dict[str, object]) -> int:
    """The bytes one execution of `text` must at least read: generated rows
    x the logical width of each base-table column the SQL names."""
    return sum(rows[table] * sum(int(widths[kind]) for kind in cols.values())
               for table, cols in text["reads"].items())


def reference_module(config: dict, text: dict):
    """The plain reference of `text`: the configuration's module, or the one
    the text names itself (a later cell brings new texts in a new module)."""
    return importlib.import_module(
        "reference." + text.get("reference_module", config["reference"]))


def reads_of(text: dict) -> Dict[str, List[str]]:
    """{table: columns} the text names, as the reference takes them."""
    return {table: list(cols) for table, cols in text["reads"].items()}


def sort_keys(texts: List[dict]) -> Dict[str, List[str]]:
    """{text: key columns} for the texts without ORDER BY."""
    return {t["name"]: t["sort_by"] for t in texts if "sort_by" in t}


def _sql(name: str) -> str:
    with open(os.path.join(CHIP, "queries", name)) as f:
        return f.read()


def p90(values: List[float]) -> float:
    """The 90th percentile, by the nearest-rank rule on the sorted sample."""
    s = sorted(values)
    return s[max(0, -(-9 * len(s) // 10) - 1)]


def thirds(latencies: Dict[str, List[float]]) -> Dict[str, Tuple[float, float, int]]:
    """{text: (median of the first third of its queries in the window, of the
    last third, queries)}: a query that slows as the process serves more
    shows here."""
    out = {}
    for name, ls in latencies.items():
        if ls:
            n = max(1, len(ls) // 3)
            out[name] = (statistics.median(ls[:n]), statistics.median(ls[-n:]), len(ls))
    return out


def per_layer_metrics(declared: List[dict], facts: dict) -> Dict[str, Tuple[float, str]]:
    """What each declared metric's reader finds in `facts`; a reader with
    nothing to read returns None and its metric is left out, never 0."""
    readers = layer_readers()
    out = {}
    for m in declared:
        value = readers[m["name"]].read(facts)
        if value is not None:
            out[m["name"]] = (value, m["unit"])
    return out


def end_to_end_metrics(declared: List[dict], latencies: Dict[str, List[float]],
                       window_s: float, clocks: Dict[str, float]) -> Dict[str, Tuple[float, str]]:
    """Rate over all the work and all the window's seconds, the tail of all
    queries, and the set-up time."""
    every = [x for ls in latencies.values() for x in ls]
    values = {"setup_s": clocks["setup_s"]}
    if every:
        values["queries_per_min"] = 60.0 * len(every) / window_s
        values["query_p90_s"] = p90(every)
    return {m["name"]: (values[m["name"]], m["unit"])
            for m in declared if m["name"] in values}


def execute(args: argparse.Namespace, rehearsal: Optional[dict] = None) -> int:
    """One run. `rehearsal` is given by tests/rehearse.py and the tests
    only: {"scale": float} runs the same control flow under CPU-jax at a
    tiny scale and prints its line marked as no result."""
    spec = load_cell(args.workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    if not os.path.isdir(os.path.join(ROOT, "ballista_tpu")):
        raise Refused("the program (ballista_tpu/) is not in this checkout")
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True, file=sys.__stderr__)

    # -- the device, established before anything else -----------------------
    from ballista_tpu.ops import device

    try:
        info = device.establish()
        import jax
    except Exception as e:
        raise Refused(f"no device: {type(e).__name__}: {e}")
    peaks = _json(os.path.join(CHIP, "peaks.json"))
    if rehearsal is None:
        if info.platform != "tpu":
            raise Refused(f"JAX found no accelerator (platform={info.platform})")
        if info.count < int(cell["chips"]):
            raise Refused(f"the cell asks for {cell['chips']} chip(s), JAX "
                          f"sees {info.count}")
        if info.device_kind not in peaks:
            raise Refused(f"device kind {info.device_kind!r} is not in peaks.json")
        peak = peaks[info.device_kind]
    else:
        if info.platform != "cpu":
            raise Refused("a rehearsal runs under JAX_PLATFORMS=cpu only")
        config = {**config, "scale": rehearsal["scale"]}
        # no device plane under CPU-jax: the reduction reads the harness's
        # own per-query annotations in its place, to rehearse the control flow
        peak = {"hbm_bytes_per_s": 819e9, "trace_plane_prefix": "/host:CPU",
                "trace_line": None, "event_prefix": trace_reduce.QUERY}
    print(f"platform={info.platform} device_kind={info.device_kind} "
          f"count={info.count} compile_cache_dir={device.compile_cache_dir()}",
          flush=True)
    compiles = CompileLog()
    collections = GcLog()

    texts = traffic["texts"]
    tables = sorted({t for text in texts for t in text["reads"]})
    run_dir = tempfile.mkdtemp(prefix="ballista-bench-")
    pool = cluster = ctx = None
    clocks: Dict[str, float] = {}
    try:
        # -- data from the seed, then the reference beside the set-up -------
        workers = max(1, min(12, (os.cpu_count() or 2) - 1))
        generator = importlib.import_module("data." + config["generator"])
        data_dir = os.path.join(run_dir, "data")
        t = time.perf_counter()
        rows = generator.generate(data_dir, config, tables, args.seed, workers)
        clocks["datagen_s"] = time.perf_counter() - t
        print(f"datagen: {clocks['datagen_s']:.1f}s rows={rows}", flush=True)

        pool = ProcessPoolExecutor(
            max_workers=min(3, len(texts)),
            mp_context=multiprocessing.get_context("spawn"),
            max_tasks_per_child=1)  # a reference's frames die with its process
        wanted = {
            text["name"]: pool.submit(
                reference_module(config, text).run, text["reference"], data_dir,
                reads_of(text))
            for text in texts}

        from ballista_tpu.client import BallistaContext
        from ballista_tpu.config import BallistaConfig
        from ballista_tpu.executor.runtime import StandaloneCluster

        settings = dict(config["settings"])
        for key, sub in config["fresh_dirs"].items():
            settings[key] = os.path.join(run_dir, "cache", sub)
        cluster = StandaloneCluster(n_executors=int(config["executors"]),
                                    config=BallistaConfig(settings))
        ctx = BallistaContext(*cluster.scheduler_addr, settings=settings)
        for table in tables:
            ctx.register_parquet(table, os.path.join(data_dir, table))
        sqls = {text["name"]: _sql(text["sql"]) for text in texts}

        # -- warm-up: every text, in order, `warmup_rounds` times ------------
        drain_counters()
        compiles.drain()
        clocks["first_exec_s"] = clocks["first_exec_compile_s"] = 0.0
        for rnd in range(int(traffic["warmup_rounds"])):
            for text in texts:
                t = time.perf_counter()
                ctx.sql(sqls[text["name"]]).collect()
                dt = time.perf_counter() - t
                c = compiles.drain()
                clocks["setup_compile_s"] = clocks.get("setup_compile_s", 0.0) + c["compile_s"]
                if rnd == 0:
                    clocks["first_exec_s"] += dt
                    clocks["first_exec_compile_s"] += c["compile_s"]
                print(f"warm-up {rnd} {text['name']}: {dt:.3f}s "
                      f"compile_s={c['compile_s']:.2f} cache_hits={c['cache_hits']} "
                      f"cache_misses={c['cache_misses']}", flush=True)

        # -- the reference has to be done before the window: the host is then
        # idle but for the system. Its wait is not set-up.
        t = time.perf_counter()
        wants = {name: f.result() for name, f in wanted.items()}
        clocks["reference_wait_s"] = time.perf_counter() - t
        pool.shutdown(wait=True)
        pool = None
        print(f"reference: waited {clocks['reference_wait_s']:.1f}s more", flush=True)

        # -- the window ------------------------------------------------------
        setup_counters = drain_counters()
        compiles.drain()
        trace_dir = os.path.join(run_dir, "trace")
        annotate = contextlib.nullcontext
        slice_queries = int(traffic["trace_rounds"]) * len(texts) if args.trace else 0
        slice_cm = contextlib.ExitStack()
        slice_s = 0.0
        if args.trace:
            annotate = jax.profiler.TraceAnnotation
            # annotations and the device, not every Python call: the
            # .xplane.pb stays small and the host is not slowed
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            slice_cm.enter_context(jax.profiler.TraceAnnotation(trace_reduce.SLICE))
        print(f"gc in set-up: {collections.drain()}", flush=True)
        answers: List[dict] = []
        order: List[Tuple[float, int, str]] = []  # (seconds, index, text)
        latencies: Dict[str, List[float]] = {text["name"]: [] for text in texts}
        attempted = failed = 0
        t_open = time.perf_counter()
        clocks["setup_s"] = t_open - _T0 - clocks["reference_wait_s"]
        now = t_open
        while now - t_open < args.seconds or attempted < slice_queries:
            text = texts[attempted % len(texts)]
            attempted += 1
            t = time.perf_counter()
            try:
                with annotate(trace_reduce.QUERY + text["name"]):
                    table = ctx.sql(sqls[text["name"]]).collect()
            except Exception as e:  # a failed query is counted, the loop goes on
                failed += 1
                print(f"query {text['name']} failed: {type(e).__name__}: "
                      f"{str(e)[:400]}", flush=True)
            else:
                latencies[text["name"]].append(time.perf_counter() - t)
                order.append((latencies[text["name"]][-1], attempted, text["name"]))
                answers.append({"text": text["name"], "table": table})
            now = time.perf_counter()
            if args.trace and attempted == slice_queries:
                slice_s = now - t_open
                slice_cm.close()
                jax.profiler.stop_trace()
                slice_done = len(answers)
                now = time.perf_counter()
        window_s = now - t_open

        window = drain_counters()
        window.update(seconds=window_s, completed=len(answers),
                      compiles=compiles.drain(), thirds=thirds(latencies))
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        ctx.close()
        ctx = None
        cluster.shutdown()
        cluster = None

        # -- per-text lines, then the comparison ------------------------------
        for name, ls in latencies.items():
            if ls:
                first, last, _n = window["thirds"][name]
                print(f"{name}: n={len(ls)} median={statistics.median(ls):.4f}s "
                      f"min={min(ls):.4f}s max={max(ls):.4f}s first_third="
                      f"{first:.4f}s last_third={last:.4f}s", flush=True)
        print("slowest: " + ", ".join(
            f"#{i} {name} {dt:.3f}s" for dt, i, name in sorted(order, reverse=True)[:3])
            + f"; gc in window: {collections.drain()}", flush=True)
        print(f"set-up counters: {setup_counters}", flush=True)
        print(f"window counters: {window}", flush=True)
        verdict = compare.compare_window(
            answers, wants, sort_keys(texts), failed, config["limits"])
        for name, s in verdict["per_text"].items():
            print(f"compare {name}: {s}", flush=True)

        # -- metrics -----------------------------------------------------------
        device_block = {"platform": info.platform, "kind": info.device_kind,
                        "count": info.count, "memory_peak_bytes": memory_peak}
        breakdown = None
        if args.trace:
            reduced = trace_reduce.reduce(
                trace_reduce.find_xplane(trace_dir), peak["trace_plane_prefix"],
                peak["trace_line"], slice_s, peak.get("event_prefix", ""))
            done = [a["text"] for a in answers[:slice_done]]
            by_name = {t["name"]: t for t in texts}
            reduced["completed"] = len(done)
            reduced["floor_bytes"] = sum(
                floor_bytes(by_name[n], rows, peaks["logical_width_bytes"])
                for n in done)
            device_block["window_s"] = reduced["window_s"]
            device_block["busy_s"] = reduced["busy_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            declared = spec["per_layer"]
            metrics = per_layer_metrics(declared, {
                "clocks": clocks, "window": window, "trace": reduced, "peaks": peak})
        else:
            declared = spec["end_to_end"]
            metrics = end_to_end_metrics(declared, latencies, window_s, clocks)
        expected = [(m["name"], m["unit"]) for m in declared]
        print(f"clocks: {clocks}", flush=True)

        line = lastline.build(
            correct=verdict["correct"], attempted=attempted, failed=failed,
            metrics=metrics, device=device_block, breakdown=breakdown,
            compared=verdict["compared"])
        faults = lastline.validate(line, expected, bool(args.trace))
        if faults:
            raise Refused("the result line does not meet the contract: "
                          + "; ".join(faults))
    finally:
        faulthandler.cancel_dump_traceback_later()
        if pool is not None:
            pool.shutdown(wait=True, cancel_futures=True)
        if ctx is not None:
            ctx.close()
        if cluster is not None:
            cluster.shutdown()
        if args.keep_trace and os.path.isdir(os.path.join(run_dir, "trace")):
            shutil.copytree(os.path.join(run_dir, "trace"), args.keep_trace,
                            dirs_exist_ok=True)
        shutil.rmtree(run_dir, ignore_errors=True)

    for note in verdict["notes"][:10]:
        print(f"not equal: {note}", file=sys.stderr)
    for name, c in verdict["compared"].items():
        print(f"compared {name}: value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    text = lastline.render(line)
    if rehearsal is not None:
        text = "REHEARSAL under CPU-jax, no result and no device metric: " + text
    print(text, flush=True)
    return 0


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the profiler's directory here before it is "
                         "deleted (to look at a trace by hand)")
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    try:
        return execute(parse(argv))
    except Refused as e:
        print(f"benchmarks/chip/run.py: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
