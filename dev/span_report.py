"""One run of a benchmark cell, then what its window's spans say.

    python3 dev/span_report.py <out.txt> --workload <cell> --seed <n> --seconds <s> --trace <0|1> [...]

Runs benchmarks/chip/run.py's `main` in this process with the arguments after
<out.txt>, then reads the window's spans from the recorder's last drained log
(`utils/tracing.py`) and writes, per text: the `tracing.timeline()` of the
query of median length; per span name the seconds a query spends in it
(median over the text's queries: whole and self), and the median of the
first third of the window's queries against the last third (what grows as
the process serves more); above them the window's counters, the `serde.*`
and how many rank maps were served (`device.rank_map_hit`) and built, the
batches the unrolled engine read and the entries it gathered them into
(`device.gathered_batches_in` / `_out`), the rows the
device aggregates handed the host (`device.groups_out`, and per text the
`groups` of its `runtime.to_arrow` spans), the device joins (`runtime.join`:
spans, seconds, `path`, `method` and `entries`, `out_rows`), the host joins
(`executor.host_join`: `type`, `declined`, rows) and the key-set links
(`keyset_links` of the `scheduler.plan` spans, the groups the sorted engine
kept and dropped, `device.keyset_groups_kept` / `_dropped`, and per text the
`keyset` of its `runtime.to_arrow` spans). Texts are matched to jobs by the
order of the `client.collect` spans: the window sends its texts round-robin.

With `--trace 1` it also reads the run's `.xplane.pb` (from `--keep-trace`,
a temporary directory where none is given) and puts the whole log on the
device's clock: a span opened while the profiler ran is an annotation that
carries its id (`span=<id>`), the offset between the two clocks is the
median over those of (trace start - `start_ns`), and the residuals' p50 and
p99 say how well one offset fits. Every span of the log then has its place on
the device's time line, a `record()` (`scheduler.queue`, `scheduler.assign`,
`executor.receive`, `executor.report`, a streamed `shuffle.fetch`) as well.
The device's idle time inside the harness's `bench.slice` (the gaps between
the `XLA Ops` events of `/device:TPU:0`) is cut at every span edge and query
annotation; each piece goes to the innermost (shortest) span open in it on
any host thread, else to `(no span)`, and to the text whose query holds it.
"""

from __future__ import annotations

import bisect
import collections
import os
import shutil
import statistics
import sys
import tempfile
from typing import Dict, List, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmarks", "chip")

SLICE, QUERY = "bench.slice", "bench.query:"
# the program's layers; `executor.` holds `executor.operator` and
# `executor.host_join`, `shuffle.` the write's `partition`, `ipc`, `publish`
LAYERS = ("client.", "scheduler.", "executor.", "shuffle.", "flight.", "runtime.", "engine.")
PLANE, LINE = "/device:TPU:0", "XLA Ops"

Interval = Tuple[int, int, str]


def _harness() -> None:
    for p in (ROOT, CHIP):
        if p not in sys.path:
            sys.path.insert(0, p)


def _joins(spans: list) -> str:
    """`runtime.join` spans in one line: how many, their seconds, what they emitted."""
    probes = [s for s in spans if s.attrs.get("path") != "encode"]
    paths = sorted({str(s.attrs.get("path")) for s in probes})
    # how a probe found its run: position table or search, and the entries of its key range
    how = collections.Counter((str(s.attrs.get("method")), s.attrs.get("entries", 0)) for s in probes)
    methods = ", ".join(f"{n} {m} of {e} entries" for (m, e), n in sorted(how.items()))
    return (f"runtime.join: {len(spans)} spans, {sum(s.seconds for s in spans) * 1e3:.1f} ms, "
            f"{len(probes)} probe batches ({'/'.join(paths) or 'no path'}; {methods or 'no method'}) of "
            f"{sum(s.attrs.get('build_rows', 0) for s in probes)} build and "
            f"{sum(s.attrs.get('probe_rows', 0) for s in probes)} probe rows, "
            f"out_rows {sum(s.attrs.get('out_rows', 0) for s in probes)}")


def _host_joins(spans: list) -> str:
    """`executor.host_join` spans in one line: by type and whether the device declined."""
    kinds = collections.Counter(
        (s.attrs.get("type"), "declined" if s.attrs.get("declined") else "not asked")
        for s in spans)
    return (f"executor.host_join: {len(spans)} spans, {sum(s.seconds for s in spans) * 1e3:.1f} ms ("
            + (", ".join(f"{n} {t} {d}" for (t, d), n in sorted(kinds.items())) or "none")
            + f") of {sum(s.attrs.get('build_rows', 0) for s in spans)} build and "
            f"{sum(s.attrs.get('probe_rows', 0) for s in spans)} probe rows, "
            f"out_rows {sum(s.attrs.get('out_rows', 0) for s in spans)}")


def _keysets(spans: list, counters: dict) -> str:
    """The key-set links of these spans' plans and the groups they dropped."""
    plans = [s for s in spans if s.name == "scheduler.plan"]
    return (f"keyset_links {sum(s.attrs.get('keyset_links', 0) for s in plans)} on "
            f"{len(plans)} plans; device.keyset_groups_kept "
            f"{counters.get('device.keyset_groups_kept', 0)}, dropped "
            f"{counters.get('device.keyset_groups_dropped', 0)}")


def _by_op(spans: list, tracing) -> str:
    """ms of self time of the `executor.operator` spans, by operator."""
    children: Dict[int, list] = {}
    for s in spans:
        children.setdefault(s.parent, []).append(s)
    own: Dict[str, float] = collections.defaultdict(float)
    for s in spans:
        if s.name == "executor.operator":
            inside = tracing.covered_s(children.get(s.id, ()), [(s.start_ns, s.end_ns)])
            own[s.attrs.get("op")] += s.seconds - inside
    return ", ".join(f"{op} {1e3 * v:.1f}" for op, v in
                     sorted(own.items(), key=lambda kv: -kv[1])) or "none"


# -- the device's clock -----------------------------------------------------------

def read_xplane(path: str, plane: str = PLANE, line: str = LINE) -> dict:
    """{"busy": [(s, e)] of the device line, "window": (lo, hi) of the
    `bench.slice` or None, "queries": [(s, e, name)] of the harness's
    annotations, "annotated": {span id: start} of the program's spans}."""
    from jax.profiler import ProfileData

    out = {"busy": [], "window": None, "queries": [], "annotated": {}}
    for p in ProfileData.from_file(path).planes:
        device = p.name.startswith("/device:")
        if device and not p.name.startswith(plane):
            continue
        for ln in p.lines:
            if device and ln.name != line:
                continue
            for ev in ln.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if device:
                    out["busy"].append((s, e))
                elif ev.name == SLICE:
                    out["window"] = (s, e)
                elif ev.name.startswith(QUERY):
                    out["queries"].append((s, e, ev.name))
                elif ev.name.startswith(LAYERS):
                    sid = dict(ev.stats).get("span")
                    if sid is not None:
                        out["annotated"][int(sid)] = s
    return out


def _quantile(xs: Sequence[float], q: float) -> float:
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def clock_offset(annotated: Dict[int, int], log: list) -> Tuple[float, List[float]]:
    """(offset ns, residuals ns): the median of the trace's start less the
    span's `start_ns` over the spans the trace annotated, and each one's
    distance from it."""
    diffs = [annotated[s.id] - s.start_ns for s in log if s.id in annotated]
    if not diffs:
        raise ValueError("no span of the log is annotated in the trace")
    off = statistics.median(diffs)
    return off, [d - off for d in diffs]


def attribute_idle(busy: List[Tuple[int, int]], window: Tuple[int, int],
                   queries: List[Interval], spans: List[Interval]):
    """({span name: idle s}, {text: {span name: idle s}}, gaps): the gaps
    between `busy` inside `window`, each cut at every edge of `spans` and
    `queries` inside it; a piece goes to the shortest span that holds its
    middle (else `(no span)`) and to the text of the query that holds it
    (else `between queries`). All on one clock."""
    import numpy as np

    lo, hi = window
    gaps, end = [], lo
    for s, e in sorted(busy):
        if end < s <= hi:
            gaps.append((end, s))
        end = max(end, e)
    gaps += [(end, hi)] if hi > end else []
    cuts = sorted({t for a, b, _n in spans + queries for t in (a, b)})
    a, b = (np.array([iv[i] for iv in spans], dtype=np.float64) for i in (0, 1))
    by_span: Dict[str, float] = collections.defaultdict(float)
    by_text: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for s, e in gaps:
        edges = [s] + cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)] + [e]
        for p, q in zip(edges, edges[1:]):
            mid = (p + q) / 2
            held = np.flatnonzero((a <= mid) & (mid <= b))
            name = spans[held[np.argmin((b - a)[held])]][2] if len(held) else "(no span)"
            text = next((n[len(QUERY):] for x, y, n in queries if x <= mid <= y),
                        "between queries")
            by_span[name] += (q - p) / 1e9
            by_text[text][name] += (q - p) / 1e9
    return by_span, by_text, gaps


def idle_report(path: str, log: list) -> str:
    """The clock's fit and the idle device by span, for one run's trace and log."""
    trace = read_xplane(path)
    if not trace["busy"] or trace["window"] is None:
        return f"no {LINE!r} events on {PLANE!r}, or no {SLICE!r}: no idle table"
    if not trace["annotated"]:
        return "no annotation carries a span id (a program from before them): no shared clock"
    off, residuals = clock_offset(trace["annotated"], log)
    lo, hi = trace["window"]
    spans = [(s.start_ns + off, s.end_ns + off, s.name) for s in log
             if s.name.startswith(LAYERS) and s.end_ns + off > lo and s.start_ns + off < hi]
    by_span, by_text, gaps = attribute_idle(trace["busy"], trace["window"],
                                            trace["queries"], spans)
    idle = sum(by_span.values()) or 1.0
    placed = len(log) - len(residuals)
    misfit = [abs(r) for r in residuals]
    out = [f"\n== the device's clock: {len(residuals)} annotated spans, offset "
           f"{off / 1e9:.6f} s, |residual| p50 {_quantile(misfit, 0.5) / 1e3:.2f} us "
           f"p99 {_quantile(misfit, 0.99) / 1e3:.2f} us; {placed} spans of the "
           f"log placed by the offset alone (record() spans and those outside the session)",
           f"slice {(hi - lo) / 1e9:.3f} s, idle {sum(by_span.values()):.3f} s, {len(gaps)} gaps, "
           f"{len(trace['queries'])} queries, {len(spans)} spans"]
    for name, s in sorted(by_span.items(), key=lambda kv: -kv[1]):
        out.append(f"{s:9.3f} s {100 * s / idle:5.1f} %  {name}")
    for text, names in sorted(by_text.items()):
        n = max(1, sum(1 for x in trace["queries"] if x[2] == QUERY + text))
        top = ", ".join(f"{k} {1e3 * v / n:.1f}" for k, v in
                        sorted(names.items(), key=lambda kv: -kv[1])[:9])
        out.append(f"{text}: {1e3 * sum(names.values()) / n:.1f} ms idle a query: {top}")
    return "\n".join(out)


def report(cell: str, trace_dir: str = "") -> str:
    _harness()
    import run
    import trace_reduce
    from ballista_tpu.utils import tracing

    texts = [t["name"] for t in run.load_cell(cell)["traffic"]["texts"]]
    log = tracing.drained()["spans"]
    roots = sorted((s for s in log if s.name == "client.collect"), key=lambda s: s.start_ns)
    by_job = {}
    for s in log:
        by_job.setdefault(s.job, []).append(s)
    counters = tracing.drained()["counters"]
    out = [f"{cell}: {len(log)} spans, {len(roots)} queries, counters {counters}",
           f"rank maps (fact aggregates, a partition a query): "
           f"{counters.get('device.rank_map_hit', 0)} served from the prepared partition, "
           f"{counters.get('device.rank_map_build', 0)} built",
           f"gathered batches (the batches the unrolled engine read, and the entries "
           f"it gathered them into): {counters.get('device.gathered_batches_in', 0)} in, "
           f"{counters.get('device.gathered_batches_out', 0)} out (none where the "
           f"prepared entries are resident; the set-up's: run.py's set-up counters)",
           f"device aggregates handed the host {counters.get('device.groups_out', 0)} rows "
           f"(device.groups_out); {_joins([s for s in log if s.name == 'runtime.join'])}; "
           f"{_host_joins([s for s in log if s.name == 'executor.host_join'])}; "
           f"{_keysets(log, counters)}"]
    for i, text in enumerate(texts):
        mine = roots[i::len(texts)]
        if not mine:
            continue
        median = sorted(mine, key=lambda s: s.seconds)[len(mine) // 2]
        out += [f"\n== {text}: {len(mine)} queries, median {median.seconds * 1e3:.1f} ms "
                f"(job {median.job})", tracing.timeline(median.job, by_job[median.job])]
        spans = by_job[median.job]
        out.append("groups by engine: " + (", ".join(
            f"{s.attrs.get('engine')} {s.attrs['groups']}" for s in spans
            if s.name == "runtime.to_arrow" and "groups" in s.attrs) or "none")
            + "; " + _joins([s for s in spans if s.name == "runtime.join"])
            + "; " + _host_joins([s for s in spans if s.name == "executor.host_join"])
            + "; key-set selects: " + (", ".join(
                str(s.attrs["keyset"]) for s in spans
                if s.name == "runtime.to_arrow" and "keyset" in s.attrs) or "none")
            + "; keyset_links " + str(sum(s.attrs.get("keyset_links", 0) for s in spans
                                          if s.name == "scheduler.plan")))
        out.append("operators' self ms: " + _by_op(spans, tracing))
        per_query = [tracing.by_name(by_job[r.job]) for r in mine]
        third = max(1, len(mine) // 3)
        out.append(f"{'span':26s} {'n':>5s} {'total ms':>9s} {'self ms':>9s} "
                   f"{'first third':>11s} {'last third':>10s}")
        for name in sorted({n for q in per_query for n in q}):
            rows = [q.get(name, (0, 0.0, 0.0)) for q in per_query]
            first = statistics.median(r[1] for r in rows[:third]) * 1e3
            last = statistics.median(r[1] for r in rows[-third:]) * 1e3
            out.append(f"{name:26s} {statistics.median(r[0] for r in rows):5.0f} "
                       f"{statistics.median(r[1] for r in rows) * 1e3:9.2f} "
                       f"{statistics.median(r[2] for r in rows) * 1e3:9.2f} "
                       f"{first:11.2f} {last:10.2f}")
    if trace_dir:
        out.append(idle_report(trace_reduce.find_xplane(trace_dir), log))
    return "\n".join(out)


def main(argv: List[str]) -> int:
    _harness()
    import run

    out_path, args = argv[0], list(argv[1:])
    traced = run.parse(args).trace == 1
    scratch = ""
    if traced and "--keep-trace" not in args:
        scratch = tempfile.mkdtemp(prefix="span-report-trace-")
        args += ["--keep-trace", scratch]
    try:
        rc = run.main(args)
        if rc == 0:
            cell = args[args.index("--workload") + 1]
            trace_dir = args[args.index("--keep-trace") + 1] if traced else ""
            os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
            with open(out_path, "w") as f:
                f.write(report(cell, trace_dir) + "\n")
        return rc
    finally:
        if scratch:
            shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
