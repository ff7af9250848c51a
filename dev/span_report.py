"""One run of a benchmark cell, then what its window's spans say.

    python3 dev/span_report.py <out.txt> --workload <cell> --seed <n> --seconds <s> --trace <0|1> [...]

Runs benchmarks/chip/run.py's `main` in this process with the arguments after
<out.txt>, then reads the window's spans from the recorder's last drained log
(`utils/tracing.py`) and writes, per text: the `tracing.timeline()` of the
query of median length; per span name the seconds a query spends in it
(median over the text's queries: whole and self), and the median of the
first third of the window's queries against the last third (what grows as
the process serves more); above them the window's counters, the `serde.*`
and how many rank maps were served (`device.rank_map_hit`) and built, the rows
the device aggregates handed the host (`device.groups_out`, and per text the
`groups` of its `runtime.to_arrow` spans), the device joins (`runtime.join`:
spans, seconds, `path`, `method` and `entries`, `out_rows`) and the key-set
links (`keyset_links` of the `scheduler.plan` spans, the groups the sorted
engine kept and dropped, `device.keyset_groups_kept` / `_dropped`, and per
text the `keyset` of its `runtime.to_arrow` spans). Texts are matched
to jobs by the order of the `client.collect` spans: the window sends its texts
round-robin.
"""

from __future__ import annotations

import collections
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks", "chip")]


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


def _keysets(spans: list, counters: dict) -> str:
    """The key-set links of these spans' plans and the groups they dropped."""
    plans = [s for s in spans if s.name == "scheduler.plan"]
    return (f"keyset_links {sum(s.attrs.get('keyset_links', 0) for s in plans)} on "
            f"{len(plans)} plans; device.keyset_groups_kept "
            f"{counters.get('device.keyset_groups_kept', 0)}, dropped "
            f"{counters.get('device.keyset_groups_dropped', 0)}")


def report(cell: str) -> str:
    import run
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
           f"device aggregates handed the host {counters.get('device.groups_out', 0)} rows "
           f"(device.groups_out); {_joins([s for s in log if s.name == 'runtime.join'])}; "
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
            + "; key-set selects: " + (", ".join(
                str(s.attrs["keyset"]) for s in spans
                if s.name == "runtime.to_arrow" and "keyset" in s.attrs) or "none")
            + "; keyset_links " + str(sum(s.attrs.get("keyset_links", 0) for s in spans
                                          if s.name == "scheduler.plan")))
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
    return "\n".join(out)


if __name__ == "__main__":
    import run

    rc = run.main(sys.argv[2:])
    if rc == 0:
        cell = sys.argv[sys.argv.index("--workload") + 1]
        os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
        with open(sys.argv[1], "w") as f:
            f.write(report(cell) + "\n")
    sys.exit(rc)
