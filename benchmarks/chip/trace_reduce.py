"""From the profiler's `.xplane.pb` to the device's busy seconds.

`busy_s` is the union of the event intervals of ONE line of the device
plane, the XLA-ops line: modules, steps and ops lie on lines of their own
and would otherwise be counted three times. The plane and the line are named
by the peaks table's entry for the device kind. Where the plane or the line
is not found, or the union is not in (0, window], the reduction raises and
names what it did find; it never returns 0.

    python3 benchmarks/chip/trace_reduce.py <file.xplane.pb>   # look by hand
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict, List, Optional, Tuple

SLICE = "bench.slice"  # the TraceAnnotation the harness puts around the slice
QUERY = "bench.query:"  # ... and around each collect(), followed by the text


class TraceError(RuntimeError):
    pass


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def describe(data) -> List[str]:
    """One line per plane and line: what a failed reduction names."""
    out = []
    for plane in data.planes:
        for line in plane.lines:
            n = sum(1 for _ in line.events)
            out.append(f"plane {plane.name!r} line {line.name!r}: {n} events")
    return out


def _short(name: str) -> str:
    """An XLA op's event is named by its whole HLO line: keep the result's
    name, `%select_reduce_fusion.17 = (s32[]...) fusion(...)` -> the part
    before ` = `."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _union(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total covered length and the merged intervals, sorted."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def _host_spans(data, prefix: str) -> List[Tuple[str, float, float]]:
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def reduce(path: str, plane_prefix: str, line_name: Optional[str],
           window_s: float, event_prefix: str = "") -> Dict[str, object]:
    """{"busy_s", "window_s", "devices", "device_ops": [[name, s] x10],
    "idle_gaps": [[what the host was doing, s] x10]}.

    Events are clipped to the harness's `bench.slice` annotation where the
    trace holds one, and `window_s` is then that annotation's length; else
    the whole trace counts against the `window_s` the harness clocked.
    `busy_s` is averaged over the device planes found. `event_prefix` keeps
    only events whose name starts with it (the CPU rehearsal reads the
    harness's own annotations in place of a device plane)."""
    data = _load(path)
    slices = _host_spans(data, SLICE)
    lo, hi = (slices[0][1], slices[0][2]) if slices else (None, None)
    if slices:
        window_s = (hi - lo) / 1e9
    busy: List[float] = []
    merged_first: List[Tuple[float, float]] = []
    ops: Dict[str, float] = {}
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line_name is not None and line.name != line_name:
                continue
            spans = []
            for ev in line.events:
                if event_prefix and not ev.name.startswith(event_prefix):
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if lo is not None:
                    s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                spans.append((s, e))
                op = _short(ev.name)
                ops[op] = ops.get(op, 0.0) + (e - s) / 1e9
            if not spans:
                continue
            total, merged = _union(spans)
            busy.append(total / 1e9)
            if not merged_first:
                merged_first = merged
    if not busy:
        raise TraceError(
            f"no events on line {line_name!r} of a plane starting with "
            f"{plane_prefix!r}; the trace holds: " + "; ".join(describe(data)))
    busy_s = sum(busy) / len(busy)
    if not 0 < busy_s <= window_s:
        raise TraceError(
            f"busy_s={busy_s} is not in (0, window_s={window_s}]; the trace "
            f"holds: " + "; ".join(describe(data)))
    return {
        "busy_s": busy_s, "window_s": window_s, "devices": len(busy),
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": _idle_gaps(merged_first, _host_spans(data, QUERY), lo, hi),
    }


def _idle_gaps(merged: List[Tuple[float, float]],
               queries: List[Tuple[str, float, float]],
               lo: Optional[float], hi: Optional[float]) -> List[List[object]]:
    """The ten longest gaps between device operations, each labelled by the
    query text in flight at its middle (or 'between queries')."""
    if not merged:
        return []
    edges = [lo if lo is not None else merged[0][0]]
    for s, e in merged:
        edges += [s, e]
    edges.append(hi if hi is not None else merged[-1][1])
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) / 2
        label = next((name[len(QUERY):] for name, qs, qe in queries
                      if qs <= mid <= qe), "between queries")
        out.append([f"idle during {label}", (e - s) / 1e9])
    return out


if __name__ == "__main__":
    for row in describe(_load(sys.argv[1])):
        print(row)
