"""Idle seconds of the device by the program span the host was in.

    python3 dev/idle_by_span.py <file.xplane.pb> [device plane prefix] [line]
Busy intervals are the `XLA Ops` events of the device plane inside the
benchmark's `bench.slice`. A gap between two is cut where a program span of
`utils/tracing.py` or a `bench.query:` annotation starts or ends inside it (a
gap of a second runs through many); each piece goes to the innermost
(shortest) span open in it on any host thread, else to `(no span)`, and to
the text whose query holds it. Summed by span name and by text.
"""

from __future__ import annotations

import bisect
import collections
import sys

import numpy as np

SLICE, QUERY = "bench.slice", "bench.query:"
LAYERS = ("client.", "scheduler.", "executor.", "shuffle.", "flight.", "runtime.", "engine.")


def main(path: str, plane_prefix: str = "/device:TPU:0", line_name: str = "XLA Ops") -> int:
    from jax.profiler import ProfileData

    busy, spans, queries, window = [], [], [], None
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and not (plane.name.startswith(plane_prefix) and line.name == line_name):
                continue
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if device:
                    busy.append(iv)
                elif ev.name == SLICE:
                    window = iv
                elif ev.name.startswith(QUERY):
                    queries.append(iv)
                elif ev.name.startswith(LAYERS):
                    spans.append(iv)
    if not busy or window is None:
        sys.exit(f"no {line_name!r} events on {plane_prefix!r}, or no {SLICE!r}")
    (lo, hi, _), gaps = window, []
    end = lo
    for s, e, _name in sorted(busy):
        if end < s <= hi:
            gaps.append((end, s))
        end = max(end, e)
    gaps += [(end, hi)] if hi > end else []
    cuts = sorted({t for a, b, _n in spans + queries for t in (a, b)})
    a, b = (np.array([iv[i] for iv in spans], dtype=np.float64) for i in (0, 1))
    by_span = collections.defaultdict(float)
    by_text = collections.defaultdict(lambda: collections.defaultdict(float))
    for s, e in gaps:
        edges = [s] + cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)] + [e]
        for p, q in zip(edges, edges[1:]):
            mid = (p + q) / 2
            held = np.flatnonzero((a <= mid) & (mid <= b))
            name = spans[held[np.argmin((b - a)[held])]][2] if len(held) else "(no span)"
            text = next((n[len(QUERY):] for x, y, n in queries if x <= mid <= y), "between queries")
            by_span[name] += (q - p) / 1e9
            by_text[text][name] += (q - p) / 1e9
    idle = sum(by_span.values())
    print(f"slice {(hi - lo) / 1e9:.3f} s, idle {idle:.3f} s, {len(gaps)} gaps, "
          f"{len(queries)} queries, {len(spans)} spans")
    for name, s in sorted(by_span.items(), key=lambda kv: -kv[1]):
        print(f"{s:9.3f} s {100 * s / idle:5.1f} %  {name}")
    for text, names in sorted(by_text.items()):
        n = max(1, sum(1 for x in queries if x[2] == QUERY + text))
        top = ", ".join(f"{k} {1e3 * v / n:.1f}" for k, v in
                        sorted(names.items(), key=lambda kv: -kv[1])[:7])
        print(f"{text}: {1e3 * sum(names.values()) / n:.1f} ms idle a query: {top}")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
