"""The share of the time inside `client.collect` that no span accounts for:
100 x (1 - covered / sum of `client.collect`), covered being the union,
inside each `client.collect` interval, of every leaf span of that job.
`client.wait` is left out of the leaves: it is the client blocked while the
others work, and would cover everything. The per-task sums of the other
readers may exceed a query's wall time; this is the one on the wall clock."""

import span_log

NAME = "host.untraced_share"
UNIT = "%"
LAYER = "served host path"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    w = span_log.window(run)
    if w is None or w is span_log.ABSENT:
        return None if w is None else 100.0
    covered = whole = 0.0
    for root in span_log.roots(w):
        work = [s for s in w.by_job[root.job] if s.name != "client.wait"]
        covered += w.tracing.covered_s(
            w.tracing.leaves(work), [(root.start_ns, root.end_ns)])
        whole += root.seconds
    if whole <= 0:
        return None
    return 100.0 * (1.0 - covered / whole)
