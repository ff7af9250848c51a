"""From the scheduler's notification of the job's terminal status to the
client holding it: per job, the end of `client.wait` less the moment the
`scheduler.status` span marked `job_done` notified (its end where it has no
such mark); the poll back-off or the push stream's delay, alone. Both ends
are in one log because the cell's cluster is one process. A job that lacks
either end is left out of the mean."""

import span_log

NAME = "client.notify_ms"
UNIT = "ms/query"
LAYER = "Client"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    w = span_log.window(run)
    if w is None or w is span_log.ABSENT:
        return None if w is None else 0.0
    delays = []
    for spans in w.by_job.values():
        waits = [s.end_ns for s in spans if s.name == "client.wait"]
        done = [s.attrs.get("notified_ns", s.end_ns) for s in spans
                if s.name == "scheduler.status" and s.attrs.get("job_done")]
        if waits and done:
            delays.append(max(0, max(waits) - max(done)))
    if not delays:
        return None
    return sum(delays) / len(delays) / 1e6
