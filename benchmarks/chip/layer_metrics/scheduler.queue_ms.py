"""Per query, summed over its tasks (which may overlap): `scheduler.queue`
(runnable to handed out) and the self time of `scheduler.assign` and
`scheduler.status`."""

import span_log

NAME = "scheduler.queue_ms"
UNIT = "ms/query"
LAYER = "Scheduler"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("scheduler.queue",),
                            own=("scheduler.assign", "scheduler.status"))
