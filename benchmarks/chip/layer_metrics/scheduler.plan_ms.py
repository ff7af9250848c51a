"""The scheduler's work before a task can run, per query: `scheduler.plan`
(physical plan, stages, the one KV batch) and the self time of
`scheduler.execute_query` (proto to plan, root checks, the queued-state
writes; with synchronous planning the plan is its child)."""

import span_log

NAME = "scheduler.plan_ms"
UNIT = "ms/query"
LAYER = "Scheduler"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("scheduler.plan",), own=("scheduler.execute_query",))
