"""Per query, summed over its tasks: `executor.receive` (thread start and the
wait for a slot) and `executor.setup` (proto to plan)."""

import span_log

NAME = "executor.dispatch_ms"
UNIT = "ms/query"
LAYER = "Executor"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("executor.receive", "executor.setup"))
