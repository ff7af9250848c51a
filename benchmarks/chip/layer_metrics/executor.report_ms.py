"""Per query, summed over its tasks: `executor.report`, a finished status's
wait on the executor's queue until a poll takes it to the scheduler."""

import span_log

NAME = "executor.report_ms"
UNIT = "ms/query"
LAYER = "Executor"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("executor.report",))
