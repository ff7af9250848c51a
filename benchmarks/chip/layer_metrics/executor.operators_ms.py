"""Per query, summed over its tasks: the self time of `executor.operator`,
one span a pull of an operator's batch iterator (`physical/plan.py`): the
host operators' own work, less the pulls of their children and the engines'
spans beneath them (`runtime.stage`, `runtime.join`, `shuffle.fetch`,
`executor.host_join`). A program without the span reads 0.0, which is no
measurement."""

import span_log

NAME = "executor.operators_ms"
UNIT = "ms/query"
LAYER = "Executor"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, own=("executor.operator",))
