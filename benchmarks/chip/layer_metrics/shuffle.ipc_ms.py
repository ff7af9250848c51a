"""Per query, summed over its tasks: the time of `shuffle.ipc`, a shuffle
write's Arrow IPC work (`distributed/stages.py`): opening the pieces, each
input batch's writes, and the close with the atomic publish. A program
without the span reads 0.0, which is no measurement."""

import span_log

NAME = "shuffle.ipc_ms"
UNIT = "ms/query"
LAYER = "Shuffle"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("shuffle.ipc",))
