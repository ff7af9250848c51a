"""Per query, summed over its tasks: the self time of `shuffle.write`, which
is the partitioning and the IPC write plus whatever of the plan's execution
beneath it opens no span of its own."""

import span_log

NAME = "shuffle.write_ms"
UNIT = "ms/query"
LAYER = "Shuffle"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, own=("shuffle.write",))
