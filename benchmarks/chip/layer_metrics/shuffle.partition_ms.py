"""Per query, summed over its tasks: the time of `shuffle.partition`, a
shuffle write's key evaluation, hashing and split of one input batch
(`distributed/stages.py`). A pass-through write has none; a program without
the span reads 0.0, which is no measurement."""

import span_log

NAME = "shuffle.partition_ms"
UNIT = "ms/query"
LAYER = "Shuffle"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("shuffle.partition",))
