"""Per query, summed over its tasks: the time of `shuffle.publish`, a map
task's pieces registered in the resident exchange (`distributed/stages.py`,
`_ExchangeCapture.publish`), its `runtime.upload` children included. A
program without the span reads 0.0, which is no measurement."""

import span_log

NAME = "shuffle.publish_ms"
UNIT = "ms/query"
LAYER = "Shuffle"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("shuffle.publish",))
