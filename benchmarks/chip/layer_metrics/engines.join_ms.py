"""Per query, summed over its tasks: the time of `runtime.join`, a device
join's work on one partition (`ops/join.py`): encoding the two sides' keys,
the sort and search programs, their readbacks and the flattening of the
matches into row selections. A text with no device join reads 0.0, and so
does a program without the span, as a layer that did no work does."""

import span_log

NAME = "engines.join_ms"
UNIT = "ms/query"
LAYER = "device engines"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("runtime.join",))
