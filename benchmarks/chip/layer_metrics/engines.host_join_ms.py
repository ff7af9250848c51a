"""Per query, summed over its tasks: the time of `executor.host_join`, a
join the host answers (`physical/join.py`): its key codes, `join_indices`
and the takes; LEFT, RIGHT and FULL always, INNER, SEMI and ANTI where the
device declined or was not asked. A text whose joins the device answers
reads 0.0, and so does a program without the span, which is no
measurement."""

import span_log

NAME = "engines.host_join_ms"
UNIT = "ms/query"
LAYER = "device engines"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("executor.host_join",))
