"""`client.submit` per query completed in the window: the plan to its proto
and the ExecuteQuery round trip."""

import span_log

NAME = "client.submit_ms"
UNIT = "ms/query"
LAYER = "Client"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("client.submit",))
