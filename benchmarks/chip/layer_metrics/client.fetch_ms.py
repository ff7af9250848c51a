"""`client.fetch` per query: the Flight or storage read of every result
partition and the table's assembly."""

import span_log

NAME = "client.fetch_ms"
UNIT = "ms/query"
LAYER = "Client"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("client.fetch",))
