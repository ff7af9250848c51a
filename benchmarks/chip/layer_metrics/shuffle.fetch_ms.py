"""Per query, summed over its tasks: `shuffle.fetch`, one map output read
(local file, storage, Flight or the resident registry). A piece read whole
on the reader's pool is a block; a piece streamed to its consumer (one
location, or no pool) lies from its first pull to its last and holds the
consumer's work between them (attribute `streamed`)."""

import span_log

NAME = "shuffle.fetch_ms"
UNIT = "ms/query"
LAYER = "Shuffle"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("shuffle.fetch",))
