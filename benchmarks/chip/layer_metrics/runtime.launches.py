"""Per query: the `runtime.launch` spans of the window, one per call of a
compiled step (a device program handed to the runtime). An engine that
covers a partition in one program reads the query's partitions; one that
launches per chunk or per group reads hundreds."""

import span_log

NAME = "runtime.launches"
UNIT = "count/query"
LAYER = "device runtime"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    w = span_log.window(run)
    if w is None or w is span_log.ABSENT:
        return None if w is None else 0.0
    return w.by_name.get("runtime.launch", (0, 0.0, 0.0))[0] / w.completed
