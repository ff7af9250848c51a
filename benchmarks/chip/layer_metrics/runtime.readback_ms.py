"""Per query, summed over its tasks: the self time of `runtime.readback`
(the d2h copy; the wait for the device that produces the array is its child
`runtime.device_wait` and is left out, so a faster program does not move
this) and `runtime.to_arrow` (decode and Arrow assembly of a device stage's
result)."""

import span_log

NAME = "runtime.readback_ms"
UNIT = "ms/query"
LAYER = "device runtime"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("runtime.to_arrow",), own=("runtime.readback",))
