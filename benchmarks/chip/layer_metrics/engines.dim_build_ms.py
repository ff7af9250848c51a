"""Per query, summed over its tasks: the time of `runtime.dim_build`, the
host's building of a device stage's dimension side before its program runs:
collecting and sorting the dimension subtrees and gathering their columns per
fact row (`engine=mapped`), the dimension side and the rank maps of a
fact-side aggregate (`engine=factagg`). A window whose stages keep their maps
reads little; a program without the span reads 0.0, as a layer that did no
work does."""

import span_log

NAME = "engines.dim_build_ms"
UNIT = "ms/query"
LAYER = "device engines"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    return span_log.span_ms(run, total=("runtime.dim_build",))
