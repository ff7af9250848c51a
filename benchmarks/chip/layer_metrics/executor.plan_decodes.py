"""Per query: the tasks for which the executor ran the plan codec, the
`executor.setup` spans of the window whose `decoded` is not false. A program
that decodes a stage's plan once for all its tasks marks the others
`decoded=False`; a span without the attribute is from a program that decodes
for every task, and counts as a decode, which it was. So the number is tasks
a query where nothing is shared and stages a query where everything is."""

import span_log

NAME = "executor.plan_decodes"
UNIT = "count/query"
LAYER = "Executor"
MOVES = "queries_per_min"
SOURCE = "program_span"


def read(run):
    w = span_log.window(run)
    if w is None or w is span_log.ABSENT:
        return None if w is None else 0.0
    decodes = sum(1 for s in w.spans
                  if s.name == "executor.setup" and s.attrs.get("decoded") is not False)
    return decodes / w.completed
