"""Per query: the rows of the tables the device aggregates handed the host,
`device.groups_out`, one count a stage result (the groups of a grouped
aggregate, the survivors of a top-k or a member select). A cell whose
aggregates end in a handful of groups reads a few; one whose whole grouped
result feeds a join reads millions. A program without the counter reads 0,
as `engines.host_answers` does."""

NAME = "engines.groups_out"
UNIT = "count/query"
LAYER = "device engines"
MOVES = "queries_per_min"
SOURCE = "program_counter"


def read(run):
    w = run["window"]
    if not w["completed"]:
        return None
    return w["counters"].get("device.groups_out", 0) / w["completed"]
