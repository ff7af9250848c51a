"""Device-busy milliseconds per query completed in the traced slice."""

NAME = "programs.busy_ms"
UNIT = "ms/query"
LAYER = "programs"
MOVES = "queries_per_min"
SOURCE = "device_trace"


def read(run):
    t = run.get("trace")
    if not t or not t["completed"]:
        return None
    return 1e3 * t["busy_s"] / t["completed"]
