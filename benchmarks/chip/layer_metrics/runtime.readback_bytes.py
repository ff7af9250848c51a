"""Bytes read back from the device per query completed in the window
(runtime.readback_stats)."""

NAME = "runtime.readback_bytes"
UNIT = "bytes/query"
LAYER = "device runtime"
MOVES = "queries_per_min"
SOURCE = "program_counter"


def read(run):
    w = run["window"]
    if not w["completed"]:
        return None
    return w["readback"]["bytes"] / w["completed"]
