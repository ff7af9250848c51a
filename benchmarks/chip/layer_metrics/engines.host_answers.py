"""Work the device engines handed to the host inside the window:
device.host_fallback plus every join path other than device. Should read 0."""

NAME = "engines.host_answers"
UNIT = "count"
LAYER = "device engines"
MOVES = "queries_per_min"
SOURCE = "program_counter"


def read(run):
    w = run["window"]
    paths = w["join_paths"]["paths"]
    return (w["counters"].get("device.host_fallback", 0)
            + sum(n for p, n in paths.items() if p != "device"))
