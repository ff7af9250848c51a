"""Share of the traced slice in which no operation ran on the device."""

NAME = "device.idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "queries_per_min"
SOURCE = "device_trace"


def read(run):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
