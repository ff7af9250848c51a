"""Share of the HBM roofline: the bytes the slice's queries must at least read
(generated rows x the logical width of each column a text names, peaks.json)
over the chip's HBM bandwidth, over the device-busy seconds. A floor for
joins. Nothing to read gives nothing, never 0."""

NAME = "programs.hbm_roofline"
UNIT = "%"
LAYER = "programs"
MOVES = "queries_per_min"
SOURCE = "device_trace"


def read(run):
    t = run.get("trace")
    if not t or not t["floor_bytes"]:
        return None
    least_s = t["floor_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
