"""Seconds of the first execution of each text (scan, encode, layout, upload),
summed, less the seconds JAX spent compiling in those intervals."""

NAME = "runtime.prepare_s"
UNIT = "s"
LAYER = "device runtime"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run):
    c = run["clocks"]
    if "first_exec_s" not in c:
        return None
    return max(0.0, c["first_exec_s"] - c["first_exec_compile_s"])
