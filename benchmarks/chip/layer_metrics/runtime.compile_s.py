"""Seconds inside JAX backend compilation (cache retrievals included), summed
over set-up: JAX's own backend_compile_duration events."""

NAME = "runtime.compile_s"
UNIT = "s"
LAYER = "device runtime"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    return run["clocks"].get("setup_compile_s")
