"""Seconds the worker processes took to generate the cell's tables."""

NAME = "setup.datagen_s"
UNIT = "s"
LAYER = "benchmark data"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run):
    return run["clocks"].get("datagen_s")
