"""Persistent-cache hits plus misses inside the window: each is a program that
was compiled or loaded while the clock ran. Should read 0."""

NAME = "runtime.window_compiles"
UNIT = "count"
LAYER = "device runtime"
MOVES = "queries_per_min"
SOURCE = "program_counter"


def read(run):
    w = run["window"]["compiles"]
    return w["cache_hits"] + w["cache_misses"]
