"""What the generators share: a random stream per (seed, table, part), and
jobs run in spawned workers that import numpy and pyarrow only."""

from __future__ import annotations

import multiprocessing
import zlib
from concurrent.futures import ProcessPoolExecutor
from typing import Callable, List, Sequence

import numpy as np


def rng(seed: int, tag: str, k: int) -> np.random.Generator:
    # crc32 and not hash(): stable across processes
    return np.random.default_rng([seed, zlib.crc32(tag.encode()), k])


def run_jobs(fn: Callable[..., int], jobs: Sequence[tuple], workers: int) -> List[int]:
    """fn(*job) for every job, in `workers` spawned processes (in this one
    where workers <= 1); the results in the jobs' order."""
    if workers <= 1 or len(jobs) <= 1:
        return [fn(*job) for job in jobs]
    with ProcessPoolExecutor(
            max_workers=min(workers, len(jobs)),
            mp_context=multiprocessing.get_context("spawn")) as pool:
        return [f.result() for f in [pool.submit(fn, *job) for job in jobs]]
