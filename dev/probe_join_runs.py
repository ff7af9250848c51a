"""Time the device join's two runs programs on the real chip (ISSUE 34).

`join_runs` (paired binary search) against `join_runs_table` (position table)
of ops/join.py, as `_counts_plane` launches them, over shapes given as
build rows x probe rows x key range: milliseconds a launch (median of seven,
host clock around block_until_ready), the first call's seconds (trace and
compile, JAX's persistent cache off) and whether the two agree where they
must. The constants of `_counts_plane`'s rule rest on these readings
(PERF.md, PR 34).

Run: python dev/probe_join_runs.py [8000x1000000x2000000 ...]
"""

import statistics
import sys
import time

import numpy as np

sys.path.insert(0, ".")

SHAPES = ["8000x1000000x2000000", "32000x16000x128000", "1000000x12500x2000000",
          "8000x1000x2000000", "8000x16000x33554432", "8000x1000000x33554432"]


def timed(fn, *args, reps=7):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    laps = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        laps.append(time.perf_counter() - t0)
    return [np.asarray(a) for a in out], first, statistics.median(laps) * 1e3


def main(shapes):
    import jax
    import jax.numpy as jnp

    from ballista_tpu.ops.join import _PAD_CODE, _runs_kernel
    from ballista_tpu.ops.runtime import bucket_rows, pad_to

    jax.config.update("jax_enable_compilation_cache", False)
    print("backend:", jax.default_backend(), jax.devices())
    rng = np.random.default_rng(34)
    for shape in shapes:
        nb, n_probe, span = (int(x) for x in shape.split("x"))
        build = np.repeat(rng.choice(span, -(-nb // 3), replace=False), 3)[:nb].astype(np.int32)
        probe = rng.integers(-1, span, n_probe).astype(np.int32)
        b = jnp.asarray(pad_to(build, bucket_rows(nb, 16), _PAD_CODE))
        p = jnp.asarray(pad_to(probe, bucket_rows(n_probe, 16), -1))
        entries = bucket_rows(span)
        search, s_first, s_ms = timed(_runs_kernel(), b, p)
        table, t_first, t_ms = timed(_runs_kernel(table=True), entries, b, p)
        hit = search[2] > 0
        same = (np.array_equal(search[0], table[0]) and np.array_equal(search[2], table[2])
                and np.array_equal(search[1][hit], table[1][hit]))
        print(f"{shape}: entries {entries}, join_runs {s_ms:.3f} ms (first call {s_first:.2f} s), "
              f"join_runs_table {t_ms:.3f} ms (first call {t_first:.2f} s), agree {same}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or SHAPES)
