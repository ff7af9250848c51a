"""CPU rehearsal of one cell: the command's whole control flow under
CPU-jax at a tiny scale, both --trace values, the same validator.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tests/rehearse.py \
        --workload tpch_sf10_1chip.scan_agg --seed 7 --seconds 3 --trace 1 --scale 0.01

It prints no result: the last line is marked as a rehearsal, and nothing in
it is a device metric.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def main() -> int:
    argv = sys.argv[1:]
    scale = 0.01
    if "--scale" in argv:
        i = argv.index("--scale")
        scale = float(argv[i + 1])
        del argv[i:i + 2]
    try:
        return run.execute(run.parse(argv), rehearsal={"scale": scale})
    except run.Refused as e:
        print(f"rehearse: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
