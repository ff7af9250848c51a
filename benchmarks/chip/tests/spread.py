"""Spreads of a cell's two sets of runs, as the builder's contract reads
them: per set and metric, the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median; the bound is
about five times the widest.

    python3 benchmarks/chip/tests/spread.py chiprun_out/sets/<cell>

expects files `set<k>.<seed>.out` whose last line is a run's result.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(d: str) -> int:
    sets = {}
    for path in sorted(glob.glob(os.path.join(d, "set*.out"))):
        k = os.path.basename(path).split(".")[0]
        with open(path) as f:
            lines = f.read().strip().splitlines()
        try:
            line = json.loads(lines[-1])
        except (IndexError, ValueError):
            print(f"{path}: no result line")
            continue
        if not line["correct"]:
            print(f"{path}: correct is false: {line['compared']}")
        for name, m in line["metrics"].items():
            sets.setdefault(name, {}).setdefault(k, []).append(m["value"])
        sets.setdefault("rel_err_max", {}).setdefault(k, []).append(
            line["compared"]["rel_err_max"]["value"])
    for name, by_set in sets.items():
        widest = 0.0
        for k, values in sorted(by_set.items()):
            if name == "setup_s":
                values = values[1:]  # the first run of a checkout compiles
            s = spread(values) if len(values) >= 2 else float("nan")
            widest = max(widest, s)
            print(f"{name} {k}: n={len(values)} median={statistics.median(values):.6g} "
                  f"min={min(values):.6g} max={max(values):.6g} spread={100 * s:.3f}%")
        print(f"{name}: widest spread {100 * widest:.3f}% -> bound about {5 * widest:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
