"""The control of `correct`: the plain reference, put in the program's place
and computed in bfloat16, the nearest precision below the float32 the device
accumulates in (inputs rounded as they are loaded, the answer rounded as it
is returned). It has to come out as NOT correct, at the cell's own size.

    python3 benchmarks/chip/tests/control.py --workload <cell> --seeds 11,12,13 [--scale 0.01]

Imports no JAX and nothing of the program: it runs on any host. Prints one
line per seed with the numbers compared, and exits 0 only if every seed's
control failed a limit.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

import compare  # noqa: E402
import pyarrow as pa  # noqa: E402
import run  # noqa: E402


def control(workload: str, seed: int, scale=None, workers: int = 8) -> dict:
    spec = run.load_cell(workload)
    config, texts = spec["config"], spec["traffic"]["texts"]
    if scale is not None:
        config = {**config, "scale": scale}
    generator = importlib.import_module("data." + config["generator"])
    tables = sorted({t for text in texts for t in text["reads"]})
    tmp = tempfile.mkdtemp(prefix="ballista-control-")
    try:
        t0 = time.perf_counter()
        generator.generate(tmp, config, tables, seed, workers)
        t1 = time.perf_counter()
        wants, answers = {}, []
        for text in texts:
            reads = run.reads_of(text)
            reference = run.reference_module(config, text)
            wants[text["name"]] = reference.run(text["reference"], tmp, reads)
            low = reference.run(text["reference"], tmp, reads, precision="bf16")
            answers.append({"text": text["name"],
                            "table": pa.Table.from_pandas(low, preserve_index=False)})
        verdict = compare.compare_window(
            answers, wants, run.sort_keys(texts), 0, config["limits"])
        verdict["datagen_s"] = t1 - t0
        verdict["reference_s"] = time.perf_counter() - t1
        return verdict
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--workers", type=int, default=8)
    a = ap.parse_args()
    failed_all = True
    for seed in [int(s) for s in a.seeds.split(",")]:
        v = control(a.workload, seed, a.scale, a.workers)
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "control_correct": v["correct"],
                          "compared": v["compared"], "per_text": v["per_text"],
                          "notes": v["notes"][:6], "datagen_s": v["datagen_s"],
                          "reference_s": v["reference_s"]}), flush=True)
        failed_all = failed_all and not v["correct"]
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
