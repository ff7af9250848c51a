"""The roofline's byte count, the generators, BENCHMARK.json against the
files it names, and the control of `correct` at a size a test can hold."""

import hashlib
import importlib
import json
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import compare
import run
from reference.lowprec import to_bf16

CELLS = ["tpch_sf10_1chip.scan_agg", "tpch_sf10_1chip.join_topk"]
SMALL = 0.01  # the scale factor a test can hold


def _bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells():
    have = {w["name"] for w in _bench()["workloads"]}
    return [c for c in CELLS if c in have]


def _digest(d):
    h = hashlib.sha256()
    for root, _dirs, files in sorted(os.walk(d)):
        for fn in sorted(files):
            t = pq.read_table(os.path.join(root, fn))
            h.update(fn.encode())
            for batch in t.to_batches():
                for col in batch.columns:
                    for buf in col.buffers():
                        if buf is not None:
                            h.update(buf)
    return h.hexdigest()


def test_q1_must_read_22_bytes_a_row_and_q6_16():
    traffic = run._json(os.path.join(run.CHIP, "traffic", "scan_agg.json"))
    widths = run._json(os.path.join(run.CHIP, "peaks.json"))["logical_width_bytes"]
    by = {t["name"]: t for t in traffic["texts"]}
    rows = {"lineitem": 59_986_052}
    assert run.floor_bytes(by["q1"], rows, widths) == 59_986_052 * 22
    assert run.floor_bytes(by["q6"], rows, widths) == 59_986_052 * 16


def test_every_named_column_is_in_the_generated_schema(tmp_path):
    for name in ("scan_agg", "join_topk"):
        traffic = run._json(os.path.join(run.CHIP, "traffic", name + ".json"))
        tpch = importlib.import_module("data.tpch")
        for text in traffic["texts"]:
            for table, cols in text["reads"].items():
                assert set(cols) <= set(tpch.SCHEMAS[table].names), (text["name"], table)


def test_the_generator_does_not_depend_on_workers_and_does_on_the_seed(tmp_path):
    mod = importlib.import_module("data.tpch")
    cfg = run._json(os.path.join(run.CHIP, "configs", "tpch_sf10_1chip.json"))
    cfg = {**cfg, "scale": SMALL}
    tables = list(mod.TABLES)
    seen = {}
    for label, seed, workers in (("a1", 2147483659, 1), ("a3", 2147483659, 3), ("b1", 5, 1)):
        d = str(tmp_path / label)
        rows = mod.generate(d, cfg, tables, seed, workers)
        assert all(n > 0 for n in rows.values())
        seen[label] = (_digest(d), rows)
    assert seen["a1"] == seen["a3"]
    assert seen["a1"][0] != seen["b1"][0]


def test_tpch_key_relations(tmp_path):
    mod = importlib.import_module("data.tpch")
    cfg = {"scale": 0.01, "files_per_table": 8}
    rows = mod.generate(str(tmp_path), cfg, list(mod.TABLES), 9, 1)
    li = pq.read_table(str(tmp_path / "lineitem")).to_pandas()
    o = pq.read_table(str(tmp_path / "orders")).to_pandas()
    assert rows["orders"] == 15000 and rows["customer"] == 1500
    assert len(os.listdir(tmp_path / "lineitem")) == 8
    assert set(li.l_orderkey) == set(o.o_orderkey)
    assert o.o_custkey.between(1, 1500).all()
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    assert (pd.to_datetime(j.l_shipdate) > pd.to_datetime(j.o_orderdate)).all()
    assert set(li.l_returnflag) == {"R", "A", "N"} and set(li.l_linestatus) == {"O", "F"}


def test_bf16_rounding():
    x = np.array([1.0, 0.05, 3.14159, 104949.5, -7.0, 255.0, 257.0])
    got = to_bf16(x)
    assert got[0] == 1.0 and got[4] == -7.0 and got[5] == 255.0
    assert got[6] in (256.0, 258.0)
    rel = np.abs(got - x) / np.abs(x)
    assert rel.max() <= 2.0 ** -8 and rel[1] > 1e-4


def test_compare_counts_what_differs():
    want = pd.DataFrame({"k": [1, 2], "s": ["a", "b"], "v": [10.0, 20.0]})
    same = pa.Table.from_pandas(want)
    off = pa.Table.from_pandas(want.assign(v=[10.0, 20.002]))
    key = pa.Table.from_pandas(want.assign(k=[1, 3]))
    short = pa.Table.from_pandas(want.head(1))
    limits = {"answers_missing": 0, "exact_mismatches": 0, "rel_err_max": 2e-5}

    def judge(*tables, failed=0):
        return compare.compare_window(
            [{"text": "t", "table": t} for t in tables], {"t": want}, {}, failed, limits)

    assert judge(same, same)["correct"]
    v = judge(same, off)
    assert not v["correct"] and v["compared"]["rel_err_max"]["value"] == pytest.approx(1e-4)
    assert judge(same, key)["compared"]["exact_mismatches"]["value"] == 1
    assert judge(short)["compared"]["exact_mismatches"]["value"] == 1
    assert not judge(same, failed=1)["correct"]
    assert not judge()["correct"]  # nothing answered is not correct


@pytest.mark.parametrize("cell", CELLS)
def test_the_bf16_control_is_not_correct(cell):
    if cell not in _cells():
        pytest.skip(f"{cell} is not in BENCHMARK.json")
    control = importlib.import_module("control")
    v = control.control(cell, 2147483659, SMALL * 5, workers=1)
    assert not v["correct"]
    assert v["compared"]["rel_err_max"]["value"] > 10 * v["compared"]["rel_err_max"]["limit"]


def test_benchmark_json_agrees_with_the_files_it_names():
    bench = _bench()
    readers = run.layer_readers()
    layers = set()
    for m in bench["per_layer"]:
        mod = readers[m["name"]]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"]), m["name"]
        layers.add(m["layer"])
    for c in bench["configs"]:
        f = run._json(os.path.join(run.ROOT, c["file"]))
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        assert all(k in f for k in c["reduced"])
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"])
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s", "queries_per_min"}
        assert spec["per_layer"]
        for text in spec["traffic"]["texts"]:
            assert os.path.exists(os.path.join(run.CHIP, "queries", text["sql"]))


@pytest.mark.parametrize("key,value", [("loop", "open"), ("clients", 3),
                                       ("order", "permuted")])
def test_a_mix_that_asks_for_another_generator_is_refused(monkeypatch, key, value):
    real = run._json

    def altered(path):
        d = real(path)
        return {**d, key: value} if os.sep + "traffic" + os.sep in path else d

    monkeypatch.setattr(run, "_json", altered)
    with pytest.raises(run.Refused, match=key):
        run.load_cell("tpch_sf10_1chip.scan_agg")


def test_window_drift_reads_the_last_third_against_the_first():
    drift = run.layer_readers()["host.window_drift"]
    lat = {"a": [1.0, 1.0, 1.1, 1.1, 1.2, 1.2], "b": [2.0] * 6, "once": [5.0]}
    got = drift.read({"window": {"thirds": run.thirds(lat)}})
    assert got == pytest.approx(100 * (0.2 + 0.0) / 2)
    assert drift.read({"window": {"thirds": run.thirds({"once": [5.0]})}}) is None
