"""`trace_reduce` on a small trace recorded on the chip: PR 25's first chip
call, six rounds of q1 and q6 of `tpch_sf10_1chip.scan_agg` on one TPU v5e
(`fixtures/scan_agg_6rounds.xplane.pb.gz`). The run printed busy_s
0.124141356 and window_s 2.823285102 from it."""

import gzip
import os
import shutil

import pytest

import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "scan_agg_6rounds.xplane.pb.gz")


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "t.xplane.pb")
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def test_busy_is_the_union_of_the_xla_ops_line_alone(xplane):
    r = trace_reduce.reduce(xplane, "/device:TPU:", "XLA Ops", window_s=99.0)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(2.823285102, rel=1e-9)  # the slice's own
    assert r["busy_s"] == pytest.approx(0.124141356, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    # the modules line covers the same seconds once more: it is not added
    m = trace_reduce.reduce(xplane, "/device:TPU:", "XLA Modules", window_s=99.0)
    assert m["busy_s"] >= r["busy_s"] and m["busy_s"] < 2 * r["busy_s"] + 0.05


def test_breakdown_names_are_short_and_gaps_name_the_query(xplane):
    r = trace_reduce.reduce(xplane, "/device:TPU:", "XLA Ops", window_s=99.0)
    assert 1 <= len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) == 10
    assert all(len(n) <= 80 and " = " not in n and s > 0 for n, s in r["device_ops"])
    assert r["device_ops"] == sorted(r["device_ops"], key=lambda x: -x[1])
    assert all(n.startswith("idle during ") for n, _s in r["idle_gaps"])
    assert any(n in ("idle during q1", "idle during q6") for n, _s in r["idle_gaps"])
    total = sum(s for _n, s in r["device_ops"])
    assert total <= r["busy_s"] * 1.001


def test_a_missing_plane_or_line_fails_loudly_and_names_what_is_there(xplane):
    with pytest.raises(trace_reduce.TraceError) as e:
        trace_reduce.reduce(xplane, "/device:GPU:", "XLA Ops", window_s=3.0)
    assert "/device:TPU:0" in str(e.value) and "XLA Ops" in str(e.value)
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce(xplane, "/device:TPU:", "No Such Line", window_s=3.0)


def test_no_xplane_file_fails(tmp_path):
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.find_xplane(str(tmp_path))
