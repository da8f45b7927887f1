"""reduce_trace.py on a small trace recorded on a TPU v5e
(benchmarks/tests/record_tiny_trace.py: three calls of one jitted
program), checked into benchmarks/tests/data/."""

import os

import pytest

from benchmarks import reduce_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tiny_tpu.xplane.pb")


def test_union_seconds():
    assert reduce_trace.union_seconds([]) == 0.0
    assert reduce_trace.union_seconds([(0, 1e9)]) == 1.0
    # overlap and containment count once, a gap counts not at all
    assert reduce_trace.union_seconds(
        [(0, 2e9), (1e9, 3e9), (1.5e9, 1.6e9), (5e9, 6e9)]) == 4.0
    assert reduce_trace.union_seconds([(5e9, 6e9), (0, 1e9)]) == 2.0


def test_tiny_trace_busy_window_and_idle_share():
    r = reduce_trace.reduce(TINY, step_prefix="jit__lambda")
    assert r["planes"] == ["/device:TPU:0"]
    assert r["steps"] == 3
    assert 0 < r["busy_s"] < r["window_s"]
    # three calls some 30 ms apart, each a few microseconds of device work
    assert 0.05 < r["window_s"] < 1.0
    idle = 1.0 - r["busy_s"] / r["window_s"]
    assert 0.99 < idle < 1.0
    # the step's programs cover its operations, and little more
    assert r["busy_s"] <= r["step_seconds_total"] * 1.01
    assert r["device_ops"] and all(s > 0 for _n, s in r["device_ops"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert r["idle_gaps"][0][1] >= r["idle_gaps"][-1][1]


def test_no_device_plane_raises():
    with pytest.raises(reduce_trace.EmptyDeviceTrace):
        reduce_trace.reduce(TINY, device_prefix="/device:GPU:")


def test_device_plane_without_operations_raises():
    # the trace's "#Chip0 Misc" plane exists and holds no operation
    with pytest.raises(reduce_trace.EmptyDeviceTrace):
        reduce_trace.reduce(TINY, device_prefix="#Chip0 Misc")


def test_no_trace_file_raises(tmp_path):
    with pytest.raises(reduce_trace.EmptyDeviceTrace):
        reduce_trace.find_xplane(str(tmp_path))
