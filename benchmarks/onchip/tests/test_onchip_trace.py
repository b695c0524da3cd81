"""The trace reduction: union of busy intervals, idle share, top device
operations and idle gaps attributed to the harness's host spans, on hand
made intervals and on a small trace recorded on a TPU v5e."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import pytest  # noqa: E402

import trace_reduce as R  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small.xplane.pb")


def test_op_name():
    assert R.op_name("%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p)",
                     "jit_chunk(123)") == "jit_chunk/fusion"
    assert R.op_name("%copy-start.3 = (bf16[2]) copy-start(%x)", "") == \
        "copy-start"


def test_merge_and_gaps():
    busy = R.merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert busy == [(0, 3), (5, 8)]
    assert R.gaps(busy, 0, 12) == [(3, 5), (8, 12)]
    assert R.gaps(R.clip(busy, 1, 6), 1, 6) == [(3, 5)]


def test_reduce_by_hand():
    devices = {"/device:TPU:0": [("dot", 10, 30), ("add", 25, 40),
                                 ("dot", 60, 70)]}
    host = {R.WINDOW_SPAN: [(0, 100)],
            "engine.step": [(5, 45), (55, 75)],
            "wait": [(45, 55), (75, 100)]}
    out = R.reduce(devices, host)
    assert out["busy_s"] == pytest.approx(40e-9)   # [10,40) and [60,70)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["device_ops"][0] == ["dot", pytest.approx(30e-9)]
    gaps = dict(out["idle_gaps"])
    # idle [0,10) mid 5 -> engine.step; [40,60) mid 50 -> wait;
    # [70,100) mid 85 -> wait
    assert gaps == {"engine.step": pytest.approx(10e-9),
                    "wait": pytest.approx(50e-9)}


def test_reduce_averages_devices():
    devices = {"/device:TPU:0": [("a", 0, 50)],
               "/device:TPU:1": [("a", 0, 10)]}
    out = R.reduce(devices, {R.WINDOW_SPAN: [(0, 100)]})
    assert out["busy_s"] == pytest.approx(30e-9) and out["devices"] == 2
    assert out["idle_gaps"] == [["other", pytest.approx(70e-9)]]


def test_reduce_needs_a_window_and_device_ops():
    with pytest.raises(ValueError):
        R.reduce({"/device:TPU:0": [("a", 0, 1)]}, {})
    with pytest.raises(ValueError):
        R.reduce({}, {R.WINDOW_SPAN: [(0, 1)]})


def test_recorded_tpu_trace():
    """Three steps of a jitted matmul between sleeps, traced on one v5e
    with the harness's span names."""
    devices, host = R.read(FIXTURE)
    assert list(devices) == ["/device:TPU:0"]
    assert len(host["engine.step"]) == 3 and len(host["wait"]) == 3
    out = R.reduce(devices, host)
    assert 0 < out["busy_s"] < out["window_s"]
    # ops are named by their program and instruction kind
    names = [n for n, _ in out["device_ops"]]
    assert names[0] == "jit__lambda/fusion"
    assert all(n.startswith("jit__lambda/") for n in names)
    gaps = dict(out["idle_gaps"])
    # each step is a ~25 us matmul between sleeps of 3 ms ("wait") and
    # 2 ms ("submit"): the device idles through both, and each gap goes to
    # the span that holds its midpoint
    assert set(gaps) <= {"wait", "submit", "engine.step", "other"}
    assert gaps["wait"] > 0.009
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
