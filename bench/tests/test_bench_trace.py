"""The reduction from a profiler trace to busy time, top operations and
named idle gaps: on hand-made events, and on a small trace recorded on a
TPU v5e chip by ``record_trace.py``."""
from __future__ import annotations

import pytest

from bench.metrics import devtrace
from bench.tests.conftest import BENCH

RECORDED = BENCH / "tests" / "data" / "small_trace.xplane.pb"


def test_reduce_hand_made_events():
    ms = 1_000_000
    dev = {"/device:TPU:0": [("fusion.1", 0, 10 * ms),
                             ("fusion.2", 5 * ms, 12 * ms),
                             ("copy.3", 12 * ms + 5_000, 13 * ms),
                             ("fusion.4", 28 * ms, 29 * ms),
                             ("fusion.1", 40 * ms, 50 * ms)],
           "/device:TPU:1": [("fusion.1", 0, 25 * ms)]}
    host = [("bench:traced_window", 0, 60 * ms),
            ("bench:step", 0, 13 * ms),
            ("bench:feed", 13 * ms, 30 * ms),
            ("program:plan", 30 * ms, 40 * ms),
            ("bench:step", 40 * ms, 50 * ms)]
    out = devtrace.reduce_events(dev, host, (0, 60 * ms))
    # chip 0 busy 13 ms less a 5 us launch gap, then 1 and 10 ms;
    # chip 1 busy 25 ms
    assert out["busy_s"] == pytest.approx(((24 * ms - 5_000) + 25 * ms)
                                          / 2 / 1e9)
    assert out["window_s"] == pytest.approx(0.060)
    assert out["chips"] == 2
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["fusion.2"] == pytest.approx(0.007)
    # a gap goes to the innermost span open at its midpoint
    gaps = dict(out["idle_gaps"])
    assert gaps["bench:feed"] == pytest.approx(0.015)
    assert gaps["program:plan"] == pytest.approx(0.011)
    assert gaps["bench:traced_window"] == pytest.approx(0.010)
    assert gaps["short_gaps"] == pytest.approx(5e-6)
    assert devtrace.reduce_events({"/device:TPU:0": []}, host) is None


def test_op_names_drop_the_hlo_text():
    assert devtrace.op_name(
        "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") \
        == "fusion.3"
    assert devtrace.op_name("copy-done") == "copy-done"


def test_reduce_recorded_chip_trace():
    dev, host = devtrace.load_events(str(RECORDED))
    assert list(dev) == ["/device:TPU:0"] and dev["/device:TPU:0"]
    win = [(s, e) for n, s, e in host if n == "bench:traced_window"]
    out = devtrace.reduce_events(dev, host, win[0])
    # three rounds of four matmul chains, each round followed by 20 ms
    # of host sleep inside bench:feed
    assert 0.060 < out["window_s"] < 0.2
    assert 0 < out["busy_s"] < 0.25 * out["window_s"]
    gaps = dict(out["idle_gaps"])
    assert max(gaps, key=gaps.get) == "bench:feed"
    assert gaps["bench:feed"] >= 0.059
    names = [n for n, _ in out["device_ops"]]
    assert any("fusion" in n for n in names)
    assert sum(v for _, v in out["device_ops"]) == pytest.approx(
        out["busy_s"], rel=0.05)
