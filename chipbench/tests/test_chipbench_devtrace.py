"""The trace reduction, on hand-built planes and on a small trace recorded
on a TPU v5e (``record_trace.py``)."""
from types import SimpleNamespace as NS

import pytest

import devtrace
from tiny import CHIPBENCH

RECORDED = CHIPBENCH / "testdata" / "tiny_step.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.1", 100, 50), _ev("fusion.2", 120, 50),
                                   _ev("copy.3", 400, 100), _ev("fusion.1", 900, 300)]),
        NS(name="XLA Modules", events=[_ev("jit_go", 100, 1100)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev(devtrace.WINDOW_SPAN, 0, 1000), _ev("engine.step", 90, 420),
        _ev("harness.account", 520, 200), _ev("wait_arrival", 720, 170)])])
    return [host, dev]


def test_reduction_by_hand():
    r = devtrace.reduce(devtrace.collect(_planes()))
    # window [0, 1000): busy [100,170) + [400,500) + [900,1000) = 270 ns
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(270e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(150e-9)]
    # gaps: [0,100) untracked... [170,400) step, [500,900) account+wait
    gaps = r["idle_gaps"]
    assert gaps[0] == ["harness.account", pytest.approx(400e-9)]
    assert ["engine.step", pytest.approx(230e-9)] in gaps
    assert sum(g for _, g in gaps) == pytest.approx(730e-9)


def test_no_window_or_no_device_gives_nothing():
    host, dev = _planes()
    assert devtrace.reduce(devtrace.collect([dev])) == {}
    assert devtrace.reduce(devtrace.collect([host])) == {}


def test_recorded_v5e_trace():
    from jax.profiler import ProfileData

    col = devtrace.collect(ProfileData.from_file(str(RECORDED)).planes)
    r = devtrace.reduce(col)
    assert 0 < r["busy_s"] < r["window_s"] < 5.0
    # as read when it was recorded (record_trace.py): 4.0 ms of device
    # work in a 42.8 ms window, the engine's host work in every long gap
    assert r["busy_s"] == pytest.approx(0.004012616, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.042819156, rel=1e-6)
    assert r["device_ops"][0][0] == "iota_reduce_fusion"
    assert all(d > 0 for _, d in r["device_ops"])
    assert [label for label, _ in r["idle_gaps"]] == ["engine.step"] * 10
