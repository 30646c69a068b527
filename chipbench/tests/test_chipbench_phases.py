"""The engine's step phases: the idle split on hand-built planes, the
step-event means, and the spans a real engine step records."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

import devtrace
import phases
import run_cell
from tiny import CHIPBENCH

RECORDED = CHIPBENCH / "testdata" / "tiny_step.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes(device_ops=((290, 190), (620, 30))):
    """One step inside ``engine.step``: schedule, prepare, launch, wait,
    account (a collection inside it), then a wait for an arrival; the
    device runs the step's program from 290 to 480 ns."""
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev(f"fusion.{i}", s, d) for i, (s, d) in enumerate(device_ops)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev(devtrace.WINDOW_SPAN, 0, 1000), _ev("engine.step", 100, 500),
        _ev("step.schedule#step=3#", 110, 90), _ev("step.prepare", 200, 50),
        _ev("step.launch", 250, 50), _ev("step.wait", 300, 200),
        _ev("step.account", 500, 90), _ev("gc", 520, 40),
        _ev("wait_arrival", 700, 200)])])
    return [host, dev]


def test_idle_splits_to_the_innermost_span():
    by_span, window_s = phases.idle_by_span(phases.collect(_planes()))
    assert window_s == pytest.approx(1000e-9)
    want = {"engine.step": 20, "step.schedule": 90, "step.prepare": 50,
            "step.launch": 40, "step.wait": 20, "step.account": 50,
            "gc": 40, "wait_arrival": 200}
    assert by_span == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    # every phase and gc; not the glue in engine.step, not the arrival
    assert phases.share(phases.collect(_planes())) == pytest.approx(29.0)


def test_idle_under_the_harness_does_not_count_under_wait_does():
    # the device idles through the whole wait: it counts, the arrival
    # and the glue inside engine.step do not
    col = phases.collect(_planes(device_ops=((620, 30),)))
    by_span, _ = phases.idle_by_span(col)
    assert by_span["step.wait"] == pytest.approx(200e-9)
    assert by_span["step.launch"] == pytest.approx(50e-9)
    assert by_span["wait_arrival"] == pytest.approx(200e-9)
    assert phases.share(col) == pytest.approx(48.0)
    assert phases.share(col) <= 100.0 * (1 - 30 / 1000)


@pytest.mark.parametrize("offset", [-30, -15, 15, 25])
def test_share_holds_under_a_device_clock_offset(offset):
    """The device plane sitting early or late against the host's slides
    idle between a gap's end phases (wait against launch/account); the
    share over all phases stays, the phases' own split does not."""
    base = phases.collect(_planes())
    col = phases.collect(_planes(device_ops=((290 + offset, 190),
                                             (620 + offset, 30))))
    assert phases.share(col) == pytest.approx(phases.share(base))
    moved = phases.idle_by_span(col)[0]
    assert moved["step.wait"] != pytest.approx(
        phases.idle_by_span(base)[0]["step.wait"])


def test_two_devices_average():
    host, dev = _planes()
    idle_dev = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[
        _ev("fusion.9", 0, 1000)])])
    assert phases.share(phases.collect([host, dev, idle_dev])) == \
        pytest.approx(14.5)


def test_nothing_to_read_gives_none():
    host, dev = _planes()
    assert phases.share(phases.collect([dev])) is None           # no window
    assert phases.share(phases.collect([host])) is None          # no device
    bare = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        e for e in host.lines[0].events
        if not e.name.startswith(("step.", "gc"))])])
    assert phases.share(phases.collect([bare, dev])) is None     # no phases


def test_recorded_v5e_trace_has_no_phases():
    from jax.profiler import ProfileData

    col = phases.collect(ProfileData.from_file(str(RECORDED)).planes)
    assert col["phases"] == []
    assert devtrace.reduce(col)["busy_s"] > 0
    assert phases.share(col) is None


def _step(ts_s, **ms):
    return {"ph": "X", "name": "step", "ts": ts_s * 1e6,
            "args": {"phase_ms": {p: ms.get(p, 0.0) for p in
                                  ("schedule", "prepare", "launch", "wait",
                                   "account")}}}


def test_host_ms_means_the_window_steps():
    events = [_step(-1.0, launch=9.0), _step(0.0, launch=1.0),
              _step(2.5, launch=3.0, account=4.0), _step(5.0, launch=9.0),
              {"ph": "i", "name": "first_token", "ts": 1e6, "tid": 1}]
    run = NS(events=events, window_s=5.0, devtrace={})
    assert run_cell.read_metric("host_ms.launch", run) == pytest.approx(2.0)
    assert run_cell.read_metric("host_ms.account", run) == pytest.approx(2.0)
    assert run_cell.read_metric("host_ms.schedule", run) == 0.0
    # no phases recorded (a program without them), or an untraced run
    old = [{"ph": "X", "name": "step", "ts": 0.0, "args": {"wall_ms": 1.0}}]
    assert run_cell.read_metric("host_ms.prepare",
                                NS(events=old, window_s=5.0)) is None
    assert run_cell.read_metric("host_ms.prepare",
                                NS(events=None, window_s=5.0)) is None
    assert run_cell.read_metric("device_idle_share.step_host", run) is None


def test_an_engine_step_records_every_phase_span(tmp_path):
    """On whatever device JAX has: the engine's phase spans reach the
    profiler's host plane under their bare names."""
    import jax

    from repro.models.api import Model
    from repro.obs import Observability
    from repro.serving.server import PagedLLMEngine
    from tiny import program_config

    model = Model(program_config())
    params = model.init(jax.random.PRNGKey(0))
    eng = PagedLLMEngine(model, params, num_blocks=17, block_size=16,
                         max_batch=2, max_len=64,
                         obs=Observability.create())
    eng.submit(np.arange(1, 20, dtype=np.int32), max_new=3)
    eng.step()                                   # compile outside
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        while not eng.idle:
            eng.step()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    col = phases.collect(ProfileData.from_file(
        devtrace.find_xplane(str(tmp_path))).planes)
    names = {n for n, _, _ in col["phases"]}
    assert {"step.schedule", "step.prepare", "step.launch", "step.wait",
            "step.account"} <= names
    assert all(e > s for _, s, e in col["phases"])
