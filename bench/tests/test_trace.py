"""The trace reduction on a trace recorded from a CPU run of a tiny jitted
function, and on hand-made intervals.  On the CPU the XLA operations run
on a host thread, so the test points the reduction at that thread; the
chip's device planes are what it reads by default."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace as T


def cpu_op_line(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation(T.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("fetch"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    return T.newest_xplane(logdir)


def test_busy_union_and_idle_share(recorded):
    tr = T.read(recorded, ("step", "fetch"), device_line=cpu_op_line)
    lo, hi = tr.window
    (dev,) = tr.ops
    merged = T.union((s, e) for _, s, e in tr.ops[dev])
    expect = sum(min(e, hi) - max(s, lo) for s, e in merged if e > lo and s < hi)
    assert tr.busy_s() == pytest.approx(expect / 1e9)
    assert 0 < tr.busy_s() < tr.window_s()
    assert tr.window_s() >= 0.06                      # three 20 ms sleeps
    idle = sum(s for _, s in tr.idle_gaps(top=100))
    assert idle == pytest.approx(tr.window_s() - tr.busy_s(), rel=1e-6)


def test_gaps_charged_to_the_open_span(recorded):
    tr = T.read(recorded, ("step", "fetch"), device_line=cpu_op_line)
    gaps = dict(tr.idle_gaps())
    assert max(gaps, key=gaps.get) == "fetch"
    assert gaps["fetch"] >= 0.06 * 0.9
    assert tr.busy_in("step") == pytest.approx(tr.busy_s(), rel=0.05)
    assert tr.busy_in("fetch") < 0.1 * tr.busy_s()


def test_no_device_plane_raises(recorded):
    with pytest.raises(ValueError, match="no device operations"):
        T.read(recorded, ("step",))


def test_hand_made_intervals():
    tr = T.Trace(
        ops={"/device:TPU:0": [("a", 10, 20), ("b", 15, 30), ("a", 50, 60)]},
        spans=[(T.WINDOW, 0, 100), ("step", 5, 45), ("fetch", 35, 70),
               ("inner", 38, 42)])
    assert tr.busy_s() == pytest.approx(30e-9)
    assert tr.window_s() == pytest.approx(100e-9)
    assert dict(tr.idle_gaps()) == pytest.approx(
        {"window": 40e-9, "inner": 20e-9, "step": 10e-9})
    assert tr.busy_in("step") == pytest.approx(20e-9)
    assert dict(tr.device_ops()) == pytest.approx({"a": 20e-9, "b": 15e-9})


def test_device_clock_lead_is_removed():
    assert T.device_lead([90, 200, 305], [100, 198, 300]) == 10
    assert T.device_lead([110, 205], [100, 200]) == 0          # no lead
    assert T.device_lead([90, 200], [100, 150, 300]) == 0      # no pairing
