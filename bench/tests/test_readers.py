"""The per-layer readers of the decode step on traces of every shape a
serving loop can leave: one execution a prompt token and a generated id
(today's loop), a prompt fed in one execution, and a loop that runs on
the device.  Where the decode phase is not one execution an id, the
readers fall silent; they never raise."""
import numpy as np
import pytest

from bench import harness, run, trace

from conftest import CPU_PEAK, small_cell

P, N, B = 5, 4, 2


def _reading(n_steps):
    cell, _ = small_cell("qwen2.5-3b.chat-b16-ctx1k")
    batch = harness.Batch(0, np.zeros((B, P), np.int32),
                          np.zeros((B, N), np.int32), 0.5, 0.3, 0.0, 1.0)
    steps = [0.010 + 0.001 * j for j in range(n_steps)]
    summary = trace.Summary(window_s=1.0, busy_s=0.9, steps_s=steps,
                            ops=[], idle=[])
    window = harness.Window([batch, batch], 0.0, 2.0)
    return run.Reading(cell, 1.0, 0.01, window, summary, CPU_PEAK)


@pytest.mark.parametrize("n_steps", [P + N - 1, 1 + N - 1])
def test_decode_phase_counted_from_the_end(n_steps):
    r = _reading(n_steps)
    steps = r.decode_steps()
    assert [f for f, _ in steps] == [P + 1, P + 2, P + 3]
    assert [s for _, s in steps] == r.trace.steps_s[-(N - 1):]
    assert run.read_metric("step_device_ms", r) == pytest.approx(
        1e3 * float(np.median(r.trace.steps_s[-(N - 1):])))
    assert run.read_metric("step_roofline", r) > 0


@pytest.mark.parametrize("n_steps", [0, 1, N - 2])
def test_silent_without_one_execution_an_id(n_steps):
    r = _reading(n_steps)
    assert r.decode_steps() is None
    for name in ("step_device_ms", "step_roofline"):
        assert run.read_metric(name, r) is None
    assert run.read_metric("step_mfu", r) > 0
