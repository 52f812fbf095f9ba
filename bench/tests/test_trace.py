"""``bench/trace.py`` on a small trace recorded on the CPU
(``record_trace.py`` says what it holds)."""
from pathlib import Path

import pytest

from bench import harness, trace

PATH = str(Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb")
PROGRAM = f"jit_{harness.STEP_NAME}"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(PATH, PROGRAM, harness.WINDOW_SPAN)


def _raw():
    """Window and op intervals straight from the file, without the
    module's helpers."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(PATH)
    window, ops = None, []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if e.name == harness.WINDOW_SPAN:
                    window = (e.start_ns, end)
                if "hlo_op" in dict(e.stats):
                    ops.append((e.start_ns, end))
    return window, ops


def test_steps_found_by_name(summary):
    assert len(summary.steps_s) == 5
    assert all(s > 0 for s in summary.steps_s)


def test_busy_is_the_union_of_ops(summary):
    window, ops = _raw()
    # count each nanosecond boundary segment covered by some op
    points = sorted({window[0], window[1]}
                    | {t for s, e in ops for t in (s, e)
                       if window[0] <= t <= window[1]})
    busy = sum(b - a for a, b in zip(points, points[1:])
               if any(s <= a and b <= e for s, e in ops))
    assert summary.busy_s == pytest.approx(busy / 1e9, abs=1e-9)
    assert summary.window_s == pytest.approx((window[1] - window[0]) / 1e9)
    assert 0 < summary.busy_s < summary.window_s


def test_idle_put_down_to_host_spans(summary):
    idle = dict(summary.idle)
    assert idle["bench.handoff"] >= 0.019
    assert idle["host.pause"] >= 5 * 0.0049
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, abs=1e-6)
    assert [s for _, s in summary.idle] == sorted(
        (s for _, s in summary.idle), reverse=True)


def test_ops_ranked(summary):
    secs = [s for _, s in summary.ops]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    assert sum(secs) <= summary.busy_s + 1e-9


def test_union_and_leaves():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    ops = [("loop", 0, 10), ("a", 1, 4), ("b", 4, 9), ("c", 12, 13)]
    assert [n for n, _, _ in trace.leaves(ops)] == ["a", "b", "c"]
