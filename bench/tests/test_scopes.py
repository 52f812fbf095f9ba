"""``bench/scopes.py`` on a small CPU trace of the serving loop
(``record_serve_trace.py`` says what it holds), and the reader of what
the program records and ``bench/probe.py``, in a traced run at the
reduced size."""
import json
from pathlib import Path

import jax
import pytest

from bench import harness, run, scopes, trace

from conftest import CPU_PEAK

DATA = Path(__file__).resolve().parent / "data"
PATH = str(DATA / "cpu_serve_trace.xplane.pb")
PROGRAM = f"jit_{harness.STEP_NAME}"
SEED = 2 ** 31 + 29


@pytest.fixture(scope="module")
def recorded():
    with open(DATA / "cpu_serve_scopes.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def per_step(recorded):
    return scopes.decode_scopes(PATH, recorded["scopes"], PROGRAM,
                                harness.WINDOW_SPAN, recorded["N"] - 1)


def _decode_leaf_ns(n_decode):
    """Nanoseconds of the leaf ops (``trace.leaves``) inside the last
    ``n_decode`` executions of the step, straight from the file: a CPU
    trace's ops are host events with an ``hlo_op``, grouped into
    executions by ``run_id``."""
    from jax.profiler import ProfileData
    window, ops, runs = None, [], {}
    for plane in ProfileData.from_file(PATH).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if e.name == harness.WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                if "hlo_op" in st:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    ops.append((e.name, *iv))
                    if st.get("hlo_module") == PROGRAM:
                        lo, hi = runs.get(st["run_id"], iv)
                        runs[st["run_id"]] = (min(lo, iv[0]),
                                              max(hi, iv[1]))
    decode = sorted(r for r in runs.values()
                    if window[0] <= r[0] and r[1] <= window[1])[-n_decode:]
    leaves = trace.leaves([o for o in ops
                           if window[0] <= o[1] and o[2] <= window[1]])
    return sum(e - s for _, s, e in leaves
               if any(a <= s and e <= b for a, b in decode))


def test_scopes_and_unscoped_add_up_to_the_decode_phase(recorded, per_step):
    assert len(per_step) == recorded["N"] - 1
    total = sum(scopes.totals(per_step).values())
    assert total == pytest.approx(_decode_leaf_ns(len(per_step)) / 1e9,
                                  rel=1e-12)
    assert scopes.unscoped_share(per_step) == 0.0
    for scope in ("attention", "ffn", "layer_loop", "lm_head"):
        assert scopes.step_ms(per_step, scope) > 0


def test_idle_gaps_in_the_loop_carry_serve_spans():
    summary = trace.reduce(PATH, PROGRAM, harness.WINDOW_SPAN)
    idle = dict(summary.idle)
    assert {"serve.sync", "serve.collect"} <= set(idle)
    assert "bench.greedy_generate" not in idle


def test_new_readers_read_a_traced_run(small):
    cell, cfg = small
    out = run.run(cell, SEED, 0.2, True, jax.devices(), cfg=cfg,
                  peak=CPU_PEAK)
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert any(n.startswith("serve.")
               for n, _ in out["breakdown"]["idle_gaps"])


def test_probe_adds_scopes_to_a_traced_run(small):
    """``bench/probe.py`` is ``run.run`` with its readings added: the
    run's own keys, and the decode phase's device time by scope."""
    from bench import probe
    cell, cfg = small
    out = probe.probe(cell, SEED + 1, 0.2, True, jax.devices(), cfg=cfg,
                      peak=CPU_PEAK)
    assert out["correct"] and "step_device_ms" in out["metrics"]
    extra = out["probe"]
    assert not extra["spans_on"]
    assert extra["batch_compiles"] == [0] * len(out["batches"])
    got = extra["scopes"]
    assert got["unscoped_share"] == 0.0
    for scope in ("attention", "attention/core", "ffn", "layer_loop",
                  "lm_head"):
        assert got["step_ms"][scope] > 0
    assert got["scoped_plus_unscoped_ms"] > 0
    assert set(got["device_scopes_s"]) >= {"attention/core", "ffn"}
