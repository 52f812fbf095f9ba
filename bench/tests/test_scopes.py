"""``bench/scopes.py`` on a small CPU trace of the serving loop
(``record_serve_trace.py`` says what it holds), and the readers of what
the program records, the scope metrics among them, in a traced run at
the reduced size."""
import json
from pathlib import Path

import jax
import pytest

from bench import harness, run, scopes, trace

from conftest import CELLS, CPU_PEAK, small_cell

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


@pytest.fixture(scope="module", params=CELLS)
def traced_run(request):
    cell, cfg = small_cell(request.param)
    return run.run(cell, SEED, 0.2, True, jax.devices(), cfg=cfg,
                   peak=CPU_PEAK)


def test_new_readers_read_a_traced_run(traced_run):
    out = traced_run
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert any(n.startswith("serve.")
               for n, _ in out["breakdown"]["idle_gaps"])


def test_traced_run_reports_scope_metrics(traced_run):
    """``bench/run.py`` reduces the decode phase by scope
    (``Reading.decode_scopes``) and its readers report each scope."""
    out = traced_run
    assert out["correct"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("attention_step_ms", "ffn_step_ms", "layer_loop_step_ms"):
        assert got[name] > 0
    assert got["unscoped_share"] == 0.0
    assert {"attention", "ffn", "layer_loop",
            "lm_head"} <= set(out["decode_scope_ms"])
