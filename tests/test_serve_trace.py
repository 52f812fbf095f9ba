"""The serving loop under ``repro.obs`` (DESIGN_OBS.md): spans change no
served id, count the loop's steps and reach a profiler capture, every
device op of the compiled decode step lies in one of its named scopes
(``repro.obs.scopes``), and a latent cache counts the slab positions that
latent attention's core reads."""
import glob

import jax
import numpy as np
import pytest

from repro.launch import serve
from repro.models import build_model, mla
from repro.obs import metrics, scopes, trace

B, P, N, SLAB = 2, 3, 4, 16
CHUNKS = len(serve.prompt_chunks(P, serve.PREFILL_WIDTHS))    # 2 + 1


@pytest.fixture(autouse=True)
def _clean_trace():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


@pytest.fixture(scope="module", params=["qwen2.5-3b", "deepseek-moe-16b"])
def served(request):
    """(api, params, decode, pick) of the family's reduced config."""
    cfg = serve.serving_config(request.param, reduced=True)
    api = build_model(cfg)
    params = serve.init_params(api, 0)
    decode, pick = serve.compile_greedy(
        jax.jit(api.decode_step, donate_argnums=(2,)), params,
        np.zeros((B, 1), np.int32), api.init_cache(cfg, B, SLAB),
        cfg.vocab_size)
    return api, params, decode, pick


def _generate(served):
    api, params, decode, pick = served
    prompts = (np.arange(B * P, dtype=np.int32).reshape(B, P) * 7
               % api.cfg.vocab_size)
    run = serve.greedy_generate(decode, pick, params, prompts,
                                api.init_cache(api.cfg, B, SLAB), N)
    return np.asarray(run.generated), np.asarray(run.logits)


def _latent_positions():
    """``serve_latent_positions_total`` by ``part``."""
    snap = metrics.snapshot().get("serve_latent_positions_total", {})
    return {dict(s["labels"])["part"]: s["value"]
            for s in snap.get("series", [])}


def _spans(name):
    """(start, end) in microseconds of each recorded span ``name``."""
    return [(e["ts"], e["ts"] + e["dur"]) for e in trace.events()
            if e["name"] == name]


def test_spans_change_nothing_served_and_count_the_steps(served):
    ids, logits = _generate(served)
    assert trace.events() == []          # tracing off: no span
    trace.enable()
    traced_ids, traced_logits = _generate(served)
    assert np.array_equal(ids, traced_ids)
    assert logits.tobytes() == traced_logits.tobytes()

    steps = _spans("serve.step")
    (prefill,), (decode,) = _spans("serve.prefill"), _spans("serve.decode")
    inside = lambda phase: [s for s in steps
                            if phase[0] <= s[0] and s[1] <= phase[1]]
    assert len(inside(prefill)) == CHUNKS and len(inside(decode)) == N - 1
    assert len(steps) == CHUNKS + N - 1
    assert len(_spans("serve.sync")) == 2
    (collect,) = _spans("serve.collect")
    assert collect[0] >= decode[1]
    assert trace.validate_chrome_trace(trace.events()) == []


def test_loop_spans_reach_a_capture_with_tracing_off(served, tmp_path):
    """Under a profiler capture the loop's spans are annotations on the
    caller's thread, while ``repro.obs`` stays off and records nothing."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.window"):
            _generate(served)
    finally:
        jax.profiler.stop_trace()
    assert not trace.enabled() and trace.events() == []
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                names.setdefault(e.name, []).append(line.name)
    (window_line,) = names["test.window"]
    assert names["serve.step"] == [window_line] * (CHUNKS + N - 1)
    for name in ("serve.prefill", "serve.decode", "serve.collect"):
        assert names[name] == [window_line]
    assert len(names["serve.sync"]) == 2


def test_every_device_op_has_a_scope(served):
    text = served[2].as_text()
    assert scopes.uncovered(text) == []
    found = set(scopes.scope_map(text).values())
    assert {"layer_loop", "attention/proj", "attention/kv_cache",
            "attention/core", "ffn", "lm_head"} <= found
    if served[0].cfg.n_experts:
        assert {f"ffn/{s}" for s in scopes.SCOPES["ffn"]} <= found


def test_kv_cache_counts_no_latent_positions(served):
    before = _latent_positions()
    _generate(served)
    assert _latent_positions() == before


def test_latent_cache_counts_the_positions_its_core_reads():
    """Moonlight reduced, over a slab of two blocks: each step reads the
    blocks below its position count (none for the prompt's first chunk,
    one block after), and counts the whole slab beside it."""
    cfg = serve.serving_config("moonlight-16b-a3b", reduced=True)
    api = build_model(cfg)
    params = serve.init_params(api, 0)
    slab = 2 * mla.BLOCK
    decode, pick = serve.compile_greedy(
        jax.jit(api.decode_step, donate_argnums=(2,)), params,
        np.zeros((B, 1), np.int32), api.init_cache(cfg, B, slab),
        cfg.vocab_size)
    before = _latent_positions()
    serve.greedy_generate(decode, pick, params,
                          np.ones((B, P), np.int32),
                          api.init_cache(cfg, B, slab), N)
    after = _latent_positions()
    read, whole = (after[k] - before.get(k, 0) for k in ("read", "slab"))
    steps = CHUNKS + N - 1
    assert whole == steps * slab
    assert read == (steps - 1) * mla.BLOCK < whole


def test_scope_of_op_names():
    assert scopes.scope_of("jit(f)/layer_loop/while/body/closed_call/"
                           "attention/core/dot_general") == "attention/core"
    assert scopes.scope_of("jit(f)/layer_loop/while/body/squeeze") \
        == "layer_loop"
    assert scopes.scope_of("jit(f)/lm_head/proj/mul") == "lm_head"
    assert scopes.scope_of("reduce_sum") is None


_HLO = """HloModule m

%fused (p0: f32[4]) -> (f32[4], f32[4]) {
  %p0 = f32[4] parameter(0)
  %c = f32[] constant(0), metadata={op_name="jit(f)/ffn/router/max"}
  %mul.9 = f32[4] multiply(%p0, %p0), metadata={op_name="jit(f)/layer_loop/while/body/attention/proj/mul"}
  ROOT %t = (f32[4], f32[4]) tuple(%mul.9, %mul.9)
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %gte.1 = f32[4] get-tuple-element(%p), index=1
  %add.1 = f32[4] add(%gte.1, %gte.1), metadata={op_name="jit(f)/layer_loop/while/body/attention/kv_cache/add"}
  %copy.1 = f32[4] copy(%add.1)
  %dot.1 = f32[4] multiply(%copy.1, %copy.1), metadata={op_name="jit(f)/layer_loop/while/body/attention/core/dot_general"}
  %copy.4 = f32[4] copy(%dot.1)
  %fusion.2 = (f32[4], f32[4]) fusion(%gte.1), kind=kLoop, calls=%fused
  %gte.2 = f32[4] get-tuple-element(%fusion.2), index=0
  %fusion.1 = f32[4] fusion(%gte.2), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/layer_loop/while/body/ffn/mul"}
  ROOT %tuple.1 = (s32[], f32[4]) tuple(%gte.1, %copy.4)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %copy.2 = f32[4] copy(%a)
  %neg.1 = f32[4] negate(%copy.2), metadata={op_name="jit(f)/embed/neg"}
  %while.1 = (s32[], f32[4]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/layer_loop/while"}
  %copy-start.1 = (f32[4], f32[4], u32[]) copy-start(%a)
  %copy-done.1 = f32[4] copy-done(%copy-start.1)
  ROOT %copy.3 = f32[4] copy(%neg.1)
}
"""


@pytest.mark.parametrize("instr, scope", [
    ("copy.1", "attention/core"),    # a copy made for its user's dot
    ("copy.2", "embed"),             # of a parameter, for its user
    ("copy.4", "attention/core"),    # into the loop's result: its operand
    ("copy.3", "embed"),             # the step's result: its operand
    ("fusion.2", "attention/proj"),  # no op_name: its fused ops' (no
                                     # constant's)
    ("fusion.1", "ffn"),             # its own op_name first
    ("while.1", "layer_loop"),
    ("copy-start.1", "embed"),       # a prefetch nothing uses: the
    ("copy-done.1", "embed"),        # parameter's other user's
])
def test_compiler_added_ops_inherit_a_scope(instr, scope):
    assert scopes.scope_map(_HLO)[instr] == scope
    assert scopes.uncovered(_HLO) == []
