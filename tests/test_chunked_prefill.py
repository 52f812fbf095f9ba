"""The prompt phase in chunks (``serve.PREFILL_WIDTHS``): a chunk fed
through the serve step serves what one token a step serves, no query of a
chunk sees a later position, the serving MoE drops no token, the loop
counts its chunks, and a recurrent cache still takes one token a step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import serve
from repro.models import build_model, layers as L, moe
from repro.obs import metrics

B, N, SLAB = 2, 4, 160
ARCHS = ["qwen2.5-3b", "deepseek-moe-16b"]


def _compile(api, params, slab=SLAB):
    return serve.compile_greedy(
        jax.jit(api.decode_step, donate_argnums=(2,)), params,
        np.zeros((B, 1), np.int32), api.init_cache(api.cfg, B, slab),
        api.cfg.vocab_size)


@pytest.fixture(scope="module", params=ARCHS)
def ladders(request):
    """(api, params, (decode, pick), and the same one-token executable
    alone: a decode that feeds the prompt one token a step)."""
    cfg = serve.serving_config(request.param, reduced=True)
    api = build_model(cfg)
    params = serve.init_params(api, 0)
    decode, pick = _compile(api, params)
    one = serve.ChunkedStep({1: decode.by_width[1]})
    return api, params, (decode, pick), (one, pick)


def _prompts(api, P, seed=0):
    rng = np.random.default_rng([seed, P])
    return rng.integers(0, api.cfg.vocab_size, (B, P)).astype(np.int32)


def _chunk_counts():
    snap = metrics.snapshot().get("serve_prefill_chunks_total", {})
    return {int(dict(s["labels"])["width"]): s["value"]
            for s in snap.get("series", [])}


@pytest.mark.parametrize("P", [1, 3, 37, 130])
def test_chunked_prefill_serves_the_one_token_ids(ladders, P):
    api, params, (decode, pick), (one, one_pick) = ladders
    assert decode.widths == serve.PREFILL_WIDTHS and one.widths == (1,)
    prompts = _prompts(api, P)
    runs = [serve.greedy_generate(d, p, params, prompts,
                                  api.init_cache(api.cfg, B, SLAB), N)
            for d, p in ((decode, pick), (one, one_pick))]
    chunked, stepped = [np.asarray(r.generated) for r in runs]
    np.testing.assert_array_equal(chunked, stepped)
    a, b = [np.asarray(r.logits, np.float32) for r in runs]
    # bfloat16 keeps 8 significant bits: a few roundings of the largest
    # logit's size, in a different order
    np.testing.assert_allclose(a, b, rtol=0, atol=0.02 * np.abs(b).max())


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("start", [0, 5])
def test_chunk_queries_see_no_later_position(arch, start):
    """Teacher-forced through one chunk after ``start`` cached positions:
    changing the chunk's last token changes no earlier position's
    attention output, nor any earlier key or value the step caches."""
    cfg = serve.serving_config(arch, reduced=True)
    api = build_model(cfg)
    params = serve.init_params(api, 1)
    S = 8
    tokens = _prompts(api, start + S, seed=1)
    other = tokens.copy()
    other[:, -1] = (other[:, -1] + 1) % cfg.vocab_size
    step = jax.jit(api.decode_step)

    def fed(toks):
        cache = api.init_cache(cfg, B, 32)
        if start:
            _, cache = step(params, toks[:, :start], cache)
        return step(params, toks[:, start:], cache)[1]

    a, b = fed(tokens), fed(other)
    for name in ("k", "v"):
        ka, kb = np.asarray(a[name]), np.asarray(b[name])
        assert np.array_equal(ka[:, :, :start + S - 1],
                              kb[:, :, :start + S - 1])
        assert not np.array_equal(ka[:, :, start + S - 1],
                                  kb[:, :, start + S - 1])

    p0 = jax.tree.map(lambda x: x[0], params["blocks"])["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model),
                          jnp.bfloat16)
    x2 = x.at[:, -1].set(1.0)
    kv = [jnp.zeros((B, 32, cfg.n_kv_heads, cfg.head_dim_), jnp.bfloat16)] * 2
    out = [np.asarray(L.attention(p0, h, cfg, kv_cache=kv,
                                  cache_index=jnp.int32(start))[0],
                      np.float32) for h in (x, x2)]
    np.testing.assert_array_equal(out[0][:, :-1], out[1][:, :-1])
    assert not np.array_equal(out[0][:, -1], out[1][:, -1])


@pytest.mark.parametrize("S", [64, 128])
def test_serving_moe_drops_no_token(S):
    """A chunk of B x S tokens that all choose the same experts overflows
    the training capacity; the serving capacity gives each token what it
    gets alone."""
    cfg = dataclasses.replace(
        serve.serving_config("deepseek-moe-16b", reduced=True),
        compute_dtype="float32", param_dtype="float32")
    api = build_model(cfg)
    p = jax.tree.map(lambda x: x[0],
                     api.init(jax.random.PRNGKey(3))["blocks"])["moe"]
    x = jnp.broadcast_to(
        jax.random.normal(jax.random.PRNGKey(4), (1, 1, cfg.d_model)),
        (B, S, cfg.d_model))
    assert moe._capacity(B * S, cfg) < B * S
    alone, _ = moe.moe_mlp(p, x[:1, :1], cfg, drop_free=True)
    served, _ = moe.moe_mlp(p, x, cfg, drop_free=True)
    trained, _ = moe.moe_mlp(p, x, cfg)
    np.testing.assert_allclose(np.asarray(served),
                               np.broadcast_to(np.asarray(alone), x.shape),
                               rtol=1e-5, atol=1e-4)   # float32 sums
    assert not np.allclose(np.asarray(trained), np.asarray(served),
                           atol=1e-3)


@pytest.mark.parametrize("n, widths, chunks", [
    (227, serve.PREFILL_WIDTHS, [128, 64, 32, 2, 1]),
    (1, serve.PREFILL_WIDTHS, [1]),
    (256, serve.PREFILL_WIDTHS, [128, 128]),
    (300, serve.PREFILL_WIDTHS, [128, 128, 32, 8, 4]),
    (37, (16, 8, 4, 2, 1), [16, 16, 4, 1]),
    (5, (1,), [1, 1, 1, 1, 1]),
])
def test_prompt_chunks_decompose_the_prompt(n, widths, chunks):
    got = serve.prompt_chunks(n, widths)
    assert [w for _, w in got] == chunks
    assert [t for t, _ in got] == list(np.cumsum([0] + chunks[:-1]))


@pytest.mark.parametrize("P", [37, 130])
def test_chunks_are_counted_by_width(ladders, P):
    api, params, (decode, pick), _ = ladders
    before = _chunk_counts()
    serve.greedy_generate(decode, pick, params, _prompts(api, P),
                          api.init_cache(api.cfg, B, SLAB), N)
    after = _chunk_counts()
    added = {w: after[w] - before.get(w, 0) for w in after
             if after[w] != before.get(w, 0)}
    expect = {}
    for _, w in serve.prompt_chunks(P, serve.PREFILL_WIDTHS):
        expect[w] = expect.get(w, 0) + 1
    assert added == expect


def test_ladder_stops_at_the_slab():
    cfg = serve.serving_config("qwen2.5-3b", reduced=True)
    api = build_model(cfg)
    assert serve.chunk_widths(api.init_cache(cfg, B, 20)) == (16, 8, 4, 2, 1)


def test_recurrent_serve_feeds_one_token_a_step():
    cfg = serve.serving_config("rwkv6-3b", reduced=True)
    api = build_model(cfg)
    params = serve.init_params(api, 0)
    decode, pick = _compile(api, params, slab=16)
    assert decode.widths == (1,)
    before = _chunk_counts()
    run = serve.greedy_generate(decode, pick, params,
                                _prompts(api, 5), api.init_cache(cfg, B, 16),
                                N)
    assert run.generated.shape == (B, N)
    after = _chunk_counts()
    assert after[1] - before.get(1, 0) == 5
    assert {w: v for w, v in after.items() if w != 1} == {
        w: v for w, v in before.items() if w != 1}
