"""The DeepSeek-V3 block (``models/mla.py``) at moonlight-16b-a3b's reduced
size on the CPU: the served path (prompt in chunks, then absorbed decode
through the latent cache) against the plain reference's full forward
(``bench/reference/deepseek_v3.py``), one chip's share of the experts
against the uncut layer, the sigmoid router's correction bias, and the
chunk ladder for every kind of cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v3 as ref
from repro.launch import serve
from repro.models import build_model, layers as L, mla, moe
from repro.obs import metrics

ARCH = "moonlight-16b-a3b"
B, P, N = 2, 13, 5          # a prompt of 8 + 4 + 1 chunks, then 4 decodes


def _cfg(**over):
    cfg = serve.serving_config(ARCH, reduced=True)
    return dataclasses.replace(cfg, compute_dtype="float32",
                               param_dtype="float32", **over)


def _ref_cfg(cfg):
    """The reference's configuration keys at ``cfg``'s sizes."""
    out = {k: getattr(cfg, f) for k, f in ref.MODEL_KEYS.items()}
    out.update(hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
               num_hidden_layers=cfg.n_layers, vocab_size=cfg.vocab_size,
               rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
               tie_word_embeddings=cfg.tie_embeddings, q_lora_rank=None,
               moe_layer_freq=1, n_group=1, norm_topk_prob=True,
               n_routed_experts=cfg.experts_held)
    return out


def _params(api, seed=0):
    """Initial weights with a correction bias that changes choices."""
    params = api.init(jax.random.PRNGKey(seed))
    bias = params["blocks"]["moe"]["e_score_correction_bias"]
    params["blocks"]["moe"]["e_score_correction_bias"] = 0.1 * \
        jax.random.normal(jax.random.PRNGKey(seed + 1), bias.shape)
    return params


# float32 everywhere but the cache: the ladder's sums in another order
# (about 2e-6 of the largest logit).  A bfloat16 cache rounds each latent
# to 8 significant bits: 0.7-1.5% of the largest logit at seeds 0-2, as
# long as no near-tie of the router flips an expert, which a rounding can
# do at another seed.
@pytest.mark.parametrize("cache_dtype, tol", [(jnp.float32, 1e-5),
                                              (jnp.bfloat16, 0.03)])
def test_served_logits_match_reference(cache_dtype, tol):
    cfg = _cfg(n_experts_held=4)
    api = build_model(cfg)
    params = _params(api)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, P)).astype(np.int32)
    cache = mla.init_cache(cfg, B, 32, dtype=cache_dtype)
    decode, pick = serve.compile_greedy(
        jax.jit(api.decode_step, donate_argnums=(2,)), params,
        prompts[:, :1], cache, cfg.vocab_size)
    assert decode.widths == (32, 16, 8, 4, 2, 1)
    run = serve.greedy_generate(decode, pick, params, prompts,
                                mla.init_cache(cfg, B, 32, cache_dtype), N)
    tokens = np.concatenate([prompts, np.asarray(run.generated)[:, :-1]], 1)
    rows = jnp.tile(jnp.arange(P - 1, P - 1 + N), (B, 1))
    want = ref.forward(params, jnp.asarray(tokens), rows, _ref_cfg(cfg))
    got = np.asarray(run.logits)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, np.asarray(want), rtol=0,
                               atol=tol * scale)
    gauge = metrics.snapshot()["serve_cache_bytes"]["series"]
    assert {"labels": {"layout": "latent"},
            "value": sum(a.nbytes for a in jax.tree.leaves(cache))} in gauge


def test_forward_matches_reference():
    cfg = _cfg(n_experts_held=4)
    api = build_model(cfg)
    params = _params(api, seed=3)
    # 8 tokens: under the training capacity's floor, so none is dropped
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (1, 8)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = api.logits_fn(params, {"tokens": tokens})
    want = ref.forward(params, tokens, jnp.arange(8)[None], _ref_cfg(cfg))
    # float32 both, summed in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_chunk_sees_no_later_position():
    """Changing a chunk's last token changes no earlier position's output
    nor any latent the step caches before it."""
    cfg = serve.serving_config(ARCH, reduced=True)
    api = build_model(cfg)
    params = serve.init_params(api, 1)
    start, S = 5, 8
    tokens = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (B, start + S)).astype(np.int32)
    other = tokens.copy()
    other[:, -1] = (other[:, -1] + 1) % cfg.vocab_size
    step = jax.jit(api.decode_step)

    def fed(toks):
        _, cache = step(params, toks[:, :start], api.init_cache(cfg, B, 32))
        return step(params, toks[:, start:], cache)[1]

    a, b = fed(tokens), fed(other)
    for name in ("c_kv", "k_pe"):
        ka, kb = np.asarray(a[name]), np.asarray(b[name])
        last = start + S - 1
        assert np.array_equal(ka[:, :, :last], kb[:, :, :last])
        assert not np.array_equal(ka[:, :, last], kb[:, :, last])


def _moe_params(cfg, seed):
    p = jax.tree.map(lambda x: x[0],
                     _params(build_model(cfg), seed)["blocks"])["moe"]
    return p


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight experts split four ways: each share routes over all eight
    and computes its own two (its experts first, as a chip holds them);
    with the shared experts counted once, the shares add up to the
    reference's layer with every expert."""
    cfg = _cfg()
    whole = _moe_params(cfg, 5)
    E, n = cfg.n_experts, 2
    h = jax.random.normal(jax.random.PRNGKey(6), (B, 7, cfg.d_model))
    share_cfg = dataclasses.replace(cfg, n_experts_held=n)
    shares = []
    for j in range(E // n):
        order = np.roll(np.arange(E), -j * n)   # this share's experts first
        p = dict(whole, router=whole["router"][:, order],
                 e_score_correction_bias=whole["e_score_correction_bias"][
                     order],
                 **{k: whole[k][j * n:(j + 1) * n]
                    for k in ("w_gate", "w_up", "w_down")})
        y, _ = moe.moe_mlp(p, h, share_cfg, drop_free=True)
        shares.append(np.asarray(y))
    shared = np.asarray(L.mlp(whole["shared"], h, cfg))
    total = sum(shares) - (len(shares) - 1) * shared

    rc = _ref_cfg(cfg)
    gates = ref.router_gates(h, whole["router"],
                             whole["e_score_correction_bias"], rc)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(whole, h, gates, ref.C.EXACT) + shared
    # float32 sums in another order: a few units in the last place of the
    # largest value
    np.testing.assert_allclose(total, np.asarray(want), rtol=0,
                               atol=1e-6 * float(jnp.abs(want).max()))
    assert not np.allclose(shares[0], shares[1], atol=1e-3)


def test_correction_bias_steers_choice_not_gates():
    cfg = _cfg()
    p = _moe_params(cfg, 7)
    E, k = cfg.n_experts, cfg.experts_per_token
    xf = jax.random.normal(jax.random.PRNGKey(8), (3, cfg.d_model))
    scores = jax.nn.sigmoid(xf @ p["router"])

    def route(bias):
        g, idx, _ = moe._router(xf, dict(p, e_score_correction_bias=bias),
                                cfg)
        return np.asarray(g), np.sort(np.asarray(idx), -1)

    zero = jnp.zeros((E,))
    g0, i0 = route(zero)
    np.testing.assert_array_equal(
        i0, np.sort(np.asarray(jax.lax.top_k(scores, k)[1]), -1))
    # an expert no token chose, lifted past all the others
    low = min(set(range(E)) - set(i0.ravel().tolist()))
    g1, i1 = route(zero.at[low].set(10.0))
    assert (i1 == low).any(-1).all()
    # its gate is its unbiased score, renormalised and scaled
    chosen = np.take_along_axis(np.asarray(scores), i1, -1)
    np.testing.assert_allclose(
        np.sort(g1, -1), np.sort(chosen / chosen.sum(-1, keepdims=True)
                                 * cfg.routed_scaling, -1), rtol=1e-6)
    # a bias that keeps every choice keeps every gate
    g2, i2 = route(zero.at[low].set(-10.0))
    np.testing.assert_array_equal(i2, i0)
    np.testing.assert_array_equal(g2, g0)


@pytest.mark.parametrize("arch, slab, widths", [
    (ARCH, 20, (16, 8, 4, 2, 1)),
    (ARCH, 8192, serve.PREFILL_WIDTHS),
    ("qwen2.5-3b", 20, (16, 8, 4, 2, 1)),
    ("deepseek-moe-16b", 1024, serve.PREFILL_WIDTHS),
    ("rwkv6-3b", 64, (1,)),
    ("zamba2-1.2b", 64, (1,)),
    ("seamless-m4t-medium", 64, (1,)),
])
def test_chunk_widths_by_cache(arch, slab, widths):
    cfg = serve.serving_config(arch, reduced=True)
    cache = jax.eval_shape(
        lambda: build_model(cfg).init_cache(cfg, B, slab))
    assert serve.chunk_widths(cache) == widths


# a slab that is no multiple of the block, so the last block starts early
SLAB = 2 * mla.BLOCK + 200


@pytest.fixture(scope="module")
def core():
    """(cfg, layer 0's attention weights, the jitted cached_attention over
    a one-layer latent cache) in float32."""
    cfg = _cfg()
    p = jax.tree.map(lambda x: x[0],
                     build_model(cfg).init(jax.random.PRNGKey(9))["blocks"])
    return cfg, p["attn"], jax.jit(
        lambda p, x, cache: mla.cached_attention(p, x, cfg, cache, 0))


def _masked_whole_slab(p, x, cfg, c_kv, k_pe, index):
    """The absorbed attention with one softmax over the whole slab, masked
    at ``index``, and the chunk's own positions: what the blocked core
    must equal."""
    S = x.shape[1]
    q_nope, q_pe, new_c, new_pe = mla._project(p, x, cfg,
                                               index + jnp.arange(S))
    q_lat = jnp.einsum("bshn,hrn->bshr", q_nope, p["wk_b"])
    ck = jnp.concatenate([c_kv, new_c], 1)
    kp = jnp.concatenate([k_pe, new_pe], 1)
    s = (jnp.einsum("bshr,btr->bhst", q_lat, ck)
         + jnp.einsum("bshp,btp->bhst", q_pe, kp)) * mla._scale(cfg)
    seen = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(c_kv.shape[1]) < index,
                         (S, c_kv.shape[1])),
        jnp.tril(jnp.ones((S, S), bool))], 1)
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,btr->bshr", w, ck)
    return mla._out(p, jnp.einsum("bshr,hrv->bshv", o, p["wv_b"]))


def _slab(cfg, seed):
    c = jax.random.normal(jax.random.PRNGKey(seed),
                          (1, B, SLAB, cfg.kv_lora_rank))
    pe = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (1, B, SLAB, cfg.qk_rope_head_dim))
    return c, pe


INDEXES = [0, 1, mla.BLOCK - 1, mla.BLOCK, mla.BLOCK + 1, "T-S"]


@pytest.mark.parametrize("S", [1, 8])
@pytest.mark.parametrize("index", INDEXES)
def test_blocked_core_matches_masked_whole_slab(core, index, S):
    cfg, p, attend = core
    index = SLAB - S if index == "T-S" else index
    c, pe = _slab(cfg, 10)
    x = jax.random.normal(jax.random.PRNGKey(12), (B, S, cfg.d_model))
    got, new_c, new_pe = attend(p, x, {"c_kv": c, "k_pe": pe,
                                       "index": jnp.int32(index)})
    with jax.default_matmul_precision("highest"):
        want = _masked_whole_slab(p, x, cfg, c[0], pe[0], index)
    # float32 both, the softmax summed block by block
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert new_c.shape == (B, S, cfg.kv_lora_rank)


@pytest.mark.parametrize("S", [1, 8])
@pytest.mark.parametrize("index", INDEXES)
def test_blocked_core_reads_no_block_past_the_filled_length(core, index, S):
    """NaN in every position from the first block past ``index`` on, and
    large values in the unfilled tail of the last block it reads, change
    nothing: those blocks are never read, that tail is masked."""
    cfg, p, attend = core
    index = SLAB - S if index == "T-S" else index
    width, blocks = mla.slab_blocks(index, SLAB)
    c, pe = _slab(cfg, 20)
    x = jax.random.normal(jax.random.PRNGKey(22), (B, S, cfg.d_model))
    clean = attend(p, x, {"c_kv": c, "k_pe": pe, "index": jnp.int32(index)})
    end = width * blocks
    dirty = {k: a.at[:, :, index:end].set(1e30).at[:, :, end:].set(jnp.nan)
             for k, a in (("c_kv", c), ("k_pe", pe))}
    got = attend(p, x, dict(dirty, index=jnp.int32(index)))
    assert np.isfinite(np.asarray(got[0])).all()
    for a, b in zip(got, clean):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("index, blocks", [
    (0, 0), (1, 1), (mla.BLOCK, 1), (mla.BLOCK + 1, 2), (SLAB, 3)])
def test_slab_blocks(index, blocks):
    assert mla.slab_blocks(index, SLAB) == (mla.BLOCK, blocks)
    assert mla.slab_blocks(index, 32) == (32, -(-index // 32))
