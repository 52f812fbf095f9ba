"""DeepSeek-V3 decoder with multi-head latent attention (moonlight-16b-a3b).

Latent attention as DeepSeek-V2 §2.1 (arXiv:2405.04434) gives it and
DeepSeek-V3's published ``modeling_deepseek.py`` runs it, with no query
low-rank:

* ``q = x W_q``, split per head into ``q_nope`` (``qk_nope_head_dim``)
  and ``q_pe`` (``qk_rope_head_dim``);
* ``[c_kv, k_pe] = x W_kva``, then ``c_kv = RMSNorm(c_kv)`` (the published
  code's default eps, 1e-6);
* RoPE on ``q_pe`` and on ``k_pe``, one rope key that all heads share;
* per head ``k_nope = c_kv W_UK`` and ``v = c_kv W_UV``; scores
  ``q_nope.k_nope + q_pe.k_pe`` at scale ``(nope + rope) ** -0.5``.

RoPE rotates the two halves of ``q_pe`` and ``k_pe`` as pairs
(``layers.apply_rope``), where the published code rotates interleaved
pairs: a fixed permutation of the rope columns of ``W_q`` and ``W_kva``.

The same attention in two forms:

* expanded (``forward``, ``loss_fn``): keys and values per head from the
  latent, then causal attention as ``layers`` computes it;
* absorbed (``decode_step``): ``W_UK`` folded into the query and ``W_UV``
  into the output, so the step reads only the latent cache, ``c_kv`` and
  the rotated ``k_pe`` (``kv_lora_rank + qk_rope_head_dim`` values a layer
  and position), in its dtype with float32 accumulation.

Blocks: ``n_dense_layers`` leading blocks with a dense MLP of width
``d_ff``, then the MoE blocks of ``models/moe.py`` (router over all
``n_experts``, the ``experts_held`` here, shared experts).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import constrain
from . import layers as L
from . import moe
from .param import LeafSpec, stack_specs

Params = Dict[str, Any]
LATENT_NORM_EPS = 1e-6
# slab positions the absorbed core reads at a time; it visits only the
# blocks that hold a filled position (:func:`slab_blocks`)
BLOCK = 512
# the running max before any position: finite, so that a step with no
# filled position (a prompt's first chunk) gives no NaN
NEG_INF = -1e30


# ------------------------------------------------------------------- spec
def attention_spec(cfg: ModelConfig) -> Params:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": LeafSpec((d, H, dn + dr), ("embed", "q_heads", "head_dim")),
        "wkv_a": LeafSpec((d, r + dr), ("embed", None)),
        "kv_norm": {"scale": LeafSpec((r,), (None,), init="ones")},
        "wk_b": LeafSpec((H, r, dn), ("q_heads", None, "head_dim")),
        "wv_b": LeafSpec((H, r, dv), ("q_heads", None, "head_dim")),
        "wo": LeafSpec((H, dv, d), ("q_heads", "head_dim", "embed")),
    }


def block_spec(cfg: ModelConfig, dense: bool) -> Params:
    spec = {"attn_norm": L.rmsnorm_spec(cfg.d_model),
            "attn": attention_spec(cfg),
            "mlp_norm": L.rmsnorm_spec(cfg.d_model)}
    if dense:
        spec["mlp"] = L.mlp_spec(cfg)
    else:
        spec["moe"] = moe.moe_mlp_spec(cfg)
    return spec


def mla_spec(cfg: ModelConfig) -> Params:
    spec: Params = {
        "embed": L.embedding_spec(cfg),
        "blocks": stack_specs(block_spec(cfg, dense=False),
                              cfg.n_layers - cfg.n_dense_layers),
        "final_norm": L.rmsnorm_spec(cfg.d_model),
        "lm_head": L.lm_head_spec(cfg),
    }
    if cfg.n_dense_layers:
        spec["dense_blocks"] = stack_specs(block_spec(cfg, dense=True),
                                           cfg.n_dense_layers)
    return spec


# -------------------------------------------------------------- attention
def _project(p: Params, x: jax.Array, cfg: ModelConfig,
             positions: jax.Array):
    """-> q_nope (B,S,H,nope), rotated q_pe (B,S,H,rope), normed c_kv
    (B,S,r) and rotated k_pe (B,S,rope)."""
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q = constrain(q, ("batch", "seq", "q_heads", "head_dim"))
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c_kv = L.rmsnorm(p["kv_norm"], kv[..., :r], LATENT_NORM_EPS)
    cos, sin = L.rope_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta,
                                  positions)
    q_pe = L.apply_rope(q[..., dn:], cos, sin)
    k_pe = L.apply_rope(kv[..., None, r:], cos, sin)[:, :, 0]
    return q[..., :dn], q_pe, c_kv, k_pe


def _scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _out(p: Params, o: jax.Array) -> jax.Array:
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(o.dtype))
    return constrain(out, ("batch", "seq", "embed"))


def attention(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Causal self-attention over ``x`` (B, S, d) in the expanded form."""
    B, S, _ = x.shape
    with jax.named_scope("proj"):
        q_nope, q_pe, c_kv, k_pe = _project(p, x, cfg, jnp.arange(S))
        k_nope = jnp.einsum("bsr,hrn->bshn", c_kv, p["wk_b"].astype(x.dtype))
        v = jnp.einsum("bsr,hrv->bshv", c_kv, p["wv_b"].astype(x.dtype))
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe[:, :, None], k_pe.shape[:2] + (cfg.n_heads,
                                                k_pe.shape[-1]))], axis=-1)
    with jax.named_scope("core"):
        o = L._sdpa_xla(q, k, v, True, _scale(cfg))
    with jax.named_scope("proj"):
        return _out(p, o)


def slab_blocks(index, slab: int):
    """(block width, blocks) of the absorbed core over a slab of ``slab``
    positions that holds ``index``: blocks of :data:`BLOCK` positions (the
    whole slab where that is shorter), those that start below ``index``.
    ``index`` is the step's traced count or a host integer."""
    width = min(BLOCK, slab)
    return width, (index + width - 1) // width


def cached_attention(p: Params, x: jax.Array, cfg: ModelConfig,
                     cache: Dict[str, jax.Array], layer: jax.Array):
    """Absorbed attention of the S new positions ``x`` (B, S, d) at
    ``index = cache["index"]``, over layer ``layer``'s latent slab of the
    latent cache (:func:`init_cache`) as it held ``index`` positions, and
    over the new positions themselves, query i seeing positions up to
    ``index + i``.  The core reads the slab a block at a time straight
    from the cache, only the blocks below ``index``
    (:func:`slab_blocks`).  Returns (out, the new positions' c_kv and k_pe
    as the cache stores them)."""
    B, S, _ = x.shape
    dt, index = x.dtype, cache["index"]
    c_kv, k_pe = cache["c_kv"], cache["k_pe"]
    with jax.named_scope("proj"):
        q_nope, q_pe, new_c, new_pe = _project(p, x, cfg,
                                               index + jnp.arange(S))
        # W_UK folded into the query: a kv_lora_rank-wide query per head
        q_lat = jnp.einsum("bshn,hrn->bshr", q_nope, p["wk_b"].astype(dt))
    with jax.named_scope("kv_cache"):
        new_c, new_pe = new_c.astype(c_kv.dtype), new_pe.astype(k_pe.dtype)
    with jax.named_scope("core"):
        # one online softmax over the slab's first ``index`` positions,
        # block by block, and then the chunk's own, each read where it
        # lies: no block past ``index`` is read
        T, f32 = c_kv.shape[2], jnp.float32
        width, n = slab_blocks(index, T)

        def fold(state, ck, kp, seen):
            m, denom, acc = state
            ck, kp = ck.astype(dt), kp.astype(dt)
            s = (L.einsum_f32("bshr,btr->bhst", q_lat, ck)
                 + L.einsum_f32("bshp,btp->bhst", q_pe, kp)) * _scale(cfg)
            s = jnp.where(seen, s, NEG_INF)
            top = jnp.maximum(m, jnp.max(s, axis=-1))
            e, rescale = jnp.exp(s - top[..., None]), jnp.exp(m - top)
            return (top, denom * rescale + jnp.sum(e, axis=-1),
                    acc * rescale[..., None]
                    + L.einsum_f32("bhst,btr->bhsr", e.astype(dt), ck))

        def block(j, state):
            # the last block of a slab that is no multiple of ``width``
            # starts early; its positions before ``j * width`` are masked,
            # as the block before counted them
            start = jnp.minimum(j * width, T - width)
            ck, kp = (jax.lax.dynamic_slice(
                a, (layer, 0, start, 0), (1, B, width, a.shape[3]))[0]
                for a in (c_kv, k_pe))
            pos = start + jnp.arange(width)
            return fold(state, ck, kp, (pos >= j * width) & (pos < index))

        H, r = q_lat.shape[2], q_lat.shape[3]
        state = (jnp.full((B, H, S), NEG_INF, f32), jnp.zeros((B, H, S), f32),
                 jnp.zeros((B, H, S, r), f32))
        state = jax.lax.fori_loop(0, n, block, state)
        _, denom, acc = fold(state, new_c, new_pe,
                             jnp.tril(jnp.ones((S, S), bool)))
        o_lat = jnp.swapaxes(acc / denom[..., None], 1, 2).astype(dt)
    with jax.named_scope("proj"):
        # W_UV folded into the output
        o = jnp.einsum("bshr,hrv->bshv", o_lat, p["wv_b"].astype(dt))
        return _out(p, o), new_c, new_pe


# ----------------------------------------------------------------- blocks
def _ffn(p: Params, h: jax.Array, cfg: ModelConfig, drop_free: bool
         ) -> Tuple[jax.Array, jax.Array]:
    if "mlp" in p:
        return L.mlp(p["mlp"], h, cfg), jnp.zeros((), jnp.float32)
    return moe.moe_mlp(p["moe"], h, cfg, drop_free=drop_free)


def _block(p: Params, x: jax.Array, cfg: ModelConfig):
    with jax.named_scope("attention"):
        h = L.rmsnorm(p["attn_norm"], x, cfg.norm_eps)
        x = x + attention(p["attn"], h, cfg)
    with jax.named_scope("ffn"):
        h = L.rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
        y, aux = _ffn(p, h, cfg, drop_free=False)
    return x + y, aux


def _stacks(params: Params, cfg: ModelConfig):
    """(stacked blocks, their layer numbers), in layer order: the dense
    blocks, then the MoE blocks."""
    k = cfg.n_dense_layers
    out = [(params["dense_blocks"], jnp.arange(k))] if k else []
    return out + [(params["blocks"], jnp.arange(k, cfg.n_layers))]


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """-> (logits, total_aux_loss)."""
    x = L.embed(params["embed"], tokens, cfg)

    def body(carry, layer_params):
        h, aux = carry
        h, a = _block(layer_params, h, cfg)
        return (h, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    carry = (x, jnp.zeros((), jnp.float32))
    for blocks, _ in _stacks(params, cfg):
        carry, _ = jax.lax.scan(body, carry, blocks)
    x, aux = carry
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_head(params.get("lm_head", {}), x, cfg,
                       embed_params=params["embed"])
    return logits, aux


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(params, batch["tokens"], cfg)
    xent = L.softmax_xent(logits, batch["labels"])
    return xent + aux, {"loss": xent, "aux_loss": aux}


# ----------------------------------------------------------------- serving
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """The latent cache: per layer and position ``c_kv`` and the rotated
    ``k_pe``, and the one position count of the batch."""
    lead = (cfg.n_layers, batch, max_len)
    return {
        "c_kv": jnp.zeros(lead + (cfg.kv_lora_rank,), dtype),
        "k_pe": jnp.zeros(lead + (cfg.qk_rope_head_dim,), dtype),
        "index": jnp.zeros((), jnp.int32),
    }


def cache_logical_axes() -> Dict[str, Tuple[Optional[str], ...]]:
    ax = ("layers", "batch", "kv_seq", None)
    return {"c_kv": ax, "k_pe": ax, "index": ()}


def decode_step(params: Params, tokens: jax.Array,
                cache: Dict[str, jax.Array], cfg: ModelConfig
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One serving step under the dense step's named scopes: S = 1
    decodes, S > 1 feeds a prompt chunk (as ``transformer.decode_step``).

    The dense blocks run first, then the scan over the MoE blocks; each
    reads the blocks of its layer's latent slab that hold positions
    (``cached_attention``) and returns only its S new positions,
    which one write puts into the donated cache after the loop: no
    layer's slab is restacked.  No token is dropped by expert capacity."""
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], tokens, cfg)
    idx = cache["index"]

    def body(h, xs):
        p, layer = xs
        with jax.named_scope("attention"):
            a = L.rmsnorm(p["attn_norm"], h, cfg.norm_eps)
            a, new_c, new_pe = cached_attention(p["attn"], a, cfg, cache,
                                                layer)
        h = h + a
        with jax.named_scope("ffn"):
            a = L.rmsnorm(p["mlp_norm"], h, cfg.norm_eps)
            y, _ = _ffn(p, a, cfg, drop_free=True)
        return h + y, (new_c, new_pe)

    new = []
    with jax.named_scope("layer_loop"):
        for blocks, layers in _stacks(params, cfg):
            x, ys = jax.lax.scan(body, x, (blocks, layers))
            new.append(ys)
        with jax.named_scope("attention"), jax.named_scope("kv_cache"):
            c_kv, k_pe = (jax.lax.dynamic_update_slice_in_dim(
                cache[k], jnp.concatenate([ys[i] for ys in new]), idx,
                axis=2) for i, k in enumerate(("c_kv", "k_pe")))
            idx = idx + tokens.shape[1]
    with jax.named_scope("lm_head"):
        if tokens.shape[1] > 1:
            x = x[:, -1:]
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = L.lm_head(params.get("lm_head", {}), x, cfg,
                           embed_params=params["embed"])
    return logits, {"c_kv": c_kv, "k_pe": k_pe, "index": idx}
