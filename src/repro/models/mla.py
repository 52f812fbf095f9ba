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


def cached_attention(p: Params, x: jax.Array, cfg: ModelConfig,
                     c_kv: jax.Array, k_pe: jax.Array, index: jax.Array):
    """Absorbed attention of the S new positions ``x`` (B, S, d) at
    ``index``, over this layer's latent slab ``c_kv`` (B, T, r) and
    ``k_pe`` (B, T, rope) as it held ``index`` positions, and over the
    new positions themselves, query i seeing positions up to
    ``index + i``.  Returns (out, the new positions' c_kv and k_pe as the
    cache stores them)."""
    B, S, _ = x.shape
    dt = x.dtype
    with jax.named_scope("proj"):
        q_nope, q_pe, new_c, new_pe = _project(p, x, cfg,
                                               index + jnp.arange(S))
        # W_UK folded into the query: a kv_lora_rank-wide query per head
        q_lat = jnp.einsum("bshn,hrn->bshr", q_nope, p["wk_b"].astype(dt))
    with jax.named_scope("kv_cache"):
        new_c, new_pe = new_c.astype(c_kv.dtype), new_pe.astype(k_pe.dtype)
    with jax.named_scope("core"):
        # one softmax over the slab's first ``index`` positions and the
        # chunk's own, each read where it lies
        parts = []
        for ck, kp, seen in (
                (c_kv, k_pe, jnp.arange(c_kv.shape[1])[None, :] < index),
                (new_c, new_pe, jnp.tril(jnp.ones((S, S), bool)))):
            ck, kp = ck.astype(dt), kp.astype(dt)
            s = (L.einsum_f32("bshr,btr->bhst", q_lat, ck)
                 + L.einsum_f32("bshp,btp->bhst", q_pe, kp)) * _scale(cfg)
            parts.append((jnp.where(seen[None, None], s, -jnp.inf), ck))
        m = jnp.maximum(*[jnp.max(s, axis=-1, keepdims=True)
                          for s, _ in parts])
        o_lat, denom = 0.0, 0.0
        for s, ck in parts:
            e = jnp.exp(s - m)
            denom = denom + jnp.sum(e, axis=-1)
            o_lat = o_lat + L.einsum_f32("bhst,btr->bshr", e.astype(dt), ck)
        o_lat = (o_lat / jnp.swapaxes(denom, 1, 2)[..., None]).astype(dt)
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
    reads its layer's latent slab and returns only its S new positions,
    which one write puts into the donated cache after the loop: no
    layer's slab is restacked.  No token is dropped by expert capacity."""
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], tokens, cfg)
    idx = cache["index"]

    def body(h, xs):
        p, layer = xs
        with jax.named_scope("attention"):
            a = L.rmsnorm(p["attn_norm"], h, cfg.norm_eps)
            c_kv, k_pe = (jax.lax.dynamic_index_in_dim(
                cache[k], layer, 0, keepdims=False) for k in ("c_kv", "k_pe"))
            a, new_c, new_pe = cached_attention(p["attn"], a, cfg, c_kv,
                                                k_pe, idx)
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
