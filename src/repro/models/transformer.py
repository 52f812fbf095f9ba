"""Dense decoder-only transformer (gemma-7b, qwen2.5-3b, llama3-405b,
deepseek-67b; backbone of internvl2 and the MoE variants).

Pre-norm blocks, GQA + RoPE attention, SwiGLU/GeGLU MLP.  Layers are stacked
and executed with ``jax.lax.scan`` (+ optional remat) so HLO size and compile
time are depth-independent — essential for the 126-layer llama3-405b dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import constrain
from . import layers as L
from .param import LeafSpec, stack_specs

Params = Dict[str, Any]


def block_spec(cfg: ModelConfig) -> Params:
    return {
        "attn_norm": L.rmsnorm_spec(cfg.d_model),
        "attn": L.attention_spec(cfg),
        "mlp_norm": L.rmsnorm_spec(cfg.d_model),
        "mlp": L.mlp_spec(cfg),
    }


def transformer_spec(cfg: ModelConfig) -> Params:
    spec: Params = {
        "embed": L.embedding_spec(cfg),
        "blocks": stack_specs(block_spec(cfg), cfg.n_layers),
        "final_norm": L.rmsnorm_spec(cfg.d_model),
    }
    spec.update({"lm_head": L.lm_head_spec(cfg)})
    return spec


def block_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                kv_cache=None, cache_index=None, causal: bool = True):
    with jax.named_scope("attention"):
        h = L.rmsnorm(p["attn_norm"], x, cfg.norm_eps)
        attn_out, new_cache = L.attention(p["attn"], h, cfg, causal=causal,
                                          kv_cache=kv_cache,
                                          cache_index=cache_index)
    x = x + attn_out
    with jax.named_scope("ffn"):
        h = L.rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
        x = x + L.mlp(p["mlp"], h, cfg)
    return x, new_cache


def _scan_blocks(params: Params, x: jax.Array, cfg: ModelConfig,
                 causal: bool = True) -> jax.Array:
    def body(h, layer_params):
        h2, _ = block_apply(layer_params, h, cfg, causal=causal)
        return h2, None

    if cfg.remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return x


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig
            ) -> jax.Array:
    """tokens: (B, S) -> logits (B, S, V)."""
    x = L.embed(params["embed"], tokens, cfg)
    if cfg.name.startswith("gemma"):
        x = x * (cfg.d_model ** 0.5)        # gemma embedding scaling
    x = _scan_blocks(params, x, cfg)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.lm_head(params.get("lm_head", {}), x, cfg,
                     embed_params=params["embed"])


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    tokens = batch["tokens"]
    B, S = tokens.shape
    if B * S * cfg.padded_vocab > L.FUSED_XENT_THRESHOLD:
        # fused chunked head+loss: never materializes (tokens x vocab) f32
        x = L.embed(params["embed"], tokens, cfg)
        if cfg.name.startswith("gemma"):
            x = x * (cfg.d_model ** 0.5)
        x = _scan_blocks(params, x, cfg)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            loss = L.fused_head_xent(x, params["embed"]["table"],
                                     batch["labels"], w_is_vd=True)
        else:
            loss = L.fused_head_xent(x, params["lm_head"]["w"],
                                     batch["labels"])
        return loss, {"loss": loss}
    logits = forward(params, tokens, cfg)
    loss = L.softmax_xent(logits, batch["labels"])
    return loss, {"loss": loss}


# ----------------------------------------------------------------- serving
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim_)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "index": jnp.zeros((), jnp.int32),
    }


def cache_logical_axes() -> Dict[str, Tuple[Optional[str], ...]]:
    ax = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": ax, "v": ax, "index": ()}


def decode_step(params: Params, tokens: jax.Array,
                cache: Dict[str, jax.Array], cfg: ModelConfig
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One serving step.  tokens: (B, S); cache k/v: (L, B, T, nkv, hd).

    S = 1 decodes one token; S > 1 feeds a chunk of the prompt, causal
    within the chunk, and returns the logits of its last position only, so
    the logits are (B, 1, V) whatever S is.

    Its named scopes (``embed``, ``layer_loop``, ``lm_head``; within each
    block ``attention`` and ``ffn``) are the device trace's stable names
    for the parts of the step (DESIGN_OBS.md)."""
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], tokens, cfg)
        if cfg.name.startswith("gemma"):
            x = x * (cfg.d_model ** 0.5)
    idx = cache["index"]

    def body(h, xs):
        layer_params, ck, cv = xs
        h2, new_kv = block_apply(layer_params, h, cfg,
                                 kv_cache=(ck, cv), cache_index=idx)
        return h2, new_kv

    with jax.named_scope("layer_loop"):
        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"]))
    with jax.named_scope("lm_head"):
        if tokens.shape[1] > 1:
            x = x[:, -1:]
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = L.lm_head(params.get("lm_head", {}), x, cfg,
                           embed_params=params["embed"])
    new_cache = {"k": new_k, "v": new_v, "index": idx + tokens.shape[1]}
    return logits, new_cache
