"""Mixture-of-Experts transformer (qwen3-moe-30b-a3b, deepseek-moe-16b).

Sort-based capacity dispatch (O(T*k) memory — no T x E x cap one-hots, so the
32k-prefill dry-run fits):

1. router softmax -> top-k experts/weights per token;
2. flatten (token, slot) pairs, sort by expert id;
3. rank-in-expert via sorted-position minus group offset; drop beyond
   capacity;
4. scatter into the dense (E, cap, d) buffer, run the grouped expert FFN
   (``kernels.moe_gmm`` on the pallas path, einsum on the xla path),
   scatter-add back with the gate weights.

DeepSeekMoE details honoured: ``n_shared_experts`` dense experts always on
(fine-grained experts with small ``moe_d_ff``), plus the standard
load-balancing auxiliary loss.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import constrain
from . import layers as L
from .param import LeafSpec, stack_specs

Params = Dict[str, Any]


def moe_mlp_spec(cfg: ModelConfig) -> Params:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    spec: Params = {
        "router": LeafSpec((d, E), ("embed", "experts")),
        "w_gate": LeafSpec((E, d, f), ("experts", "embed", "ffn")),
        "w_up": LeafSpec((E, d, f), ("experts", "embed", "ffn")),
        "w_down": LeafSpec((E, f, d), ("experts", "ffn", "embed")),
    }
    if cfg.n_shared_experts:
        spec["shared"] = L.mlp_spec(cfg, d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return spec


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    cap = int(math.ceil(tokens * cfg.experts_per_token * cfg.capacity_factor
                        / cfg.n_experts))
    return max(8, -(-cap // 8) * 8)      # round up to a multiple of 8


def _dispatch_ffn_combine(xf: jax.Array, p_gate, p_up, p_down,
                          gate_vals: jax.Array, expert_idx: jax.Array,
                          cfg: ModelConfig, e_lo, n_local: int,
                          cap: int) -> jax.Array:
    """Sort-based dispatch -> grouped FFN -> weighted combine, for the expert
    slice ``[e_lo, e_lo + n_local)`` over local tokens ``xf`` (T, d).

    Runs unchanged in two regimes: whole-mesh (e_lo=0, n_local=E) and inside
    the shard_map expert-parallel path (each model-rank owns E/TP experts and
    produces a partial sum over its slice).
    """
    T, d = xf.shape
    k = expert_idx.shape[-1]
    e_flat = expert_idx.reshape(T * k)
    w_flat = gate_vals.reshape(T * k)
    tok_flat = jnp.arange(T * k, dtype=jnp.int32) // k
    local = e_flat - e_lo                                     # local slot id
    in_range = (local >= 0) & (local < n_local)
    local_c = jnp.where(in_range, local, n_local)             # park OOR at end
    order = jnp.argsort(local_c)                              # stable
    se = local_c[order]
    st = tok_flat[order]
    sw = w_flat[order]
    counts = jnp.bincount(local_c, length=n_local + 1)[:n_local]
    starts = jnp.cumsum(counts) - counts                      # (n_local,)
    se_c = jnp.minimum(se, n_local - 1)
    rank = jnp.arange(T * k, dtype=jnp.int32) - starts[se_c]
    keep = (se < n_local) & (rank >= 0) & (rank < cap)
    rank_c = jnp.where(keep, rank, 0)

    xe = jnp.zeros((n_local, cap, d), xf.dtype)
    xe = xe.at[se_c, rank_c].add(
        jnp.where(keep[:, None], xf[st], 0).astype(xf.dtype))

    act = jax.nn.gelu if cfg.mlp_activation == "gelu" else jax.nn.silu
    if cfg.kernels == "pallas":
        from repro.kernels import ops
        g = ops.grouped_matmul(xe, p_gate.astype(xf.dtype))
        u = ops.grouped_matmul(xe, p_up.astype(xf.dtype))
        h = act(g) * u
        ye = ops.grouped_matmul(h, p_down.astype(xf.dtype))
    else:
        g = jnp.einsum("ecd,edf->ecf", xe, p_gate.astype(xf.dtype))
        u = jnp.einsum("ecd,edf->ecf", xe, p_up.astype(xf.dtype))
        h = act(g) * u
        ye = jnp.einsum("ecf,efd->ecd", h, p_down.astype(xf.dtype))

    gathered = ye[se_c, rank_c] * jnp.where(keep, sw, 0.0)[:, None
                                                           ].astype(xf.dtype)
    return jnp.zeros((T, d), xf.dtype).at[st].add(gathered)


def _router(xf: jax.Array, router_w: jax.Array, cfg: ModelConfig):
    logits = jnp.einsum("td,de->te", xf, router_w.astype(xf.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    # load-balancing auxiliary loss (Switch-style)
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32),
                          axis=1), axis=0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_weight
    return gate_vals, expert_idx, aux


def _ep_axes() -> Tuple[Optional[Tuple[str, ...]], Optional[str]]:
    """(token mesh axes, expert mesh axis) from the active plan, if the mesh
    context makes the shard_map expert-parallel path applicable."""
    from repro.parallel import sharding as SH
    plan, mesh = SH._CTX.plan, SH._CTX.mesh
    if plan is None or mesh is None:
        return None, None
    e_ax = plan.mesh_axes("experts")
    if not isinstance(e_ax, str) or e_ax not in mesh.shape:
        return None, None
    b_ax = plan.mesh_axes("batch")
    if b_ax is None:
        b_axes: Tuple[str, ...] = ()
    else:
        b_axes = (b_ax,) if isinstance(b_ax, str) else tuple(
            a for a in b_ax if a in mesh.shape)
    return b_axes, e_ax


def moe_mlp(p: Params, x: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    Two execution paths:
    * **shard_map expert-parallel** (active when the current ShardingPlan maps
      'experts' to a mesh axis): tokens stay local to their data shard,
      each model-rank runs only its E/TP expert slice and the partial outputs
      are psum'd over the expert axis — no data-dependent scatter ever
      crosses a shard boundary (GSPMD cannot shard those; see DESIGN.md S8).
    * **single-shard** fallback (tests, CPU smoke): same dispatch over all E.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.parallel import sharding as SH

    B, S, d = x.shape
    b_axes, e_ax = _ep_axes()
    mesh = SH._CTX.mesh
    if e_ax is not None and cfg.n_experts % mesh.shape[e_ax] == 0 \
            and B % max(1, math.prod(mesh.shape[a] for a in b_axes)) == 0:
        ep = mesh.shape[e_ax]
        n_local = cfg.n_experts // ep
        bspec = tuple(b_axes) if len(b_axes) > 1 else (
            b_axes[0] if b_axes else None)

        def local_moe(xl, router_w, wg, wu, wd):
            Bl, Sl, _ = xl.shape
            xf = xl.reshape(Bl * Sl, d)
            gate_vals, expert_idx, aux = _router(xf, router_w, cfg)
            e_lo = jax.lax.axis_index(e_ax) * n_local
            cap = _capacity(Bl * Sl, cfg)
            yf = _dispatch_ffn_combine(xf, wg, wu, wd, gate_vals,
                                       expert_idx, cfg, e_lo, n_local, cap)
            yf = jax.lax.psum(yf, e_ax)
            aux = jax.lax.pmean(aux, e_ax)
            if b_axes:
                aux = jax.lax.pmean(aux, b_axes)
            return yf.reshape(Bl, Sl, d), aux

        y, aux = shard_map(
            local_moe, mesh=mesh,
            in_specs=(P(bspec, None, None), P(None, None),
                      P(e_ax, None, None), P(e_ax, None, None),
                      P(e_ax, None, None)),
            out_specs=(P(bspec, None, None), P()),
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        xf = x.reshape(B * S, d)
        gate_vals, expert_idx, aux = _router(xf, p["router"], cfg)
        cap = _capacity(B * S, cfg)
        yf = _dispatch_ffn_combine(xf, p["w_gate"], p["w_up"], p["w_down"],
                                   gate_vals, expert_idx, cfg, 0,
                                   cfg.n_experts, cap)
        y = yf.reshape(B, S, d)

    if "shared" in p:
        y = y + L.mlp(p["shared"], x, cfg)
    return constrain(y, ("batch", "seq", "embed")), aux


# ------------------------------------------------------------------- model
def moe_block_spec(cfg: ModelConfig) -> Params:
    return {
        "attn_norm": L.rmsnorm_spec(cfg.d_model),
        "attn": L.attention_spec(cfg),
        "mlp_norm": L.rmsnorm_spec(cfg.d_model),
        "moe": moe_mlp_spec(cfg),
    }


def moe_spec(cfg: ModelConfig) -> Params:
    spec: Params = {
        "embed": L.embedding_spec(cfg),
        "blocks": stack_specs(moe_block_spec(cfg), cfg.n_layers),
        "final_norm": L.rmsnorm_spec(cfg.d_model),
        "lm_head": L.lm_head_spec(cfg),
    }
    return spec


def _moe_block_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                     kv_cache=None, cache_index=None):
    h = L.rmsnorm(p["attn_norm"], x, cfg.norm_eps)
    attn_out, new_cache = L.attention(p["attn"], h, cfg, causal=True,
                                      kv_cache=kv_cache,
                                      cache_index=cache_index)
    x = x + attn_out
    h = L.rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
    y, aux = moe_mlp(p["moe"], h, cfg)
    return x + y, aux, new_cache


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """-> (logits, total_aux_loss)."""
    x = L.embed(params["embed"], tokens, cfg)

    def body(carry, layer_params):
        h, aux = carry
        h2, a, _ = _moe_block_apply(layer_params, h, cfg)
        return (h2, aux + a), None

    body_fn = body
    if cfg.remat:
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
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
from .transformer import cache_logical_axes, init_cache  # same cache layout


def decode_step(params: Params, tokens: jax.Array,
                cache: Dict[str, jax.Array], cfg: ModelConfig
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x = L.embed(params["embed"], tokens, cfg)
    idx = cache["index"]

    def body(h, xs):
        layer_params, ck, cv = xs
        h2, _, new_kv = _moe_block_apply(layer_params, h, cfg,
                                         kv_cache=(ck, cv), cache_index=idx)
        return h2, new_kv

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_head(params.get("lm_head", {}), x, cfg,
                       embed_params=params["embed"])
    return logits, {"k": new_k, "v": new_v, "index": idx + tokens.shape[1]}
