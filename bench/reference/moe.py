"""Plain reference of a DeepSeekMoE decoder: pre-norm blocks of multi-head
attention with rotary positions, then a mixture of experts: a softmax
router over ``n_routed_experts``, the ``num_experts_per_tok`` largest
weights (renormalised to sum to one where ``norm_topk_prob``), each routed
expert a SwiGLU of width ``moe_intermediate_size``, plus
``n_shared_experts`` such experts that every token passes through.  Every
expert is computed for every token and weighted by its router weight, zero
for the experts not chosen; nothing is dropped for capacity."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import common as C

MODEL_KEYS = {"moe_intermediate_size": "moe_d_ff",
              "n_routed_experts": "n_experts",
              "n_shared_experts": "n_shared_experts",
              "num_experts_per_tok": "experts_per_token"}
TEST_CUT = {"n_layers": 2, "n_experts": 16}


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    if cfg["first_k_dense_replace"] or cfg["moe_layer_freq"] != 1:
        raise ValueError("the reference has MoE layers only")
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    E, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    out = C.outer_layout(cfg)
    out["blocks"] = {
        "attn": C.attention_layout(cfg, n),
        "attn_norm": {"scale": C.Leaf((n, d), "ones")},
        "mlp_norm": {"scale": C.Leaf((n, d), "ones")},
        "moe": {
            "router": C.Leaf((n, d, E)),
            "w_gate": C.Leaf((n, E, d, f)), "w_up": C.Leaf((n, E, d, f)),
            "w_down": C.Leaf((n, E, f, d)),
            "shared": C.mlp_layout(n, d, f * cfg["n_shared_experts"]),
        },
    }
    return out


def block_counts(cfg: Dict[str, Any], batch: int) -> C.BlockCounts:
    """Attention and a mixture of experts in every block.  The routed
    experts' weights are read for the expected number of distinct experts
    that ``batch`` tokens hit under uniform routing,
    E(1 - (1 - k/E)^batch), and used for k a token; the router and the
    shared experts count whole."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    read, used, cache, flops = C.gqa_block_counts(cfg)
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    shared = expert * cfg["n_shared_experts"]
    hit = E * (1.0 - (1.0 - k / E) ** batch)
    mlp_read = d * E + shared + hit * expert
    mlp_used = d * E + shared + k * expert
    return C.BlockCounts(L * (read + mlp_read), L * (used + mlp_used),
                         L * cache, L * flops)


def router_weights(h: jax.Array, router: jax.Array, cfg: Dict[str, Any]
                   ) -> jax.Array:
    """(n, S, E): each token's weight on each expert, zero off its top k."""
    if cfg["scoring_func"] != "softmax":
        raise ValueError(cfg["scoring_func"])
    probs = jax.nn.softmax(jnp.einsum("nsd,de->nse", h, router,
                                      precision="highest"), -1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    E = probs.shape[-1]
    return jnp.sum(jax.nn.one_hot(idx, E, dtype=C.F32) * top[..., None], -2)


def experts(p, h: jax.Array, gates: jax.Array, prec: C.Precision
            ) -> jax.Array:
    """Sum over routed experts of gate x SwiGLU, one expert at a time."""
    def one(acc, xs):
        wg, wu, wd, g = xs
        y = C.swiglu({"w_gate": wg.astype(C.F32), "w_up": wu.astype(C.F32),
                      "w_down": wd.astype(C.F32)}, h, prec)
        return acc + g[..., None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["w_gate"], p["w_up"], p["w_down"],
                           jnp.moveaxis(gates, -1, 0)))
    return acc


def forward(w, tokens: jax.Array, rows: jax.Array, cfg: Dict[str, Any],
            prec: C.Precision = C.EXACT) -> jax.Array:
    """Logits (n, R, vocab) at positions ``rows`` (n, R) of ``tokens``
    (n, S), one layer's weights in float32 at a time."""
    eps = cfg["rms_norm_eps"]
    x = w["embed"]["table"][tokens].astype(C.F32)

    def block(x, p):
        moe = p["moe"]
        p = C.f32({k: v for k, v in p.items() if k != "moe"})
        x = C.rnd(x + C.attention(p["attn"], C.rmsnorm(
            x, p["attn_norm"]["scale"], eps), cfg, prec), prec)
        h = C.rmsnorm(x, p["mlp_norm"]["scale"], eps)
        gates = router_weights(h, moe["router"].astype(C.F32), cfg)
        y = experts(moe, h, gates, prec)
        y = y + C.swiglu(C.f32(moe["shared"]), h, prec)
        return C.rnd(x + y, prec), None

    x, _ = jax.lax.scan(block, x, w["blocks"])
    return C.logits_at(w, x, rows, cfg, prec)
