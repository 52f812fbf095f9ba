"""Plain reference of a DeepSeek-V3 decoder (Moonlight-16B-A3B): pre-norm
blocks of multi-head latent attention, then a dense SwiGLU in the first
``first_k_dense_replace`` blocks and a mixture of experts in the others;
final RMSNorm and an untied head.

Latent attention in its expanded form, as DeepSeek-V2 §2.1 gives it, with
no query low-rank: ``q = x W_q`` split into ``qk_nope_head_dim`` and
``qk_rope_head_dim`` per head; ``[c_kv, k_pe] = x W_kva``, ``c_kv``
RMS-normed (eps 1e-6, the published code's default); per head keys
``[c_kv W_UK, rope(k_pe)]`` (one rope key for all heads) and values
``c_kv W_UV``; causal softmax at scale ``(nope + rope) ** -0.5``.

The router scores all ``router_experts`` (the published
``n_routed_experts``) by a sigmoid; the ``num_experts_per_tok`` largest of
score plus ``e_score_correction_bias`` are chosen (``noaux_tc`` with one
group), and their gates are their unbiased scores, renormalised (the
reference takes only ``norm_topk_prob`` true), times
``routed_scaling_factor``.  The layer's output is the shared experts plus the gated sum over the experts held here, the
first ``n_routed_experts``: the part this chip computes of the published
layer, which adds the other chips' experts through the exchange.  Every
held expert is computed for every token and weighted by its gate; nothing
is dropped for capacity.

Departures from the published model, made by program and reference alike
(the configuration file lists them): RoPE rotates the two halves of each
rope vector as pairs, not interleaved pairs; ``kv_b_proj`` is the two
tensors ``wk_b`` and ``wv_b``; no auxiliary loss.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import common as C
from .moe import experts

MODEL_KEYS = {"intermediate_size": "d_ff",
              "moe_intermediate_size": "moe_d_ff",
              "n_routed_experts": "n_experts_held",
              "router_experts": "n_experts",
              "n_shared_experts": "n_shared_experts",
              "num_experts_per_tok": "experts_per_token",
              "first_k_dense_replace": "n_dense_layers",
              "kv_lora_rank": "kv_lora_rank",
              "qk_nope_head_dim": "qk_nope_head_dim",
              "qk_rope_head_dim": "qk_rope_head_dim",
              "v_head_dim": "v_head_dim",
              "scoring_func": "router_scoring",
              "routed_scaling_factor": "routed_scaling"}
# the dense block and one MoE block at the published widths
TEST_CUT = {"n_layers": 2}
LATENT_NORM_EPS = 1e-6


def _check(cfg: Dict[str, Any]) -> None:
    if cfg["q_lora_rank"] or cfg["moe_layer_freq"] != 1 \
            or cfg["n_group"] != 1 or cfg["scoring_func"] != "sigmoid" \
            or not cfg["norm_topk_prob"]:
        raise ValueError("the reference has no query low-rank, MoE in "
                         "every block after the dense ones, one router "
                         "group, sigmoid scores and renormalised gates")


def _attention_layout(cfg: Dict[str, Any], n: int) -> Dict[str, Any]:
    d, H, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {"wq": C.Leaf((n, d, H, dn + dr)),
            "wkv_a": C.Leaf((n, d, r + dr)),
            "kv_norm": {"scale": C.Leaf((n, r), "ones")},
            "wk_b": C.Leaf((n, H, r, dn)), "wv_b": C.Leaf((n, H, r, dv)),
            "wo": C.Leaf((n, H, dv, d))}


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    _check(cfg)
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    k = cfg["first_k_dense_replace"]
    n, E, held = L - k, cfg["router_experts"], cfg["n_routed_experts"]
    f = cfg["moe_intermediate_size"]

    def norms(m):
        return {"attn_norm": {"scale": C.Leaf((m, d), "ones")},
                "mlp_norm": {"scale": C.Leaf((m, d), "ones")}}

    out = C.outer_layout(cfg)
    out["blocks"] = {
        "attn": _attention_layout(cfg, n), **norms(n),
        "moe": {
            "router": C.Leaf((n, d, E)),
            "e_score_correction_bias": C.Leaf((n, E)),
            "w_gate": C.Leaf((n, held, d, f)),
            "w_up": C.Leaf((n, held, d, f)),
            "w_down": C.Leaf((n, held, f, d)),
            "shared": C.mlp_layout(n, d, f * cfg["n_shared_experts"]),
        },
    }
    if k:
        out["dense_blocks"] = {
            "attn": _attention_layout(cfg, k), **norms(k),
            "mlp": C.mlp_layout(k, d, cfg["intermediate_size"])}
    return out


def _attention_counts(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """One block's latent attention and its two norms: (weights read,
    weights used).  The absorbed step multiplies W_UK and W_UV once a
    token, as the expanded form does."""
    d, H, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    used = d * H * (dn + dr) + d * (r + dr) + H * r * (dn + dv) + H * dv * d
    return used + r + 2 * d, used


def cache_values(cfg: Dict[str, Any]) -> int:
    """Latent cache values a position holds in one layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def position_flops(cfg: Dict[str, Any]) -> int:
    """Absorbed attention's FLOPs a token spends per cached position in one
    layer: scores over the latent and rope key, then p.c_kv."""
    return 2 * cfg["num_attention_heads"] * (cache_values(cfg)
                                             + cfg["kv_lora_rank"])


def block_counts(cfg: Dict[str, Any], batch: int) -> C.BlockCounts:
    """The dense blocks and the MoE blocks.  Of the experts held here, the
    weights are read for the expected number that ``batch`` tokens hit
    under uniform routing over all ``router_experts``,
    held (1 - (1 - k/E)^batch), and used for the k held/E of a token's k
    that land here; the router (with its bias) and the shared experts
    count whole."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    k_dense = cfg["first_k_dense_replace"]
    attn_read, attn_used = _attention_counts(cfg)
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    held = cfg["n_routed_experts"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    shared = expert * cfg["n_shared_experts"]
    hit = held * (1.0 - (1.0 - k / E) ** batch)
    dense = 3 * d * cfg["intermediate_size"]
    moe_read = d * E + E + shared + hit * expert
    moe_used = d * E + shared + k * held / E * expert
    n = L - k_dense
    return C.BlockCounts(
        L * attn_read + k_dense * dense + n * moe_read,
        L * attn_used + k_dense * dense + n * moe_used,
        L * cache_values(cfg), L * position_flops(cfg))


def core_counts(cfg: Dict[str, Any], batch: int, filled: int
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) that the step's ``attention/core`` needs at least:
    the ``filled`` latent positions of every layer read (the new one
    written), and ``position_flops`` a position, for ``batch`` tokens."""
    L = cfg["num_hidden_layers"]
    flops = float(batch * L * position_flops(cfg) * filled)
    return flops, float(2 * batch * L * cache_values(cfg) * filled)


def router_gates(h: jax.Array, router: jax.Array, bias: jax.Array,
                 cfg: Dict[str, Any]) -> jax.Array:
    """(n, S, E): each token's gate on each of all ``router_experts``,
    zero off its chosen ones."""
    scores = jax.nn.sigmoid(jnp.einsum("nsd,de->nse", h, router,
                                       precision="highest"))
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, -1)
    top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    E = scores.shape[-1]
    return jnp.sum(jax.nn.one_hot(idx, E, dtype=C.F32) * top[..., None], -2)


def attention(p, h: jax.Array, cfg: Dict[str, Any], prec: C.Precision
              ) -> jax.Array:
    """Causal latent attention in its expanded form, one head at a time."""
    dn, r = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    theta = cfg["rope_theta"]
    q = C.linear("nsd,dhk->nshk", h, p["wq"], prec)
    kv = C.linear("nsd,dr->nsr", h, p["wkv_a"], prec)
    c = C.rnd(C.rmsnorm(kv[..., :r], p["kv_norm"]["scale"],
                        LATENT_NORM_EPS), prec)
    k_pe = C.rnd(C.rope(kv[..., None, r:], theta), prec)[:, :, 0]
    q_pe = C.rope(q[..., dn:], theta)
    k_nope = C.linear("nsr,hrk->nshk", c, p["wk_b"], prec)
    v = C.linear("nsr,hrv->nshv", c, p["wv_b"], prec)
    S = h.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = 1.0 / math.sqrt(dn + cfg["qk_rope_head_dim"])

    def head(xs):
        qn, qp, kn, vh = xs                   # (n, S, .) each
        s = (jnp.einsum("nsk,ntk->nst", qn, kn, precision="highest")
             + jnp.einsum("nsk,ntk->nst", qp, k_pe, precision="highest"))
        s = jnp.where(causal, s * scale, -jnp.inf)
        return jnp.einsum("nst,ntv->nsv", jax.nn.softmax(s, -1), vh,
                          precision="highest")

    heads = (q[..., :dn], q_pe, k_nope, v)
    a = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in heads))
    return C.linear("nshv,hvd->nsd", jnp.moveaxis(a, 0, 2), p["wo"], prec)


def forward(w, tokens: jax.Array, rows: jax.Array, cfg: Dict[str, Any],
            prec: C.Precision = C.EXACT) -> jax.Array:
    """Logits (n, R, vocab) at positions ``rows`` (n, R) of ``tokens``
    (n, S), one layer's weights in float32 at a time."""
    _check(cfg)
    eps = cfg["rms_norm_eps"]
    x = w["embed"]["table"][tokens].astype(C.F32)

    def attend(x, p):
        return C.rnd(x + attention(p["attn"], C.rmsnorm(
            x, p["attn_norm"]["scale"], eps), cfg, prec), prec)

    def dense(x, p):
        p = C.f32(p)
        x = attend(x, p)
        h = C.rmsnorm(x, p["mlp_norm"]["scale"], eps)
        return C.rnd(x + C.swiglu(p["mlp"], h, prec), prec), None

    def block(x, p):
        moe = p["moe"]
        p = C.f32({k: v for k, v in p.items() if k != "moe"})
        x = attend(x, p)
        h = C.rmsnorm(x, p["mlp_norm"]["scale"], eps)
        gates = router_gates(h, moe["router"].astype(C.F32),
                             moe["e_score_correction_bias"].astype(C.F32),
                             cfg)
        y = experts(moe, h, gates[..., :cfg["n_routed_experts"]], prec)
        y = y + C.swiglu(C.f32(moe["shared"]), h, prec)
        return C.rnd(x + y, prec), None

    if "dense_blocks" in w:
        x, _ = jax.lax.scan(dense, x, w["dense_blocks"])
    x, _ = jax.lax.scan(block, x, w["blocks"])
    return C.logits_at(w, x, rows, cfg, prec)
