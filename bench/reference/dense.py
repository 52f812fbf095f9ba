"""Plain reference of a dense decoder (Qwen2-style): pre-norm blocks of
grouped-query attention with rotary positions and optional q/k/v bias, then
a SwiGLU MLP; final RMSNorm and a head, tied to the embedding or not."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import common as C

MODEL_KEYS = {"intermediate_size": "d_ff", "qkv_bias": "qkv_bias"}
TEST_CUT = {"n_layers": 4}


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    out = C.outer_layout(cfg)
    out["blocks"] = {
        "attn": C.attention_layout(cfg, n),
        "attn_norm": {"scale": C.Leaf((n, d), "ones")},
        "mlp": C.mlp_layout(n, d, cfg["intermediate_size"]),
        "mlp_norm": {"scale": C.Leaf((n, d), "ones")},
    }
    return out


def block_counts(cfg: Dict[str, Any], batch: int) -> C.BlockCounts:
    """Attention and a SwiGLU MLP in every block, all weights used."""
    L = cfg["num_hidden_layers"]
    read, used, cache, flops = C.gqa_block_counts(cfg)
    mlp = 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return C.BlockCounts(L * (read + mlp), L * (used + mlp), L * cache,
                         L * flops)


def forward(w, tokens: jax.Array, rows: jax.Array, cfg: Dict[str, Any],
            prec: C.Precision = C.EXACT) -> jax.Array:
    """Logits (n, R, vocab) at positions ``rows`` (n, R) of ``tokens``
    (n, S), one layer's weights in float32 at a time."""
    eps = cfg["rms_norm_eps"]
    x = w["embed"]["table"][tokens].astype(C.F32)

    def block(x, p):
        p = C.f32(p)
        x = C.rnd(x + C.attention(p["attn"], C.rmsnorm(
            x, p["attn_norm"]["scale"], eps), cfg, prec), prec)
        h = C.rmsnorm(x, p["mlp_norm"]["scale"], eps)
        return C.rnd(x + C.swiglu(p["mlp"], h, prec), prec), None

    x, _ = jax.lax.scan(block, x, w["blocks"])
    return C.logits_at(w, x, rows, cfg, prec)
