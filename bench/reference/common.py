"""What the dense and MoE references share: the seeded weights, plain
float32 layers, and the work of grouped-query attention in a serving step.

The weights are the benchmark's own: a run serves them and the reference
reads them, and neither takes them from the program.  One leaf per tensor,
layers stacked on a leading axis; every matrix is drawn as a float32 normal
with the configuration's ``initializer_range`` as its deviation and stored
in bfloat16, biases are zero and norm scales one.  ``make_weights`` makes
them all in one jitted call, leaf ``i`` (in sorted-key order) from the
``i``-th key that ``jax.random.split`` makes of the seed's key (its low 32
bits, with the rest folded in).
``bench/weights.py`` places them in the program's parameter tree.

Every matrix product here runs at ``highest`` precision in float32.  The
check's controls compute at a lower precision (``Precision``): float8
(e4m3) weights with a scale per output channel, and activations (the
products' inputs and outputs, the residual stream, the keys and values, the
logits) kept in float8 with a scale per row, or in bfloat16 as the served
model keeps them.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Precision:
    """What a control rounds: weights to ``float8`` or not, activations to
    ``float8``, ``bfloat16`` or not."""
    weights: Optional[str] = None
    acts: Optional[str] = None


EXACT = Precision()
CONTROLS = {
    # the configuration's bfloat16, one step down, everywhere
    "fp8": Precision(weights="float8", acts="float8"),
    # float8 weights under the served model's bfloat16 activations
    "fp8_weights": Precision(weights="float8", acts="bfloat16"),
}


@dataclasses.dataclass(frozen=True)
class Leaf:
    """Shape and init of one weight tensor."""
    shape: Tuple[int, ...]
    init: str = "normal"          # "normal" | "zeros" | "ones"


def padded_vocab(cfg: Dict[str, Any]) -> int:
    """The served embedding and head have a row per id, padded to a
    multiple of 256."""
    return -(-cfg["vocab_size"] // 256) * 256


def attention_layout(cfg: Dict[str, Any], n: int) -> Dict[str, Leaf]:
    d, nh, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd = d // nh
    out = {"wq": Leaf((n, d, nh, hd)), "wk": Leaf((n, d, nkv, hd)),
           "wv": Leaf((n, d, nkv, hd)), "wo": Leaf((n, nh, hd, d))}
    if cfg.get("qkv_bias"):
        out.update(bq=Leaf((n, nh, hd), "zeros"),
                   bk=Leaf((n, nkv, hd), "zeros"),
                   bv=Leaf((n, nkv, hd), "zeros"))
    return out


class BlockCounts(NamedTuple):
    """The work of a serving step in all of a model's decoder blocks, in
    values and FLOPs (``bench/counts.py`` says what counts as needed)."""
    weights_read: float     # weight values the step reads
    weights_used: float     # weight values each token multiplies
    cache_values: int       # cache values a position of one sequence holds
    attention_flops: int    # q.k and p.v FLOPs a token spends per position


def gqa_block_counts(cfg: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """One block's grouped-query attention and its two norms, as
    ``BlockCounts``'s four numbers: weights read (with the q/k/v bias),
    weights used, a position's keys and values, and a token's FLOPs per
    position."""
    d, nh, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd = d // nh
    attn = 2 * d * nh * hd + 2 * d * nkv * hd
    bias = (nh + 2 * nkv) * hd if cfg.get("qkv_bias") else 0
    norms = 2 * d
    return attn + bias + norms, attn, 2 * nkv * hd, 2 * 2 * nh * hd


def mlp_layout(n: int, d: int, f: int) -> Dict[str, Leaf]:
    return {"w_gate": Leaf((n, d, f)), "w_up": Leaf((n, d, f)),
            "w_down": Leaf((n, f, d))}


def outer_layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Embedding, final norm and (untied) head."""
    d, vp = cfg["hidden_size"], padded_vocab(cfg)
    out: Dict[str, Any] = {
        "embed": {"table": Leaf((vp, d))},
        "final_norm": {"scale": Leaf((d,), "ones")},
    }
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = {"w": Leaf((d, vp))}
    return out


def make_weights(layout: Dict[str, Any], seed: int, std: float
                 ) -> Dict[str, Any]:
    """The bfloat16 weights that ``seed`` stands for, on the default
    device, from one jitted call."""
    leaves, treedef = jax.tree.flatten(
        layout, is_leaf=lambda x: isinstance(x, Leaf))
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)
    made = _maker(tuple(leaves), float(std))(key)
    return jax.tree.unflatten(treedef, jax.block_until_ready(made))


@functools.lru_cache(maxsize=None)
def _maker(leaves: Tuple[Leaf, ...], std: float):
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, leaf in zip(keys, leaves):
            if leaf.init == "zeros":
                out.append(jnp.zeros(leaf.shape, jnp.bfloat16))
            elif leaf.init == "ones":
                out.append(jnp.ones(leaf.shape, jnp.bfloat16))
            else:
                out.append((jax.random.normal(k, leaf.shape, F32) * std
                            ).astype(jnp.bfloat16))
        return out
    return jax.jit(make)


# ------------------------------------------------------------------ layers
def f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rnd(t: jax.Array, prec: Precision) -> jax.Array:
    """``t`` as an activation is stored at ``prec``: float8 with a scale
    per row, bfloat16, or as it is."""
    return _round(t, prec.acts, (-1,))


def _round(t: jax.Array, to: Optional[str], axes) -> jax.Array:
    if to is None:
        return t
    if to == "bfloat16":
        return t.astype(jnp.bfloat16).astype(F32)
    if to != "float8":
        raise ValueError(to)
    # float8 e4m3 under a scale that maps the largest magnitude over
    # ``axes`` to the format's largest value
    amax = jnp.max(jnp.abs(t), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (t / scale).astype(FP8).astype(F32) * scale


def linear(spec: str, x: jax.Array, w: jax.Array, prec: Precision
           ) -> jax.Array:
    """``einsum(spec, x, w)`` contracting x's trailing axes with w's
    leading ones, its inputs and output rounded as ``prec`` says (each
    input's scale is over the axes it contracts)."""
    if prec != EXACT:
        ins, _ = spec.split("->")
        xs, ws = ins.split(",")
        shared = [c for c in xs if c in ws]
        x = _round(x, prec.acts, tuple(xs.index(c) for c in shared))
        w = _round(w, prec.weights, tuple(ws.index(c) for c in shared))
    return rnd(jnp.einsum(spec, x, w, precision="highest"), prec)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def swiglu(p, h: jax.Array, prec: Precision) -> jax.Array:
    g = linear("nsd,df->nsf", h, p["w_gate"], prec)
    u = linear("nsd,df->nsf", h, p["w_up"], prec)
    return linear("nsf,fd->nsd", jax.nn.silu(g) * u, p["w_down"], prec)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of (n, S, H, D) at positions 0..S-1, the two
    halves of D rotated as pairs."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(p, h: jax.Array, cfg: Dict[str, Any], prec: Precision
              ) -> jax.Array:
    """Causal self-attention with rotary positions; each group of
    ``heads / kv_heads`` query heads reads one key/value head."""
    q = linear("nsd,dhk->nshk", h, p["wq"], prec)
    k = linear("nsd,dhk->nshk", h, p["wk"], prec)
    v = linear("nsd,dhk->nshk", h, p["wv"], prec)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, cfg["rope_theta"])
    k = rnd(rope(k, cfg["rope_theta"]), prec)
    n, S, H, D = q.shape
    G = H // k.shape[2]
    q = q.reshape(n, S, k.shape[2], G, D)
    s = jnp.einsum("nsjgd,ntjd->njgst", q, k, precision="highest")
    s = s / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jnp.einsum("njgst,ntjd->nsjgd", jax.nn.softmax(s, -1), v,
                   precision="highest").reshape(n, S, H, D)
    return linear("nshk,hkd->nsd", a, p["wo"], prec)


def logits_at(w, x: jax.Array, rows: jax.Array, cfg: Dict[str, Any],
              prec: Precision) -> jax.Array:
    """Final norm and head at positions ``rows`` (n, R) of the last hidden
    states x (n, S, d): (n, R, vocab)."""
    x = jnp.take_along_axis(x, rows[..., None], axis=1)
    x = rmsnorm(x, w["final_norm"]["scale"].astype(F32), cfg["rms_norm_eps"])
    if cfg["tie_word_embeddings"]:
        logits = linear("nrd,vd->nrv", x, w["embed"]["table"].astype(F32),
                        prec)
    else:
        logits = linear("nrd,dv->nrv", x, w["lm_head"]["w"].astype(F32),
                        prec)
    return logits[..., :cfg["vocab_size"]]
