"""Shared model building blocks (pure-functional JAX).

Every block takes ``(params, x, cfg, ...)`` and is sharding-annotated with
logical axes via ``parallel.sharding.constrain``.  Attention and the MLP have
two kernel paths: ``"xla"`` (plain jnp; fused by XLA — used by smoke tests and
the dry-run whose roofline reads XLA HLO) and ``"pallas"`` (the TPU kernels of
``repro.kernels``, interpret-validated on CPU).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import constrain
from .param import LeafSpec

Params = Dict[str, Any]


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ------------------------------------------------------------------ norms
def rmsnorm_spec(d: int) -> Params:
    return {"scale": LeafSpec((d,), ("embed",), init="ones")}


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(dt)


def einsum_f32(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """``einsum(spec, a, b)`` with a float32 result and accumulation,
    reading ``a`` and ``b`` in their own dtype: no float32 copy of a
    bfloat16 operand.  XLA's CPU backend has no bfloat16 dot with a
    float32 result, so there the operands are widened first (the same
    products: a bfloat16 product is exact in float32)."""
    f32 = jnp.float32
    return jax.lax.platform_dependent(
        a, b,
        cpu=lambda a, b: jnp.einsum(spec, a.astype(f32), b.astype(f32)),
        default=lambda a, b: jnp.einsum(spec, a, b,
                                        preferred_element_type=f32))


# ------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float, positions: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freq   # (..., S, half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D) with D even; cos/sin: (S, D/2) or broadcastable."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# -------------------------------------------------------------- attention
def attention_spec(cfg: ModelConfig, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.head_dim_
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    spec: Params = {
        "wq": LeafSpec((d, nh, hd), ("embed", "q_heads", "head_dim")),
        "wk": LeafSpec((d, nkv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": LeafSpec((d, nkv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": LeafSpec((nh, hd, d), ("q_heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        spec["bq"] = LeafSpec((nh, hd), ("q_heads", "head_dim"), init="zeros")
        spec["bk"] = LeafSpec((nkv, hd), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = LeafSpec((nkv, hd), ("kv_heads", "head_dim"), init="zeros")
    return spec


def _project_qkv(p: Params, x: jax.Array, cfg: ModelConfig,
                 kv_input: Optional[jax.Array] = None):
    kv_x = x if kv_input is None else kv_input
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = constrain(q, ("batch", "seq", "q_heads", "head_dim"))
    k = constrain(k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "kv_seq", "kv_heads", "head_dim"))
    return q, k, v


def _repeat_kv(k: jax.Array, q_per_kv: int) -> jax.Array:
    if q_per_kv == 1:
        return k
    return jnp.repeat(k, q_per_kv, axis=2)


CHUNKED_ATTN_THRESHOLD = 8192     # dense S x T scores above this use chunking


def _sdpa_xla_chunked(q, k, v, causal: bool, sm_scale: float,
                      q_block: int = 1024, kv_block: int = 1024) -> jax.Array:
    """Flash-style online-softmax attention in plain jnp: lax.scan over query
    blocks, inner scan over KV blocks — O(q_block x kv_block) score memory
    instead of O(S x T).  This is the XLA-path analogue of the Pallas flash
    kernel, required for the 32k prefill cells (a dense 32k x 32k x heads f32
    score tensor is ~120 GB/device; measured in the dry-run)."""
    B, S, H, D = q.shape
    T = k.shape[1]

    def _fit(n, desired):                 # largest pow2 divisor <= desired
        b = 1
        while b * 2 <= desired and n % (b * 2) == 0:
            b *= 2
        return b

    kb = _fit(T, min(kv_block, T))
    if T % kb or kb < 8:
        return _sdpa_xla_dense(q, k, v, causal, sm_scale, None)
    nk = T // kb
    # q is NOT re-blocked: reshaping a sharded seq dim would break GSPMD
    # propagation (measured: tp2d prefill went from 289 GB to fitting once
    # kv-only blocking landed).  Score memory per step: (B, S, kb, H).
    qf = q.astype(jnp.float32)
    m0 = jnp.full((B, S, H), -1e30, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    a0 = jnp.zeros((B, S, H, v.shape[-1]), jnp.float32)
    qpos = jnp.arange(S)[:, None] + (T - S)

    def kv_step(carry, kj):
        m, l, acc = carry
        kblk = jax.lax.dynamic_slice_in_dim(k, kj * kb, kb, axis=1)
        vblk = jax.lax.dynamic_slice_in_dim(v, kj * kb, kb, axis=1)
        s = jnp.einsum("bqhd,bthd->bqth", qf, kblk.astype(jnp.float32))
        s = s * sm_scale
        if causal:
            kpos = kj * kb + jnp.arange(kb)[None, :]
            s = jnp.where((qpos >= kpos)[None, :, :, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[:, :, None, :])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=2)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqth,bthd->bqhd", p, vblk.astype(jnp.float32))
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)


def _sdpa_xla(q, k, v, causal: bool, sm_scale: float,
              kv_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """Dispatch: dense scores for short sequences, flash-style chunking for
    long ones (decode S=1 always dense — its score row is (B,H,1,T))."""
    S, T = q.shape[1], k.shape[1]
    if S > 1 and kv_valid_len is None and S * T > CHUNKED_ATTN_THRESHOLD ** 2:
        # adaptive kv block: keep the global per-step score tensor
        # (B x S x kb x H x 4B) under ~64 GB so its shard stays transient-small
        B, H = q.shape[0], q.shape[2]
        row = B * S * H * 4
        kb = 1024
        while kb > 8 and row * kb > 64e9:
            kb //= 2
        return _sdpa_xla_chunked(q, k, v, causal, sm_scale, kv_block=kb)
    return _sdpa_xla_dense(q, k, v, causal, sm_scale, kv_valid_len)


def _sdpa_xla_dense(q, k, v, causal: bool, sm_scale: float,
                    kv_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """q: (B,S,H,D), k/v: (B,T,H,D) -> (B,S,H,D).  ``kv_valid_len``: the
    number of leading keys every query sees (a scalar), or each query's
    number (shape (S,))."""
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        S, T = s.shape[-2], s.shape[-1]
        qi = jnp.arange(S)[:, None] + (T - S)   # align ends (decode-friendly)
        ki = jnp.arange(T)[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    if kv_valid_len is not None:
        T = s.shape[-1]
        ki = jnp.arange(T)
        if jnp.ndim(kv_valid_len):     # one length per query: (S,)
            s = jnp.where((ki[None, :] < kv_valid_len[:, None])[None, None],
                          s, -jnp.inf)
        else:
            s = jnp.where((ki < kv_valid_len)[None, None, None, :], s,
                          -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _sdpa_pallas(q, k, v, causal: bool, sm_scale: float) -> jax.Array:
    from repro.kernels import ops
    B, S, H, D = q.shape
    T = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    if S == 1:
        out = ops.flash_decode(qf, kf, vf, sm_scale=sm_scale)
    else:
        out = ops.attention(qf, kf, vf, sm_scale=sm_scale, causal=causal)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
              causal: bool = True,
              positions: Optional[jax.Array] = None,
              kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
              cache_index: Optional[jax.Array] = None,
              kv_input: Optional[jax.Array] = None,
              precomputed_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              use_rope: bool = True):
    """GQA attention.  Returns (out, new_kv_cache | None).

    * train/prefill: ``kv_cache is None`` — full self (or cross) attention.
    * decode: ``kv_cache=(k, v)`` of shape (B, T, nkv, hd); the current
      tokens' k/v are inserted at ``cache_index``, and query i of the S
      tokens sees cache positions up to ``cache_index + i``.
    * cross-attention: ``kv_input`` projects k/v from another sequence, or
      ``precomputed_kv`` supplies already-projected (k, v) (cached cross
      attention during decode).

    Named scopes: ``proj`` (q/k/v/o projections and RoPE), ``kv_cache``
    (the cache write) and ``core`` (K/V repeat, scores, softmax, p.v).
    """
    B, S, d = x.shape
    hd = cfg.head_dim_
    if precomputed_kv is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        if "bq" in p:
            q = q + p["bq"].astype(x.dtype)
        k, v = precomputed_kv
        kr = _repeat_kv(k.astype(x.dtype), cfg.q_per_kv)
        vr = _repeat_kv(v.astype(x.dtype), cfg.q_per_kv)
        out = _sdpa_xla(q, kr, vr, False, hd ** -0.5)
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        return constrain(out, ("batch", "seq", "embed")), None
    with jax.named_scope("proj"):
        q, k, v = _project_qkv(p, x, cfg, kv_input)
        if use_rope and kv_input is None:
            pos = positions if positions is not None else jnp.arange(S)
            cos, sin = rope_frequencies(hd, cfg.rope_theta, pos)
            if kv_cache is not None and cache_index is not None:
                qpos = cache_index + jnp.arange(S)
                qcos, qsin = rope_frequencies(hd, cfg.rope_theta, qpos)
                q = apply_rope(q, qcos, qsin)
                k = apply_rope(k, qcos, qsin)
            else:
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
    new_cache = None
    if kv_cache is not None:
        with jax.named_scope("kv_cache"):
            ck, cv = kv_cache
            if cache_index is not None:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    ck, k.astype(ck.dtype), cache_index, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cv, v.astype(cv.dtype), cache_index, axis=1)
            k, v = ck, cv
            new_cache = (ck, cv)
            k = constrain(k, ("batch", "kv_seq", "kv_heads", "head_dim"))
            v = constrain(v, ("batch", "kv_seq", "kv_heads", "head_dim"))
    with jax.named_scope("core"):
        kr = _repeat_kv(k, cfg.q_per_kv)
        vr = _repeat_kv(v, cfg.q_per_kv)
        sm_scale = hd ** -0.5
        is_causal = causal and kv_input is None and kv_cache is None
        if cfg.kernels == "pallas" and (kv_cache is None
                                        or cache_index is None):
            # pallas decode path assumes a fully-valid cache (production
            # kernels take a length scalar; the xla path below masks
            # exactly)
            out = _sdpa_pallas(q, kr, vr, is_causal, sm_scale)
        else:
            cached = kv_cache is not None and cache_index is not None
            valid = None
            if cached:
                # query i of a chunk sees the cache up to its own position
                valid = (cache_index + S if S == 1
                         else cache_index + 1 + jnp.arange(S))
            out = _sdpa_xla(q, kr, vr, is_causal, sm_scale,
                            kv_valid_len=valid)
        out = constrain(out, ("batch", "seq", "q_heads", "head_dim"))
    with jax.named_scope("proj"):
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        out = constrain(out, ("batch", "seq", "embed"))
    return out, new_cache


# -------------------------------------------------------------------- MLP
def mlp_spec(cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": LeafSpec((d, f), ("embed", "ffn")),
        "w_up": LeafSpec((d, f), ("embed", "ffn")),
        "w_down": LeafSpec((f, d), ("ffn", "embed")),
    }


def mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = jax.nn.gelu if cfg.mlp_activation == "gelu" else jax.nn.silu
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    h = act(g) * u
    h = constrain(h, ("batch", "seq", "ffn"))
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
    return constrain(out, ("batch", "seq", "embed"))


# -------------------------------------------------------------- embeddings
def embedding_spec(cfg: ModelConfig) -> Params:
    return {"table": LeafSpec((cfg.padded_vocab, cfg.d_model),
                              ("vocab", "embed"), scale=1.0)}


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(p["table"].astype(cdtype(cfg)), tokens, axis=0)
    return constrain(x, ("batch", "seq", "embed"))


def lm_head_spec(cfg: ModelConfig) -> Params:
    if cfg.tie_embeddings:
        return {}
    return {"w": LeafSpec((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"))}


def lm_head(p: Params, x: jax.Array, cfg: ModelConfig,
            embed_params: Optional[Params] = None) -> jax.Array:
    if cfg.tie_embeddings:
        w = embed_params["table"].astype(x.dtype).T
    else:
        w = p["w"].astype(x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return constrain(logits, ("batch", "seq", "vocab"))


# ------------------------------------------------------------------ losses
def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy, numerically stable in f32."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# tokens x vocab above this fuses head+loss.  Disabled by default: measured
# WORSE on XLA:CPU HLO-bytes (EXPERIMENTS.md SPerf B4 — the scan's carried
# state and bwd rematerialization outweigh the saved logits materialization
# when the logits are already vocab-sharded).  Opt in by lowering this.
FUSED_XENT_THRESHOLD = 1 << 60


def fused_head_xent(x: jax.Array, w: jax.Array, labels: jax.Array, *,
                    chunk: int = 2048, w_is_vd: bool = False) -> jax.Array:
    """LM head + cross-entropy fused over token chunks: the full
    (tokens x vocab) f32 logits tensor is never materialized — each chunk's
    logits live only inside one scan step (EXPERIMENTS.md §Perf B3).

    x: (B, S, d); w: (d, V); labels: (B, S) -> scalar mean xent.

    Chunks along the SEQUENCE axis only — reshaping (B, S) away would break
    GSPMD batch-sharding propagation (measured: 3.6x bytes regression; same
    lesson as the chunked attention, see _sdpa_xla_chunked).
    """
    B, S, d = x.shape
    eq = "bsd,vd->bsv" if w_is_vd else "bsd,dv->bsv"
    c = min(chunk, S)
    if S % c:
        logits = jnp.einsum(eq, x, w.astype(x.dtype))
        return softmax_xent(logits, labels)
    n = S // c

    def step(acc, i):
        xs = jax.lax.dynamic_slice_in_dim(x, i * c, c, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
        xs = constrain(xs, ("batch", "seq", "embed"))
        logits = jnp.einsum(eq, xs, w.astype(xs.dtype)).astype(jnp.float32)
        logits = constrain(logits, ("batch", "seq", "vocab"))
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), jnp.arange(n))
    return total / (B * S)
