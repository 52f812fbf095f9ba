"""Chunked RWKV6 (Finch) WKV Pallas kernel.

RWKV6's data-dependent-decay recurrence per head (state S in R^{d x d}):

    o_t[j]   = sum_i r_t[i] * (S_{t-1}[i,j] + u[i] * k_t[i] * v_t[j])
    S_t[i,j] = w_t[i] * S_{t-1}[i,j] + k_t[i] * v_t[j]

The paper's planning insight applied here (DESIGN.md S5): there is *no
spatial reuse of K across query rows* (the recurrence serializes time), so
the TPU-native formulation is the chunked scan: grid = (batch*heads, chunks)
with the chunk axis sequential, the S state carried in VMEM scratch, and the
intra-chunk part expressed as dense matmuls for the MXU:

    decays  lw = log w, cum[t] = sum_{s<=t} lw[s]          (inclusive)
    r~[t,i] = r[t,i] * exp(cum[t,i] - lw[t,i])             (exclusive decay)
    k~[s,i] = k[s,i] * exp(-cum[s,i])
    scores  = tril(r~ @ k~^T, -1) + diag(sum_i r*u*k)
    o       = r~ @ S_in + scores @ v
    S_out   = exp(cum[C-1]) (.) S_in + (k (.) exp(cum[C-1]-cum))^T @ v

Stability: the separable score factors are offset by the per-channel chunk
midpoint decay (exact — offsets cancel in the product), keeping exponents
within f32 range for per-chunk total log-decay up to ~160.  Validated against
the token-level jnp scan oracle in ref.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *,
                 chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # (C, d)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)        # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)          # (1, d) bonus
    S = state_ref[...]                        # (d, d)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over time as a lower-triangular matmul (Mosaic
    # has no cumsum lowering)
    cum = jax.lax.dot_general(jnp.where(t_idx >= s_idx, 1.0, 0.0), lw,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # (C, d)
    cum_excl = cum - lw
    # inter-chunk: decayed read of the carried state (factor <= 1, exact)
    r_decay = r * jnp.exp(cum_excl)           # (C, d)
    o = jax.lax.dot_general(r_decay, S, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (C, d)

    # intra-chunk pairwise scores.  score[t,s] = sum_i r*k*e^{cum_excl[t,i]
    # - cum[s,i]} is separable; a per-channel midpoint offset c_i keeps both
    # factors within f32 range (exact: offsets cancel in the product).
    last = cum[chunk - 1:chunk]               # (1, d) chunk log-decay
    c_off = 0.5 * last
    r_sc = r * jnp.exp(cum_excl - c_off)
    k_sc = k * jnp.exp(c_off - cum)
    scores = jax.lax.dot_general(r_sc, k_sc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (C, C)
    scores = jnp.where(t_idx > s_idx, scores, 0.0)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)   # (C, 1)
    o = o + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o = o + diag * v

    # state propagation to the next chunk; S's rows scale by the chunk's
    # decay, summed as a column (d, 1) so no vector transpose is needed
    last_col = jax.lax.dot_general(lw, jnp.ones((chunk, 1), jnp.float32),
                                   (((0,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    k_carry = k * jnp.exp(last - cum)         # (C, d)
    state_ref[...] = (S * jnp.exp(last_col)
                      + jax.lax.dot_general(k_carry, v, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))
    o_ref[0] = o.astype(o_ref.dtype)


def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array,
         u: jax.Array, *, chunk: int = DEFAULT_CHUNK,
         interpret: bool = False) -> jax.Array:
    """r/k/v/log_w: (BH, T, d); u: (BH, d) -> (BH, T, d).

    ``log_w`` is the elementwise log of the decay (<= 0).  T must be a
    multiple of ``chunk`` (ops.py pads).
    """
    BH, T, d = r.shape
    c = min(chunk, T)
    assert T % c == 0, (T, c)
    u2 = u.reshape(BH, 1, d)
    kernel = functools.partial(_wkv6_kernel, chunk=c)
    return pl.pallas_call(
        kernel,
        grid=(BH, T // c),
        in_specs=[
            pl.BlockSpec((1, c, d), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, c, d), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, c, d), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, c, d), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, 1, d), lambda h, t: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, d), lambda h, t: (h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), r.dtype),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(r, k, v, log_w, u2)
