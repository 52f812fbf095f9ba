"""Unified model API: one entry point per architecture family.

``build_model(cfg)`` returns a :class:`ModelAPI` whose members close over the
config: parameter spec (single source of truth for init / abstract shapes /
sharding axes), loss function, decode step, cache constructors, and the
ShapeDtypeStruct input specs the dry-run lowers against.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from . import encdec, mla, moe, param as P, rwkv6, transformer, vlm, zamba2

Params = Dict[str, Any]


@dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    spec: Params
    loss_fn: Callable[[Params, Dict[str, jax.Array]], Tuple[jax.Array, Dict]]
    logits_fn: Callable[[Params, Dict[str, jax.Array]], jax.Array]
    decode_step: Optional[Callable]
    init_cache: Optional[Callable]
    cache_axes: Optional[Callable]

    # -- params -------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        return P.materialize(rng, self.spec)

    def abstract_params(self) -> Params:
        return P.abstract(self.spec)

    def param_axes(self) -> Params:
        return P.axes_of(self.spec)

    def n_params(self) -> int:
        return P.count_params(self.spec)

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: only routed experts count)."""
        cfg = self.cfg
        if not cfg.n_experts:
            return self.n_params()
        total = 0
        for path, leaf in _iter_leaves(self.spec):
            size = 1
            for s in leaf.shape:
                size *= s
            if "experts" in leaf.axes:
                frac = (cfg.experts_per_token or cfg.n_experts) / cfg.n_experts
                size = int(size * frac)
            total += size
        return total

    # -- input specs (ShapeDtypeStruct stand-ins; NO allocation) --------------
    def input_specs(self, shape: ShapeConfig,
                    batch_override: Optional[int] = None) -> Dict[str, Any]:
        cfg = self.cfg
        B = batch_override or shape.global_batch
        S = shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train" or shape.kind == "prefill":
            specs: Dict[str, Any] = {
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
            if cfg.family == "vlm":
                specs["patches"] = jax.ShapeDtypeStruct(
                    (B, cfg.frontend_len, cfg.frontend_dim), jnp.bfloat16)
            if cfg.family == "audio":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.frontend_len, cfg.frontend_dim), jnp.bfloat16)
                specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
                specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
            return specs
        if shape.kind == "decode":
            assert self.init_cache is not None, f"{cfg.name} has no decode step"
            cache = jax.eval_shape(lambda: self.init_cache(cfg, B, S))
            return {
                "tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "cache": cache,
            }
        raise ValueError(shape.kind)


def _iter_leaves(spec, prefix=()):
    if isinstance(spec, P.LeafSpec):
        yield prefix, spec
        return
    if isinstance(spec, dict):
        for k, v in spec.items():
            yield from _iter_leaves(v, prefix + (k,))


def _cast(spec, cfg: ModelConfig):
    return P.cast_spec_dtype(spec, jnp.dtype(cfg.param_dtype))


def build_model(cfg: ModelConfig) -> ModelAPI:
    fam = cfg.family
    if fam in ("dense",):
        return ModelAPI(
            cfg=cfg, spec=_cast(transformer.transformer_spec(cfg), cfg),
            loss_fn=lambda p, b: transformer.loss_fn(p, b, cfg),
            logits_fn=lambda p, b: transformer.forward(p, b["tokens"], cfg),
            decode_step=lambda p, t, c: transformer.decode_step(p, t, c, cfg),
            init_cache=transformer.init_cache,
            cache_axes=transformer.cache_logical_axes)
    if fam == "moe":
        return ModelAPI(
            cfg=cfg, spec=_cast(moe.moe_spec(cfg), cfg),
            loss_fn=lambda p, b: moe.loss_fn(p, b, cfg),
            logits_fn=lambda p, b: moe.forward(p, b["tokens"], cfg)[0],
            decode_step=lambda p, t, c: moe.decode_step(p, t, c, cfg),
            init_cache=moe.init_cache,
            cache_axes=moe.cache_logical_axes)
    if fam == "mla":
        return ModelAPI(
            cfg=cfg, spec=_cast(mla.mla_spec(cfg), cfg),
            loss_fn=lambda p, b: mla.loss_fn(p, b, cfg),
            logits_fn=lambda p, b: mla.forward(p, b["tokens"], cfg)[0],
            decode_step=lambda p, t, c: mla.decode_step(p, t, c, cfg),
            init_cache=mla.init_cache,
            cache_axes=mla.cache_logical_axes)
    if fam == "ssm":
        return ModelAPI(
            cfg=cfg, spec=_cast(rwkv6.rwkv6_spec(cfg), cfg),
            loss_fn=lambda p, b: rwkv6.loss_fn(p, b, cfg),
            logits_fn=lambda p, b: rwkv6.forward(p, b["tokens"], cfg),
            decode_step=lambda p, t, c: rwkv6.decode_step(p, t, c, cfg),
            init_cache=rwkv6.init_cache,
            cache_axes=rwkv6.cache_logical_axes)
    if fam == "hybrid":
        return ModelAPI(
            cfg=cfg, spec=_cast(zamba2.zamba2_spec(cfg), cfg),
            loss_fn=lambda p, b: zamba2.loss_fn(p, b, cfg),
            logits_fn=lambda p, b: zamba2.forward(p, b["tokens"], cfg),
            decode_step=lambda p, t, c: zamba2.decode_step(p, t, c, cfg),
            init_cache=zamba2.init_cache,
            cache_axes=zamba2.cache_logical_axes)
    if fam == "vlm":
        return ModelAPI(
            cfg=cfg, spec=_cast(vlm.vlm_spec(cfg), cfg),
            loss_fn=lambda p, b: vlm.loss_fn(p, b, cfg),
            logits_fn=lambda p, b: vlm.forward(p, b["tokens"], b["patches"],
                                               cfg),
            decode_step=lambda p, t, c: vlm.decode_step(p, t, c, cfg),
            init_cache=vlm.init_cache,
            cache_axes=vlm.cache_logical_axes)
    if fam == "audio":
        return ModelAPI(
            cfg=cfg, spec=_cast(encdec.encdec_spec(cfg), cfg),
            loss_fn=lambda p, b: encdec.loss_fn(p, b, cfg),
            logits_fn=lambda p, b: encdec.forward(p, b["frames"],
                                                  b["tokens"], cfg),
            decode_step=lambda p, t, c: encdec.decode_step(p, t, c, cfg),
            init_cache=encdec.init_cache,
            cache_axes=encdec.cache_logical_axes)
    raise ValueError(f"unknown family {fam!r}")
