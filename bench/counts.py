"""The work one serving step needs, from the configuration's sizes.

A step feeds one token to each of ``batch`` sequences whose caches hold
``filled`` positions once the new one is written.  What it needs, whatever
implements it:

- FLOPs: two per multiply-add of every weight the step's tokens meet, plus
  attention's q.k and p.v over the filled positions of each query head.
- Bytes (bfloat16, two a value): every weight the step needs read once
  (the embedding only in the rows the tokens pick, unless the head is tied
  to it), the keys and values of the ``filled - 1`` earlier positions read
  and the new ones written, at key/value-head width, and the logits
  written.
- For a mixture of experts, the routed experts' weights count for the
  expected number of distinct experts that ``batch`` tokens hit under
  uniform routing, E(1 - (1 - k/E)^batch); the shared experts, the router
  and everything outside the experts count whole.

Never what an implementation happens to move: reading an unfilled cache
or an expert no token chose is not needed work.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

BYTES = 2      # bfloat16


def _attention_weights(cfg: Dict[str, Any]) -> int:
    d, nh, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd = d // nh
    return 2 * d * nh * hd + 2 * d * nkv * hd


def step_counts(cfg: Dict[str, Any], batch: int, filled: int
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one step of ``batch`` tokens over caches of
    ``filled`` positions."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    attn = _attention_weights(cfg)
    bias = (nh + 2 * nkv) * hd if cfg.get("qkv_bias") else 0
    norms = 2 * d
    if cfg["family"] == "dense":
        mlp_read = mlp_used = 3 * d * cfg["intermediate_size"]
    elif cfg["family"] == "moe":
        E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
        expert = 3 * d * cfg["moe_intermediate_size"]
        shared = expert * cfg["n_shared_experts"]
        hit = E * (1.0 - (1.0 - k / E) ** batch)
        mlp_read = d * E + shared + hit * expert
        mlp_used = d * E + shared + k * expert
    else:
        raise ValueError(cfg["family"])
    head = d * V
    flops = 2.0 * batch * (L * (attn + mlp_used) + head)
    flops += 2.0 * 2 * batch * L * nh * hd * filled
    weights = L * (attn + bias + norms + mlp_read) + d + head
    if not cfg["tie_word_embeddings"]:
        weights += batch * d                   # embedding rows looked up
    kv = 2 * batch * L * nkv * hd * filled     # filled keys and values
    logits = batch * V
    return flops, float(BYTES * (weights + kv + logits))


def least_seconds(cfg: Dict[str, Any], batch: int, filled: int,
                  peak: Dict[str, float]) -> float:
    """The least time a chip with ``peak`` could take for the step."""
    flops, nbytes = step_counts(cfg, batch, filled)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
