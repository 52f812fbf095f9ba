"""The work one serving step needs, from the configuration's sizes.

A step feeds one token to each of ``batch`` sequences whose caches hold
``filled`` positions once the new one is written.  What it needs, whatever
implements it:

- FLOPs: two per multiply-add of every weight the step's tokens meet, plus
  attention's q.k and p.v over the filled positions.
- Bytes (bfloat16, two a value): every weight the step needs read once
  (the embedding only in the rows the tokens pick, unless the head is tied
  to it), the cache of the ``filled - 1`` earlier positions read and the
  new one written, and the logits written.

The decoder blocks' share is the family's (``block_counts`` of its
``bench/reference/<family>.py``: which weights a step reads and uses, what
a position's cache holds, and attention's FLOPs a position); the
embedding, head and logits are every family's and are counted here.

Never what an implementation happens to move: reading an unfilled cache
or an expert no token chose is not needed work.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from bench import reference

BYTES = 2      # bfloat16


def step_counts(cfg: Dict[str, Any], batch: int, filled: int
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one step of ``batch`` tokens over caches of
    ``filled`` positions."""
    fam = reference.family(cfg)
    if not hasattr(fam, "block_counts"):
        raise ValueError(f"bench/reference/{cfg['family']}.py has no "
                         f"block_counts: its step's work is not counted")
    blocks = fam.block_counts(cfg, batch)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    head = d * V
    flops = 2.0 * batch * (blocks.weights_used + head)
    flops += batch * blocks.attention_flops * filled
    weights = blocks.weights_read + d + head           # + final norm
    if not cfg["tie_word_embeddings"]:
        weights += batch * d                   # embedding rows looked up
    kv = batch * blocks.cache_values * filled  # filled cache positions
    logits = batch * V
    return flops, float(BYTES * (weights + kv + logits))


def least_seconds(cfg: Dict[str, Any], batch: int, filled: int,
                  peak: Dict[str, float]) -> float:
    """The least time a chip with ``peak`` could take for the step."""
    flops, nbytes = step_counts(cfg, batch, filled)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
