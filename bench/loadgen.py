"""Traffic from a mix's data file (``bench/traffic/<name>.json``) and a seed.

A mix is a closed loop of fixed batches: every request of a batch has the
same prompt length (the server's cache keeps one position per batch), and
the next batch is handed over when the last completes.  Prompt lengths are
a fixed schedule of the stated distribution's quantiles (times the mix's
``scale``, where it cuts the source's lengths), the same for every seed,
ordered so that every prefix of a power-of-two length spans the range and
the longest comes first; the seed draws only the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def prompt_lengths(spec: Dict[str, Any]) -> List[int]:
    """The schedule of prompt lengths, one per batch, repeated in turn."""
    n = spec["quantiles"]
    if n & (n - 1):
        raise ValueError(f"quantiles must be a power of two, not {n}")
    if spec["dist"] != "lognormal":
        raise ValueError(spec["dist"])
    median = spec["median"] * spec["scale"]
    sizes = [max(1, round(median * math.exp(
        spec["sigma"] * NormalDist().inv_cdf((i + 0.5) / n))))
        for i in range(n)]
    bits = n.bit_length() - 1
    order = [n - 1 - int(format(i, f"0{bits}b")[::-1] or "0", 2)
             for i in range(n)]
    return [sizes[i] for i in order]


def batch_prompts(seed: int, batch_index: int, batch: int, length: int,
                  vocab: int) -> np.ndarray:
    """Token ids (batch, length) of one batch, uniform over the
    vocabulary; batch ``b`` of seed ``s`` is the same in every run."""
    rng = np.random.default_rng([seed % 2 ** 64, batch_index % 2 ** 64])
    return rng.integers(0, vocab, (batch, length), dtype=np.int32)


def longest_request(spec: Dict[str, Any]) -> int:
    """Prompt plus output positions of the longest request."""
    return max(prompt_lengths(spec["prompt_len"])) + spec["output_tokens"]
