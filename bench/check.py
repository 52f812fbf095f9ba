"""Whether what the window served is right: the comparison with the plain
reference.

A sample of the requests the window finished, drawn from the seed, holds a
request of the batch with the longest prompt (the longest request must be
in it) and, of the others, one request from each part of the batch's
slots, ``check_requests`` parts in all.  The reference (``bench/reference``) runs once
over each sampled prompt followed by its served ids, with weights it makes
itself from the seed.  At every position where an id was served, the gap is
how far that id's reference logit lies below the reference's best.  Two
numbers sum the gaps up: the widest (``max_logit_gap``), and the share of
served ids that are not the reference's best (``off_best_pct``), and the
mean gap over all served positions (``mean_logit_gap``).  Greedy
ids from a sound server lie on the reference's best but where rounding
reorders a near-tie; an id produced wrongly lies far below it.  Each number
that the configuration's ``check`` gives a limit is compared with it.

A control (``control``, a key of ``reference.common.CONTROLS``) puts the
reference in the program's place at a lower precision than the served
bfloat16: at each position of the same prompts and ids, the gap of the id
that the lower precision puts first.  ``fp8`` is float8 everywhere, the
next precision down; ``fp8_weights`` float8 weights under bfloat16
activations, the step that would tempt a faster server.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import numpy as np

from bench import loadgen, weights


@dataclasses.dataclass
class Sample:
    tokens: np.ndarray      # (n, S) prompt then served ids, zero-padded
    rows: np.ndarray        # (n, N) positions whose next id was served
    served: np.ndarray      # (n, N) the served ids


def sample(batches, traffic: Dict[str, Any], seed: int) -> Sample:
    """The requests checked: one in each of ``check_requests`` parts of the
    slots, the first from the batch with the longest prompt, the others
    from finished batches drawn from the seed."""
    n = traffic["check_requests"]
    B, N = traffic["batch"], traffic["output_tokens"]
    rng = np.random.default_rng([seed % 2 ** 64, 2 ** 32])
    longest = max(batches, key=lambda b: b.prompts.shape[1])
    picks = [longest] + [batches[i] for i in
                         rng.integers(0, len(batches), n - 1)]
    edges = np.linspace(0, B, n + 1).astype(int)
    slots = [int(rng.integers(lo, max(lo + 1, hi)))
             for lo, hi in zip(edges[:-1], edges[1:])]
    S = loadgen.longest_request(traffic) - 1
    tokens = np.zeros((n, S), np.int32)
    rows = np.zeros((n, N), np.int32)
    served = np.zeros((n, N), np.int32)
    for i, (b, slot) in enumerate(zip(picks, slots)):
        P = b.prompts.shape[1]
        if b.generated.shape != (B, N):
            raise ValueError(f"batch {b.index} served {b.generated.shape}")
        seq = np.concatenate([b.prompts[slot], b.generated[slot, :-1]])
        tokens[i, :len(seq)] = seq
        rows[i] = P - 1 + np.arange(N)
        served[i] = b.generated[slot]
    return Sample(tokens, rows, served)


@functools.lru_cache(maxsize=None)
def _forward(family: str, cfg_json: str, control: Optional[str]):
    import json
    import jax
    import jax.numpy as jnp
    from bench import reference
    cfg = json.loads(cfg_json)
    fwd = reference.family({"family": family}).forward

    def gaps(w, tokens, rows, served):
        """The gap of each served id, or (control) of the id that the
        reference at lower precision puts first."""
        ref = fwd(w, tokens, rows, cfg)
        best = jnp.max(ref, -1)
        pick = served
        if control:
            prec = reference.common.CONTROLS[control]
            pick = jnp.argmax(fwd(w, tokens, rows, cfg, prec), -1)
        chosen = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
        return best - chosen

    return jax.jit(gaps)


def reference_gaps(model: Dict[str, Any], seed: int, s: Sample,
                   control: Optional[str] = None) -> np.ndarray:
    """(n, N): the gap at each served position of each sampled request."""
    import json
    import jax
    w = weights.make(model, seed)
    fn = _forward(model["family"], json.dumps(model, sort_keys=True),
                  control)
    out = fn(w, s.tokens, s.rows, s.served)
    return np.asarray(jax.device_get(out), np.float64)


def numbers(gaps: np.ndarray) -> Dict[str, float]:
    return {"max_logit_gap": float(gaps.max()),
            "off_best_pct": 100.0 * float(np.mean(gaps > 0)),
            "mean_logit_gap": float(gaps.mean())}


def verdict(model: Dict[str, Any], gaps: np.ndarray) -> Dict[str, Any]:
    """Each number that has a limit, beside it; correct when there is one
    and every such number is within its limit."""
    got = numbers(gaps)
    compared = {k: {"value": got[k], "limit": v}
                for k, v in model["check"].items() if v is not None}
    ok = bool(compared) and all(np.isfinite(c["value"])
                                and c["value"] <= c["limit"]
                                for c in compared.values())
    return {"ok": ok, "numbers": got, "compared": compared}
