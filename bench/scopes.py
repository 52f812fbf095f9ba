"""Put the decode step's device time down to the program's named scopes.

Which scope each instruction of the compiled step lies in is the
program's contract: ``repro.obs.scopes.scope_map`` reads it from the
compiled step's HLO text (``as_text()``; DESIGN_OBS.md).
:func:`decode_scopes` sums a profiler trace's leaf-op device time by
scope over each execution of the step in the decode phase of the traced
batch, the executions ``run.Reading.decode_steps`` reads; ``bench/run.py``
puts the result on ``Reading.decode_scopes`` for the metric readers.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

from bench import trace
from repro.obs.scopes import UNSCOPED


def decode_scopes(xplane: str, scopes: Dict[str, str], program: str,
                  window_span: str, n_decode: int
                  ) -> Optional[List[Dict[str, float]]]:
    """Per execution of ``program`` in the decode phase (the last
    ``n_decode`` inside ``window_span``, on the first chip), leaf-op
    device seconds by scope path; an op the map does not know counts as
    ``UNSCOPED``.  None where the trace holds fewer executions."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane)
    _, window = trace._host_spans(pd, window_span)
    chips = trace._device_timelines(pd, program)
    if not chips or n_decode < 1:
        return None
    ops, runs = chips[0]
    runs = sorted((s, e) for _, s, e in runs
                  if s >= window[0] and e <= window[1])
    if len(runs) < n_decode:
        return None
    leaves = trace.leaves([(n, s, e) for n, s, e in ops
                           if window[0] <= s and e <= window[1]])
    out = []
    for s0, e0 in runs[-n_decode:]:
        by = defaultdict(float)
        for n, s, e in leaves:
            if s0 <= s and e <= e0:
                by[scopes.get(n, UNSCOPED)] += (e - s) / 1e9
        out.append(dict(by))
    return out


def totals(per_step: List[Dict[str, float]]) -> Dict[str, float]:
    """Decode-phase seconds by scope path, summed over the executions."""
    out: Dict[str, float] = defaultdict(float)
    for step in per_step:
        for k, v in step.items():
            out[k] += v
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def step_ms(per_step: List[Dict[str, float]], scope: str
            ) -> Optional[float]:
    """Median over the executions of the milliseconds spent in ``scope``
    and its sub-scopes; None where no execution ran an op in it."""
    import numpy as np
    inside = [[v for k, v in step.items()
               if k == scope or k.startswith(scope + "/")]
              for step in per_step]
    if not any(inside):
        return None
    return 1e3 * float(np.median([sum(v) for v in inside]))


def top_level_ms(per_step: List[Dict[str, float]]) -> Dict[str, float]:
    """``step_ms`` of each top-level scope, and of ``UNSCOPED``."""
    tops = sorted({k.split("/")[0] for step in per_step for k in step})
    return {t: step_ms(per_step, t) for t in tops}


def unscoped_share(per_step: List[Dict[str, float]]) -> float:
    """Percent of the decode phase's leaf-op time in no scope."""
    t = totals(per_step)
    return 100.0 * t.get(UNSCOPED, 0.0) / sum(t.values())
