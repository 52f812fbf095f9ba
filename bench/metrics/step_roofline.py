"""The decode step's share of its roofline over the decode phase of the
traced batch: the sum over its executions of the least time the needed
work takes at the chip's peaks (``bench/counts.py``, ``bench/peaks.json``),
over the sum of their device times (profiler trace)."""
from bench import counts


def read(r):
    steps = r.decode_steps()
    if not steps:
        return None
    B = r.traced_batch().generated.shape[0]
    least = sum(counts.least_seconds(r.model, B, filled, r.peak)
                for filled, _ in steps)
    return 100.0 * least / sum(s for _, s in steps)
