"""The whole serving loop's share of the chip's peak FLOP/s over the traced
batch: the model FLOPs of all its token steps (``bench/counts.py``) over
the traced window's wall time times the peak (``bench/peaks.json``)."""
from bench import counts


def read(r):
    if r.trace is None:
        return None
    b = r.traced_batch()
    B, n = b.generated.shape[0], b.prompts.shape[1] + b.generated.shape[1] - 1
    flops = sum(counts.step_counts(r.model, B, j + 1)[0] for j in range(n))
    return 100.0 * flops / (r.trace.window_s * r.peak["bf16_flops_per_s"])
