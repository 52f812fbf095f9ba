"""Median over the window's batches of ``prefill_s`` per prompt position:
one token step of the prompt phase, dispatch included (host clock)."""
import numpy as np


def read(r):
    return float(np.median([b.prefill_s * 1e3 / b.prompts.shape[1]
                            for b in r.window.batches]))
