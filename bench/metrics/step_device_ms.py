"""Median device time of one execution of the decode step in the decode
phase of the traced batch (profiler trace)."""
import numpy as np


def read(r):
    steps = r.decode_steps()
    if not steps:
        return None
    return float(np.median([s for _, s in steps])) * 1e3
