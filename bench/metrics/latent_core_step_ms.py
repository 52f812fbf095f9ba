"""Median over the decode executions of the traced batch of the device
time of the step's leaf ops in scope ``attention/core``: what reads the
latent slab, scores, softmax and p.c_kv (``bench/scopes.py``; profiler
trace and the step's HLO).  Silent in a step with no such scope."""
from bench import scopes


def read(r):
    if not r.decode_scopes:
        return None
    return scopes.step_ms(r.decode_scopes, "attention/core")
