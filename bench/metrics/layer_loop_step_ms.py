"""Median over the decode executions of the traced batch of the device
time of the step's leaf ops in scope ``layer_loop``, its sub-scopes included
(``bench/scopes.py``; profiler trace and the step's HLO)."""
from bench import scopes


def read(r):
    if not r.decode_scopes:
        return None
    return scopes.step_ms(r.decode_scopes, "layer_loop")
