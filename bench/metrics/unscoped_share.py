"""Percent of the leaf-op device time of the decode executions of the
traced batch that lies in no named scope (``bench/scopes.py``; profiler
trace and the step's HLO)."""
from bench import scopes


def read(r):
    if not r.decode_scopes:
        return None
    return scopes.unscoped_share(r.decode_scopes)
