"""Plain float32 references of the benchmark's model families, independent
of the program under test.  ``forward(cfg)`` picks one by the
configuration's ``family``."""
from . import dense, moe

FAMILIES = {"dense": dense, "moe": moe}


def family(cfg):
    return FAMILIES[cfg["family"]]
