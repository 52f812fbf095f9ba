"""Plain float32 references of the benchmark's model families, independent
of the program under test.  A family is one module of this package, named
by the configuration's ``family`` and found by that name: a new family is
a new ``<family>.py`` here, with no list to edit.

A family module gives:

- ``layout(cfg)``: the weights' shapes and inits (``common.Leaf``);
- ``forward(w, tokens, rows, cfg, prec)``: logits at ``rows``;
- ``MODEL_KEYS``: configuration file key -> the program's ``ModelConfig``
  field, for the keys beyond ``harness.COMMON_KEYS``;
- ``block_counts(cfg, batch)``: the work of one serving step in the
  decoder blocks (``common.BlockCounts``; ``bench/counts.py`` adds the
  embedding, head and logits);
- ``TEST_CUT``: ``ModelConfig`` fields that cut the published model to a
  size a test run holds at the published widths
  (``bench/tests/test_control.py``).
"""
import importlib
import pkgutil
from types import ModuleType
from typing import Any, Dict, List

SHARED = ("common",)          # modules of this package that are no family


def names() -> List[str]:
    """The families present."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__)
                  if m.name not in SHARED)


def family(cfg: Dict[str, Any]) -> ModuleType:
    """The reference module of ``cfg``'s ``family``."""
    name = cfg["family"]
    if name in SHARED or name not in names():
        raise LookupError(f"no reference for family {name!r}: "
                          f"bench/reference holds {names()}")
    return importlib.import_module(f"{__name__}.{name}")
