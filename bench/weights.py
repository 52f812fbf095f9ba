"""The weights a run serves: the benchmark's own (``reference.common``),
made from the seed, then placed in the program's parameter tree.

The program's tree is read from the model it builds (``abstract_params``).
Each of its leaves takes the benchmark's leaf at the same path, or at the
path ``RENAMES`` gives; shapes and types have to agree, and every leaf of
the benchmark has to be taken.  A change of the program's layout that keeps
its arithmetic (a leaf renamed or moved) is one entry in ``RENAMES``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax

from bench import reference
from bench.reference import common

RENAMES: Dict[str, str] = {}       # program path -> benchmark path


def make(model: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The configuration's weights for ``seed``, in the reference's
    layout, on the default device."""
    layout = reference.family(model).layout(model)
    return common.make_weights(layout, seed, model["initializer_range"])


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def for_program(w: Dict[str, Any], abstract: Any) -> Any:
    """``w`` in the tree of the program's ``abstract`` parameters."""
    mine = {_path(p): a for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for p, want in paths:
        src = RENAMES.get(_path(p), _path(p))
        if src not in mine:
            raise KeyError(f"the program's leaf {_path(p)} has no weight "
                           f"{src} here")
        a = mine.pop(src)
        if a.shape != want.shape or a.dtype != want.dtype:
            raise ValueError(f"{src}: {a.dtype}{list(a.shape)} here, "
                             f"{want.dtype}{list(want.shape)} in the "
                             f"program")
        out.append(a)
    if mine:
        raise KeyError(f"the program takes no leaf for {sorted(mine)}")
    return jax.tree_util.tree_unflatten(treedef, out)
