"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — only ``dryrun.py`` (which sets
``--xla_force_host_platform_device_count=512`` before any jax import) and real
TPU launches ever call it with the full shapes.

Mesh axes are ``Auto``: the model code places activations with
``with_sharding_constraint`` (``parallel.sharding.constrain``), which only
refers to ``Auto`` axes, and ``jax.make_mesh`` would otherwise make them
``Explicit``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """A small mesh over whatever devices exist (tests / single host)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(1, data)))
    return _auto_mesh((data, model), ("data", "model"))
