"""Mesh factories: every mesh the repo builds comes from ``make_mesh``.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS for 512 host devices *before* any jax
import; tests and benches see the single real CPU device).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto``.  ``jax.make_mesh`` defaults to
    ``Explicit`` axes, and ``with_sharding_constraint`` (the activation
    hints of :mod:`repro.dist.sharding`) refuses specs that name them.
    ``devices`` (row-major over ``shape``) pins the device order; left
    None, jax picks a topology-aware order over all devices."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    devs = np.asarray(list(devices)).reshape(tuple(shape))
    return Mesh(devs, tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (CPU smoke tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh((data, model), ("data", "model"))
