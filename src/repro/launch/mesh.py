"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: shardings come from the
    ``with_sharding_constraint`` annotations in parallel/sharding.py, which
    jax.make_mesh's default ``Explicit`` axes would refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one 256-chip v5e pod) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh(n_devices: int | None = None, pods: int = 1):
    """Small host-device mesh for CPU tests (requires XLA_FLAGS device count)."""
    n = n_devices or len(jax.devices())
    if pods > 1:
        per = n // pods
        model = 2 if per % 2 == 0 else 1
        return _auto_mesh((pods, per // model, model),
                          ("pod", "data", "model"))
    model = 2 if n % 2 == 0 else 1
    return _auto_mesh((n // model, model), ("data", "model"))
