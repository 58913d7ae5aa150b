"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Shapes:
    single-pod : (16, 16)    axes ("data", "model")   = 256 chips (one v5e pod)
    multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

"pod" is the outer data-parallel axis crossing inter-pod DCI links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(n_devices: int = 1, model_parallel: int = 1):
    """Small mesh over locally visible devices (tests, examples)."""
    data = max(1, n_devices // model_parallel)
    return jax.make_mesh(
        (data, model_parallel), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
