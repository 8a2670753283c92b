"""Production mesh construction. A FUNCTION, not a module constant — importing
this module never touches jax device state.

Every mesh in the repo is built here, so callers — ServeEngine, the
drivers, the distributed tests — never construct ``Mesh(...)`` ad hoc.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """General mesh over the available devices, every axis ``Auto``.

    ``shape``/``axes`` as for ``jax.make_mesh`` — e.g.
    ``make_mesh((8,), ("data",))`` or ``make_mesh((2, 4), ("data", "model"))``.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small (data, model) mesh over host CPU devices for tests/drivers
    (requires XLA_FLAGS=--xla_force_host_platform_device_count set before
    jax init when forcing more devices than the host has)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return make_mesh((data, model), ("data", "model"))
