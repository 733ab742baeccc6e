"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """`jax.make_mesh` with every axis Auto: shardings propagate from
    the `constrain` hints (parallel/context.py), not from explicit
    per-array types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh for smoke tests."""
    return make_mesh((1, 1), ("data", "model"))


def make_elastic_mesh(n_devices: int, model: int = 16):
    """Degraded-fleet mesh: keep the model axis intact, shrink data.
    Used by the elastic-scaling path (runtime/elastic.py) after node loss."""
    data = n_devices // model
    if data < 1:
        raise ValueError(f"need >= {model} devices, have {n_devices}")
    devs = jax.devices()[: data * model]
    return make_mesh((data, model), ("data", "model"), devices=devs)
