"""Production mesh construction.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """jax.make_mesh with every axis Auto: the sharding rules place arrays
    with NamedShardings and let the partitioner propagate the rest."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever-devices-exist mesh for CPU smoke tests / examples."""
    n = jax.device_count()
    assert n % model == 0, (n, model)
    return _mesh((n // model, model), ("data", "model"))


def make_serving_mesh(model: int = 1, data: int = 1):
    """Explicit-size ("data", "model") mesh for the SPMD serving engine
    (serving/engine/sharded.py). Sizes are taken literally — the engine's
    exactness contract depends on them — and must fit the visible devices
    (force host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` off-TPU)."""
    if model < 1 or data < 1:
        raise ValueError(f"mesh axes must be >= 1, got model={model} "
                         f"data={data}")
    n = jax.device_count()
    if model * data > n:
        raise ValueError(
            f"serving mesh model={model} x data={data} needs "
            f"{model * data} devices, have {n} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={model * data} for a "
            f"host-device mesh)")
    return _mesh((data, model), ("data", "model"))
