"""Production mesh factories.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real (single) device.

``auto_axis_kwargs`` marks every mesh axis Auto (the sharding
propagation mode these meshes are written for).
"""
from __future__ import annotations

import jax


def auto_axis_kwargs(axes) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * len(axes)}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **auto_axis_kwargs(axes))


def make_elastic_mesh(n_devices: int, *, model_parallel: int = 16,
                      axis_names=("data", "model")):
    """Degraded-capacity mesh after node failures: keeps the model axis
    intact (shard layout of the checkpoint) and shrinks the data axis."""
    while model_parallel > 1 and n_devices % model_parallel != 0:
        model_parallel //= 2
    data = n_devices // model_parallel
    return jax.make_mesh((data, model_parallel), axis_names,
                         **auto_axis_kwargs(axis_names))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (requires host-device flag)."""
    return jax.make_mesh(shape, axes, **auto_axis_kwargs(axes))
