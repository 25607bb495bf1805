"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state. Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods x 256 =
512 chips (pod, data, model); the pod axis is an outer data-parallel axis —
gradients reduce over ("pod", "data"), parameters shard over "model".
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the repo shards through
    ``with_sharding_constraint`` and jit shardings, which only bind to Auto
    axes (``make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(n_devices: Optional[int] = None, model_parallel: int = 1,
                  axes: Tuple[str, ...] = ("data", "model")):
    """Elastic mesh: build the best (data, model) grid for whatever devices
    are alive — used by the trainer after restarts on fewer/more hosts."""
    n = n_devices or len(jax.devices())
    if n % model_parallel:
        raise ValueError(f"{n} devices do not split into model_parallel="
                         f"{model_parallel}")
    return _auto_mesh((n // model_parallel, model_parallel), axes)


def make_population_mesh(n_devices: Optional[int] = None, axis: str = "pop"):
    """1-D mesh over the GA *population* axis: every alive device becomes
    one population shard for the sharded candidate evaluator
    (``distributed.pop_sharding``). The search workload is embarrassingly
    parallel over candidates, so a flat axis is the whole topology — use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise it
    on a CPU host."""
    n = n_devices or len(jax.devices())
    return _auto_mesh((n,), (axis,))
