"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e pod),
axes (data, model).  Multi-pod: 2x16x16 = 512 chips, axes (pod, data,
model) — the "pod" axis is the slow DCN/ICI-superlink dimension and only
ever carries data parallelism in our configs.

Every axis is ``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` (``distributed/meshctx.constrain``) and lets
the partitioner resolve gathers from vocab-sharded tables.
``jax.make_mesh`` defaults to explicit axes, under which both raise.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for CI-sized sharding tests (requires
    xla_force_host_platform_device_count set by the test harness)."""
    if multi_pod:
        return _auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))
