"""Thin wrappers over the distributed JAX API used across the repo.

  * :func:`shard_map` — ``jax.shard_map`` with the replication check
    off by default;
  * :func:`abstract_mesh` — a device-free ``AbstractMesh`` from sizes and
    names.
"""
from __future__ import annotations

from typing import Sequence

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check disabled by default
    (our bodies use collectives whose replication the checker cannot
    prove)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def abstract_mesh(axis_sizes: Sequence[int],
                  axis_names: Sequence[str]):
    """Device-free ``jax.sharding.AbstractMesh``."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))
