"""PlaneState — the data plane's device state as one registered pytree.

Everything the step function threads through — table contents, the
instrumentation sketches, and the RW site guards — travels as a single
:class:`PlaneState` instead of loose dicts.  Because it is a registered
JAX pytree, the whole state can be

  * donated (``donate_argnums`` on the state argument: the previous
    step's buffers are reused in place, which is what makes per-step
    state threading free on accelerators),
  * sharded per leaf (a PlaneState of ``Sharding`` objects is a valid
    pytree-prefix for ``jax.jit`` in/out shardings), and
  * manipulated with ``jax.tree_util`` like any other JAX container.

The three fields:

  tables  table name -> field name -> device array (the match-action maps)
  instr   site id    -> sketch state (count-min + candidate ring)
  guards  table name -> (1,) int32, nonzero once the data plane wrote the
          table (the in-graph RW site guard, §4.3.6)

Every executable compiled by the engine follows one contract::

    step(params, state: PlaneState, batch) -> (out, PlaneState)

On a device mesh (``EngineConfig.mesh``) the canonical placement is
tables/guards replicated and each ``instr`` sketch leaf carrying a
leading per-device shard axis laid out over the mesh — built by
:func:`repro.distributed.sharding.plane_state_shardings` and installed
automatically by ``MorpheusEngine.compile``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict

import jax

Array = Any


@dataclass
class PlaneState:
    """The data plane's entire device state as one registered pytree.

    Thread it through every step (``step(params, state, batch) ->
    (out, state)``); never hold a reference to a state already handed to
    a donating executable — its buffers may have been reused."""
    tables: Dict[str, Dict[str, Array]]
    instr: Dict[str, Dict[str, Array]]
    guards: Dict[str, Array]

    def replace(self, **kw) -> "PlaneState":
        """A new PlaneState with the given fields swapped (leaves are
        shared, not copied)."""
        return dataclasses.replace(self, **kw)

    def copy(self) -> "PlaneState":
        """Deep-copy every leaf buffer.  Use before handing the state to a
        donating executable whose result you do not intend to keep (e.g.
        replaying the generic executable for a semantics check)."""
        import jax.numpy as jnp
        return jax.tree.map(jnp.copy, self)


jax.tree_util.register_dataclass(
    PlaneState, data_fields=("tables", "instr", "guards"), meta_fields=())
