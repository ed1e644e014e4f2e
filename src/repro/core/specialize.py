"""SpecializationPlan + lookup dispatch.

The plan is the engine's output: per call site, which implementation to
trace.  It is HASHABLE — the runtime caches one compiled executable per
distinct plan *signature* (the TPU analogue of Morpheus' generated
machine code: trace-time constants specialize the jaxpr, XLA folds and
DCEs, and the executable is swapped atomically by the dispatcher).
``signature`` carries exactly the trace-time constants; ``version``
carries plan identity for the host-side program guard and never enters
the traced code, so behaviorally identical plans at different table
versions share one executable (see ``repro.core.execcache``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops


@dataclass(frozen=True)
class SiteSpec:
    impl: str = "gather"       # gather | onehot | hot_cache | inline_const
                               # | const_row | eliminated | moe_fastpath
                               # | ssd_fastpath
    hot_keys: Tuple[int, ...] = ()
    guarded: bool = False      # RW site guard (guard elision decides)
    const_fields: Tuple[Tuple[str, Any], ...] = ()   # const-prop per field
    inline_fields: Tuple[Tuple[str, Any], ...] = ()  # full inlined content


@dataclass(frozen=True)
class SpecializationPlan:
    version: int = -1                                # TableSet version
    sites: Tuple[Tuple[str, SiteSpec], ...] = ()
    flags: Any = None                                # dict flag name -> bool
    instrumented: bool = False
    label: str = "generic"

    def __post_init__(self):
        # site dispatch runs once per call site per trace: a dict probe,
        # not a linear scan (quadratic on many-site planes).  Not a
        # dataclass field — excluded from eq/hash/replace.
        object.__setattr__(self, "_site_map", dict(self.sites))

    def site(self, site_id: str) -> Optional[SiteSpec]:
        """The SiteSpec planned for ``site_id`` (None = stay generic)."""
        return self._site_map.get(site_id)

    def fastpath_keys(self, table: Optional[str] = None,
                      impl: str = "moe_fastpath"
                      ) -> Optional[Tuple[int, ...]]:
        """Hot set a branch-injection pass (``moe_fastpath``,
        ``ssd_fastpath``, ...) planned for one of ``table``'s lookup
        sites (any table when None; one site when ``table`` is a site id
        such as ``router#2``), or None when no such site was
        specialized.  A trace-time constant — the caller compiles its
        injected branch in or leaves it out entirely."""
        for sid, spec in self.sites:
            if spec.impl != impl:
                continue
            if table is None or sid == table or sid.split("#")[0] == table:
                return spec.hot_keys or None
        return None

    def hot_experts(self, table: Optional[str] = None
                    ) -> Optional[Tuple[int, ...]]:
        """Hot set the MoE fast-path pass planned for ``table`` (any
        table when None), or None when no such site was specialized."""
        return self.fastpath_keys(table, "moe_fastpath")

    @property
    def signature(self):
        """Executable identity: exactly the trace-time constants — sites
        (with their inlined values / hot sets), pinned flags, and whether
        this is the instrumented twin.  Deliberately excludes ``version``:
        two plans with equal signatures trace to identical jaxprs, so one
        compiled executable serves both.  Plan *identity* (is the active
        plan stale?) lives in ``version`` and is checked host-side by the
        dispatcher's program guard — never baked into the code."""
        return (self.sites, tuple(sorted((self.flags or {}).items())),
                self.instrumented)

    @property
    def key(self):
        """Full plan identity: ``(version, *signature)``."""
        return (self.version,) + self.signature


GENERIC_PLAN = SpecializationPlan(flags={})


def _gather(table_state, idx, fields):
    names = fields or tuple(table_state.keys())
    return {f: jnp.take(table_state[f], idx, axis=0) for f in names}


def _onehot(table_state, idx, fields, n_valid: int):
    """Small-table lookup as a one-hot matmul — data-structure
    specialization (§4.3.4) adapted to the MXU: for tables of tens of
    rows, compute beats HBM gather latency on TPU."""
    names = fields or tuple(table_state.keys())
    out = {}
    for f in names:
        t = table_state[f][:n_valid]
        if jnp.issubdtype(t.dtype, jnp.floating) and t.ndim >= 2:
            # contract the one-hot axis against the table's row axis;
            # tensordot keeps this rank-polymorphic in idx (class ids
            # are (batch,), token ids (batch, seq))
            oh = jax.nn.one_hot(idx, n_valid, dtype=t.dtype)
            out[f] = jnp.tensordot(oh, t, axes=([-1], [0]))
        else:
            out[f] = jnp.take(t, jnp.clip(idx, 0, n_valid - 1), axis=0)
    return out


def _sharded_hot_gather(table, hot_rows, hot_ids, flat_idx, mesh, axes):
    """``kops.hot_gather`` on a device mesh.  A Pallas kernel cannot be
    partitioned by XLA, so it runs under ``shard_map``: every device
    gathers its own shard of the queries from the replicated table and
    hot rows.  Queries are padded with row 0 to a multiple of the shard
    count; the padded rows are dropped."""
    from jax.sharding import PartitionSpec as P
    from ..distributed.compat import shard_map

    n = int(np.prod([mesh.shape[a] for a in axes]))
    T = flat_idx.shape[0]
    pad = (-T) % n
    if pad:
        flat_idx = jnp.pad(flat_idx, (0, pad))
    spec = P(tuple(axes))
    res = shard_map(kops.hot_gather, mesh=mesh,
                    in_specs=(P(), P(), P(), spec),
                    out_specs=spec)(table, hot_rows, hot_ids, flat_idx)
    return res[:T]


def _hot_cache(table_state, idx, fields, hot_keys_arr, mesh=None,
               axes=("data",)):
    """Fast-path cache (§4.3.1): heavy-hitter rows served from a small
    VMEM-resident copy (Pallas ``hot_gather`` on TPU), cold rows from the
    full HBM table.  Semantics identical to a plain gather."""
    names = fields or tuple(table_state.keys())
    hot_ids = jnp.asarray(hot_keys_arr, jnp.int32)
    out = {}
    for f in names:
        t = table_state[f]
        if t.ndim >= 2 and jnp.issubdtype(t.dtype, jnp.floating):
            hot_rows = jnp.take(t, hot_ids, axis=0)
            flat_idx = idx.reshape(-1)
            with jax.named_scope("hot_gather"):
                if mesh is None:
                    res = kops.hot_gather(t, hot_rows, hot_ids, flat_idx)
                else:
                    res = _sharded_hot_gather(t, hot_rows, hot_ids,
                                              flat_idx, mesh, axes)
            out[f] = res.reshape(*idx.shape, *t.shape[1:])
        else:
            out[f] = jnp.take(t, idx, axis=0)
    return out


def dispatch_lookup(plan, site_id: str, name: str, table_state, idx,
                    fields, guards, mesh=None, axes=("data",)):
    """Trace ``plan``'s implementation of one lookup site, its device ops
    under the named scope ``tables.<name>``.  ``mesh`` / ``axes`` (the
    sharded runtime's) place kernels that XLA cannot partition on their
    own."""
    with jax.named_scope(f"tables.{name}"):
        return _dispatch_lookup(plan, site_id, name, table_state, idx,
                                fields, guards, mesh, axes)


def _dispatch_lookup(plan, site_id, name, table_state, idx, fields,
                     guards, mesh, axes):
    state = table_state[name]
    spec = plan.site(site_id) if plan is not None else None
    if spec is None or spec.impl in ("gather", "moe_fastpath",
                                     "ssd_fastpath"):
        # the *_fastpath impls specialize the *caller's* dispatch
        # (branch injection); the claimed lookup itself stays a plain
        # gather.
        return _gather(state, idx, fields)

    if spec.impl == "eliminated":
        # empty table (§4.3.1 table elimination): defaults, no memory touch
        names = fields or tuple(state.keys())
        out = {}
        for f in names:
            t = state[f]
            shape = idx.shape + t.shape[1:]
            const = (spec.const_fields and dict(spec.const_fields).get(f))
            if const is not None:
                out[f] = jnp.broadcast_to(jnp.asarray(const, t.dtype), shape)
            else:
                out[f] = jnp.zeros(shape, t.dtype)
        return out

    if spec.impl == "inline_const":
        # whole table baked into the executable as trace-time constants —
        # XLA constant-folds; protected by the program-level guard.
        names = fields or tuple(state.keys())
        inline = dict(spec.inline_fields)
        const_state = {f: jnp.asarray(inline[f]) for f in names}
        n_valid = len(next(iter(inline.values())))
        return _onehot(const_state, idx, names, n_valid)

    if spec.impl == "const_row":
        # every live row identical -> constant propagation (§4.3.2):
        # the lookup result does not depend on idx at all.
        names = fields or tuple(state.keys())
        consts = dict(spec.const_fields)
        out = {}
        for f in names:
            t = state[f]
            val = jnp.asarray(consts[f], t.dtype)
            out[f] = jnp.broadcast_to(val, idx.shape + t.shape[1:])
        return out

    if spec.impl == "hot_cache":
        fast = lambda: _hot_cache(state, idx, fields,
                                  np.asarray(spec.hot_keys, np.int32),
                                  mesh, axes)
        if spec.guarded and guards is not None and name in guards:
            # RW site guard: fall back to the plain gather once the data
            # plane has written the table (deoptimization, §4.3.6)
            ok = guards[name][0] == 0
            return jax.lax.cond(ok, fast, lambda: _gather(state, idx,
                                                          fields))
        return fast()

    if spec.impl == "onehot":
        t0 = next(iter(state.values()))
        n_valid = int(t0.shape[0])
        return _onehot(state, idx, fields, n_valid)

    raise ValueError(f"unknown impl {spec.impl!r} for site {site_id}")
