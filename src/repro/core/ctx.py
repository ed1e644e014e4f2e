"""DataPlaneCtx — the single data-plane API.

User data-plane code (serving step, train step) is written against this
context instead of raw arrays:

    def serve_step(params, ctx, batch):
        cls = ctx.lookup("req_class", batch["class_id"])
        if ctx.flag("vision_enabled"):
            ...
        ctx.update("sessions", batch["slot"], {...})

The ctx carries the active SpecializationPlan (trace-time!) and the
:class:`~repro.core.state.PlaneState` — tables, instrumentation sketches
and RW guards; lookups dispatch through the plan and fold instrumentation
in when this trace is the instrumented variant.

Flags and plan flags are keyed by flag *name* (not by site id): the same
feature consulted at two call sites is one control-plane fact and pins
both branches together.

On a device mesh (``EngineConfig.mesh``) the ctx records instrumentation
*per device*: each sketch leaf carries a leading shard axis and the
record runs under ``shard_map`` so every device folds only its local
shard of the looked-up keys into its own sketch slice — zero cross-device
traffic on the serving path.  The engine merges the slices into one
global traffic snapshot at plan time.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import instrument, tables as T
from .specialize import dispatch_lookup
from .state import PlaneState


class DataPlaneCtx:
    """Dispatch context threaded through one trace of the step function.

    Built by :meth:`MorpheusEngine.make_step_fn` from the incoming
    :class:`PlaneState`; mutated in place by ``lookup``/``update`` while
    tracing; read back as the step's output state via :meth:`outputs`.

    ``mesh``/``instr_axes`` (from ``EngineConfig``) select the sharded
    instrumentation path; with ``mesh=None`` recording is the classic
    single-sketch update.
    """

    def __init__(self, plan, state: PlaneState,
                 sketch_cfg: instrument.SketchConfig,
                 mesh=None, instr_axes: Tuple[str, ...] = ("data",),
                 rows_valid: Optional[jax.Array] = None):
        self.plan = plan
        self.tables = dict(state.tables)
        self.instr = dict(state.instr)
        self.guards = dict(state.guards)
        self.sketch_cfg = sketch_cfg
        self.mesh = mesh
        self.instr_axes = instr_axes
        self.rows_valid = rows_valid

    # ---- instrumentation ----------------------------------------------------
    def _record(self, site_id: str, idx: jax.Array) -> None:
        """Fold this lookup's keys into the site's sketch — per device
        (``shard_map``) when the sketch is sharded, else globally.

        With ``rows_valid`` (the batch's ``(B,)`` row mask), the keys of
        a lookup indexed per batch row are dropped for pad rows: the
        sketch counts the requests served, not the copies of row 0 that
        pad a bucket.  Counting those copies flooded the candidate ring
        whenever a window was mostly padding, and the hot set then
        depended on how full the last sampled window happened to be."""
        valid = self.rows_valid
        if (valid is not None and idx.ndim >= 1
                and idx.shape[0] == valid.shape[0]):
            idx = jnp.where(
                valid.reshape(valid.shape + (1,) * (idx.ndim - 1)), idx, -1)
        st = self.instr[site_id]
        if self.mesh is not None and instrument.n_shards(st) is not None:
            self.instr[site_id] = instrument.record_sharded(
                st, idx, self.sketch_cfg, self.mesh, self.instr_axes)
        else:
            self.instr[site_id] = instrument.record(st, idx,
                                                    self.sketch_cfg)

    # ---- data-plane API ---------------------------------------------------
    def lookup(self, name: str, idx: jax.Array,
               fields: Optional[Tuple[str, ...]] = None):
        """Read rows ``idx`` of table ``name`` (all fields, or just
        ``fields``), returning ``{field: array}`` with the table's row
        shape appended to ``idx``'s shape.  Dispatches through the plan's
        SiteSpec for this call site (gather / one-hot / hot-row cache /
        inlined constants / ...) and records instrumentation when this
        trace is the instrumented executable."""
        site_id = T._register(name, "lookup", fields or ())
        if (self.plan is not None and self.plan.instrumented
                and site_id in self.instr):
            self._record(site_id, idx)
        return dispatch_lookup(self.plan, site_id, name, self.tables,
                               idx, fields, self.guards, self.mesh,
                               self.instr_axes)

    def lookup_or_none(self, name: str, idx: jax.Array,
                       fields: Optional[Tuple[str, ...]] = None):
        """Like :meth:`lookup`, but when the plan marks this site
        ELIMINATED (empty table, §4.3.1) returns None at trace time — the
        caller's whole branch drops out of the jaxpr, exactly like the
        paper removing the lookup call from the datapath."""
        site_id = T._register(name, "lookup", fields or ())
        spec = self.plan.site(site_id) if self.plan is not None else None
        if spec is not None and spec.impl == "eliminated":
            return None
        if (self.plan is not None and self.plan.instrumented
                and site_id in self.instr):
            self._record(site_id, idx)
        return dispatch_lookup(self.plan, site_id, name, self.tables,
                               idx, fields, self.guards, self.mesh,
                               self.instr_axes)

    def update(self, name: str, idx: jax.Array,
               values: Dict[str, jax.Array]) -> None:
        """Data-plane write: scatter ``values`` into rows ``idx`` of the
        RW table ``name``.  The new contents travel in the step's output
        :class:`PlaneState`; the table's in-graph guard is invalidated in
        the same step (§4.3.6), deoptimizing any specialization that
        assumed the old contents."""
        T._register(name, "update")
        state = dict(self.tables[name])
        for k, v in values.items():
            state[k] = state[k].at[idx].set(v.astype(state[k].dtype))
        self.tables[name] = state
        if name in self.guards:
            # invalidate the site guard in the same step (§4.3.6)
            self.guards[name] = jnp.ones_like(self.guards[name])

    def flag(self, name: str, default: bool = True):
        """Read feature flag ``name`` as a trace-time Python bool.  When
        the plan pins the flag (dead-code pass), the pinned value is
        returned and the untaken branch never enters the jaxpr; on the
        generic plan the ``default`` is used."""
        T._register(name, "flag")
        plan_flags = getattr(self.plan, "flags", None) or {}
        if name in plan_flags:
            return plan_flags[name]       # trace-time constant -> DCE
        return default

    def hot_experts(self, table: str, site: Optional[int] = None
                    ) -> Optional[Tuple[int, ...]]:
        """Hot set the MoE fast-path pass planned for ``table``'s lookup
        site (branch injection, §4.3.5), or None when the pass did not
        fire.  ``site`` picks the ``site``-th lookup of ``table`` in the
        step (one per MoE layer); None takes the first planned.  A
        trace-time constant: the caller's hot path is compiled in or left
        out entirely."""
        key = table if site is None else f"{table}#{site}"
        return self.fastpath_keys(key, "moe_fastpath")

    def fastpath_keys(self, table: str, impl: str = "moe_fastpath"
                      ) -> Optional[Tuple[int, ...]]:
        """Hot set a branch-injection pass (``moe_fastpath``,
        ``ssd_fastpath``, ...) planned for one of ``table``'s lookup
        sites, or None when the pass did not fire.  A trace-time
        constant, like :meth:`hot_experts`."""
        if self.plan is None:
            return None
        return self.plan.fastpath_keys(table, impl)

    def table_array(self, name: str, field: str) -> jax.Array:
        """Raw read of one field's full backing array (current in-trace
        contents, including prior ``update`` writes).  For
        branch-injected code ONLY: a ``lax.cond`` slow branch gathering
        rows the fast branch provably does not need must not go through
        :meth:`lookup` — a lookup inside one branch would register a
        call site (and record instrumentation) that the other branch
        lacks.  No site is registered and nothing is recorded here; the
        sanctioned callers pair this with an unconditional cheap lookup
        (e.g. the SSD fast path's ``count`` site) that keeps the table
        instrumented."""
        return self.tables[name][field]

    def outputs(self) -> PlaneState:
        """The step's output :class:`PlaneState`: tables (with any
        data-plane writes), updated sketches, and guards."""
        return PlaneState(self.tables, self.instr, self.guards)
