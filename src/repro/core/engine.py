"""The Morpheus compilation pipeline (§4, Fig. 3).

    analyze (offline, once)  ->  read instrumentation  ->  run the pass
    registry  ->  trace + XLA-compile the specialized executable  ->
    hand to the runtime for the atomic swap.

Timing mirrors Table 3: ``t1`` = analysis + table/sketch read + pass
planning; ``t2`` = trace + XLA compile of the specialized executable.

The engine is deliberately *loop-free*: it plans and compiles when
asked, but when/how often cycles run, which sketches are being recorded,
and where compiles execute are all decided a layer up — by
:class:`~repro.core.controller.MorpheusController` (sampling duty
cycles, the bounded recompile worker pool, snapshot workers), with
:class:`~repro.core.runtime.MorpheusRuntime` as the data-plane half.

The step function's contract is::

    step(params, state: PlaneState, batch) -> (out, PlaneState)

One pytree in, one pytree out — which is what lets ``compile`` donate
the state argument (buffer reuse across steps) and accept per-leaf
sharding specs (a PlaneState of Shardings is a valid jit prefix).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import instrument
from .ctx import DataPlaneCtx
from .instrument import SketchConfig
from .passes import PassRegistry, PlanInputs, default_registry
from .specialize import GENERIC_PLAN, SpecializationPlan
from .state import PlaneState
from .tables import TableSet, analysis_sites, analyzing, \
    reset_site_counters
from .tracing import span


def rows_valid(batch) -> Optional[jax.Array]:
    """The batch's row mask, where it has one: the boolean ``(B,)``
    ``"valid"`` leaf that the serving frontend's ``make_request_batch``
    adds to mark real rows among a bucket's pad rows."""
    valid = batch.get("valid") if isinstance(batch, dict) else None
    if valid is None or valid.ndim != 1 or valid.dtype != jnp.bool_:
        return None
    return valid


@dataclass
class EngineConfig:
    """Static configuration of one :class:`MorpheusEngine`.

    ``mesh`` switches the engine into sharded-serving mode: tables and
    guards are replicated over the mesh, instrumentation sketches carry
    one slice per device along ``instr_axes`` (updated locally under
    ``shard_map``), and ``compile`` derives default per-leaf
    ``in_shardings``/``out_shardings`` for the whole
    ``(params, state, batch)`` signature.  ``mesh=None`` (the default)
    is the classic single-device engine."""
    sketch: SketchConfig = field(default_factory=SketchConfig)
    features: Dict[str, bool] = field(default_factory=dict)
    moe_router_table: Optional[str] = None   # table backing MoE routing
    ssd_state_table: Optional[str] = None    # table backing SSM state
    passes: Optional[PassRegistry] = None    # None => default_registry
    donate: bool = True                      # donate PlaneState buffers
    mesh: Optional[Any] = None               # jax Mesh => sharded serving
    instr_axes: Tuple[str, ...] = ("data",)  # sketch/batch mesh axes
    # --- executable cache (repro.core.execcache) ---
    signature_cache: bool = True   # key executables by plan.signature
                                   # (False: by plan.key, i.e. the
                                   # version-keyed baseline — every plan
                                   # churn recompiles; benchmarks only)
    exec_cache_capacity: int = 64  # LRU entries when the runtime builds
                                   # its own ExecutableCache
    cache_ns: Optional[str] = None  # namespace inside a *shared* cache;
                                    # same ns + same cache => runtimes
                                    # share executables (requires equal
                                    # step fn / schemas / shapes)

    @property
    def n_instr_shards(self) -> Optional[int]:
        """Per-site sketch count in sharded mode (None when unsharded)."""
        if self.mesh is None:
            return None
        n = 1
        for a in self.instr_axes:
            n *= self.mesh.shape[a]
        return n


class MorpheusEngine:
    """Plans and compiles specialized executables for one data plane."""

    def __init__(self, user_step: Callable, tables: TableSet,
                 cfg: Optional[EngineConfig] = None):
        self.user_step = user_step
        self.tables = tables
        self.cfg = cfg or EngineConfig()
        self.registry = (self.cfg.passes if self.cfg.passes is not None
                         else default_registry(self.cfg.moe_router_table,
                                               self.cfg.ssd_state_table))
        self.sites = []
        self.mutability: Dict[str, str] = {}
        self._analyzed = False
        # t2 counters: every trace+lower / XLA compile this engine runs.
        # The zero-retrace tests assert these stay flat across
        # revalidated or cache-hit recompile cycles.  Incremented under
        # a lock: the runtime compiles the specialized + instrumented
        # twins on concurrent threads, and a torn += would drop counts.
        self.lower_count = 0
        self.compile_count = 0
        self._count_lock = threading.Lock()

    # ---- §4.1 static code analysis ---------------------------------------
    def analyze(self, params, example_batch) -> Dict[str, Any]:
        """Offline static analysis (run once before anything else):
        abstractly trace ``user_step`` to register every table call site,
        then classify tables RO/RW (any in-plane ``ctx.update`` makes a
        table RW; an explicit ``Table.mutability`` annotation wins).
        Returns ``{"n_sites", "mutability", "analyze_s"}``."""
        t0 = time.time()
        state = PlaneState(self.tables.device_state(), {}, {})

        def traced(p, b):
            reset_site_counters()
            ctx = DataPlaneCtx(GENERIC_PLAN, state, self.cfg.sketch)
            out = self.user_step(p, ctx, b)
            return out

        with analyzing():
            jax.eval_shape(traced, params, example_batch)
        self.sites = analysis_sites()

        # RO/RW classification: any in-plane update => RW; explicit table
        # annotation wins.
        written = {s.table for s in self.sites if s.kind == "update"}
        for name, t in self.tables.tables.items():
            if t.mutability != "auto":
                self.mutability[name] = t.mutability
            else:
                self.mutability[name] = "rw" if name in written else "ro"
        self._analyzed = True
        return {"n_sites": len(self.sites),
                "mutability": dict(self.mutability),
                "analyze_s": time.time() - t0}

    # ---- state plumbing ----------------------------------------------------
    def instrumented_sites(self):
        """Lookup sites that get a sketch: instrumentation is on for the
        table and the table is too big to inline (§4.2 dim 1)."""
        out = []
        for s in self.sites:
            if s.kind != "lookup":
                continue
            t = self.tables[s.table]
            if t.instrument and t.n_valid > t.max_inline:
                out.append(s.site_id)
        return out

    def init_instr_state(self, sites=None):
        """Fresh sketch state per instrumented site — sharded (one slice
        per device along ``cfg.instr_axes``) when the engine has a mesh.
        ``sites`` pins the site set explicitly: callers that snapshot
        the instrumented-site tuple once per recompile cycle pass it
        here so the built structure cannot drift from the snapshot if a
        concurrent control update moves ``n_valid`` across the inline
        threshold mid-cycle."""
        if sites is None:
            sites = self.instrumented_sites()
        n = self.cfg.n_instr_shards
        return {sid: instrument.init_site_state(self.cfg.sketch, n)
                for sid in sites}

    def init_guards(self):
        """Zeroed in-graph guards, one per RW table (§4.3.6): nonzero
        once the data plane writes the table."""
        import jax.numpy as jnp
        return {name: jnp.zeros((1,), jnp.int32)
                for name, mut in self.mutability.items() if mut == "rw"}

    def init_state(self) -> PlaneState:
        """Fresh device state for this data plane (run analyze first)."""
        assert self._analyzed
        return PlaneState(self.tables.device_state(),
                          self.init_instr_state(), self.init_guards())

    # ---- §4.2 + §4.3: read instrumentation, run the registry ---------------
    def build_plan(self, instr_state, instrumented: bool = False,
                   snapshot=None, version: Optional[int] = None,
                   profile: Optional[Dict[str, Any]] = None
                   ) -> Tuple[SpecializationPlan, float, Dict]:
        """Plan a specialized executable: read the (already merged,
        host-side) instrumentation sketches, snapshot the tables, and
        walk every analyzed call site through the pass registry.

        ``instr_state`` maps site id -> *unsharded* sketch state (the
        runtime merges per-device sketches before calling; sharded
        layouts are merged here as a fallback).  ``snapshot``/``version``
        inject a pre-taken table snapshot — the off-thread snapshot
        worker's versioned handoff — and must be passed *together*: the
        plan is stamped with the snapshot's version, so a control-plane
        update racing past the snapshot deopts the plan via the
        program-level guard rather than corrupting it.  (Stamping a
        stale snapshot with the live version would defeat that guard,
        hence the ValueError.)  ``profile`` is an optional request-level
        traffic snapshot (the serving frontend's arrival profile —
        arrival rate, batch-size histogram, pad-bucket occupancy),
        exposed to plan-level passes as ``PlanInputs.profile``.

        Returns ``(plan, t1_seconds, pass_stats)``."""
        assert self._analyzed
        t0 = time.perf_counter()
        if snapshot is None:
            # read the version BEFORE copying: an update racing in
            # between then makes the plan look stale (spurious deopt,
            # safe) instead of fresher than its contents (unsafe)
            if version is None:
                version = self.tables.version
            snapshot = self.tables.snapshot()
        elif version is None:
            raise ValueError(
                "build_plan(snapshot=...) needs the snapshot's version= "
                "— stamping an injected snapshot with the live TableSet "
                "version would disable the deopt guard")
        hot_stats = {}
        for sid, st in (instr_state or {}).items():
            if instrument.n_shards(st) is not None:
                st = instrument.merge_shards(st)
            hot, cov, total = instrument.hot_keys(st, self.cfg.sketch)
            # the heaviest keys, in canonical order: the same hot set
            # plans the same signature however the estimates rank it
            hot_stats[sid] = (np.sort(hot), cov)

        inputs = PlanInputs(mutability=dict(self.mutability),
                            hot_stats=hot_stats, sketch=self.cfg.sketch,
                            features=dict(self.cfg.features),
                            profile=profile)
        draft = self.registry.build(self.sites, snapshot, inputs)
        specs = {sid: spec for sid, spec in draft.specs.items()
                 if spec is not None}

        plan = SpecializationPlan(
            version=version,
            sites=tuple(sorted(specs.items())),
            flags=dict(draft.flags),
            instrumented=instrumented,
            label="specialized" + ("+instr" if instrumented else ""),
        )
        return plan, time.perf_counter() - t0, dict(draft.stats)

    def generic_plan(self, instrumented: bool = False) -> SpecializationPlan:
        """The unspecialized plan (every site generic, no flags pinned)
        at the TableSet's current version — the deopt target and the
        reference-semantics oracle."""
        return SpecializationPlan(
            version=self.tables.version, sites=(),
            flags={}, instrumented=instrumented,
            label="generic" + ("+instr" if instrumented else ""))

    # ---- step-function construction + compile ------------------------------
    def make_step_fn(self, plan: SpecializationPlan) -> Callable:
        """Wrap ``user_step(params, ctx, batch)`` into the engine's
        ``step(params, state, batch) -> (out, state)`` contract: build a
        :class:`DataPlaneCtx` carrying ``plan`` (trace-time constants)
        and the incoming state, run the user code, and return the ctx's
        updated :class:`PlaneState` alongside the user output."""
        def step(params, state: PlaneState, batch):
            reset_site_counters()
            ctx = DataPlaneCtx(plan, state, self.cfg.sketch,
                               mesh=self.cfg.mesh,
                               instr_axes=self.cfg.instr_axes,
                               rows_valid=rows_valid(batch))
            out = self.user_step(params, ctx, batch)
            return out, ctx.outputs()
        return step

    def make_fused_step_fn(self, plan: SpecializationPlan,
                           k: int) -> Callable:
        """The ``lax.scan``-fused K-step variant of
        :meth:`make_step_fn`: one executable runs K consecutive serving
        steps, threading the :class:`PlaneState` through the scan carry
        (table writes, sketches and guards accumulate exactly as K
        single steps would).  The batch argument carries a leading
        window axis of size K; outputs come back stacked the same way.
        Trace-time constants (the plan) are hoisted to window
        granularity — which is what lets one Python dispatch amortize
        over K steps."""
        step = self.make_step_fn(plan)

        def fused(params, state: PlaneState, batches):
            def body(carry, batch):
                out, carry = step(params, carry, batch)
                return carry, out

            state, outs = jax.lax.scan(body, state, batches, length=k)
            return outs, state
        return fused

    def default_shardings(self, state: PlaneState, batch, *,
                          stacked: bool = False):
        """The sharded-serving placement for ``(params, state, batch)``:
        params replicated, ``state`` via
        :func:`repro.distributed.sharding.plane_state_shardings` (tables
        replicated, sketches device-local), batch sharded on its leading
        dim — or, with ``stacked=True`` (fused K-step executables), on
        the per-step dim under an unsharded leading window axis.
        Returns ``(in_shardings, out_shardings)`` prefix pytrees for
        :meth:`compile`, or ``(None, None)`` without a mesh."""
        if self.cfg.mesh is None:
            return None, None
        from jax.sharding import NamedSharding, PartitionSpec
        from ..distributed.sharding import plane_batch_shardings, \
            plane_state_shardings
        mesh, axes = self.cfg.mesh, self.cfg.instr_axes
        state_sh = plane_state_shardings(state, mesh, axes)
        batch_sh = plane_batch_shardings(batch, mesh, axes,
                                         stacked=stacked)
        params_sh = NamedSharding(mesh, PartitionSpec())
        # out sharding: user output left to propagation (None), state
        # pinned to its input placement so donation can reuse buffers.
        return (params_sh, state_sh, batch_sh), (None, state_sh)

    def lower(self, plan: SpecializationPlan, params, state: PlaneState,
              batch, *, donate: Optional[bool] = None,
              in_shardings=None, out_shardings=None,
              fuse: Optional[int] = None):
        """Stage 1 of ``t2``: build the step function for ``plan`` and
        trace + lower it against the concrete ``(params, state, batch)``
        avals.  Returns the jax ``Lowered`` object; stage 2
        (``.compile()``, the XLA invocation) is separate so callers can
        overlap several compiles — XLA compilation releases the GIL, so
        the runtime XLA-compiles the specialized and instrumented twins
        concurrently on the recompile thread.  ``fuse=K`` lowers the
        ``lax.scan``-fused K-step executable instead (``batch`` then
        carries a leading window axis of size K)."""
        step = (self.make_step_fn(plan) if fuse is None
                else self.make_fused_step_fn(plan, fuse))
        donate = self.cfg.donate if donate is None else donate
        if (self.cfg.mesh is not None and in_shardings is None
                and out_shardings is None):
            in_shardings, out_shardings = self.default_shardings(
                state, batch, stacked=fuse is not None)
        kw: Dict[str, Any] = {}
        if donate:
            kw["donate_argnums"] = (1,)
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        jitted = jax.jit(step, **kw)
        lowered = jitted.lower(params, state, batch)
        with self._count_lock:
            self.lower_count += 1
        return lowered

    def compile(self, plan: SpecializationPlan, params, state: PlaneState,
                batch, *, donate: Optional[bool] = None,
                in_shardings=None, out_shardings=None,
                fuse: Optional[int] = None,
                cycle: Optional[int] = None
                ) -> Tuple[Callable, float]:
        """AOT-compile ``plan`` into an executable; returns
        ``(executable, t2_seconds)`` where the executable is called as
        ``out, new_state = executable(params, state, batch)``.

        Both ``t2`` stages back to back: :meth:`lower` (trace + lower),
        then the XLA compile.  The PlaneState argument is donated by
        default (``cfg.donate``): the executable may write the new state
        into the old state's buffers, so treat the passed-in state as
        consumed.  ``in_shardings``/``out_shardings`` pass through to
        ``jax.jit`` (prefix pytrees over ``(params, state, batch)`` / the
        ``(out, state)`` result) for per-leaf placement; when the engine
        has a mesh and neither is given, :meth:`default_shardings`
        supplies the sharded-serving placement.  ``cycle``, the ordinal
        of the recompile cycle that asked (None outside one), tags the
        ``morpheus.engine.compile`` span as its stat ``n``."""
        stats = {"fuse": fuse or 0}
        if cycle is not None:
            stats["n"] = cycle
        with span("engine.compile", **stats):
            t0 = time.perf_counter()
            lowered = self.lower(plan, params, state, batch, donate=donate,
                                 in_shardings=in_shardings,
                                 out_shardings=out_shardings, fuse=fuse)
            compiled = lowered.compile()
            with self._count_lock:
                self.compile_count += 1
            return compiled, time.perf_counter() - t0
