"""Morpheus runtime: the pure data-plane half (dispatch + atomic update).

The runtime owns the executables and plays the role of the eBPF
``BPF_PROG_ARRAY`` swap:

  * **program-level guard**: one host-side version compare per step — if
    the control plane touched any table since the active plan was built,
    traffic routes to the *generic* executable until the background
    recompile lands (deoptimization without data-plane disruption);
  * **adaptive instrumentation**: sampled steps run the instrumented
    twin of the current executable; the cadence — and whether the twin
    is installed at all — is decided by the plane's
    :class:`~repro.core.controller.sampling.PlaneSampling` state machine
    on the controller;
  * **atomic update**: recompilation happens off-thread; control-plane
    updates arriving mid-compile are queued and replayed after the swap;
    the swap itself is a Python reference assignment.

Everything *control-loop* shaped lives in
:class:`~repro.core.controller.MorpheusController` — the off-thread
``t1`` snapshot workers, the shared signature-keyed
:class:`~repro.core.execcache.ExecutableCache`, the adaptive sampling
scheduler, and the bounded recompile worker pool that replaces the old
per-runtime compile threads.  A runtime registers itself with a
controller at construction; passing ``controller=None`` builds a
*private* controller so the classic single-plane API is unchanged
(``rt.close()`` closes it along with the runtime).  Several runtimes
passed the same controller form one fleet: one executable cache, one
recompile scheduler prioritizing planes by staleness x traffic, per-plane
sampling duty cycles driven by plan churn.

Device state lives in one :class:`PlaneState` pytree (``runtime.state``)
threaded through every executable; the executables donate its buffers, so
after a step the *previous* state must be treated as consumed.  State
transitions follow a **seqlock/epoch protocol** instead of one step-wide
mutex: dispatch reads the atomic ``_active`` tuple plus the generation
counter ``_gen``, claims the single in-flight step slot with a brief
validated acquire, runs the executable **outside any lock**, and commits
the fresh state with a second brief critical section.  Writers — the
background recompile's swap, control-plane table refreshes — quiesce: they
wait for the in-flight step to commit, mutate under the lock, and bump
``_gen`` so any dispatch prepared against the old world revalidates and
retries.  Control updates arriving while a step (or fused window) is in
flight are queued and drained at commit, so the control plane never
blocks behind device execution.  For semantics checks use
:meth:`run_generic`, a non-donating twin of the generic executable; when
replaying a *donating* executable by hand, pass it ``state.copy()``.

:meth:`step_many` is the fused fast path: a ``lax.scan``-fused K-step
executable (cached in the :class:`ExecutableCache` with K in the key)
amortizes the per-step Python dispatch K-fold.  The program guard and
the sampling decision are hoisted to window granularity — a control
update landing mid-window deopts the *next* window, same §4.4 semantics
as single-stepping.  :meth:`place_batch` is the non-blocking prefetch
half: it device-places a batch asynchronously (arrays already committed
with the right sharding pass through untouched), so a serve loop can
overlap the H2D of batch N+1 with the compute of batch N.

Instrumentation readout is **double-buffered**
(:class:`~repro.core.instrument.SketchDoubleBuffer`): each sampled step
publishes a device-side copy of the freshly recorded sketches (dispatch
only, under the lock the step already holds), and the controller's
``t1`` reads that quiesced back buffer — the device->host transfer runs
with **no runtime lock held**, so planning never stalls the serving
path.

Sharded serving (``EngineConfig.mesh``): the same runtime spans a device
mesh.  Tables and guards are replicated; each device keeps its own
instrumentation sketch slice, updated locally inside the jitted step
(``shard_map``); at plan time the slices are psum-merged on device into
one global traffic snapshot — the per-core eBPF pipelines of the paper
mapped onto a JAX mesh.  On a 1-device host pass ``mesh=None`` and every
mesh code path degrades to the classic behavior.

``t2`` is paid only for genuinely new code: executables live in the
signature-keyed :class:`~repro.core.execcache.ExecutableCache` (plan
*signature* excludes the table version), a recompile cycle whose planned
signature equals the active one just *revalidates* — restamps the plan's
version under the lock, zero trace/compile/swap — and when the
specialized + instrumented twins do need compiling, their XLA compiles
run concurrently.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# placement indirection: every batch transfer the runtime performs goes
# through this hook, so tests (and the zero-transfer regression in
# benchmarks/bench_dispatch.py) can count actual H2D placements
_device_put = jax.device_put


@jax.jit
def _stack_window(batches):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


def stack_batches(batches: Sequence[Any]):
    """Stack K same-shaped batches into one pytree with a leading window
    axis — the input contract of :meth:`MorpheusRuntime.step_many`'s
    fused executable, K=1 included.  One jitted call stacks the whole
    window, whatever its K and leaf count (jit's cache keys it by
    structure): an eager op per leaf costs a host dispatch each.  Use
    :meth:`MorpheusRuntime.place_batch` with ``fused=True`` to also
    device-place the stack ahead of dispatch."""
    return _stack_window(tuple(batches))


def _induced_window_avals(plan, fused_shapes):
    """Window shapes a batch-shape-selecting plan will *induce*: when
    :class:`~repro.core.passes.batch_shape.BatchShapePass` planned
    ``(buckets, K)``, the batcher will form ``(bucket, k=1)`` windows
    for every pad bucket plus ``(primary, 2..K)`` overflow chunks —
    shapes that may never have been served yet.  Derive their stacked
    avals from the most recently served window structure by resizing
    the two leading (window, batch) axes; returns
    ``[((bkey, k), avals), ...]`` for the recompile cycle to precompile
    alongside the shapes traffic has already shown."""
    from .passes.batch_shape import plan_batch_shape
    sel = plan_batch_shape(plan)
    if sel is None or not fused_shapes:
        return []
    buckets, kk = sel
    primary = buckets[-1]
    want = [(b, 1) for b in buckets]
    want += [(primary, j) for j in range(2, max(kk, 1) + 1)]
    _, template = fused_shapes[-1]          # MRU structure
    if any(len(s.shape) < 2 for s in jax.tree.leaves(template)):
        return []                           # not a stacked batch pytree
    out = []
    for b, j in want:
        avals = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((j, b) + s.shape[2:],
                                           s.dtype), template)
        out.append(((batch_key(avals), j), avals))
    return out

from .controller import ControllerConfig, MorpheusController
from .engine import EngineConfig, MorpheusEngine
from .execcache import ExecutableCache, batch_key
from .histogram import StreamingHistogram
from . import instrument
from .snapshot import TableSnapshotWorker, VersionedSnapshot
from .specialize import SpecializationPlan
from .state import PlaneState
from .tables import TableSet
from .tracing import install_gc_spans, span


@dataclass
class RuntimeStats:
    """Counters and timing histories of one runtime (all host-side).

    Mutated concurrently by the dispatch path, the control plane, and
    the controller's recompile workers — every write goes through
    :meth:`bump` (scalar counters), :meth:`log` (histories) or
    :meth:`observe`/:meth:`observe_many` (latency histograms) under one
    internal lock, so no increment is ever torn or lost.  Plain
    attribute *reads* are fine for printouts and tests;
    :meth:`snapshot` returns a consistent plain-dict copy (what
    ``controller.stats()`` aggregates across planes).

    Latency distributions (step latency, the serving frontend's
    per-request queue/batch/execute/total waits) all go through ONE
    implementation — named :class:`~repro.core.histogram.\
StreamingHistogram` series in ``hists`` — so p50/p99 everywhere in the
    repo mean the same thing and fleet aggregation is a bucket-wise
    merge."""
    steps: int = 0
    deopt_steps: int = 0          # routed to generic by the program guard
    instr_steps: int = 0
    recompiles: int = 0
    swaps: int = 0
    revalidations: int = 0        # cycles that only restamped the version
    cache_hits: int = 0           # executables served from the exec cache
    cache_misses: int = 0         # executables that had to be compiled
    queued_updates: int = 0
    batch_transfers: int = 0      # actual H2D batch placements performed
    # ---- request-level accounting (repro.serving.frontend) ----
    requests_submitted: int = 0
    requests_rejected: int = 0    # admission control: bounded queue full
    requests_shed: int = 0        # deadline expired before dispatch
    requests_completed: int = 0
    slo_met: int = 0              # completed with deadline, in time
    slo_missed: int = 0           # completed with deadline, late
    batches_formed: int = 0
    pad_rows: int = 0             # padding rows dispatched (occupancy)
    shape_mispredicts: int = 0    # batches whose ideal pad bucket was
                                  # not in the active plan's bucket set
    locked_calls: int = 0         # stats-lock acquisitions (bump/log/
                                  # observe) — the dispatch fast path
                                  # must make at most ONE per step or
                                  # fused window (regression-checked by
                                  # benchmarks/bench_dispatch.py)
    # ---- fleet health (repro.core.controller.health) ----
    faults: int = 0               # dispatch-layer faults survived
    degraded_steps: int = 0       # steps served generic-only (degraded)
    recoveries: int = 0           # degraded -> specialized swaps
    straggler_events: int = 0     # StragglerMonitor mitigations fired
    requests_rejected_degraded: int = 0   # admissions shed PLANE_DEGRADED
    requests_failed: int = 0      # in-flight requests lost to a fault
    warm_errors: List[str] = field(default_factory=list)  # failed
                                  # background fused-generic warms
    t1_history: List[float] = field(default_factory=list)
    t2_history: List[float] = field(default_factory=list)
    swap_history: List[float] = field(default_factory=list)
    pass_stats: Dict[str, int] = field(default_factory=dict)
    snapshot_versions: List[int] = field(default_factory=list)
    hists: Dict[str, "StreamingHistogram"] = field(default_factory=dict)

    def __post_init__(self):
        self._lock = threading.Lock()

    def bump(self, **deltas: int) -> None:
        """Atomically add ``deltas`` to the named scalar counters.  One
        call is one lock acquisition however many counters it carries —
        the dispatch path coalesces every per-step delta into a single
        ``bump`` at commit."""
        with self._lock:
            self.locked_calls += 1
            for name, d in deltas.items():
                setattr(self, name, getattr(self, name) + d)

    def log(self, name: str, value) -> None:
        """Atomically append ``value`` to the named history list."""
        with self._lock:
            self.locked_calls += 1
            getattr(self, name).append(value)

    def observe(self, name: str, value: float, **counters: int) -> None:
        """Record one sample into the named latency histogram (created
        on first use), optionally bumping scalar counters in the SAME
        lock acquisition."""
        with self._lock:
            self.locked_calls += 1
            h = self.hists.get(name)
            if h is None:
                h = self.hists[name] = StreamingHistogram()
            h.observe(value)
            for cname, d in counters.items():
                setattr(self, cname, getattr(self, cname) + d)

    def observe_many(self, series: Dict[str, Sequence[float]],
                     **counters: int) -> None:
        """Record many samples across several histograms plus any scalar
        counter deltas in ONE lock acquisition — the serving frontend
        coalesces a whole fused window's per-request timings (4 series x
        up to K·bucket requests) into a single locked call, same
        discipline as the dispatch path's single ``bump`` per window."""
        with self._lock:
            self.locked_calls += 1
            for name, values in series.items():
                h = self.hists.get(name)
                if h is None:
                    h = self.hists[name] = StreamingHistogram()
                h.observe_all(values)
            for cname, d in counters.items():
                setattr(self, cname, getattr(self, cname) + d)

    def quantile(self, name: str, q: float) -> float:
        """The q-quantile of the named histogram (NaN when absent or
        empty) — e.g. ``stats.quantile("request_total_s", 0.99)``."""
        with self._lock:
            h = self.hists.get(name)
            return h.quantile(q) if h is not None else float("nan")

    def hist(self, name: str) -> Optional["StreamingHistogram"]:
        """A consistent copy of the named histogram, or None."""
        with self._lock:
            h = self.hists.get(name)
            return h.copy() if h is not None else None

    def reset_hist(self, *names: str) -> None:
        """Drop the named histogram series (e.g. to exclude a warmup
        phase from the timed run's quantiles)."""
        with self._lock:
            for name in names:
                self.hists.pop(name, None)

    def snapshot(self) -> Dict[str, Any]:
        """A consistent plain-dict copy of every field (lists/dicts
        shallow-copied; histograms reduced to their plain-dict
        ``summary()``) — safe to aggregate while the runtime serves."""
        with self._lock:
            out: Dict[str, Any] = {}
            for f in dataclasses.fields(self):
                v = getattr(self, f.name)
                if f.name == "hists":
                    v = {k: h.summary() for k, h in v.items()}
                elif isinstance(v, list):
                    v = list(v)
                elif isinstance(v, dict):
                    v = dict(v)
                out[f.name] = v
            return out


_NS_COUNTER = itertools.count()


def _instr_has_samples(instr: Dict[str, Dict[str, Any]]) -> bool:
    """Did this sketch window record anything?  A window with zero
    totals (no sampled step since the last cycle — e.g. the sampler
    backed way off) carries no information about traffic, as opposed to
    evidence that traffic vanished."""
    return any(int(np.asarray(st.get("total", 0)).sum()) > 0
               for st in instr.values())


class MorpheusRuntime:
    """Serve one data plane under dynamic recompilation.

    Call :meth:`step` with request batches (the data plane),
    :meth:`control_update` / :meth:`set_feature` from the control plane,
    and :meth:`recompile` to run one Morpheus cycle.  The engine's
    contract for every executable is
    ``step(params, state, batch) -> (out, state)`` with the state
    argument donated.

    Parameters: ``user_step(params, ctx, batch)`` written against
    :class:`~repro.core.ctx.DataPlaneCtx`; the :class:`TableSet`;
    model params; one example batch (shapes drive AOT compilation); an
    :class:`EngineConfig` (set ``cfg.mesh`` for sharded serving);
    ``enable=False`` to pin the generic executable (baselines);
    ``controller=`` to join an existing
    :class:`~repro.core.controller.MorpheusController` fleet (omit it
    for a private single-plane controller); ``exec_cache=`` to override
    the controller's shared executable cache; ``plane_id=`` to name the
    plane in controller stats.
    """

    def __init__(self, user_step: Callable, tables: TableSet, params,
                 example_batch, cfg: Optional[EngineConfig] = None,
                 enable: bool = True,
                 exec_cache: Optional[ExecutableCache] = None,
                 controller: Optional[MorpheusController] = None,
                 plane_id: Optional[str] = None):
        install_gc_spans()
        self.engine = MorpheusEngine(user_step, tables, cfg)
        self.tables = tables
        self.enable = enable
        self.stats = RuntimeStats()
        self.mesh = self.engine.cfg.mesh

        # ---- join (or build) the control plane ----
        self._private_controller = controller is None
        if controller is None:
            controller = MorpheusController(ControllerConfig(
                exec_cache_capacity=self.engine.cfg.exec_cache_capacity))
        self.controller = controller
        self.plane_id = controller.register(self, plane_id)
        self.sampler = controller.sampler_for(self.plane_id)
        # tear the control loop down when the owner drops the runtime
        # without close(): a private controller dies with its plane, a
        # shared one just stops this plane's snapshot worker.  Neither
        # finalizer holds a reference back to the runtime (the
        # controller's plane table is weak), so this cannot leak.  The
        # handle is kept so close() can detach it — a closed runtime's
        # later GC must not unregister a NEW plane reusing its plane_id.
        if self._private_controller:
            self._finalizer = weakref.finalize(self, controller.close)
        else:
            self._finalizer = weakref.finalize(
                self, controller.unregister, self.plane_id)

        self.analysis = self.engine.analyze(params, example_batch)
        self.params = self._place_params(params)
        self.state: PlaneState = self._place_state(self.engine.init_state())

        # every executable this runtime holds — specialized, instrumented
        # twin, generic, run_generic oracles — lives in the controller's
        # shared LRU ExecutableCache keyed by plan *signature* (no
        # version); each runtime namespaces its keys unless
        # EngineConfig.cache_ns opts into full sharing.  An explicit
        # ``exec_cache=`` overrides the controller's (tests, baselines).
        self.exec_cache = (exec_cache if exec_cache is not None
                           else controller.exec_cache)
        # process-unique default namespace: id(self) can be recycled by
        # the allocator after a runtime dies, which would serve a dead
        # runtime's executables out of a shared cache
        self._cache_ns = (self.engine.cfg.cache_ns
                         if self.engine.cfg.cache_ns is not None
                         else f"rt-{next(_NS_COUNTER)}")
        # ---- seqlock'd dispatch state ----
        # `_lock` + `_cond` protect the tiny claim/commit critical
        # sections; the executable itself always runs with NO lock held.
        # `_stepping` is the single in-flight step slot (state donation
        # serializes steps per plane anyway); `_writers` counts writers
        # waiting to quiesce (steps hold off so writers cannot starve);
        # `_gen` is the generation counter every committed writer bumps —
        # dispatch work prepared outside the lock (e.g. a fused
        # executable fetched for the active plan) is validated against
        # it at claim time and retried on mismatch.
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._gen = 0
        self._stepping = False
        self._writers = 0
        self._step_seq = 0            # dispatch ordinal (sampling cadence)
        self._window_seq = 0          # fused-window ordinal
        self._fused_memo: Dict[Any, Callable] = {}   # gen-scoped, see
                                                     # _fused_exec
        # the most recent (batch structure, K) pairs step_many has
        # served, as stacked avals: recompile cycles precompile fused
        # executables for these alongside the single-step twins, so a
        # swap (or deopt) never stalls a fused window on an inline XLA
        # compile.  LRU-bounded — per-cycle precompile work (and
        # time-to-swap) must not grow with every structure ever seen.
        from collections import OrderedDict
        self._fused_shapes: "OrderedDict[Any, Any]" = OrderedDict()
        self._fused_shapes_cap = 8
        self._warm_threads: List[threading.Thread] = []
        self._recompile_mutex = threading.Lock()
        self._compiling = False
        # recompile-cycle ordinal, and the one the current thread is
        # running (read by _compile_into_cache to tag engine.compile)
        self._cycle_seq = 0
        self._cycle_local = threading.local()
        self._queued: List[tuple] = []
        self._closed = False
        self._merge_fn: Optional[Callable] = None
        self._batch_sh_cache: Dict[Any, Any] = {}
        # ---- fleet health (dispatch fault boundary) ----
        # `_degraded` flips only under _write() (so every claim's gen
        # validation observes it); while set, dispatch is generic-only
        # regardless of the guard — the fault that set it proved the
        # specialized/instrumented executables unsafe.  `_fault_injector`
        # is the chaos hook (distributed/fault.py FailureInjector): its
        # check runs INSIDE the step's try-block BEFORE the executable,
        # so an injected fault aborts the claim with the state tuple
        # untouched (not donated) and the same batch can be retried.
        self._degraded = False
        self._degrade_reason: Optional[str] = None
        self._fault_injector: Optional[Any] = None
        self._compile_faults = 0      # armed recompile-cycle failures
        self._last_plan_signature: Optional[Any] = None
        self.last_snapshot: Optional[VersionedSnapshot] = None
        self._steps_at_cycle = 0
        # the sketch snapshot retained from the last ARMED cycle: while
        # the sampler has the instrumented twin swapped out, plans keep
        # being built from this profile instead of an empty one (which
        # would drop every traffic-dependent fast path and oscillate)
        self._plan_instr: Dict[str, Dict[str, Any]] = {}

        # generic + generic-instrumented executables (always available;
        # the runtime holds direct references so cache eviction can
        # never take the deopt target away)
        self.generic_plan = self.engine.generic_plan()
        self._active_isites = self._isites()
        example_batch = self._place_batch(example_batch)
        gen_exec, gen_instr = self._get_many(
            [self.generic_plan,
             self._instr_twin(self.generic_plan, self._active_isites)],
            example_batch, self._active_isites)
        self.generic_instr_exec = gen_instr
        # the active (plan, exec, instr_exec, generic_exec) tuple: ONE
        # attribute, so dispatch reads a consistent set with a single
        # reference load while a background recompile swaps it — the
        # generic deopt target is part of the tuple because a topology-
        # changing swap replaces it together with the state structure
        self._active: Tuple[SpecializationPlan, Callable, Callable,
                            Callable] = (
            self.generic_plan, gen_exec, gen_instr, gen_exec)
        self._example_batch = example_batch
        # optional traffic-profile source (the serving frontend's
        # ArrivalProfile): snapshotted at each recompile cycle and
        # merged into the plan inputs — see attach_profile
        self._traffic_profile: Optional[Any] = None

        # double-buffered instrumentation: publish the initial (zeroed)
        # sketches now — this also compiles the tiny jitted copy fn
        # outside any lock, so steady-state publishes are dispatch-only
        self._backbuf = instrument.SketchDoubleBuffer()
        self._backbuf.publish(self.state.instr)

        # warm the plan-time psum merge now, while nothing is serving:
        # its one-time jit compile must never happen under the runtime
        # lock (it would stall every in-flight step behind t1)
        if self.mesh is not None and self.state.instr:
            jax.block_until_ready(
                self._merge_instr_on_device(self.state.instr))

    # ---- mesh placement ----------------------------------------------------
    def _place_params(self, params):
        """Replicate params over the mesh (no-op without one)."""
        if self.mesh is None:
            return params
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(params,
                              NamedSharding(self.mesh, PartitionSpec()))

    def _place_state(self, state: PlaneState) -> PlaneState:
        """Lay a PlaneState out over the mesh: tables/guards replicated,
        sketches device-local (no-op without a mesh)."""
        if self.mesh is None:
            return state
        from ..distributed.sharding import plane_state_shardings
        return jax.device_put(
            state, plane_state_shardings(state, self.mesh,
                                         self.engine.cfg.instr_axes))

    def _batch_shardings(self, batch, stacked: bool):
        """The (cached) per-leaf sharding pytree for a batch structure.
        Batch shapes are pinned by the AOT-compile contract, so
        steady-state steps pay one dict probe, not a tree_map of fresh
        NamedShardings."""
        key = (batch_key(batch), stacked)
        sh = self._batch_sh_cache.get(key)
        if sh is None:
            from ..distributed.sharding import plane_batch_shardings
            sh = plane_batch_shardings(batch, self.mesh,
                                       self.engine.cfg.instr_axes,
                                       stacked=stacked)
            self._batch_sh_cache[key] = sh
        return sh

    @staticmethod
    def _batch_resident(batch, sh) -> bool:
        """True when every leaf is already a committed device array whose
        sharding matches the target — re-placing it would be a wasted
        transfer (and a wasted dispatch) every step."""
        for leaf, want in zip(jax.tree.leaves(batch), jax.tree.leaves(sh)):
            if not isinstance(leaf, jax.Array):
                return False
            have = leaf.sharding
            if have == want:
                continue
            try:
                if not have.is_equivalent_to(want, leaf.ndim):
                    return False
            except (AttributeError, TypeError):
                return False
        return True

    def _place_batch(self, batch, *, stacked: bool = False,
                     count: Optional[dict] = None):
        """Shard a request batch over the mesh (no-op without one).
        Arrays whose committed sharding already matches the target pass
        through untouched — a batch placed once (or prefetched via
        :meth:`place_batch`) is never re-``device_put`` on later steps.
        ``stacked`` selects the fused-window layout (leading K axis
        unsharded, per-step batch dim sharded).  ``count`` (a mutable
        dict) receives a ``transfers`` delta instead of a locked stats
        bump, so the dispatch path stays at one stats call per step."""
        if self.mesh is None:
            return batch
        sh = self._batch_shardings(batch, stacked)
        if self._batch_resident(batch, sh):
            return batch
        if count is not None:
            count["transfers"] = count.get("transfers", 0) + 1
        return _device_put(batch, sh)

    def place_batch(self, batch, *, fused: bool = False):
        """Public prefetch API: device-place ``batch`` ahead of dispatch
        (non-blocking — ``device_put`` dispatches asynchronously), so a
        pipelined serve loop overlaps the H2D of batch N+1 with the
        compute of batch N.  With ``fused=True``, ``batch`` is a
        *sequence* of K per-step batches: they are stacked along a
        leading window axis and placed in the fused layout that
        :meth:`step_many` consumes.  Already-resident arrays pass
        through untouched, so prefetching — or re-stepping — the same
        placed batch performs zero transfers."""
        count: dict = {}
        with span("runtime.place") as s:
            if fused and isinstance(batch, (list, tuple)):
                batch = stack_batches(batch)
            placed = self._place_batch(batch, stacked=fused, count=count)
            s.set_metadata(transfers=count.get("transfers", 0))
        if count:
            self.stats.bump(batch_transfers=count["transfers"])
        return placed

    # ---- executable cache --------------------------------------------
    @property
    def plan(self) -> SpecializationPlan:
        """The active plan (read from the atomic ``_active`` tuple)."""
        return self._active[0]

    @property
    def exec(self) -> Callable:
        """The active specialized executable."""
        return self._active[1]

    @property
    def instr_exec(self) -> Callable:
        """The active instrumented twin (the specialized executable
        itself while the sampler has instrumentation disarmed)."""
        return self._active[2]

    @property
    def generic_exec(self) -> Callable:
        """The active generic (deopt target) executable — swapped with
        the rest of the tuple when the instr topology changes."""
        return self._active[3]

    def _instr_twin(self, plan: SpecializationPlan,
                    isites: Tuple[str, ...]) -> SpecializationPlan:
        """The instrumented twin of ``plan`` — ``plan`` itself when no
        site is instrumented (``isites``, the caller's once-per-cycle
        snapshot): with nothing to record, the twin traces to identical
        code, so one executable serves both dispatch roles.  A disarmed
        sampler passes ``isites=()`` — that is how the twin gets swapped
        out entirely."""
        if plan.instrumented or not isites:
            return plan
        return dataclasses.replace(plan, instrumented=True,
                                   label=plan.label + "+instr")

    def _isites(self) -> Tuple[str, ...]:
        """Canonical identity of a *fresh* sketch window's structure:
        the sorted instrumented site ids.  Executables are AOT-compiled
        against a concrete PlaneState treedef, and ``state.instr``'s
        keys are the one structural component the control plane can
        change (e.g. ``n_valid`` crossing the inline threshold flips a
        site in or out of instrumentation) — so this tuple is part of
        every cache key and of the revalidation condition."""
        return tuple(sorted(self.engine.instrumented_sites()))

    def _exec_key(self, plan: SpecializationPlan, batch,
                  donate: bool, instr_struct: Tuple[str, ...],
                  fuse: Optional[int] = None):
        """Cache key for ``plan`` × ``batch`` structure × the instr
        structure the executable was lowered against: the plan's
        *signature* (version-free — behaviorally identical plans share
        one executable), or its full version-stamped ``key`` when
        ``EngineConfig.signature_cache`` is off (the version-keyed
        baseline benchmarks measure against).  ``donate=False`` is the
        non-donating oracle twin; ``fuse=K`` is the ``lax.scan``-fused
        K-step executable (K is part of the key — a fused window and a
        single step never alias)."""
        pkey = (plan.signature if self.engine.cfg.signature_cache
                else plan.key)
        return ExecutableCache.make_key(self._cache_ns,
                                        (pkey, instr_struct),
                                        batch_key(batch), donate,
                                        fuse=fuse)

    def _get_oracle(self, batch) -> Tuple[Callable, Tuple[str, ...]]:
        """Fetch (or compile) the non-donating ``run_generic`` oracle
        for the LIVE state structure, returning ``(exe, instr_struct)``.
        Reads ``self.state`` ONCE so the cache key and the lowering
        avals describe the same object even under a concurrent swap;
        kept out of the serving cache counters and the ``t2`` history
        (an oracle compile is not part of a Morpheus cycle)."""
        state = self.state
        instr_struct = tuple(sorted(state.instr.keys()))
        key = self._exec_key(self.generic_plan, batch, False,
                             instr_struct)
        exe = self.exec_cache.probe(key)    # miss accounting happens in
        if exe is None:                     # get_or_compile, not twice
            exe = self._compile_into_cache(
                [(self.generic_plan, False)], batch, state=state,
                instr_struct=instr_struct, serving=False)[0]
        return exe, instr_struct

    def _compile_into_cache(self, plans: List[Tuple[SpecializationPlan,
                                                    bool]],
                            batch, *, state: PlaneState,
                            instr_struct: Tuple[str, ...],
                            serving: bool = True,
                            fuse: Optional[int] = None) -> List[Callable]:
        """Compile every ``(plan, donate)`` pair against ``state``'s
        avals and insert it into the cache.  Two or more pairs compile
        concurrently — one thread per executable; XLA compilation
        releases the GIL, so the specialized and instrumented twins' t2
        overlaps on the recompile path.  Compiles go through
        ``ExecutableCache.get_or_compile``, so when several data planes
        sharing one cache (``EngineConfig.cache_ns``) chase the same
        fleet-wide config push, each key is XLA-compiled by exactly one
        plane and the rest wait for its insert (no compile stampede).
        ``serving=False`` (the oracle) keeps RuntimeStats' t2 history
        and cache counters untouched — they describe the Morpheus cycle,
        not oracle traffic (the cache's own ``stats`` always count)."""
        results: List[Any] = [None] * len(plans)
        cycle = getattr(self._cycle_local, "n", None)

        def compile_one(i: int, plan: SpecializationPlan, donate: bool):
            key = self._exec_key(plan, batch, donate, instr_struct,
                                 fuse=fuse)
            try:
                results[i] = ("ok", self.exec_cache.get_or_compile(
                    key, lambda: self.engine.compile(
                        plan, self.params, state, batch, donate=donate,
                        fuse=fuse, cycle=cycle)))
            except BaseException as e:          # re-raised on the caller
                results[i] = ("err", e)

        if len(plans) == 1:
            compile_one(0, *plans[0])
        else:
            threads = [threading.Thread(
                target=compile_one, args=(i, plan, donate),
                name=f"morpheus-compile-{i}", daemon=True)
                for i, (plan, donate) in enumerate(plans)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        out = []
        for (plan, donate), (status, payload) in zip(plans, results):
            if status == "err":
                raise payload
            compiled, t2 = payload
            if serving:
                if t2 is not None:          # this plane paid the t2
                    self.stats.log("t2_history", t2)
                    self.stats.bump(cache_misses=1)
                else:                       # another plane's compile (or
                    self.stats.bump(cache_hits=1)   # a racing insert)
            out.append(compiled)
        return out

    # ---- the seqlock protocol ----------------------------------------
    @contextlib.contextmanager
    def _write(self, bump_gen: bool = True):
        """Writer side of the dispatch seqlock: quiesce the in-flight
        step (the state's buffers are being donated while one runs),
        mutate ``_active``/``state`` under the lock, and bump the
        generation counter so dispatch work prepared against the old
        world revalidates.  Writers take precedence over new steps
        (steps wait while ``_writers`` is nonzero), so a busy data plane
        cannot starve the control plane.  ``bump_gen=False`` is the
        read-mostly variant (e.g. the :meth:`run_generic` oracle, which
        must only keep the state un-donated while it reads it)."""
        with self._cond:
            self._writers += 1
            try:
                while self._stepping:
                    self._cond.wait()
                yield
                if bump_gen:
                    # clear BEFORE bumping: a lock-free step_many reader
                    # that observes the new generation must already see
                    # the memo empty — the reverse order would let it
                    # pass claim validation holding a stale executable
                    # compiled for the old state structure
                    self._fused_memo = {}
                    self._gen += 1
            finally:
                self._writers -= 1
                self._cond.notify_all()

    def _begin_step(self, expect_gen: Optional[int] = None):
        """Claim the single in-flight step slot (brief critical
        section).  Returns ``(gen, active_tuple, state)``, or None when
        ``expect_gen`` no longer matches — the validated part of the
        protocol: work prepared outside the lock (a fused executable
        fetched for the active plan) is only committed to if no writer
        landed in between; otherwise the caller retries."""
        with self._cond:
            while self._stepping or self._writers:
                self._cond.wait()
            if expect_gen is not None and self._gen != expect_gen:
                return None
            self._stepping = True
            self._step_seq += 1
            return self._gen, self._active, self.state

    def _abort_step(self) -> None:
        """Release the step slot without committing (executable raised —
        the state may be half-donated, exactly as a mid-step crash under
        the old step-wide mutex).  Control updates queued while the
        failed step was in flight still drain here: leaving them queued
        would let a *later* direct update apply first and then be
        overwritten by the stale replay at the next commit — the FIFO
        invariant must hold on the failure path too."""
        notify = False
        with self._cond:
            if self._queued and not self._compiling:
                queued, self._queued = self._queued, []
                for (name, fields, n_valid) in queued:
                    self._apply_update_locked(name, fields, n_valid)
                # clear BEFORE bumping (same ordering rule as _write)
                self._fused_memo = {}
                self._gen += 1
                notify = True
            self._stepping = False
            self._cond.notify_all()
        if notify:
            self.controller.notify_update(self)

    def _commit_step(self, gen: int, new_state: PlaneState,
                     publish: bool, deltas: Dict[str, int]):
        """Commit one step's fresh state (brief critical section): a
        validated store — writers quiesce on in-flight steps, so the
        generation cannot have moved since the claim.  Control updates
        queued while the step (or fused window) was executing are
        drained here, *before* the next dispatch can claim: the device
        tables are fresh and the program guard deopts the next
        step/window (§4.4 at window granularity).  All stats for the
        step coalesce into ONE locked ``bump``."""
        notify = False
        with self._cond:
            assert self._gen == gen, "writer landed during in-flight step"
            self.state = new_state
            if publish and new_state.instr:
                # publish the freshly recorded sketches to the back
                # buffer: a device-side copy, dispatch-only — the t1
                # readout then never needs this lock
                self._backbuf.publish(new_state.instr)
            if self._queued and not self._compiling:
                queued, self._queued = self._queued, []
                for (name, fields, n_valid) in queued:
                    self._apply_update_locked(name, fields, n_valid)
                # clear BEFORE bumping (same ordering rule as _write)
                self._fused_memo = {}
                self._gen += 1
                notify = True
            self._stepping = False
            self._cond.notify_all()
        self.stats.bump(**deltas)
        if notify:
            self.controller.notify_update(self)

    # ---- the data plane entry point ----------------------------------
    def step(self, batch):
        """Run one serving step; returns the user output.  Dispatch is
        the paper's three-way choice: deopt to generic when the program
        guard trips, the instrumented twin on sampled steps (cadence set
        by the controller's per-plane sampling state machine), else the
        specialized executable.

        The executable runs with NO lock held: the claim/commit pair
        brackets it with two brief critical sections (see module
        docstring), so the control plane and other planes' recompiles
        never serialize behind device execution."""
        with span("runtime.step", k=1):
            cnt: dict = {}
            batch = self._place_batch(batch, count=cnt)
            with span("runtime.claim"):
                gen, active, state = self._begin_step()
            plan, spec_exec, instr_exec, generic_exec = active
            sampled = False
            deltas = {"steps": 1}
            if cnt:
                deltas["batch_transfers"] = cnt["transfers"]
            # degraded-mode check first, then the program-level guard
            # (ONE host compare covering every RO table): a faulted plane
            # serves generic-only until a re-specialization cycle clears
            # the flag
            if self._degraded:
                exec_, role = generic_exec, "degraded"
                deltas["degraded_steps"] = 1
            elif self.tables.version != plan.version:
                exec_, role = generic_exec, "generic"
                deltas["deopt_steps"] = 1
            elif self.enable and self.sampler.should_sample(
                    self._step_seq):
                exec_, role = instr_exec, "instr"
                sampled = True
                deltas["instr_steps"] = 1
            else:
                exec_, role = spec_exec, "spec"
            try:
                # the chaos hook fires BEFORE the executable runs: the
                # state tuple is not donated yet, so the abort below
                # leaves the plane's state intact and the same batch can
                # be retried through the degraded (generic) path —
                # byte-identically
                with span("runtime.launch", role=role):
                    if self._fault_injector is not None:
                        self._fault_injector.check(self._step_seq)
                    out, new_state = exec_(self.params, state, batch)
            except BaseException as e:
                self._abort_step()
                if isinstance(e, Exception):
                    self._on_step_fault(e)
                raise
            with span("runtime.commit"):
                self._commit_step(gen, new_state, sampled, deltas)
            return out

    def step_many(self, batches, k: Optional[int] = None):
        """Run a fused window of K serving steps through ONE
        ``lax.scan``-fused executable; returns the stacked outputs
        (leading axis K).  ``batches`` is a sequence of K same-shaped
        batches, or a pre-stacked/pre-placed pytree from
        :meth:`place_batch` (``fused=True``) — in the pre-stacked case
        ``k`` is REQUIRED and validated against every leaf's leading
        axis: a plain per-step batch is indistinguishable from a stacked
        window by shape alone, and silently scanning over the batch
        dimension would serve wrong outputs without an error.

        This is the steady-state fast path: one Python dispatch, one
        claim/commit pair and one locked stats update amortize over K
        steps.  Every K, K=1 included, runs the fused executable of the
        window's structure.  The program guard and the sampling decision
        are hoisted to window granularity — the whole window runs
        specialized, instrumented, or (guard tripped) generic; a control
        update landing mid-window is queued and drained at the window's
        commit, so the *next* window deopts (§4.4 semantics at window
        granularity, byte-identical outputs to K=1 stepping)."""
        n = len(batches) if isinstance(batches, (list, tuple)) else k
        with span("runtime.step_many", k=n or 0):
            return self._step_many(batches, k)

    def _step_many(self, batches, k: Optional[int]):
        if isinstance(batches, (list, tuple)):
            if k is not None and k != len(batches):
                raise ValueError(
                    f"step_many: k={k} but {len(batches)} batches given")
            k = len(batches)
            stacked = stack_batches(batches)
        else:
            if k is None:
                raise TypeError(
                    "step_many(stacked_pytree) needs an explicit k= "
                    "(window size): pass the sequence of per-step "
                    "batches instead, or the output of "
                    "place_batch(batches, fused=True) together with "
                    "k=len(batches)")
            stacked = batches
            lead = {int(leaf.shape[0])
                    for leaf in jax.tree.leaves(stacked)}
            if lead != {k}:
                raise ValueError(
                    f"step_many: leading axes {sorted(lead)} do not "
                    f"match the window size k={k}")
        cnt: dict = {}
        stacked = self._place_batch(stacked, stacked=True, count=cnt)
        with self._cond:
            # the window ordinal drives the sampling cadence: increment
            # under the lock — concurrent step_many callers must never
            # observe (and both instrument) the same ordinal
            self._window_seq += 1
            window = self._window_seq
        retries = -1
        while True:
            retries += 1
            # prepare OUTSIDE any lock: read the active world, pick the
            # window's role, and fetch (possibly compile) its fused
            # executable — then claim with generation validation and
            # retry if a writer landed in between.
            with span("runtime.prepare"):
                gen = self._gen
                plan = self._active[0]
                isites = self._active_isites
                deltas = {"steps": k}
                if cnt:
                    deltas["batch_transfers"] = cnt["transfers"]
                sampled = False
                if self._degraded:
                    # safe to read lock-free here: the flag only flips
                    # under _write(), which bumps the generation — a
                    # stale read is caught by the claim validation below
                    # and retried
                    role_plan, role = self.generic_plan, "degraded"
                    deltas["degraded_steps"] = k
                elif self.tables.version != plan.version:
                    role_plan, role = self.generic_plan, "generic"
                    deltas["deopt_steps"] = k
                elif (self.enable and self.sampler.should_sample_window(
                        window, k)):
                    role_plan = self._instr_twin(plan, isites)
                    role = "instr"
                    sampled = True
                    deltas["instr_steps"] = k
                else:
                    role_plan, role = plan, "spec"
                fexec, mkey = self._fused_exec(role_plan, stacked, isites,
                                               k)
                # an executable taken from the memo is the memo's object
                memo = "hit" if self._fused_memo.get(mkey) is fexec \
                    else "miss"
            with span("runtime.claim"):
                claim = self._begin_step(expect_gen=gen)
            if claim is not None:
                break
        gen, _, state = claim
        # memoize only now: the claim validated the generation and
        # writers are quiesced while ``_stepping`` is held, so the entry
        # provably belongs to the current world (a stale executable in
        # the memo would donate a state structure it was not compiled
        # for)
        self._fused_memo[mkey] = fexec
        try:
            # same fault-boundary contract as step(): the chaos hook
            # fires before the executable, so the abort is state-safe
            with span("runtime.launch", role=role, memo=memo,
                      retries=retries):
                if self._fault_injector is not None:
                    self._fault_injector.check(self._step_seq)
                out, new_state = fexec(self.params, state, stacked)
        except BaseException as e:
            self._abort_step()
            if isinstance(e, Exception):
                self._on_step_fault(e)
            raise
        with span("runtime.commit"):
            self._commit_step(gen, new_state, sampled, deltas)
        return out

    def warm_fused(self, batches, k: Optional[int] = None) -> None:
        """Precompile the K-step fused executables for a window
        structure AHEAD of serving: the active plan, its instrumented
        twin, and the generic deopt target all compile here (concurrent
        misses, shared-cache dedup across planes), and the structure is
        registered so future recompile cycles keep its fused variants
        precompiled.  A serving frontend calls this once per pad bucket
        at startup — the first real window (sampled or not, deopted or
        not) then never stalls on an inline t2."""
        if isinstance(batches, (list, tuple)):
            k = len(batches)
            stacked = stack_batches(batches)
        else:
            if k is None:
                raise TypeError("warm_fused(stacked_pytree) needs k=")
            stacked = batches
        stacked = self._place_batch(stacked, stacked=True)
        self._register_fused_shape(batch_key(stacked), k, stacked)
        isites = self._active_isites
        plan = self._active[0]
        wanted = [plan, self._instr_twin(plan, isites),
                  self.generic_plan,
                  self._instr_twin(self.generic_plan, isites)]
        avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), stacked)
        self._get_many(wanted, avals, isites, fuse=k)

    def _register_fused_shape(self, bkey, k: int, stacked) -> None:
        """First sight of a (window structure, K): record its stacked
        avals (recompile cycles precompile fused executables for every
        registered structure) and warm the fused generic deopt target in
        the background — the first guard-tripped window after a control
        update must swap to generic without paying t2, same as the
        single-step path's precompiled deopt target.  Called only on the
        fused slow lane (memo miss), never on the steady path."""
        warm = None
        with self._cond:         # the recompile thread iterates this map
            if (bkey, k) in self._fused_shapes:
                self._fused_shapes.move_to_end((bkey, k))
            else:
                self._fused_shapes[(bkey, k)] = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    stacked)
                while len(self._fused_shapes) > self._fused_shapes_cap:
                    self._fused_shapes.popitem(last=False)
                warm = threading.Thread(
                    target=self._warm_fused_generic,
                    args=(self._fused_shapes[(bkey, k)], k),
                    name="morpheus-warm-fused", daemon=True)
                # prune finished warms so the list stays bounded over a
                # long-lived server's lifetime; close() joins the rest
                self._warm_threads = [t for t in self._warm_threads
                                      if t.is_alive()]
                self._warm_threads.append(warm)
        if warm is not None:
            warm.start()

    def _warm_fused_generic(self, avals, k: int) -> None:
        """Background warm of the fused generic executable for a newly
        seen (batch structure, K): compiled through the shared cache's
        in-flight dedup, kept out of the serving counters (it is
        insurance, not a Morpheus cycle).  A failure is recorded in
        ``stats.warm_errors`` (`launch/serve.py` fails the run on it):
        serving goes on, and the first deopt window pays the compile
        inline."""
        try:
            isites = self._active_isites
            key = self._exec_key(self.generic_plan, avals,
                                 self.engine.cfg.donate, isites, fuse=k)
            if self.exec_cache.peek(key) is None:
                self._compile_into_cache(
                    [(self.generic_plan, self.engine.cfg.donate)], avals,
                    state=self.state.replace(
                        instr=self.engine.init_instr_state(isites)),
                    instr_struct=isites, serving=False, fuse=k)
        except Exception as e:  # noqa: BLE001 — background thread boundary
            self.stats.log("warm_errors", f"{type(e).__name__}: {e}")

    def _fused_exec(self, plan: SpecializationPlan, stacked,
                    instr_struct: Tuple[str, ...], k: int
                    ) -> Tuple[Callable, Any]:
        """Fetch (or compile) the K-step fused executable for ``plan``;
        returns ``(exe, memo_key)``.  The steady-state window pays one
        plain dict probe — no cache lock, no stats lock; the memo is
        invalidated by every committed writer (``_write`` clears it), so
        a swap or control update forces a re-probe of the shared
        :class:`ExecutableCache` (and a compile on a genuine miss,
        outside any lock).  The *caller* publishes to the memo after a
        validated claim — never here, where a racing writer could let a
        stale executable outlive its generation."""
        bkey = batch_key(stacked)
        mkey = (plan.signature, bkey, k)
        exe = self._fused_memo.get(mkey)
        if exe is not None:
            return exe, mkey
        # memo miss (first window, or a writer just landed): the slow
        # lane — also the right moment to register the window structure
        # for swap-time precompile + the background generic-deopt warm,
        # keeping that bookkeeping entirely OFF the steady path
        self._register_fused_shape(bkey, k, stacked)
        donate = self.engine.cfg.donate
        key = self._exec_key(plan, stacked, donate, instr_struct, fuse=k)
        exe = self.exec_cache.probe(key)
        if exe is None:
            # compile against the canonical state structure for this
            # instr snapshot (same discipline as _get_many): the key,
            # the lowering avals and the swap's state reset must all
            # derive from the same site tuple
            state = self.state.replace(
                instr=self.engine.init_instr_state(instr_struct))
            exe = self._compile_into_cache(
                [(plan, donate)], stacked, state=state,
                instr_struct=instr_struct, fuse=k)[0]
        else:
            self.stats.bump(cache_hits=1)
        return exe, mkey

    def run_generic(self, batch):
        """Replay ``batch`` through the generic plan WITHOUT committing
        state — the reference-semantics oracle.  Uses a non-donating
        twin of the generic executable (cached per batch structure in
        the shared ExecutableCache, ``donate=False`` keyed) so the live
        state is neither consumed nor copied.  The oracle is compiled
        outside the lock (compiles must never stall serving), so a
        racing topology-changing swap can invalidate it between fetch
        and call — the structure is rechecked under the lock and the
        fetch retried."""
        batch = self._place_batch(batch)
        for _ in range(4):
            oracle, instr_struct = self._get_oracle(batch)
            # write-side of the seqlock WITHOUT a generation bump: the
            # oracle mutates nothing, but the live state must not be
            # donated out from under it mid-read
            with self._write(bump_gen=False):
                if tuple(sorted(self.state.instr.keys())) == instr_struct:
                    out, _ = oracle(self.params, self.state, batch)
                    return out
        raise RuntimeError(
            "run_generic: the state structure kept changing under "
            "concurrent recompiles; retry when the control plane settles")

    # ---- instrumentation readout -------------------------------------
    def _merge_instr_on_device(self, instr):
        """psum-merge the per-device sketch slices into global sketches
        (replicated) — one jitted collective per recompile, not a host
        gather of every slice."""
        if self._merge_fn is None:
            mesh = self.mesh
            axes = self.engine.cfg.instr_axes

            def merge_all(tree):
                return {sid: (instrument.merge_on_device(st, mesh, axes)
                              if instrument.n_shards(st) is not None
                              else st)
                        for sid, st in tree.items()}

            self._merge_fn = jax.jit(merge_all)
        return self._merge_fn(instr)

    def _host_instr_snapshot(self) -> Dict[str, Dict[str, Any]]:
        """Host copy of the instrumentation sketches, read from the
        double-buffered *back* buffer — quiesced device copies published
        by the sampled steps themselves, so **no runtime lock is held**
        for the device->host transfer (sketches only advance on sampled
        steps, so the back buffer is exactly the current contents, not
        an approximation).  On a mesh the per-device slices are
        psum-merged on device first, so the pass registry always sees
        ONE global traffic snapshot regardless of topology."""
        instr = self._backbuf.read()
        if self.mesh is not None and instr:
            instr = self._merge_instr_on_device(instr)
        return {sid: {k: np.asarray(v) for k, v in st.items()}
                for sid, st in instr.items()}

    # ---- control plane -------------------------------------------------
    @property
    def snapshot_worker(self) -> TableSnapshotWorker:
        """This plane's off-thread t1 snapshotter — owned by the
        controller, created on first use, stopped when the plane is
        unregistered or the controller closed.  Raises after
        :meth:`close` so a racing background recompile cannot silently
        resurrect the thread."""
        if self._closed:
            raise RuntimeError("runtime closed")
        return self.controller.snapshot_worker_for(self)

    def control_update(self, name: str, fields, n_valid=None) -> None:
        """Control-plane table write.  Queued while a compile is in
        flight (§4.4) — or while a step/fused window is executing, so
        the control plane never blocks behind device execution; queued
        updates drain in FIFO order at the window's commit (or the
        recompile's replay), the device copy is refreshed before the
        next dispatch, the program guard deopts specialized executables
        until the next recompile, and the controller re-arms this
        plane's instrumentation sampling."""
        with self._cond:
            if self._compiling or self._stepping:
                self._queued.append((name, fields, n_valid))
                self.stats.bump(queued_updates=1)
                return
        self._apply_update(name, fields, n_valid)

    def _apply_update_locked(self, name, fields, n_valid):
        """Apply one control update with the runtime lock held and no
        step in flight (callers: :meth:`_apply_update` via the write
        side, :meth:`_commit_step`'s drain): host TableSet write +
        version bump, then refresh the device copy so the very next
        dispatch serves the new contents (through the generic
        executable — the guard now trips)."""
        self.tables.control_update(name, fields, n_valid)
        tables = dict(self.state.tables)
        tables[name] = self.tables[name].device_arrays()
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            tables[name] = _device_put(
                tables[name],
                NamedSharding(self.mesh, PartitionSpec()))
        self.state = self.state.replace(tables=tables)

    def _apply_update(self, name, fields, n_valid):
        with self._write():
            self._apply_update_locked(name, fields, n_valid)
        # re-arm sampling + refresh the t1 snapshot off-thread
        self.controller.notify_update(self)

    def attach_profile(self, profile) -> None:
        """Attach a traffic-profile source — any object with a
        ``snapshot() -> dict`` method (canonically the serving
        frontend's :class:`~repro.serving.frontend.ArrivalProfile`).
        Every recompile cycle reads one snapshot and merges it into the
        plan inputs (``PlanInputs.profile``), so plan-level passes like
        :class:`~repro.core.passes.batch_shape.BatchShapePass` can
        specialize against request-level dynamics (arrival rate, batch
        size distribution, pad-bucket occupancy) exactly as site passes
        specialize against key-level sketches.  Pass ``None`` to
        detach."""
        self._traffic_profile = profile

    def set_feature(self, name: str, value: bool) -> None:
        """Flip a control-plane feature flag.  Bumps the table version:
        flags are control-plane state, so the program guard deopts any
        executable compiled with the old pinning."""
        self.engine.cfg.features[name] = value
        self.tables.bump_version(f"flag:{name}")   # control-plane state
        self.controller.notify_update(self)

    # ---- fleet health: the dispatch fault boundary ---------------------
    @property
    def degraded(self) -> bool:
        """True while this plane serves generic-only after a fault."""
        return self._degraded

    @property
    def degrade_reason(self) -> Optional[str]:
        return self._degrade_reason

    def set_fault_injector(self, injector) -> None:
        """Attach a chaos hook (:class:`~repro.distributed.fault.\
FailureInjector`): its ``check(step)`` runs inside every step/window's
        try-block BEFORE the executable, so an injected fault exercises
        the real abort/degrade/recover machinery with the state tuple
        untouched.  Pass ``None`` to detach."""
        self._fault_injector = injector

    def arm_compile_faults(self, n: int = 1) -> None:
        """Make the next ``n`` recompile cycles raise a
        :class:`~repro.distributed.fault.SimulatedCompileFailure` right
        after planning — exercising the scheduler's backoff-retry and
        (past ``max_retries``) the signature-quarantine path."""
        self._compile_faults += n

    def degrade_to_generic(self, reason: str) -> None:
        """Swap this plane to generic-only dispatch (the Morpheus deopt
        target doubles as the fault-survival mode): every subsequent
        step/window routes to the generic executable regardless of the
        program guard, until a re-specialization cycle swaps specialized
        code back in and clears the flag.  The flip happens under the
        write side of the seqlock, so in-flight dispatch work prepared
        against the healthy world fails its claim validation and
        retries into the degraded path."""
        with self._write():
            self._degraded = True
            self._degrade_reason = str(reason)
        self.stats.bump(faults=1)
        try:
            self.controller.on_plane_fault(self, reason)
        except Exception:
            pass        # the fault path must survive a closed controller

    def simulate_device_loss(self, reason: str = "device-loss") -> None:
        """Fault path for a lost device: shrink the plane to
        single-device serving.  The LIVE state (including RW tables —
        sessions, SSM state — whose truth is on device, not in the host
        ``TableSet``) is pulled to host byte-exactly, the mesh dropped,
        the executable-cache namespace rotated (cache keys do not carry
        the mesh — old-placement executables must never be served for
        the shrunken plane), a generic executable compiled for the new
        placement, and the plane degraded — all under one write-side
        quiesce, serialized against recompile cycles so a concurrent
        swap cannot re-install old-mesh code.  On a real pod the same
        sequence runs through checkpoint-based
        :func:`~repro.distributed.fault.elastic_reshard`; in-process the
        host round-trip IS the resharding ``device_put``."""
        if self.mesh is None:
            # single-device already: nothing to shrink, plain degrade
            self.degrade_to_generic(reason)
            return
        with self._recompile_mutex:     # no cycle swaps mid-handoff
            with self._write():
                # byte-exact live-state handoff (np.asarray gathers the
                # addressable shards of each replicated/sharded array)
                self.state = jax.tree.map(np.asarray, self.state)
                self.params = jax.tree.map(np.asarray, self.params)
                self._example_batch = jax.tree.map(
                    np.asarray, self._example_batch)
                self.mesh = None
                self._cache_ns = f"{self._cache_ns}@shrunk"
                self._batch_sh_cache = {}
                self._merge_fn = None
                isites = tuple(sorted(self.state.instr.keys()))
                # compile the new placement's generic pair inline: the
                # plane has nothing safe to serve until it lands, so the
                # stall is the fault's cost, not a serving regression
                execs = self._compile_into_cache(
                    [(self.generic_plan, self.engine.cfg.donate),
                     (self._instr_twin(self.generic_plan, isites),
                      self.engine.cfg.donate)],
                    self._example_batch, state=self.state,
                    instr_struct=isites, serving=False)
                gen_exec = execs[0]
                self.generic_instr_exec = execs[1]
                self._active = (self.generic_plan, gen_exec,
                                execs[1], gen_exec)
                self._active_isites = isites
                self._degraded = True
                self._degrade_reason = str(reason)
        self.stats.bump(faults=1)
        try:
            self.controller.on_plane_fault(self, reason)
        except Exception:
            pass

    def _on_step_fault(self, exc: Exception) -> None:
        """A step/window raised: route the plane into degraded mode.
        Runs AFTER ``_abort_step`` released the slot (so the degrade's
        write-side quiesce cannot deadlock on our own claim) and must
        never mask the original exception."""
        if self._closed:
            return
        try:
            from ..distributed.fault import SimulatedDeviceLoss
            if isinstance(exc, SimulatedDeviceLoss):
                self.simulate_device_loss(f"device-loss: {exc!r}")
            else:
                self.degrade_to_generic(f"step-fault: {exc!r}")
        except Exception:
            pass

    # ---- recompilation ---------------------------------------------------
    def recompile(self, block: bool = True) -> Optional[dict]:
        """Run one Morpheus compilation cycle (§4.4).  ``block=False``
        queues the cycle on the controller's bounded recompile worker
        pool (coalesced if one is already pending for this plane) — the
        data plane keeps executing the old code meanwhile.  Even with
        ``block=True`` the t1 table snapshot runs on the snapshot
        worker's thread, never this one."""
        if not self.enable:
            return None
        if block:
            return self._recompile_now()
        self.controller.schedule(self)
        return None

    def recompile_priority(self) -> float:
        """Scheduler ordering for this plane: staleness (control-plane
        versions the active plan is behind) × traffic weight (steps
        served since this plane's last cycle), both floored at one so a
        queued plane always eventually runs."""
        staleness = max(self.tables.version - self.plan.version, 0) + 1
        traffic = max(self.stats.steps - self._steps_at_cycle, 1)
        return float(staleness * traffic)

    def _get_many(self, plans: List[SpecializationPlan], batch,
                  instr_struct: Tuple[str, ...],
                  fuse: Optional[int] = None) -> List[Callable]:
        """Fetch one serving executable per plan, deduplicating by cache
        key and compiling ALL misses concurrently in one batch (one
        thread per missing executable; XLA compilation releases the
        GIL).  Used for the specialized + instrumented twins — and, on a
        topology-changing cycle, the refreshed generic deopt targets in
        the same batch, so the worst-case cycle's t2 still overlaps.
        ``instr_struct`` is the caller's once-per-cycle snapshot of the
        instrumented-site tuple: key, lowering avals, and the swap's
        state reset all derive from the same tuple, so a concurrent
        control update moving ``n_valid`` across the inline threshold
        cannot mis-key an executable mid-cycle."""
        donate = self.engine.cfg.donate
        keys = [self._exec_key(p, batch, donate, instr_struct, fuse=fuse)
                for p in plans]
        found: Dict[Any, Callable] = {}
        missing: List[Tuple[Any, SpecializationPlan]] = []
        for k, p in zip(keys, plans):
            if k in found or any(k == mk for mk, _ in missing):
                continue
            # probe, not get: a miss here flows into get_or_compile,
            # which does the authoritative miss accounting
            exe = self.exec_cache.probe(k)
            if exe is None:
                missing.append((k, p))
            else:
                self.stats.bump(cache_hits=1)
                found[k] = exe
        if missing:
            state = self.state.replace(
                instr=self.engine.init_instr_state(instr_struct))
            compiled = self._compile_into_cache(
                [(p, donate) for _, p in missing], batch, state=state,
                instr_struct=instr_struct, fuse=fuse)
            for (k, _), exe in zip(missing, compiled):
                found[k] = exe
        return [found[k] for k in keys]

    def _fresh_instr_guards(self, isites: Tuple[str, ...]
                            ) -> Tuple[Dict, Dict]:
        """A fresh sketch window + zeroed RW guards for newly swapped
        code, built and mesh-placed OUTSIDE the runtime lock — the
        commit under the lock is then a plain ``state.replace``."""
        instr = self.engine.init_instr_state(isites)
        guards = self.engine.init_guards()
        if self.mesh is not None:
            from ..distributed.sharding import plane_state_shardings
            sh = plane_state_shardings(
                PlaneState({}, instr, guards), self.mesh,
                self.engine.cfg.instr_axes)
            instr = jax.device_put(instr, sh.instr)
            guards = jax.device_put(guards, sh.guards)
        return instr, guards

    def _recompile_now(self) -> dict:
        # ONE cycle at a time.  The controller's scheduler never runs
        # two cycles for the same plane concurrently, but a blocking
        # recompile can race a scheduled one — this mutex serializes
        # whole cycles, which is what makes the pre-swap reads of
        # _active/_active_isites below safe (the only other writer is
        # another cycle).
        with self._recompile_mutex:
            self._cycle_seq += 1
            n = self._cycle_seq
            self._cycle_local.n = n
            try:
                with span("cycle", plane=str(self.plane_id), n=n):
                    return self._recompile_cycle()
            finally:
                self._cycle_local.n = None

    def _recompile_cycle(self) -> dict:
        with self._cond:
            self._compiling = True
        try:
            # t1: versioned snapshot handoff (copied on the worker
            # thread) + lock-free back-buffer instrumentation readout +
            # pass planning.  While the sampler has this plane disarmed
            # the live sketches are gone from the state, so plan from
            # the profile retained at the last armed cycle — dropping it
            # would lose every traffic-dependent fast path and make the
            # signature oscillate.
            with span("cycle.snapshot"):
                snap = self.snapshot_worker.get(self.tables.version)
                instr = self._host_instr_snapshot()
            self.last_snapshot = snap
            self.stats.log("snapshot_versions", snap.version)
            if self.sampler.armed and _instr_has_samples(instr):
                self._plan_instr = instr
            else:
                # an empty window (disarmed plane, or no sampled step
                # landed since the last cycle) carries no new traffic
                # information — plan from the retained profile instead
                # of dropping every traffic-dependent fast path and
                # oscillating the signature
                instr = self._plan_instr or instr
            src = self._traffic_profile
            profile = src.snapshot() if src is not None else None
            if profile is not None:
                # the pass applies hysteresis against the shape that is
                # actually serving — selections hovering around a bucket
                # edge must not flip the plan signature every cycle
                from .passes.batch_shape import plan_batch_shape
                profile["prev_shape"] = \
                    plan_batch_shape(self._active[0])
            with span("cycle.plan"):
                plan, t1, pass_stats = self.engine.build_plan(
                    instr, snapshot=snap.tables, version=snap.version,
                    profile=profile)
            self.stats.log("t1_history", t1)
            self.stats.pass_stats = pass_stats
            # recorded BEFORE any failure below: the scheduler's give-up
            # hook quarantines exactly the signature whose cycle died
            self._last_plan_signature = plan.signature
            if self._compile_faults > 0:      # chaos: injected t2 failure
                self._compile_faults -= 1
                from ..distributed.fault import SimulatedCompileFailure
                raise SimulatedCompileFailure(
                    "injected recompile failure")
            if self.exec_cache.is_quarantined(plan.signature):
                # poisoned signature (this plane's give-up, or another
                # plane's on a shared cache): never re-attempted — keep
                # serving generic; a degraded plane drops back to
                # DEGRADED (the schedule gate had flipped it RECOVERING)
                if self._degraded:
                    try:
                        self.controller.on_plane_fault(
                            self, "quarantined plan signature")
                    except Exception:
                        pass
                self._steps_at_cycle = self.stats.steps
                return {"t1": t1, "pass_stats": pass_stats,
                        "plan": plan.label, "n_sites": len(plan.sites),
                        "quarantined": True}

            # plan churn drives this plane's sampling duty cycle; after
            # enough stable cycles the sampler disarms and isites
            # becomes () — the swap below then installs executables
            # with no sketches in their state at all (the instrumented
            # twin is swapped out, per the paper's adaptive
            # instrumentation)
            self.sampler.observe_cycle(plan.signature)
            isites = self._isites() if self.sampler.armed else ()

            active_plan, active_exec, active_instr, active_generic = \
                self._active
            if (self.engine.cfg.signature_cache
                    and plan.signature == active_plan.signature
                    and isites == self._active_isites):
                # REVALIDATION fast path: the freshly planned code is
                # behaviorally identical to what is already running
                # (same trace-time constants, same state structure) —
                # restamp the active plan's version under the lock,
                # zero trace/compile/swap.  Sketch window and RW guards
                # re-arm exactly as a swap would: the plan came from a
                # snapshot that saw every write the guards were
                # tracking.
                fresh_instr, fresh_guards = \
                    self._fresh_instr_guards(isites)
                recovered = False
                with span("cycle.revalidate"), self._write():
                    self._active = (
                        dataclasses.replace(active_plan,
                                            version=plan.version),
                        active_exec, active_instr, active_generic)
                    self.state = self.state.replace(
                        instr=fresh_instr, guards=fresh_guards)
                    self._backbuf.publish(fresh_instr)
                    if self._degraded:      # the code is fresh-validated
                        self._degraded = False    # against the current
                        self._degrade_reason = None   # basis: recovered
                        recovered = True
                deltas = {"revalidations": 1, "recompiles": 1}
                if recovered:
                    deltas["recoveries"] = 1
                self.stats.bump(**deltas)
                if recovered:
                    self.controller.on_plane_recovered(self)
                self._steps_at_cycle = self.stats.steps
                return {"t1": t1, "pass_stats": pass_stats,
                        "plan": self.plan.label,
                        "n_sites": len(plan.sites),
                        "revalidated": True, "recovered": recovered}

            wanted = [plan, self._instr_twin(plan, isites)]
            if isites != self._active_isites:
                # the instr topology changed (a site crossed the inline
                # threshold, the sampler disarmed or re-armed): the
                # deopt targets must match the new state structure too —
                # compiled in the SAME concurrent batch as the twins
                wanted += [self.generic_plan,
                           self._instr_twin(self.generic_plan, isites)]
            with span("cycle.compile"):
                execs = self._get_many(wanted, self._example_batch, isites)
                # precompile the fused variants for every window structure
                # step_many has served (specialized + twin, and the generic
                # deopt target on a topology change): still on the recompile
                # thread, concurrently per miss — a post-swap fused window
                # must hit the cache, not stall serving on an inline t2
                with self._cond:     # step_many registers entries under it
                    fused_shapes = list(self._fused_shapes.items())
                # ... and for the window shapes the NEW plan itself induces
                # (BatchShapePass bucket/K selection): the swap must land
                # with every shape the batcher will now form already
                # compiled, not just the shapes traffic happened to show
                done = {sk for sk, _ in fused_shapes}
                for sk, avals in _induced_window_avals(plan, fused_shapes):
                    if sk not in done:
                        done.add(sk)
                        fused_shapes.append((sk, avals))
                for (bk, k), avals in fused_shapes:
                    fused_wanted = [plan, self._instr_twin(plan, isites)]
                    if isites != self._active_isites:
                        fused_wanted.append(self.generic_plan)
                    self._get_many(fused_wanted, avals, isites, fuse=k)
            new_exec, new_instr_exec = execs[0], execs[1]
            new_generic = (execs[2] if len(execs) > 2
                           else active_generic)
            new_generic_instr = (execs[3] if len(execs) > 3
                                 else self.generic_instr_exec)

            # fresh sketch window + guards built (and the back-buffer
            # copy fn traced, on a structure change) outside the lock
            fresh_instr, fresh_guards = self._fresh_instr_guards(isites)
            self._backbuf.publish(fresh_instr)
            t0 = time.perf_counter()
            recovered = False
            with span("cycle.swap"), self._write():
                # ATOMIC swap (the BPF_PROG_ARRAY pointer update): one
                # reference assignment replaces the whole tuple — after
                # quiescing the in-flight step, since the state reset
                # below retires a (possibly half-donated) PlaneState
                self._active = (plan, new_exec, new_instr_exec,
                                new_generic)
                self.generic_instr_exec = new_generic_instr
                self._active_isites = isites
                # reset sketch window + revalidate RW guards for the new
                # code — from the SAME site snapshot the executables
                # were keyed and lowered with
                self.state = self.state.replace(
                    instr=fresh_instr, guards=fresh_guards)
                # re-publish under the lock: a sampled step may have
                # published pre-swap sketches since the warm above
                self._backbuf.publish(fresh_instr)
                if self._degraded:      # specialized code is back: the
                    self._degraded = False      # plane has re-specialized
                    self._degrade_reason = None
                    recovered = True
            self.stats.log("swap_history", time.perf_counter() - t0)
            deltas = {"recompiles": 1, "swaps": 1}
            if recovered:
                deltas["recoveries"] = 1
            self.stats.bump(**deltas)
            if recovered:
                self.controller.on_plane_recovered(self)
            self._steps_at_cycle = self.stats.steps
            return {"t1": t1, "pass_stats": pass_stats,
                    "plan": plan.label, "n_sites": len(plan.sites),
                    "revalidated": False, "recovered": recovered}
        finally:
            # drain queued control updates (§4.4 replay) BEFORE clearing
            # _compiling, in FIFO order: updates arriving during the
            # drain keep queueing behind the ones being replayed, so a
            # replayed stale write can never land on top of a newer
            # concurrent one.  Runs on the failure path too — a recompile
            # that died (e.g. closed runtime) must not strand updates.
            while True:
                with self._cond:
                    queued, self._queued = self._queued, []
                    if not queued:
                        self._compiling = False
                        break
                for (name, fields, n_valid) in queued:
                    self._apply_update(name, fields, n_valid)

    # ---- introspection -----------------------------------------------------
    def hot_experts(self) -> Optional[Tuple[int, ...]]:
        """Hot set of the active plan's MoE fast path, or None."""
        return self.plan.hot_experts(self.engine.cfg.moe_router_table)

    def close(self) -> None:
        """Detach from the control plane.  Idempotent.  With a private
        controller (the single-runtime convenience path) the whole
        controller is closed — recompile workers and the snapshot worker
        stop; with a shared controller only this plane is unregistered.
        The runtime remains usable for stepping (and an in-flight
        background recompile finishes or fails cleanly), but further
        recompiles raise — a closed runtime never restarts the workers
        behind the caller's back."""
        self._closed = True
        # the GC-time safety net is no longer needed — and must not fire
        # later against a new plane registered under this plane_id
        self._finalizer.detach()
        # let in-flight fused-generic warms finish: they compile against
        # this runtime's state/cache and must not outlive the teardown
        for t in self._warm_threads:
            t.join(timeout=60.0)
        if self._private_controller:
            self.controller.close()
        else:
            self.controller.unregister(self.plane_id)
