"""The steady-state dispatch fast path: seqlock'd dispatch, fused
multi-step windows, and the batch-placement fast path.

Covers the PR's acceptance criteria: the executable runs OUTSIDE the
runtime lock and writers quiesce on the in-flight step; `step_many`'s
fused K-step windows are byte-identical to K=1 stepping (generic and
specialized), cached with K in the executable-cache key, and hoisting
the program guard / sampling decision to window granularity preserves
§4.4 semantics — a control update landing mid-window is queued, the
*next* window runs generic, and replayed updates land in FIFO order;
`place_batch`/`_place_batch` never re-transfer an already-resident
batch; steady-state dispatch coalesces its stats into one locked call
per step (or per window); `PlaneSampling` learns the window-granular
cadence.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, MorpheusRuntime, PlaneSampling, \
    SketchConfig, Table, TableSet, stack_batches
from repro.core import runtime as runtime_mod
from repro.serving import ServeConfig, make_request_batch, \
    make_request_rows

N_VALID = 48


def _user_step(params, ctx, batch):
    row = ctx.lookup("classes", batch["cls"], fields=("scale",))
    x = batch["x"] * row["scale"][:, None]
    old = ctx.lookup("sess", batch["slot"], fields=("count",))
    ctx.update("sess", batch["slot"], {"count": old["count"] + 1})
    return x


def _tables(seed=0):
    return TableSet([
        Table("classes",
              {"scale": np.linspace(1.0, 2.0, N_VALID).astype(np.float32)
               + seed},
              n_valid=N_VALID, instrument=True),
        Table("sess", {"count": np.zeros(16, np.int32)}, n_valid=16,
              mutability="rw"),
    ])


def _batch(i=0):
    rng = np.random.default_rng(i)
    cls = np.arange(16) % N_VALID
    cls[:12] = np.arange(12) % 3          # skewed hot classes {0,1,2}
    return {"cls": jnp.asarray(cls, jnp.int32),
            "x": jnp.asarray(rng.standard_normal((16, 4)), jnp.float32),
            "slot": jnp.asarray(rng.integers(0, 16, 16), jnp.int32)}


def _mk(seed=0, sample_every=2, **kw):
    cfg = EngineConfig(sketch=SketchConfig(sample_every=sample_every,
                                           max_hot=4, hot_coverage=0.5),
                       **kw)
    return MorpheusRuntime(_user_step, _tables(seed), None, _batch(),
                           cfg=cfg)


# ---------------------------------------------------------------------------
# fused multi-step execution
# ---------------------------------------------------------------------------

def test_step_many_byte_identical_to_single_steps():
    """One lax.scan-fused K-step window == K single steps, bit for bit —
    outputs AND the threaded state (RW table writes, guards)."""
    rt1, rt2 = _mk(), _mk()
    try:
        batches = [_batch(i) for i in range(8)]
        singles = [np.asarray(rt1.step(b)) for b in batches]
        fused = np.asarray(rt2.step_many(batches))
        assert fused.shape[0] == 8
        for i in range(8):
            np.testing.assert_array_equal(singles[i], fused[i])
        np.testing.assert_array_equal(
            np.asarray(rt1.state.tables["sess"]["count"]),
            np.asarray(rt2.state.tables["sess"]["count"]))
        # specialized windows too
        rt1.recompile(block=True)
        rt2.recompile(block=True)
        assert rt2.plan.label.startswith("specialized")
        batches = [_batch(100 + i) for i in range(4)]
        singles = [np.asarray(rt1.step(b)) for b in batches]
        fused = np.asarray(rt2.step_many(batches))
        for i in range(4):
            np.testing.assert_array_equal(singles[i], fused[i])
    finally:
        rt1.close()
        rt2.close()


def test_step_many_cached_with_k_in_the_key():
    """Fused executables live in the ExecutableCache with K in the key:
    the second window of the same K compiles nothing, a different K
    compiles its own executable, and K never aliases the single-step
    entry."""
    rt = _mk()

    def join_warms():
        # the first window of each (structure, K) kicks off a background
        # warm of the fused generic deopt target — join it so compile
        # counts below are deterministic
        for t in rt._warm_threads:
            t.join(timeout=120)

    try:
        rt.sampler.pin(1)                 # every window instruments
        batches = [_batch(i) for i in range(4)]
        rt.step_many(batches)
        join_warms()
        c0 = rt.engine.compile_count
        rt.step_many([_batch(10 + i) for i in range(4)])
        assert rt.engine.compile_count == c0          # K=4 cached
        rt.step_many([_batch(20 + i) for i in range(2)])
        join_warms()
        # K=2 is a new executable (+ its background generic warm)
        assert rt.engine.compile_count == c0 + 2
        # with the sampler pinned at 1 every window samples -> the
        # instrumented twin is the fused role plan that ran and cached
        twin = rt._instr_twin(rt.plan, rt._active_isites)
        k4 = rt._exec_key(twin, stack_batches(batches), True,
                          rt._active_isites, fuse=4)
        k1 = rt._exec_key(twin, batches[0], True, rt._active_isites)
        assert k4 != k1
        assert rt.exec_cache.peek(k4) is not None
    finally:
        rt.close()


def test_step_many_rejects_ambiguous_prestacked_input():
    """A plain per-step batch is shape-indistinguishable from a stacked
    window: without an explicit k the call must fail loudly instead of
    silently scanning over the batch dimension."""
    rt = _mk()
    try:
        with pytest.raises(TypeError):
            rt.step_many(_batch())                   # no k: ambiguous
        with pytest.raises(ValueError):
            rt.step_many([_batch(0), _batch(1)], k=3)   # k mismatch
        with pytest.raises(ValueError):
            rt.step_many(stack_batches([_batch(i) for i in range(4)]),
                         k=8)                        # wrong leading axis
    finally:
        rt.close()


def test_step_many_k1_degrades_to_single_step(monkeypatch):
    """A K=1 window gives a single step's output, bit for bit, from the
    fused K=1 executable: ``step()`` is never called, even for a window
    with the example batch's structure."""
    ref_rt = _mk()
    try:
        ref = np.asarray(ref_rt.step(_batch(3)))
    finally:
        ref_rt.close()
    rt = _mk()
    try:
        def no_step(self, batch):
            raise AssertionError("step_many routed a K=1 window to step()")
        monkeypatch.setattr(MorpheusRuntime, "step", no_step)
        out = np.asarray(rt.step_many([_batch(3)]))
        assert out.shape == (1,) + ref.shape
        np.testing.assert_array_equal(out[0], ref)
        assert rt.stats.steps == 1
    finally:
        rt.close()


TINY_SERVE = ServeConfig(d_model=32, n_layers=1, n_heads=4, vocab=128,
                         n_experts=4, d_ff=32, n_classes=8, n_slots=32,
                         seq=4)


def _frontend_batch(seed):
    rows = make_request_rows(TINY_SERVE, jax.random.PRNGKey(seed), 3)
    return make_request_batch(rows, 4)     # carries the bool ``valid``


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("make", [_frontend_batch, _batch],
                         ids=["frontend", "example"])
def test_stack_batches_matches_np_stack(make, k):
    """One jitted call stacks a window exactly as ``np.stack`` does —
    shapes, dtypes (the frontend's bool ``valid`` leaf included) and
    values — and a second window of the same structure compiles
    nothing."""
    batches = [make(10 * k + i) for i in range(k)]
    out = stack_batches(batches)
    assert out.keys() == batches[0].keys()
    for name, got in out.items():
        want = np.stack([np.asarray(b[name]) for b in batches])
        assert got.shape == want.shape and got.dtype == want.dtype, name
        np.testing.assert_array_equal(np.asarray(got), want)
    if make is _frontend_batch:
        assert out["valid"].dtype == np.bool_
    compiled = runtime_mod._stack_window._cache_size()
    again = stack_batches([make(100 * k + i) for i in range(k)])
    assert runtime_mod._stack_window._cache_size() == compiled
    assert jax.tree.map(np.shape, again) == jax.tree.map(np.shape, out)


# ---------------------------------------------------------------------------
# §4.4 semantics at window granularity
# ---------------------------------------------------------------------------

def test_midwindow_update_queues_then_next_window_deopts_in_order():
    """A control_update landing mid-`step_many` window does NOT block:
    it queues, drains (FIFO) at the window's commit, and the *next*
    window runs generic via the program guard — byte-identical to the
    same schedule under K=1 stepping."""
    rt = _mk()
    ref = _mk()
    try:
        w0 = [_batch(i) for i in range(4)]
        w1 = [_batch(10 + i) for i in range(4)]
        rt.step_many(w0)
        rt.recompile(block=True)
        for b in w0:
            ref.step(b)
        ref.recompile(block=True)

        # block the fused executable mid-window so the updates land
        # while the window is provably in flight
        started, release = threading.Event(), threading.Event()
        real = rt._fused_exec

        def gated(*a, **kw):
            exe, mkey = real(*a, **kw)

            def wrapper(params, state, batch):
                started.set()
                assert release.wait(timeout=30)
                return exe(params, state, batch)
            return wrapper, mkey

        rt._fused_exec = gated
        out = {}
        th = threading.Thread(
            target=lambda: out.update(w=rt.step_many(w1)))
        th.start()
        assert started.wait(timeout=30)
        sA = np.full(N_VALID, 5.0, np.float32)
        sB = np.full(N_VALID, 7.0, np.float32)
        rt.control_update("classes", {"scale": sA})   # queued: in flight
        rt.control_update("classes", {"scale": sB})   # queued behind A
        assert len(rt._queued) == 2                   # did not block
        v_before = rt.tables.version
        release.set()
        th.join(timeout=60)
        assert not th.is_alive()
        rt._fused_exec = real

        # the drain applied both updates, in order: B is live
        assert rt.tables.version > v_before
        np.testing.assert_array_equal(
            np.asarray(rt.state.tables["classes"]["scale"]), sB)
        # the window itself ran pre-update code
        for b, o in zip(w1, np.asarray(out["w"])):
            np.testing.assert_array_equal(np.asarray(ref.step(b)), o)
        # the NEXT window deopts (program guard) and serves B's contents
        ref.control_update("classes", {"scale": sA})
        ref.control_update("classes", {"scale": sB})
        w2 = [_batch(20 + i) for i in range(4)]
        d0 = rt.stats.deopt_steps
        fused = np.asarray(rt.step_many(w2))
        assert rt.stats.deopt_steps == d0 + 4
        for b, o in zip(w2, fused):
            np.testing.assert_array_equal(np.asarray(ref.step(b)), o)
    finally:
        rt.close()
        ref.close()


def test_fused_generic_deopt_target_is_precompiled():
    """The §4.4 guarantee at window granularity: the fused generic
    deopt target is warmed in the background when a window structure is
    first seen, so a guard-tripped window swaps to generic with ZERO
    inline compiles."""
    rt = _mk()
    try:
        w = [_batch(i) for i in range(4)]
        rt.step_many(w)
        for t in rt._warm_threads:
            t.join(timeout=120)
        c0 = rt.engine.compile_count
        rt.control_update("classes",
                          {"scale": np.full(N_VALID, 2.5, np.float32)})
        d0 = rt.stats.deopt_steps
        rt.step_many(w)                          # guard trips
        assert rt.stats.deopt_steps == d0 + 4
        assert rt.engine.compile_count == c0     # no inline t2
    finally:
        rt.close()


def test_update_queued_during_single_step_drains_at_commit():
    """The same queue/drain protocol covers plain step(): the control
    plane never blocks behind an in-flight executable."""
    rt = _mk()
    try:
        rt.step(_batch())
        started, release = threading.Event(), threading.Event()
        spec = rt._active

        def gated(params, state, batch):
            started.set()
            assert release.wait(timeout=30)
            return spec[1](params, state, batch)

        with rt._cond:
            rt._active = (spec[0], gated, gated, gated)
        th = threading.Thread(target=lambda: rt.step(_batch(1)))
        th.start()
        assert started.wait(timeout=30)
        rt.control_update("classes",
                          {"scale": np.full(N_VALID, 9.0, np.float32)})
        assert rt._queued                               # non-blocking
        release.set()
        th.join(timeout=60)
        assert not th.is_alive()
        assert not rt._queued                           # drained
        assert float(rt.state.tables["classes"]["scale"][0]) == 9.0
        with rt._cond:
            rt._active = spec
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# the seqlock protocol
# ---------------------------------------------------------------------------

def test_executable_runs_outside_the_runtime_lock():
    """The tentpole property: during device execution the runtime lock
    is FREE (the seed held it across the whole step)."""
    rt = _mk()
    try:
        seen = {}
        spec = rt._active

        def probe(params, state, batch):
            seen["locked"] = rt._lock.locked()
            seen["stepping"] = rt._stepping
            return spec[1](params, state, batch)

        with rt._cond:
            rt._active = (spec[0], probe, probe, probe)
        rt.step(_batch())
        with rt._cond:
            rt._active = spec
        assert seen["locked"] is False
        assert seen["stepping"] is True
    finally:
        rt.close()


def test_writer_quiesces_and_bumps_generation():
    """A writer (recompile swap / control update) waits for the
    in-flight step, then bumps the generation so prepared dispatch work
    revalidates."""
    rt = _mk()
    try:
        g0 = rt._gen
        rt.control_update("classes",
                          {"scale": np.full(N_VALID, 3.0, np.float32)})
        assert rt._gen > g0                      # writer bumped
        g1 = rt._gen
        rt.recompile(block=True)                 # swap is a writer too
        assert rt._gen > g1
        # claim validation: a stale generation is refused
        assert rt._begin_step(expect_gen=g0) is None
        claim = rt._begin_step(expect_gen=rt._gen)
        assert claim is not None
        rt._abort_step()
    finally:
        rt.close()


def test_concurrent_steps_and_control_churn_stay_consistent():
    """Stress the seqlock: steppers, a control-update writer and
    blocking recompiles race; every step commits, nothing deadlocks,
    and the final state matches the last update."""
    rt = _mk()
    errors = []
    N = 40

    def stepper():
        try:
            for i in range(N):
                rt.step(_batch(i))
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    def churner():
        try:
            for i in range(10):
                rt.control_update(
                    "classes",
                    {"scale": np.full(N_VALID, float(i), np.float32)})
                rt.recompile(block=True)
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    try:
        threads = [threading.Thread(target=stepper) for _ in range(2)]
        threads.append(threading.Thread(target=churner))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive(), "deadlocked"
        assert not errors, errors
        assert rt.stats.steps == 2 * N
        # queued updates all landed (none stranded)
        assert not rt._queued
        assert float(rt.state.tables["classes"]["scale"][0]) == 9.0
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# batch placement fast path
# ---------------------------------------------------------------------------

def _mesh1():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def test_second_step_on_placed_batch_performs_zero_transfers():
    """The placement satellite: arrays whose committed sharding already
    matches pass through — stepping the same batch object twice
    transfers it once."""
    cfg = EngineConfig(sketch=SketchConfig(sample_every=2, max_hot=4,
                                           hot_coverage=0.5),
                       mesh=_mesh1())
    rt = MorpheusRuntime(_user_step, _tables(), None, _batch(), cfg=cfg)
    calls = []
    real = runtime_mod._device_put
    try:
        runtime_mod._device_put = \
            lambda *a, **kw: (calls.append(1), real(*a, **kw))[1]
        host = {k: np.asarray(v) for k, v in _batch().items()}
        placed = rt.place_batch(host)                # host arrays: H2D
        assert len(calls) == 1                       # first placement
        jax.block_until_ready(rt.step(placed))
        assert len(calls) == 1                       # step re-used it
        jax.block_until_ready(rt.step(placed))
        assert len(calls) == 1                       # zero transfers
        assert rt.place_batch(placed) is placed      # prefetch no-op
        assert rt.stats.batch_transfers == 1
        # fused layout is place-once too
        w = rt.place_batch([_batch(i) for i in range(4)], fused=True)
        n = len(calls)
        jax.block_until_ready(rt.step_many(w, k=4))
        jax.block_until_ready(rt.step_many(w, k=4))
        assert len(calls) == n
    finally:
        runtime_mod._device_put = real
        rt.close()


# ---------------------------------------------------------------------------
# coalesced stats + window-granular sampling cadence
# ---------------------------------------------------------------------------

def test_steady_step_makes_one_locked_stats_call():
    rt = _mk()
    try:
        b = _batch()
        rt.step(b)
        lc0, s0 = rt.stats.locked_calls, rt.stats.steps
        for _ in range(6):
            rt.step(b)
        assert rt.stats.locked_calls - lc0 <= rt.stats.steps - s0
        rt.sampler.pin(1)                            # every window samples
        w = [_batch(i) for i in range(4)]
        rt.step_many(w)                              # compile path (twin)
        lc0 = rt.stats.locked_calls
        for _ in range(3):
            rt.step_many(w)
        assert rt.stats.locked_calls - lc0 <= 3      # one per WINDOW
    finally:
        rt.close()


def test_sampling_learns_window_granular_cadence():
    sampler = PlaneSampling(SketchConfig(sample_every=8))
    sampler.pin(4)
    # one sampled window per sample_every WINDOWS, for any K: a sampled
    # window instruments all K steps, so this is what preserves the
    # per-step duty cycle (K / (4*K) = 1/4) and the sketch data rate
    for k in (2, 4, 32):
        assert sampler.window_every(k) == 4
    hits = [sampler.should_sample_window(w, 8) for w in range(1, 9)]
    assert hits == [False, False, False, True] * 2
    duty = sum(8 for w in range(1, 33)
               if sampler.should_sample_window(w, 8)) / (32 * 8)
    assert duty == 1.0 / 4
    # disarmed: never
    sampler.disarm_after = 1
    sampler.armed = False
    assert not sampler.should_sample_window(4, 4)


def test_fused_window_instruments_and_publishes_once():
    """A sampled fused window records all K steps' traffic into the
    sketches and publishes the back buffer once per window."""
    rt = _mk(sample_every=2)
    try:
        rt.sampler.pin(1)                            # sample every window
        seq0 = rt._backbuf.seq
        i0 = rt.stats.instr_steps
        rt.step_many([_batch(i) for i in range(4)])  # window 1: sampled
        assert rt.stats.instr_steps == i0 + 4
        assert rt._backbuf.seq == seq0 + 1           # ONE publish
        snap = rt._host_instr_snapshot()
        assert int(snap["classes#0"]["total"]) > 0
    finally:
        rt.close()
