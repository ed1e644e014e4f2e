"""The request-level serving frontend, end to end.

Covers the PR's acceptance criteria: the ragged->bucket packer's masked
rows never perturb real rows; the bounded queue rejects at capacity and
sheds expired deadlines; a mid-serve control-plane update deopts
without dropping or reordering queued requests; open-loop arrivals
through the frontend produce per-request outputs byte-identical to
one-per-batch execution; BatchShapePass selects pad buckets + window
depth K from the observed arrival profile (visible in ``plan.sites``)
and bucket misprediction deopts through the existing program guard;
``step_many`` serves non-example batch structures at every K;
``warm_fused`` precompiles all of a shape's role executables; and the
shared :class:`StreamingHistogram` backs both step- and request-level
quantiles through one ``RuntimeStats`` implementation.
"""
import math
import time

import jax
import numpy as np
import pytest

from repro.core import BATCH_SHAPE_SITE, EngineConfig, MorpheusRuntime, \
    RuntimeStats, SketchConfig, StreamingHistogram, plan_batch_shape
from repro.serving import ServeConfig, build_params, build_tables, \
    make_request_batch, make_request_rows, make_serve_step, \
    make_synthetic_batch
from repro.serving.frontend import FrontendConfig, OpenLoopDriver, \
    Request, RequestQueue, ServingFrontend, bursty_onoff_gaps, \
    poisson_gaps

TINY = ServeConfig(d_model=32, n_layers=1, n_heads=4, vocab=128,
                   n_experts=4, d_ff=32, n_classes=8, n_slots=32, seq=4)


def _mk_rt(cfg=TINY, seed=0, batch_size=8):
    key = jax.random.PRNGKey(seed)
    return MorpheusRuntime(
        make_serve_step(cfg), build_tables(cfg, key),
        build_params(cfg, key),
        make_synthetic_batch(cfg, key, batch_size),
        cfg=EngineConfig(
            sketch=SketchConfig(sample_every=2, max_hot=4,
                                hot_coverage=0.6),
            features={"vision_enabled": False, "track_sessions": True},
            moe_router_table="router"))


class FakeClock:
    """Virtual monotonic clock for deterministic queue/deadline tests."""

    def __init__(self, t=100.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


class StubProfile:
    """A fixed profile snapshot — drives BatchShapePass deterministically."""

    def __init__(self, d):
        self.d = dict(d)

    def snapshot(self):
        return dict(self.d)


def _profile_dict(size_hist, rate, ladder=(1, 2, 4, 8), max_wait=2e-3,
                  k_max=4):
    return {"ladder": ladder, "max_wait_s": max_wait,
            "window_k_max": k_max, "arrival_rate_hz": rate,
            "size_hist": tuple(size_hist)}


# ---------------------------------------------------------------------------
# StreamingHistogram + RuntimeStats (one quantile implementation for
# step AND request latency)
# ---------------------------------------------------------------------------

def test_histogram_quantiles_match_numpy():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-6.0, sigma=1.5, size=20_000)
    h = StreamingHistogram()
    h.observe_all(xs)
    for q in (0.1, 0.5, 0.9, 0.99):
        exact = float(np.quantile(xs, q))
        # geometric buckets: ~5.1% relative bucket width
        assert h.quantile(q) == pytest.approx(exact, rel=0.06)
    assert h.quantile(0.0) == pytest.approx(xs.min(), rel=0.06)
    assert h.quantile(1.0) == pytest.approx(xs.max(), rel=0.06)
    assert h.mean == pytest.approx(xs.mean(), rel=1e-6)


def test_histogram_merge_equals_union():
    rng = np.random.default_rng(1)
    a, b = rng.exponential(0.01, 5000), rng.exponential(0.1, 5000)
    ha, hb, hu = (StreamingHistogram() for _ in range(3))
    ha.observe_all(a)
    hb.observe_all(b)
    hu.observe_all(np.concatenate([a, b]))
    ha.merge(hb)
    for q in (0.25, 0.5, 0.99):
        assert ha.quantile(q) == pytest.approx(hu.quantile(q), rel=1e-9)
    assert ha.summary()["count"] == 10_000


def test_histogram_empty():
    h = StreamingHistogram()
    assert math.isnan(h.quantile(0.5))
    assert h.summary() == {"count": 0}


def test_stats_observe_many_and_quantiles():
    s = RuntimeStats()
    s.observe_many({"request_total_s": [0.01, 0.02, 0.03],
                    "request_queue_wait_s": [0.001]},
                   requests_completed=3, slo_met=2, slo_missed=1)
    assert s.requests_completed == 3 and s.slo_met == 2
    assert s.quantile("request_total_s", 0.5) == pytest.approx(
        0.02, rel=0.06)
    assert math.isnan(s.quantile("no_such_series", 0.5))
    snap = s.snapshot()
    assert snap["hists"]["request_total_s"]["count"] == 3
    s.reset_hist("request_total_s")
    assert math.isnan(s.quantile("request_total_s", 0.5))
    # the untouched series survives a selective reset
    assert s.quantile("request_queue_wait_s", 0.5) > 0


# ---------------------------------------------------------------------------
# ragged -> bucket packer
# ---------------------------------------------------------------------------

def test_request_batch_shapes_and_mask():
    rows = make_request_rows(TINY, jax.random.PRNGKey(0), 3)
    b = make_request_batch(rows, 8)
    assert b["tokens"].shape == (8, TINY.seq)
    assert b["valid"].shape == (8,)
    np.testing.assert_array_equal(
        np.asarray(b["valid"]), [True] * 3 + [False] * 5)
    # pad rows replicate row 0 (deterministic duplicate-index scatters)
    np.testing.assert_array_equal(np.asarray(b["tokens"])[3:],
                                  np.tile(np.asarray(b["tokens"])[:1],
                                          (5, 1)))
    with pytest.raises(ValueError):
        make_request_batch([], 4)
    with pytest.raises(ValueError):
        make_request_batch(rows, 2)


def test_masked_rows_never_perturb_real_rows():
    """Same real rows, different pad-row contents, same bucket: the real
    rows' outputs are byte-identical — the data plane never lets a pad
    row leak into a real row."""
    rt = _mk_rt()
    try:
        key = jax.random.PRNGKey(3)
        rows = make_request_rows(TINY, key, 8)
        real, junk = rows[:3], rows[3:]
        b_pad = make_request_batch(real, 8)          # pads = row-0 copies
        b_junk = make_request_batch(real + junk, 8)  # "pads" = junk rows
        out_pad = np.asarray(rt.run_generic(b_pad))
        out_junk = np.asarray(rt.run_generic(b_junk))
        np.testing.assert_array_equal(out_pad[:3], out_junk[:3])
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# queue: admission control + deadline shedding
# ---------------------------------------------------------------------------

def test_queue_full_rejects_at_submit():
    rt = _mk_rt()
    try:
        clock = FakeClock()
        fe = ServingFrontend(rt, FrontendConfig(capacity=4, max_batch=4),
                             clock=clock)
        rows = make_request_rows(TINY, jax.random.PRNGKey(0), 6)
        reqs = [fe.submit(r) for r in rows]
        assert [r.status for r in reqs] == ["pending"] * 4 + \
            ["rejected"] * 2
        assert reqs[4].done and reqs[4].output is None
        assert rt.stats.requests_submitted == 6
        assert rt.stats.requests_rejected == 2
    finally:
        rt.close()


def test_queue_sheds_deadline_expiring_between_admission_and_take():
    """The admission/take gap, on the queue itself: a request whose
    deadline is comfortably in the future at ``submit`` (so admission
    accepts it) but past by the time the batcher calls ``take`` must
    come back in the *shed* list — and must NOT consume a ``max_n``
    batch slot, so a live request behind it in FIFO order still fills
    the window.  ``now == deadline`` exactly is already late (the
    answer could not be produced in zero time)."""
    clock = FakeClock()
    q = RequestQueue(capacity=8)
    expiring = Request(id=0, payload="a", arrival_ts=clock(),
                       deadline=clock() + 0.05)
    exact = Request(id=1, payload="b", arrival_ts=clock(),
                    deadline=clock() + 0.10)
    live = Request(id=2, payload="c", arrival_ts=clock(),
                   deadline=clock() + 99.0)
    assert q.submit(expiring) and q.submit(exact) and q.submit(live)
    assert len(q) == 3
    clock.advance(0.10)            # expiring now past, exact == now
    ready, shed = q.take(1, clock())
    assert [r.id for r in shed] == [0, 1]
    assert [r.id for r in ready] == [2]    # shed never ate the slot
    assert len(q) == 0
    # shed_expired=False: the policy knob hands even late requests out
    q2 = RequestQueue(capacity=8, shed_expired=False)
    late = Request(id=3, payload="d", arrival_ts=clock(),
                   deadline=clock() - 1.0)
    assert q2.submit(late)
    ready, shed = q2.take(4, clock())
    assert [r.id for r in ready] == [3] and shed == []


def test_deadline_expired_requests_are_shed():
    rt = _mk_rt()
    try:
        clock = FakeClock()
        fe = ServingFrontend(rt, FrontendConfig(capacity=16, max_batch=4,
                                                max_wait_s=0.0),
                             clock=clock)
        rows = make_request_rows(TINY, jax.random.PRNGKey(0), 3)
        late = [fe.submit(r, deadline_s=0.01) for r in rows[:2]]
        live = fe.submit(rows[2], deadline_s=10.0)
        clock.advance(0.02)            # both deadlines now in the past
        n = fe.pump()
        assert n == 1                  # only the live request dispatched
        fe.drain()
        assert [r.status for r in late] == ["shed", "shed"]
        assert late[0].timing["total_s"] == pytest.approx(0.02)
        assert live.status == "ok"
        assert rt.stats.requests_shed == 2
        assert rt.stats.requests_completed == 1
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# mid-serve control update: deopt, no drops, no reorder
# ---------------------------------------------------------------------------

def test_midserve_control_update_keeps_fifo_and_completes_all():
    rt = _mk_rt()
    try:
        fe = ServingFrontend(rt, FrontendConfig(
            capacity=64, max_batch=4, ladder=(4,), window_k_max=1,
            max_wait_s=0.0))
        rows = make_request_rows(TINY, jax.random.PRNGKey(0), 12)
        reqs = [fe.submit(r) for r in rows]
        assert fe.pump() == 4          # first window out the door
        d0 = rt.stats.deopt_steps
        rt.control_update("req_class", {"temperature": np.full(
            TINY.n_classes, 1.3, np.float32)})
        assert fe.drain(timeout=120.0)
        assert [r.status for r in reqs] == ["ok"] * 12
        assert rt.stats.requests_completed == 12
        # the post-update windows ran the generic deopt target
        assert rt.stats.deopt_steps > d0
        # strict FIFO: requests were taken in submission order
        taken = [r._taken_ts for r in reqs]
        assert all(a <= b for a, b in zip(taken, taken[1:]))
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# E2E: open-loop arrivals, byte-identical outputs
# ---------------------------------------------------------------------------

def test_e2e_poisson_outputs_byte_identical_to_one_per_batch():
    """Poisson arrivals through the full queue->batcher->step_many path,
    with a single-slot bucket ladder so every request runs exactly as a
    one-per-batch execution — outputs must match the generic oracle on
    the same single-request batch, byte for byte."""
    rt = _mk_rt()
    try:
        fe = ServingFrontend(rt, FrontendConfig(
            capacity=64, max_batch=1, ladder=(1,), window_k_max=4,
            max_wait_s=1e-4))
        rows = make_request_rows(TINY, jax.random.PRNGKey(7), 24)
        gaps = poisson_gaps(2000.0, 24, seed=1)
        driver = OpenLoopDriver([fe], rows, gaps)
        driver.run()                   # inline: deterministic arrival order
        assert fe.drain(timeout=120.0)
        assert rt.stats.requests_completed == 24
        for r in driver.requests:
            assert r.status == "ok"
            ref = rt.run_generic(make_request_batch([r.payload], 1))
            np.testing.assert_array_equal(np.asarray(r.output),
                                          np.asarray(ref)[0])
            assert set(r.timing) == {"queue_wait_s", "batch_wait_s",
                                     "execute_s", "total_s"}
        # request-latency quantiles flow through the shared histogram
        assert rt.stats.quantile("request_total_s", 0.5) > 0
    finally:
        rt.close()


def test_arrival_generators_hit_target_rate():
    for fn in (poisson_gaps, bursty_onoff_gaps):
        gaps = fn(500.0, 4000, seed=0)
        assert float(np.mean(gaps)) == pytest.approx(1 / 500.0, rel=0.1)


# ---------------------------------------------------------------------------
# BatchShapePass: profile -> (buckets, K) in plan.sites
# ---------------------------------------------------------------------------

def test_batch_shape_pass_selects_from_profile():
    rt = _mk_rt()
    try:
        hist = [0] * 8
        hist[0], hist[3] = 10, 10      # half size-1, half size-4 groups
        rt.attach_profile(StubProfile(_profile_dict(hist, rate=8000.0)))
        rt.recompile(block=True)
        sig_a = rt.plan.signature
        assert plan_batch_shape(rt.plan) == ((1, 4), 4)
        assert BATCH_SHAPE_SITE in dict(rt.plan.sites)
        # the pseudo-site never reaches lookup dispatch: serving works
        b = make_synthetic_batch(TINY, jax.random.PRNGKey(1), 8)
        jax.block_until_ready(rt.step(b))

        # a drifted profile is a genuinely different plan (new signature
        # => new executables => atomic swap), not a mutation in place
        hist2 = [0] * 8
        hist2[7] = 20                  # all groups size 8 now, light rate
        rt.attach_profile(StubProfile(_profile_dict(hist2, rate=100.0)))
        rt.recompile(block=True)
        assert plan_batch_shape(rt.plan) == ((8,), 1)
        assert rt.plan.signature != sig_a
    finally:
        rt.close()


def test_batch_shape_hysteresis_stabilizes_edge_hovering():
    """Traffic hovering at a bucket edge converges to a stable bucket
    superset instead of flipping the plan signature every recompile
    cycle; a regime change (primary moving two or more ladder steps)
    still takes the fresh selection outright."""
    rt = _mk_rt()
    try:
        # sizes 3..5 straddle the 4/8 bucket edge: median fits 4,
        # p95 fits 8 => ((4, 8), 4) at this rate
        edge = [0] * 8
        edge[2], edge[3], edge[4] = 7, 7, 6
        rt.attach_profile(StubProfile(_profile_dict(edge,
                                                    rate=16000.0)))
        rt.recompile(block=True)
        assert plan_batch_shape(rt.plan) == ((4, 8), 4)
        sig = rt.plan.signature

        # the median hovers up past the edge (fresh selection would be
        # ((8,), 3)): bucket 4 still has mass, so the serving superset
        # holds — and the one-step K shrink is damped too.  Signature
        # stable => the revalidation fast path, no swap.
        edge_up = [0] * 8
        edge_up[3], edge_up[4] = 6, 14
        rt.attach_profile(StubProfile(_profile_dict(edge_up,
                                                    rate=12000.0)))
        rt.recompile(block=True)
        assert plan_batch_shape(rt.plan) == ((4, 8), 4)
        assert rt.plan.signature == sig

        # regime change: all size-1 groups at a light rate is a
        # multi-step primary shrink — fresh selection applies, and the
        # abandoned buckets (no observed mass) drop out entirely
        hist1 = [0] * 8
        hist1[0] = 20
        rt.attach_profile(StubProfile(_profile_dict(hist1, rate=100.0)))
        rt.recompile(block=True)
        assert plan_batch_shape(rt.plan) == ((1,), 1)
        assert rt.plan.signature != sig
    finally:
        rt.close()


def test_e2e_batch_shape_selected_from_observed_traffic():
    """Inject a size-4-group arrival pattern; after warmup the recompiled
    plan's bucket set matches the injected distribution."""
    rt = _mk_rt()
    try:
        clock = FakeClock()
        fe = ServingFrontend(rt, FrontendConfig(
            capacity=64, max_batch=8, ladder=(1, 2, 4, 8),
            window_k_max=1, max_wait_s=1e-4), clock=clock)
        key = jax.random.PRNGKey(0)
        for i in range(20):            # 20 groups of exactly 4
            for r in make_request_rows(TINY, jax.random.fold_in(key, i),
                                       4):
                fe.submit(r)
                clock.advance(1e-3)    # 1000 req/s on the virtual clock
            fe.pump()
        fe.drain(timeout=120.0)
        assert rt.stats.requests_completed == 80
        rt.recompile(block=True)
        shape = plan_batch_shape(rt.plan)
        assert shape is not None, "BatchShapePass did not fire"
        buckets, k = shape
        assert buckets == (4,)         # the injected group size's bucket
        assert k == 1                  # 1000 req/s can't fill K>1 windows
        # the batcher reads its shape straight off the swapped plan
        assert fe.batcher.current_shape() == ((4,), 1)
    finally:
        rt.close()


def test_bucket_mispredict_deopts_through_program_guard():
    rt = _mk_rt()
    try:
        clock = FakeClock()
        fe = ServingFrontend(rt, FrontendConfig(
            capacity=64, max_batch=8, ladder=(1, 8), window_k_max=1,
            max_wait_s=0.0, mispredict_window=8, mispredict_deopt=0.4),
            clock=clock)
        # plan buckets = (8,) only — then serve size-1 groups, whose
        # ideal ladder bucket (1) the plan does not offer
        hist = [0] * 8
        hist[7] = 20
        rt.attach_profile(StubProfile(_profile_dict(
            hist, rate=100.0, ladder=(1, 8))))
        rt.recompile(block=True)
        assert plan_batch_shape(rt.plan) == ((8,), 1)
        rt.attach_profile(fe.profile)  # back to the live profile
        v0 = rt.tables.version
        rows = make_request_rows(TINY, jax.random.PRNGKey(2), 20)
        for r in rows:                 # one-at-a-time => size-1 groups
            fe.submit(r)
            clock.advance(1e-3)
            fe.pump()
        fe.drain(timeout=120.0)
        assert rt.stats.shape_mispredicts >= 8
        assert rt.tables.version > v0, "mispredict did not bump version"
        # recompile from the live profile: size-1 groups => bucket 1
        rt.recompile(block=True)
        buckets, _ = plan_batch_shape(rt.plan)
        assert buckets == (1,)
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# step_many on non-example structures + warm_fused
# ---------------------------------------------------------------------------

def test_step_many_serves_bucket_shapes_at_any_k():
    rt = _mk_rt()
    try:
        rows = make_request_rows(TINY, jax.random.PRNGKey(5), 3)
        b = make_request_batch(rows, 4)          # not the example shape
        ref = np.asarray(rt.run_generic(b))
        out1 = np.asarray(rt.step_many([b]))     # K=1, bucket structure
        assert out1.shape[0] == 1
        np.testing.assert_array_equal(out1[0], ref)
        # K=2 fused window: the step runs as a lax.scan body, which XLA
        # fuses and orders differently from the standalone step, so
        # float32 sums may round differently (a few ulps, not bitwise)
        out2 = np.asarray(rt.step_many([b, b]))
        np.testing.assert_allclose(out2[0], ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out2[1], ref, rtol=1e-5, atol=1e-5)
    finally:
        rt.close()


def test_step_many_serves_primary_bucket_k1_without_step(monkeypatch):
    """A frontend K=1 window at the primary bucket (the example batch's
    leading dim, plus the ``valid`` leaf) is served by the fused K=1
    executable through ``step_many``: ``step()`` is never called, and
    the outputs equal ``run_generic``'s bit for bit."""
    rt = _mk_rt()
    try:
        rows = make_request_rows(TINY, jax.random.PRNGKey(7), 5)
        b = make_request_batch(rows, 8)          # the primary bucket
        assert b["valid"].dtype == np.bool_
        ref = np.asarray(rt.run_generic(b))

        def no_step(self, batch):
            raise AssertionError("step_many routed a K=1 window to step()")
        monkeypatch.setattr(MorpheusRuntime, "step", no_step)
        placed = rt.place_batch([b], fused=True)
        out = np.asarray(rt.step_many(placed, k=1))
        assert out.shape == (1,) + ref.shape
        np.testing.assert_array_equal(out[0], ref)
    finally:
        rt.close()


def test_sketch_counts_real_rows_not_padding():
    """A sampled window of 2 requests padded to bucket 8 records the 2
    requests' tokens only: the pad rows (copies of row 0) neither count
    in the sketch nor take places in its candidate ring."""
    rt = _mk_rt()
    try:
        rt.sampler.pin(1)                        # every window samples
        rows = make_request_rows(TINY, jax.random.PRNGKey(11), 2)
        rt.step_many([make_request_batch(rows, 8)])
        st = rt._host_instr_snapshot()["vocab_embed#0"]
        real = np.concatenate([r["tokens"] for r in rows])
        assert int(st["total"]) == real.size
        cand = np.asarray(st["cand"])
        assert (cand >= 0).sum() == real.size
        assert set(cand[cand >= 0].tolist()) == set(real.tolist())
    finally:
        rt.close()


def test_warm_fused_precompiles_every_role():
    """After warm_fused, serving that shape never compiles inline —
    sampled windows (instrumented twin) and deopt windows (generic)
    included."""
    rt = _mk_rt()
    try:
        rows = make_request_rows(TINY, jax.random.PRNGKey(6), 4)
        b = make_request_batch(rows, 4)
        rt.warm_fused([b])
        rt.warm_fused([b, b])
        misses0 = rt.exec_cache.stats.misses
        for _ in range(4):             # crosses the sampling cadence
            rt.step_many([b])
        rt.step_many([b, b])
        rt.control_update("req_class", {"temperature": np.full(
            TINY.n_classes, 1.1, np.float32)})
        rt.step_many([b])              # guard-tripped => generic, warm
        assert rt.exec_cache.stats.misses == misses0
    finally:
        rt.close()
