"""Program spans on the profiler's clock (``repro.core.tracing``).

One tiny serve through the request frontend is traced with
``jax.profiler``: synchronous pumps form and retire windows, a direct
``step`` runs the single-step path, a control update makes the snapshot
worker copy, ``gc.collect()`` runs a collection, and a forced
``recompile(block=True)`` runs a cycle that compiles.  The trace must
hold every span with its stats, children inside their parents on the
same thread, pump and retire spans paired one to one by ``w``, and the
cycle's compiles tagged with its ordinal ``n``.
"""
import gc
import glob
import os
import tempfile
from collections import namedtuple

import jax
import numpy as np
import pytest

from repro.core import EngineConfig, MorpheusRuntime, SketchConfig, \
    tracing
from repro.serving import ServeConfig, build_params, build_tables, \
    make_request_rows, make_serve_step, make_synthetic_batch
from repro.serving.frontend import FrontendConfig, ServingFrontend

TINY = ServeConfig(d_model=32, n_layers=1, n_heads=4, vocab=128,
                   n_experts=4, d_ff=32, n_classes=8, n_slots=32, seq=4)
KEY = jax.random.PRNGKey(0)

Span = namedtuple("Span", "thread name start end stats")

# span -> the stats it must carry
STATS = {
    "morpheus.batcher.pump": {"w"},
    "morpheus.batcher.fill": {"target"},
    "morpheus.batcher.pack": {"rows", "k", "bucket", "pad"},
    "morpheus.runtime.place": {"transfers"},
    "morpheus.runtime.step_many": {"k"},
    "morpheus.runtime.step": {"k"},
    "morpheus.runtime.prepare": set(),
    "morpheus.runtime.claim": set(),
    "morpheus.runtime.launch": {"role"},
    "morpheus.runtime.commit": set(),
    "morpheus.batcher.retire": {"w"},
    "morpheus.batcher.retire.wait": set(),
    "morpheus.batcher.retire.d2h": {"bytes"},
    "morpheus.batcher.retire.fanback": {"requests"},
    "morpheus.cycle": {"plane", "n"},
    "morpheus.cycle.snapshot": set(),
    "morpheus.cycle.plan": set(),
    "morpheus.cycle.compile": set(),
    "morpheus.cycle.swap": set(),
    "morpheus.engine.compile": {"fuse", "n"},
    "morpheus.snapshot.copy": {"version"},
    "morpheus.gc": {"generation"},
}

# child -> the spans it may lie inside, on its own thread
PARENTS = {
    "morpheus.batcher.fill": {"morpheus.batcher.pump"},
    "morpheus.batcher.pack": {"morpheus.batcher.pump"},
    "morpheus.runtime.place": {"morpheus.batcher.pump"},
    "morpheus.runtime.step_many": {"morpheus.batcher.pump"},
    "morpheus.runtime.prepare": {"morpheus.runtime.step_many"},
    "morpheus.runtime.claim": {"morpheus.runtime.step_many",
                               "morpheus.runtime.step"},
    "morpheus.runtime.launch": {"morpheus.runtime.step_many",
                                "morpheus.runtime.step"},
    "morpheus.runtime.commit": {"morpheus.runtime.step_many",
                                "morpheus.runtime.step"},
    "morpheus.batcher.retire.wait": {"morpheus.batcher.retire"},
    "morpheus.batcher.retire.d2h": {"morpheus.batcher.retire"},
    "morpheus.batcher.retire.fanback": {"morpheus.batcher.retire"},
    "morpheus.cycle.snapshot": {"morpheus.cycle"},
    "morpheus.cycle.plan": {"morpheus.cycle"},
    "morpheus.cycle.compile": {"morpheus.cycle"},
    "morpheus.cycle.swap": {"morpheus.cycle"},
}


def _mk_rt():
    return MorpheusRuntime(
        make_serve_step(TINY), build_tables(TINY, KEY),
        build_params(TINY, KEY), make_synthetic_batch(TINY, KEY, 8),
        cfg=EngineConfig(
            sketch=SketchConfig(sample_every=2, max_hot=4,
                                hot_coverage=0.6),
            features={"vision_enabled": False, "track_sessions": True},
            moe_router_table="router"))


def _load(log_dir):
    """The trace's program spans; ``thread`` names the host line (each
    thread has its own)."""
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                       recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("morpheus."):
                    out.append(Span((plane.name, i), e.name,
                                    e.start_ns, e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def _serve(fe, rows):
    for r in rows:
        fe.submit(r)
    assert fe.drain(timeout=120.0)


@pytest.fixture(scope="module")
def traced():
    rt = _mk_rt()
    fe = ServingFrontend(rt, FrontendConfig(
        capacity=64, max_batch=8, ladder=(1, 8), max_wait_s=1e-3,
        window_k_max=2, inflight=2))
    rows = make_request_rows(TINY, KEY, 40)
    try:
        _serve(fe, rows[:20])            # compiles outside the trace
        log_dir = tempfile.mkdtemp(prefix="tracing-test-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            _serve(fe, rows[20:])
            jax.block_until_ready(rt.step(make_synthetic_batch(TINY,
                                                               KEY, 8)))
            gc.collect()
            rt.control_update("req_class", {"temperature": np.full(
                TINY.n_classes, 0.5, np.float32)})
            res = rt.recompile(block=True)
        finally:
            jax.profiler.stop_trace()
        assert res is not None and not res.get("revalidated")
        yield _load(log_dir), rt
    finally:
        fe.stop()
        rt.close()


def test_every_span_is_recorded_with_its_stats(traced):
    spans, _ = traced
    for name, stats in STATS.items():
        found = [s for s in spans if s.name == name]
        assert found, f"no {name} span"
        for s in found:
            assert stats <= set(s.stats), (name, s.stats)
    launches = [s for s in spans if s.name == "morpheus.runtime.launch"]
    assert {s.stats["role"] for s in launches} <= {
        "spec", "instr", "generic", "degraded"}
    fused = [s for s in launches if "memo" in s.stats]
    assert fused and all(s.stats["memo"] in ("hit", "miss")
                         and s.stats["retries"] >= 0 for s in fused)
    packs = [s for s in spans if s.name == "morpheus.batcher.pack"]
    assert sum(s.stats["rows"] for s in packs) == 20
    assert all(s.stats["pad"] == s.stats["bucket"] * s.stats["k"]
               - s.stats["rows"] for s in packs)
    # a window's copy is the logits of every row: (k, bucket, seq, vocab)
    # float32, the k and bucket its pack span gave
    pumps = [s for s in spans if s.name == "morpheus.batcher.pump"]
    shape = {}
    for p in pumps:
        [pack] = [s for s in packs if s.thread == p.thread
                  and p.start <= s.start and s.end <= p.end]
        shape[p.stats["w"]] = pack.stats["k"] * pack.stats["bucket"]
    for r in (s for s in spans if s.name == "morpheus.batcher.retire"):
        [d2h] = [s for s in spans
                 if s.name == "morpheus.batcher.retire.d2h"
                 and s.thread == r.thread
                 and r.start <= s.start and s.end <= r.end]
        assert d2h.stats["bytes"] == \
            shape[r.stats["w"]] * TINY.seq * TINY.vocab * 4


def test_children_lie_inside_their_parents_on_the_same_thread(traced):
    spans, _ = traced
    for child, parents in PARENTS.items():
        for c in (s for s in spans if s.name == child):
            holders = [p for p in spans if p.name in parents
                       and p.thread == c.thread
                       and p.start <= c.start and c.end <= p.end]
            assert holders, f"{child} at {c.start} has no parent"


def test_pump_and_retire_spans_pair_one_to_one_by_w(traced):
    spans, _ = traced
    pumps = [s.stats["w"] for s in spans
             if s.name == "morpheus.batcher.pump"]
    retires = [s.stats["w"] for s in spans
               if s.name == "morpheus.batcher.retire"]
    assert pumps and len(set(pumps)) == len(pumps)
    assert sorted(pumps) == sorted(retires)
    by_w = {s.stats["w"]: s for s in spans
            if s.name == "morpheus.batcher.pump"}
    for s in spans:
        if s.name == "morpheus.batcher.retire":
            assert s.start >= by_w[s.stats["w"]].start


def test_forced_cycle_records_its_children_and_its_compiles(traced):
    spans, rt = traced
    [cycle] = [s for s in spans if s.name == "morpheus.cycle"]
    assert cycle.stats["plane"] == str(rt.plane_id)
    kids = [s for s in spans if s.name.startswith("morpheus.cycle.")
            and s.thread == cycle.thread
            and cycle.start <= s.start and s.end <= cycle.end]
    assert [s.name.rsplit(".", 1)[1] for s in sorted(
        kids, key=lambda s: s.start)] == ["snapshot", "plan", "compile",
                                          "swap"]
    compiles = [s for s in spans if s.name == "morpheus.engine.compile"
                and s.stats.get("n") == cycle.stats["n"]]
    assert compiles
    [cspan] = [s for s in kids if s.name == "morpheus.cycle.compile"]
    assert all(cspan.start <= s.start and s.end <= cspan.end
               for s in compiles)


def test_gc_collect_records_a_gc_span(traced):
    spans, _ = traced
    gcs = [s for s in spans if s.name == "morpheus.gc"]
    assert any(s.stats["generation"] == 2 for s in gcs)


def test_unread_histogram_series_are_no_longer_written(traced):
    _, rt = traced
    assert {"request_queue_wait_s", "request_total_s"} <= set(rt.stats.hists)
    assert not {"request_batch_wait_s", "request_execute_s"} & set(
        rt.stats.hists)


def test_two_runtimes_leave_exactly_one_gc_hook():
    a, b = _mk_rt(), _mk_rt()
    try:
        assert gc.callbacks.count(tracing._gc_hook) == 1
    finally:
        a.close()
        b.close()
    tracing.install_gc_spans()
    assert gc.callbacks.count(tracing._gc_hook) == 1
