"""The trace-to-metric reduction, on hand-made events and on a small
trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench.lib import trace as T

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur, **stats):
    return T.Event(plane, line, name, float(start), float(dur), stats)


def view():
    return T.TraceView([
        ev(HOST, "main", "bench.window", 100, 1000),
        ev(HOST, "sender", "bench.submit", 150, 40),
        ev(HOST, "main", "bench.replay.spec", 1200, 100),
        ev(DEV, T.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), "
           "kind=kLoop, calls=%fc.1", 120, 30),
        ev(DEV, T.OPS_LINE, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b), "
           "kind=kLoop, calls=%fc.2", 140, 30),        # overlaps
        ev(DEV, T.OPS_LINE, "%hot_gather_kernel.3 = f32[128,512]{1,0} "
           "custom-call(s32[128]{0} %i)", 400, 100),
        ev(DEV, T.OPS_LINE, "fusion.4", 1050, 100),      # crosses the end
        ev(DEV, T.OPS_LINE, "copy.5", 1220, 50),
    ])


def test_union_merges_and_sorts():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == \
        [(0, 3), (5, 9)]
    assert T.covered([(0, 2), (1, 3)]) == 3


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    v = view()
    lo, hi = v.span("bench.window")
    assert (lo, hi) == (100, 1100)
    # [120,170] + [400,500] + [1050,1100] = 50 + 100 + 50
    assert v.busy_ns(lo, hi) == 200
    assert v.device_planes == [DEV]


def test_idle_gaps_cover_the_rest_of_the_window():
    v = view()
    gaps = v.idle_gaps(100, 1100, DEV)
    assert gaps == [(100, 120), (170, 400), (500, 1050)]
    assert sum(e - s for s, e in gaps) + v.busy_ns(100, 1100) == 1000


def test_spans_and_busy_inside_them():
    v = view()
    assert v.annotations("bench.replay.spec") == [(1200, 1300)]
    assert v.busy_ns(1200, 1300) == 50
    assert v.span("missing") is None


def test_breakdown_names_ops_and_gaps_by_host_activity():
    v = view()
    b = T.breakdown(v, 100, 1100)
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "hot_gather_kernel"
    assert dict(b["device_ops"])["fusion:kLoop"] == pytest.approx(60e-9)
    longest = b["idle_gaps"][0]
    assert longest[1] == pytest.approx(550e-9)
    # the host ran nothing of the benchmark's there but the window
    assert longest[0] == "bench.window"
    assert b["idle_gaps"][1][0] == "bench.submit"      # gap [170, 400]


def test_op_label_drops_numeric_suffixes():
    e = ev(DEV, T.OPS_LINE, "%cond.2.clone.1 = (f32[8]{0}) conditional("
           "s32[] %p), branch_computations={%r.1, %r.2}", 0, 1)
    assert T.op_label(e) == "cond"
    assert T.op_label(ev(DEV, T.OPS_LINE, "copy.5", 0, 1)) == "copy"


RECORDED = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace_reduces():
    """Three calls of a Pallas row gather and a small jitted matmul,
    recorded on a TPU v5e inside a ``bench.window`` span."""
    v = T.TraceView(T.load_events(str(RECORDED)))
    lo, hi = v.span("bench.window")
    assert v.device_planes
    busy = v.busy_ns(lo, hi)
    assert 0 < busy < hi - lo
    assert len(v.annotations("bench.submit")) == 3
    b = T.breakdown(v, lo, hi)
    assert b["device_ops"] and b["idle_gaps"]
    assert sum(s for _, s in b["device_ops"]) == pytest.approx(
        busy * 1e-9, rel=0.5)
