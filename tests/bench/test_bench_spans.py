"""The program-span queries (``bench/lib/spans.py``) and the four readers
of the program's spans, on hand-made events; then one traced serving
run on the CPU at a small size, whose result line carries all four."""
import dataclasses
from types import SimpleNamespace

import pytest

from bench.lib import harness
from bench.lib import spans as S
from bench.lib import trace as T
from bench_tiny import run as tiny_run

HOST = "/host:CPU"
DEV = "/device:TPU:0"
MS = 1_000_000          # ns


def ev(name, start, dur, line="python", plane=HOST, **stats):
    return T.Event(plane, line, name, float(start), float(dur), stats)


def window_events():
    """A 100 ms window with two dispatched and retired windows on the
    batcher thread, a sender thread, and one collection."""
    return [
        ev("bench.window", 0, 100 * MS, line="main"),
        # window 1: pump with its children, then its retirement
        ev("morpheus.batcher.pump", 1 * MS, 4 * MS, w=1),
        ev("morpheus.batcher.fill", 1 * MS, 2 * MS, target=16),
        ev("morpheus.batcher.pack", 3 * MS, MS // 2, rows=8, k=1,
           bucket=8, pad=0),
        ev("morpheus.runtime.step_many", 4 * MS, MS, k=1),
        ev("morpheus.runtime.launch", 4 * MS, MS // 2, role="spec"),
        ev("morpheus.batcher.retire", 10 * MS, 30 * MS, w=1),
        ev("morpheus.batcher.retire.wait", 10 * MS, 20 * MS),
        ev("morpheus.batcher.retire.d2h", 30 * MS, 6 * MS, bytes=1),
        ev("morpheus.batcher.retire.fanback", 36 * MS, 4 * MS,
           requests=8),
        # window 2: a step_many holding a step (counted once)
        ev("morpheus.batcher.pump", 50 * MS, 4 * MS, w=2),
        ev("morpheus.runtime.step_many", 51 * MS, 3 * MS, k=1),
        ev("morpheus.runtime.step", 51 * MS, 2 * MS, k=1),
        ev("morpheus.batcher.retire", 60 * MS, 30 * MS, w=2),
        ev("morpheus.batcher.retire.wait", 60 * MS, 10 * MS),
        ev("morpheus.batcher.retire.d2h", 70 * MS, 1 * MS, bytes=1),
        ev("morpheus.batcher.retire.fanback", 71 * MS, 1 * MS,
           requests=8),
        # a retire.wait that runs past the window's end counts inside it
        ev("morpheus.batcher.retire", 95 * MS, 20 * MS, w=3),
        ev("morpheus.batcher.retire.wait", 95 * MS, 10 * MS),
        # the sender, on a thread of the same name, and a collection
        ev("bench.submit", 45 * MS, 12 * MS, line="python"),
        ev("morpheus.gc", 52 * MS, 10 * MS, generation=2),
        ev("%fusion.1", 5 * MS, 20 * MS, line=T.OPS_LINE, plane=DEV),
    ]


def run_of(events):
    return SimpleNamespace(trace=T.TraceView(events) if events is not None
                           else None)


def read(metric, events):
    return harness.reader(metric)(run_of(events))


def test_tree_children_self_time_and_outermost():
    tree = S.SpanTree(S.program_spans(window_events()))
    [pump] = [e for e in tree.spans if e.stats.get("w") == 1
              and e.name == "morpheus.batcher.pump"]
    names = [k.name for k in tree.children(pump)]
    assert names == ["morpheus.batcher.fill", "morpheus.batcher.pack",
                     "morpheus.runtime.step_many"]
    # 4 ms pump: fill 2 + pack 0.5 + step_many 1 leave 0.5 ms
    assert tree.self_ns(pump) == pytest.approx(0.5 * MS)
    outer = tree.outermost(["morpheus.runtime.step_many",
                            "morpheus.runtime.step"])
    assert [e.start_ns for e in outer] == [4 * MS, 51 * MS]


def test_a_span_of_another_thread_that_overlaps_is_no_parent():
    # the collection starts inside window 2's pump, on the sender's
    # thread, and ends after it
    tree = S.SpanTree(S.program_spans(window_events()))
    [gc_span] = [e for e in tree.spans if e.name == "morpheus.gc"]
    assert tree.parent[id(gc_span)] is None


def test_window_spans_and_pairing_by_w():
    view = T.TraceView(window_events())
    pumps = S.window_spans(view, "morpheus.batcher.pump")
    retires = S.window_spans(view, "morpheus.batcher.retire")
    assert [w for w, _, _ in S.pair_by_w(pumps, retires)] == [1, 2]
    assert S.window_spans(None, "morpheus.batcher.pump") is None
    no_window = T.TraceView([e for e in window_events()
                             if e.name != "bench.window"])
    assert S.window_spans(no_window, "morpheus.batcher.pump") is None


def test_a_gap_is_attributed_to_the_innermost_open_spans():
    events = window_events()
    # the device is idle over [25, 45] ms: the batcher is in retire.wait
    # of window 1 until 30 ms, then in its d2h and fan-back
    open_ = S.innermost_open(events, 25 * MS, 45 * MS)
    names = [e.name for e, _ in open_]
    assert names == ["morpheus.batcher.retire.d2h",
                     "morpheus.batcher.retire.wait",
                     "morpheus.batcher.retire.fanback"]
    assert [ov for _, ov in open_] == [6 * MS, 5 * MS, 4 * MS]
    # over [55, 57] ms the collection is the only program span open
    [(e, ov)] = S.innermost_open(events, 55 * MS, 57 * MS)
    assert e.name == "morpheus.gc" and ov == 2 * MS
    assert S.innermost_open(events, 42 * MS, 44 * MS) == []


def test_batcher_blocked_share():
    # waits of 20 + 10 + 5 (clipped at 100 ms) of a 100 ms window
    assert read("batcher_blocked_share.serve", window_events()) == \
        pytest.approx(35.0)


def test_fanback_p99_is_the_slowest_copy_and_fanback():
    assert read("fanback_p99_ms", window_events()) == pytest.approx(10.0)


def test_report_splits_fanback_by_window_size_and_loads_as_bench():
    from bench import span_report
    view = T.TraceView(window_events())
    by_k = span_report.fanback_by_k(view, 0, 100 * MS)
    # window 1 was packed with k=1; windows 2 and 3 have no pack span
    assert by_k["1"] == {"windows": 1, "p50": 10.0, "p99": 10.0,
                         "max": 10.0}
    assert by_k["?"]["windows"] == 2 and by_k["?"]["max"] == 2.0
    assert max(v["p99"] for v in by_k.values()) == \
        read("fanback_p99_ms", window_events())
    # thread-distinct host lines fold back to the thread name alone
    threads = [dataclasses.replace(e, line=f"{e.line}#{i}")
               if e.plane == HOST else e
               for i, e in enumerate(window_events())]
    merged = span_report.as_bench(T.TraceView(threads))
    assert [e.line for e in merged.events] == \
        [e.line for e in window_events()]


def test_dispatch_host_ms_counts_a_nested_step_once():
    assert read("dispatch_host_ms.serve", window_events()) == \
        pytest.approx(2.0)


def test_gc_pause_is_the_collections_in_the_window():
    assert read("gc_pause_ms.serve", window_events()) == \
        pytest.approx(10.0)
    no_gc = [e for e in window_events() if e.name != "morpheus.gc"]
    assert read("gc_pause_ms.serve", no_gc) == 0.0


METRICS = ("batcher_blocked_share.serve", "fanback_p99_ms",
           "dispatch_host_ms.serve", "gc_pause_ms.serve")


@pytest.mark.parametrize("metric", METRICS)
def test_without_program_spans_or_a_trace_nothing_is_read(metric):
    # a program without the spans: only the benchmark's own and the
    # device's events
    bare = [e for e in window_events()
            if not e.name.startswith("morpheus.")]
    assert read(metric, bare) is None
    assert read(metric, None) is None


def test_a_traced_small_serve_reads_all_four():
    res = tiny_run("phi35moe-skewed", seed=2**31 + 5, trace=True)
    assert res["correct"], res["compared"]
    got = res["metrics"]
    for m in METRICS:
        assert m in got, sorted(got)
    assert 0.0 <= got["batcher_blocked_share.serve"]["value"] <= 100.0
    assert got["fanback_p99_ms"]["value"] > 0
    assert got["dispatch_host_ms.serve"]["value"] > 0
    assert got["gc_pause_ms.serve"]["value"] >= 0.0
