"""The open-loop generator and the latency arithmetic."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench.drivers.serve_plane import check_sessions, request_times
from bench.lib import arrivals
from bench.lib.common import quantile, rng


def test_gap_generators_match_the_programs():
    from repro.serving.frontend import arrivals as prog
    for seed in (0, 3):
        assert np.allclose(arrivals.poisson_gaps(50.0, 200,
                                                 np.random.default_rng(seed)),
                           prog.poisson_gaps(50.0, 200, seed=seed))
        assert np.allclose(
            arrivals.bursty_onoff_gaps(50.0, 200,
                                       np.random.default_rng(seed)),
            prog.bursty_onoff_gaps(50.0, 200, seed=seed))


@pytest.mark.parametrize("process", [{"process": "poisson"},
                                     {"process": "onoff", "burst_len": 8,
                                      "duty": 0.25}])
def test_schedule_fixes_the_count_and_fills_the_window(process):
    a = arrivals.schedule(process, 300.0, 10.0, rng(2**31 + 7, "w"))
    b = arrivals.schedule(process, 300.0, 10.0, rng(2**31 + 7, "w"))
    c = arrivals.schedule(process, 300.0, 10.0, rng(8, "w"))
    assert len(a) == len(c) == 3000
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and 0 < a[0] and a[-1] < 10.0
    # every seed offers the same gaps in another order (one of the n + 1
    # gaps falls after the window's last request)
    gaps_a = np.diff(a, prepend=0.0)
    gaps_c = np.sort(np.diff(c, prepend=0.0))
    i = np.clip(np.searchsorted(gaps_c, gaps_a), 1, len(gaps_c) - 1)
    nearest = np.minimum(abs(gaps_c[i] - gaps_a), abs(gaps_c[i - 1] - gaps_a))
    assert np.sum(nearest > 1e-9) <= 2


class _Req:
    def __init__(self, status="ok", arrival_ts=0.0, total_s=0.0,
                 queue_s=0.0):
        self.status = status
        self.arrival_ts = arrival_ts
        self.timing = {"total_s": total_s, "queue_wait_s": queue_s}
        self.done = True
        self.output = None


def test_latency_counts_from_the_due_time_and_missing_is_infinite():
    due = np.array([0.0, 1.0, 2.0, 3.0])
    t0 = 100.0
    sender = SimpleNamespace(
        requests=[
            _Req("ok", arrival_ts=100.5, total_s=0.25, queue_s=0.1),
            _Req("shed"),
            _Req("failed"),
            None,                           # never submitted
        ],
        lateness_s=lambda: np.array([0.5, 0.0, 0.0, np.nan]))
    t = request_times({"t0": t0, "due": due, "sender": sender})
    # sent 0.5 s late, served in 0.25 s: 0.75 s after it was due
    assert t["latency_ms"][0] == pytest.approx(750.0)
    assert t["latency_ms"][1:] == [float("inf")] * 3
    assert t["queue_wait_ms"] == [pytest.approx(100.0)]
    assert t["completed_ok"] == 1
    assert t["lateness_ms"][3] == float("inf")
    assert quantile(t["latency_ms"], 0.5) == float("inf")
    assert quantile(t["latency_ms"], 0.25) == pytest.approx(750.0)


def test_nearest_rank_quantile():
    v = list(range(1, 101))
    assert quantile(v, 0.5) == 50
    assert quantile(v, 0.99) == 99
    assert quantile([3.0], 0.99) == 3.0
    assert quantile(v + [float("inf")] * 2, 0.99) == float("inf")


def test_sender_keeps_the_schedule_and_reports_lateness():
    got = []

    def submit(x):
        got.append((x, time.monotonic()))
        if x == 1:
            time.sleep(0.05)            # a slow submit delays the next
        return x

    due = np.array([0.0, 0.01, 0.02, 0.2])
    t0 = time.monotonic() + 0.02
    s = arrivals.Sender(submit, [0, 1, 2, 3], due, t0).start()
    assert s.join(timeout=5.0)
    late = s.lateness_s()
    assert [x for x, _ in got] == [0, 1, 2, 3]
    assert late[2] >= 0.03             # held up behind request 1
    assert late[3] < 0.03              # back on schedule
    assert all(t >= t0 + d - 1e-3 for (_, t), d in zip(got, due))


def test_sessions_check_counts_writes_per_slot():
    rows = [{"slot": 0}, {"slot": 0}, {"slot": 2}]
    before = {"count": np.zeros(4, np.int32),
              "last_token": np.zeros(4, np.int32)}
    good = {"count": np.array([2, 0, 1, 0]),
            "last_token": np.zeros(4, np.int32)}
    assert check_sessions(before, good, rows, [None] * 3, {}, [])[0] == 0
    unchanged = {"count": np.zeros(4, np.int32),
                 "last_token": np.zeros(4, np.int32)}
    assert check_sessions(before, unchanged, rows, [None] * 3, {}, [])[0] \
        == 2
    stray = {"count": np.array([1, 1, 1, 0]),
             "last_token": np.zeros(4, np.int32)}
    assert check_sessions(before, stray, rows, [None] * 3, {}, [])[0] == 1
