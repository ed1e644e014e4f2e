"""Open-loop arrivals on an absolute schedule.

The gap generators are copies of ``repro.serving.frontend.arrivals``'s
``poisson_gaps`` and ``bursty_onoff_gaps``, kept here so that a change
to the program cannot move the yardstick.  :func:`schedule` turns gaps
into due times: a fixed number of requests, ``rate x seconds``, spread
over the window by one fixed draw of the gaps, turned by an offset drawn
from the seed, so every seed offers the same work in another order.
:class:`Sender` submits each request at its due time on the monotonic
clock and records when it really went out; latency is taken from the due
time, so a late sender shows as latency and as lateness, never as a
faster server.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np


def poisson_gaps(rate_hz: float, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """N exponential inter-arrival gaps with mean ``1/rate_hz``."""
    return rng.exponential(1.0 / float(rate_hz), n)


def bursty_onoff_gaps(rate_hz: float, n: int, rng: np.random.Generator,
                      burst_len: int = 32, duty: float = 0.25
                      ) -> np.ndarray:
    """N gaps of an ON/OFF process at long-run rate ``rate_hz``: bursts
    of ``burst_len`` arrivals at ``rate_hz/duty``, separated by OFF gaps
    that keep the mean gap at ``1/rate_hz``."""
    if not (0.0 < duty <= 1.0):
        raise ValueError("duty must be in (0, 1]")
    gaps = rng.exponential(duty / float(rate_hz), n)
    off_mean = (burst_len / float(rate_hz)) * (1.0 - duty)
    idx = np.arange(n) % burst_len == 0
    idx[0] = False
    gaps[idx] = rng.exponential(off_mean, int(idx.sum()))
    return gaps


GAPS = {"poisson": poisson_gaps, "onoff": bursty_onoff_gaps}
GAP_SEED = 20210615      # the one draw of gaps that every seed turns


def schedule(arrival: dict, rate_hz: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from the window's start) of
    ``round(rate_hz * seconds)`` requests: one fixed draw of the process's
    gaps, the same for every seed, turned by an offset drawn from
    ``rng`` and scaled so that the requests and one more gap fill the
    window exactly."""
    n = max(int(round(rate_hz * seconds)), 1)
    kw = {k: v for k, v in arrival.items() if k != "process"}
    gaps = GAPS[arrival["process"]](rate_hz, n + 1,
                                    np.random.default_rng(GAP_SEED), **kw)
    t = np.cumsum(np.roll(gaps, int(rng.integers(n + 1))))
    return t[:n] * (seconds / t[n])


class Sender:
    """Submits ``payloads[i]`` at ``t0 + due[i]`` (monotonic clock) on a
    thread of its own.  ``sent[i]`` is when the submit really started;
    ``requests[i]`` is what ``submit`` returned.  ``on_sent`` runs after
    every submit (the harness drops outputs it will not check there)."""

    def __init__(self, submit: Callable, payloads: Sequence,
                 due: np.ndarray, t0: float,
                 on_sent: Optional[Callable[[int], None]] = None,
                 annotate: Optional[Callable[[str], object]] = None):
        if len(payloads) != len(due):
            raise ValueError("need one due time per payload")
        self.submit = submit
        self.payloads = payloads
        self.due = np.asarray(due, np.float64)
        self.t0 = float(t0)
        self.on_sent = on_sent
        self.annotate = annotate
        self.sent: List[float] = [float("nan")] * len(payloads)
        self.requests: List = [None] * len(payloads)
        self._thread = threading.Thread(target=self._run,
                                        name="bench-sender", daemon=True)

    def _run(self) -> None:
        for i, payload in enumerate(self.payloads):
            wait = self.t0 + self.due[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            self.sent[i] = time.monotonic()
            if self.annotate is not None:
                with self.annotate("bench.submit"):
                    self.requests[i] = self.submit(payload)
            else:
                self.requests[i] = self.submit(payload)
            if self.on_sent is not None:
                self.on_sent(i)

    def start(self) -> "Sender":
        self._thread.start()
        return self

    def join(self, timeout: Optional[float] = None) -> bool:
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def lateness_s(self) -> np.ndarray:
        """How late each request went out (seconds; NaN if never)."""
        return np.asarray(self.sent) - (self.t0 + self.due)
