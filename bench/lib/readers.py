"""Helpers the metric readers share: the traced window and device busy
time inside host spans.  Each returns None where the run has no trace,
so a reader that finds nothing to read reports nothing."""
from __future__ import annotations

import re
from typing import List, Optional, Tuple


def window_busy_s(run) -> Optional[float]:
    """Seconds of the traced window in which the device was busy."""
    if run.trace is None or not run.trace.device_planes:
        return None
    span = run.trace.span("bench.window")
    if span is None:
        return None
    return run.trace.busy_ns(*span) * 1e-9


def idle_share(run) -> Optional[float]:
    """Percent of the traced window with no operation on the device."""
    if run.trace is None or not run.trace.device_planes:
        return None
    span = run.trace.span("bench.window")
    if span is None:
        return None
    lo, hi = span
    return 100.0 * (1.0 - run.trace.busy_ns(lo, hi) / (hi - lo))


def span_busy_s(run, name: str) -> Optional[float]:
    """Seconds of device work inside the host spans called ``name``."""
    if run.trace is None or not run.trace.device_planes:
        return None
    spans = run.trace.annotations(name)
    if not spans:
        return None
    return sum(run.trace.busy_ns(lo, hi) for lo, hi in spans) * 1e-9


_SHAPE = re.compile(r"f32\[(\d+),(\d+)\]")


def kernel_calls(run, kernel: str) -> Optional[List[Tuple[int, int, float]]]:
    """``(rows, width, seconds)`` of every device op of ``kernel`` in the
    traced window: ops whose name or HLO stats name the kernel, their
    float32 output shape read from the op's stats."""
    if run.trace is None or not run.trace.device_planes:
        return None
    span = run.trace.span("bench.window")
    if span is None:
        return None
    lo, hi = span
    out = []
    for e in run.trace.ops():
        if e.start_ns < lo or e.end_ns > hi:
            continue
        text = " ".join([e.name] + [str(v) for v in e.stats.values()])
        if kernel not in text:
            continue
        m = _SHAPE.search(text)
        if m is None:
            continue
        out.append((int(m.group(1)), int(m.group(2)), e.dur_ns * 1e-9))
    return out or None
