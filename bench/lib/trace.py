"""Profiler trace -> events -> device busy time, idle gaps and breakdowns.

A trace is read once into a flat list of :class:`Event` (plane, line,
name, start, duration, stats); every reduction below works on that list,
so the tests can drive it from a small recorded trace.  Times are in
nanoseconds on the profiler's clock, which host annotations
(``jax.profiler.TraceAnnotation``) and device operations share.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object] = field(default_factory=dict, compare=False)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    return paths[0]


def load_events(path: str) -> List[Event]:
    """Every event of an ``.xplane.pb`` file as an :class:`Event`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                try:
                    stats = dict(e.stats)
                except (TypeError, ValueError):
                    stats = {}
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 stats))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


class TraceView:
    """Queries over one trace's events."""

    def __init__(self, events: Sequence[Event]):
        self.events = list(events)
        self.device_planes = sorted({e.plane for e in self.events
                                     if e.plane.startswith(
                                         DEVICE_PLANE_PREFIX)})
        # per device plane: the union of its op intervals, for fast
        # busy-time queries over many spans
        self._busy: Dict[str, List[Interval]] = {
            p: union((e.start_ns, e.end_ns) for e in self.ops(p))
            for p in self.device_planes}

    # ---- host side ---------------------------------------------------
    def annotations(self, name: str) -> List[Interval]:
        """Intervals of the host events called ``name`` (the benchmark's
        own ``TraceAnnotation`` spans)."""
        return sorted((e.start_ns, e.end_ns) for e in self.events
                      if e.name == name
                      and not e.plane.startswith(DEVICE_PLANE_PREFIX))

    def span(self, name: str) -> Optional[Interval]:
        """The single host span called ``name`` (None when absent)."""
        spans = self.annotations(name)
        if not spans:
            return None
        if len(spans) > 1:
            raise ValueError(f"{len(spans)} spans called {name!r}")
        return spans[0]

    def host_events(self) -> List[Event]:
        return [e for e in self.events
                if not e.plane.startswith(DEVICE_PLANE_PREFIX)
                and e.dur_ns > 0]

    # ---- device side -------------------------------------------------
    def ops(self, plane: Optional[str] = None) -> List[Event]:
        """Device operations (the ``XLA Ops`` line) of one device plane,
        or of every device plane."""
        return [e for e in self.events
                if e.line == OPS_LINE and e.dur_ns > 0
                and (e.plane == plane if plane is not None
                     else e.plane.startswith(DEVICE_PLANE_PREFIX))]

    def busy_ns(self, lo: float, hi: float,
                plane: Optional[str] = None) -> float:
        """Time in [lo, hi] during which some operation ran on the
        device; averaged over the device planes when ``plane`` is None."""
        planes = [plane] if plane is not None else self.device_planes
        if not planes:
            return 0.0
        total = 0.0
        for p in planes:
            busy = self._busy[p]
            i = max(bisect.bisect_left(busy, (lo, lo)) - 1, 0)
            while i < len(busy) and busy[i][0] < hi:
                s, e = busy[i]
                total += max(0.0, min(e, hi) - max(s, lo))
                i += 1
        return total / len(planes)

    def idle_gaps(self, lo: float, hi: float, plane: str
                  ) -> List[Interval]:
        """The intervals in [lo, hi] with no operation on ``plane``."""
        busy = clip(self._busy[plane], lo, hi)
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def op_time_by_name(self, lo: float, hi: float,
                        plane: Optional[str] = None) -> Dict[str, float]:
        """Device time (ns) per operation name inside [lo, hi], summed
        over the device planes."""
        out: Dict[str, float] = {}
        for e in self.ops(plane):
            for s, t in clip([(e.start_ns, e.end_ns)], lo, hi):
                key = op_label(e)
                out[key] = out.get(key, 0.0) + (t - s)
        return out

    def host_activity(self, lo: float, hi: float,
                      prefix: str = "bench.") -> str:
        """What the host was doing in [lo, hi]: the benchmark's span
        (``prefix``) that overlaps it most, else the longest-overlapping
        host event of any name, else ``"none"``."""
        best: Dict[str, float] = {}
        for e in self.host_events():
            ov = min(e.end_ns, hi) - max(e.start_ns, lo)
            if ov <= 0:
                continue
            best[e.name] = best.get(e.name, 0.0) + ov
        if not best:
            return "none"
        ours = {k: v for k, v in best.items() if k.startswith(prefix)
                and k != "bench.window"}
        pool = ours or {k: v for k, v in best.items()
                        if k != "bench.window"} or best
        return max(pool, key=pool.get)


def op_label(e: Event) -> str:
    """A stable label for a device op: its HLO instruction's name with
    the numeric suffixes dropped (``%fusion.52 = ... fusion(...),
    kind=kLoop`` -> ``fusion:kLoop``, ``%cond.2.clone.1 = ...`` ->
    ``cond``), the fusion kind kept for fusions."""
    text = str(e.stats.get("hlo_op", e.name))
    name = text.split(" = ", 1)[0].lstrip("%").strip()
    base = name.split(".", 1)[0] or name
    if base.startswith("fusion") and "kind=" in text:
        base += ":" + text.split("kind=", 1)[1].split(",", 1)[0].strip()
    return base


def breakdown(view: TraceView, lo: float, hi: float, top: int = 10
              ) -> Dict[str, list]:
    """The device ops that took most time in [lo, hi], and the longest
    idle gaps of the first device labelled by the host's activity, as
    ``[[name, seconds], ...]`` (at most ``top`` entries each)."""
    ops = sorted(view.op_time_by_name(lo, hi).items(),
                 key=lambda kv: -kv[1])[:top]
    gaps: List[list] = []
    if view.device_planes:
        plane = view.device_planes[0]
        longest = sorted(view.idle_gaps(lo, hi, plane),
                         key=lambda g: g[0] - g[1])[:top]
        gaps = [[view.host_activity(s, e), (e - s) * 1e-9]
                for s, e in longest]
    n_planes = max(len(view.device_planes), 1)
    return {"device_ops": [[k, v * 1e-9 / n_planes] for k, v in ops],
            "idle_gaps": gaps}
