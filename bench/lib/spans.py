"""Queries over the program's own spans (``morpheus.*``) in a trace.

The program opens ``jax.profiler.TraceAnnotation`` spans named
``morpheus.<layer>.<part>`` on the serve and control paths
(``src/repro/core/tracing.py``); their keyword arguments are the events'
stats.  These helpers work on the flat :class:`~bench.lib.trace.Event`
list of a trace, so the tests can drive them with hand-made events:

* the span tree: a span's children are the spans of its line that lie
  inside it, and its self time is what they leave uncovered;
* the spans whose start lies in the ``bench.window`` span;
* pairing two kinds of span by their ``w`` stat (the batcher's window
  ordinal);
* the innermost program spans open over an interval, on any host line.

A trace loaded by :func:`bench.lib.trace.load_events` names a host line
by its thread's name, and every Python thread is called ``python``
there, so spans of two threads can share a line.  Each query tolerates
that: a span is only a child of one that holds it whole.
:func:`load_thread_events` gives each host line a name of its own.

Where the program has no such spans (a program without them, or no
trace) every query returns nothing, and the readers report nothing.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .trace import DEVICE_PLANE_PREFIX, Event, TraceView

PREFIX = "morpheus."


def load_thread_events(path: str) -> List[Event]:
    """Every event of an ``.xplane.pb`` file, as
    :func:`bench.lib.trace.load_events` reads it, but with each host
    line named ``<thread name>#<index in its plane>``, so that two
    threads of one name stay apart."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        host = not plane.name.startswith(DEVICE_PLANE_PREFIX)
        for i, line in enumerate(plane.lines):
            name = f"{line.name}#{i}" if host else line.name
            for e in line.events:
                try:
                    stats = dict(e.stats)
                except (TypeError, ValueError):
                    stats = {}
                out.append(Event(plane.name, name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 stats))
    return out


def program_spans(events: Iterable[Event], prefix: str = PREFIX
                  ) -> List[Event]:
    """Host events of some duration whose name starts with ``prefix``
    (any, for ``""``), by start time."""
    return sorted((e for e in events
                   if e.name.startswith(prefix) and e.dur_ns > 0
                   and not e.plane.startswith(DEVICE_PLANE_PREFIX)),
                  key=lambda e: (e.start_ns, -e.dur_ns))


def named(events: Iterable[Event], *names: str) -> List[Event]:
    """The events called one of ``names``, by start time."""
    want = set(names)
    return sorted((e for e in events if e.name in want),
                  key=lambda e: e.start_ns)


def window(view: Optional[TraceView]) -> Optional[Tuple[float, float]]:
    """The ``bench.window`` span of a traced run (None without one)."""
    if view is None:
        return None
    return view.span("bench.window")


def starting_in(events: Iterable[Event], lo: float, hi: float
                ) -> List[Event]:
    """The events whose start lies in ``[lo, hi)``."""
    return [e for e in events if lo <= e.start_ns < hi]


def window_spans(view: Optional[TraceView], *names: str
                 ) -> Optional[List[Event]]:
    """The program spans called one of ``names`` that start in the
    traced window; None without a trace or a window."""
    span = window(view)
    if span is None:
        return None
    return starting_in(named(program_spans(view.events), *names), *span)


def clipped_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Summed time of ``events`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
               for e in events)


def holds(outer: Event, inner: Event) -> bool:
    """True when ``outer``, on ``inner``'s line, holds it whole and is
    not the same interval."""
    return (outer is not inner and outer.line == inner.line
            and outer.start_ns <= inner.start_ns
            and inner.end_ns <= outer.end_ns
            and (outer.dur_ns > inner.dur_ns
                 or outer.start_ns < inner.start_ns))


class SpanTree:
    """The nesting of a set of spans: a span's parent is the smallest
    span of its line that holds it whole."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns))
        self.parent: Dict[int, Optional[Event]] = {}
        self._children: Dict[int, List[Event]] = {}
        stacks: Dict[str, List[Event]] = {}
        for e in self.spans:
            stack = stacks.setdefault(e.line, [])
            while stack and stack[-1].end_ns <= e.start_ns:
                stack.pop()
            # a span of another thread on the same line may overlap
            # without holding: look past it for the one that holds
            parent = next((s for s in reversed(stack) if holds(s, e)),
                          None)
            self.parent[id(e)] = parent
            if parent is not None:
                self._children.setdefault(id(parent), []).append(e)
            stack.append(e)

    def children(self, e: Event, name: Optional[str] = None
                 ) -> List[Event]:
        kids = self._children.get(id(e), [])
        return [k for k in kids if name is None or k.name == name]

    def self_ns(self, e: Event) -> float:
        """Time of ``e`` its children leave uncovered."""
        covered = 0.0
        t = e.start_ns
        for k in sorted(self.children(e), key=lambda k: k.start_ns):
            s, f = max(k.start_ns, t), min(k.end_ns, e.end_ns)
            if f > s:
                covered += f - s
                t = f
        return e.dur_ns - covered

    def outermost(self, names: Iterable[str]) -> List[Event]:
        """The spans called one of ``names`` that no other such span
        holds (``step`` inside ``step_many`` counts once)."""
        want = set(names)
        out = []
        for e in self.spans:
            if e.name not in want:
                continue
            p = self.parent.get(id(e))
            while p is not None and p.name not in want:
                p = self.parent.get(id(p))
            if p is None:
                out.append(e)
        return out


def by_w(spans: Iterable[Event]) -> Dict[int, Event]:
    """Spans keyed by their ``w`` stat (spans without one are left
    out)."""
    out = {}
    for e in spans:
        w = e.stats.get("w")
        if w is not None:
            out[int(w)] = e
    return out


def pair_by_w(first: Iterable[Event], second: Iterable[Event]
              ) -> List[Tuple[int, Event, Event]]:
    """``(w, a, b)`` for every ``w`` that both kinds of span carry, in
    order of ``w``."""
    a, b = by_w(first), by_w(second)
    return [(w, a[w], b[w]) for w in sorted(a.keys() & b.keys())]


def innermost_open(events: Iterable[Event], lo: float, hi: float,
                   prefix: str = PREFIX) -> List[Tuple[Event, float]]:
    """What the host's threads were in over ``[lo, hi]``: each program
    span (host event, for ``prefix=""``) overlapping it that holds no
    other such overlapping event of its line, with the overlap in ns,
    longest overlap first."""
    live = [e for e in program_spans(events, prefix)
            if e.start_ns < hi and e.end_ns > lo]
    out = []
    for e in live:
        if any(holds(e, o) for o in live):
            continue
        out.append((e, min(e.end_ns, hi) - max(e.start_ns, lo)))
    return sorted(out, key=lambda p: -p[1])
