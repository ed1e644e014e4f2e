"""Program spans on the profiler's clock.

Every span the runtime, the serving frontend and the control loop open
goes through :func:`span`: a ``jax.profiler.TraceAnnotation`` named
``morpheus.<name>`` whose keyword arguments become the event's stats
(small ints or short strings).  There is no switch: with the profiler
off a span costs about a microsecond and records nothing; with it on
(``jax.profiler.start_trace`` / ``stop_trace``) the spans land on the
host threads' timelines beside the device's operations, on one clock.

A span's stats are given when it opens, so a value belongs on the first
span opened after it is known; a count that only the spanned work
yields is added before the span closes with the span's
``set_metadata(**stats)``.

:func:`install_gc_spans` adds one ``gc.callbacks`` hook per process that
wraps every garbage collection in a ``morpheus.gc`` span (stat
``generation``) on the collecting thread, so a host stall that stops
every thread can be told apart from a lock.
"""
from __future__ import annotations

import gc
import threading
from typing import Optional

from jax.profiler import TraceAnnotation

PREFIX = "morpheus."


def span(name: str, **stats) -> TraceAnnotation:
    """A context manager recording ``morpheus.<name>`` with ``stats``
    while the profiler runs."""
    return TraceAnnotation(PREFIX + name, **stats)


_gc_lock = threading.Lock()
_gc_open: Optional[TraceAnnotation] = None


def _gc_hook(phase: str, info: dict) -> None:
    # collections never overlap (the collecting thread holds the GIL and
    # the collector does not re-enter), so one slot holds the open span
    global _gc_open
    if phase == "start":
        ann = TraceAnnotation(PREFIX + "gc",
                              generation=int(info.get("generation", -1)))
        ann.__enter__()
        _gc_open = ann
    elif _gc_open is not None:
        ann, _gc_open = _gc_open, None
        ann.__exit__(None, None, None)


def install_gc_spans() -> None:
    """Add the ``morpheus.gc`` hook to ``gc.callbacks`` once for the
    process (idempotent; it is never removed, so a runtime closing while
    another lives cannot take it away)."""
    with _gc_lock:
        if _gc_hook not in gc.callbacks:
            gc.callbacks.append(_gc_hook)
