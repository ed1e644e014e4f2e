"""Milliseconds of the traced window spent in Python's garbage
collector: the summed ``morpheus.gc`` spans inside the window (program
spans; the collecting thread holds the interpreter, so every host
thread waits).  0.0 where no collection ran there; nothing where the
window holds none of the program's spans, as a program without them."""
from bench.lib import spans


def read(run):
    span = spans.window(run.trace)
    if span is None:
        return None
    lo, hi = span
    program = spans.program_spans(run.trace.events)
    if not any(lo <= e.start_ns < hi and e.name != "morpheus.gc"
               for e in program):
        return None
    return spans.clipped_ns(spans.named(program, "morpheus.gc"),
                            lo, hi) * 1e-6
