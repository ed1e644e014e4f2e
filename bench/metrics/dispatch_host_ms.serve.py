"""Mean host time of one dispatch in the traced window: the duration of
each ``morpheus.runtime.step_many`` or ``morpheus.runtime.step`` span
that starts there, a ``step`` inside a ``step_many`` counted once
(program spans).  It covers preparing, claiming, launching and
committing a window, not the device's work."""
from bench.lib import spans

NAMES = ("morpheus.runtime.step_many", "morpheus.runtime.step")


def read(run):
    span = spans.window(run.trace)
    if span is None:
        return None
    tree = spans.SpanTree(spans.program_spans(run.trace.events,
                                              "morpheus.runtime."))
    calls = spans.starting_in(tree.outermost(NAMES), *span)
    if not calls:
        return None
    return sum(e.dur_ns for e in calls) * 1e-6 / len(calls)
