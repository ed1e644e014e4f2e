"""Share of the window in which the frontend's batcher thread waited for
a dispatched window's device work to finish, in percent: the summed
``morpheus.batcher.retire.wait`` spans inside the window, over the
window (program spans).  While it waits the thread forms no window and
retires none."""
from bench.lib import spans


def read(run):
    span = spans.window(run.trace)
    if span is None:
        return None
    lo, hi = span
    program = spans.program_spans(run.trace.events, "morpheus.batcher.")
    if not spans.starting_in(program, lo, hi):
        return None
    waits = spans.named(program, "morpheus.batcher.retire.wait")
    return 100.0 * spans.clipped_ns(waits, lo, hi) / (hi - lo)
