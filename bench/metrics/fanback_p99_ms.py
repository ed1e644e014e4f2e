"""99th percentile, over the windows retired in the traced window, of
the host time a retired window spends after its device work: the copy of
its output to the host (``morpheus.batcher.retire.d2h``) plus the
fan-back of its requests (``morpheus.batcher.retire.fanback``), both
inside that window's ``morpheus.batcher.retire`` span (program
spans)."""
from bench.lib import spans
from bench.lib.common import quantile

PARTS = ("morpheus.batcher.retire.d2h", "morpheus.batcher.retire.fanback")


def read(run):
    retires = spans.window_spans(run.trace, "morpheus.batcher.retire")
    if not retires:
        return None
    tree = spans.SpanTree(spans.program_spans(run.trace.events,
                                              "morpheus.batcher."))
    per_window = [sum(k.dur_ns for k in tree.children(r)
                      if k.name in PARTS) * 1e-6 for r in retires]
    return quantile(per_window, 0.99)
