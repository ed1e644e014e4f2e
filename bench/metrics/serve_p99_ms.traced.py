"""99th percentile, in the traced run, over every request due in the
window of completion minus due time; a request that never completed ok
counts as infinitely late.  It is a per-layer reading and not an
end-to-end metric because one host stall of 0.1 s or more in a 10 s
window moves it by half or more (PERF.md)."""
from bench.lib.common import quantile


def read(run):
    return quantile(run.values["latency_ms"], 0.99)
