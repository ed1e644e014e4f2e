"""99th percentile of how late the open-loop sender submitted each
request after its due time (host clock)."""
from bench.lib.common import quantile


def read(run):
    return quantile(run.values["lateness_ms"], 0.99)
