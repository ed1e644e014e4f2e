"""99th percentile of the frontend's queue wait of the window's requests
(``Request.timing["queue_wait_s"]``, exact per request)."""
from bench.lib.common import quantile


def read(run):
    waits = run.values["queue_wait_ms"]
    return quantile(waits, 0.99) if waits else None
