"""Median over every request due in the window of completion minus due
time; a request that never completed ok counts as infinitely late."""
from bench.lib.common import quantile


def read(run):
    return quantile(run.values["latency_ms"], 0.50)
