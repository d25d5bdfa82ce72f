"""Median of all inter-token gaps of the window's requests."""
from bench.stats import itl_s, percentile


def read(run):
    v = percentile(itl_s(run.window), 50)
    return None if v is None else v * 1e3
