"""Median over the window's requests of due time -> first token."""
from bench.stats import percentile, ttft_s


def read(run):
    v = percentile([ttft_s(r) for r in run.window], 50)
    return None if v is None else v * 1e3
