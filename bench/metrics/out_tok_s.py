"""Output tokens emitted inside the window, any request's, per second of
the window."""


def read(run):
    lo, hi = run.window_open_s, run.window_open_s + run.seconds
    n = sum(1 for r in run.records for t in r.token_ts if lo <= t < hi)
    return n / run.seconds
