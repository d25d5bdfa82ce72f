"""95th percentile of due time -> admission (the engine's own stamp
``metrics.admitted_s``) over the window's admitted requests."""
from bench.stats import percentile


def read(run):
    v = percentile([r.admitted_s - r.due_s for r in run.window
                    if r.admitted_s is not None], 95)
    return None if v is None else v * 1e3
