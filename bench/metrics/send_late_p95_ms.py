"""95th percentile of how late the harness submitted the window's requests
against their due times (a starved load generator shows here)."""
from bench.stats import percentile


def read(run):
    v = percentile([r.sent_s - r.due_s for r in run.window
                    if r.sent_s is not None], 95)
    return None if v is None else v * 1e3
