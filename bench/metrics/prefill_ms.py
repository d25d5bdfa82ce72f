"""Mean device time of one prefill call (the jitted prefill step's XLA
module in the trace)."""
from bench.program import PREFILL_MODULE


def read(run):
    if run.trace is None:
        return None
    ev = run.trace.module_events(PREFILL_MODULE)
    return sum(d for _, d in ev) / len(ev) * 1e3 if ev else None
