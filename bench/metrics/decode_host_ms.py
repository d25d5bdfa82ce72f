"""Host time of a decode step: the mean, over the program's
``engine.decode`` spans in the trace, of the span's length less the device
busy time inside it (``bench/spans.py``)."""
from bench import spans


def read(run):
    s = spans.load(run)
    return None if s is None else s.host_ms("engine.decode")
