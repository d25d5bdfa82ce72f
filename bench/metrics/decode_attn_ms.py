"""Mean device time of attention per decode call: the union of the
intervals of the decode step's operations under the named scope ``attn``
(``bench/spans.py``), over the decode calls the trace holds."""
from bench import spans
from bench.program import DECODE_MODULE


def read(run):
    s = spans.load(run)
    return None if s is None else s.scope_ms(spans.jit_name(DECODE_MODULE),
                                             "attn")
