"""Mean device time of the MoE layers per prefill call: the union of the
intervals of the prefill step's operations under the named scope ``moe``
(router, routed and shared experts; ``bench/spans.py``), over the prefill
calls the trace holds."""
from bench import spans
from bench.program import PREFILL_MODULE


def read(run):
    s = spans.load(run)
    return None if s is None else s.scope_ms(spans.jit_name(PREFILL_MODULE),
                                             "moe")
