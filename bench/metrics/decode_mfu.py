"""Whole decode step against the chip's bf16 peak, host gaps included: the
mean required operations of a decode step (bench/flops.py, each active
request's one token over its real context) over the mean time from one
decode call's device start to the next, taken over pairs with no prefill
between them, times the peak.

Decode steps are rebuilt from the engine's emission stamps: every token a
decode step emits carries the same ``token_ts``, so the tokens (after each
request's first, which its prefill emits) that share a stamp inside the
traced span are one step."""
import collections

from bench import flops
from bench.program import DECODE_MODULE, PREFILL_MODULE


def read(run):
    if run.trace is None or run.peak is None:
        return None
    steps = collections.defaultdict(int)
    for r in run.records:
        for j in range(1, len(r.token_ts)):
            t = r.token_ts[j]
            if run.in_trace(t):
                steps[t] += flops.decode_flops(run.config, len(r.prompt) + j)
    dec = run.trace.module_events(DECODE_MODULE)
    pre = [s for s, _ in run.trace.module_events(PREFILL_MODULE)]
    clean = [b[0] - a[0] for a, b in zip(dec, dec[1:])
             if not any(a[0] < p < b[0] for p in pre)]
    if not steps or not clean:
        return None
    need = sum(steps.values()) / len(steps)
    took = sum(clean) / len(clean)
    return 100.0 * need / (took * run.peak["bfloat16_flops"])
