"""Whole prefill step against the chip's bf16 peak: the mean required
operations of a prefill (bench/flops.py, at the real prompt length, one
logits row, routed experts only) over the mean device time of a prefill
call times the peak. Requests whose first token came inside the traced
span stand for the prefills the trace holds."""
from bench import flops
from bench.program import PREFILL_MODULE


def read(run):
    if run.trace is None or run.peak is None:
        return None
    ev = run.trace.module_events(PREFILL_MODULE)
    lens = [len(r.prompt) for r in run.records
            if r.token_ts and run.in_trace(r.token_ts[0])]
    if not ev or not lens:
        return None
    need = sum(flops.prefill_flops(run.config, n) for n in lens) / len(lens)
    took = sum(d for _, d in ev) / len(ev)
    return 100.0 * need / (took * run.peak["bfloat16_flops"])
