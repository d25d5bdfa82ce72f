"""One run of one cell: set-up, the open-loop window, the check.

``run_cell`` is the internal entry; ``bench/run.py`` adds the command line
and the look for a chip. The order of a run:

1. set-up: weights from the seed on the device (one jitted call), the
   Router with one in-process host, and one warm-up request per prefill
   bucket the mix can reach, so every program the window runs is compiled
   (or loaded from the cache) here. ``setup_s`` runs from process start to
   the first due request.
2. the window: requests are submitted when due (open loop) and
   ``Router.step`` runs between arrivals. A lead-in fills the server first;
   after the close, arrivals go on until every request due in the window has
   its first token, at most the cell's ``tail_s`` seconds; requests still
   streaming then are cut there, with the gaps they had so far.
3. with ``trace``: the profiler traces from ``TRACE_AFTER_S`` after the
   window opens to the end of the loop; the trace is written out after the
   loop, so writing it stalls nothing that is measured.
4. the device's peak memory is read, the program's state freed, and the
   plain reference scores a sample of the finished requests (see
   :func:`check`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import common, traffic, weights
from bench import program
from bench import trace as tracing

TRACE_AFTER_S = 2.0
SAMPLE_TOKENS = 512        # served tokens the check scores, at least,
                           # unless the configuration sets check_tokens
SAMPLE_MAX = 32            # requests the check scores, at most


@dataclasses.dataclass
class Record:
    """One request as the harness saw it; times on ``program.now``'s clock."""
    phase: str
    due_s: float                    # absolute once the traffic clock starts
    prompt: np.ndarray
    max_new_tokens: int
    sent_s: Optional[float] = None
    rejected: bool = False
    admitted_s: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_ts: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    handle: object = None           # the RouterRequest, once sent


@dataclasses.dataclass
class Run:
    """What the metric readers read (``bench/metrics/<name>.py``)."""
    config: Dict
    seconds: float
    setup_s: float
    window_open_s: float
    records: List[Record]
    peak: Optional[Dict]            # this device's row of bench/peaks.json
    trace: Optional[tracing.Trace]
    trace_span: Optional[tuple]     # (start_s, stop_s) on program.now's clock

    @property
    def window(self) -> List[Record]:
        return [r for r in self.records if r.phase == "window"]

    def in_trace(self, t: float) -> bool:
        return (self.trace_span is not None
                and self.trace_span[0] <= t <= self.trace_span[1])


class CompileClock:
    """Counts jax's backend compiles while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


def warmup_lengths(buckets, lo: int, hi: int) -> List[int]:
    """One prompt length per prefill bucket that prompts of ``lo..hi``
    tokens can land in."""
    out, prev = [], 0
    for b in sorted(buckets):
        if b >= lo and prev < hi:
            out.append(min(b, hi))
        prev = b
    return out


def _warm(r, c: Dict, mix: Dict, seed: int) -> None:
    rng = np.random.default_rng([seed, 1])
    buckets = r.engines[0].scheduler.buckets
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    # twice: the first admission writes into the freshly made cache, whose
    # placement differs from the one decode hands back, so the slot write
    # of the first bucket compiles again on the second pass
    for n in warmup_lengths(buckets, lo, hi) * 2:
        r.submit(rng.integers(0, c["vocab_size"], n, dtype=np.int32), 2,
                 strict=True)
        r.run_until_complete()


def _drive(r, recs: List[Record], window_open: float, window_end: float,
           tail_s: float, trace_dir: Optional[str]) -> Optional[tuple]:
    """The open loop, until the window has closed and every request due in
    it has its first token (or was refused), at most ``tail_s`` past the
    close. Returns the traced span, if any."""
    now = program.now
    # host spans for the trace's idle gaps; no-ops in an untraced run
    span_of = (jax.profiler.TraceAnnotation if trace_dir
               else lambda name: contextlib.nullcontext())
    live: Dict[int, object] = {}          # record index -> RouterRequest
    unanswered = {k for k, x in enumerate(recs) if x.phase == "window"}
    tr_start = window_open + TRACE_AFTER_S
    tracing_on, span = False, None
    i = 0
    deadline = window_end + tail_s
    while True:
        t = now()
        if trace_dir and not tracing_on and span is None and t >= tr_start:
            jax.profiler.start_trace(trace_dir)
            tracing_on, span = True, (now(), None)
        while i < len(recs) and recs[i].due_s <= t:
            rec = recs[i]
            rec.sent_s = now()
            with span_of("bench.submit"):
                rr = r.submit(rec.prompt, rec.max_new_tokens)
            if rr is None:
                rec.rejected = True
                unanswered.discard(i)
            else:
                live[i] = rec.handle = rr
            i += 1
        unanswered = {k for k in unanswered
                      if k not in live or not live[k].tokens}
        if (t >= window_end and not unanswered) or t >= deadline:
            break
        if r.has_work():
            with span_of("bench.step"):
                r.step()
            for k in [k for k, rr in live.items() if rr.done]:
                recs[k].done = True
                live.pop(k)
        elif i < len(recs):
            with span_of("bench.wait_arrival"):
                time.sleep(max(0.0, min(0.005, recs[i].due_s - now())))
    if tracing_on:
        span = (span[0], now())
        jax.profiler.stop_trace()
    return span


def collect(r, recs: List[Record]) -> None:
    """Copy each sent request's tokens and stamps off the router, and its
    admission stamp off the engine. Requests still streaming keep what came
    so far."""
    for x in recs:
        if x.handle is not None:
            x.tokens, x.token_ts = list(x.handle.tokens), list(
                x.handle.token_ts)
    by_prompt = {x.prompt.tobytes(): x for x in recs if x.sent_s is not None}
    for q in program.engine_requests(r):
        rec = by_prompt.get(np.asarray(q.prompt, np.int32).tobytes())
        if rec is not None:
            rec.admitted_s = q.metrics.admitted_s


def sample(recs: List[Record], seed: int,
           tokens: int = SAMPLE_TOKENS) -> List[Record]:
    """The finished requests the check scores, of any phase: the longest,
    then others drawn from the seed until ``tokens`` served tokens."""
    done = [x for x in recs if x.done]
    if not done:
        return []
    done.sort(key=lambda x: -(len(x.prompt) + len(x.tokens)))
    out, rest = [done[0]], done[1:]
    rng = np.random.default_rng([seed, 2])
    order = rng.permutation(len(rest))
    for j in order:
        if sum(len(x.tokens) for x in out) >= tokens or \
                len(out) >= SAMPLE_MAX:
            break
        out.append(rest[j])
    return out


def check(c: Dict, seed: int, recs: List[Record]):
    """The numbers ``correct`` compares, each with its limit.

    lost: window requests that never sent a token, refused ones included
    (limit 0); one still streaming at the tail's end is late, counted in
    ``failed``.
    short: finished requests with fewer tokens than asked (limit 0).
    Over a sample of finished requests (:func:`sample`), the plain float32
    reference is run over each prompt and its served tokens, and each
    served token's gap below the reference's best logit at its position is
    read (0 where it is the reference's argmax):
    max_logit_gap: the widest gap.
    mean_logit_gap: the mean gap over every scored token.
    first_token_max_gap: the widest gap among each request's first token,
    the one its prefill produces.
    untied_mean_gap (configurations with a ``router_tie_margin``): the
    mean gap over each request's first token and every later token whose
    position the reference routes with a margin of at least
    ``router_tie_margin`` in every layer (the k-th router logit above the
    (k+1)-th). Where the margin is smaller, bf16 and float32 may pick
    different experts, and the gap there measures the tie, not the program;
    the first tokens, the prefill's only output, always count, so a fault
    of the prefill cannot hide behind the rule.
    Each is compared where the configuration file gives it a limit
    (``correct``); one without a limit is reported under ``readings`` only.
    Returns the checks and every scored request's per-token rows."""
    win = [x for x in recs if x.phase == "window"]
    lost = sum(1 for x in win if not x.tokens)
    short = sum(1 for x in recs if x.done
                and len(x.tokens) != x.max_new_tokens)
    picked = sample(recs, seed, c.get("check_tokens", SAMPLE_TOKENS))
    scored = common.reference(c["family"]).score(
        c, seed, [np.concatenate([x.prompt, np.asarray(x.tokens, np.int32)])
                  for x in picked],
        [len(x.prompt) for x in picked]) if picked else []
    out = {"lost": {"value": lost, "limit": 0},
           "short": {"value": short, "limit": 0}}
    allg = np.concatenate([s["gap"] for s in scored]) if scored \
        else np.full(1, np.inf)
    first = np.asarray([s["gap"][0] for s in scored]) if scored else allg
    values = [("max_logit_gap", float(np.max(allg))),
              ("mean_logit_gap", float(np.mean(allg))),
              ("first_token_max_gap", float(np.max(first)))]
    tie = c.get("router_tie_margin")
    if tie is not None:
        margin = np.concatenate([s["router_margin"] for s in scored]) \
            if scored else np.zeros(1)
        is_first = np.concatenate([np.arange(s["gap"].size) == 0
                                   for s in scored]) if scored \
            else np.ones(1, bool)
        kept = allg[(margin >= tie) | is_first]
        values.append(("untied_mean_gap", float(np.mean(kept))))
        print(f"[bench] untied or first tokens (router margin >= {tie} in "
              f"every layer): {kept.size} of {allg.size}", file=sys.stderr,
              flush=True)
    for name, value in values:
        out[name] = {"value": value, "limit": c["correct"].get(name)}
    if scored:
        print(f"[bench] logit gaps over {allg.size} served tokens of "
              f"{len(scored)} requests: {int(np.sum(allg > 0))} nonzero, sum "
              f"{float(np.sum(allg))}, largest "
              f"{[round(float(x), 4) for x in np.sort(allg)[::-1][:16]]}",
              file=sys.stderr, flush=True)
    return out, scored


def passed(checks: Dict[str, Dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values()
               if v["limit"] is not None)


def peak_row(kind: str) -> Dict:
    peaks = common.load_json(common.BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def run_cell(c: Dict, mix: Dict, cell: Dict, seed: int, seconds: float,
             trace: bool, metrics: List[Dict], *, t_process: float,
             quantize: str = "off", trace_dir: Optional[str] = None,
             keep_scored: bool = False,
             log=lambda s: print(s, file=sys.stderr, flush=True)) -> Dict:
    """One run. ``cell`` is the cell's file (``rate_per_s``, ``lead_s``,
    ``tail_s``); ``metrics`` are the BENCHMARK.json entries to report;
    ``t_process`` is the process start on ``program.now``'s clock."""
    dev = jax.devices()[0]
    params = weights.model_params(c, seed)
    jax.block_until_ready(params)
    if quantize == "serve":
        params = program.quantize_w8a8(params)
    r = program.router(c, params, quantize)
    del params
    _warm(r, c, mix, seed)
    recs = [Record(phase=q.phase, due_s=q.due_s, prompt=q.prompt,
                   max_new_tokens=q.max_new_tokens)
            for q in traffic.requests(mix, cell["rate_per_s"], seconds,
                                      c["vocab_size"], seed, cell["lead_s"],
                                      cell["tail_s"])]
    if trace and trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    t_base = program.now()
    setup_s = t_base - t_process
    for x in recs:
        x.due_s += t_base
    window_open = t_base + cell["lead_s"]
    with CompileClock() as clock:
        span = _drive(r, recs, window_open, window_open + seconds,
                      cell["tail_s"], trace_dir if trace else None)
    collect(r, recs)
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    r.close()
    del r
    gc.collect()
    t_ref = time.monotonic()
    checks, scored = check(c, seed, recs)
    log(f"[bench] reference check took {time.monotonic() - t_ref:.1f} s "
        f"over {sum(row['gap'].size for row in scored)} served tokens")
    tr = None
    if span is not None:
        path = tracing.find_xplane(trace_dir)
        tr = tracing.reduce(path, span[1] - span[0]) if path else None
    run = Run(config=c, seconds=seconds, setup_s=setup_s,
              window_open_s=window_open, records=recs,
              peak=peak_row(dev.device_kind) if dev.platform == "tpu"
              else None,
              trace=tr, trace_span=span)
    out_metrics = {}
    for m in metrics:
        v = common.metric_reader(m["name"]).read(run)
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    win = run.window
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem_peak}
    result = {
        "correct": passed(checks),
        "attempted": len(win),
        "failed": sum(1 for x in win if x.rejected or not x.tokens),
        "metrics": out_metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = tr.busy_s if tr else None
        device["window_s"] = tr.window_s if tr else None
        if tr is not None:
            result["breakdown"] = {
                "device_ops": [list(x) for x in tracing.top_ops(tr)],
                "idle_gaps": [list(x) for x in tracing.idle_gaps(tr)]}
    log(f"[bench] compiles inside the window: {clock.count}; requests "
        f"sent {sum(1 for x in recs if x.sent_s is not None)}, window "
        f"{len(win)}, rejected {sum(1 for x in win if x.rejected)}")
    # every reading, compared or not, then (last) the compared ones
    result["readings"] = {k: v["value"] for k, v in checks.items()}
    if keep_scored:
        result["scored"] = [{k: [round(float(x), 6) for x in v]
                             for k, v in row.items()} for row in scored]
    result["checks"] = {k: v for k, v in checks.items()
                        if v["limit"] is not None}
    return result
