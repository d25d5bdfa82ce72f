"""Serving telemetry: per-request latency records + engine-level counters.

Per request we track the two numbers a serving SLO is written against —
TTFT (arrival -> first generated token, queue wait included) and the decode
rate after the first token. TTFT decomposes as queue_wait_s (arrival ->
admission) + prefill_s (admission -> first token: the fused prefill forward
plus the batched cache-seed write); the engine aggregates the device-side
halves as prefill_wait_s / seed_write_s. Engine counters are designed to *reconcile*:
``tokens_generated`` must equal the sum of every completed/active request's
``n_generated`` (asserted in tests/test_serving.py).

Host spans: :class:`span` marks a layer of the serving path on the JAX
profiler's trace (``router.*``, ``engine.*``, ``opq.*``, ``python.gc``), on
the same clock as the device's operations, so a trace says what the host
was doing in every gap of the device; ``bench/spans.py`` reads them. A span
can also feed an engine counter, which is how ``prefill_wait_s`` and
``seed_write_s`` are timed.

Cache-memory telemetry comes from ``SlotStore.memory_stats()`` (bytes per
backend, block occupancy for the paged store) — surfaced through
``Engine.stats()["cache"]`` and rendered by :func:`format_memory_stats` in
the launch/serve.py end-of-run report. ``admissions_deferred`` counts store
lease refusals (paged block-pool backpressure).
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, Optional

import jax
from jax.profiler import TraceAnnotation


def now() -> float:
    return time.monotonic()


def span(name: str, metrics=None, field: str = ""):
    """``with span("engine.decode"):`` — one named host span on the
    profiler's trace (a ``jax.profiler.TraceAnnotation``). Outside a trace
    it records nothing and costs a few hundred ns. Given ``metrics`` and
    ``field``, the span's wall time is also added to that counter
    (``span("engine.seed", self.metrics, "seed_write_s")``)."""
    if metrics is None:
        return TraceAnnotation(name)
    return _CountedSpan(name, metrics, field)


class _CountedSpan:
    """A span that also adds its wall time to ``metrics.<field>``."""

    __slots__ = ("_ann", "_metrics", "_field", "_t0")

    def __init__(self, name: str, metrics, field: str):
        self._ann = TraceAnnotation(name)
        self._metrics, self._field = metrics, field

    def __enter__(self):
        self._t0 = now()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        setattr(self._metrics, self._field,
                getattr(self._metrics, self._field) + now() - self._t0)


class _GcSpans:
    """A ``python.gc`` span over each collection of the garbage collector,
    opened and closed from ``gc.callbacks`` (start and stop run on the
    collecting thread, and collections never overlap)."""

    def __init__(self):
        self._open: Optional[TraceAnnotation] = None

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._open = TraceAnnotation("python.gc")
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def trace_hooks() -> None:
    """What a trace of the serving path relies on, process-wide and made
    once however often this is called: a ``python.gc`` span over every
    garbage collection, and op metadata in the key of JAX's persistent
    compilation cache. Without the latter an executable loaded from the
    cache keeps the metadata (the named scopes) of whatever code compiled
    it first, since scopes leave the program itself unchanged, and a device
    trace shows those in place of the running code's."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not any(isinstance(cb, _GcSpans) for cb in gc.callbacks):
        gc.callbacks.append(_GcSpans())


@dataclasses.dataclass
class RequestMetrics:
    arrival_s: float
    prompt_len: int = 0
    admitted_s: Optional[float] = None         # slot leased, prefill dispatched
    first_token_s: Optional[float] = None      # set when prefill emits token 1
    finish_s: Optional[float] = None
    n_generated: int = 0

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.arrival_s

    @property
    def queue_wait_s(self) -> Optional[float]:
        """TTFT share spent waiting for a slot (arrival -> admission)."""
        if self.admitted_s is None:
            return None
        return self.admitted_s - self.arrival_s

    @property
    def prefill_s(self) -> Optional[float]:
        """TTFT share spent in the fused prefill + cache seeding (admission ->
        first token). With fused admission this is one forward + one batched
        slot write, flat in prompt length — the replay era's O(prompt_len)
        decode chain lived here."""
        if self.admitted_s is None or self.first_token_s is None:
            return None
        return self.first_token_s - self.admitted_s

    @property
    def decode_tok_s(self) -> Optional[float]:
        """Post-first-token generation rate for this request."""
        if self.finish_s is None or self.first_token_s is None:
            return None
        dt = self.finish_s - self.first_token_s
        return (self.n_generated - 1) / dt if dt > 0 else float("inf")


@dataclasses.dataclass
class EngineMetrics:
    submitted: int = 0
    rejected: int = 0
    admissions_deferred: int = 0               # store lease refusals (paged
                                               # block-pool backpressure)
    evicted: int = 0                           # queued requests pulled by a
                                               # router drain (never admitted
                                               # here; re-placed elsewhere)
    preempted: int = 0                         # in-flight requests handed off
                                               # by a router drain (slot
                                               # retired, tokens stand)
    completed: int = 0
    tokens_generated: int = 0                  # prefill first-tokens + ALL
                                               # tokens decode rounds emitted
                                               # (accepted counts, NOT steps:
                                               # a speculative round emits
                                               # 1..k+1 per slot, so tok/s is
                                               # token-based by construction)
    decode_steps: int = 0                      # dispatched TARGET decode-path
                                               # forwards (plain steps +
                                               # verify rounds) — spec decode
                                               # drives steps/token below 1
    spec_rounds: int = 0                       # draft-verify rounds dispatched
    draft_steps: int = 0                       # narrow draft decode dispatches
    proposed_tokens: int = 0                   # draft proposals verified
                                               # (spec_k per active slot-round)
    accepted_tokens: int = 0                   # proposals the target confirmed
                                               # (emitted - 1 per slot-round:
                                               # the window's position-0 token
                                               # comes free, draft or no draft)
    accept_hist: Dict[int, int] = dataclasses.field(default_factory=dict)
                                               # tokens-emitted-per-slot-round
                                               # histogram {length: rounds}
    sampled_tokens: int = 0                    # tokens emitted by non-greedy
                                               # (sampled) requests — greedy
                                               # traffic keeps this at 0
    stop_hits: int = 0                         # requests finished by a stop-
                                               # sequence suffix match
    embed_requests: int = 0                    # non-generative forwards
                                               # (serve API embeddings/
                                               # classification)
    prefill_batches: int = 0                   # prefill dispatches, one per admission
    prefill_tokens: int = 0                    # unpadded prompt tokens prefilled
    prefill_chunks: int = 0                    # block-size prefill chunks
                                               # actually computed (prefix-
                                               # cache engines only: cached
                                               # chunks are skipped, so this
                                               # is the dispatched-work unit
                                               # BENCH_prefix.json tracks)
    prefix_hits: int = 0                       # leases that matched cached
                                               # prefix blocks (or a COW fork)
    prefix_blocks_reused: int = 0              # whole cached blocks leased by
                                               # refcount instead of prefilled
    prefix_tokens_reused: int = 0              # prompt positions whose prefill
                                               # was skipped outright
    exported_slots: int = 0                    # in-flight requests extracted
                                               # WITH their cache blocks for
                                               # cross-host shipping (disagg)
    exported_blocks: int = 0                   # pool blocks serialized out
    imported_slots: int = 0                    # requests admitted from a
                                               # shipped block payload — zero
                                               # prefill dispatches each
    imported_blocks: int = 0                   # pool blocks adopted verbatim
    prefill_wait_s: float = 0.0                # wall time blocked on prefill
                                               # forwards (engine.prefill_wait)
    seed_write_s: float = 0.0                  # wall time in batched slot
                                               # writes (engine.seed)
    steps: int = 0                             # engine iterations observed
    queue_depth_sum: int = 0                   # for mean queue depth
    occupancy_sum: int = 0                     # active slots summed per step
    started_s: float = dataclasses.field(default_factory=now)
    first_token_s: Optional[float] = None      # first token the engine produced
    last_token_s: Optional[float] = None

    def observe_step(self, queue_depth: int, n_active: int) -> None:
        self.steps += 1
        self.queue_depth_sum += queue_depth
        self.occupancy_sum += n_active

    def observe_tokens(self, n: int) -> None:
        t = now()
        if self.first_token_s is None:
            self.first_token_s = t
        self.last_token_s = t
        self.tokens_generated += n

    def sustained_tok_s(self) -> float:
        """Generated tokens over the first->last token wall span (the number
        the throughput benchmark sweeps offered load against)."""
        if self.first_token_s is None or self.last_token_s is None:
            return 0.0
        dt = self.last_token_s - self.first_token_s
        return self.tokens_generated / dt if dt > 0 else float("inf")

    def summary(self) -> Dict[str, float]:
        return {
            "submitted": self.submitted,
            "rejected": self.rejected,
            "admissions_deferred": self.admissions_deferred,
            "evicted": self.evicted,
            "preempted": self.preempted,
            "completed": self.completed,
            "tokens_generated": self.tokens_generated,
            "decode_steps": self.decode_steps,
            "spec_rounds": self.spec_rounds,
            "draft_steps": self.draft_steps,
            "proposed_tokens": self.proposed_tokens,
            "accepted_tokens": self.accepted_tokens,
            "acceptance_rate": (self.accepted_tokens
                                / max(self.proposed_tokens, 1)),
            "accept_hist": dict(sorted(self.accept_hist.items())),
            "sampled_tokens": self.sampled_tokens,
            "stop_hits": self.stop_hits,
            "embed_requests": self.embed_requests,
            "prefill_batches": self.prefill_batches,
            "prefill_tokens": self.prefill_tokens,
            "prefill_chunks": self.prefill_chunks,
            "prefix_hits": self.prefix_hits,
            "prefix_blocks_reused": self.prefix_blocks_reused,
            "prefix_tokens_reused": self.prefix_tokens_reused,
            "exported_slots": self.exported_slots,
            "exported_blocks": self.exported_blocks,
            "imported_slots": self.imported_slots,
            "imported_blocks": self.imported_blocks,
            "prefill_wait_s": self.prefill_wait_s,
            "seed_write_s": self.seed_write_s,
            "sustained_tok_s": self.sustained_tok_s(),
            "mean_queue_depth": self.queue_depth_sum / max(self.steps, 1),
            "mean_occupancy": self.occupancy_sum / max(self.steps, 1),
        }


@dataclasses.dataclass
class TransportMetrics:
    """Per-transport RPC telemetry (serving/transport.py): every Router->host
    call is one RPC, whether it crosses a process boundary (SubprocessTransport
    frames over a local socket) or not (InProcessTransport method calls — timed
    the same way so the subprocess overhead is measured against a real
    baseline, reports/BENCH_transport.json)."""

    rpcs: int = 0
    retries: int = 0                           # idempotent calls re-sent after
                                               # a timeout/drop (fresh seq; the
                                               # stale reply is discarded)
    errors: int = 0                            # calls that raised
                                               # TransportError (timeouts,
                                               # EOF/connection loss)
    rpc_wait_s: float = 0.0                    # wall time inside RPCs

    def observe(self, dt: float) -> None:
        self.rpcs += 1
        self.rpc_wait_s += dt

    def summary(self) -> Dict[str, float]:
        return {
            "rpcs": self.rpcs,
            "retries": self.retries,
            "errors": self.errors,
            "rpc_wait_s": self.rpc_wait_s,
        }


def format_transport_stats(stats: Dict) -> str:
    """One-line fleet-transport summary from ``Router.stats()`` — per-host
    RPC volume/latency plus loss/recovery counters, the launch/serve.py
    report line when hosts run as real processes."""
    r = stats["router"]
    per_host = r.get("transport", [])
    kinds = {t["kind"] for t in per_host}
    rpcs = sum(t["rpcs"] for t in per_host)
    retries = sum(t["retries"] for t in per_host)
    errors = sum(t["errors"] for t in per_host)
    mean_us = (1e6 * sum(t["rpc_wait_s"] for t in per_host) / rpcs
               if rpcs else 0.0)
    lost = f" | lost={r['lost']}" if r.get("lost") else ""
    return (f"transport[{'/'.join(sorted(kinds))}]: {rpcs} rpcs "
            f"({mean_us:.0f} us mean) | {retries} retries, {errors} errors | "
            f"{r.get('hosts_lost', 0)} hosts lost -> "
            f"{r.get('recovered', 0)} streams recovered{lost}")


def format_router_stats(stats: Dict) -> str:
    """One-line fleet summary from ``Router.stats()`` — placement counters in
    the same shape OPQ reports per-lane scheduling (placed/affinity_hits, the
    cross-host analog of issued/affinity_hits) plus drain/handoff activity —
    the launch/serve.py multi-host report line."""
    r = stats["router"]
    f = stats["fleet"]
    drained = f" | draining={r['draining']}" if r.get("draining") else ""
    ships = ""
    if r.get("roles"):
        ships = (f" | disagg: {r.get('ships', 0)} ships "
                 f"({r.get('shipped_blocks', 0)} blocks, "
                 f"{r.get('ship_fallbacks', 0)} fallbacks)")
    return (f"{r['hosts']} hosts | {r['placed']} placed "
            f"({r['affinity_hits']} affinity hits, {r['spills']} spills) | "
            f"{r['drains']} drains -> {r['handoffs']} handoffs + "
            f"{r['requeued']} requeued{ships} | fleet: {f['completed']} done, "
            f"{f['tokens_generated']} tok, {f['sustained_tok_s']:.1f} tok/s"
            f"{drained}")


def format_spec_stats(s: Dict) -> str:
    """One-line speculative-decode summary from ``EngineMetrics.summary()``
    — the launch/serve.py report line when ``--speculative`` is on. Shows
    the lever (target decode-path dispatches vs tokens they bought) and the
    accepted-length histogram {tokens-emitted-in-a-round: rounds}."""
    hist = " ".join(f"{length}:{count}"
                    for length, count in s["accept_hist"].items())
    spt = s["decode_steps"] / max(s["tokens_generated"] - s["completed"], 1)
    return (f"speculative: {s['spec_rounds']} rounds + {s['draft_steps']} "
            f"draft steps | {s['accepted_tokens']}/{s['proposed_tokens']} "
            f"proposals accepted ({s['acceptance_rate']:.2f}) | "
            f"{spt:.2f} target steps/decode-token | "
            f"accepted-length hist {{{hist}}}")


def format_sampling_stats(s: Dict) -> str:
    """One-line sampling summary from ``EngineMetrics.summary()`` — the
    launch/serve.py report line when the traffic mix includes non-greedy
    requests or stop sequences."""
    return (f"sampling: {s['sampled_tokens']}/{s['tokens_generated']} tokens "
            f"sampled | {s['stop_hits']} stop-sequence finishes | "
            f"{s['embed_requests']} embed requests")


def format_memory_stats(ms: Dict) -> str:
    """One-line cache-memory summary from ``SlotStore.memory_stats()`` —
    the end-of-run report line (launch/serve.py) and log decoration."""
    kib = ms.get("bytes", 0) / 1024.0
    if ms.get("backend") == "paged":
        if ms.get("native"):
            tail = "block-native decode (no transient view)"
        else:
            view_kib = ms.get("decode_view_bytes", 0) / 1024.0
            tail = f"+{view_kib:.1f} KiB transient decode view"
        if "prefix_cached_blocks" in ms:
            tail += (f" | prefix: {ms['prefix_hits']} hits, "
                     f"{ms['prefix_blocks_reused']} blocks reused, "
                     f"{ms['prefix_cached_blocks']} cached, "
                     f"{ms['prefix_evictions']} evicted, "
                     f"{ms['cow_forks']} COW forks")
        return (f"paged: {kib:.1f} KiB pool | block={ms['block_size']} tok | "
                f"{ms['blocks_used']}/{ms['blocks_total']} blocks used "
                f"({ms['blocks_free']} free) | {tail}")
    per_slot = ms.get("bytes_per_slot", 0) / 1024.0
    return (f"{ms.get('backend', '?')}: {kib:.1f} KiB "
            f"({per_slot:.1f} KiB/slot x {ms.get('slots', 0)} slots)")
