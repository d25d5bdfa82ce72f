"""Continuous-batching inference engine over the OPQ runtime.

The production-shaped layer the GPTPU runtime was missing: requests enter a
bounded FIFO (admission control), a slot-based scheduler joins them into a
fixed-width in-flight decode batch and retires them as they finish — no
full-batch barrier, so a long generation never stalls short ones — and a
SlotStore backend leases per-slot cache capacity (allocate once, reset on
retire, int8-KV aware). All device work is dispatched as OPQ instructions, so the
paper's buffer-affinity scheduling and backup-task straggler mitigation apply
to serving traffic, not just the Rodinia apps.

Admission is *fused prefill-with-cache*: one bucketed forward per admitted
request returns the first token AND the per-layer K/V in cache layout
(models/serve.py ``prefill_with_cache``), which one donated scatter writes
into the leased slot rows (serving/kv.py ``write_slots``). Seeding a prompt of
length L therefore costs exactly one dispatched forward + one slot write —
O(1) instructions instead of the old O(L) B=1 replay-decode chain — keeping
admission on the matmul-bound side of the roofline (the GPTPU
whole-kernel-offload argument applied to TTFT). Each prefill runs alone
(width 1), so it is one program whatever arrives with it; an admission
round dispatches all its prefills before it waits once.

Decode semantics are *greedy and batch-invariant*: every slot computes exactly
the math of a single-request decode at its own position (per-slot cache index,
see models/attention.py) in the one fixed-width decode program, and every
prefill runs alone, so concurrent or staggered arrivals are bit-identical to
one-at-a-time sequential decoding — asserted in tests/test_serving.py, which
also keeps a reference replay seeder proving fused admission is bit-identical
to the replay era. MoE routing is per-request isolated: idle slots are masked
out of the expert-capacity cumsum at decode, prefill routes row-isolated, and
serving capacity is dropless (models/moe.py), so a token's expert assignment
never depends on its batchmates.

The cache itself lives behind the SlotStore protocol (serving/store.py): the
engine only leases, seeds, resets, and exchanges an opaque pytree with the
decode step — it never touches cache leaves. Backends: ``contiguous``
(per-slot rows sized to max_seq_len), ``paged`` (vLLM-style block pool +
per-slot block tables; ``lease`` returning False is admission backpressure
when the pool runs dry), and ``recurrent`` (per-slot mamba/xlstm state rows —
ssm and hybrid families serve through the same engine, admitted by a
masked-scan prefill that is one dispatch per request like the dense path).
The paged store additionally runs block-native (``paged_native=True``): the
decode step receives the pool + tables directly and attends in place — no
gather-bridge view, peak decode working set = the pool — bit-identical to
the bridge, with an optional Pallas kernel path (``paged_kernel=True``).
Long prompts admit via chunked prefill (``prefill_chunk=W``): buckets wider
than W scan the prompt W tokens at a time, peak score memory (B, H, W, S)
instead of (B, H, S, S), bit-identical to single-shot fused prefill — so the
admissible prompt length is no longer capped by the quadratic score matrix.
A request deferred by the store lease while zero slots are active can never
make progress; ``step`` raises a diagnostic immediately instead of spinning
``max_steps`` no-ops (the fits-vs-lease drift guard).

Scope: token-input dense/moe/ssm/hybrid families. encdec/vlm (embeds input)
serving is a ROADMAP item.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.core.opq import OPQ, Buffer
from repro.distributed import sharding as shd
from repro.models import steps as ST
from repro.serving.metrics import (EngineMetrics, RequestMetrics, now, span,
                                   trace_hooks)
from repro.serving.sampling import (
    GREEDY, TOP_LOGPROBS, SamplingParams, stack_params, stop_match,
)
from repro.serving.scheduler import Scheduler, bucket_for, default_buckets
from repro.serving.store import RECURRENT_FAMILIES, SlotStore, make_store


class RequestState(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    DONE = "done"
    PREEMPTED = "preempted"    # pulled mid-flight by a drain handoff: the
                               # slot was retired, the tokens generated so
                               # far stand, and a continuation request on
                               # another engine carries the remainder


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray                     # (L,) int32
    max_new_tokens: int
    state: RequestState = RequestState.QUEUED
    tokens: List[int] = dataclasses.field(default_factory=list)
    # emission time (metrics.now epoch) of each entry in ``tokens``, stamped
    # where the engine appends — the only honest inter-token-latency source
    # for a free-running worker, whose poll deltas arrive in bursts
    token_ts: List[float] = dataclasses.field(default_factory=list)
    metrics: RequestMetrics = None         # set at submit
    sampling: Optional[SamplingParams] = None   # None == greedy
    # tokens generated in earlier segments of this logical stream (router
    # drain/handoff continuations): stop sequences match against
    # stop_history + tokens, so a handoff never re-arms or misses a stop
    stop_history: Tuple[int, ...] = ()
    finish_reason: Optional[str] = None    # "length" | "eos" | "stop"
    # logprob capture (serve API): None == off; an int N asks for the
    # chosen token's logprob plus its top-N alternatives per emitted token
    # (N == 0 records the chosen logprob only; N <= sampling.TOP_LOGPROBS)
    want_logprobs: Optional[int] = None
    logprobs: List[float] = dataclasses.field(default_factory=list)
    top_logprobs: List[List[Tuple[int, float]]] = dataclasses.field(
        default_factory=list)

    @property
    def last_token(self) -> int:
        return self.tokens[-1]

    @property
    def done(self) -> bool:
        return self.state == RequestState.DONE

    def to_wire(self) -> Dict:
        """The request's transport wire form: plain JSON/msgpack-able data,
        sufficient to re-admit the stream as a continuation elsewhere
        (serving/transport.py). The prompt travels as a token list; sampling
        params via their own wire form; metrics stay host-local."""
        from repro.serving.sampling import sampling_to_wire
        return {
            "id": self.id,
            "prompt": [int(t) for t in self.prompt],
            "max_new_tokens": self.max_new_tokens,
            "state": self.state.value,
            "tokens": [int(t) for t in self.tokens],
            "sampling": sampling_to_wire(self.sampling),
            "stop_history": [int(t) for t in self.stop_history],
            "finish_reason": self.finish_reason,
            "want_logprobs": self.want_logprobs,
            "logprobs": [float(v) for v in self.logprobs],
            "top_logprobs": [[[int(t), float(v)] for t, v in row]
                             for row in self.top_logprobs],
        }


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Per-engine serving knobs. Operator-facing documentation (including the
    CLI flag each field maps to) lives in ``docs/serving.md``.

    max_slots
        Width of the in-flight decode batch — the number of requests that
        decode concurrently. Each slot leases one cache row (or block set)
        from the SlotStore for its whole residency.
    max_queue
        Admission control: the bound on the waiting FIFO. ``submit`` rejects
        (returns None, or raises :class:`QueueFull` with ``strict=True``)
        once this many requests are queued — backpressure at the door rather
        than unbounded buffering.
    max_seq_len
        Per-slot sequence budget: a request's ``prompt + max_new_tokens``
        must fit in it. Sizes the contiguous rows / the paged slot tables /
        the recurrent prefill scan length.
    buckets
        Prefill pad lengths. Prompts are right-padded up to the smallest
        bucket that holds them, and each prefills alone, so the number of
        compiled prefill shapes is ``len(buckets)``, not set by traffic.
        ``None`` = powers of two from 16 capped at ``max_seq_len``
        (scheduler.default_buckets). A bucket wider than ``max_seq_len`` is
        rejected at construction.
    eos_id
        Early-finish token id: a request retires when it emits this token
        (or at ``max_new_tokens``, whichever first). ``None`` = length-only.
    use_opq
        Dispatch every device step through the OPQ runtime (buffer affinity +
        backup-task straggler mitigation). ``False`` runs steps eagerly —
        tests/microbenchmarks only; the OPQ instruction-flag audit trail
        (``stats()["opq"]``) disappears with it.
    cache_backend
        SlotStore backend: ``auto`` | ``contiguous`` | ``paged`` |
        ``recurrent`` (serving/store.py). ``auto`` picks contiguous for
        dense/moe archs and recurrent for ssm/hybrid.
    block_size
        Paged backend only: tokens per KV block. Must divide
        ``max_seq_len`` (the gathered view must be exactly ``max_seq_len``
        long — the bit-identity contract with the contiguous decode program).
    n_blocks
        Paged backend only: block-pool size INCLUDING the reserved null
        block 0. ``None`` sizes the pool to full capacity
        (``max_slots * max_seq_len / block_size`` + the null block); smaller
        pools trade admission backpressure for resident bytes
        (reports/BENCH_paged.json).
    paged_native
        Paged backend only (added PR 4): block-native decode. The decode
        step receives the pool + tables and writes/attends through them in
        place — no transient gather-bridge view
        (``memory_stats()["decode_view_bytes"] == 0``), tokens bit-identical
        to the bridge, which remains the reference oracle.
    paged_kernel
        With ``paged_native`` (added PR 4): route the attention contraction
        through the Pallas paged-attention kernel
        (kernels/paged_attention.py — scalar-prefetch block-table addressing
        + online softmax, block-sized VMEM working set). Float KV only: an
        int8 KV cache is refused at construction. Runs in interpret mode
        off-TPU, which is how CPU CI exercises it.
    prefill_chunk
        Dense families only (added PR 4): chunked prefill width W. Buckets
        wider than W admit through a ``lax.scan`` of W-token chunks — peak
        prefill score memory (B, H, W, S) instead of (B, H, S, S) — and the
        bucket set extends past the fused buckets by multiples of W up to
        ``max_seq_len``, lifting the long-prompt admission cap. Bit-identical
        to single-shot fused prefill. Rejected for recurrent families (their
        masked-scan prefill is already linear) and mrope position encoding.
    prefix_cache
        Paged backend only (added PR 6): shared-prefix radix cache. The
        store keeps a refcounted trie of full prompt blocks
        (serving/store.py); a lease whose prompt walks onto cached blocks
        leases them by refcount and admission dispatches the SUFFIX prefill
        step only over the unmatched chunks (block-size-wide, traced start —
        TTFT for a hot prefix is O(suffix)). Copy-on-write forks the
        divergence block before any slot write; retire scrubs only blocks
        whose refcount hits zero; unreferenced cached prefixes LRU-evict
        under pool pressure, so caching never refuses an admission the bare
        pool could serve. Tokens and cache bits stay bit-identical to cold
        admission (tests/test_prefix_cache.py). Requires ``block_size`` to
        divide every prefill bucket; rejected for mrope (the suffix scan is
        the chunked scan).
    speculative
        Draft-verify decode (added PR 7): every round a small DRAFT model
        proposes ``spec_k`` tokens per active slot in narrow decode steps,
        then the target scores all ``spec_k + 1`` window positions for the
        whole batch in ONE wide verify forward — one dispatched target step
        buys up to k+1 tokens per slot. Greedy acceptance keeps the emitted
        stream bit-identical to plain decode (each window position's greedy
        token is exactly what sequential decode would emit there); rejected
        draft K/V is scrubbed from both caches before the round ends. Slots
        advance 1..k+1 tokens per round independently — EOS/length stops
        land mid-window and retire at the stop position. Requires ``draft``
        + ``draft_params``; target must be a dense-family arch (the draft
        may be recurrent — its rollback is snapshot selection); rejected
        with ``paged_kernel`` (a single-query decode kernel) and mrope.
    spec_k
        Draft proposals per speculative round (window = ``spec_k + 1``).
    draft
        The draft model's ArchConfig (vocab must match the target's); its
        params go to ``Engine(..., draft_params=...)``. The draft runs as a
        second OPQ program with its own slot-synced store, kept in lockstep
        through admission, rollback, preemption, and retire.
    """

    max_slots: int = 4
    max_queue: int = 64
    max_seq_len: int = 64
    buckets: Optional[Tuple[int, ...]] = None
    eos_id: Optional[int] = None
    use_opq: bool = True
    cache_backend: str = "auto"
    block_size: int = 16
    n_blocks: Optional[int] = None
    paged_native: bool = False
    paged_kernel: bool = False
    prefill_chunk: Optional[int] = None
    prefix_cache: bool = False
    speculative: bool = False
    spec_k: int = 4
    draft: Optional[ArchConfig] = None


@functools.lru_cache(maxsize=None)
def _jitted_steps(cfg: ArchConfig, kind: str, max_seq_len: int = 0,
                  native: bool = False, kernel: bool = False, chunk: int = 0,
                  prefix_chunk: int = 0, spec_k: int = 0):
    """Compiled step fns shared across Engine instances of the same
    (config, store kind, decode/prefill mode) — rebuilding an engine (tests,
    benchmark sweeps) reuses XLA executables. ``max_seq_len`` keys the cache
    ONLY for the recurrent backend (its prefill scan allocates the state
    cache at that length); dense/moe callers pass 0 so engines with
    different seq budgets keep sharing one set of compiled executables.
    Dense-family prefill is the fused prefill-with-cache step: right-padded
    bucket row(s) in, (first_tokens, per-layer K/V in cache layout) out —
    causal attention means pad tokens after a row's prompt never reach its
    logits or its K/V rows, so a small fixed bucket set is exact for any pad
    content. ``chunk`` additionally builds the chunked prefill step (same
    contract, (B, H, chunk, S) peak score memory) for the long-prompt
    buckets. Recurrent-family prefill is the masked scan of the decode body
    (same contract, state rows out). The decode step is shared across
    contiguous/paged-bridge backends — paged layout translation happens
    inside the store's decode_cache/swap bridge, which is what makes paged
    decode bit-identical to contiguous; ``native`` compiles the block-native
    decode instead (pool in, pool out — models/serve.py decode_paged), which
    is bit-identical to the bridge by construction."""
    if kind == "recurrent":
        prefill = jax.jit(ST.make_recurrent_prefill_step(cfg, max_seq_len))
    else:
        prefill = jax.jit(ST.make_prefill_with_cache_step(cfg))
    prefill_chunked = (jax.jit(ST.make_chunked_prefill_step(cfg, chunk))
                       if chunk else None)
    # ``prefix_chunk`` (== the paged block size) builds the suffix prefill
    # for prefix-cache hits: the chunked scan with a TRACED start chunk and
    # cache-seeded accumulators, so one executable per (B, bucket) serves
    # every matched-prefix length
    prefill_suffix = (
        jax.jit(ST.make_suffix_prefill_step(cfg, prefix_chunk))
        if prefix_chunk else None)
    decode_fn = (ST.make_paged_decode_step(cfg, use_kernel=kernel)
                 if native else ST.make_decode_step(cfg))
    decode = jax.jit(decode_fn, donate_argnums=(1,))
    # ``spec_k`` builds the speculative verify step: the W = spec_k + 1 wide
    # target forward that scores a whole draft window in one dispatch
    # (models/steps.py make_verify_step). Block-native engines verify through
    # the pool + tables; the paged gather bridge and the contiguous backend
    # share the contiguous verify program, exactly like plain decode.
    verify = None
    if spec_k:
        verify_fn = (ST.make_paged_verify_step(cfg, spec_k + 1) if native
                     else ST.make_verify_step(cfg, spec_k + 1))
        verify = jax.jit(verify_fn, donate_argnums=(1,))
    return prefill, prefill_chunked, prefill_suffix, decode, verify


@functools.lru_cache(maxsize=None)
def _jitted_draft_steps(cfg: ArchConfig, kind: str, max_seq_len: int = 0,
                        donate: bool = True):
    """Compiled DRAFT-model steps for speculative decode: the bucketed
    admission prefill (the draft cache must be seeded with the prompt through
    the draft's own weights) and the narrow proposal decode. Recurrent drafts
    pass ``donate=False``: the engine keeps one state snapshot per draft step
    of a round so rollback can per-slot select the post-acceptance state —
    donating would overwrite snapshot i while producing i+1."""
    if kind == "recurrent":
        prefill = jax.jit(ST.make_recurrent_prefill_step(cfg, max_seq_len))
    else:
        prefill = jax.jit(ST.make_prefill_with_cache_step(cfg))
    decode_fn = ST.make_decode_step(cfg)
    decode = (jax.jit(decode_fn, donate_argnums=(1,))
              if donate else jax.jit(decode_fn))
    return prefill, decode


@functools.lru_cache(maxsize=None)
def _jitted_embed(cfg: ArchConfig):
    """Compiled non-generative forward (serve API embeddings/classification):
    bucketed tokens in, (last-position hidden, last-position logits) out —
    shared across Engine instances like the serving steps above."""
    return jax.jit(ST.make_embed_step(cfg))


class _Ready:
    """Completed-future shim for the OPQ-disabled direct-dispatch path."""

    def __init__(self, value):
        self._value = value

    def result(self):
        return self._value


class QueueFull(Exception):
    """Raised by submit(strict=True) when admission control rejects."""


def _placed(method):
    """Run an Engine method under the engine's device and mesh (see
    :meth:`Engine.placed`)."""
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with self.placed():
            return method(self, *args, **kwargs)
    return wrapper


class Engine:
    """See module docstring. Typical use::

        engine = Engine(cfg, params, EngineConfig(max_slots=4, max_seq_len=64))
        engine.submit(prompt_ids, max_new_tokens=16)
        done = engine.run_until_complete()
    """

    def __init__(self, cfg: ArchConfig, params, engine_cfg: EngineConfig = None,
                 *, opq: Optional[OPQ] = None, draft_params=None,
                 mesh: Optional[Mesh] = None):
        if (cfg.family not in ("dense", "moe") + RECURRENT_FAMILIES
                or cfg.input_mode != "tokens"):
            raise ValueError(
                f"serving engine supports token-input dense/moe/ssm/hybrid "
                f"archs, got family={cfg.family} input_mode={cfg.input_mode}")
        trace_hooks()
        self.cfg = cfg
        self.ecfg = engine_cfg or EngineConfig()
        # one device per engine: its params, cache and every step it traces
        # or runs live there (the ambient one-device mesh, else device 0)
        if mesh is None:
            mesh = shd.active_mesh() or shd.device_mesh(jax.devices()[0])
        if mesh.size != 1:
            raise ValueError(
                f"an Engine runs on exactly one device, got a mesh of "
                f"{mesh.size} ({dict(mesh.shape)}); serve several devices "
                f"with one Engine per device behind the Router")
        self.mesh = mesh
        self.device = mesh.devices.flat[0]
        if self.ecfg.paged_kernel and not self.ecfg.paged_native:
            raise ValueError("paged_kernel requires paged_native=True")
        if self.ecfg.paged_kernel and cfg.kv_cache_dtype == "int8":
            raise ValueError(
                "paged_kernel attends over a float KV pool; the int8 KV cache "
                "(kv_cache_dtype='int8') has no kernel path — drop "
                "paged_kernel or use a float KV cache")
        if self.ecfg.paged_native and self.ecfg.cache_backend != "paged":
            raise ValueError(
                f"paged_native requires cache_backend='paged', got "
                f"{self.ecfg.cache_backend!r}")
        if self.ecfg.prefix_cache:
            if self.ecfg.cache_backend != "paged":
                raise ValueError(
                    f"prefix_cache (shared-prefix radix cache) requires "
                    f"cache_backend='paged', got {self.ecfg.cache_backend!r}")
            if cfg.rope_kind == "mrope":
                raise ValueError(
                    "prefix_cache does not support mrope position encoding "
                    "(the suffix prefill is the chunked scan, which does not "
                    "thread positions3)")
        if self.ecfg.speculative:
            if self.ecfg.draft is None or draft_params is None:
                raise ValueError(
                    "speculative decode needs a draft model: set "
                    "EngineConfig.draft (the draft ArchConfig) and pass "
                    "Engine(..., draft_params=...)")
            if self.ecfg.spec_k < 1:
                raise ValueError(
                    f"spec_k must be >= 1, got {self.ecfg.spec_k}")
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"speculative decode verifies through the K/V-window "
                    f"path, so the TARGET must be a dense-family arch, got "
                    f"{cfg.family} (a recurrent model can be the draft, "
                    f"not the target)")
            if cfg.rope_kind == "mrope":
                raise ValueError(
                    "speculative verify does not support mrope position "
                    "encoding (the window forward does not thread positions3)")
            if self.ecfg.paged_kernel:
                raise ValueError(
                    "speculative decode does not route through the Pallas "
                    "paged-attention kernel (a single-query decode shape; "
                    "the verify window is multi-query) — drop paged_kernel")
            d = self.ecfg.draft
            if d.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {d.vocab} != target vocab {cfg.vocab}: "
                    f"draft proposals must be target token ids")
            if (d.input_mode != "tokens"
                    or d.family not in ("dense", "moe") + RECURRENT_FAMILIES):
                raise ValueError(
                    f"draft must be a token-input dense/moe/ssm/hybrid arch, "
                    f"got family={d.family} input_mode={d.input_mode}")
        elif self.ecfg.draft is not None:
            raise ValueError(
                "EngineConfig.draft is set but speculative=False — enable "
                "speculative or drop the draft config")
        buckets = self.ecfg.buckets or default_buckets(self.ecfg.max_seq_len)
        chunk = self.ecfg.prefill_chunk
        if chunk:
            if cfg.family in RECURRENT_FAMILIES:
                raise ValueError(
                    "prefill_chunk applies to the dense-family score-matrix "
                    f"prefill, not the recurrent scan ({cfg.family})")
            if cfg.rope_kind == "mrope":
                raise ValueError(
                    "prefill_chunk does not support mrope position encoding "
                    "(the chunked scan does not thread positions3)")
            if not 1 <= chunk <= self.ecfg.max_seq_len:
                raise ValueError(
                    f"prefill_chunk {chunk} must be in [1, max_seq_len "
                    f"{self.ecfg.max_seq_len}]")
            # buckets at most one chunk wide keep the single-shot fused step;
            # beyond that, admission goes through chunk-multiple buckets and
            # the chunked scan — which is what lifts the admissible prompt
            # length past the widest fused bucket
            fused = tuple(b for b in buckets if b <= chunk)
            chunked = tuple(
                k * chunk for k in range(1, self.ecfg.max_seq_len // chunk + 1)
                if k * chunk > max(fused, default=0))
            self._chunked_buckets = frozenset(chunked)
            buckets = tuple(sorted(set(fused) | set(chunked)))
        else:
            self._chunked_buckets = frozenset()
        if max(buckets) > self.ecfg.max_seq_len:
            # a bucket wider than the slot rows could admit prompts whose
            # fused K/V block cannot be scattered into the cache
            raise ValueError(
                f"largest prefill bucket {max(buckets)} exceeds "
                f"max_seq_len {self.ecfg.max_seq_len} (the slot-row length)")
        if self.ecfg.prefix_cache:
            bad = [b for b in buckets if b % self.ecfg.block_size]
            if bad:
                # the suffix prefill scans block-size-wide chunks, so a
                # bucket must be a whole number of them to resume mid-prompt
                raise ValueError(
                    f"prefix_cache requires block_size "
                    f"{self.ecfg.block_size} to divide every prefill bucket "
                    f"(got {bad})")
        self.scheduler = Scheduler(self.ecfg.max_slots, buckets)
        with self.placed():
            self._build_device_state(cfg, params, draft_params, chunk, opq)
        self._req_ids = itertools.count()
        # host-side token-presence bitmap per slot (prompt + generated): the
        # repetition penalty's input, maintained through admit/emit/retire so
        # it rides the slot lease like the cache does
        self._presence = np.zeros((self.ecfg.max_slots, cfg.vocab_padded),
                                  bool)
        self.metrics = EngineMetrics()
        # fleet counter reconciliation: which queued request ids this engine
        # has already counted as deferred, and each slot's prefix-cache hit
        # contribution — evict_queued/preempt unwind exactly what admission
        # counted, so a request drained and re-admitted on another host shows
        # up once (not once per host) in fleet-summed stats()
        self._deferred_ids: set = set()
        self._prefix_contrib: Dict[int, Tuple[int, int, int]] = {}
        self.completed: List[Request] = []

    def _build_device_state(self, cfg, params, draft_params, chunk, opq):
        """Everything that lives on the engine's device: params, the slot
        store, the compiled steps, the OPQ lanes, and the draft model's."""
        self.params = jax.device_put(params, self.device)
        self.store: SlotStore = make_store(
            cfg, self.ecfg.max_slots, self.ecfg.max_seq_len,
            backend=self.ecfg.cache_backend,
            block_size=self.ecfg.block_size, n_blocks=self.ecfg.n_blocks,
            native=self.ecfg.paged_native,
            prefix_cache=self.ecfg.prefix_cache)
        (self._prefill, self._prefill_chunked, self._prefill_suffix,
         self._decode, self._verify) = _jitted_steps(
            cfg, self.store.kind,
            self.ecfg.max_seq_len if self.store.kind == "recurrent" else 0,
            native=self.ecfg.paged_native, kernel=self.ecfg.paged_kernel,
            chunk=chunk or 0,
            prefix_chunk=self.ecfg.block_size if self.ecfg.prefix_cache else 0,
            spec_k=self.ecfg.spec_k if self.ecfg.speculative else 0)
        self._owns_opq = opq is None and self.ecfg.use_opq
        self.opq = ((OPQ([self.device]) if self._owns_opq else opq)
                    if self.ecfg.use_opq else None)
        self._params_buf = self._resident(self.params, "params")
        # the draft model is a SECOND program over the same slot geometry:
        # its own params buffer, its own slot-synced store (contiguous for
        # dense drafts, per-slot state rows for recurrent ones), admitted /
        # rolled back / reset in lockstep with the target's slots
        self.draft_store: Optional[SlotStore] = None
        if self.ecfg.speculative:
            dcfg = self.ecfg.draft
            self.draft_store = make_store(dcfg, self.ecfg.max_slots,
                                          self.ecfg.max_seq_len)
            self._draft_recurrent = self.draft_store.kind == "recurrent"
            self._draft_prefill, self._draft_decode = _jitted_draft_steps(
                dcfg, self.draft_store.kind,
                self.ecfg.max_seq_len if self._draft_recurrent else 0,
                donate=not self._draft_recurrent)
            self._draft_params_buf = self._resident(
                jax.device_put(draft_params, self.device), "draft-params")

    @contextlib.contextmanager
    def placed(self):
        """Enter this engine's device (default placement) and mesh on the
        calling thread. Both are thread-local, so every entry point that
        touches the device — and every OPQ worker running one of its
        steps — enters them itself; engines on different devices in one
        process never see each other's placement."""
        with jax.default_device(self.device), shd.use_mesh(self.mesh):
            yield

    @property
    def kv(self) -> SlotStore:
        """Back-compat alias from the KVSlotManager era — the slot store."""
        return self.store

    # ------------------------------------------------------------ OPQ bridge

    def _resident(self, tree, name: str) -> Buffer:
        return Buffer.resident(tree, self.device, name=name)

    def _dispatch(self, fn, *bufs: Buffer, flags: str = ""):
        """Run one instruction to completion (decode path)."""
        return self._dispatch_async(fn, *bufs, flags=flags).result()

    def _dispatch_async(self, fn, *bufs: Buffer, flags: str = ""):
        """Issue one instruction and return its future: through the OPQ
        scheduler (affinity + backup tasks), or eagerly when the runtime is
        disabled. Admission uses this to overlap the per-bucket prefills of
        one round on the lanes before a single wait. Untracked: the engine
        consumes each result itself, so nothing is retained for sync() and
        the task registry stays empty over an unbounded serving run."""
        def run(*args):
            with self.placed():
                return fn(*args)
        if self.opq is None:
            return _Ready(run(*(b.data for b in bufs)))
        return self.opq.invoke_operator(run, *bufs, flags=flags, track=False)

    # ------------------------------------------------------------- admission

    def would_accept(self, prompt_len: int, max_new_tokens: int) -> bool:
        """The submit-time admission predicate, side-effect free: whether a
        request of this shape would pass the door right now (queue bound, seq
        budget, bucket cap, store total-capacity ``fits``). The multi-host
        router asks this before placing or handing off a request so a
        rejection never costs a preemption (serving/router.py)."""
        return not (self.scheduler.queue_depth >= self.ecfg.max_queue
                    or prompt_len < 1
                    or max_new_tokens < 1
                    or prompt_len + max_new_tokens > self.ecfg.max_seq_len
                    # custom buckets may cap below max_seq_len: reject at the
                    # door, not mid-admission after a slot was leased
                    or prompt_len > max(self.scheduler.buckets)
                    # a request exceeding the store's TOTAL capacity (e.g.
                    # more paged blocks than the pool holds) could never be
                    # leased: deferring it would livelock the queue head
                    or not self.store.fits(prompt_len, max_new_tokens))

    def lease_headroom(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Whether the store could lease this request RIGHT NOW (free paged
        blocks vs. ``fits``'s total-capacity check). False means admission
        would defer on backpressure — the router's cue to spill the request
        to another host instead of head-of-line blocking behind a dry pool."""
        return self.store.available_now(prompt_len, max_new_tokens)

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               *, sampling: Optional[SamplingParams] = None,
               stop_history: Sequence[int] = (),
               want_logprobs: Optional[int] = None,
               strict: bool = False) -> Optional[Request]:
        """Admission control at the door: a bounded queue and a hard per-slot
        sequence budget. Returns the Request, or None when rejected
        (QueueFull when ``strict``).

        ``sampling`` (None == greedy) rides the request through its whole
        slot residency; ``stop_history`` is the generated prefix of an
        earlier segment (router drain handoff) that stop sequences must see.
        ``want_logprobs`` (None == off) records each emitted token's logprob
        plus its top-N alternatives from the very logits row the token
        choice used. Non-greedy params on a speculative engine are a
        configuration error (greedy acceptance is what makes draft-verify
        exact; rejection sampling is a ROADMAP item), diagnosed here rather
        than emitting a silently-greedy stream."""
        if (sampling is not None and not sampling.greedy
                and self.ecfg.speculative):
            raise ValueError(
                f"speculative decode is greedy-only: temperature="
                f"{sampling.temperature} requires sampled acceptance "
                f"(rejection sampling — a ROADMAP follow-up). Drop "
                f"--speculative or the sampling params.")
        if want_logprobs is not None and not 0 <= want_logprobs <= TOP_LOGPROBS:
            raise ValueError(
                f"want_logprobs must be in [0, {TOP_LOGPROBS}] (the device-"
                f"side top-K capture width), got {want_logprobs}")
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if not self.would_accept(len(prompt), max_new_tokens):
            self.metrics.rejected += 1
            if strict:
                raise QueueFull(
                    f"rejected: queue_depth={self.scheduler.queue_depth}, "
                    f"prompt={len(prompt)} + gen={max_new_tokens} vs "
                    f"max_seq_len={self.ecfg.max_seq_len}")
            return None
        req = Request(id=next(self._req_ids), prompt=prompt,
                      max_new_tokens=max_new_tokens,
                      sampling=sampling, stop_history=tuple(stop_history),
                      want_logprobs=want_logprobs,
                      metrics=RequestMetrics(arrival_s=now(),
                                             prompt_len=len(prompt)))
        self.scheduler.enqueue(req)
        self.metrics.submitted += 1
        return req

    # ----------------------------------------------------------- engine step

    def _try_lease(self, slot: int, req: Request) -> bool:
        """Reserve store capacity for a request before the scheduler commits
        the slot. A False return (paged block-pool dry) leaves the request at
        the queue head — admission backpressure, never mid-flight corruption.
        With the prefix cache on, the lease also walks the radix trie with
        the prompt tokens; matched cached blocks are leased by refcount and
        their prefill skipped (``_admit`` reads ``prefix_lease_info``)."""
        ok = self.store.lease(
            slot, len(req.prompt), req.max_new_tokens,
            tokens=req.prompt if self.ecfg.prefix_cache else None)
        if not ok:
            # counted once per request, not per attempt: the deferred queue
            # head is re-tried every step, and a fleet drain + re-admission
            # elsewhere reconciles this host's count back out (evict_queued)
            # — so admissions_deferred means "requests that experienced
            # deferral", summable across hosts without double-counting
            if req.id not in self._deferred_ids:
                self._deferred_ids.add(req.id)
                self.metrics.admissions_deferred += 1
            return ok
        self._deferred_ids.discard(req.id)
        if self.ecfg.prefix_cache:
            info = self.store.prefix_lease_info(slot)
            if info["hit"]:
                self.metrics.prefix_hits += 1
                self.metrics.prefix_blocks_reused += info["shared_blocks"]
                self.metrics.prefix_tokens_reused += info["prefill_start"]
                self._prefix_contrib[slot] = (
                    1, info["shared_blocks"], info["prefill_start"])
        return ok

    def _prefix_start_chunk(self, slot: int) -> int:
        """The first prompt chunk a prefix-cache slot's prefill computes:
        its lease already holds every cached chunk before it."""
        return (self.store.prefix_lease_info(slot)["prefill_start"]
                // self.ecfg.block_size)

    def _admit(self) -> int:
        """Fused admission: ONE dispatched prefill forward per admitted
        request (first token + cache payload out — per-layer K/V for dense
        families, post-prompt state rows for recurrent ones) and ONE donated
        scatter into its leased slot rows — zero B=1 replay decodes, seeding
        cost O(1) instructions in prompt length. Every prefill runs at width
        1, so a request's prefill is the same program whatever arrives with
        it: its first token and K/V cannot depend on its batchmates. (A
        prefill batched at width N is another compiled program, and on a TPU
        its rows differ from the width-1 ones — scripts/prefill_width.py.)
        All prefills of the round are dispatched before the first wait, so
        they queue back to back on the device. Buckets wider than
        ``prefill_chunk`` dispatch the chunked prefill step instead (long
        prompts — linear-in-S peak score memory, same contract and
        bit-identical output); a prefix-cache hit dispatches the suffix
        prefill from its first uncached chunk. Returns the number of
        requests admitted this round (step() uses 0 to detect a
        zero-progress deferral with an idle engine)."""
        pending = []
        for bucket, slot, req in self.scheduler.plan_admissions(
                self._try_lease):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :len(req.prompt)] = req.prompt
            last = np.asarray([len(req.prompt) - 1], np.int32)
            presence = np.zeros((1, self.cfg.vocab_padded), bool)
            presence[0, req.prompt] = True
            # the slot inherits the row's presence for decode steps
            self._presence[slot, :] = presence[0]
            req.metrics.admitted_s = now()
            smp = stack_params([req.sampling], presence)
            start_chunk = (self._prefix_start_chunk(slot)
                           if self.ecfg.prefix_cache else 0)
            chunked = bucket in self._chunked_buckets
            if start_chunk > 0:
                kv0 = self.store.gather_prefix_rows([slot], bucket)
                fut = self._dispatch_async(
                    lambda p, t, li, k0, s, fn=self._prefill_suffix,
                    sc=start_chunk: fn(p, t, li, k0, sc, s),
                    self._params_buf, Buffer(toks, name=f"prefill{bucket}"),
                    Buffer(last), self._resident(kv0, "prefix-kv0"),
                    Buffer(smp, name="sampling"),
                    flags=f"prefill_prefix/{bucket}")
                self.metrics.prefill_chunks += (
                    bucket // self.ecfg.block_size - start_chunk)
            else:
                step_fn = self._prefill_chunked if chunked else self._prefill
                flag = (f"prefill_chunked/{bucket}" if chunked
                        else f"prefill/{bucket}")
                # sampling params always ride the dispatch — ONE prefill
                # executable per bucket regardless of the greedy/sampled mix
                fut = self._dispatch_async(
                    lambda p, t, li, s, fn=step_fn: fn(p, t, li, s),
                    self._params_buf, Buffer(toks, name=f"prefill{bucket}"),
                    Buffer(last), Buffer(smp, name="sampling"), flags=flag)
                if self.ecfg.prefix_cache:
                    # cold prefills compute every block-size chunk — the unit
                    # the prefix benchmark counts dispatched prefill work in
                    self.metrics.prefill_chunks += (
                        bucket // self.ecfg.block_size)
            # speculative: the draft cache must hold the prompt through the
            # DRAFT's own weights, so every admission also dispatches a draft
            # prefill (always the full prompt — the draft store has no prefix
            # cache, and a suffix skip of the target never applies to it)
            dfut = None
            if self.draft_store is not None:
                dfut = self._dispatch_async(
                    lambda p, t, li, fn=self._draft_prefill: fn(p, t, li),
                    self._draft_params_buf,
                    Buffer(toks, name=f"draft-prefill{bucket}"),
                    Buffer(last), flags=f"draft_prefill/{bucket}")
            pending.append((slot, req, fut, dfut))
        for slot, req, fut, dfut in pending:
            with span("engine.prefill_wait", self.metrics, "prefill_wait_s"):
                first, kv, lp = fut.result()
                tok = int(np.asarray(first)[0])
            self.metrics.prefill_batches += 1
            self.metrics.prefill_tokens += len(req.prompt)
            with span("engine.seed", self.metrics, "seed_write_s"):
                self._seed_admitted(slot, req, kv)
                if dfut is not None:
                    # draft first token discarded — the TARGET's prefill
                    # token is the emitted one; the draft only needed its
                    # cache seeded
                    _, dkv = dfut.result()
                    self.draft_store.write_slots([slot], dkv,
                                                 [len(req.prompt)])
            req.state = RequestState.RUNNING
            req.tokens.append(tok)
            req.token_ts.append(now())
            self._record_logprob(req, lp, 0)
            self._presence[slot, tok] = True
            if req.sampling is not None and not req.sampling.greedy:
                self.metrics.sampled_tokens += 1
            req.metrics.first_token_s = now()
            req.metrics.n_generated = 1
            self.metrics.observe_tokens(1)
            if self._finished(req):           # done at the prefill token:
                self._retire(slot)            # reset scrubs the seeded row
        return len(pending)

    def _record_logprob(self, req: Request, lp, idx) -> None:
        """Append one emitted token's logprob record from a step's
        ``logprob_info`` payload (idx selects the request's row — an int for
        prefill/decode, a (slot, window_pos) pair for verify). Free for
        requests that didn't opt in: the payload was computed inside the
        already-dispatched step (one executable), only the host-side copy
        is skipped."""
        if req.want_logprobs is None:
            return
        req.logprobs.append(float(np.asarray(lp["lp"])[idx]))
        ids = np.asarray(lp["top_ids"])[idx]
        lps = np.asarray(lp["top_lps"])[idx]
        req.top_logprobs.append(
            [(int(t), float(v)) for t, v in zip(ids, lps)])

    def _sampling_batch(self) -> Dict:
        """The decode batch's stacked per-slot sampling params + presence
        rows (serving/sampling.py). Always attached to the dispatch, so the
        decode program is ONE executable across every greedy/sampled mix —
        idle and paramless slots stack as GREEDY."""
        return stack_params(self.scheduler.sampling_by_slot(GREEDY),
                            self._presence.copy())

    def _seed_admitted(self, slot: int, req: Request, kv) -> None:
        """Seed one admitted slot's leased rows from the fused prefill's
        payload — one donated scatter through the store. Overridable seam:
        tests substitute the PR-1 B=1 replay seeder here to prove fused
        admission is bit-identical to prompt replay."""
        self.store.write_slots([slot], kv, [len(req.prompt)])

    def _decode_once(self) -> None:
        """One batched decode step, in three host spans inside
        ``engine.decode``: ``prepare`` (batch, sampling params, presence
        copy, buffers), ``wait`` (dispatch until the result is in hand) and
        ``emit`` (cache swap, host copy of the tokens, the slot loop,
        retires)."""
        with span("engine.decode"):
            with span("engine.decode.prepare"):
                toks, active = self.scheduler.decode_batch()
                bufs = (self._params_buf,
                        self._resident(self.store.decode_cache(), "kv-cache"),
                        Buffer({"tokens": toks, "active": active,
                                "sampling": self._sampling_batch()},
                               name="decode-tokens"))
            with span("engine.decode.wait"):
                next_tok, cache, lp = self._dispatch(
                    lambda p, c, b: self._decode(p, c, b), *bufs,
                    flags="decode")
            with span("engine.decode.emit"):
                self._emit_decoded(next_tok, cache, lp)

    def _emit_decoded(self, next_tok, cache, lp) -> None:
        """Swap in the step's cache, copy its tokens to the host and hand
        each active slot its token; retire the finished."""
        self.store.swap(cache)
        self.metrics.decode_steps += 1
        next_np = np.asarray(next_tok)
        produced = 0
        t_emit = now()
        for slot, req in list(self.scheduler.active.items()):
            tok = int(next_np[slot])
            req.tokens.append(tok)
            req.token_ts.append(t_emit)
            self._record_logprob(req, lp, slot)
            self._presence[slot, tok] = True
            if req.sampling is not None and not req.sampling.greedy:
                self.metrics.sampled_tokens += 1
            req.metrics.n_generated += 1
            produced += 1
            if self._finished(req):
                self._retire(slot)
        self.metrics.observe_tokens(produced)

    def _spec_decode_once(self) -> None:
        """One speculative draft-verify round. k+1 NARROW draft decode steps
        propose k tokens per active slot (the last proposal is discarded —
        the extra step keeps the draft cache in lockstep through a fully
        accepted window), then ONE W = k+1 wide target verify forward scores
        every window position for the whole batch, and each slot advances by
        its own acceptance length: 1..k+1 tokens per round, EOS/length stops
        landing mid-window. Greedy acceptance makes the stream provably
        bit-identical to plain decode — window position j's greedy token is
        exactly what sequential decode would emit after j accepted tokens,
        so a bad draft costs speed, never correctness. Rejected window
        positions are scrubbed from BOTH caches before any retire: future
        verify horizons reach them, and the retire-time row bits must equal
        plain decode's (the cache-bit half of the invariant). Host spans as
        in :meth:`_decode_once`; ``wait`` holds the draft steps and the
        verify."""
        with span("engine.decode"):
            with span("engine.decode.prepare"):
                toks, active = self.scheduler.decode_batch()
                # window column 0 is each slot's last emitted token; columns
                # 1..k the draft's chained proposals
                window = np.zeros((self.ecfg.max_slots, self.ecfg.spec_k + 1),
                                  np.int32)
                window[:, 0] = toks[:, 0]
                snapshots = ([self.draft_store.decode_cache()]
                             if self._draft_recurrent else None)
            with span("engine.decode.wait"):
                greedy, cache, lp = self._spec_propose_verify(
                    toks, active, window, snapshots)
            with span("engine.decode.emit"):
                self._spec_accept(window, snapshots, greedy, cache, lp)

    def _spec_propose_verify(self, toks, active, window, snapshots):
        """The draft's k+1 chained steps (filling ``window`` and
        ``snapshots``), then the target's one wide verify forward."""
        k = self.ecfg.spec_k
        W = k + 1
        n = self.ecfg.max_slots
        cur = toks
        for i in range(W):
            nxt, dcache = self._dispatch(
                lambda p, c, b: self._draft_decode(p, c, b),
                self._draft_params_buf,
                self._resident(self.draft_store.decode_cache(), "draft-cache"),
                Buffer({"tokens": cur, "active": active}, name="draft-tokens"),
                flags="draft_decode")
            self.draft_store.swap(dcache)
            if snapshots is not None:
                snapshots.append(dcache)
            self.metrics.draft_steps += 1
            nxt_np = np.asarray(nxt).reshape(n).astype(np.int32)
            if i < k:
                window[:, i + 1] = nxt_np
            cur = nxt_np.reshape(n, 1)
        # ---- verify: one wide target forward for the whole batch
        return self._dispatch(
            lambda p, c, b: self._verify(p, c, b),
            self._params_buf,
            self._resident(self.store.decode_cache(), "kv-cache"),
            Buffer({"tokens": window, "active": active}, name="verify-window"),
            flags="verify")

    def _spec_accept(self, window, snapshots, greedy, cache, lp) -> None:
        """Each slot's acceptance, the emitted tokens, the rollback of both
        caches and the retires of one draft-verify round."""
        k = self.ecfg.spec_k
        W = k + 1
        n = self.ecfg.max_slots
        self.store.swap_window(cache, W)
        self.metrics.decode_steps += 1
        self.metrics.spec_rounds += 1
        greedy_np = np.asarray(greedy)                     # (B, W)
        # ---- per-slot acceptance (host) + fixed-shape rollback plan
        slot_ids = np.full((n,), n, np.int64)              # pad: dropped
        new_index = np.zeros((n,), np.int64)
        scrub = np.full((n, k), self.ecfg.max_seq_len, np.int64)
        sel = np.zeros((n,), np.int64)                     # recurrent draft
        produced = 0
        to_retire = []
        for slot, req in list(self.scheduler.active.items()):
            # pre-round write position: prompt + generated - 1, the last
            # emitted token's (unwritten) slot — pure host arithmetic, no
            # device sync in the hot loop
            p = len(req.prompt) + req.metrics.n_generated - 1
            g = greedy_np[slot]
            a = 0             # leading draft proposals the target confirms
            while a < k and window[slot, a + 1] == g[a]:
                a += 1
            emit = min(a + 1, req.max_new_tokens - req.metrics.n_generated)
            if self.ecfg.eos_id is not None:
                hits = np.flatnonzero(g[:emit] == self.ecfg.eos_id)
                if hits.size:     # stop lands mid-window: nothing past it
                    emit = int(hits[0]) + 1
            stop = req.sampling.stop if req.sampling is not None else ()
            if stop:
                # a stop sequence can complete mid-window too: truncate the
                # emission at the first window position whose suffix matches
                hist = tuple(req.stop_history) + tuple(req.tokens)
                for j in range(emit):
                    if stop_match(hist + tuple(int(t) for t in g[:j + 1]),
                                  stop):
                        emit = j + 1
                        break
            req.tokens.extend(int(t) for t in g[:emit])
            req.token_ts.extend([now()] * emit)
            for j in range(emit):
                self._record_logprob(req, lp, (slot, j))
            self._presence[slot, [int(t) for t in g[:emit]]] = True
            req.metrics.n_generated += emit
            produced += emit
            self.metrics.proposed_tokens += k
            self.metrics.accepted_tokens += emit - 1
            self.metrics.accept_hist[emit] = (
                self.metrics.accept_hist.get(emit, 0) + 1)
            slot_ids[slot] = slot
            new_index[slot] = p + emit
            sel[slot] = emit
            scrub[slot, :W - emit] = p + emit + np.arange(W - emit)
            if self._finished(req):
                to_retire.append(slot)
        self.store.rollback(slot_ids, new_index, scrub)
        if self._draft_recurrent:
            # recurrent state has no positions to scrub: each slot adopts
            # the snapshot taken right after its last accepted token
            self.draft_store.adopt_selected(snapshots, sel)
        else:
            # the draft wrote K/V at exactly the target's window positions
            # (feed i writes position p+i), so the same rollback plan applies
            self.draft_store.rollback(slot_ids, new_index, scrub)
        for slot in to_retire:
            self._retire(slot)
        self.metrics.observe_tokens(produced)

    def _finished(self, req: Request) -> bool:
        """Finish check after every emitted token, setting
        ``req.finish_reason`` (priority: length, eos, stop). Stop sequences
        suffix-match the generated stream only — ``stop_history + tokens``,
        so a drain-handoff continuation still sees a match spanning the
        handoff point, and a match spanning a decode-step boundary fires at
        its last token."""
        if req.metrics.n_generated >= req.max_new_tokens:
            req.finish_reason = "length"
            return True
        if (self.ecfg.eos_id is not None
                and req.last_token == self.ecfg.eos_id):
            req.finish_reason = "eos"
            return True
        stop = req.sampling.stop if req.sampling is not None else ()
        if stop and stop_match(tuple(req.stop_history) + tuple(req.tokens),
                               stop):
            req.finish_reason = "stop"
            self.metrics.stop_hits += 1
            return True
        return False

    def _unwind_prefix(self, slot: int) -> None:
        """Take back a departing slot's prefix-cache hit counters: a
        preempted/exported request is re-admitted elsewhere, where its prefix
        walk is counted afresh — keeping this host's contribution would make
        the fleet-summed hit/reuse totals count one logical admission twice
        (the ISSUE-10 counter-reconciliation fix; regression in
        tests/test_disagg.py). Requests that COMPLETE here keep their counts
        (_retire drops the record without decrementing)."""
        contrib = self._prefix_contrib.pop(slot, None)
        if contrib is not None:
            hits, blocks, toks = contrib
            self.metrics.prefix_hits -= hits
            self.metrics.prefix_blocks_reused -= blocks
            self.metrics.prefix_tokens_reused -= toks

    def _retire(self, slot: int) -> None:
        req = self.scheduler.retire(slot)
        self._prefix_contrib.pop(slot, None)
        self.store.reset(slot)
        self._presence[slot, :] = False
        if self.draft_store is not None:
            self.draft_store.reset(slot)
        req.state = RequestState.DONE
        req.metrics.finish_s = now()
        self.metrics.completed += 1
        self.completed.append(req)

    # ------------------------------------------------------------ drain hooks
    # The multi-host router (serving/router.py) drains an engine by (1) no
    # longer placing traffic on it, (2) pulling its not-yet-admitted queue
    # with evict_queued, and (3) preempting long in-flight generations for
    # re-admission elsewhere. Both hooks operate at step boundaries only —
    # nothing is ever interrupted mid-dispatch.

    def evict_queued(self) -> List[Request]:
        """Pull every not-yet-admitted request out of the waiting FIFO, in
        order, leaving in-flight slots untouched. The requests hold no cache
        state yet (admission is what leases and seeds a slot), so the caller
        can re-submit them anywhere verbatim."""
        out = list(self.scheduler.waiting)
        self.scheduler.waiting.clear()
        for req in out:
            req.state = RequestState.PREEMPTED
            if req.id in self._deferred_ids:
                # the deferral leaves with the request: whichever host
                # re-admits it counts (or not) on its own, so the fleet sum
                # sees one deferral per logical request, not one per host it
                # ever waited on
                self._deferred_ids.discard(req.id)
                self.metrics.admissions_deferred -= 1
        self.metrics.evicted += len(out)
        return out

    @_placed
    def preempt(self, req_id: int) -> Request:
        """Remove an in-flight request at a step boundary: retire its slot,
        scrub its cache rows, and return it with the tokens it generated so
        far (>= 1 — admission produced the first). Greedy decode is
        deterministic, so a continuation submitted elsewhere with
        ``prompt + tokens`` as its prompt regenerates the EXACT remaining
        stream — the fused prefill-with-cache seeding path is bit-identical
        to decode replay, which is what makes drain handoff lossless
        (asserted in tests/test_router.py)."""
        for slot, req in self.scheduler.active.items():
            if req.id == req_id:
                self.scheduler.retire(slot)
                self.store.reset(slot)
                self._presence[slot, :] = False
                if self.draft_store is not None:
                    self.draft_store.reset(slot)
                self._unwind_prefix(slot)
                req.state = RequestState.PREEMPTED
                self.metrics.preempted += 1
                return req
        raise KeyError(f"request {req_id} is not in flight on this engine")

    # -------------------------------------------------- disaggregated handoff
    # Prefill/decode disaggregation (serving/router.py --disaggregate): a
    # prefill host admits and prefills a request, then its finished cache
    # blocks are SHIPPED to a decode host instead of recomputed there.
    # extract_seeded is the export side (a preempt whose KV leaves as a wire
    # payload); submit_seeded the import side (admission from a payload —
    # zero prefill dispatches, which is what keeps decode hosts' OPQ flag
    # audit free of prefill instructions). Shipped blocks carry exact cache
    # bits, so the continued stream is bit-identical to never having moved —
    # unlike re-prefill continuation, which remains the fallback oracle.

    @_placed
    def extract_seeded(self, req_id: int) -> Tuple[Request, Dict]:
        """Preempt an in-flight request AND export its slot's cache blocks
        as a serialized payload (store.export_blocks): the request's wire
        state plus exactly the bits a decode host needs to continue it
        without re-prefill. The payload id is cursor-named
        (``r<id>c<n_tokens>``) so a retried ship of the same cut is
        recognisable and never double-imports. The exported blocks stay on
        this host's export ledger — still counted as referenced — until
        ``release_exported`` acks the ship, so a failed ship falls back to
        re-prefill without having freed blocks a retry might still frame."""
        if self.ecfg.speculative:
            raise ValueError(
                "extract_seeded does not support speculative engines (the "
                "draft store's state cannot ship with the target's blocks)")
        if not hasattr(self.store, "export_blocks"):
            raise ValueError(
                f"extract_seeded requires the paged cache backend "
                f"(cross-host block shipping), got {self.store.kind!r} — "
                f"use preempt + re-prefill continuation instead")
        for slot, req in self.scheduler.active.items():
            if req.id == req_id:
                payload = self.store.export_blocks(
                    slot, payload_id=f"r{req.id}c{len(req.tokens)}")
                self.scheduler.retire(slot)
                self._presence[slot, :] = False
                self._unwind_prefix(slot)
                req.state = RequestState.PREEMPTED
                self.metrics.exported_slots += 1
                self.metrics.exported_blocks += payload["n_blocks"]
                return req, payload
        raise KeyError(f"request {req_id} is not in flight on this engine")

    @_placed
    def release_exported(self, payload_id: str) -> bool:
        """Ack a shipped payload: release the export ledger's hold on its
        blocks (refcount-correct — trie-cached blocks stay cached, private
        ones scrub free). Idempotent; False when the id is unknown or
        already acked."""
        return self.store.release_exported(payload_id)

    @_placed
    def submit_seeded(self, prompt: Sequence[int], max_new_tokens: int,
                      tokens: Sequence[int], payload: Dict,
                      *, sampling: Optional[SamplingParams] = None,
                      stop_history: Sequence[int] = (),
                      want_logprobs: Optional[int] = None,
                      logprobs: Sequence[float] = (),
                      top_logprobs: Sequence = ()) -> Optional[Request]:
        """Admit a mid-flight stream straight into the in-flight batch from
        a shipped block payload: lease a slot, import the payload's cache
        bits into it (validated in full BEFORE any device write — a corrupt
        payload raises ValueError with the slot left clean), and join the
        next decode step. No prefill is dispatched at all.

        ``tokens`` is the stream's generated-so-far suffix (>= 1 — the last
        token is what the next decode step feeds); ``max_new_tokens`` the
        ORIGINAL budget, which ``len(tokens)`` already counts against.
        Returns None when no slot is free or the lease is refused — the
        router's cue to fall back to re-prefill continuation."""
        if self.ecfg.speculative:
            raise ValueError(
                "submit_seeded does not support speculative engines (no "
                "draft-store payload ships with the target's blocks)")
        if not hasattr(self.store, "import_blocks"):
            raise ValueError(
                f"submit_seeded requires the paged cache backend "
                f"(cross-host block shipping), got {self.store.kind!r}")
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError(
                "submit_seeded needs >= 1 generated token (the decode step "
                "feeds the stream's last emitted token)")
        if len(tokens) >= max_new_tokens:
            raise ValueError(
                f"stream already finished: {len(tokens)} generated tokens "
                f">= max_new_tokens {max_new_tokens} — nothing to decode")
        if len(prompt) + max_new_tokens > self.ecfg.max_seq_len:
            raise ValueError(
                f"prompt={len(prompt)} + gen={max_new_tokens} exceeds "
                f"max_seq_len {self.ecfg.max_seq_len}")
        if not self.scheduler.free:
            return None
        slot = self.scheduler.free[-1]
        # a plain lease (no prompt-token trie walk): imported blocks stay
        # PRIVATE to this slot — they never register in the radix trie,
        # because their content hash belongs to the shipping host's cache
        if not self.store.lease(slot, len(prompt), max_new_tokens):
            return None
        try:
            self.store.import_blocks(slot, payload)
        except Exception:
            self.store.reset(slot)
            raise
        req = Request(id=next(self._req_ids), prompt=prompt,
                      max_new_tokens=max_new_tokens,
                      state=RequestState.RUNNING, tokens=tokens,
                      sampling=sampling, stop_history=tuple(stop_history),
                      want_logprobs=want_logprobs,
                      metrics=RequestMetrics(arrival_s=now(),
                                             prompt_len=len(prompt)))
        req.logprobs = [float(v) for v in logprobs]
        req.top_logprobs = [[(int(t), float(v)) for t, v in row]
                            for row in top_logprobs]
        # keep token_ts index-aligned with tokens: the seeded prefix was
        # emitted (and harvested) on the shipping host, so its entries are
        # placeholders behind every caller's cursor
        req.token_ts = [now()] * len(tokens)
        self.scheduler.admit_seeded(req)
        t = now()
        req.metrics.admitted_s = t
        req.metrics.first_token_s = t
        req.metrics.n_generated = len(tokens)
        self._presence[slot, :] = False
        self._presence[slot, prompt] = True
        self._presence[slot, tokens] = True
        self.metrics.submitted += 1
        self.metrics.imported_slots += 1
        self.metrics.imported_blocks += payload["n_blocks"]
        return req

    @_placed
    def step(self) -> None:
        """One engine iteration (span ``engine.step``): join waiting requests
        into free slots (``engine.admit``), then one batched decode step for
        whatever is in flight (``engine.decode``)."""
        with span("engine.step"):
            with span("engine.admit"):
                admitted = self._admit()
            if (admitted == 0 and not self.scheduler.active
                    and self.scheduler.waiting):
                # zero-progress state: the queue head was deferred by the
                # store lease while NOTHING is in flight — no retire can
                # ever free capacity, so every further step would be an
                # identical no-op. (fits() should have bounced such a
                # request at submit; this guards the submit-time-reject vs
                # lease-time-defer line against drift, which previously
                # burned max_steps idle iterations.)
                head = self.scheduler.waiting[0]
                raise RuntimeError(
                    f"admission livelock: request {head.id} "
                    f"(prompt={len(head.prompt)} tok, "
                    f"max_new_tokens={head.max_new_tokens}) was deferred by "
                    f"the {self.store.kind} store's lease with zero active "
                    f"slots — no retire can free capacity for it; "
                    f"store: {self.store.memory_stats()}")
            # occupancy sampled before the decode's retires, so slots busy
            # this step count even when their request finishes in it
            n_active = self.scheduler.n_active
            if n_active:
                if self.ecfg.speculative:
                    self._spec_decode_once()
                else:
                    self._decode_once()
            self.metrics.observe_step(self.scheduler.queue_depth, n_active)

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def run_until_complete(self, max_steps: int = 100_000) -> List[Request]:
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")
        return self.completed

    # ------------------------------------------------------- non-generative

    @_placed
    def embed(self, prompt: Sequence[int]) -> Dict[str, np.ndarray]:
        """Non-generative forward for the serve API (embeddings /
        classification): one bucketed dispatch returning the prompt's
        last-position final-norm hidden state and its last-position logits
        row (padded vocab columns trimmed). Reuses the prefill bucketing so
        the number of compiled embed shapes is bounded like admission's, and
        goes through the same OPQ dispatch (flag ``embed/<bucket>``) —
        no slot is leased, nothing touches the cache."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("embed needs a non-empty prompt")
        bucket = bucket_for(len(prompt), self.scheduler.buckets)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(prompt)] = prompt
        last = np.asarray([len(prompt) - 1], np.int32)
        hid, row = self._dispatch(
            lambda p, t, li, fn=_jitted_embed(self.cfg): fn(p, t, li),
            self._params_buf, Buffer(toks, name=f"embed{bucket}"),
            Buffer(last), flags=f"embed/{bucket}")
        self.metrics.embed_requests += 1
        return {"embedding": np.asarray(hid)[0],
                "logits": np.asarray(row)[0, :self.cfg.vocab]}

    # --------------------------------------------------------------- summary

    def stats(self) -> Dict:
        out = dict(self.metrics.summary())
        out["cache"] = self.store.memory_stats()
        if self.opq is not None:
            out["opq"] = dict(self.opq.stats)
            # per-flag instruction counts: the dispatch-shape audit trail
            # (tests assert admission issues one prefill/<bucket> instruction
            # per admitted request and zero replay decodes)
            out["opq"]["flags"] = dict(self.opq.flag_counts)
        return out

    def close(self) -> None:
        if self._owns_opq and self.opq is not None:
            self.opq.shutdown()
