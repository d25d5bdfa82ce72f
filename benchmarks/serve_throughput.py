"""Serving throughput sweep: offered load vs sustained tok/s through the
continuous-batching engine (Jouppi et al.'s framing: a serving accelerator is
judged at its latency-bounded throughput, not peak batch FLOPs).

    PYTHONPATH=src python benchmarks/serve_throughput.py [--quantize serve] \
        [--cache-backend contiguous|paged] \
        [--paged-report reports/BENCH_paged.json]

Sweeps the arrival stagger (engine steps between request arrivals — smaller
stagger = higher offered load) and the slot count, and emits the CSV contract
of benchmarks/common.py: name,us_per_call,derived. ``us_per_call`` is the
microseconds per generated token (1e6 / sustained tok/s); ``derived`` carries
sustained tok/s, mean TTFT, and mean slot occupancy. ``--cache-backend``
selects the SlotStore backend the sweep runs through (serving/store.py).

``--paged-report PATH`` skips the sweep and runs the paged-vs-contiguous
memory cell instead: the same short-prompt mix served by both backends
(tokens asserted bit-identical), with the paged block pool sized BELOW the
contiguous footprint — the JSON records cache bytes per admitted concurrent
request for each backend and the admission-backpressure counters, the
regression record for reports/BENCH_paged.json and the CI artifact.

``--router-report PATH`` runs the multi-host cell instead: the same request
mix served through the Router at 1/2/4 hosts (sessions cycling so the
second lap of arrivals pins by cache affinity), with a mid-run drain of
host 0 on every multi-host cell — tokens asserted bit-identical to the
single-engine run across the drain/handoff — recording wall-clock fleet
throughput, affinity hits, spills, and handoff counts per host count: the
regression record for reports/BENCH_router.json and the CI artifact.

``--prefix-report PATH`` runs the shared-prefix radix-cache cell instead:
the same request count served at 0% / 50% / 90% shared-prefix traffic
through a prefix-cache engine (tokens asserted bit-identical to a
prefix-cache-OFF paged engine at every share), recording per-cell TTFT and
the prefill work actually dispatched (block-size chunk units — cached
chunks are leased by refcount and skipped). Prefill dispatches are asserted
strictly decreasing as the share rises: the regression record for
reports/BENCH_prefix.json and the CI artifact.

``--spec-report PATH`` runs the speculative-decoding cell instead: the same
request mix served plain and with draft-verify decode at each ``--spec-k``
(draft == target, the full-acceptance ceiling), hard-asserting the token
streams bit-identical to plain greedy, mean accepted length > 1, and target
decode-path dispatches per emitted token strictly < 1.0 — recording tok/s
vs plain and the accepted-length histogram: the regression record for
reports/BENCH_spec.json and the CI artifact.

``--transport-report PATH`` runs the transport cell instead: the raw RPC
round-trip and per-decode-step overhead of the subprocess backend (framed
RPC over an AF_UNIX socket, workers rebuilding bit-identical weights from
the model spec) against the in-process backend serving the same mix —
tokens asserted bit-identical across the process boundary — plus fleet
throughput at 1/2/4 worker processes and the recovery timeline after a
hard SIGKILL of one worker mid-decode (loss detection, first re-placed
token, full drain): the regression record for reports/BENCH_transport.json
and the CI artifact.

``--disagg-report PATH`` runs the prefill/decode disaggregation cell
instead: a bimodal mix (short interactive prompts decoding while long
batch prompts keep arriving) served by a two-worker-process fleet without
roles and again split ``prefill:1,decode:1`` — streams ship their exact
KV blocks to the decode host once past the handoff threshold — recording
the interactive streams' p50/p99 inter-token gap in each mode, with
tokens hard-asserted bit-identical to a single engine in both modes (and
again in-process for int8-KV, whose dequant scales travel inside the
shipped payloads) and ZERO prefill instructions dispatched on the decode
host (OPQ flag audit): the regression record for
reports/BENCH_disagg.json and the CI artifact.

``--sampling-report PATH`` runs the sampling-engine cell instead: the same
request mix served all-greedy and all-sampled (temperature/top-k/top-p,
per-request seeds) through the ONE shared executable, recording the
per-decode-step sampler overhead; a seeded request's stream is hard-asserted
bit-identical alone vs inside mixed traffic vs on the paged backend (the
batch-invariance claim), and streaming TTFT is measured from HTTP POST to
the first SSE token event through serving/api.py next to the engine-loop
TTFT: the regression record for reports/BENCH_sampling.json and the CI
artifact.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import numpy as np

from repro.configs import get_config
from repro.core import tensorizer as tz
from repro.distributed import sharding as shd
from repro.launch.serve import _quant_predicate
from repro.models import init_model
from repro.serving.engine import Engine, EngineConfig
from repro.serving.router import Router, RouterConfig

from common import emit


def run_cell(cfg, params, *, slots: int, stagger: int, n_requests: int,
             prompt_len: int, gen: int, backend: str = "auto",
             block_size: int = 16, n_blocks=None, max_seq_len=None,
             paged_native=False, prefill_chunk=None, buckets=None):
    engine = Engine(cfg, params, EngineConfig(
        max_slots=slots, max_queue=n_requests,
        max_seq_len=max_seq_len or (prompt_len + gen), cache_backend=backend,
        block_size=block_size, n_blocks=n_blocks, paged_native=paged_native,
        prefill_chunk=prefill_chunk, buckets=buckets))
    rng = np.random.default_rng(0)
    reqs = []
    for _ in range(n_requests):
        reqs.append(engine.submit(
            rng.integers(0, cfg.vocab, (prompt_len,), dtype=np.int32), gen,
            strict=True))
        for _ in range(stagger):
            engine.step()
    engine.run_until_complete()
    s = engine.stats()
    ttft_ms = 1e3 * float(np.mean([r.metrics.ttft_s for r in reqs]))
    toks = [list(r.tokens) for r in reqs]
    engine.close()
    return s, ttft_ms, toks


def paged_memory_report(cfg, params, *, slots: int, prompt_len: int, gen: int,
                        block_size: int, out_path: str) -> dict:
    """The paged-KV memory claim, measured: serve one short-prompt mix through
    both backends under the same per-slot sequence BUDGET (``max_seq``, 4x the
    requests' true length — the headroom a production engine must offer), with
    the paged pool sized to the mix's true footprint. The contiguous backend
    reserves full max_seq rows per slot — a footprint that exceeds the whole
    paged pool — while paged leases only ceil((prompt+gen)/block) blocks per
    request, so it serves strictly more concurrent short requests per byte.
    Token streams are asserted bit-identical, so the bytes saved cost zero
    output fidelity."""
    req_len = prompt_len + gen
    max_seq = 4 * req_len                  # the budget slots must offer
    n_requests = 2 * slots
    blocks_per_req = -(-req_len // block_size)
    # pool: exactly the blocks the admitted short-request concurrency needs
    # (+ the reserved null block) — well under slots x max_seq rows
    n_blocks = slots * blocks_per_req + 1

    s_c, ttft_c, toks_c = run_cell(
        cfg, params, slots=slots, stagger=0, n_requests=n_requests,
        prompt_len=prompt_len, gen=gen, backend="contiguous",
        max_seq_len=max_seq)
    s_p, ttft_p, toks_p = run_cell(
        cfg, params, slots=slots, stagger=0, n_requests=n_requests,
        prompt_len=prompt_len, gen=gen, backend="paged",
        block_size=block_size, n_blocks=n_blocks, max_seq_len=max_seq)
    assert toks_c == toks_p, "paged decode diverged from contiguous"

    bytes_c = s_c["cache"]["bytes"]
    bytes_p = s_p["cache"]["bytes"]
    report = {
        "benchmark": "paged_kv_memory",
        "arch": cfg.name,
        "kv_cache_dtype": cfg.kv_cache_dtype,
        "slots": slots,
        "prompt_len": prompt_len,
        "gen": gen,
        "max_seq_len": max_seq,
        "block_size": block_size,
        "n_blocks": n_blocks,
        "requests": n_requests,
        "bit_identical_tokens": True,
        "contiguous": {
            "cache_bytes": bytes_c,
            "bytes_per_admitted_request": bytes_c // slots,
            "ttft_ms": ttft_c,
            "sustained_tok_s": s_c["sustained_tok_s"],
        },
        "paged": {
            "cache_bytes": bytes_p,
            "bytes_per_admitted_request": bytes_p // slots,
            # per-step transient contiguous view (the bit-identity gather
            # bridge) — the peak decode working set is cache + view, so the
            # byte saving is in the RESIDENT allocation, not the step peak
            "decode_view_bytes": s_p["cache"]["decode_view_bytes"],
            "ttft_ms": ttft_p,
            "sustained_tok_s": s_p["sustained_tok_s"],
            "admissions_deferred": s_p["admissions_deferred"],
            "blocks_total": s_p["cache"]["blocks_total"],
        },
        "paged_over_contiguous_bytes": bytes_p / bytes_c,
        # the headline: concurrent admitted requests a byte of cache buys
        "requests_per_mib_contiguous": slots / (bytes_c / 2**20),
        "requests_per_mib_paged": slots / (bytes_p / 2**20),
    }
    emit("paged_kv_bytes_per_req", report["paged"]["bytes_per_admitted_request"],
         f"contiguous={report['contiguous']['bytes_per_admitted_request']}B "
         f"ratio={report['paged_over_contiguous_bytes']:.2f}")
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# paged {bytes_p}B vs contiguous {bytes_c}B "
          f"({report['paged_over_contiguous_bytes']:.2f}x) for the same "
          f"admitted concurrency, tokens bit-identical")
    print(f"# wrote {out_path}")
    return report


def paged_native_report(cfg, params, *, slots: int, prompt_len: int, gen: int,
                        block_size: int, chunk: int, long_prompt: int,
                        out_path: str) -> dict:
    """The block-native claim, measured: (1) the same short-prompt mix served
    through the paged gather-bridge and the block-native decode — tokens
    asserted bit-identical — recording each mode's PEAK decode working set.
    The bridge's peak is pool + the all-layer gather view; native mode's
    store-level view is gone (decode_view_bytes == 0), but the jnp
    block-native path still gathers ONE layer's rows transiently inside the
    layer scan (view_bytes / n_layers), so its honest peak is pool +
    per-layer gather; only the Pallas kernel path (paged_kernel=True) works
    from block-sized VMEM tiles alone, reported as kernel_peak_decode_bytes.
    (2) A long prompt (wider than every fused bucket) admitted via the
    chunked prefill, recording its TTFT against single-shot fused admission
    of the same prompt (tokens asserted bit-identical) and the peak prefill
    score-matrix bytes each mode materializes (B*H*S*S f32 single-shot vs
    B*H*chunk*S chunked — the quadratic term that caps admissible prompt
    length)."""
    req_len = prompt_len + gen
    n_requests = 2 * slots

    s_b, ttft_b, toks_b = run_cell(
        cfg, params, slots=slots, stagger=0, n_requests=n_requests,
        prompt_len=prompt_len, gen=gen, backend="paged",
        block_size=block_size)
    s_n, ttft_n, toks_n = run_cell(
        cfg, params, slots=slots, stagger=0, n_requests=n_requests,
        prompt_len=prompt_len, gen=gen, backend="paged",
        block_size=block_size, paged_native=True)
    assert toks_b == toks_n, "block-native decode diverged from gather bridge"
    assert s_n["cache"]["decode_view_bytes"] == 0

    # long-prompt admission: fused buckets capped at `chunk`, so the long
    # prompt can only enter through the chunked path
    long_seq = long_prompt + gen
    s_lc, ttft_lc, toks_lc = run_cell(
        cfg, params, slots=1, stagger=0, n_requests=1,
        prompt_len=long_prompt, gen=gen, max_seq_len=long_seq,
        buckets=(chunk,), prefill_chunk=chunk)
    s_lf, ttft_lf, toks_lf = run_cell(
        cfg, params, slots=1, stagger=0, n_requests=1,
        prompt_len=long_prompt, gen=gen, max_seq_len=long_seq)
    assert toks_lc == toks_lf, "chunked prefill diverged from single-shot"

    import math
    from repro.serving import bucket_for, default_buckets
    bucket = math.ceil(long_prompt / chunk) * chunk          # chunked engine
    fused_bucket = bucket_for(long_prompt, default_buckets(long_seq))
    score_fused = 4 * cfg.n_heads * fused_bucket * fused_bucket  # B=1, f32
    score_chunked = 4 * cfg.n_heads * chunk * bucket
    report = {
        "benchmark": "paged_native",
        "arch": cfg.name,
        "kv_cache_dtype": cfg.kv_cache_dtype,
        "slots": slots,
        "prompt_len": prompt_len,
        "gen": gen,
        "block_size": block_size,
        "requests": n_requests,
        "bit_identical_tokens": True,
        "decode": {
            "bridge": {
                "pool_bytes": s_b["cache"]["bytes"],
                "decode_view_bytes": s_b["cache"]["decode_view_bytes"],
                "peak_decode_bytes": (s_b["cache"]["bytes"]
                                      + s_b["cache"]["decode_view_bytes"]),
                "ttft_ms": ttft_b,
                "sustained_tok_s": s_b["sustained_tok_s"],
            },
            "native": {
                "pool_bytes": s_n["cache"]["bytes"],
                "decode_view_bytes": 0,
                # the jnp block-native path gathers one layer's rows
                # transiently inside the layer scan
                "per_layer_gather_bytes":
                    s_b["cache"]["decode_view_bytes"] // cfg.n_layers,
                "peak_decode_bytes": (
                    s_n["cache"]["bytes"]
                    + s_b["cache"]["decode_view_bytes"] // cfg.n_layers),
                # the Pallas kernel path holds only block-sized VMEM tiles
                "kernel_peak_decode_bytes": s_n["cache"]["bytes"],
                "ttft_ms": ttft_n,
                "sustained_tok_s": s_n["sustained_tok_s"],
                "table_uploads": s_n["cache"]["table_uploads"],
            },
            "native_over_bridge_peak_bytes": (
                (s_n["cache"]["bytes"]
                 + s_b["cache"]["decode_view_bytes"] // cfg.n_layers)
                / (s_b["cache"]["bytes"] + s_b["cache"]["decode_view_bytes"])),
        },
        "long_prompt": {
            "prompt_len": long_prompt,
            "prefill_chunk": chunk,
            "bucket": bucket,
            "fused": {"ttft_ms": ttft_lf,
                      "bucket": fused_bucket,
                      "score_matrix_bytes": score_fused},
            "chunked": {"ttft_ms": ttft_lc,
                        "score_matrix_bytes": score_chunked},
            "score_bytes_ratio": score_chunked / score_fused,
        },
    }
    emit("paged_native_peak_decode_bytes",
         report["decode"]["native"]["peak_decode_bytes"],
         f"bridge_peak={report['decode']['bridge']['peak_decode_bytes']}B "
         f"ratio={report['decode']['native_over_bridge_peak_bytes']:.2f}")
    emit("chunked_prefill_score_bytes",
         score_chunked,
         f"fused={score_fused}B ratio={report['long_prompt']['score_bytes_ratio']:.3f} "
         f"ttft_chunked={ttft_lc:.0f}ms ttft_fused={ttft_lf:.0f}ms")
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# native peak decode {report['decode']['native']['peak_decode_bytes']}B "
          f"vs bridge {report['decode']['bridge']['peak_decode_bytes']}B "
          f"({report['decode']['native_over_bridge_peak_bytes']:.2f}x), "
          f"tokens bit-identical; long-prompt score matrix "
          f"{score_chunked}B vs {score_fused}B")
    print(f"# wrote {out_path}")
    return report


def router_report(cfg, params, *, hosts_swept=(1, 2, 4), slots: int,
                  prompt_len: int, gen: int, requests: int, drain_at: int,
                  out_path: str) -> dict:
    """The multi-host claim, measured: one request mix served through the
    Router at increasing host counts, sessions cycling over the host count
    so the second lap of arrivals pins to the host already holding that
    session's blocks. Every multi-host cell drains host 0 mid-run — its
    queued work re-places and its long in-flight generations hand off — and
    each cell's stitched token streams are asserted bit-identical to the
    1-host run, so scale-out and elastic restarts cost zero output
    fidelity. Records wall-clock fleet tok/s and the placement ledger
    (affinity hits / spills / handoffs) per host count."""
    import time

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (prompt_len,), dtype=np.int32)
               for _ in range(requests)]

    # warmup: compile the prefill/decode executables (shared across every
    # cell's engines via the engine step cache) so cells measure serving,
    # not XLA
    warm = Router(cfg, params, EngineConfig(
        max_slots=slots, max_queue=requests,
        max_seq_len=prompt_len + gen), RouterConfig(n_hosts=1))
    for p in prompts[:2]:
        warm.submit(p, gen, strict=True)
        warm.step()
    warm.run_until_complete()
    warm.close()

    cells = []
    baseline_tokens = None
    for n_hosts in hosts_swept:
        router = Router(cfg, params, EngineConfig(
            max_slots=slots, max_queue=requests,
            max_seq_len=prompt_len + gen),
            RouterConfig(n_hosts=n_hosts, handoff_threshold=0))
        fleet_steps = 0
        t0 = time.perf_counter()
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(router.submit(p, gen, session=str(i % n_hosts),
                                      strict=True))
            router.step()
            fleet_steps += 1
            if n_hosts > 1 and fleet_steps == drain_at:
                router.drain(0)
        while router.has_work():
            router.step()
            fleet_steps += 1
            if n_hosts > 1 and fleet_steps == drain_at:
                router.drain(0)
        wall_s = time.perf_counter() - t0
        toks = [list(r.tokens) for r in reqs]
        if baseline_tokens is None:
            baseline_tokens = toks
        else:
            assert toks == baseline_tokens, (
                f"{n_hosts}-host tokens diverged from single-host "
                f"(drain at step {drain_at})")
        s = router.stats()
        r = s["router"]
        cells.append({
            "hosts": n_hosts,
            "drained_host": 0 if n_hosts > 1 else None,
            "drain_at_step": drain_at if n_hosts > 1 else None,
            "wall_s": wall_s,
            "fleet_tok_s": requests * gen / wall_s,
            "placed": r["placed"],
            "affinity_hits": r["affinity_hits"],
            "spills": r["spills"],
            "handoffs": r["handoffs"],
            "requeued": r["requeued"],
            "completed_per_host": [h["completed"] for h in s["per_host"]],
            "preempted_per_host": [h["preempted"] for h in s["per_host"]],
        })
        router.close()

    report = {
        "benchmark": "router_multi_host",
        "arch": cfg.name,
        "kv_cache_dtype": cfg.kv_cache_dtype,
        "slots_per_host": slots,
        "prompt_len": prompt_len,
        "gen": gen,
        "requests": requests,
        "bit_identical_tokens": True,
        "cells": cells,
    }
    base = cells[0]["fleet_tok_s"]
    for c in cells:
        emit(f"router_h{c['hosts']}", 1e6 / max(c["fleet_tok_s"], 1e-9),
             f"fleet={c['fleet_tok_s']:.1f}tok/s "
             f"speedup={c['fleet_tok_s'] / base:.2f}x "
             f"affinity={c['affinity_hits']} spills={c['spills']} "
             f"handoffs={c['handoffs']}")
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# router: {len(cells)} host-count cells, tokens bit-identical "
          f"across scale-out AND a mid-run drain/handoff on every "
          f"multi-host cell")
    print(f"# wrote {out_path}")
    return report


def prefix_report(cfg, params, *, prompt_len: int, gen: int, block_size: int,
                  requests: int, out_path: str) -> dict:
    """The shared-prefix claim, measured: the same request count served at
    0% / 50% / 90% shared-prefix traffic through a prefix-cache engine. At
    each share the first request is cold (it populates the radix trie); the
    rest lease the cached preamble blocks by refcount and run chunked
    prefill only over the suffix, so the prefill work actually dispatched —
    counted in block-size chunk units — drops as the share rises, and TTFT
    drops with it. Every cell's token streams are asserted bit-identical to
    a prefix-cache-OFF paged engine serving the same prompts: the reused
    cache bits cost zero output fidelity. Chunk units are hard-asserted
    strictly decreasing across shares; TTFT is recorded but not asserted
    (wall-clock on shared CI is noisy)."""
    import time

    rng = np.random.default_rng(0)
    max_seq = prompt_len + gen
    bps = max_seq // block_size

    def make_prompts(share_pct):
        shared = int(round(prompt_len * share_pct / 100.0))
        preamble = rng.integers(0, cfg.vocab, (shared,), dtype=np.int32)
        return [np.concatenate([
            preamble,
            rng.integers(0, cfg.vocab, (prompt_len - shared,),
                         dtype=np.int32)]) for _ in range(requests)]

    def make_engine(prefix):
        return Engine(cfg, params, EngineConfig(
            max_slots=2, max_queue=requests, max_seq_len=max_seq,
            cache_backend="paged", block_size=block_size,
            n_blocks=3 * bps + 1, prefix_cache=prefix))

    # warmup: compile the fused-prefill, suffix-prefill and decode
    # executables (shared across every cell's engines via the engine step
    # cache) so cells measure serving, not XLA
    warm = make_engine(True)
    for p in make_prompts(90)[:2]:
        warm.submit(p, gen, strict=True)
        warm.run_until_complete()
    warm.close()

    cells = []
    prev_dispatch = None
    for share in (0, 50, 90):
        prompts = make_prompts(share)
        hot = make_engine(True)
        cold = make_engine(False)
        toks_hot, toks_cold, ttfts = [], [], []
        chunks_after_first = 0
        for i, p in enumerate(prompts):
            rh = hot.submit(p, gen, strict=True)
            hot.run_until_complete()
            rc = cold.submit(p, gen, strict=True)
            cold.run_until_complete()
            toks_hot.append(list(rh.tokens))
            toks_cold.append(list(rc.tokens))
            ttfts.append(rh.metrics.ttft_s)
            if i == 0:
                chunks_after_first = hot.metrics.prefill_chunks
        assert toks_hot == toks_cold, (
            f"prefix-hit tokens diverged from prefix-cache-off serving at "
            f"{share}% shared-prefix traffic")
        s = hot.stats()
        # prefill work per WARM request (requests 2..N — request 1 always
        # pays the cold full-prompt prefill that populates the trie)
        dispatch = ((s["prefill_chunks"] - chunks_after_first)
                    / (requests - 1))
        if prev_dispatch is not None:
            assert dispatch < prev_dispatch, (
                f"prefill dispatches did not drop as shared-prefix share "
                f"rose to {share}%: {dispatch} >= {prev_dispatch}")
        prev_dispatch = dispatch
        cells.append({
            "share_pct": share,
            "shared_prefix_tokens": int(round(prompt_len * share / 100.0)),
            "prefill_chunk_units_per_warm_request": dispatch,
            "prefill_chunk_units_total": s["prefill_chunks"],
            "prefix_hits": s["prefix_hits"],
            "prefix_blocks_reused": s["prefix_blocks_reused"],
            "prefix_tokens_reused": s["prefix_tokens_reused"],
            "cow_forks": s["cache"]["cow_forks"],
            "prefix_evictions": s["cache"]["prefix_evictions"],
            "cold_ttft_ms": 1e3 * ttfts[0],
            "warm_ttft_ms": 1e3 * float(np.mean(ttfts[1:])),
        })
        hot.close()
        cold.close()

    report = {
        "benchmark": "prefix_cache",
        "arch": cfg.name,
        "kv_cache_dtype": cfg.kv_cache_dtype,
        "block_size": block_size,
        "prompt_len": prompt_len,
        "gen": gen,
        "requests": requests,
        "bit_identical_tokens": True,
        "cells": cells,
    }
    for c in cells:
        emit(f"prefix_s{c['share_pct']}", 1e3 * c["warm_ttft_ms"],
             f"chunks/warm-req={c['prefill_chunk_units_per_warm_request']:.1f} "
             f"hits={c['prefix_hits']} reused={c['prefix_blocks_reused']} "
             f"ttft={c['warm_ttft_ms']:.1f}ms")
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    trend = " -> ".join(
        f"{c['prefill_chunk_units_per_warm_request']:.1f}" for c in cells)
    print(f"# prefix: chunk units per warm request {trend} across shares "
          f"0/50/90%, tokens bit-identical to prefix-cache-off")
    print(f"# wrote {out_path}")
    return report


def spec_report(cfg, params, *, slots: int, prompt_len: int, gen: int,
                requests: int, spec_ks=(2, 4), out_path: str) -> dict:
    """The speculative-decoding claim, measured: the same request mix served
    plain and with draft-verify decode at each ``spec_k``. The draft is the
    TARGET model itself (same config, same weights): the full-acceptance
    ceiling, which makes the mechanism measurable without a second trained
    checkpoint — every verify round advances each slot by the whole window,
    so target decode-path dispatches per emitted token land at their floor
    ~1/(k+1). Token streams are hard-asserted bit-identical to plain greedy
    decode, mean accepted length is hard-asserted > 1, and dispatched target
    steps per decode token hard-asserted strictly < 1.0. Wall-clock tok/s is
    recorded vs plain but NOT asserted: with a draft as large as the target
    the (k+1) narrow draft forwards cost what they save — a deployment's
    draft is far smaller, and the dispatch-count reduction is the claim."""
    import time

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (prompt_len,), dtype=np.int32)
               for _ in range(requests)]
    base = dict(max_slots=slots, max_queue=requests,
                max_seq_len=prompt_len + gen)

    def serve(ecfg, dparams=None):
        eng = Engine(cfg, params, ecfg, draft_params=dparams)
        t0 = time.perf_counter()
        reqs = [eng.submit(p, gen, strict=True) for p in prompts]
        eng.run_until_complete()
        wall_s = time.perf_counter() - t0
        s = eng.stats()
        toks = [list(r.tokens) for r in reqs]
        eng.close()
        return s, wall_s, toks

    # warmup: compile the prefill/decode/draft/verify executables for every
    # spec_k (distinct window widths), so cells measure serving, not XLA
    serve(EngineConfig(**base))
    for k in spec_ks:
        serve(EngineConfig(**base, speculative=True, spec_k=k, draft=cfg),
              dparams=params)

    s_p, wall_p, toks_p = serve(EngineConfig(**base))
    decoded_p = s_p["tokens_generated"] - s_p["completed"]
    cells = []
    for k in spec_ks:
        s, wall_s, toks = serve(
            EngineConfig(**base, speculative=True, spec_k=k, draft=cfg),
            dparams=params)
        assert toks == toks_p, (
            f"speculative decode (spec_k={k}) diverged from plain greedy")
        decoded = s["tokens_generated"] - s["completed"]
        slot_rounds = sum(s["accept_hist"].values())
        mean_acc = decoded / slot_rounds
        spt = s["decode_steps"] / decoded
        assert mean_acc > 1.0, (
            f"mean accepted length {mean_acc:.2f} <= 1 at spec_k={k}: "
            f"speculation bought nothing")
        assert spt < 1.0, (
            f"target decode steps per emitted token {spt:.2f} >= 1 at "
            f"spec_k={k}: more dispatches than plain decode")
        # batching already puts plain below 1 step/token, so also pin the
        # stronger claim: strictly fewer target dispatches than plain made
        # for the very same streams
        assert s["decode_steps"] < s_p["decode_steps"], (
            f"spec_k={k} dispatched {s['decode_steps']} target decode "
            f"steps, plain needed only {s_p['decode_steps']}")
        cells.append({
            "spec_k": k,
            "wall_s": wall_s,
            "sustained_tok_s": s["sustained_tok_s"],
            "tok_s_vs_plain": s["sustained_tok_s"]
                              / max(s_p["sustained_tok_s"], 1e-9),
            "decode_steps": s["decode_steps"],
            "spec_rounds": s["spec_rounds"],
            "draft_steps": s["draft_steps"],
            "proposed_tokens": s["proposed_tokens"],
            "accepted_tokens": s["accepted_tokens"],
            "acceptance_rate": s["acceptance_rate"],
            "mean_accepted_len": mean_acc,
            "steps_per_decode_token": spt,
            "accept_hist": {str(length): count
                            for length, count in s["accept_hist"].items()},
        })

    report = {
        "benchmark": "speculative_decode",
        "arch": cfg.name,
        "kv_cache_dtype": cfg.kv_cache_dtype,
        "slots": slots,
        "prompt_len": prompt_len,
        "gen": gen,
        "requests": requests,
        "draft": {"arch": cfg.name,
                  "note": "draft == target (full-acceptance ceiling)"},
        "bit_identical_tokens": True,
        "plain": {
            "wall_s": wall_p,
            "sustained_tok_s": s_p["sustained_tok_s"],
            "decode_steps": s_p["decode_steps"],
            "tokens_generated": s_p["tokens_generated"],
            "steps_per_decode_token": s_p["decode_steps"] / decoded_p,
        },
        "cells": cells,
    }
    for c in cells:
        emit(f"spec_k{c['spec_k']}",
             1e6 / max(c["sustained_tok_s"], 1e-9),
             f"steps/tok={c['steps_per_decode_token']:.2f} "
             f"mean_acc={c['mean_accepted_len']:.2f} "
             f"accept={c['acceptance_rate']:.2f} "
             f"vs_plain={c['tok_s_vs_plain']:.2f}x")
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    trend = " ".join(f"k={c['spec_k']}:{c['steps_per_decode_token']:.2f}"
                     for c in cells)
    print(f"# speculative: target steps per decode token {trend} "
          f"(plain {report['plain']['steps_per_decode_token']:.2f}), "
          f"tokens bit-identical to plain greedy")
    print(f"# wrote {out_path}")
    return report


def sampling_report(cfg, params, *, slots: int, prompt_len: int, gen: int,
                    requests: int, out_path: str) -> dict:
    """The sampling-engine claims, measured: (1) per-decode-step overhead of
    the batched sampler vs pure greedy traffic — both mixes run the SAME
    executable (masked param application), so the cost is the sampler math,
    not a second program; (2) batch invariance, hard-asserted on tokens: one
    seeded request decodes alone, inside mixed traffic, and on the paged
    backend — three bit-identical streams or the report dies; (3) streaming
    TTFT — wall time from HTTP POST to the first SSE token event through
    serving/api.py, next to the engine-loop TTFT the CLI path records."""
    import http.client
    import time

    from repro.serving.api import serve_api
    from repro.serving.sampling import SamplingParams

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (prompt_len,), dtype=np.int32)
               for _ in range(requests)]
    base = dict(max_slots=slots, max_queue=requests,
                max_seq_len=prompt_len + gen)
    sampled_sp = [SamplingParams(temperature=0.8, top_k=20, top_p=0.95,
                                 seed=1000 + i) for i in range(requests)]

    def serve(sampling_for, ecfg_kw=None):
        eng = Engine(cfg, params, EngineConfig(**base, **(ecfg_kw or {})))
        t0 = time.perf_counter()
        reqs = [eng.submit(p, gen, sampling=sampling_for(i), strict=True)
                for i, p in enumerate(prompts)]
        eng.run_until_complete()
        wall_s = time.perf_counter() - t0
        s = eng.stats()
        toks = [list(r.tokens) for r in reqs]
        eng.close()
        return s, wall_s, toks

    # warmup compiles the shared executable and both prefill buckets; the
    # sampled warmup also pays the one-off sampler trace
    serve(lambda i: None)
    serve(lambda i: sampled_sp[i])
    serve(lambda i: sampled_sp[i], dict(cache_backend="paged", block_size=8))

    def decode_us(s, wall_s):
        # decode-path wall only: prefill forwards and cache-seed writes are
        # admission cost, identical across the two mixes
        decode_s = wall_s - s["prefill_wait_s"] - s["seed_write_s"]
        return 1e6 * decode_s / max(s["decode_steps"], 1)

    s_g, wall_g, _ = serve(lambda i: None)
    s_s, wall_s_, toks_mixed_base = serve(lambda i: sampled_sp[i])
    us_greedy = decode_us(s_g, wall_g)
    us_sampled = decode_us(s_s, wall_s_)

    # --- batch invariance, asserted on tokens --------------------------
    def solo(ecfg_kw=None):
        eng = Engine(cfg, params, EngineConfig(**base, **(ecfg_kw or {})))
        req = eng.submit(prompts[0], gen, sampling=sampled_sp[0], strict=True)
        eng.run_until_complete()
        out = list(req.tokens)
        eng.close()
        return out

    alone = solo()
    assert toks_mixed_base[0] == alone, (
        "seeded stream changed with batchmates: sampling is not "
        "batch-invariant")
    assert solo(dict(cache_backend="paged", block_size=8)) == alone, (
        "seeded stream changed across cache backends")
    _, _, toks_paged = serve(lambda i: sampled_sp[i],
                             dict(cache_backend="paged", block_size=8))
    assert toks_paged == toks_mixed_base, (
        "sampled batch diverged between contiguous and paged backends")

    # --- streaming TTFT over HTTP vs the engine-loop TTFT ---------------
    eng = Engine(cfg, params, EngineConfig(**base))
    req = eng.submit(prompts[0], gen, strict=True)
    eng.run_until_complete()
    cli_ttft_ms = 1e3 * req.metrics.ttft_s
    eng.close()

    eng = Engine(cfg, params, EngineConfig(**base))
    srv = serve_api(eng, port=0)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
        t0 = time.perf_counter()
        conn.request("POST", "/v1/completions",
                     body=json.dumps({
                         "prompt": [int(t) for t in prompts[0]],
                         "max_new_tokens": gen, "stream": True}).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        http_ttft_ms = None
        for raw in resp.fp:
            line = raw.decode().strip()
            if line.startswith("data: ") and "token" in line:
                http_ttft_ms = 1e3 * (time.perf_counter() - t0)
                break
        conn.close()
        assert http_ttft_ms is not None, "no SSE token event arrived"
    finally:
        srv.close()
        eng.close()

    report = {
        "benchmark": "sampling",
        "arch": cfg.name,
        "slots": slots,
        "prompt_len": prompt_len,
        "gen": gen,
        "requests": requests,
        "params": {"temperature": 0.8, "top_k": 20, "top_p": 0.95},
        "batch_invariant": True,        # hard-asserted above, or we died
        "greedy": {
            "wall_s": wall_g,
            "decode_us_per_step": us_greedy,
            "sustained_tok_s": s_g["sustained_tok_s"],
        },
        "sampled": {
            "wall_s": wall_s_,
            "decode_us_per_step": us_sampled,
            "sustained_tok_s": s_s["sustained_tok_s"],
            "sampled_tokens": s_s["sampled_tokens"],
        },
        "sampling_overhead_pct": 100.0 * (us_sampled - us_greedy)
                                 / max(us_greedy, 1e-9),
        "streaming": {
            "http_ttft_ms": http_ttft_ms,
            "cli_ttft_ms": cli_ttft_ms,
        },
    }
    emit("sample_greedy", us_greedy,
         f"tok/s={s_g['sustained_tok_s']:.1f}")
    emit("sample_full", us_sampled,
         f"tok/s={s_s['sustained_tok_s']:.1f} "
         f"overhead={report['sampling_overhead_pct']:.1f}%")
    emit("stream_ttft", 1e3 * http_ttft_ms,
         f"http={http_ttft_ms:.1f}ms cli={cli_ttft_ms:.1f}ms")
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# sampling: {us_sampled:.0f}us vs {us_greedy:.0f}us greedy per "
          f"decode step ({report['sampling_overhead_pct']:+.1f}%), seeded "
          f"streams bit-identical across batchmates and backends, "
          f"HTTP TTFT {http_ttft_ms:.1f}ms vs CLI {cli_ttft_ms:.1f}ms")
    print(f"# wrote {out_path}")
    return report


def transport_report(cfg, params, *, arch: str, prompt_len: int, gen: int,
                     requests: int, hosts_swept=(1, 2, 4),
                     out_path: str) -> dict:
    """The transport claim, measured: (1) raw RPC round-trip — the same
    ``load`` call timed over the in-process backend (a method call) and the
    subprocess backend (a framed request over an AF_UNIX socket); (2) the
    same request mix served through one in-process host and one subprocess
    host — tokens asserted bit-identical, per-RPC and per-token overhead
    recorded from the TransportMetrics both backends share; (3) fleet
    throughput at 1/2/4 worker processes; (4) recovery after a hard SIGKILL
    of one worker mid-decode — time from the kill to the router marking the
    host LOST, to the first token of a re-placed continuation, and to the
    full mix completing. Workers rebuild bit-identical weights from the
    model spec, so no params cross the wire."""
    import os
    import signal
    import time

    from repro.serving.transport import (
        SubprocessTransport, build_inproc_fleet, build_model_spec,
        default_codec,
    )

    max_seq = prompt_len + gen
    ecfg = EngineConfig(max_slots=2, max_queue=max(requests, 8),
                        max_seq_len=max_seq)
    spec = build_model_spec(arch, smoke=True, seed=0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (prompt_len,), dtype=np.int32)
               for _ in range(requests)]

    def spawn(n, cfg_override=None):
        fleet = []
        try:
            for _ in range(n):
                fleet.append(SubprocessTransport(spec, cfg_override or ecfg))
        except Exception:
            for t in fleet:
                t.close()
            raise
        return fleet

    def warm(fleet):
        # every worker compiles its prefill/decode executables up front so
        # the cells measure serving + transport, not XLA
        for t in fleet:
            eid = t.submit(prompts[0][:4], 2)
            deadline = time.monotonic() + 300
            while not t.poll({eid: 0}).get(eid, {}).get("done"):
                assert time.monotonic() < deadline, "warmup never finished"
                if t.kind == "in-process":
                    t.pump()               # no worker process: we step
                else:
                    time.sleep(0.005)      # the worker free-runs
            t.poll({}, drop=[eid])

    def rpc_micro(t, n=300):
        for _ in range(20):
            t.load()                           # steady-state the path
        t0 = time.perf_counter()
        for _ in range(n):
            t.load()
        return 1e6 * (time.perf_counter() - t0) / n

    def serve(transports):
        router = Router(transports=transports)
        t0 = time.perf_counter()
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(router.submit(p, gen, session=str(i % len(transports)),
                                      strict=True))
            router.step()
        router.run_until_complete()
        wall_s = time.perf_counter() - t0
        toks = [list(r.tokens) for r in reqs]
        rows = router.stats()["router"]["transport"]
        router.close()
        return wall_s, toks, rows

    # --- (1)+(2): one in-process host vs one subprocess host ------------
    inproc = build_inproc_fleet(cfg, params, ecfg, n_hosts=1)
    warm(inproc)
    us_rpc_inproc = rpc_micro(inproc[0])
    wall_i, toks_i, rows_i = serve(inproc)

    sub = spawn(1)
    warm(sub)
    us_rpc_sub = rpc_micro(sub[0])
    wall_s_, toks_s, rows_s = serve(sub)
    assert toks_s == toks_i, (
        "subprocess host diverged from the in-process engine")

    n_toks = requests * gen

    def backend_cell(wall, rows, us_rpc):
        rpcs = sum(r["rpcs"] for r in rows)
        return {
            "wall_s": wall,
            "tok_s": n_toks / wall,
            "rpc_round_trip_us": us_rpc,
            "rpcs": rpcs,
            "rpcs_per_token": rpcs / n_toks,
            "rpc_wait_s": sum(r["rpc_wait_s"] for r in rows),
            "retries": sum(r["retries"] for r in rows),
            "errors": sum(r["errors"] for r in rows),
        }

    overhead = {
        "in_process": backend_cell(wall_i, rows_i, us_rpc_inproc),
        "subprocess": backend_cell(wall_s_, rows_s, us_rpc_sub),
        "bit_identical_tokens": True,
        "rpc_overhead_us": us_rpc_sub - us_rpc_inproc,
        "overhead_us_per_token": 1e6 * (wall_s_ - wall_i) / n_toks,
    }

    # --- (3): fleet throughput at 1/2/4 worker processes ----------------
    fleet_cells = []
    for n_hosts in hosts_swept:
        fleet = spawn(n_hosts)
        warm(fleet)
        wall, _, rows = serve(fleet)
        fleet_cells.append({
            "hosts": n_hosts,
            "wall_s": wall,
            "fleet_tok_s": n_toks / wall,
            "rpcs": sum(r["rpcs"] for r in rows),
            "rpc_wait_s": sum(r["rpc_wait_s"] for r in rows),
        })

    # --- (4): recovery after SIGKILL of one worker mid-decode -----------
    kill_gen = max(8 * gen, 128)
    kill_ecfg = EngineConfig(max_slots=2, max_queue=16,
                             max_seq_len=prompt_len + kill_gen)
    fleet = spawn(2, kill_ecfg)
    warm(fleet)
    router = Router(transports=fleet,
                    router_cfg=RouterConfig(handoff_threshold=0))
    reqs = [router.submit(prompts[i % requests], kill_gen,
                          session=str(i % 2), strict=True)
            for i in range(6)]
    victim = reqs[0].hosts[0]
    victim_reqs = [r for r in reqs if r.hosts[0] == victim]
    deadline = time.monotonic() + 120
    while not any(0 < len(r.tokens) < r.max_new_tokens for r in victim_reqs):
        router.step()
        assert time.monotonic() < deadline, "victim never got mid-decode"
    snap = [len(r.tokens) for r in victim_reqs]
    t_kill = time.perf_counter()
    os.kill(fleet[victim].pid, signal.SIGKILL)
    t_lost = t_first = None
    while router.has_work() and time.monotonic() < deadline:
        router.step()
        if t_lost is None and router.stats()["router"]["hosts_lost"]:
            t_lost = time.perf_counter() - t_kill
        if t_first is None and any(
                len(r.tokens) > s for r, s in zip(victim_reqs, snap)):
            t_first = time.perf_counter() - t_kill
            break
    router.run_until_complete()
    t_all = time.perf_counter() - t_kill
    r_stats = router.stats()["router"]
    recovery = {
        "kill_gen": kill_gen,
        "requests": len(reqs),
        "victim_streams": len(victim_reqs),
        "tokens_harvested_at_kill": sum(snap),
        "detect_lost_s": t_lost,
        "first_recovered_token_s": t_first,
        "drain_all_after_kill_s": t_all,
        "hosts_lost": r_stats["hosts_lost"],
        "recovered": r_stats["recovered"],
    }
    router.close()

    report = {
        "benchmark": "transport",
        "arch": cfg.name,
        "codec": default_codec(),
        "prompt_len": prompt_len,
        "gen": gen,
        "requests": requests,
        "slots_per_host": ecfg.max_slots,
        "overhead": overhead,
        "fleet": fleet_cells,
        "recovery_after_sigkill": recovery,
    }
    emit("transport_rpc_us", us_rpc_sub,
         f"inproc={us_rpc_inproc:.1f}us overhead="
         f"{overhead['rpc_overhead_us']:.1f}us codec={default_codec()}")
    for c in fleet_cells:
        emit(f"transport_h{c['hosts']}", 1e6 / max(c["fleet_tok_s"], 1e-9),
             f"fleet={c['fleet_tok_s']:.1f}tok/s rpcs={c['rpcs']}")
    emit("transport_recover_ms",
         1e3 * (t_first if t_first is not None else t_all),
         f"lost_detect={t_lost if t_lost is None else round(t_lost, 4)}s "
         f"drain_all={t_all:.2f}s")
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# transport: RPC {us_rpc_sub:.0f}us subprocess vs "
          f"{us_rpc_inproc:.0f}us in-process; SIGKILL recovery "
          f"first-token {t_first if t_first is None else round(t_first, 3)}s, "
          f"tokens bit-identical across the process boundary")
    print(f"# wrote {out_path}")
    return report


def disagg_report(cfg, params, *, arch: str, prompt_len: int, gen: int,
                  requests: int, out_path: str, smoke: bool = True,
                  block_size: int = 8) -> dict:
    """The disaggregation claim, measured: a bimodal mix — short
    "interactive" prompts decoding while long "batch" prompts keep
    arriving — served by a two-worker-process fleet twice, without roles
    (both hosts prefill AND decode, so every batch arrival stalls
    whichever decode batch shares its host) and with the
    ``prefill:1,decode:1`` role split (admissions land on the prefill
    host; once a stream clears the handoff threshold its exact KV blocks
    ship to the decode host and decode continues there, prefill-free).
    Records the p50/p99 inter-token gap of the interactive streams in
    each mode. A shipped stream's single largest gap is the handoff
    boundary itself (the synchronous export->wire->import leg, plus the
    decode host's one-time import-scatter compile on the first ship); it
    is excluded from the gap series and reported separately as
    ``handoff_stall_ms`` — the steady-state series is what the role
    split is supposed to smooth, the one-time stall is what it costs.
    Hard asserts: tokens bit-identical to a single in-process engine in
    BOTH modes, at least one stream actually shipped, and zero prefill
    instructions dispatched on the decode host after warmup (OPQ flag
    audit). The same bit-identity + audit runs again in-process for
    int8-KV (``quantize="serve"``) over the full mix — serving
    quantization is batch-invariant (per-row activation calibration,
    models/layers.pdot), so the staggered disaggregated mix must match
    the all-at-once single engine exactly. The p99 ordering is recorded,
    not asserted — CPU wall-clock is too noisy for a hard latency gate."""
    import time

    from repro.serving.router import parse_disaggregate
    from repro.serving.transport import SubprocessTransport, build_model_spec

    # interactive prompts stay genuinely short (a chat turn), batch prompts
    # take the full --prompt-len (a document): the short side bounds the
    # ship payload (import cost on the decode host), the long side sets the
    # prefill interference the role split removes
    short_prompt = max(block_size, min(prompt_len // 4, 32))
    long_prompt = prompt_len
    n_inter = max(requests // 2, 2)
    n_batch = max(requests - n_inter, 2)
    # the canonical bimodal shape: interactive = short prompt + LONG decode,
    # batch = long prompt + SHORT decode (summarization-style). The handoff
    # threshold sits exactly at the batch budget, so interactive streams
    # (remaining >> threshold) ship to the decode host while batch streams
    # (remaining <= threshold from their first token) finish where they
    # prefilled — batch imports never stall the decode host's batch
    batch_gen = max(2, min(8, gen // 4))
    threshold = batch_gen
    # slots >= the interactive set, so a disaggregated decode host can hold
    # EVERY interactive stream at once — otherwise late ships sit decoding
    # on the prefill host, stalled by the very burst the split avoids
    ecfg = EngineConfig(max_slots=max(n_inter, 2),
                        max_queue=n_inter + n_batch + 2,
                        max_seq_len=long_prompt + gen,
                        cache_backend="paged", block_size=block_size,
                        paged_native=True)
    roles = parse_disaggregate("prefill:1,decode:1", 2)

    rng = np.random.default_rng(0)
    mix = ([("interactive",
             rng.integers(0, cfg.vocab, (short_prompt,), dtype=np.int32),
             gen) for _ in range(n_inter)]
           + [("batch",
               rng.integers(0, cfg.vocab, (long_prompt,), dtype=np.int32),
               batch_gen) for _ in range(n_batch)])

    def reference(rcfg, rparams):
        engine = Engine(rcfg, rparams, ecfg)
        reqs = [engine.submit(p, g, strict=True) for _, p, g in mix]
        engine.run_until_complete()
        toks = [list(r.tokens) for r in reqs]
        engine.close()
        return toks

    def prefill_issued(flags):
        return sum(n for f, n in flags.items()
                   if f.startswith(("prefill", "draft_prefill")))

    def serve_mix(router):
        """Interactive streams submit up front; once every one of them is
        established mid-decode (>= 2 tokens harvested — by which point a
        disaggregated fleet has shipped them to the decode host), the batch
        prompts trickle in one per fleet step, so the batch prefill burst
        lands while the interactive streams are decoding. Gaps come from
        the tokens' ENGINE-SIDE emission timestamps (RouterRequest
        .token_ts, stamped where the worker appends): a free-running
        worker's tokens reach the router in bursts, so harvest-time diffs
        would measure the router's poll cadence, not the decode host's."""
        inter = [(p, g) for k, p, g in mix if k == "interactive"]
        batch = [(p, g) for k, p, g in mix if k == "batch"]
        reqs = []
        for i, (p, g) in enumerate(inter):
            reqs.append(router.submit(p, g, session=str(i % 2),
                                      strict=True))
        bi = 0
        deadline = time.monotonic() + 600
        t0 = time.perf_counter()
        while router.has_work() or bi < len(batch):
            if bi < len(batch) and all(len(r.tokens) >= 2
                                       for r in reqs[:n_inter]):
                bp, bg = batch[bi]
                reqs.append(router.submit(bp, bg,
                                          session=str(bi % 2), strict=True))
                bi += 1
            router.step()
            assert time.monotonic() < deadline, "disagg mix never drained"
        wall = time.perf_counter() - t0
        seen = [list(r.token_ts) for r in reqs]
        shipped = [len(r.hosts) > 1 for r in reqs]
        return [list(r.tokens) for r in reqs], wall, seen, shipped

    def gap_stats(seen, shipped):
        """Interactive inter-token gaps, with each SHIPPED stream's single
        largest gap pulled out as its handoff stall (see docstring)."""
        gaps, stalls = [], []
        for ts, sh in zip(seen[:n_inter], shipped[:n_inter]):
            g = sorted(np.diff(ts))
            if sh and g:
                stalls.append(g.pop())
            gaps.extend(g)
        return gaps, stalls

    def run_fleet(with_roles):
        spec = build_model_spec(arch, smoke=smoke, seed=0)
        fleet = []
        try:
            for _ in range(2):
                fleet.append(SubprocessTransport(spec, ecfg))
            for t in fleet:
                # warm with the mix's own shapes so the cells measure
                # steady-state serving, not XLA — and so the decode host's
                # prefill-flag BASELINE includes exactly the warmup prefills.
                # The batch prompts trickle in while interactive streams
                # decode, so the width-2 fused long-prompt prefill is a
                # MID-STREAM shape in both modes: warm it too, or its
                # one-time compile lands as a fake inter-token gap
                for plens in ((short_prompt,), (short_prompt, short_prompt),
                              (long_prompt,), (long_prompt, long_prompt)):
                    eids = [t.submit(rng.integers(0, cfg.vocab, (plen,),
                                                  dtype=np.int32), 2)
                            for plen in plens]
                    warm_deadline = time.monotonic() + 300
                    for eid in eids:
                        while not t.poll({eid: 0}).get(eid, {}).get("done"):
                            assert time.monotonic() < warm_deadline, \
                                "warmup never finished"
                            time.sleep(0.005)
                    t.poll({}, drop=eids)
            # warm the ship path too: the export gather and import scatter
            # compile once per pool geometry — keep that off the clock
            wp = rng.integers(0, cfg.vocab, (short_prompt,), dtype=np.int32)
            eid = fleet[0].submit(wp, gen)
            warm_deadline = time.monotonic() + 300
            while not (fleet[0].poll({eid: 0}).get(eid) or {}).get("t"):
                assert time.monotonic() < warm_deadline, "warm ship stalled"
                time.sleep(0.002)
            entry = fleet[0].ship_blocks(eid)
            if entry is not None:           # a too-fast worker already retired
                nid = fleet[1].recv_blocks(entry)
                fleet[0].ack_ship(entry["payload_id"])
                while not fleet[1].poll({nid: 0}).get(nid, {}).get("done"):
                    assert time.monotonic() < warm_deadline, \
                        "warm ship stalled"
                    time.sleep(0.005)
                fleet[1].poll({}, drop=[nid])
            else:
                fleet[0].poll({}, drop=[eid])
            base = [prefill_issued(t.stats()["opq"]["flags"]) for t in fleet]
        except Exception:
            for t in fleet:
                t.close()
            raise
        router = Router(transports=fleet,
                        router_cfg=RouterConfig(
                            n_hosts=2, handoff_threshold=threshold,
                            roles=roles if with_roles else None))
        toks, wall, seen, shipped = serve_mix(router)
        s = router.stats()
        after = [prefill_issued(h["opq"]["flags"]) for h in s["per_host"]]
        router.close()                      # closes the worker transports
        gaps, stalls = gap_stats(seen, shipped)
        return toks, wall, gaps, stalls, s, base, after

    ref_dense = reference(cfg, params)

    def cell(wall, gaps, stalls, s):
        g = 1e3 * np.asarray(gaps)
        return {
            "wall_s": wall,
            "interactive_streams": n_inter,
            "itl_p50_ms": float(np.percentile(g, 50)),
            "itl_p99_ms": float(np.percentile(g, 99)),
            "itl_max_ms": float(g.max()),
            "handoff_stall_ms": (1e3 * float(max(stalls))
                                 if stalls else None),
            "ships": s["router"]["ships"],
            "shipped_blocks": s["router"]["shipped_blocks"],
            "ship_fallbacks": s["router"]["ship_fallbacks"],
        }

    toks_off, wall_off, gaps_off, stalls_off, s_off, _, _ = run_fleet(False)
    assert toks_off == ref_dense, (
        "role-less fleet diverged from the single engine")
    (toks_on, wall_on, gaps_on, stalls_on, s_on,
     base_on, after_on) = run_fleet(True)
    assert toks_on == ref_dense, (
        "disaggregated fleet diverged from the single engine")
    assert s_on["router"]["ships"] >= 1, "no stream ever shipped"
    decode_host = roles.index("decode")
    assert after_on[decode_host] == base_on[decode_host], (
        f"decode host dispatched "
        f"{after_on[decode_host] - base_on[decode_host]} prefill "
        "instructions during disaggregated serving")

    off_cell = cell(wall_off, gaps_off, stalls_off, s_off)
    on_cell = cell(wall_on, gaps_on, stalls_on, s_on)

    # --- int8: same split, full mix, in-process. Serving quantization is
    # batch-invariant (per-row activation calibration in models/layers.pdot:
    # a row's scale depends only on that row), so the whole staggered mix
    # must match the all-at-once single engine bit-for-bit — any divergence
    # here is the ship itself: the quantized weights' int8 path decoding
    # over shipped blocks that did not land bit-exact.
    cfg_q = cfg.replace(quantize="serve")
    params_q = tz.quantize_params(params, predicate=_quant_predicate)
    ref_q = reference(cfg_q, params_q)
    router = Router(cfg_q, params_q, ecfg,
                    RouterConfig(n_hosts=2, handoff_threshold=threshold,
                                 roles=roles))
    toks_q, _, _, _ = serve_mix(router)
    s_q = router.stats()
    q_flags = dict(s_q["per_host"][decode_host]["opq"]["flags"])
    router.close()
    assert toks_q == ref_q, (
        "int8-KV disagg diverged from the single engine")
    q_ships = s_q["router"]["ships"]
    assert q_ships >= 1, "int8-KV cell never shipped"
    assert prefill_issued(q_flags) == 0, q_flags

    report = {
        "benchmark": "disagg",
        "arch": cfg.name,
        "block_size": block_size,
        "gen": gen,
        "handoff_threshold": threshold,
        "mix": {"interactive": n_inter, "interactive_prompt": short_prompt,
                "interactive_gen": gen, "batch": n_batch,
                "batch_prompt": long_prompt, "batch_gen": batch_gen},
        "modes": {"off": off_cell, "on": on_cell},
        "itl_p99_improvement_ms": off_cell["itl_p99_ms"] - on_cell["itl_p99_ms"],
        "bit_identical": {"dense": True, "int8_kv": True},
        "decode_host_prefill_instructions": 0,
        "int8_kv": {"streams": n_inter + n_batch, "ships": q_ships,
                    "decode_host_flags": q_flags},
    }
    emit("disagg_itl_p99_off", 1e3 * off_cell["itl_p99_ms"],
         f"p50={off_cell['itl_p50_ms']:.2f}ms "
         f"max={off_cell['itl_max_ms']:.2f}ms ships=0")
    emit("disagg_itl_p99_on", 1e3 * on_cell["itl_p99_ms"],
         f"p50={on_cell['itl_p50_ms']:.2f}ms "
         f"max={on_cell['itl_max_ms']:.2f}ms ships={on_cell['ships']} "
         f"blocks={on_cell['shipped_blocks']}")
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# disagg: interactive p99 gap {off_cell['itl_p99_ms']:.2f}ms "
          f"role-less vs {on_cell['itl_p99_ms']:.2f}ms disaggregated "
          f"({on_cell['ships']} ships, {on_cell['shipped_blocks']} blocks); "
          "tokens bit-identical (dense + int8-KV), decode host prefill-free")
    print(f"# wrote {out_path}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--quantize", default="off", choices=["off", "serve"])
    ap.add_argument("--cache-backend", default="auto",
                    choices=["auto", "contiguous", "paged"])
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--paged-report", default="",
                    help="write the paged-vs-contiguous memory JSON here "
                         "and skip the throughput sweep")
    ap.add_argument("--paged-native-report", default="",
                    help="write the block-native-vs-bridge decode working "
                         "set + chunked long-prompt TTFT JSON here and skip "
                         "the throughput sweep")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunk width for the long-prompt cell of "
                         "--paged-native-report")
    ap.add_argument("--long-prompt", type=int, default=48,
                    help="long-prompt length for --paged-native-report "
                         "(must exceed --prefill-chunk)")
    ap.add_argument("--router-report", default="",
                    help="write the multi-host router JSON (scale-out sweep "
                         "+ mid-run drain/handoff, tokens asserted "
                         "bit-identical) here and skip the throughput sweep")
    ap.add_argument("--drain-at", type=int, default=3,
                    help="fleet step at which --router-report drains host 0 "
                         "in every multi-host cell")
    ap.add_argument("--prefix-report", default="",
                    help="write the shared-prefix radix-cache JSON (TTFT + "
                         "prefill chunk units dispatched at 0/50/90%% shared "
                         "traffic, tokens asserted bit-identical to "
                         "prefix-cache-off) here and skip the throughput "
                         "sweep")
    ap.add_argument("--spec-report", default="",
                    help="write the speculative-decoding JSON (tok/s + "
                         "accepted-length histogram at each --spec-k, tokens "
                         "hard-asserted bit-identical to plain greedy and "
                         "target steps per decode token < 1) here and skip "
                         "the throughput sweep")
    ap.add_argument("--spec-k", type=int, nargs="+", default=[2, 4],
                    help="spec_k values --spec-report sweeps")
    ap.add_argument("--transport-report", default="",
                    help="write the transport JSON (RPC round-trip + "
                         "per-decode-step overhead subprocess vs in-process "
                         "with tokens asserted bit-identical, fleet "
                         "throughput at 1/2/4 worker processes, recovery "
                         "time after SIGKILL of one worker mid-decode) here "
                         "and skip the throughput sweep")
    ap.add_argument("--disagg-report", default="",
                    help="write the prefill/decode disaggregation JSON "
                         "(interactive-stream p99 inter-token gap for a "
                         "bimodal mix with and without the prefill:1,"
                         "decode:1 role split over two worker processes, "
                         "tokens hard-asserted bit-identical to a single "
                         "engine for dense AND int8-KV, zero prefill "
                         "instructions on the decode host) here and skip "
                         "the throughput sweep; requires --quantize off "
                         "(the cell quantizes its own int8 copy)")
    ap.add_argument("--sampling-report", default="",
                    help="write the sampling-engine JSON (per-decode-step "
                         "sampler overhead vs greedy, seeded streams "
                         "hard-asserted bit-identical across batchmates and "
                         "backends, HTTP streaming TTFT vs the CLI loop) "
                         "here and skip the throughput sweep")
    ap.add_argument("--prefix-prompt-len", type=int, default=40,
                    help="prompt length for --prefix-report (its own flag: "
                         "the shares 0/50/90%% must land on distinct "
                         "full-block prefix lengths)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch).smoke().replace(quantize=args.quantize)
    with shd.use_mesh(shd.device_mesh(jax.devices()[0])):
        params = init_model(cfg, jax.random.PRNGKey(0))
        if args.quantize == "serve":
            params = tz.quantize_params(params, predicate=_quant_predicate)

        if args.prefix_report:
            if args.prefix_prompt_len % args.block_size:
                ap.error(f"--prefix-prompt-len {args.prefix_prompt_len} must "
                         f"be a multiple of --block-size {args.block_size} "
                         "so the 0/50/90% shares land on distinct full-block "
                         "prefix lengths")
            prefix_report(
                cfg, params, prompt_len=args.prefix_prompt_len, gen=8,
                block_size=args.block_size, requests=max(args.requests, 4),
                out_path=args.prefix_report)
            return 0

        if args.disagg_report:
            if args.quantize != "off":
                ap.error("--disagg-report runs the dense AND int8-KV cells "
                         "itself; leave --quantize off")
            disagg_report(
                cfg, params, arch=args.arch, prompt_len=args.prompt_len,
                gen=args.gen, requests=args.requests,
                block_size=args.block_size, out_path=args.disagg_report)
            return 0

        if args.transport_report:
            transport_report(
                cfg, params, arch=args.arch, prompt_len=args.prompt_len,
                gen=args.gen, requests=args.requests,
                out_path=args.transport_report)
            return 0

        if args.sampling_report:
            sampling_report(
                cfg, params, slots=2, prompt_len=args.prompt_len,
                gen=args.gen, requests=args.requests,
                out_path=args.sampling_report)
            return 0

        if args.spec_report:
            spec_report(
                cfg, params, slots=2, prompt_len=args.prompt_len,
                gen=args.gen, requests=args.requests,
                spec_ks=tuple(args.spec_k), out_path=args.spec_report)
            return 0

        if args.router_report:
            router_report(
                cfg, params, slots=2, prompt_len=args.prompt_len,
                gen=args.gen, requests=args.requests,
                drain_at=args.drain_at, out_path=args.router_report)
            return 0

        if args.paged_report:
            paged_memory_report(
                cfg, params, slots=4, prompt_len=args.prompt_len,
                gen=args.gen, block_size=args.block_size,
                out_path=args.paged_report)
            return 0

        if args.paged_native_report:
            if args.long_prompt <= args.prefill_chunk:
                ap.error(f"--long-prompt {args.long_prompt} must exceed "
                         f"--prefill-chunk {args.prefill_chunk}, or the "
                         "'chunked' cell would measure the fused path")
            long_seq = args.long_prompt + args.gen
            if (long_seq // args.prefill_chunk) * args.prefill_chunk < args.long_prompt:
                ap.error(f"--long-prompt {args.long_prompt} does not fit a "
                         f"chunk-multiple bucket within prompt+gen "
                         f"{long_seq} (chunk {args.prefill_chunk}); raise "
                         "--gen or align the prompt to the chunk width")
            paged_native_report(
                cfg, params, slots=4, prompt_len=args.prompt_len,
                gen=args.gen, block_size=args.block_size,
                chunk=args.prefill_chunk, long_prompt=args.long_prompt,
                out_path=args.paged_native_report)
            return 0

        for slots in (1, 2, 4, 8):
            # warmup compiles this slot count's executables with the sweep's
            # own shapes — same prompt_len+gen (cache/max_seq_len), the
            # all-at-once admission width (B = min(slots, requests) prefill)
            # AND the B=1 staggered-admission prefill — so the sweep cells
            # measure steady-state serving, not XLA
            run_cell(cfg, params, slots=slots, stagger=0,
                     n_requests=args.requests, prompt_len=args.prompt_len,
                     gen=args.gen, backend=args.cache_backend,
                     block_size=args.block_size)
            run_cell(cfg, params, slots=slots, stagger=1, n_requests=2,
                     prompt_len=args.prompt_len, gen=args.gen,
                     backend=args.cache_backend, block_size=args.block_size)
            for stagger in (0, 1, 4):          # all-at-once .. trickle
                s, ttft_ms, _ = run_cell(
                    cfg, params, slots=slots, stagger=stagger,
                    n_requests=args.requests, prompt_len=args.prompt_len,
                    gen=args.gen, backend=args.cache_backend,
                    block_size=args.block_size)
                tps = s["sustained_tok_s"]
                emit(f"serve_s{slots}_g{stagger}",
                     1e6 / max(tps, 1e-9),
                     f"sustained={tps:.1f}tok/s ttft={ttft_ms:.0f}ms "
                     f"occ={s['mean_occupancy']:.2f} "
                     f"backend={s['cache']['backend']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
