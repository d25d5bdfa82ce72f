"""Serving CLI: a thin driver over the continuous-batching engine
(serving/engine.py) with the Tensorizer W8A8 fast path.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
        --quantize serve --requests 4 --prompt-len 32 --gen 16

The paper's technique is the serving fast path: with ``--quantize serve``,
every >=2D weight is Tensorizer-quantized to int8 (per-output-channel scales,
int32 accumulation, fused dequant) — half the HBM bytes per decode step, which
is exactly the dominant roofline term of the decode cells (§Perf).

Batching model: requests flow through the Engine's bounded queue into a
slot-based in-flight decode batch (continuous batching — joins and retires per
step, no full-batch barrier); admission is fused prefill-with-cache — one
bucketed forward returns the first token plus per-layer K/V that a single
batched scatter writes into the leased slot rows (O(1) dispatches per bucket,
zero replay decodes); all device work is dispatched through the OPQ runtime.
``--stagger-steps N`` offsets arrivals by N engine steps to exercise
mid-flight joins.

The cache sits behind the SlotStore protocol (serving/store.py):
``--cache-backend contiguous`` leases per-slot rows sized to the seq budget,
``--cache-backend paged`` leases fixed-size blocks from a pool
(``--block-size``, ``--n-blocks``) with admission backpressure when the pool
runs dry, and ``auto`` picks contiguous for dense/moe and the recurrent-state
backend for ssm/hybrid archs (xlstm/zamba2 serve end-to-end now). With
``--paged-native`` decode attends over the block pool through the per-slot
tables — no transient gather view, ``decode_view_bytes == 0`` — and
``--paged-kernel`` routes the contraction through the Pallas paged-attention
kernel. ``--prefill-chunk W`` admits prompts wider than the fused buckets
through the chunked prefill scan (peak score memory W*S, not S^2). The
end-of-run report prints ``memory_stats()`` for the selected backend.

``--hosts N`` serves the same traffic through the multi-host Router
(serving/router.py): N hosts, cache-affinity placement (requests cycle
through N sessions here, so repeat sessions pin to the host holding their
blocks), load-aware spill, and — with ``--drain-at K`` — a drain of host 0
after K fleet steps, handing its in-flight generations off to the other
hosts mid-run (tokens provably unchanged; see docs/serving.md). By default
hosts are in-process engines; ``--host-procs`` runs each host as its own OS
process (serving/host_main.py workers over SubprocessTransport) — real
process parallelism, spawned and supervised here, reaped on exit. Workers
rebuild the model deterministically from the arch/smoke/quantize/seed spec,
so fleet tokens stay bit-identical to the in-process fleet.

In ``--api-port`` server mode, SIGINT/SIGTERM trigger a graceful shutdown:
admissions stop, live SSE streams are flushed with a terminal frame, hosts
drain, and worker processes (with ``--host-procs``) are reaped — no
orphans.

Every flag is documented operator-style in docs/serving.md, which
tests/test_docs.py keeps in lockstep with this parser.
"""

from __future__ import annotations

import argparse
import signal
import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.core import tensorizer as tz
from repro.core.compile_cache import enable_compile_cache
from repro.distributed import sharding as shd
from repro.models import init_model
from repro.serving.api import serve_api
from repro.serving.engine import Engine, EngineConfig
from repro.serving.metrics import (format_memory_stats, format_router_stats,
                                   format_sampling_stats, format_spec_stats,
                                   format_transport_stats)
from repro.serving.router import Router, RouterConfig, parse_disaggregate
from repro.serving.sampling import SamplingParams
from repro.serving.transport import (WORKER_ON_TPU, SubprocessTransport,
                                     build_model_spec)


def _quant_predicate(path, leaf):
    """Quantize projection weights only (allowlist: names starting with "w",
    plus lm_head) — norms, biases, conv taps, LoRA adapters, and the SSM/xLSTM
    recurrence weights stay f32 (DESIGN.md §Arch-applicability)."""
    name = ""
    for p in reversed(path):
        name = getattr(p, "key", getattr(p, "name", ""))
        if name:
            break
    skip = {"conv_w",                      # depthwise taps (tiny, shape-critical)
            "wup", "wdown",                # sLSTM block FFN adjacent to recurrence
            "rz", "ri", "rf", "ro"}        # sLSTM recurrence
    return (name == "lm_head" or name.startswith("w")) and name not in skip


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI surface — kept at module level so tests/test_docs.py can
    assert every flag here is documented in docs/serving.md and vice versa."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quantize", default="off", choices=["off", "serve"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="in-flight decode batch width (engine slots)")
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--stagger-steps", type=int, default=0,
                    help="engine steps between request arrivals (0 = all at once)")
    ap.add_argument("--cache-backend", default="auto",
                    choices=["auto", "contiguous", "paged", "recurrent"],
                    help="SlotStore backend (auto: contiguous for dense/moe, "
                         "recurrent for ssm/hybrid)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged backend: tokens per KV block")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="paged backend: pool size in blocks (0 = full "
                         "slots x max-seq capacity)")
    ap.add_argument("--paged-native", action="store_true",
                    help="paged backend: block-native decode — attend over "
                         "the block pool through the tables, no transient "
                         "gather view (decode_view_bytes == 0)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="with --paged-native: route the attention "
                         "contraction through the Pallas paged-attention "
                         "kernel (interpret mode off-TPU)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill width for long prompts: buckets "
                         "wider than this admit via the chunked scan "
                         "(peak score memory chunk*S instead of S^2; "
                         "0 = single-shot fused prefill only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged backend: shared-prefix radix cache — "
                         "admission leases matched immutable prefix blocks "
                         "by refcount and prefills only the suffix (COW "
                         "fork at mid-block divergence; LRU eviction of "
                         "unreferenced cached prefixes under pool "
                         "pressure). Requests here share a half-prompt "
                         "preamble to exercise hits")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-verify decode: a small draft model proposes "
                         "--spec-k tokens per active slot each round and the "
                         "target verifies the whole window in ONE wide "
                         "forward — slots advance 1..k+1 tokens per target "
                         "dispatch, tokens bit-identical to plain greedy "
                         "decode (greedy acceptance)")
    ap.add_argument("--draft-config", default="tinyllama-1.1b",
                    help="with --speculative: the draft model's arch config "
                         "(must share the target's vocab; smoke-reduced "
                         "under --smoke)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="with --speculative: draft proposals per round "
                         "(verify window = spec-k + 1 positions)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="simulated hosts: 1 = a single engine; >1 serves "
                         "through the multi-host Router (one engine per "
                         "host, cache-affinity placement + load-aware "
                         "spill; serving/router.py)")
    ap.add_argument("--host-procs", action="store_true",
                    help="with --hosts: run each host as its own OS process "
                         "(a serving/host_main.py worker speaking framed RPC "
                         "over a local socket) instead of an in-process "
                         "engine — real process parallelism; workers are "
                         "spawned, supervised, and reaped here, and a dead "
                         "worker's streams recover on the surviving hosts")
    ap.add_argument("--drain-at", type=int, default=0,
                    help="with --hosts > 1: drain host 0 after this many "
                         "fleet steps — queued requests re-place, long "
                         "in-flight generations hand off to other hosts "
                         "(0 = never drain)")
    ap.add_argument("--disaggregate", default="",
                    help="with --hosts > 1: split the fleet into prefill and "
                         "decode roles (\"prefill:N,decode:M\", or the \"N:M\" "
                         "shorthand; N+M must equal --hosts). Admissions go "
                         "to prefill hosts only; once a stream's remaining "
                         "budget clears the handoff threshold its KV blocks "
                         "ship to the least-loaded decode host and decode "
                         "continues there — tokens bit-identical, decode "
                         "hosts dispatch zero prefill instructions. Requires "
                         "--cache-backend paged --paged-native (block "
                         "shipping exports pool blocks)")
    ap.add_argument("--disagg-report", default="",
                    help="write the prefill/decode disaggregation JSON here "
                         "and exit (runs benchmarks/serve_throughput.py's "
                         "disagg cell): decode p99 inter-token gap for a "
                         "bimodal interactive+batch mix with and without the "
                         "role split, tokens hard-asserted bit-identical to "
                         "a single engine for dense AND int8-KV, zero "
                         "prefill instructions on decode hosts")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for the sampled half of the "
                         "synthetic traffic mix (0 = all-greedy). Even-"
                         "indexed requests sample at this temperature with "
                         "per-request seeds, odd ones stay greedy, so decode "
                         "batches mix both through ONE executable")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sampled requests: keep only the k highest-logit "
                         "tokens (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="sampled requests: nucleus filtering — smallest "
                         "probability mass >= p survives (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed: request i samples with "
                         "seed + i; randomness is counter-style per (seed, "
                         "position), so a seeded stream is batch-invariant")
    ap.add_argument("--stop", action="append", metavar="IDS",
                    help="stop sequence as comma-separated token ids "
                         "(repeatable; applies to every request) — a request "
                         "retires when its generated stream ends with one")
    ap.add_argument("--api-port", type=int, default=-1,
                    help="boot the streaming HTTP serve API (SSE "
                         "completions + embeddings/classify; serving/api.py) "
                         "on this port instead of running the synthetic "
                         "traffic loop (0 = OS-assigned, -1 = off). Fronts "
                         "the single engine, or the Router with --hosts > 1")
    return ap


def _roles_for(args):
    """--disaggregate spec -> per-host role tuple (None when off). Validated
    once in main() via ap.error; recomputed here so both Router construction
    sites (synthetic fleet loop, --api-port server) share one source."""
    if not args.disaggregate:
        return None
    return parse_disaggregate(args.disaggregate, args.hosts)


def _sampling_for(args, i: int):
    """The synthetic traffic generator's per-request sampling mix: with
    --temperature > 0, EVEN-indexed requests sample (per-request seed =
    --seed + i) while odd ones stay greedy — every decode batch then mixes
    greedy and sampled rows through the one masked executable, which is the
    heterogeneous-batch case worth smoking. --stop applies to all."""
    stops = tuple(tuple(int(t) for t in s.split(","))
                  for s in (args.stop or []))
    if args.temperature > 0 and i % 2 == 0:
        return SamplingParams(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed + i, stop=stops)
    if stops:
        return SamplingParams(stop=stops)
    return None


def _spawn_fleet(args, ecfg):
    """--host-procs: one worker process per host, each rebuilding the model
    deterministically from the same spec (bit-identical weights to the
    in-process path). A boot failure reaps the partial fleet — no orphans."""
    spec = build_model_spec(
        args.arch, smoke=args.smoke, quantize=args.quantize, seed=0,
        draft_arch=args.draft_config if args.speculative else None)
    fleet = []
    try:
        for _ in range(args.hosts):
            fleet.append(SubprocessTransport(spec, ecfg))
    except Exception:
        for t in fleet:
            t.close()
        raise
    print(f"[serve] spawned {len(fleet)} host processes "
          f"(pids {[t.pid for t in fleet]})", flush=True)
    return fleet


def _serve_fleet(cfg, params, ecfg, prompts, args, *, draft_params=None,
                 transports=None) -> int:
    """The --hosts > 1 path: the same traffic through the multi-host Router.
    Requests cycle over ``hosts`` session keys so the second lap of arrivals
    pins to the hosts already holding those sessions' blocks (affinity
    hits); ``--drain-at K`` drains host 0 after K fleet steps, exercising
    queued-requeue + in-flight handoff mid-run. ``transports`` (the
    --host-procs fleet) swaps the in-process engines for worker
    processes."""
    router = Router(cfg, params, ecfg,
                    RouterConfig(n_hosts=args.hosts, roles=_roles_for(args)),
                    draft_params=draft_params, transports=transports)
    requests = []
    fleet_steps = 0

    def tick(n):
        nonlocal fleet_steps
        for _ in range(n):
            router.step()
            fleet_steps += 1
            if args.drain_at and fleet_steps == args.drain_at:
                router.drain(0)
                print(f"[serve] draining host 0 at fleet step {fleet_steps}",
                      flush=True)

    for i in range(args.requests):
        requests.append(router.submit(prompts[i], args.gen,
                                      session=str(i % args.hosts),
                                      sampling=_sampling_for(args, i),
                                      strict=True))
        tick(args.stagger_steps)
    while router.has_work():
        tick(1)

    for r in requests:
        trail = "->".join(str(h) for h in r.hosts)
        handed = " (handoff)" if len(r.hosts) > 1 else ""
        print(f"[serve] req {r.id}: prompt {len(r.prompt)} tok | "
              f"host {trail}{handed} | {r.n_generated} tok", flush=True)
    s = router.stats()
    print(f"[serve] router: {format_router_stats(s)}", flush=True)
    if any(t["kind"] != "in-process" for t in s["router"]["transport"]):
        print(f"[serve] {format_transport_stats(s)}", flush=True)
    if args.temperature > 0 or args.stop:
        print(f"[serve] fleet {format_sampling_stats(s['fleet'])}",
              flush=True)
    if args.speculative:
        f = s["fleet"]
        rate = f["accepted_tokens"] / max(f["proposed_tokens"], 1)
        print(f"[serve] fleet speculative: {f['spec_rounds']} rounds + "
              f"{f['draft_steps']} draft steps | "
              f"{f['accepted_tokens']}/{f['proposed_tokens']} proposals "
              f"accepted ({rate:.2f})", flush=True)
    for h, hs in enumerate(s["per_host"]):
        o = hs.get("opq", {})
        drained = " [drained]" if router.is_drained(h) else ""
        print(f"[serve] host {h}{drained}: {hs['completed']} done | "
              f"{hs['decode_steps']} decode steps | "
              f"{hs['preempted']} preempted, {hs['evicted']} evicted | "
              f"cache {format_memory_stats(hs['cache'])} | "
              f"opq {o.get('issued', 0)} instr, "
              f"{o.get('affinity_hits', 0)} affinity hits", flush=True)
    print(f"[serve] sample generation (req 0): {requests[0].tokens}",
          flush=True)
    router.close()
    return 0


def main(argv=None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)
    for name in ("requests", "prompt_len", "gen", "slots", "max_queue"):
        if getattr(args, name) < 1:
            ap.error(f"--{name.replace('_', '-')} must be >= 1")
    if (args.paged_native or args.paged_kernel) and args.cache_backend != "paged":
        ap.error("--paged-native/--paged-kernel require --cache-backend paged")
    if args.paged_kernel and not args.paged_native:
        ap.error("--paged-kernel requires --paged-native")
    if args.prefix_cache and args.cache_backend != "paged":
        ap.error("--prefix-cache requires --cache-backend paged")
    if args.hosts < 1:
        ap.error("--hosts must be >= 1")
    if args.drain_at and args.hosts < 2:
        ap.error("--drain-at needs --hosts >= 2 (handoff requires another "
                 "host to admit the drained work)")
    if args.disaggregate:
        if args.hosts < 2:
            ap.error("--disaggregate needs --hosts >= 2 (at least one "
                     "prefill host and one decode host)")
        if args.cache_backend != "paged" or not args.paged_native:
            ap.error("--disaggregate requires --cache-backend paged "
                     "--paged-native (KV block shipping exports and imports "
                     "pool blocks)")
        if args.speculative:
            ap.error("--disaggregate does not support --speculative (the "
                     "draft model's KV does not ship; drop one)")
        try:
            parse_disaggregate(args.disaggregate, args.hosts)
        except ValueError as e:
            ap.error(str(e))
    if args.disagg_report and args.quantize == "serve":
        ap.error("--disagg-report runs the dense AND int8-KV cells itself "
                 "(it quantizes a copy of the params for the second cell); "
                 "leave --quantize off")
    if args.spec_k < 1:
        ap.error("--spec-k must be >= 1")
    if args.speculative and args.paged_kernel:
        ap.error("--speculative does not support --paged-kernel (the Pallas "
                 "kernel is a single-query decode shape)")
    if args.temperature < 0:
        ap.error("--temperature must be >= 0 (0 = greedy)")
    if not 0.0 < args.top_p <= 1.0:
        ap.error("--top-p must be in (0, 1]")
    if args.top_k < 0:
        ap.error("--top-k must be >= 0 (0 = off)")
    if args.temperature > 0 and args.speculative:
        ap.error("--speculative is greedy-only: non-greedy sampling needs "
                 "rejection-sampling acceptance (a ROADMAP follow-up) — "
                 "drop --speculative or --temperature")
    for s in args.stop or []:
        if not all(t.strip().lstrip("-").isdigit() for t in s.split(",")):
            ap.error(f"--stop takes comma-separated token ids, got {s!r}")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = cfg.replace(quantize=args.quantize)
    if args.speculative and cfg.family not in ("dense", "moe"):
        ap.error(f"--speculative needs a dense-family TARGET arch, got "
                 f"{args.arch} (family={cfg.family}); recurrent models can "
                 "be the draft, not the target")
    if (cfg.family not in ("dense", "moe", "ssm", "hybrid")
            or cfg.input_mode != "tokens"):
        ap.error(f"--arch {args.arch} (family={cfg.family}, "
                 f"input_mode={cfg.input_mode}) is not servable yet: the "
                 "engine handles token-input dense/moe (contiguous or paged "
                 "KV) and ssm/hybrid (recurrent-state) archs; encdec/vlm "
                 "serving is a ROADMAP item")
    if args.host_procs and jax.default_backend() == "tpu":
        ap.error(WORKER_ON_TPU)
    enable_compile_cache()
    # an engine runs on one device (device 0); the Router puts host i on
    # device i mod n
    with shd.use_mesh(shd.device_mesh(jax.devices()[0])):
        params = init_model(cfg, jax.random.PRNGKey(0))
        if args.quantize == "serve":
            params = tz.quantize_params(params, predicate=_quant_predicate)
            n_q = sum(isinstance(l, tz.QTensor)
                      for l in jax.tree.leaves(params, is_leaf=lambda x: isinstance(x, tz.QTensor)))
            print(f"[serve] Tensorizer W8A8: {n_q} weight tensors quantized", flush=True)

        if args.disagg_report:
            # the bench module owns the disagg measurement cell; load it by
            # path (benchmarks/ is not a package) and hand over the already-
            # built params so its reference engine matches the worker spec
            import importlib.util
            from pathlib import Path
            bench_py = (Path(__file__).resolve().parents[3] / "benchmarks"
                        / "serve_throughput.py")
            bspec = importlib.util.spec_from_file_location(
                "serve_throughput_bench", bench_py)
            bench = importlib.util.module_from_spec(bspec)
            bspec.loader.exec_module(bench)
            bench.disagg_report(
                cfg, params, arch=args.arch, smoke=args.smoke,
                prompt_len=args.prompt_len, gen=args.gen,
                requests=args.requests, out_path=args.disagg_report)
            return 0

        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab, (args.requests, args.prompt_len),
                               dtype=np.int32)
        if args.prefix_cache:
            # hot-prefix traffic shape: every request opens with the same
            # half-prompt preamble (a shared system prompt), so all but the
            # first admission walk onto cached blocks
            prompts[:, :args.prompt_len // 2] = prompts[0, :args.prompt_len // 2]

        draft_cfg = None
        draft_params = None
        if args.speculative:
            draft_cfg = get_config(args.draft_config)
            if args.smoke:
                draft_cfg = draft_cfg.smoke()
            # seed 0, like the target: with --draft-config == --arch the
            # draft IS the target and acceptance is total — the cheap way to
            # smoke the full accept path; a real deployment points this at a
            # genuinely smaller config
            draft_params = init_model(draft_cfg, jax.random.PRNGKey(0))
            print(f"[serve] speculative: draft {args.draft_config}, "
                  f"k={args.spec_k} (verify window {args.spec_k + 1})",
                  flush=True)

        ecfg = EngineConfig(
            max_slots=args.slots, max_queue=args.max_queue,
            max_seq_len=args.prompt_len + args.gen,
            cache_backend=args.cache_backend, block_size=args.block_size,
            n_blocks=args.n_blocks or None,
            paged_native=args.paged_native,
            paged_kernel=args.paged_kernel,
            prefill_chunk=args.prefill_chunk or None,
            prefix_cache=args.prefix_cache,
            speculative=args.speculative, spec_k=args.spec_k,
            draft=draft_cfg)

        transports = _spawn_fleet(args, ecfg) if args.host_procs else None

        if args.api_port >= 0:
            # server mode: no synthetic traffic — expose the engine (or the
            # fleet) over HTTP and block until interrupted
            if args.hosts > 1 or transports is not None:
                target = Router(cfg, params, ecfg,
                                RouterConfig(n_hosts=args.hosts,
                                             roles=_roles_for(args)),
                                draft_params=draft_params,
                                transports=transports)
                front = (f"router, {args.hosts} host "
                         f"{'processes' if transports else 'engines'}")
            else:
                target = Engine(cfg, params, ecfg,
                                draft_params=draft_params)
                front = "single engine"
            srv = serve_api(target, port=args.api_port)
            print(f"[serve] HTTP API on {srv.url} ({front}) — "
                  f"POST /v1/completions (SSE with \"stream\": true), "
                  f"/v1/embeddings, /v1/classify; GET /v1/stats /healthz",
                  flush=True)

            # graceful shutdown on SIGINT and SIGTERM: wait() turns the
            # KeyboardInterrupt into close(), which stops admissions,
            # flushes a terminal frame to every live SSE stream, and —
            # through target.close() — drains the hosts and reaps worker
            # processes. No orphans, exit 0.
            def _graceful(signum, frame):
                raise KeyboardInterrupt
            signal.signal(signal.SIGTERM, _graceful)
            srv.wait()
            print("[serve] shutdown: streams flushed, closing fleet",
                  flush=True)
            target.close()
            print("[serve] shutdown complete (workers reaped)", flush=True)
            return 0

        if args.hosts > 1 or transports is not None:
            return _serve_fleet(cfg, params, ecfg, prompts, args,
                                draft_params=draft_params,
                                transports=transports)

        engine = Engine(cfg, params, ecfg, draft_params=draft_params)
        requests = []
        for i in range(args.requests):
            requests.append(engine.submit(prompts[i], args.gen,
                                          sampling=_sampling_for(args, i),
                                          strict=True))
            for _ in range(args.stagger_steps):
                engine.step()
        engine.run_until_complete()

        for r in requests:
            print(f"[serve] req {r.id}: prompt {r.metrics.prompt_len} tok | "
                  f"TTFT {r.metrics.ttft_s*1e3:.1f} ms "
                  f"(queue {r.metrics.queue_wait_s*1e3:.1f} + "
                  f"prefill+seed {r.metrics.prefill_s*1e3:.1f}) | "
                  f"{r.metrics.n_generated} tok @ {r.metrics.decode_tok_s:.1f} tok/s",
                  flush=True)
        s = engine.stats()
        print(f"[serve] engine: {s['completed']} requests | "
              f"{s['prefill_batches']} prefill batches | "
              f"{s['decode_steps']} decode steps | "
              f"sustained {s['sustained_tok_s']:.1f} tok/s | "
              f"mean queue depth {s['mean_queue_depth']:.2f} | "
              f"mean occupancy {s['mean_occupancy']:.2f}/{args.slots}", flush=True)
        print(f"[serve] admission: fused prefill-with-cache | "
              f"prefill wait {s['prefill_wait_s']*1e3:.1f} ms | "
              f"batched seed writes {s['seed_write_s']*1e3:.1f} ms | "
              f"0 replay decodes | "
              f"{s['admissions_deferred']} deferred (backpressure)", flush=True)
        if args.speculative:
            print(f"[serve] {format_spec_stats(s)}", flush=True)
        if args.temperature > 0 or args.stop:
            print(f"[serve] {format_sampling_stats(s)}", flush=True)
        if args.prefix_cache:
            print(f"[serve] prefix cache: {s['prefix_hits']} hits | "
                  f"{s['prefix_blocks_reused']} blocks reused | "
                  f"{s['prefix_tokens_reused']} prompt positions skipped | "
                  f"{s['prefill_chunks']} prefill chunks computed", flush=True)
        print(f"[serve] cache: {format_memory_stats(s['cache'])}", flush=True)
        if "opq" in s:
            o = s["opq"]
            print(f"[serve] opq: {o['issued']} instructions | "
                  f"{o['affinity_hits']} affinity hits | "
                  f"{o['backups_issued']} backups", flush=True)
        print(f"[serve] sample generation (req 0): {requests[0].tokens}", flush=True)
        engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
