"""Admission (seeding) latency: fused prefill-with-cache vs B=1 prompt replay.

    PYTHONPATH=src python benchmarks/admission_latency.py [--smoke] \
        [--out reports/BENCH_admission.json]

Sweeps prompt length x admission batch and times how long it takes to seed a
leased slot's KV cache, two ways:

  * fused  — the engine's admission path: ONE bucketed prefill forward per
    request returning first-token + per-layer K/V, ONE donated scatter into
    its slot rows (models/serve.py prefill_with_cache + serving/kv.py
    write_slots). One dispatch per request, flat in prompt length.
  * replay — the PR-1 baseline, reconstructed here (it no longer exists in
    src/): replay the prompt token-by-token through the B=1 decode step and
    copy the region into the slot row. L dispatches, linear in prompt length.

Emits the CSV contract of benchmarks/common.py (name,us_per_call,derived) with
per-request seeding microseconds, and writes a JSON artifact (--out) carrying
the full sweep — the per-PR regression record for reports/BENCH_admission.json
and the CI artifact upload.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.models import init_model
from repro.models import serve as SV
from repro.models import steps as ST
from repro.serving.engine import Engine, EngineConfig
from repro.serving.store import ContiguousKVStore

from common import emit


def _time(fn, iters: int) -> float:
    """Best-of-iters wall seconds per call (fn must block on device results).
    min, not median: seeding cost is deterministic work, so the floor is the
    signal and everything above it is scheduler noise on a shared CPU host."""
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def fused_seed_cell(cfg, params, *, prompt_len: int, batch: int, max_seq: int,
                    iters: int):
    """Seed ``batch`` same-bucket requests through the engine's fused
    admission; returns (seconds per request, dispatched forwards)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (prompt_len,), dtype=np.int32)
               for _ in range(batch)]
    eng = Engine(cfg, params, EngineConfig(max_slots=batch, max_seq_len=max_seq))

    def admit_once():
        for p in prompts:
            eng.submit(p, max_new_tokens=2, strict=True)
        eng._admit()                      # one prefill + one write each
        jax.block_until_ready(eng.kv.cache["k"])
        for slot in list(eng.scheduler.active):
            eng._retire(slot)

    admit_once()                          # warmup 1: compile this bucket shape
    admit_once()                          # warmup 2: first post-compile call
    sec = _time(admit_once, iters)        # still pays one-time warmup costs
    forwards = eng.stats()["prefill_batches"] / (iters + 2)
    eng.close()
    return sec / batch, forwards


def replay_seed_cell(cfg, params, *, prompt_len: int, batch: int, max_seq: int,
                     iters: int):
    """The deleted PR-1 seeding, reconstructed: per-request B=1 replay decode
    chain + per-slot write. Returns (seconds per request, decode dispatches
    per request)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (prompt_len,), dtype=np.int32)
               for _ in range(batch)]
    replay = jax.jit(ST.make_decode_step(cfg))
    template = SV.init_cache(cfg, 1, max_seq)
    mgr = ContiguousKVStore(cfg, n_slots=batch, max_seq_len=max_seq)

    def seed_all():
        for slot, p in enumerate(prompts):
            rc = template
            for t in p:
                _, rc = replay(params, rc,
                               {"tokens": jnp.asarray([[int(t)]], jnp.int32)})
            mgr.write_slot(slot, rc, n_valid=len(p))
        jax.block_until_ready(mgr.cache["k"])

    seed_all()                            # warmup (the B=1 decode step shape)
    seed_all()
    sec = _time(seed_all, iters)
    return sec / batch, float(prompt_len)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--kv-dtype", default="bfloat16", choices=["bfloat16", "int8"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI (seconds, not minutes)")
    ap.add_argument("--iters", type=int, default=0,
                    help="timing iterations per cell (0 = auto)")
    ap.add_argument("--out", default="",
                    help="write the sweep as a JSON artifact to this path")
    args = ap.parse_args(argv)

    lengths = (8, 16) if args.smoke else (8, 16, 32, 64)
    batches = (1, 2) if args.smoke else (1, 4)
    iters = args.iters or (3 if args.smoke else 7)
    max_seq = max(lengths) + 8

    cfg = get_config(args.arch).smoke().replace(kv_cache_dtype=args.kv_dtype)
    mesh = shd.device_mesh(jax.devices()[0])
    cells = []
    with shd.use_mesh(mesh):
        params = init_model(cfg, jax.random.PRNGKey(0))
        for batch in batches:
            for L in lengths:
                fused_s, forwards = fused_seed_cell(
                    cfg, params, prompt_len=L, batch=batch, max_seq=max_seq,
                    iters=iters)
                replay_s, decodes = replay_seed_cell(
                    cfg, params, prompt_len=L, batch=batch, max_seq=max_seq,
                    iters=iters)
                cell = {
                    "prompt_len": L,
                    "batch": batch,
                    "fused_seed_us_per_req": 1e6 * fused_s,
                    "replay_seed_us_per_req": 1e6 * replay_s,
                    "fused_forwards_per_admission": forwards,
                    "replay_decodes_per_req": decodes,
                    "speedup": replay_s / max(fused_s, 1e-12),
                }
                cells.append(cell)
                emit(f"admission_L{L}_b{batch}_fused", 1e6 * fused_s,
                     f"1 forward/request speedup={cell['speedup']:.1f}x")
                emit(f"admission_L{L}_b{batch}_replay", 1e6 * replay_s,
                     f"{L} B=1 decodes/req (deleted baseline)")

    # the headline claim, checked numerically: fused per-request seeding is
    # ~flat in L while replay grows ~linearly
    by_batch = {b: [c for c in cells if c["batch"] == b] for b in batches}
    for b, cs in by_batch.items():
        lo, hi = cs[0], cs[-1]
        growth_f = hi["fused_seed_us_per_req"] / lo["fused_seed_us_per_req"]
        growth_r = hi["replay_seed_us_per_req"] / lo["replay_seed_us_per_req"]
        print(f"# batch={b}: L {lo['prompt_len']}->{hi['prompt_len']}: "
              f"fused grew {growth_f:.2f}x, replay grew {growth_r:.2f}x")

    if args.out:
        out = {
            "benchmark": "admission_latency",
            "arch": args.arch,
            "kv_cache_dtype": args.kv_dtype,
            "smoke": bool(args.smoke),
            "iters": iters,
            "max_seq_len": max_seq,
            "cells": cells,
        }
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
        print(f"# wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
