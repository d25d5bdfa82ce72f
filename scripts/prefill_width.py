#!/usr/bin/env python3
"""Prefill batch-width variance, what prefilling alone costs, and
re-prefill continuation divergence.

    python scripts/prefill_width.py                           # TPU, full width
    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/prefill_width.py --smoke

Serves ``tinyllama-1.1b`` (random weights from seed 0) with float and with
Tensorizer W8A8 weights, through the Engine's own compiled prefill step:

  width         the same prompt prefilled at width 1 and in row 0 of a
                width-N batch (other prompts beside it): whether row 0's
                first token, top-logprob row and per-layer K/V are
                bit-equal, with the largest absolute difference. The
                Engine prefills every request alone because these differ
                on a TPU.
  cost          a burst of N same-bucket prompts prefilled as N width-1
                dispatches (the Engine's policy) against one width-N
                dispatch: best-of-``--iters`` wall milliseconds on the host
                clock, each timing blocked on the results.
  continuation  a request decoded straight through against the same
                request re-admitted at cut W as prompt + its first W tokens
                (what a drain handoff does): the first token where the
                stitched stream differs, per cut (ROADMAP Design 10).

Each reading is one ``[prefill_width] ...`` line on standard output.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import tensorizer as tz  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import _quant_predicate  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.serving import Engine, EngineConfig  # noqa: E402
from repro.serving.sampling import stack_params  # noqa: E402

ARCH = "tinyllama-1.1b"
SEED = 0


def log(msg: str) -> None:
    print(f"[prefill_width] {msg}", flush=True)


def prompts_for(cfg, bucket: int, n: int):
    rng = np.random.default_rng(SEED + bucket)
    return [rng.integers(0, cfg.vocab, (bucket,), dtype=np.int32)
            for _ in range(n)]


def prefill(engine: Engine, prompts):
    """The engine's compiled prefill step over ``prompts`` (one bucket, no
    padding), blocked on its results: (first tokens, K/V, logprob info)."""
    w, bucket = len(prompts), len(prompts[0])
    toks = np.stack(prompts).astype(np.int32)
    last = np.full((w,), bucket - 1, np.int32)
    presence = np.zeros((w, engine.cfg.vocab_padded), bool)
    for i, p in enumerate(prompts):
        presence[i, p] = True
    with engine.placed():
        out = engine._prefill(engine.params, toks, last,
                              stack_params([None] * w, presence))
        return jax.block_until_ready(out)


def row0(tree, w: int):
    """Row 0 of every leaf: K/V leaves carry the batch on axis 1 (layers
    first), the token and logprob leaves on axis 0."""
    return [np.asarray(x)[:, :1] if x.ndim > 1 and x.shape[1] == w
            and x.shape[0] != w else np.asarray(x)[:1]
            for x in jax.tree.leaves(tree)]


def max_diff(a, b) -> float:
    return float(np.max(np.abs(a.astype(np.float64) - b.astype(np.float64))))


def width_reading(name, engine, bucket: int, width: int) -> None:
    prompts = prompts_for(engine.cfg, bucket, width)
    (t1, kv1, lp1), (tn, kvn, lpn) = (prefill(engine, prompts[:1]),
                                      prefill(engine, prompts))
    kv_a, kv_b = row0(kv1, 1), row0(kvn, width)
    kv_equal = all(np.array_equal(a, b) for a, b in zip(kv_a, kv_b))
    kv_diff = max(max_diff(a, b) for a, b in zip(kv_a, kv_b))
    lp_diff = max_diff(np.asarray(lp1["top_lps"])[0],
                       np.asarray(lpn["top_lps"])[0])
    log(f"width {name} bucket {bucket}: row 0 at width 1 vs {width}: first "
        f"token {int(t1[0])} vs {int(tn[0])}, K/V bit-equal {kv_equal} (max "
        f"abs diff {kv_diff}), top-logprob max abs diff {lp_diff}")


def cost_reading(name, engine, bucket: int, width: int, iters: int) -> None:
    prompts = prompts_for(engine.cfg, bucket, width)

    def alone():
        return [prefill(engine, [p]) for p in prompts]

    def together():
        return prefill(engine, prompts)

    best = {}
    for label, fn in (("alone", alone), ("together", together)):
        fn()                                      # compile / warm
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        best[label] = 1e3 * min(times)
    log(f"cost {name} bucket {bucket}: {width} prompts as {width} width-1 "
        f"prefills {best['alone']} ms, as one width-{width} prefill "
        f"{best['together']} ms (best of {iters}, host wall clock)")


def continuation_reading(name, engine, prompt_len: int, gen: int,
                         cuts) -> None:
    prompt = prompts_for(engine.cfg, prompt_len, 1)[0]

    def run(p, n):
        req = engine.submit(p, n, strict=True)
        engine.run_until_complete()
        return list(req.tokens)

    base = run(prompt, gen)
    firsts = []
    for w in cuts:
        got = base[:w] + run(
            np.concatenate([prompt, np.asarray(base[:w], np.int32)]), gen - w)
        firsts.append((w, next((j for j, (a, b) in enumerate(zip(got, base))
                                if a != b), None)))
    n_bad = sum(j is not None for _, j in firsts)
    log(f"continuation {name} prompt {prompt_len}: {n_bad} of {len(cuts)} "
        f"cuts diverge; (W, first differing token) {firsts}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smoke() width and short prompts (CPU)")
    ap.add_argument("--width", type=int, default=4)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = get_config(ARCH)
    buckets, gen, max_seq = (128, 512), 24, 1024
    if args.smoke:
        cfg, buckets, gen, max_seq = cfg.smoke(), (16, 32), 8, 64
    cuts = sorted({1, 2, gen // 6, gen // 3, gen // 2, 2 * gen // 3,
                   5 * gen // 6} - {0})
    d = jax.devices()[0]
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}; device "
        f"{d.platform} {d.device_kind}; jax {jax.__version__}")
    params = init_model(cfg, jax.random.PRNGKey(SEED))
    ecfg = EngineConfig(max_slots=args.width, max_seq_len=max_seq,
                        cache_backend="paged", paged_native=True)
    for name in ("float", "int8"):
        if name == "int8":
            cfg = cfg.replace(quantize="serve")
            params = tz.quantize_params(params, predicate=_quant_predicate)
        engine = Engine(cfg, params, ecfg)
        try:
            for bucket in buckets:
                width_reading(name, engine, bucket, args.width)
            for bucket in buckets:
                cost_reading(name, engine, bucket, args.width, args.iters)
            for bucket in buckets:
                continuation_reading(name, engine, bucket, gen, cuts)
        finally:
            engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
