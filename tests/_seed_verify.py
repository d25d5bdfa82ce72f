"""Preemption-seed verification harness.

The transport/router test suite pins its reference streams to specific RNG
seeds (6/4/0 in tests/test_transport.py), hand-picked so
that a re-prefill CONTINUATION — re-admitting ``prompt + harvested_tokens``
with the remaining budget, exactly what the router does after a drain, a
host loss, or a failed block ship — happens to be bit-identical to the
uninterrupted stream. That identity is NOT guaranteed in general: prefilling
the first W generated tokens computes their cache entries through the fused
prefill path, whose reduction shapes differ from decode's, so a stream can
diverge at SOME continuation points W and not others. A seed that survives
the particular W a test happens to cut at proves nothing about the next W.

This module replaces the hand-pinned convention with an exhaustive check:
``sweep_continuations`` cuts one stream at EVERY continuation point and
reports the clean/divergent W ranges, and ``assert_clean_continuations``
turns that into a test-time guarantee. ROADMAP requires any new preemption
mechanism to re-verify its seeds through this harness — the disaggregation
fallback tests (tests/test_disagg.py) consume it, and the pinned seeds in
tests/test_transport.py are documented against its output.

Run standalone for a report:

    PYTHONPATH=src python tests/_seed_verify.py --seed 21 --prompt-len 6 --gen 48
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class SweepReport:
    """Outcome of sweeping every continuation point of one stream."""

    baseline: List[int]                 # the uninterrupted stream's tokens
    clean: List[int]                    # W values whose stitch is bit-equal
    divergent: List[Tuple[int, int]]    # (W, first differing token index)

    @property
    def all_clean(self) -> bool:
        return not self.divergent

    def ranges(self) -> List[Tuple[int, int]]:
        """Clean W values compressed to inclusive (lo, hi) runs."""
        runs: List[Tuple[int, int]] = []
        for w in self.clean:
            if runs and w == runs[-1][1] + 1:
                runs[-1] = (runs[-1][0], w)
            else:
                runs.append((w, w))
        return runs

    def summary(self) -> str:
        runs = ", ".join(f"{lo}-{hi}" if lo != hi else str(lo)
                         for lo, hi in self.ranges())
        bad = ", ".join(f"W={w}@tok{i}" for w, i in self.divergent[:8])
        more = f" (+{len(self.divergent) - 8} more)" \
            if len(self.divergent) > 8 else ""
        return (f"clean W: [{runs or 'none'}]"
                + (f"; divergent: {bad}{more}" if self.divergent else ""))


def _default_engine_factory(cfg, params, ecfg_kw):
    from repro.serving import Engine, EngineConfig
    return Engine(cfg, params, EngineConfig(**ecfg_kw))


def run_stream(cfg, params, prompt, gen, *, sampling=None,
               ecfg_kw=None, engine_factory=None) -> List[int]:
    """One uninterrupted stream on a fresh engine — the baseline."""
    factory = engine_factory or _default_engine_factory
    eng = factory(cfg, params, dict(ecfg_kw or {}))
    req = eng.submit(np.asarray(prompt, np.int32), gen, sampling=sampling,
                     strict=True)
    eng.run_until_complete()
    tokens = list(req.tokens)
    eng.close()
    return tokens


def sweep_continuations(
    cfg, params, prompt, gen, *,
    sampling=None,
    ecfg_kw: Optional[dict] = None,
    cut_points: Optional[Sequence[int]] = None,
    engine_factory: Optional[Callable] = None,
    baseline: Optional[Sequence[int]] = None,
    _tamper: Optional[Callable[[int, List[int]], List[int]]] = None,
) -> SweepReport:
    """Cut one greedy stream at every continuation point W and re-admit it
    as ``prompt + baseline[:W]`` with budget ``gen - W`` on a FRESH engine —
    the router's re-prefill continuation, reproduced at engine level. A cut
    is *clean* when the stitched stream equals the baseline bit-for-bit.

    ``cut_points`` restricts the sweep (default: every W in 1..gen-1).
    ``_tamper(W, continuation_tokens)`` is the harness's own self-test hook
    (tests/test_disagg.py uses it to prove the sweep has teeth); real
    callers never pass it.
    """
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    base = (list(baseline) if baseline is not None
            else run_stream(cfg, params, prompt, gen, sampling=sampling,
                            ecfg_kw=ecfg_kw, engine_factory=engine_factory))
    if len(base) != gen:
        raise ValueError(
            f"baseline stopped early ({len(base)} of {gen} tokens) — "
            "sweep continuation points would be ill-defined; raise "
            "max_seq_len or drop stop conditions")
    factory = engine_factory or _default_engine_factory
    ws = list(cut_points) if cut_points is not None else range(1, gen)
    clean: List[int] = []
    divergent: List[Tuple[int, int]] = []
    for w in ws:
        if not 1 <= w < gen:
            raise ValueError(f"cut point W={w} outside 1..{gen - 1}")
        eng = factory(cfg, params, dict(ecfg_kw or {}))
        cont_prompt = np.concatenate([prompt, np.asarray(base[:w], np.int32)])
        req = eng.submit(cont_prompt, gen - w, sampling=sampling, strict=True)
        eng.run_until_complete()
        cont = list(req.tokens)
        eng.close()
        if _tamper is not None:
            cont = _tamper(w, cont)
        stitched = base[:w] + cont
        if stitched == base:
            clean.append(w)
        else:
            first_bad = next(i for i, (x, y) in enumerate(zip(stitched, base))
                             if x != y)
            divergent.append((w, first_bad))
    return SweepReport(baseline=base, clean=clean, divergent=divergent)


def assert_clean_continuations(cfg, params, prompt, gen, **kw) -> SweepReport:
    """Assert every swept continuation point is clean; returns the report."""
    report = sweep_continuations(cfg, params, prompt, gen, **kw)
    assert report.all_clean, (
        f"continuation-seed sweep found divergent cut points: "
        f"{report.summary()} — this (config, seed, prompt) pair is not safe "
        "to pin as a preemption/fallback reference")
    return report


def main(argv=None) -> int:
    import argparse

    import jax

    from repro.configs import get_config
    from repro.distributed import sharding as shd
    from repro.distributed.sharding import make_mesh
    from repro.models import init_model

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--seed", type=int, default=21,
                    help="prompt RNG seed to verify (the pinned value)")
    ap.add_argument("--prompt-len", type=int, default=6)
    ap.add_argument("--gen", type=int, default=48)
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--big", action="store_true",
                    help="use the transport tests' BIG geometry "
                         "(4 layers, d_model 256) instead of plain smoke")
    args = ap.parse_args(argv)

    shd.set_mesh(make_mesh((1,), ("data",)))
    cfg = get_config(args.arch).smoke()
    if args.big:
        cfg = cfg.replace(n_layers=4, d_model=256, n_heads=8, n_kv=4,
                          d_ff=1024, vocab=512, head_dim=32)
    params = init_model(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(args.seed).integers(
        0, cfg.vocab, (args.prompt_len,), dtype=np.int32)
    report = sweep_continuations(
        cfg, params, prompt, args.gen,
        ecfg_kw=dict(max_slots=2, max_seq_len=args.max_seq_len))
    print(f"seed={args.seed} prompt_len={args.prompt_len} gen={args.gen}: "
          f"{report.summary()}")
    return 0 if report.all_clean else 1


if __name__ == "__main__":
    raise SystemExit(main())
