"""The one traffic generator: a mix file's parameters -> timed requests.

Open loop. A run has three phases: a lead-in that fills the server, the
measured window, and a tail that keeps the load on until every request of
the window has its first token; the cell's file gives the lengths of the
lead-in and of the longest tail (``lead_s``, ``tail_s``). Each phase draws
its lengths and its exponential (Poisson) inter-arrival gaps by stratified
quantiles, ``F^-1((i + 0.5) / n)``, so every seed offers the same set of
sizes and gaps; the seed orders them and writes the prompt tokens. The
gaps are put in a free seeded order, so arrivals cluster as a Poisson
process's do. The lengths are arranged in blocks (:func:`arrange`) so every
stretch of the window gets its share of long prompts and long outputs:
two seeds differ in order, never in the amount of work, and a tail
percentile does not swing with where one seed happens to cluster its long
prompts.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np



@dataclasses.dataclass
class Request:
    index: int
    phase: str            # "lead" | "window" | "tail"
    due_s: float          # offset from the start of the lead-in
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


BLOCK = 8             # requests per stratified block (see arrange)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of one length spec of a mix file."""
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] + 1 - spec["min"])
        v = np.floor(v)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified exponential inter-arrival gaps at ``rate`` per s,
    rescaled so they sum to exactly ``n / rate``."""
    g = -np.log1p(-_quantiles(n))
    return g * (n / rate) / g.sum()


def arrange(values: np.ndarray, rng: np.random.Generator,
            block: int = BLOCK) -> np.ndarray:
    """A seeded order of stratified ``values`` in which every run of
    ``block`` consecutive requests takes one value from each of ``block``
    strata (the sorted values cut into equal parts), so no stretch of the
    window gets more than its share of long prompts or long outputs; the
    seed orders each stratum and each block."""
    strata = [rng.permutation(part)
              for part in np.array_split(np.sort(values), block)]
    out = []
    for t in range(max(len(s) for s in strata)):
        row = [s[t] for s in strata if t < len(s)]
        out.extend(rng.permutation(row))
    return np.asarray(out, values.dtype)


def requests(mix: Dict, rate: float, seconds: float, vocab: int,
             seed: int, lead_s: float, tail_s: float) -> List[Request]:
    """Every request a run may send, in due order."""
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrivals {mix.get('arrivals')!r}")
    rng = np.random.default_rng(seed)
    out: List[Request] = []
    t0 = 0.0
    for phase, span in (("lead", lead_s), ("window", float(seconds)),
                        ("tail", tail_s)):
        n = max(1, math.floor(rate * span))
        p_len = arrange(lengths(mix["prompt_len"], n), rng)
        o_len = arrange(lengths(mix["output_len"], n), rng)
        # the first request of a phase is due at its opening
        g = rng.permutation(gaps(rate, n))
        due = t0 + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        for i in range(n):
            out.append(Request(
                index=len(out), phase=phase, due_s=float(due[i]),
                prompt=rng.integers(0, vocab, int(p_len[i]), dtype=np.int32),
                max_new_tokens=int(o_len[i])))
        t0 += span
    return out
