"""Files of the benchmark, found by name, and the seed's keys.

Everything that belongs to one configuration, one traffic mix, one cell or
one metric is a file of its own under ``bench/``:

  configs/<config>.json     sizes as run, source, cuts, departures from the
                            source, deployment, limits
  families/<family>.py      what the harness knows of a family's shape: its
                            weight tree with each stack's length, its
                            fields of the program's ArchConfig, and the
                            operations a token requires per layer
                            (contract in ``families/dense.py``)
  references/<family>.py    the plain float32 reference of that family,
                            ``score(c, seed, seqs, n_prompt)``
  mixes/<traffic>.json      traffic parameters for the general generator
  cells/<workload>.json     the cell's offered rate
  metrics/<metric>.py       one reader per metric, ``read(run) -> float|None``

so a later change adds a model family, a configuration, a cell, a mix or a
metric as new files plus an entry in ``BENCHMARK.json``, and edits nothing
here.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> Dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def mix(name: str) -> Dict:
    return load_json(BENCH / "mixes" / f"{name}.json")


def cell(name: str) -> Dict:
    return load_json(BENCH / "cells" / f"{name}.json")


def _module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def family(name: str) -> ModuleType:
    """Loaded once a process: the FLOP counts read it for every token."""
    return _module(BENCH / "families" / f"{name}.py")


def reference(family: str) -> ModuleType:
    return _module(BENCH / "references" / f"{family}.py")


def metric_reader(name: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{name}.py")


def seed_key(seed: int):
    """A JAX key from a seed of up to 64 bits: ``PRNGKey`` keeps only the
    low 32, so the high word is folded in."""
    import jax
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)
