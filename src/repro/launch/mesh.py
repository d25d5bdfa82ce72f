"""The launchers' meshes, built with ``sharding.make_mesh`` (Auto axes).

Importing this module never touches jax device state. The dry-run launcher
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so the host platform exposes enough placeholder devices.

Mesh geometry (TPU v5e pods, 256 chips each):
  single-pod  (16, 16)        ("data", "model")
  multi-pod   (2, 16, 16)     ("pod", "data", "model")   2 pods = 512 chips

The "pod" axis composes with "data" for batch sharding: gradient reduction is
hierarchical (reduce-scatter over ICI within the pod, all-reduce over DCI
between pods) — GSPMD derives the two-level schedule from the sharding.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_model: int = 1) -> Mesh:
    """Training mesh over every visible device: (n/n_model, n_model)
    ``("data", "model")`` when ``n_model`` divides the device count, else
    all of them on ``("data",)``."""
    n = len(jax.devices())
    if n_model > 1 and n % n_model == 0:
        return make_mesh((n // n_model, n_model), ("data", "model"))
    return make_mesh((n,), ("data",))
