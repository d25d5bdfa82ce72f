"""Sharding rules: logical roles -> mesh axes, for any of the three meshes.

Meshes (built with :func:`make_mesh`; the pod meshes in launch/mesh.py):
  device      (1,)            ("data",)                     one serving Engine
  single-pod  (16, 16)        ("data", "model")             256 chips
  multi-pod   (2, 16, 16)     ("pod", "data", "model")      512 chips

Roles:
  batch      -> ("pod","data")  hierarchical DP (intra-pod ICI reduce-scatter,
                                inter-pod DCI all-reduce — GSPMD derives it)
  model-dim  -> "model"         TP: attention heads / d_ff / vocab / experts (EP)
  sequence   -> "data"          SP for long-context KV caches (decode cells)

Every mesh is built by :func:`make_mesh` with ``AxisType.Auto`` axes: the
model code places values with ``lax.with_sharding_constraint``
(:func:`with_sharding`), which jax only accepts on Auto axes outside an
explicit-mesh context, while ``jax.make_mesh`` defaults to Explicit axes.

The mesh is carried in a context so model code never takes a mesh parameter.
``set_mesh`` sets the process-wide default; ``use_mesh`` overrides it for the
calling thread only, so several engines in one process (one per device) can
each trace and run their steps under their own mesh, from any thread.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_DEFAULT_MESH: Optional[Mesh] = None
_LOCAL = threading.local()


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types (over ``devices`` when given)."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    return Mesh(np.asarray(devices, dtype=object).reshape(shape), axes,
                axis_types=types)


def device_mesh(device) -> Mesh:
    """The one-device ``("data",)`` mesh a serving Engine runs under."""
    return make_mesh((1,), ("data",), devices=[device])


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def active_mesh() -> Optional[Mesh]:
    """The calling thread's ``use_mesh`` mesh, else the process default."""
    mesh = getattr(_LOCAL, "mesh", None)
    return mesh if mesh is not None else _DEFAULT_MESH


def current_mesh() -> Mesh:
    mesh = active_mesh()
    if mesh is None:
        raise RuntimeError("no mesh set — call distributed.set_mesh(...) or use_mesh(...)")
    return mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = getattr(_LOCAL, "mesh", None)
    _LOCAL.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _LOCAL.mesh = prev


def axis_names() -> Tuple[str, ...]:
    return tuple(current_mesh().axis_names)


def batch_axes() -> Tuple[str, ...]:
    """Axes the batch dimension shards over (pod+data when present)."""
    return tuple(a for a in ("pod", "data") if a in axis_names())


def model_axis() -> Optional[str]:
    return "model" if "model" in axis_names() else None


def seq_axis() -> Optional[str]:
    """Axis used for sequence sharding of long KV caches (SP)."""
    return "data" if "data" in axis_names() else None


def data_parallel_size() -> int:
    m = current_mesh()
    n = 1
    for a in batch_axes():
        n *= m.shape[a]
    return n


def model_parallel_size() -> int:
    m = current_mesh()
    a = model_axis()
    return m.shape[a] if a else 1


def batch_spec(*trailing) -> P:
    """P((pod,data), *trailing) — the activation batch sharding."""
    ax = batch_axes()
    lead = ax if len(ax) > 1 else (ax[0] if ax else None)
    return P(lead, *trailing)


def with_sharding(x, spec: P):
    """``lax.with_sharding_constraint`` against the current mesh (no-op when
    the spec refers to axes the mesh doesn't have)."""
    mesh = current_mesh()
    names = set(mesh.axis_names)

    def scrub(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(e for e in entry if e in names)
            return kept if kept else None
        return entry if entry in names else None

    spec = P(*(scrub(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard(x, spec: P):
    """device_put with a NamedSharding on the current mesh."""
    return jax.device_put(x, NamedSharding(current_mesh(), spec))
