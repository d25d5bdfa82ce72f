"""Weights from the seed, made by the benchmark, not by the program.

Every leaf is drawn from its own key: leaf ``i`` of the family's ``leaves``
(a fixed order) takes ``fold_in(seed_key, i)``, and layer ``l`` of a
stacked leaf takes ``fold_in(leaf_key, l)``. So the serving side builds the
whole tree in one jitted call on the device, in the dtype it is served in,
and the reference rebuilds one layer at a time, bit for bit, without
holding the model in float32.

The tree's layout is the family's (``bench/families/<family>.py``): each
leaf names the stack it is stacked in (its first path key) and that
stack's length, so a family may hold several stacks of different lengths,
such as one leading dense layer before its MoE layers. The dense and MoE
families hold one stack, ``layers``; a test checks their trees against the
program's own initializer, shape for shape.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import common

# (path, shape without the layer axis, init, layers); init is a float std,
# "ones" or "zeros"; layers is None for a leaf held once, else the length
# of the stack path[0]
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], object, Optional[int]]


def leaves(c: Dict) -> List[Leaf]:
    """The leaves of a configuration file's model, in a fixed order."""
    return common.family(c["family"]).leaves(c)


def stacks(c: Dict) -> Dict[str, int]:
    """Each stack's length, in the order of the leaves."""
    out: Dict[str, int] = {}
    for path, _, _, n in leaves(c):
        if n is not None and out.setdefault(path[0], n) != n:
            raise ValueError(f"stack {path[0]!r} of {c['name']!r} has leaves "
                             f"of {out[path[0]]} and {n} layers")
    return out


def _draw(key, shape, init, dtype):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * init).astype(dtype)


def _set(tree: Dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def build_fn(c: Dict):
    """The jitted maker of the whole tree from a key, in the configuration's
    ``torch_dtype``. Stacked leaves are drawn layer by layer under
    ``lax.map`` so no float32 copy of a whole stack is ever live."""
    dtype = jnp.dtype(c["torch_dtype"])
    tree_leaves = leaves(c)

    def build(key):
        tree: Dict = {}
        for i, (path, shape, init, n) in enumerate(tree_leaves):
            k = jax.random.fold_in(key, i)
            if n is not None:
                v = jax.lax.map(
                    lambda l, k=k, s=shape, std=init: _draw(
                        jax.random.fold_in(k, l), s, std, dtype),
                    jnp.arange(n))
            else:
                v = _draw(k, shape, init, dtype)
            _set(tree, path, v)
        return tree

    return jax.jit(build)


def model_params(c: Dict, seed: int) -> Dict:
    """The whole tree on the default device, in one jitted call."""
    return build_fn(c)(common.seed_key(seed))


def layer_params(c: Dict, seed: int, layer, stack: str = "layers") -> Dict:
    """Layer ``layer`` of ``stack`` (``layer`` = None: the leaves held once)
    exactly as :func:`model_params` draws them, returned in float32, without
    the stack's key."""
    key = common.seed_key(seed)
    tree: Dict = {}
    for i, (path, shape, init, n) in enumerate(leaves(c)):
        if (n is None) != (layer is None) or \
                (layer is not None and path[0] != stack):
            continue
        k = jax.random.fold_in(key, i)
        if n is not None:
            k = jax.random.fold_in(k, layer)
        v = _draw_f32(k, shape, init, jnp.dtype(c["torch_dtype"]))
        _set(tree, path[1:] if n is not None else path, v)
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw_f32(key, shape, init, dtype):
    return _draw(key, shape, init, dtype).astype(jnp.float32)
