"""Weights from the seed, made by the benchmark, not by the program.

Every leaf is drawn from its own key: leaf ``i`` of :func:`leaves` (a fixed
order) takes ``fold_in(seed_key, i)``, and layer ``l`` of a stacked leaf
takes ``fold_in(leaf_key, l)``. So the serving side builds the whole tree in
one jitted call on the device, in the dtype it is served in, and the
reference rebuilds one layer at a time, bit for bit, without holding the
model in float32.

The tree has the layout of the program's dense and MoE families (embed,
final_ln, lm_head, layers/{ln1, attn, ln2, mlp|moe}); a test checks it
against the program's own initializer, shape for shape.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.common import seed_key

# (path, shape without the layer axis, init, stacked); init is a float std,
# "ones" or "zeros"
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], object, bool]


def leaves(c: Dict) -> List[Leaf]:
    """The leaves of a configuration file's model, in a fixed order."""
    D, V = c["hidden_size"], c["vocab_size"]
    Vp = (V + 15) // 16 * 16
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    ln = (("scale", "ones"), ("bias", "zeros")) if c["norm_type"] == \
        "layernorm" else (("scale", "ones"),)
    out: List[Leaf] = [(("embed",), (Vp, D), 0.02, False)]
    out += [(("final_ln", k), (D,), v, False) for k, v in ln]
    out.append((("lm_head",), (D, Vp), D ** -0.5, False))
    for norm in ("ln1", "ln2"):
        out += [(("layers", norm, k), (D,), v, True) for k, v in ln]
    for name, shape in (("wq", (D, H * hd)), ("wk", (D, KV * hd)),
                        ("wv", (D, KV * hd)), ("wo", (H * hd, D))):
        out.append((("layers", "attn", name), shape, shape[0] ** -0.5, True))
    if c["family"] == "moe":
        E, F = c["n_routed_experts"], c["moe_intermediate_size"]
        Fs = c["n_shared_experts"] * F
        out.append((("layers", "moe", "router"), (D, E), D ** -0.5, True))
        for name, shape in (("wi", (E, D, F)), ("wg", (E, D, F)),
                            ("wo", (E, F, D))):
            out.append((("layers", "moe", name), shape, shape[-2] ** -0.5,
                        True))
        for name, shape in (("wi", (D, Fs)), ("wg", (D, Fs)),
                            ("wo", (Fs, D))):
            out.append((("layers", "moe", "shared", name), shape,
                        shape[0] ** -0.5, True))
    else:
        F = c["intermediate_size"]
        gated = c["hidden_act"] == "silu"
        names = (("wi", (D, F)), ("wg", (D, F)), ("wo", (F, D))) if gated \
            else (("wi", (D, F)), ("wo", (F, D)))
        for name, shape in names:
            out.append((("layers", "mlp", name), shape, shape[0] ** -0.5,
                        True))
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
    L = c["num_hidden_layers"]
    dtype = jnp.dtype(c["torch_dtype"])

    def build(key):
        tree: Dict = {}
        for i, (path, shape, init, stacked) in enumerate(leaves(c)):
            k = jax.random.fold_in(key, i)
            if stacked:
                v = jax.lax.map(
                    lambda l, k=k, s=shape, n=init: _draw(
                        jax.random.fold_in(k, l), s, n, dtype),
                    jnp.arange(L))
            else:
                v = _draw(k, shape, init, dtype)
            _set(tree, path, v)
        return tree

    return jax.jit(build)


def model_params(c: Dict, seed: int) -> Dict:
    """The whole tree on the default device, in one jitted call."""
    return build_fn(c)(seed_key(seed))


def layer_params(c: Dict, seed: int, layer) -> Dict:
    """One layer's leaves (``layer`` = None: the unstacked ones) exactly as
    :func:`model_params` draws them, returned in float32."""
    key = seed_key(seed)
    tree: Dict = {}
    for i, (path, shape, init, stacked) in enumerate(leaves(c)):
        if stacked != (layer is not None):
            continue
        k = jax.random.fold_in(key, i)
        if stacked:
            k = jax.random.fold_in(k, layer)
        v = _draw_f32(k, shape, init, jnp.dtype(c["torch_dtype"]))
        _set(tree, path[1:] if stacked else path, v)
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw_f32(key, shape, init, dtype):
    return _draw(key, shape, init, dtype).astype(jnp.float32)
