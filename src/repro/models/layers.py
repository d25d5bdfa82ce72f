"""Shared model layers: norms, activations, MLPs, RoPE / M-RoPE, init helpers.

Everything is functional: params are nested dicts of jnp arrays; init_* builds
them, apply functions consume them. Compute dtype is cfg.dtype (bf16 on TPU);
master params and norm math stay f32.

Named scopes: :func:`scoped` puts a layer's ops under one name in their op
metadata (``attn``, ``mlp``, ``moe``, ``logits``), which the device trace
carries as each op's ``tf_op`` path, e.g.
``jit(decode_step)/while/body/closed_call/attn/dot_general``; scopes change
no compiled program but its metadata.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import tensorizer as tz
from repro.distributed import sharding as shd


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, so
    every caller gets the scope. A scope is made per call: one
    ``named_scope`` used as a decorator keeps its entry state on itself,
    which two threads tracing at once would share."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def cdtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, in_axis: int = 0) -> jax.Array:
    """LeCun-normal init in f32 (master precision)."""
    fan_in = shape[in_axis]
    return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)


# ---------------------------------------------------------------------------
# Quantizable matmul: the Tensorizer integration point (DESIGN.md §4)
# ---------------------------------------------------------------------------

def pdot(x: jax.Array, w, cfg: ArchConfig) -> jax.Array:
    """Activation @ weight with the framework's precision policy.

    ``w`` is a plain array (training / quantize=off) or a ``QTensor`` produced
    by ``tensorizer.quantize_params`` (serving, quantize="serve") — in which
    case the contraction runs int8 x int8 with wide accumulation and fused
    dequant (the paper's technique as the serving fast path).

    Activations are calibrated per-ROW (amax over the contraction dim only),
    not per-tensor: a row's quantization scale must depend only on that row,
    or one slot's numerics shift with whatever else shares the decode batch —
    an idle slot's stale cache row changing another stream's sampled token.
    Per-row scales make serving batch-invariant (same stream, same tokens,
    regardless of co-residents or admission order), which is what lets a
    disaggregated continuation on another host stay bit-identical. The
    paper-faithful per-tensor calibration lives in ``tensorizer.qdot`` /
    ``qdot_paper`` for the accuracy benchmarks.
    """
    if isinstance(w, tz.QTensor):
        qx = tz.quantize(x.astype(jnp.float32), axis=(x.ndim - 1,))
        acc = jax.lax.dot_general(
            qx.q, w.q,
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return (acc.astype(jnp.float32) * (qx.scale * w.scale)).astype(cdtype(cfg))
    # preferred_element_type pins the output dtype even when XLA folds an
    # upstream f32->bf16 convert into the dot — otherwise the TP partial-sum
    # all-reduce after row-parallel matmuls silently runs at f32 (2x bytes;
    # found via HLO metadata in §Perf cell A)
    return jnp.dot(x, w.astype(cdtype(cfg)),
                   preferred_element_type=cdtype(cfg))


# ---------------------------------------------------------------------------
# bf16 gradient barrier (comm-dtype discipline)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def bf16_grad(x):
    """Identity forward; backward casts the cotangent to bf16 *before* it
    flows into the TP dgrad matmuls — keeping the big activation-gradient
    all-reduces in bf16 instead of f32 (halves §Perf cell A's collective
    bytes). Standard Megatron communication-precision discipline."""
    return x


def _bf16_grad_fwd(x):
    return x, x.dtype


def _bf16_grad_bwd(x_dtype, g):
    # truncate cotangent mantissa to bf16, keep the primal's dtype contract
    return (g.astype(jnp.bfloat16).astype(x_dtype),)


bf16_grad.defvjp(_bf16_grad_fwd, _bf16_grad_bwd)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, d: int) -> Dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Dict, x: jax.Array, cfg: ArchConfig, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.dtype(cfg.norm_dtype))
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


def rms_head_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """qk_norm (qwen3): RMS-normalize the last (head) dim of q/k."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d: int, f: int) -> Dict:
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "wi": dense_init(ks[0], (d, f)),
            "wg": dense_init(ks[1], (d, f)),
            "wo": dense_init(ks[2], (f, d)),
        }
    return {"wi": dense_init(ks[0], (d, f)), "wo": dense_init(ks[2], (f, d))}


def mlp_specs(cfg: ArchConfig) -> Dict:
    if cfg.act == "swiglu":
        return {"wi": P(None, "model"), "wg": P(None, "model"), "wo": P("model", None)}
    return {"wi": P(None, "model"), "wo": P("model", None)}


@scoped("mlp")
def apply_mlp(p: Dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    h = pdot(x, p["wi"], cfg)
    if cfg.act == "swiglu":
        h = jax.nn.silu(h) * pdot(x, p["wg"], cfg)
    else:
        h = jax.nn.gelu(h)
    h = shd.with_sharding(h, shd.batch_spec(*([None] * (h.ndim - 2)), "model"))
    return pdot(h, p["wo"], cfg)


# ---------------------------------------------------------------------------
# RoPE and M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                                   # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv          # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions3: jax.Array, theta: float, sections: Tuple[int, int, int]
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the rotary half-dims are split into
    (temporal, height, width) sections, each rotated by its own position id.

    x: (B, S, H, hd); positions3: (3, B, S).
    """
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, hd)
    inv = rope_freqs(hd, theta)                                   # (half,)
    # build per-frequency position: section s of the freq axis uses positions3[s]
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.array(sections), total_repeat_length=half
    )                                                             # (half,)
    pos = positions3.astype(jnp.float32)[sec_id]                  # (half, B, S): section gather
    ang = jnp.moveaxis(pos, 0, -1) * inv                          # (B, S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
