"""Pallas TPU kernel: int8-weight x f32-activation GEMV for the decode path.

Decode (one token per step) is *memory-roofline bound*: the whole weight matrix
streams HBM->VMEM per step while compute is a single row of MACs. The paper's
int8 tensorization therefore pays exactly 2x here (half the bytes of bf16
weights), which is the dominant-term optimization recorded in EXPERIMENTS.md
§Perf for the decode cells.

Layout: weights (K, N) int8 with per-output-channel scales; activations
(B, K) f32 (B = decode batch, small). Blocks stream N in bn-wide stripes with
the full K resident — for LM d_model up to ~6k, a (K x 256) int8 stripe is
~1.5 MiB, well within VMEM, and B x K activations are reused across stripes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BN = 256


def _qgemv_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[...]                                    # (B, K) f32
    w = w_ref[...].astype(jnp.float32)                # (K, bn) int8 -> f32 on VREGs
    o_ref[...] = (x @ w) * s_ref[...]                 # dequant epilogue


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def qgemv(
    x: jax.Array,         # (B, K) f32 activations
    w_q: jax.Array,       # (K, N) int8 weights
    scale: jax.Array,     # (N,) f32 per-channel dequant scales
    *,
    bn: int = BN,
    interpret: bool = False,
) -> jax.Array:
    B, K = x.shape
    K2, N = w_q.shape
    assert K == K2 and N % bn == 0, (x.shape, w_q.shape, bn)
    grid = (N // bn,)
    return pl.pallas_call(
        _qgemv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, K), lambda j: (0, 0)),
            pl.BlockSpec((K, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((B, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(x, w_q, scale.reshape(1, N))
