"""Pallas TPU kernel: 3x3 weighted stencil (HotSpot3D inner loop, paper §7.2.2).

The paper maps HotSpot3D onto the Edge TPU's ``conv2D`` instruction with a 3x3
kernel and no striding. On TPU we implement the stencil as a row-blocked Pallas
kernel: the wrapper materializes three row-shifted views (top/mid/bot) of the
zero-padded field so every grid step reads non-overlapping (bm, W+2) VMEM
blocks; the 3 column taps are static slices inside the block. This keeps the
working set in VMEM and turns the 9-tap stencil into fused VPU FMAs — the
memory-bound-optimal formulation (arithmetic intensity ~9 FLOP / 4 bytes).

The z-coupling of HotSpot3D (layer above/below + power density) is applied by
the caller as pairwise adds, exactly as the paper composes it from ``add``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BM = 256  # rows per block; W is kept whole (stencils are row-contiguous)


def _stencil_kernel(top_ref, mid_ref, bot_ref, w_ref, o_ref):
    w = w_ref[...]                          # (3, 3) in SMEM-like small block
    top, mid, bot = top_ref[...], mid_ref[...], bot_ref[...]
    Wp = mid.shape[1]
    acc = (
        top[:, 0:Wp - 2] * w[0, 0] + top[:, 1:Wp - 1] * w[0, 1] + top[:, 2:Wp] * w[0, 2]
        + mid[:, 0:Wp - 2] * w[1, 0] + mid[:, 1:Wp - 1] * w[1, 1] + mid[:, 2:Wp] * w[1, 2]
        + bot[:, 0:Wp - 2] * w[2, 0] + bot[:, 1:Wp - 1] * w[2, 1] + bot[:, 2:Wp] * w[2, 2]
    )
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def stencil3x3(
    x: jax.Array,        # (H, W) f32 field
    w: jax.Array,        # (3, 3) f32 stencil weights
    *,
    bm: int = BM,
    interpret: bool = False,
) -> jax.Array:
    H, W = x.shape
    Hp = ((H + bm - 1) // bm) * bm
    xp = jnp.pad(x, [(1, 1 + (Hp - H)), (1, 1)])       # halo + row-block padding
    top = xp[0:Hp, :]
    mid = xp[1:Hp + 1, :]
    bot = xp[2:Hp + 2, :]
    grid = (Hp // bm,)
    out = pl.pallas_call(
        _stencil_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, W + 2), lambda i: (i, 0)),
            pl.BlockSpec((bm, W + 2), lambda i: (i, 0)),
            pl.BlockSpec((bm, W + 2), lambda i: (i, 0)),
            pl.BlockSpec((3, 3), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Hp, W), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
    )(top, mid, bot, w.astype(jnp.float32))
    return out[:H]
