"""Pallas TPU kernel: W8A8 int8 GEMM with int32 MXU accumulation + fused dequant.

This is the compute hot-spot of the paper's technique mapped to TPU v5e: the
Edge TPU's 128x128x8-bit systolic array (paper §3.3 — "the Edge TPU's matrix
unit is designed for computing on 128x128x8-bit matrices") corresponds exactly
to the v5e MXU, which runs int8 at 394 TOPS (2x bf16). BlockSpec tiling keeps
an (bm x bk) activation tile, a (bk x bn) weight tile and an (bm x bn) int32
accumulator resident in VMEM; the K-loop is the innermost ("arbitrary") grid
dimension so the accumulator never round-trips to HBM; dequantization happens
once per output tile in the epilogue (the paper's "aggregate in wider
registers", §6.2.1, fused on-chip).

Two variants:
  * ``qgemm``              — per-output-channel weight scales (production W8A8)
  * ``qgemm_tile_scales``  — per-128x128-tile scales for both operands (the
                             Tensorizer's blocked calibration, paper §6.2.1)

Validated against ``ref.py`` oracles in interpret mode (CPU container); on a
real TPU the same code lowers to MXU ops. Block shapes are hardware-aligned:
multiples of 128 in both MXU dims; int8 minor tiling (32, 128) divides them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# MXU-aligned defaults. bk=512 amortizes the accumulator epilogue; VMEM use:
# bm*bk + bk*bn (int8) + bm*bn*4 (int32 acc) = 128*512*2 + 128*128*4 ≈ 196 KiB.
BM, BN, BK = 128, 128, 512


def _qgemm_kernel(a_ref, b_ref, sb_ref, o_ref, acc_ref, *, nk: int):
    """Grid = (M/bm, N/bn, K/bk); K is the sequential (arbitrary) axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        # fused dequant: int32 accumulator -> f32, scaled per output channel
        o_ref[...] = acc_ref[...].astype(jnp.float32) * sb_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def qgemm(
    a_q: jax.Array,          # (M, K) int8 activations
    b_q: jax.Array,          # (K, N) int8 weights
    sb: jax.Array,           # (N,) f32 combined scale (sa * per-channel sb)
    *,
    bm: int = BM,
    bn: int = BN,
    bk: int = BK,
    interpret: bool = False,
) -> jax.Array:
    M, K = a_q.shape
    K2, N = b_q.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"shapes must be block-aligned: {a_q.shape} @ {b_q.shape} vs ({bm},{bn},{bk})"
    )
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_qgemm_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],  # int32 accumulator tile
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(a_q, b_q, sb.reshape(1, N))


def _qgemm_tile_scales_kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref, *, nk: int):
    """Per-tile dequant: partial products are scaled by sa[i,k]*sb[k,j] *before*
    accumulation (scales differ along K), accumulator is f32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    partial_i32 = jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc_ref[...] += partial_i32.astype(jnp.float32) * (sa_ref[0, 0] * sb_ref[0, 0])

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def qgemm_tile_scales(
    a_q: jax.Array,          # (M, K) int8, tile-quantized
    b_q: jax.Array,          # (K, N) int8, tile-quantized
    sa: jax.Array,           # (M/128, K/128) f32 per-tile scales of a
    sb: jax.Array,           # (K/128, N/128) f32 per-tile scales of b
    *,
    interpret: bool = False,
) -> jax.Array:
    t = 128
    M, K = a_q.shape
    _, N = b_q.shape
    assert M % t == 0 and N % t == 0 and K % t == 0
    nk = K // t
    grid = (M // t, N // t, nk)
    return pl.pallas_call(
        functools.partial(_qgemm_tile_scales_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t, t), lambda i, j, k: (i, k)),
            pl.BlockSpec((t, t), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((t, t), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)],  # f32 accumulator tile
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(a_q, b_q, sa, sb)
