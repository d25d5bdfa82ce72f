"""Attention execution-path variants: chunked vs plain, bf16acc internals,
SP (query-sharded) attention, int8 KV cache — the §Perf knobs must preserve
semantics — and the grouped cache contraction every decode, verify and
prefill-with-cache path shares."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import forward, init_model, serve
from repro.models.attention import (
    NEG_INF, _cached_attend, _chunked_attention, _dequant, _plain_attention,
    _quantize_kv,
)


def _setup(arch="tinyllama_1_1b", **overrides):
    cfg = get_config(arch).smoke().replace(remat=False, **overrides)
    params = init_model(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, cfg.vocab)
    return cfg, params, toks


def test_bf16acc_close_to_f32():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
    f32 = _chunked_attention(q, k, v, causal=True, chunk=16, impl="f32")
    b16 = _chunked_attention(q, k, v, causal=True, chunk=16, impl="bf16acc")
    np.testing.assert_allclose(np.asarray(b16), np.asarray(f32), rtol=0.05, atol=0.05)


def test_forward_same_across_attn_impls():
    """Model logits must agree between f32 and bf16acc chunked paths (S=32 >
    smoke attn_chunk=16 -> chunked path exercised)."""
    cfg, params, toks = _setup()
    l_f32, _ = forward(params, cfg, {"tokens": toks})
    cfg2 = cfg.replace(attn_impl="bf16acc")
    l_b16, _ = forward(params, cfg2, {"tokens": toks})
    np.testing.assert_allclose(
        np.asarray(l_b16, np.float32), np.asarray(l_f32, np.float32),
        rtol=0.2, atol=0.2)


def test_forward_same_with_attn_sp():
    """SP attention (query sharding) is a pure re-layout on 1 device."""
    cfg, params, toks = _setup()
    l_base, _ = forward(params, cfg, {"tokens": toks})
    l_sp, _ = forward(params, cfg.replace(attn_sp=True), {"tokens": toks})
    np.testing.assert_allclose(
        np.asarray(l_sp, np.float32), np.asarray(l_base, np.float32),
        rtol=1e-2, atol=1e-2)


def test_mrope_chunked_path():
    cfg = get_config("qwen2_vl_2b").smoke().replace(remat=False)
    params = init_model(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = {"embeds": jnp.ones((B, S, cfg.d_model), jnp.bfloat16),
             "positions3": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (3, B, S))}
    logits, _ = forward(params, cfg, batch)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.slow
def test_int8_kv_cache_decode_accuracy():
    cfg, params, toks = _setup()
    full_logits, _ = forward(params, cfg, {"tokens": toks})
    cfg8 = cfg.replace(kv_cache_dtype="int8")
    cache = serve.init_cache(cfg8, 2, 32)
    assert cache["k"].dtype == jnp.int8
    for t in range(32):
        dl, cache = serve.decode(params, cfg8, cache, {"tokens": toks[:, t:t + 1]})
    err = np.abs(np.asarray(dl[:, 0], np.float32)
                 - np.asarray(full_logits[:, -1], np.float32)).max()
    assert err < 0.5, err


def test_long_context_decode_ssm_constant_state():
    """SSM decode state size is independent of context length (the
    sub-quadratic property that qualifies xlstm/zamba2 for long_500k)."""
    cfg = get_config("xlstm_125m").smoke()
    c_small = serve.init_cache(cfg, 2, 128)
    c_large = serve.init_cache(cfg, 2, 4096)
    for k in ("mlstm_C", "slstm_c"):
        assert c_small[k].shape == c_large[k].shape  # no seq dimension


def _expand_and_upcast(q, k, v, valid, hd):
    """The cache contraction as it was before the grouped one: K/V repeated
    to all H heads and upcast to float32 on both sides of both einsums."""
    rep = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, rep, axis=2).astype(jnp.float32)
    v = jnp.repeat(v, rep, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) * (hd ** -0.5)
    s = jnp.where(valid[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def _ordered_bf16(x):
    """bf16 bit patterns as integers in the order of the values, so that
    adjacent bf16 numbers differ by one (across zero too)."""
    b = np.asarray(x.astype(jnp.bfloat16)).view(np.uint16).astype(np.int32)
    return np.where(b & 0x8000, -(b & 0x7FFF), b)


@pytest.mark.parametrize("sq", [1, 4], ids=["decode", "window"])
@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])
@pytest.mark.parametrize("kv_dtype", ["float", "int8"])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2), (4, 1)],
                         ids=["mha", "gqa", "mqa"])
def test_grouped_cache_attend_matches_expand_and_upcast(heads, kv_dtype,
                                                        per_row, sq):
    """The grouped contraction reads the cache in its own dtype; the old
    formula expanded it to H float32 heads. Products of bf16 values are
    exact in float32 and the probabilities stay float32, so only the order
    of the float32 sums may differ."""
    (H, KV), B, S, hd = heads, 3, 40, 16
    ks = jax.random.split(jax.random.PRNGKey(H + 10 * KV + 100 * sq), 3)
    q = jax.random.normal(ks[0], (B, sq, H, hd)).astype(jnp.bfloat16)
    k = (2 * jax.random.normal(ks[1], (B, S, KV, hd))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(jnp.bfloat16)
    if kv_dtype == "int8":
        k_q, v_q, k_sc, v_sc = _quantize_kv(k, v)
        k, v = _dequant(k_q, k_sc, jnp.bfloat16), _dequant(v_q, v_sc, jnp.bfloat16)
    index = jnp.array([0, 17, 36]) if per_row else jnp.array(20)
    positions = jnp.atleast_1d(index)[:, None] + jnp.arange(sq)
    valid = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    new = jax.jit(_cached_attend, static_argnums=4)(q, k, v, valid, hd)
    ref = jax.jit(_expand_and_upcast, static_argnums=4)(q, k, v, valid, hd)
    assert new.dtype == ref.dtype == jnp.float32
    assert new.shape == ref.shape == (B, sq, H, hd)
    new, ref = np.asarray(new), np.asarray(ref)
    assert np.abs(new - ref).max() <= 2e-6 * np.abs(ref).max()
    assert np.abs(_ordered_bf16(new) - _ordered_bf16(ref)).max() <= 1


@pytest.mark.parametrize("n_kv", [2, 8], ids=["gqa", "mha"])
def test_decode_reads_the_cache_unexpanded(n_kv):
    """Decode, lowered for the TPU (no chip needed), holds cache rows
    (B, S, *, hd) only as the cache stores them: KV heads in the cache
    dtype, never H heads or float32. The MHA case, where H == KV, shows the
    check passes the cache itself. Lowered for the CPU instead, JAX would
    convert both operands of the mixed-dtype value dot to float32."""
    cfg = get_config("starcoder2-15b").smoke().replace(n_heads=8, n_kv=n_kv)
    B, S, hd = 3, 40, cfg.hd
    params = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: serve.init_cache(cfg, B, S,
                                                    per_slot_index=True))
    step = jax.jit(lambda p, c, t: serve.decode(p, cfg, c, {"tokens": t}))
    text = step.trace(params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32)
                      ).lower(lowering_platforms=("tpu",)).as_text()
    rows = set(re.findall(rf"tensor<{B}x{S}x(\d+)x{hd}x(\w+)>", text))
    assert rows == {(str(n_kv), "bf16")}, rows
