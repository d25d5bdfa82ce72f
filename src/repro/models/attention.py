"""Attention: GQA/MQA/MHA with RoPE / M-RoPE / qk_norm, three execution paths.

  * plain      — materialized scores; used below ``cfg.attn_chunk`` seq len
  * chunked    — online-softmax scan over KV chunks (flash-style, O(S·C) memory
                 instead of O(S^2)); the train_4k / prefill_32k path
  * decode     — single-query attention against a (possibly sequence-sharded)
                 KV cache; softmax reductions over the sharded seq dim are
                 GSPMD-partitioned (SP for the 32k/500k decode cells)

plus the serving-admission and paged-serving variants:
  * ``prefill_attention_with_kv`` — the fused admission path: decode-mirrored
    full-sequence attention that also emits the cache-layout K/V entries
    (float or int8+scales) so one prefill forward can seed a serving slot
  * ``chunked_prefill_attention_with_kv`` — the long-prompt admission path:
    one fixed-width chunk attending over the accumulated rows, (B,H,W,S)
    scores instead of (B,H,S,S), bit-identical to the single-shot path
  * ``paged_decode_attention`` — block-native decode over the paged block
    pool through per-slot tables (no gather-bridge view), bit-identical to
    ``decode_attention`` on the gathered view; optional Pallas kernel path
    (kernels/paged_attention.py)

Sharding: q/k/v heads constrained to the ``model`` axis when
``cfg.shard_heads`` (TP); KV caches shard (batch->data, heads->model) and for
long-context cells additionally sequence->data.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.models import layers as L

NEG_INF = -1e30


def init_attn(key, cfg: ArchConfig, d: int) -> Dict:
    ks = jax.random.split(key, 5)
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    p = {
        "wq": L.dense_init(ks[0], (d, H * hd)),
        "wk": L.dense_init(ks[1], (d, KV * hd)),
        "wv": L.dense_init(ks[2], (d, KV * hd)),
        "wo": L.dense_init(ks[3], (H * hd, d)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.hd,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.hd,), jnp.float32)
    return p


def attn_specs(cfg: ArchConfig) -> Dict:
    # Param sharding is decoupled from activation head-sharding: the flat
    # projection columns (H*hd) divide the model axis even when the head
    # count doesn't (qwen3: 40 heads but 5120 columns), so weights always
    # shard; only the activation layout (_heads_spec / attn_sp) is gated.
    m = "model"
    p = {"wq": P(None, m), "wk": P(None, m), "wv": P(None, m), "wo": P(m, None)}
    if cfg.qk_norm:
        p["q_norm"] = P(None)
        p["k_norm"] = P(None)
    return p


def _divisible_model(n: int) -> bool:
    try:
        return n % shd.model_parallel_size() == 0
    except RuntimeError:
        return True


def _heads_spec(cfg: ArchConfig, n_heads: Optional[int] = None) -> P:
    """Head-axis sharding, only when the head count divides the model axis —
    uneven head sharding triggers GSPMD involuntary full rematerialization
    (replicate-then-reshard), observed in the dry-run. Non-divisible archs
    (qwen3 40H, qwen2-vl 12H, xlstm 4H) replicate heads (see §Perf)."""
    n = cfg.n_heads if n_heads is None else n_heads
    m = "model" if (cfg.shard_heads and _divisible_model(n)) else None
    return shd.batch_spec(None, m, None)


def _project_qkv(
    p: Dict, x: jax.Array, cfg: ArchConfig,
    positions: Optional[jax.Array], positions3: Optional[jax.Array],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = L.pdot(x, p["wq"], cfg).reshape(B, S, H, hd)
    k = L.pdot(x, p["wk"], cfg).reshape(B, S, KV, hd)
    v = L.pdot(x, p["wv"], cfg).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = L.rms_head_norm(q, p["q_norm"])
        k = L.rms_head_norm(k, p["k_norm"])
    if cfg.rope_kind == "mrope":
        assert positions3 is not None, "mrope requires (3,B,S) positions"
        q = L.apply_mrope(q, positions3, cfg.rope_theta, cfg.mrope_sections)
        k = L.apply_mrope(k, positions3, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_kind == "rope":
        assert positions is not None
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    q = shd.with_sharding(q, _heads_spec(cfg))
    k = shd.with_sharding(k, _heads_spec(cfg, cfg.n_kv))
    v = shd.with_sharding(v, _heads_spec(cfg, cfg.n_kv))
    return q, k, v


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """GQA: repeat kv heads to match q heads (B, S, KV, hd) -> (B, S, H, hd)."""
    B, S, KV, hd = k.shape
    rep = n_heads // KV
    return jnp.repeat(k, rep, axis=2) if rep > 1 else k


def _dequant(c: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """int8 KV entries (..., KV, hd) x per-token/per-head scales (..., KV)
    back to the activation dtype, as every cache-attention path reads them."""
    return (c.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _table_rows(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """One layer's pool (n_blocks, block_size, ...) read through per-slot
    block tables (B, MB) as contiguous rows (B, MB*block_size, ...)."""
    B, MB = tables.shape
    rows = jnp.take(pool, tables.reshape(-1), axis=0)
    return rows.reshape(B, MB * pool.shape[1], *pool.shape[2:])


def _cached_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                   valid: jax.Array, hd: int) -> jax.Array:
    """Queries (B, Sq, H, hd) over cache rows (B, S, KV, hd) under the mask
    ``valid`` (B or 1, Sq or 1, S); returns the float32 output (B, Sq, H, hd).

    The one contraction every cache-attention path shares, so decode,
    verify, paged and fused/chunked prefill agree bit for bit by
    construction. Grouped: each KV head is contracted against its
    G = H // KV query heads straight from the cache dtype, with float32
    accumulation (a bf16 x bf16 product is exact in float32), so no H-head
    or float32 copy of the cache is made. The float32 probabilities meet
    the cache-dtype values in one mixed-dtype dot at HIGHEST precision,
    which keeps them float32 on the TPU. (Splitting them into bf16 pieces
    by hand does not: XLA's TPU compiler may drop the f32 -> bf16 -> f32
    round trip that makes the pieces, leaving bf16 probabilities.)"""
    B, Sq, H, _ = q.shape
    KV = k.shape[2]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.reshape(B, Sq, KV, H // KV, hd), k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None], s * (hd ** -0.5), NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    # (B, KV, G, Sq, S) x (B, S, KV, hd): contract S, batch B and KV
    o = jax.lax.dot_general(w, v, (((4,), (1,)), ((0, 1), (0, 2))),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)


def _quantize_kv(k_new: jax.Array, v_new: jax.Array):
    """Tensorizer int8 KV-cache quantization: per-token / per-head amax scales
    (exact per-position calibration — no cross-step rescaling). The SINGLE
    definition shared by decode_attention and prefill_attention_with_kv: the
    fused-admission bit-identity contract (tests/test_serving.py) requires the
    two paths to quantize identically, epsilon and all.

    Returns (k_q, v_q, k_scale, v_scale): int8 entries (..., KV, hd) and f32
    dequant scales (..., KV)."""
    k_sc = jnp.max(jnp.abs(k_new.astype(jnp.float32)), axis=-1) / 127.0 + 1e-12
    v_sc = jnp.max(jnp.abs(v_new.astype(jnp.float32)), axis=-1) / 127.0 + 1e-12
    k_q = jnp.clip(jnp.round(k_new.astype(jnp.float32) / k_sc[..., None]), -127, 127).astype(jnp.int8)
    v_q = jnp.clip(jnp.round(v_new.astype(jnp.float32) / v_sc[..., None]), -127, 127).astype(jnp.int8)
    return k_q, v_q, k_sc, v_sc


def _plain_attention(q, k, v, causal: bool, q_offset: int = 0) -> jax.Array:
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * (hd ** -0.5)
    if causal:
        qpos = jnp.arange(Sq)[:, None] + q_offset
        kpos = jnp.arange(Sk)[None, :]
        scores = jnp.where(kpos <= qpos, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def _chunked_attention(q, k, v, causal: bool, chunk: int, unroll: bool = False,
                       impl: str = "f32") -> jax.Array:
    """Online-softmax scan over KV chunks (flash-style).

    impl="f32": all internals f32 (the conservative baseline).
    impl="bf16acc": q/k/v and the probability matrix stay bf16; only the
    softmax statistics (m, l) and the output accumulator are f32 — the TPU
    flash-attention recipe. Halves the bytes of the two big streams (scores
    inputs and p), measured in §Perf.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bf16 = impl == "bf16acc"
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    if bf16:
        qf = (q.astype(jnp.float32) * (hd ** -0.5)).astype(jnp.bfloat16)
    else:
        qf = q.astype(jnp.float32) * (hd ** -0.5)
    qpos = jnp.arange(Sq)[:, None]

    def step(carry, inputs):
        m, l, o = carry                       # (B,H,Sq,1), (B,H,Sq,1), (B,Sq,H,hd)
        ci, (kb, vb) = inputs
        kb_c = kb if bf16 else kb.astype(jnp.float32)
        # bf16 inputs with f32 accumulation (MXU-native mixed precision)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb_c,
                       preferred_element_type=jnp.float32)
        kpos = ci * chunk + jnp.arange(chunk)[None, :]
        mask = (kpos < Sk) if not causal else ((kpos <= qpos) & (kpos < Sk))
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = (p.astype(jnp.bfloat16) if bf16 else p)
        vb_c = vb if bf16 else vb.astype(jnp.float32)
        o_new = o * corr.squeeze(-1).transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", pv, vb_c,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, H, Sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq, 1), jnp.float32)
    o0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        step, (m0, l0, o0), (jnp.arange(n_chunks), (kc, vc)),
        unroll=True if unroll else 1,   # exact-cost mode for the dry-run
    )
    o = o / jnp.maximum(l.squeeze(-1).transpose(0, 2, 1)[..., None], 1e-30)
    return o.astype(q.dtype)


@L.scoped("attn")
def attention(
    p: Dict,
    x: jax.Array,
    cfg: ArchConfig,
    *,
    positions: Optional[jax.Array] = None,
    positions3: Optional[jax.Array] = None,
    causal: bool = True,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None,   # cross-attention K/V source
) -> jax.Array:
    """Full-sequence attention (train / prefill). ``kv`` overrides K/V for
    cross-attention (enc-dec); cross-attention is non-causal."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    if kv is not None:
        k, v = kv
        causal = False
    k = _expand_kv(k, cfg.n_heads)
    v = _expand_kv(v, cfg.n_heads)
    if cfg.attn_sp and S > cfg.attn_chunk:
        # SP attention: shard *queries* over 'model' (for archs whose head
        # count doesn't divide the axis — qwen2-vl 12H, qwen3 40H); K/V stay
        # replicated over model, every device computes all heads for S/16
        # query rows. Even work split where head sharding can't be.
        q = shd.with_sharding(q, shd.batch_spec("model", None, None))
    if max(S, k.shape[1]) > cfg.attn_chunk:
        o = _chunked_attention(q, k, v, causal, cfg.attn_chunk,
                               unroll=cfg.scan_unroll, impl=cfg.attn_impl)
    else:
        o = _plain_attention(q, k, v, causal)
    if cfg.attn_sp and S > cfg.attn_chunk:
        o = shd.with_sharding(o, shd.batch_spec("model", None, None))
    o = shd.with_sharding(o, _heads_spec(cfg))
    out = L.pdot(o.reshape(B, S, cfg.n_heads * cfg.hd), p["wo"], cfg)
    return out


@L.scoped("attn")
def prefill_attention_with_kv(
    p: Dict,
    x: jax.Array,                 # (B, S, D) prompt activations
    cfg: ArchConfig,
    *,
    positions: jax.Array,
    positions3: Optional[jax.Array] = None,
    int8_kv: bool = False,
) -> Tuple[jax.Array, ...]:
    """Full-sequence causal attention that also returns this layer's K/V rows
    exactly as the decode cache stores them (fused prefill-with-cache).

    Returns ``(out, k_entry, v_entry)`` with entries in the cache dtype, or
    ``(out, k_q, v_q, k_scale, v_scale)`` on the int8-KV path — shapes
    (B, S, KV, hd) and (B, S, KV), ready to stack into the (L, B, S, KV, hd)
    cache layout and scatter into serving slot rows.

    The math deliberately mirrors :func:`decode_attention` bit-for-bit rather
    than reusing :func:`attention`'s plain/chunked paths: both run
    :func:`_cached_attend` against the *cache-dtype* K/V (int8 entries are
    quantized with the same per-token/per-head amax scales and dequantized
    before use, exactly as decode reads them back). That makes a cache seeded
    from these entries continue decoding with the identical token stream the
    B=1 prompt-replay seeding produced — the fused-admission bit-identity
    guarantee asserted in tests/test_serving.py.

    Memory: materializes the (B, H, S, S) f32 score matrix (the rounding
    anchor is decode's full-row softmax, which the chunked/online-softmax
    kernel does not reproduce bitwise). S here is an admission bucket — the
    engine bounds it by ``max_seq_len`` (slot-row length) at construction —
    not the 32k-class training/prefill sequence lengths, which keep using
    :func:`attention`'s chunked path. Paged long-prompt admission is the
    ROADMAP item.
    """
    B, S, _ = x.shape
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, positions3)
    if int8_kv:
        k_q, v_q, k_sc, v_sc = _quantize_kv(k_new, v_new)
        k, v = _dequant(k_q, k_sc, x.dtype), _dequant(v_q, v_sc, x.dtype)
        entries: Tuple[jax.Array, ...] = (k_q, v_q, k_sc, v_sc)
    else:
        k = k_new.astype(L.cdtype(cfg))
        v = v_new.astype(L.cdtype(cfg))
        entries = (k, v)
    valid = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None]
    o = _cached_attend(q, k, v, valid, cfg.hd).astype(x.dtype)
    out = L.pdot(o.reshape(B, S, cfg.n_heads * cfg.hd), p["wo"], cfg)
    return (out,) + entries


@L.scoped("attn")
def chunked_prefill_attention_with_kv(
    p: Dict,
    x: jax.Array,                 # (B, W, D) one prompt chunk's activations
    cfg: ArchConfig,
    *,
    positions: jax.Array,         # (B, W) absolute positions of this chunk
    chunk_start,                  # () int32 — first absolute position (traced)
    k_acc: jax.Array,             # (B, S, KV, hd) cache-layout accumulator
    v_acc: jax.Array,
    k_sc_acc: Optional[jax.Array] = None,   # (B, S, KV) int8-KV scales
    v_sc_acc: Optional[jax.Array] = None,
    int8_kv: bool = False,
) -> Tuple[jax.Array, ...]:
    """One chunk of the chunked prefill-with-cache: project this chunk's
    K/V into the accumulated cache rows at ``chunk_start`` and attend the
    chunk's queries over everything written so far — already-written chunks
    plus the chunk itself, under the absolute causal mask.

    Returns ``(out, k_acc, v_acc)`` (+ scale accumulators on the int8 path)
    with the accumulators updated in place (``dynamic_update_slice``).

    Bit-identity with :func:`prefill_attention_with_kv` (the single-shot
    fused path) is the contract, and it is structural: the accumulator rows
    carry exactly the single-shot path's cache-dtype entries at written
    positions and zeros beyond the writing frontier; scores against the
    unwritten tail are masked to NEG_INF by the same absolute causal mask
    (``kpos <= qpos``: every unwritten position is in some future chunk,
    hence past every current query), so each query's softmax row is the
    single-shot row — same length S, same values, exact-zero tail — and the
    value contraction adds exact-zero terms for the tail. The score matrix
    is (B, H, W, S) per chunk instead of (B, H, S, S): peak prefill memory
    drops from quadratic to linear in S, which is what lets 32k-class
    prompts admit (models/serve.py ``prefill_with_cache_chunked``)."""
    B, W, _ = x.shape
    S = k_acc.shape[1]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, None)
    if int8_kv:
        k_q, v_q, k_sc, v_sc = _quantize_kv(k_new, v_new)
        k_acc = jax.lax.dynamic_update_slice(k_acc, k_q, (0, chunk_start, 0, 0))
        v_acc = jax.lax.dynamic_update_slice(v_acc, v_q, (0, chunk_start, 0, 0))
        k_sc_acc = jax.lax.dynamic_update_slice(k_sc_acc, k_sc, (0, chunk_start, 0))
        v_sc_acc = jax.lax.dynamic_update_slice(v_sc_acc, v_sc, (0, chunk_start, 0))
        k, v = _dequant(k_acc, k_sc_acc, x.dtype), _dequant(v_acc, v_sc_acc, x.dtype)
    else:
        cache_dt = L.cdtype(cfg)
        k_acc = jax.lax.dynamic_update_slice(
            k_acc, k_new.astype(cache_dt), (0, chunk_start, 0, 0))
        v_acc = jax.lax.dynamic_update_slice(
            v_acc, v_new.astype(cache_dt), (0, chunk_start, 0, 0))
        k, v = k_acc, v_acc
    valid = (jnp.arange(S)[None, :] <= chunk_start + jnp.arange(W)[:, None])[None]
    o = _cached_attend(q, k, v, valid, cfg.hd).astype(x.dtype)
    out = L.pdot(o.reshape(B, W, cfg.n_heads * cfg.hd), p["wo"], cfg)
    if int8_kv:
        return out, k_acc, v_acc, k_sc_acc, v_sc_acc
    return out, k_acc, v_acc


def project_kv_for_cross(p: Dict, enc_out: jax.Array, cfg: ArchConfig):
    """Pre-compute cross-attention K/V from encoder output (cached at prefill)."""
    B, S, _ = enc_out.shape
    k = L.pdot(enc_out, p["wk"], cfg).reshape(B, S, cfg.n_kv, cfg.hd)
    v = L.pdot(enc_out, p["wv"], cfg).reshape(B, S, cfg.n_kv, cfg.hd)
    return k, v


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

def gather_block_kv(pool: Dict, tables: jax.Array) -> Dict:
    """Block-paged K/V gather (serving/store.py PagedKVStore): pool leaves
    (L, n_blocks, block_size, ...) + per-slot block tables (B, MB) -> the
    contiguous view (L, B, MB*block_size, ...) that :func:`decode_attention`
    consumes — every slot's blocks concatenated in table order, one
    ``jnp.take`` over the block axis per leaf (XLA lowers it to a single
    dynamic-gather; rows stay block-aligned so the copy is contiguous per
    block). Table entries past a slot's lease point at the reserved null
    block 0; those view positions sit beyond the slot's causal horizon, where
    decode masks scores to -inf and the softmax weight is exactly 0."""
    B, MB = tables.shape
    flat = tables.reshape(-1)
    out = {}
    for name, leaf in pool.items():
        bs = leaf.shape[2]
        g = jnp.take(leaf, flat, axis=1)                   # (L, B*MB, bs, ...)
        out[name] = g.reshape(leaf.shape[0], B, MB * bs, *leaf.shape[3:])
    return out


def cache_spec(cfg: ArchConfig, seq_shard: bool) -> P:
    """Cache (B, S, KV, hd): batch->data axes, seq->data when SP (long ctx,
    batch too small to shard), heads->model when divisible."""
    names = ()
    try:
        names = shd.axis_names()
    except RuntimeError:
        pass
    model = "model" if ("model" in names and cfg.shard_heads and _divisible_model(cfg.n_kv)) else None
    if seq_shard:
        return P(None, "data", model, None)
    b = shd.batch_axes()
    lead = b if len(b) > 1 else (b[0] if b else None)
    return P(lead, None, model, None)


def init_cache(cfg: ArchConfig, n_layers: int, batch: int, seq: int, dtype) -> Dict:
    return {
        "k": jnp.zeros((n_layers, batch, seq, cfg.n_kv, cfg.hd), dtype),
        "v": jnp.zeros((n_layers, batch, seq, cfg.n_kv, cfg.hd), dtype),
        "index": jnp.zeros((), jnp.int32),
    }


@L.scoped("attn")
def decode_attention(
    p: Dict,
    x: jax.Array,                 # (B, 1, D) current token
    cache_k: jax.Array,           # (B, S, KV, hd)
    cache_v: jax.Array,
    index: jax.Array,             # () int32 — number of valid cache entries;
                                  # or (B,) int32 — per-row (slot) positions,
                                  # the continuous-batching serving layout
    cfg: ArchConfig,
    *,
    positions: Optional[jax.Array] = None,
    positions3: Optional[jax.Array] = None,
    update_cache: bool = True,
    cache_scales: Optional[Tuple[jax.Array, jax.Array]] = None,  # (B,S,KV) x2
) -> Tuple[jax.Array, ...]:
    """One-token attention against the cache. Returns (out, new_k, new_v
    [, new_k_scale, new_v_scale]).

    The softmax reduction runs over the cache's (possibly sharded) seq dim —
    GSPMD partitions the max/sum (the SP decode path for 32k/500k cells).

    A vector ``index`` gives every batch row its own write position and causal
    horizon (requests admitted at different times share one decode batch —
    serving/engine.py); writes then go through a per-row scatter
    (``dynamic_update_slice`` needs a batch-uniform start), touching O(B)
    cache rows per step. mode="drop" skips rows whose index is out of range
    (idle serving slots whose position ran past the cache).

    ``cache_scales`` enables the Tensorizer int8 KV cache: entries are stored
    int8 with a *per-token, per-head* scale (exact per-position calibration —
    no cross-step rescaling), halving the dominant decode-bandwidth stream.
    """
    B, _, _ = x.shape
    S = cache_k.shape[1]
    index = jnp.asarray(index)
    per_row = index.ndim == 1
    if per_row:
        rows = jnp.arange(B)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, positions3)
    int8_cache = cache_scales is not None
    if int8_cache:
        ks, vs = cache_scales
        k_q, v_q, k_sc, v_sc = _quantize_kv(k_new, v_new)
        if update_cache and per_row:
            cache_k = cache_k.at[rows, index].set(k_q[:, 0], mode="drop")
            cache_v = cache_v.at[rows, index].set(v_q[:, 0], mode="drop")
            ks = ks.at[rows, index].set(k_sc[:, 0], mode="drop")
            vs = vs.at[rows, index].set(v_sc[:, 0], mode="drop")
        elif update_cache:
            cache_k = jax.lax.dynamic_update_slice(cache_k, k_q, (0, index, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(cache_v, v_q, (0, index, 0, 0))
            ks = jax.lax.dynamic_update_slice(ks, k_sc, (0, index, 0))
            vs = jax.lax.dynamic_update_slice(vs, v_sc, (0, index, 0))
        k, v = _dequant(cache_k, ks, x.dtype), _dequant(cache_v, vs, x.dtype)
    else:
        if update_cache and per_row:
            cache_k = cache_k.at[rows, index].set(
                k_new[:, 0].astype(cache_k.dtype), mode="drop")
            cache_v = cache_v.at[rows, index].set(
                v_new[:, 0].astype(cache_v.dtype), mode="drop")
        elif update_cache:
            cache_k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype), (0, index, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype), (0, index, 0, 0))
        k, v = cache_k, cache_v
    horizon = index[:, None, None] if per_row else index
    valid = jnp.arange(S)[None, None, :] <= horizon           # causal: <= current
    o = _cached_attend(q, k, v, valid, cfg.hd).astype(x.dtype)
    out = L.pdot(o.reshape(B, 1, cfg.n_heads * cfg.hd), p["wo"], cfg)
    if int8_cache:
        return out, cache_k, cache_v, ks, vs
    return out, cache_k, cache_v


@L.scoped("attn")
def verify_decode_attention(
    p: Dict,
    x: jax.Array,                 # (B, W, D) window: last token + k draft tokens
    cache_k: jax.Array,           # (B, S, KV, hd)
    cache_v: jax.Array,
    index: jax.Array,             # (B,) int32 per-slot window start positions
    cfg: ArchConfig,
    *,
    positions: jax.Array,         # (B, W) absolute positions = index + arange(W)
    cache_scales: Optional[Tuple[jax.Array, jax.Array]] = None,  # (B,S,KV) x2
) -> Tuple[jax.Array, ...]:
    """Speculative-verify attention: W tokens per row scored in ONE forward,
    each against the same cache row sequential decode would have seen.

    All W new K/V entries are scattered into the cache rows *before* the
    contraction (positions ``index[b]+j``, mode="drop" for rows past the
    slot extent), and the causal horizon is per-query: query j attends
    ``kpos <= index + j``, so entries written for later window positions are
    masked to NEG_INF (exact-zero softmax weight) exactly as if they had not
    been written yet. The visible entries are the same bits sequential
    :func:`decode_attention` steps would have produced (same `_project_qkv`
    / `_quantize_kv` math per position), so the (B, H, W, S) score rows are
    the (B, H, 1, S) decode rows stacked — the speculative==plain
    bit-identity contract (tests/test_speculative.py).

    Rejected-window entries are real writes; the engine scrubs them back to
    pristine via the store rollback after acceptance (serving/store.py).

    Returns (out, new_k, new_v[, new_k_scale, new_v_scale])."""
    B, W, _ = x.shape
    S = cache_k.shape[1]
    rows = jnp.arange(B)
    index = jnp.asarray(index)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, None)
    int8_cache = cache_scales is not None
    if int8_cache:
        ks, vs = cache_scales
        k_q, v_q, k_sc, v_sc = _quantize_kv(k_new, v_new)
        cache_k = cache_k.at[rows[:, None], positions].set(k_q, mode="drop")
        cache_v = cache_v.at[rows[:, None], positions].set(v_q, mode="drop")
        ks = ks.at[rows[:, None], positions].set(k_sc, mode="drop")
        vs = vs.at[rows[:, None], positions].set(v_sc, mode="drop")
        k, v = _dequant(cache_k, ks, x.dtype), _dequant(cache_v, vs, x.dtype)
    else:
        cache_k = cache_k.at[rows[:, None], positions].set(
            k_new.astype(cache_k.dtype), mode="drop")
        cache_v = cache_v.at[rows[:, None], positions].set(
            v_new.astype(cache_v.dtype), mode="drop")
        k, v = cache_k, cache_v
    # per-query causal horizon: query j sees kpos <= index + j
    valid = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    o = _cached_attend(q, k, v, valid, cfg.hd).astype(x.dtype)
    out = L.pdot(o.reshape(B, W, cfg.n_heads * cfg.hd), p["wo"], cfg)
    if int8_cache:
        return out, cache_k, cache_v, ks, vs
    return out, cache_k, cache_v


@L.scoped("attn")
def paged_verify_attention(
    p: Dict,
    x: jax.Array,                 # (B, W, D) window: last token + k draft tokens
    pool_k: jax.Array,            # (n_blocks, block_size, KV, hd) — ONE layer's pool
    pool_v: jax.Array,
    tables: jax.Array,            # (B, MB) int32 per-slot block tables
    index: jax.Array,             # (B,) int32 per-slot window start positions
    cfg: ArchConfig,
    *,
    positions: jax.Array,         # (B, W) absolute positions = index + arange(W)
    cache_scales: Optional[Tuple[jax.Array, jax.Array]] = None,  # (NB,bs,KV) x2
) -> Tuple[jax.Array, ...]:
    """Block-native speculative verify: :func:`verify_decode_attention`'s
    windowed write-then-attend, addressed through the block tables. Window
    positions past the slot extent redirect to the reserved null block 0 (the
    same null-block machinery the bridge writeback clamps into) instead of
    landing in a live cell, so an end-of-budget window can never corrupt a
    leased position; the engine rollback un-writes rejected cells back to
    pristine. Per-layer transient gather + the exact contraction of
    :func:`paged_decode_attention`, W queries wide."""
    B, W, _ = x.shape
    bs = pool_k.shape[1]
    MB = tables.shape[1]
    S = MB * bs
    rows = jnp.arange(B)
    index = jnp.asarray(index)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, None)
    pos_c = jnp.minimum(positions, S - 1)
    in_range = positions < S
    phys = jnp.where(in_range, tables[rows[:, None], pos_c // bs], 0)
    off = jnp.where(in_range, pos_c % bs, 0)
    int8_cache = cache_scales is not None
    if int8_cache:
        pks, pvs = cache_scales
        k_q, v_q, k_sc, v_sc = _quantize_kv(k_new, v_new)
        pool_k = pool_k.at[phys, off].set(k_q)
        pool_v = pool_v.at[phys, off].set(v_q)
        pks = pks.at[phys, off].set(k_sc)
        pvs = pvs.at[phys, off].set(v_sc)
    else:
        pool_k = pool_k.at[phys, off].set(k_new.astype(pool_k.dtype))
        pool_v = pool_v.at[phys, off].set(v_new.astype(pool_v.dtype))
    k, v = _table_rows(pool_k, tables), _table_rows(pool_v, tables)
    if int8_cache:
        k = _dequant(k, _table_rows(pks, tables), x.dtype)
        v = _dequant(v, _table_rows(pvs, tables), x.dtype)
    valid = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    o = _cached_attend(q, k, v, valid, cfg.hd).astype(x.dtype)
    out = L.pdot(o.reshape(B, W, cfg.n_heads * cfg.hd), p["wo"], cfg)
    if int8_cache:
        return out, pool_k, pool_v, pks, pvs
    return out, pool_k, pool_v


@L.scoped("attn")
def paged_decode_attention(
    p: Dict,
    x: jax.Array,                 # (B, 1, D) current token
    pool_k: jax.Array,            # (n_blocks, block_size, KV, hd) — ONE layer's pool
    pool_v: jax.Array,
    tables: jax.Array,            # (B, MB) int32 per-slot block tables
    index: jax.Array,             # (B,) int32 per-slot positions
    cfg: ArchConfig,
    *,
    positions: Optional[jax.Array] = None,
    positions3: Optional[jax.Array] = None,
    cache_scales: Optional[Tuple[jax.Array, jax.Array]] = None,  # (NB,bs,KV) x2
    use_kernel: bool = False,
) -> Tuple[jax.Array, ...]:
    """Block-native single-token attention: the paged pool stays in block
    layout end to end. The new token's K/V is scattered straight into its
    slot's current pool cell ``(tables[b, index[b] // bs], index[b] % bs)``
    and attention runs against the table-addressed blocks — no store-level
    ``gather_block_kv`` view of all layers is ever materialized
    (``PagedKVStore`` native mode passes the pool through unchanged and
    reports ``decode_view_bytes: 0``).

    Bit-identity with the gather-bridge decode is the contract: this path
    gathers exactly one layer's table-addressed rows transiently inside the
    layer body and then computes byte-for-byte the math of
    :func:`decode_attention` on them (the same :func:`_cached_attend`,
    length-S softmax rows and masks), so native tokens equal bridge tokens
    equal contiguous tokens (tests/test_serving.py). Rows whose index ran past the
    slot extent (idle/retired slots) clamp into their zeroed table — the
    reserved null block 0 — mirroring the bridge writeback's clamped null
    write; the null block is never read unmasked.

    ``use_kernel`` routes the attention contraction through the Pallas
    kernel (kernels/paged_attention.py) — truly block-granular HBM traffic,
    online softmax (float-equivalent, not bit-exact; float KV only — an
    int8 KV cache raises). Off-TPU the kernel runs in interpret mode, which
    is how CPU CI exercises it.

    Returns ``(out, pool_k, pool_v)`` (+ scale pools on the int8 path)."""
    B = x.shape[0]
    bs = pool_k.shape[1]
    MB = tables.shape[1]
    S = MB * bs
    rows = jnp.arange(B)
    index = jnp.asarray(index)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, positions3)
    pos = jnp.minimum(index, S - 1)          # idle rows: index can run on
    phys = tables[rows, pos // bs]           # zeroed table -> null block 0
    off = pos % bs
    int8_cache = cache_scales is not None
    if int8_cache:
        pks, pvs = cache_scales
        k_q, v_q, k_sc, v_sc = _quantize_kv(k_new, v_new)
        pool_k = pool_k.at[phys, off].set(k_q[:, 0])
        pool_v = pool_v.at[phys, off].set(v_q[:, 0])
        pks = pks.at[phys, off].set(k_sc[:, 0])
        pvs = pvs.at[phys, off].set(v_sc[:, 0])
    else:
        pool_k = pool_k.at[phys, off].set(k_new[:, 0].astype(pool_k.dtype))
        pool_v = pool_v.at[phys, off].set(v_new[:, 0].astype(pool_v.dtype))

    if use_kernel:
        if int8_cache:
            raise ValueError("the Pallas paged-attention kernel reads a "
                             "float KV pool; int8 KV has no kernel path")
        from repro.kernels.paged_attention import (
            paged_decode_attention as _pallas_paged)
        o = _pallas_paged(
            q[:, 0].astype(jnp.float32), pool_k, pool_v,
            tables.astype(jnp.int32), index.astype(jnp.int32),
            interpret=shd.current_mesh().devices.flat[0].platform != "tpu")
        o = o[:, None].astype(x.dtype)        # (B, 1, H, hd)
    else:
        # per-layer transient gather of this layer's table-addressed rows,
        # then exactly decode_attention's math — the bit-identity oracle
        k, v = _table_rows(pool_k, tables), _table_rows(pool_v, tables)
        if int8_cache:
            k = _dequant(k, _table_rows(pks, tables), x.dtype)
            v = _dequant(v, _table_rows(pvs, tables), x.dtype)
        valid = jnp.arange(S)[None, None, :] <= index[:, None, None]
        o = _cached_attend(q, k, v, valid, cfg.hd).astype(x.dtype)
    out = L.pdot(o.reshape(B, 1, cfg.n_heads * cfg.hd), p["wo"], cfg)
    if int8_cache:
        return out, pool_k, pool_v, pks, pvs
    return out, pool_k, pool_v
