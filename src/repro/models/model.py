"""LMModel: init / forward / prefill / decode for all 10 assigned families.

Families
  dense   — [pre-norm attn] + [pre-norm MLP], scan over stacked layers
  moe     — dense with the MLP replaced by expert-parallel MoE (models/moe.py)
  vlm     — dense backbone consuming precomputed patch embeddings + M-RoPE
  encdec  — bidirectional encoder (frame-embedding stub input) + causal
            decoder with cross-attention (seamless-m4t)
  hybrid  — zamba2: groups of [shared-attn-block (+LoRA per application);
            attn_every x mamba2], remainder mamba2 layers at the end
  ssm     — xlstm: alternating (mLSTM, sLSTM) pairs

All stacks run under ``lax.scan`` with per-layer ``jax.checkpoint`` (constant
HLO size in depth — the 1000-node compile-time posture, DESIGN.md §7).
Params are nested dicts with a leading stacked-layer axis; ``param_specs``
mirrors the tree with PartitionSpecs (layer axis never sharded).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import xlstm as XL

LORA_RANK = 16  # zamba2 per-application adapter rank


# ===========================================================================
# init
# ===========================================================================

def _stack(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _init_dense_layer(cfg: ArchConfig):
    def f(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = {
            "ln1": L.init_norm(cfg, cfg.d_model),
            "attn": A.init_attn(k1, cfg, cfg.d_model),
            "ln2": L.init_norm(cfg, cfg.d_model),
        }
        if cfg.family == "moe":
            p["moe"] = MOE.init_moe(k2, cfg, cfg.d_model)
        else:
            p["mlp"] = L.init_mlp(k2, cfg, cfg.d_model, cfg.d_ff)
        return p
    return f


def _init_encdec(cfg: ArchConfig, key):
    ke, kd = jax.random.split(key)

    def enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": L.init_norm(cfg, cfg.d_model),
            "attn": A.init_attn(k1, cfg, cfg.d_model),
            "ln2": L.init_norm(cfg, cfg.d_model),
            "mlp": L.init_mlp(k2, cfg, cfg.d_model, cfg.d_ff),
        }

    def dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "ln1": L.init_norm(cfg, cfg.d_model),
            "self_attn": A.init_attn(k1, cfg, cfg.d_model),
            "ln_x": L.init_norm(cfg, cfg.d_model),
            "cross_attn": A.init_attn(k2, cfg, cfg.d_model),
            "ln2": L.init_norm(cfg, cfg.d_model),
            "mlp": L.init_mlp(k3, cfg, cfg.d_model, cfg.d_ff),
        }

    return {
        "encoder": _stack(enc_layer, ke, cfg.n_enc_layers),
        "decoder": _stack(dec_layer, kd, cfg.n_layers),
    }


def _init_hybrid(cfg: ArchConfig, key):
    """zamba2: n_groups x [shared attn ; attn_every x mamba] + remainder mamba."""
    n_groups = cfg.n_layers // cfg.attn_every
    rem = cfg.n_layers - n_groups * cfg.attn_every
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def mamba_layer(k):
        return {"ln": L.init_norm(cfg, cfg.d_model), "mamba": SSM.init_mamba2(k, cfg)}

    def group(k):
        return _stack(mamba_layer, k, cfg.attn_every)

    shared = {
        "ln1": L.init_norm(cfg, cfg.d_model),
        "attn": A.init_attn(k1, cfg, cfg.d_model),
        "ln2": L.init_norm(cfg, cfg.d_model),
        "mlp": L.init_mlp(k2, cfg, cfg.d_model, cfg.d_ff),
    }

    def lora(k):
        ka, kb = jax.random.split(k)
        return {
            "qA": jax.random.normal(ka, (cfg.d_model, LORA_RANK), jnp.float32) * 0.02,
            "qB": jnp.zeros((LORA_RANK, cfg.n_heads * cfg.hd), jnp.float32),
        }

    return {
        "groups": _stack(group, k3, n_groups),          # (G, attn_every, ...)
        "shared": shared,
        "lora": _stack(lora, k4, n_groups),             # per-application adapters
        "tail": _stack(mamba_layer, jax.random.fold_in(k3, 7), rem) if rem else None,
    }


def _init_xlstm(cfg: ArchConfig, key):
    n_pairs = cfg.n_layers // 2
    k1, k2 = jax.random.split(key)

    def pair(k):
        ka, kb = jax.random.split(k)
        return {
            "ln_m": L.init_norm(cfg, cfg.d_model),
            "mlstm": XL.init_mlstm(ka, cfg),
            "ln_s": L.init_norm(cfg, cfg.d_model),
            "slstm": XL.init_slstm(kb, cfg),
        }

    return {"pairs": _stack(pair, k1, n_pairs)}


def init_model(cfg: ArchConfig, key) -> Dict:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(k_emb, (cfg.vocab_padded, cfg.d_model), jnp.float32) * 0.02,
        "final_ln": L.init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, (cfg.d_model, cfg.vocab_padded))
    if cfg.family in ("dense", "moe", "vlm"):
        params["layers"] = _stack(_init_dense_layer(cfg), k_layers, cfg.n_layers)
    elif cfg.family == "encdec":
        params.update(_init_encdec(cfg, k_layers))
        params["enc_final_ln"] = L.init_norm(cfg, cfg.d_model)
    elif cfg.family == "hybrid":
        params["hybrid"] = _init_hybrid(cfg, k_layers)
    elif cfg.family == "ssm":
        params["xlstm"] = _init_xlstm(cfg, k_layers)
    else:
        raise ValueError(cfg.family)
    if cfg.param_dtype != "float32":
        # serving stores weights at compute precision (half the HBM bytes of
        # the f32 training master copy) — the decode-cell §Perf baseline fix
        dt = jnp.dtype(cfg.param_dtype)
        params = jax.tree.map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params)
    return params


# ===========================================================================
# param sharding specs
# ===========================================================================

def _norm_specs(cfg: ArchConfig) -> Dict:
    p = {"scale": P(None)}
    if cfg.norm == "layernorm":
        p["bias"] = P(None)
    return p


def _prepend(spec_tree, axis_entry=None):
    """Add a leading (stacked-layer) axis to every spec in a tree."""
    return jax.tree.map(
        lambda s: P(axis_entry, *s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def param_specs(cfg: ArchConfig) -> Dict:
    specs: Dict[str, Any] = {
        "embed": P("model", None),
        "final_ln": _norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model")
    if cfg.family in ("dense", "moe", "vlm"):
        layer = {
            "ln1": _norm_specs(cfg),
            "attn": A.attn_specs(cfg),
            "ln2": _norm_specs(cfg),
        }
        if cfg.family == "moe":
            layer["moe"] = MOE.moe_specs(cfg)
        else:
            layer["mlp"] = L.mlp_specs(cfg)
        specs["layers"] = _prepend(layer)
    elif cfg.family == "encdec":
        enc = {"ln1": _norm_specs(cfg), "attn": A.attn_specs(cfg),
               "ln2": _norm_specs(cfg), "mlp": L.mlp_specs(cfg)}
        dec = {"ln1": _norm_specs(cfg), "self_attn": A.attn_specs(cfg),
               "ln_x": _norm_specs(cfg), "cross_attn": A.attn_specs(cfg),
               "ln2": _norm_specs(cfg), "mlp": L.mlp_specs(cfg)}
        specs["encoder"] = _prepend(enc)
        specs["decoder"] = _prepend(dec)
        specs["enc_final_ln"] = _norm_specs(cfg)
    elif cfg.family == "hybrid":
        mamba = {"ln": _norm_specs(cfg), "mamba": SSM.mamba2_specs(cfg)}
        specs["hybrid"] = {
            "groups": _prepend(_prepend(mamba)),        # (G, attn_every, ...)
            "shared": {"ln1": _norm_specs(cfg), "attn": A.attn_specs(cfg),
                       "ln2": _norm_specs(cfg), "mlp": L.mlp_specs(cfg)},
            "lora": _prepend({"qA": P(None, None), "qB": P(None, "model")}),
            "tail": _prepend(mamba) if cfg.n_layers % cfg.attn_every else None,
        }
    elif cfg.family == "ssm":
        pair = {"ln_m": _norm_specs(cfg), "mlstm": XL.mlstm_specs(cfg),
                "ln_s": _norm_specs(cfg), "slstm": XL.slstm_specs(cfg)}
        specs["xlstm"] = {"pairs": _prepend(pair)}
    return specs


# ===========================================================================
# forward (train / prefill)
# ===========================================================================

def _maybe_remat(f, cfg: ArchConfig):
    return jax.checkpoint(f) if cfg.remat else f


def _scan(body, init, xs, cfg: ArchConfig):
    """lax.scan over stacked layers; fully unrolled when cfg.scan_unroll (the
    dry-run's exact-cost mode — while bodies are cost-counted once by XLA)."""
    return jax.lax.scan(body, init, xs, unroll=True if cfg.scan_unroll else 1)


def _embed_in(params, cfg: ArchConfig, batch: Dict) -> jax.Array:
    if "embeds" in batch:
        x = batch["embeds"].astype(L.cdtype(cfg))
    else:
        # cast the (vocab-sharded) table BEFORE the gather: the combine
        # all-reduce then moves bf16, not the f32 master rows (§Perf cell A)
        x = params["embed"].astype(L.cdtype(cfg))[batch["tokens"]]
    return shd.with_sharding(x, shd.batch_spec(None, None))


@L.scoped("logits")
def _logits(params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    x = L.apply_norm(params["final_ln"], x, cfg)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = L.pdot(x, w, cfg)
    if cfg.vocab_padded != cfg.vocab:
        # padded vocab columns (model-axis divisibility) masked to -inf:
        # exp(-1e30) == 0 in the CE logsumexp, argmax never selects them
        mask = jnp.where(jnp.arange(cfg.vocab_padded) < cfg.vocab, 0.0, -1e30)
        logits = logits + mask.astype(logits.dtype)
    return shd.with_sharding(logits, shd.batch_spec(None, "model"))


def _dense_layer_fwd(lp, x, cfg: ArchConfig, positions, positions3):
    h = L.apply_norm(lp["ln1"], x, cfg)
    x = x + A.attention(lp["attn"], h, cfg, positions=positions, positions3=positions3)
    h = L.apply_norm(lp["ln2"], x, cfg)
    if cfg.family == "moe":
        y, aux = MOE.apply_moe(lp["moe"], h, cfg)
    else:
        y, aux = L.apply_mlp(lp["mlp"], h, cfg), jnp.zeros((), jnp.float32)
    return x + y, aux


def _with_hidden(params, cfg: ArchConfig, x, aux, return_hidden: bool):
    """Tail of forward: logits (+ final-norm hidden states when asked).
    The hidden row at the last prompt position is the embedding surface the
    serve API's non-generative endpoints read."""
    if return_hidden:
        return _logits(params, cfg, x), aux, L.apply_norm(
            params["final_ln"], x, cfg)
    return _logits(params, cfg, x), aux


def forward(params: Dict, cfg: ArchConfig, batch: Dict, *,
            return_kv: bool = False, return_hidden: bool = False):
    """Full-sequence forward. Returns (logits, aux_loss), or with
    ``return_kv=True`` (dense/moe/vlm only) (logits, aux_loss, kv) where kv is
    the per-layer K/V in decode-cache layout — {"k","v": (L, B, S, KV, hd)}
    plus {"k_scale","v_scale": (L, B, S, KV)} on the int8-KV path.

    The return_kv path is the fused serving admission (prefill-with-cache): it
    swaps the plain/chunked attention for the decode-mirrored
    ``prefill_attention_with_kv`` so the emitted entries (and hence every token
    decoded from a cache seeded with them) are bit-identical to replaying the
    prompt through the B=1 decode step, and routes MoE layers row-isolated so
    requests sharing one bucketed forward never perturb each other's experts.
    """
    B = (batch.get("tokens") if "tokens" in batch else batch["embeds"]).shape[0]
    S = (batch.get("tokens") if "tokens" in batch else batch["embeds"]).shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    positions3 = batch.get("positions3")

    x = _embed_in(params, cfg, batch)

    if return_hidden and return_kv:
        raise ValueError("return_hidden and return_kv are exclusive paths")

    if cfg.family in ("dense", "moe", "vlm"):
        if return_kv:
            int8_kv = cfg.kv_cache_dtype == "int8"

            def body_kv(carry, lp):
                x, aux = carry
                h = L.apply_norm(lp["ln1"], x, cfg)
                o, *kv = A.prefill_attention_with_kv(
                    lp["attn"], h, cfg, positions=positions,
                    positions3=positions3, int8_kv=int8_kv)
                x = x + o
                h = L.apply_norm(lp["ln2"], x, cfg)
                if cfg.family == "moe":
                    y, a = MOE.apply_moe(lp["moe"], h, cfg, row_isolated=True)
                else:
                    y, a = L.apply_mlp(lp["mlp"], h, cfg), jnp.zeros((), jnp.float32)
                return (x + y, aux + a), tuple(kv)

            (x, aux), kv = _scan(_maybe_remat(body_kv, cfg), (x, 0.0),
                                 params["layers"], cfg)
            names = ("k", "v", "k_scale", "v_scale") if int8_kv else ("k", "v")
            return _logits(params, cfg, x), aux, dict(zip(names, kv))

        def body(carry, lp):
            x, aux = carry
            x, a = _dense_layer_fwd(lp, x, cfg, positions, positions3)
            return (x, aux + a), None
        (x, aux), _ = _scan(_maybe_remat(body, cfg), (x, 0.0), params["layers"], cfg)
        return _with_hidden(params, cfg, x, aux, return_hidden)

    if return_kv:
        raise ValueError(f"return_kv is a dense/moe/vlm cache path, not {cfg.family}")

    if cfg.family == "encdec":
        if return_hidden:
            raise ValueError("return_hidden is a decoder-only path, not encdec")
        return _encdec_forward(params, cfg, batch, positions)

    if cfg.family == "hybrid":
        x, _ = _hybrid_forward(params["hybrid"], cfg, x, positions)
        return _with_hidden(params, cfg, x, jnp.zeros((), jnp.float32),
                            return_hidden)

    if cfg.family == "ssm":
        def body(carry, lp):
            x = carry
            h = L.apply_norm(lp["ln_m"], x, cfg)
            y, _ = XL.apply_mlstm(lp["mlstm"], h, cfg)
            x = x + y
            h = L.apply_norm(lp["ln_s"], x, cfg)
            y, _ = XL.apply_slstm(lp["slstm"], h, cfg)
            return x + y, None
        x, _ = _scan(_maybe_remat(body, cfg), x, params["xlstm"]["pairs"], cfg)
        return _with_hidden(params, cfg, x, jnp.zeros((), jnp.float32),
                            return_hidden)

    raise ValueError(cfg.family)


def _hybrid_forward(hp, cfg: ArchConfig, x, positions):
    """Training/prefill pass for zamba2. Returns (x, per-application attn K/V
    is not cached here — see decode path)."""
    def mamba_body(x, lp):
        h = L.apply_norm(lp["ln"], x, cfg)
        y, _ = SSM.apply_mamba2(lp["mamba"], h, cfg)
        return x + y, None

    def group_body(x, inp):
        gp, lora = inp
        # shared attention block with per-application LoRA on W_q
        h = L.apply_norm(hp["shared"]["ln1"], x, cfg)
        attn_p = dict(hp["shared"]["attn"])
        wq = attn_p["wq"]
        if hasattr(wq, "dequantize"):      # Tensorizer-quantized shared block
            wq = wq.dequantize()
        attn_p["wq"] = wq + (lora["qA"] @ lora["qB"])
        x = x + A.attention(attn_p, h, cfg, positions=positions)
        h = L.apply_norm(hp["shared"]["ln2"], x, cfg)
        x = x + L.apply_mlp(hp["shared"]["mlp"], h, cfg)
        # attn_every mamba layers
        x, _ = _scan(mamba_body, x, gp, cfg)
        return x, None

    x, _ = _scan(_maybe_remat(group_body, cfg), x, (hp["groups"], hp["lora"]), cfg)
    if hp.get("tail") is not None:
        x, _ = _scan(_maybe_remat(lambda c, lp: mamba_body(c, lp), cfg),
                     x, hp["tail"], cfg)
    return x, None


def _encdec_forward(params, cfg: ArchConfig, batch, positions):
    enc_x = batch["embeds"].astype(L.cdtype(cfg))          # frame stub (B, Se, D)
    enc_x = shd.with_sharding(enc_x, shd.batch_spec(None, None))
    Se = enc_x.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), enc_x.shape[:2])

    def enc_body(x, lp):
        h = L.apply_norm(lp["ln1"], x, cfg)
        x = x + A.attention(lp["attn"], h, cfg, positions=enc_pos, causal=False)
        h = L.apply_norm(lp["ln2"], x, cfg)
        return x + L.apply_mlp(lp["mlp"], h, cfg), None

    enc_x, _ = _scan(_maybe_remat(enc_body, cfg), enc_x, params["encoder"], cfg)
    enc_out = L.apply_norm(params["enc_final_ln"], enc_x, cfg)

    x = params["embed"][batch["tokens"]].astype(L.cdtype(cfg))
    x = shd.with_sharding(x, shd.batch_spec(None, None))

    def dec_body(x, lp):
        h = L.apply_norm(lp["ln1"], x, cfg)
        x = x + A.attention(lp["self_attn"], h, cfg, positions=positions)
        h = L.apply_norm(lp["ln_x"], x, cfg)
        ck, cv = A.project_kv_for_cross(lp["cross_attn"], enc_out, cfg)
        x = x + A.attention(lp["cross_attn"], h, cfg, positions=positions, kv=(ck, cv))
        h = L.apply_norm(lp["ln2"], x, cfg)
        return x + L.apply_mlp(lp["mlp"], h, cfg), None

    x, _ = _scan(_maybe_remat(dec_body, cfg), x, params["decoder"], cfg)
    return _logits(params, cfg, x), jnp.zeros((), jnp.float32)
