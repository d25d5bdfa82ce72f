"""Mixture-of-Experts with expert parallelism (moonshot / deepseek-moe archs).

Design (DESIGN.md §7): activations are batch-sharded over (pod, data) and
*replicated* over ``model``; expert weights are sharded over ``model`` (EP:
64 experts / 16 = 4 per device). Each device therefore holds every local token
and a slice of experts — dispatch is purely local (sort-free cumsum binning
into fixed-capacity buffers, MXU-friendly batched matmuls), and the only
collective is one ``psum`` over ``model`` to combine expert outputs, the same
pattern (and cost) as Megatron-style TP. No all-to-all, no global sort.

Token dropping: fixed capacity C = ceil(T·topk/E · capacity_factor) per expert
(Switch-style); dropped slots scatter out-of-bounds (mode="drop").

Serving (per-request-isolated routing): ``apply_moe(active=...)`` masks idle
engine slots out of the capacity cumsum so a decode token's expert slot never
depends on idle batchmates, and ``row_isolated=True`` bins each batch row
against its own capacity so requests sharing one fused-prefill forward route
exactly as they would alone — the engine's MoE batch-invariance guarantees
(tests/test_serving.py).

The router aux (load-balance) loss is returned alongside; it is identical
across model shards (computed pre-dispatch from replicated scores).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.models import layers as L

def init_moe(key, cfg: ArchConfig, d: int) -> Dict:
    ks = jax.random.split(key, 5)
    E, F = cfg.n_experts, cfg.d_ff
    p = {
        "router": L.dense_init(ks[0], (d, E)),
        "wi": L.dense_init(ks[1], (E, d, F), in_axis=1),
        "wg": L.dense_init(ks[2], (E, d, F), in_axis=1),
        "wo": L.dense_init(ks[3], (E, F, d), in_axis=1),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(ks[4], cfg, d, cfg.n_shared_experts * F)
    return p


def moe_specs(cfg: ArchConfig) -> Dict:
    p = {
        "router": P(None, None),
        "wi": P("model", None, None),
        "wg": P("model", None, None),
        "wo": P("model", None, None),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_specs(cfg)
    return p


def _dispatch_local(x_flat, scores, E: int, E_loc: int, e_offset, topk: int, capacity: int, cfg,
                    token_valid=None):
    """Bin local tokens into (E_loc, C, D) buffers; return combine metadata.

    ``token_valid`` (T,) bool masks tokens out of the capacity cumsum entirely
    (they neither occupy expert slots nor shift other tokens' queue positions)
    — the serving engine passes the active-slot mask here so a request's expert
    assignment never depends on idle batchmates (per-request-isolated routing).
    """
    T, D = x_flat.shape
    gate, ids = jax.lax.top_k(scores, topk)                   # (T, k)
    gate = jax.nn.softmax(gate.astype(jnp.float32), axis=-1)  # normalize over selected
    flat_ids = ids.reshape(-1)                                # (T*k,)
    flat_gate = gate.reshape(-1)
    slot_token = jnp.repeat(jnp.arange(T), topk)              # (T*k,)

    local = flat_ids - e_offset                               # target local expert
    valid = (local >= 0) & (local < E_loc)
    if token_valid is not None:
        valid = valid & token_valid[slot_token]
    local_c = jnp.where(valid, local, 0)
    # position of each slot within its expert queue (sort-free: cumsum of onehots)
    oh = jax.nn.one_hot(jnp.where(valid, local, E_loc), E_loc + 1, dtype=jnp.int32)[:, :E_loc]
    pos = (jnp.cumsum(oh, axis=0) * oh).sum(axis=1) - 1       # (T*k,), -1 if invalid
    keep = valid & (pos >= 0) & (pos < capacity)

    scatter_e = jnp.where(keep, local_c, E_loc)               # OOB row drops
    scatter_c = jnp.where(keep, pos, 0)
    x_buf = jnp.zeros((E_loc + 1, capacity, D), x_flat.dtype)
    x_buf = x_buf.at[scatter_e, scatter_c].add(x_flat[slot_token])
    return x_buf[:E_loc], (slot_token, local_c, pos, keep, flat_gate)


def _combine_local(y_buf, meta, T: int, D: int):
    slot_token, local_c, pos, keep, flat_gate = meta
    pos_c = jnp.clip(pos, 0, y_buf.shape[1] - 1)
    y_slot = y_buf[local_c, pos_c] * (keep * flat_gate)[:, None].astype(y_buf.dtype)
    y = jnp.zeros((T, D), y_buf.dtype)
    return y.at[slot_token].add(y_slot)


@L.scoped("moe")
def apply_moe(p: Dict, x: jax.Array, cfg: ArchConfig, *,
              active: Optional[jax.Array] = None,
              row_isolated: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss). EP over 'model' via shard_map.

    Serving isolation knobs (training uses neither — shared batch capacity
    with Switch-style dropping). Both serving modes are *dropless*: capacity
    is raised to the worst-case per-expert load (each token contributes at
    most one entry per expert), because an expert buffer slot's value depends
    only on the token occupying it — so with dropping impossible, a token's
    MoE output is bitwise independent of its batchmates. That is what makes
    the engine's staggered==sequential bit-identity hold for MoE.

    ``active`` (B,) bool — decode: mask whole batch rows out of the capacity
    cumsum (idle slots' garbage tokens never consume capacity or shift queue
    positions) and use capacity = T so no active token can ever be dropped.

    ``row_isolated`` — fused prefill: bin each batch row against its own
    dropless capacity (= S), so a token only competes with tokens of the same
    row/request — requests sharing one bucketed admission forward route
    exactly as they would alone, and exactly as the B=1 replay decode would
    have routed them (right-padding keeps pad tokens *after* the prompt in
    the cumsum, so they never shift real tokens either).

    Named scopes under ``moe``: ``router`` (scores, dispatch into the expert
    buffers, the aux loss), ``experts`` (the expert matmuls and the combine)
    and ``shared`` (the shared experts).
    """
    mesh = shd.current_mesh()
    names = mesh.axis_names
    has_model = "model" in names
    b_axes = shd.batch_axes()
    E, topk = cfg.n_experts, cfg.topk
    mp = mesh.shape["model"] if has_model else 1
    assert E % mp == 0, (E, mp)
    E_loc = E // mp
    B, S, D = x.shape
    T_loc = (B // max(1, shd.data_parallel_size())) * S
    if row_isolated:
        capacity = max(topk, S)        # dropless within a row (see docstring)
    elif active is not None:
        capacity = max(topk, T_loc)    # dropless decode batch
    else:
        capacity = max(topk, math.ceil(T_loc * topk / E * cfg.capacity_factor))

    x = shd.with_sharding(x, shd.batch_spec(None, None))      # replicate over model
    if active is None:
        active = jnp.ones((B,), bool)
    active = shd.with_sharding(active.astype(bool), shd.batch_spec())

    batch_entry = b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None)

    # Tensorizer-quantized expert weights: dequantize before shard_map (the
    # in_specs tree expects plain arrays; the W8A8 fast path covers the dense
    # projections — expert matmuls stay bf16 in serve mode)
    from repro.core.tensorizer import QTensor
    wi, wg, wo, router = (w.dequantize() if isinstance(w, QTensor) else w
                          for w in (p["wi"], p["wg"], p["wo"], p["router"]))

    def local_fn(xb, act, router, wi, wg, wo):
        Bl, Sl, _ = xb.shape
        xf = xb.reshape(Bl * Sl, D)
        with jax.named_scope("router"):
            scores = (xf.astype(jnp.float32) @ router).astype(
                jnp.float32)                                        # (T, E)
            e_offset = ((jax.lax.axis_index("model") * E_loc) if has_model
                        else 0)
            if row_isolated:
                # per-row dispatch: buffers (Bl, E_loc, C, D), cumsum in a row
                x_buf, meta = jax.vmap(
                    lambda xr, sr: _dispatch_local(
                        xr, sr, E, E_loc, e_offset, topk, capacity, cfg)
                )(xb, scores.reshape(Bl, Sl, E))
            else:
                token_valid = jnp.broadcast_to(act[:, None],
                                               (Bl, Sl)).reshape(-1)
                x_buf, meta = _dispatch_local(
                    xf, scores, E, E_loc, e_offset, topk, capacity, cfg,
                    token_valid=token_valid)
        with jax.named_scope("experts"):
            if row_isolated:
                h = jnp.einsum("becd,edf->becf", x_buf, wi.astype(xb.dtype))
                h = jax.nn.silu(h) * jnp.einsum("becd,edf->becf", x_buf,
                                                wg.astype(xb.dtype))
                y_buf = jnp.einsum("becf,efd->becd", h, wo.astype(xb.dtype))
                y = jax.vmap(lambda yb, m: _combine_local(yb, m, Sl, D))(
                    y_buf, meta)
                y = y.reshape(Bl * Sl, D)
            else:
                h = jnp.einsum("ecd,edf->ecf", x_buf, wi.astype(xb.dtype))
                h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", x_buf,
                                                wg.astype(xb.dtype))
                y_buf = jnp.einsum("ecf,efd->ecd", h, wo.astype(xb.dtype))
                y = _combine_local(y_buf, meta, Bl * Sl, D)
            if has_model:
                y = jax.lax.psum(y, "model")
        with jax.named_scope("router"):
            # Switch-style load-balance aux: E * sum_e f_e * p_e
            # (replicated over model)
            probs = jax.nn.softmax(scores, axis=-1)
            _, ids = jax.lax.top_k(scores, topk)
            f = jnp.mean(jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(axis=1),
                         axis=0)
            pmean = jnp.mean(probs, axis=0)
            aux = E * jnp.sum(f * pmean)
        return y.reshape(Bl, Sl, D), aux[None]

    in_specs = (
        P(batch_entry, None, None),
        P(batch_entry),
        P(None, None),
        P("model" if has_model else None, None, None),
        P("model" if has_model else None, None, None),
        P("model" if has_model else None, None, None),
    )
    out_specs = (P(batch_entry, None, None), P(batch_entry))
    y, aux = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x, active, router, wi, wg, wo)

    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            y = y + L.apply_mlp(p["shared"], x, cfg)
    return y, jnp.mean(aux)
