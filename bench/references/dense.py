"""Plain float32 reference of the dense family (StarCoder2-style blocks).

Pre-norm decoder: x += Attn(Norm(x)); x += MLP(Norm(x)); logits =
Norm(x) @ lm_head. Attention is causal GQA with rotate-half RoPE; the MLP
is GELU (tanh) or SwiGLU. Everything in float32 with
``jax.default_matmul_precision("highest")``, no cache and no batching:
each sequence is one full forward over its prompt and served tokens. The
model is walked one layer at a time and attention one block of queries at a
time, so only one layer's weights are live in float32. Weights are drawn
from the seed by ``bench/weights.py``, never taken from the program.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import layer_params

Q_BLOCK = 512       # queries per attention block


def norm(p: Dict, x, c: Dict):
    if c["norm_type"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + c["norm_epsilon"]) * p["scale"] \
            + p["bias"]
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + c["rms_norm_eps"]) * p["scale"]


def rope(x, theta: float):
    """x: (S, heads, hd); rotate-half RoPE at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p: Dict, h, c: Dict):
    S = h.shape[0]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    q = rope((h @ p["wq"]).reshape(S, H, hd), c["rope_theta"])
    k = rope((h @ p["wk"]).reshape(S, KV, hd), c["rope_theta"])
    v = (h @ p["wv"]).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, H, hd)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= qpos, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qb))
    return o.reshape(S, H * hd) @ p["wo"]


def mlp(p: Dict, h, c: Dict):
    if "wg" in p:
        return (jax.nn.silu(h @ p["wi"]) * (h @ p["wg"])) @ p["wo"]
    return jax.nn.gelu(h @ p["wi"], approximate=True) @ p["wo"]


def dense_ffn(lp: Dict, h, c: Dict):
    """The MLP's output, and no router margin (the dense family routes
    nothing)."""
    return mlp(lp["mlp"], h, c), None


def make_layer(ffn: Callable, c: Dict):
    @jax.jit
    def layer(lp, x):
        x = x + attention(lp["attn"], norm(lp["ln1"], x, c), c)
        y, margin = ffn(lp, norm(lp["ln2"], x, c), c)
        return x + y, margin
    return layer


def hidden(c: Dict, seed: int, seqs: Sequence[np.ndarray],
           ffn: Callable = dense_ffn):
    """The unstacked weights, each sequence's last hidden states (padded at
    the end, which causal attention never reads back), one layer at a time,
    and each position's smallest router margin over the layers (None where
    ``ffn`` routes nothing)."""
    top = layer_params(c, seed, None)
    layer = make_layer(ffn, c)
    xs, margins = [], [None] * len(seqs)
    for s in seqs:
        # powers of two from Q_BLOCK up: few shapes, each compiled once
        ids = np.zeros(max(Q_BLOCK, 1 << (len(s) - 1).bit_length()),
                       np.int32)
        ids[:len(s)] = s
        xs.append(top["embed"][jnp.asarray(ids)])
    for li in range(c["num_hidden_layers"]):
        lp = layer_params(c, seed, li)
        outs = [layer(lp, x) for x in xs]
        xs = [x for x, _ in outs]
        margins = [a if m is None else jnp.minimum(m, a)
                   for m, (_, a) in zip(margins, outs)] \
            if outs and outs[0][1] is not None else margins
        del lp
    return top, xs, margins


def logits(c: Dict, seed: int, seq: np.ndarray, ffn: Callable = dense_ffn):
    """Every position's logits of one sequence (for small tests)."""
    with jax.default_matmul_precision("highest"):
        top, (x,), _ = hidden(c, seed, [seq], ffn)
        h = norm(top["final_ln"], x[:len(seq)], c)
        return np.asarray((h @ top["lm_head"])[:, :c["vocab_size"]])


def score(c: Dict, seed: int, seqs: Sequence[np.ndarray],
          n_prompt: Sequence[int], ffn: Callable = dense_ffn
          ) -> List[Dict[str, np.ndarray]]:
    """For each sequence (prompt + served tokens), one entry per served
    token, at the position p that predicts it (from the last prompt
    position to the second-to-last token):

    ``gap``: by how much the served token's logit lies below the
    reference's best, ``max(logits[p]) - logits[p][seq[p + 1]]``; 0 where
    it is the reference's argmax.
    ``router_margin`` (routed families only): the smallest, over the
    layers, of the reference's margin between the last router logit that
    picks an expert at p and the first that does not."""
    with jax.default_matmul_precision("highest"):
        top, xs, margins = hidden(c, seed, seqs, ffn)
        out = []
        for s, n, x, m in zip(seqs, n_prompt, xs, margins):
            pos = np.arange(n - 1, len(s) - 1)
            row = {"gap": np.asarray(_gaps(top, x, jnp.asarray(pos),
                                           jnp.asarray(s[pos + 1]),
                                           _norm_key(c)))}
            if m is not None:
                row["router_margin"] = np.asarray(m)[pos]
            out.append(row)
        return out


def _norm_key(c: Dict):
    return tuple(sorted((k, c[k]) for k in
                        ("norm_type", "norm_epsilon", "rms_norm_eps",
                         "vocab_size") if k in c))


@functools.partial(jax.jit, static_argnums=(4,))
def _gaps(top, x, pos, target, nk):
    c = dict(nk)
    h = norm(top["final_ln"], x[pos], c)
    logits = (h @ top["lm_head"])[:, :c["vocab_size"]]
    best = jnp.max(logits, -1)
    return best - jnp.take_along_axis(logits, target[:, None], -1)[:, 0]
