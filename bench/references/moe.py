"""Plain float32 reference of the MoE family (DeepSeek-MoE-style blocks).

The dense family's blocks (``dense.py``) with the MLP replaced by a routed
mixture plus always-on shared experts: router logits ``h @ router``, the
top-k experts by logit, gates the softmax over those k logits (the repo's
gating; the published model takes the softmax over all experts without
renormalizing, noted in the configuration file), and
``y = sum_k gate_k * expert_k(h) + shared(h)`` with SwiGLU experts. Every
expert is evaluated on every token and weighted by a gate that is zero
where the token did not pick it: plain, and exact in float32. Each layer
also hands back, per position, the margin between the k-th and the
(k+1)-th router logit: where it is near zero, a tiny change of the hidden
state swaps an expert.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.references import dense


def moe_ffn(lp: Dict, h, c: Dict):
    p = lp["moe"]
    T, E, k = h.shape[0], c["n_routed_experts"], c["num_experts_per_tok"]
    scores = h @ p["router"]
    top, ids = jax.lax.top_k(scores, k + 1)
    margin = top[:, k - 1] - top[:, k]
    top, ids = top[:, :k], ids[:, :k]
    gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], ids].set(jax.nn.softmax(top, -1))

    def expert(acc, e):
        y = (jax.nn.silu(h @ p["wi"][e]) * (h @ p["wg"][e])) @ p["wo"][e]
        return acc + gates[:, e][:, None] * y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(E))
    return y + dense.mlp(p["shared"], h, c), margin


def score(c: Dict, seed: int, seqs: Sequence[np.ndarray],
          n_prompt: Sequence[int]) -> List[Dict[str, np.ndarray]]:
    return dense.score(c, seed, seqs, n_prompt, ffn=moe_ffn)


def logits(c: Dict, seed: int, seq: np.ndarray):
    return dense.logits(c, seed, seq, ffn=moe_ffn)
