"""Operations a served token requires, counted from shapes.

"Required" means what the model's mathematics needs at the real lengths:
one logits row per emitted token, attention over the real context (not the
padded bucket or the whole cache row), and for MoE only the experts a token
is routed to plus the shared ones. So padding, full-vocabulary prefill
logits and expert capacity the program computes and throws away lower a
share instead of raising it, and no implementation can push a share built
on these counts past the chip's peak. A multiply-add counts as 2.
"""

from __future__ import annotations

from typing import Dict


def token_matmul_flops(c: Dict) -> int:
    """Per token, per layer: the weight matmuls (attention projections and
    the MLP or the routed + shared experts and the router)."""
    D, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    attn = 2 * D * (2 * H * hd + 2 * KV * hd)
    if c["family"] == "moe":
        F = c["moe_intermediate_size"]
        experts = c["num_experts_per_tok"] + c["n_shared_experts"]
        return attn + experts * 3 * 2 * D * F + 2 * D * c["n_routed_experts"]
    mats = 3 if c["hidden_act"] == "silu" else 2
    return attn + mats * 2 * D * c["intermediate_size"]


def attention_flops(c: Dict, n_keys: int) -> int:
    """Per query token, per layer: scores and the weighted sum over
    ``n_keys`` keys."""
    return 2 * 2 * c["num_attention_heads"] * c["head_dim"] * n_keys


def head_flops(c: Dict) -> int:
    return 2 * c["hidden_size"] * c["vocab_size"]


def prefill_flops(c: Dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens, causal, and its one logits row."""
    L = c["num_hidden_layers"]
    keys = prompt_len * (prompt_len + 1) // 2      # sum of 1..prompt_len
    return (L * (prompt_len * token_matmul_flops(c)
                 + attention_flops(c, 1) * keys)
            + head_flops(c))


def decode_flops(c: Dict, n_keys: int) -> int:
    """One decoded token attending over ``n_keys`` cached positions (its
    own included)."""
    L = c["num_hidden_layers"]
    return (L * (token_matmul_flops(c) + attention_flops(c, n_keys))
            + head_flops(c))
