"""Operations a served token requires, counted from shapes.

"Required" means what the model's mathematics needs at the real lengths:
one logits row per emitted token, attention over the real context (not the
padded bucket or the whole cache row), and for MoE only the experts a token
is routed to plus the shared ones. So padding, full-vocabulary prefill
logits and expert capacity the program computes and throws away lower a
share instead of raising it, and no implementation can push a share built
on these counts past the chip's peak. A multiply-add counts as 2.

A layer's counts are its family's (``bench/families/<family>.py``); this
file sums them over the family's stacks of layers and adds the head.
"""

from __future__ import annotations

from typing import Dict

from bench import common, weights


def token_matmul_flops(c: Dict, stack: str = "layers") -> int:
    """Per token, per layer of ``stack``: the weight matmuls (attention
    projections and the MLP or the routed + shared experts and the router),
    as the family counts them."""
    return common.family(c["family"]).token_matmul_flops(c, stack)


def attention_flops(c: Dict, n_keys: int, stack: str = "layers") -> int:
    """Per query token, per layer of ``stack``: scores and the weighted sum
    over ``n_keys`` keys, as the family counts them."""
    return common.family(c["family"]).attention_flops(c, n_keys, stack)


def head_flops(c: Dict) -> int:
    return 2 * c["hidden_size"] * c["vocab_size"]


def prefill_flops(c: Dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens, causal, and its one logits row."""
    keys = prompt_len * (prompt_len + 1) // 2      # sum of 1..prompt_len
    return (sum(n * (prompt_len * token_matmul_flops(c, s)
                     + attention_flops(c, 1, s) * keys)
                for s, n in weights.stacks(c).items())
            + head_flops(c))


def decode_flops(c: Dict, n_keys: int) -> int:
    """One decoded token attending over ``n_keys`` cached positions (its
    own included)."""
    return (sum(n * (token_matmul_flops(c, s) + attention_flops(c, n_keys, s))
                for s, n in weights.stacks(c).items())
            + head_flops(c))
