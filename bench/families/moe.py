"""The MoE family (DeepSeek-MoE-style blocks): the dense family's norms and
attention (``dense.py``) with the MLP replaced by a routed mixture of
SwiGLU experts plus always-on shared experts, all in one stack ``layers``
of ``num_hidden_layers``. The file's contract is in ``dense.py``."""

from __future__ import annotations

from typing import Dict, List

from bench.families import dense

STACK = dense.STACK
attention_flops = dense.attention_flops


def moe_leaves(c: Dict, stack: str, n: int) -> List:
    """Router, routed experts (stacked on their first axis) and the shared
    experts as one MLP of ``n_shared_experts`` times the expert width."""
    D, E, F = (c["hidden_size"], c["n_routed_experts"],
               c["moe_intermediate_size"])
    Fs = c["n_shared_experts"] * F
    out = [((stack, "moe", "router"), (D, E), D ** -0.5, n)]
    for name, shape in (("wi", (E, D, F)), ("wg", (E, D, F)),
                        ("wo", (E, F, D))):
        out.append(((stack, "moe", name), shape, shape[-2] ** -0.5, n))
    for name, shape in (("wi", (D, Fs)), ("wg", (D, Fs)), ("wo", (Fs, D))):
        out.append(((stack, "moe", "shared", name), shape, shape[0] ** -0.5,
                    n))
    return out


def leaves(c: Dict) -> List:
    L = c["num_hidden_layers"]
    return (dense.top_leaves(c) + dense.attn_block_leaves(c, STACK, L)
            + moe_leaves(c, STACK, L))


def arch(c: Dict) -> Dict:
    return {**dense.arch(c), "d_ff": c["moe_intermediate_size"],
            "n_experts": c["n_routed_experts"],
            "n_shared_experts": c["n_shared_experts"],
            "topk": c["num_experts_per_tok"]}


def moe_flops(c: Dict) -> int:
    """Per token, per layer: the routed experts a token picks, the shared
    ones, and the router."""
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    experts = c["num_experts_per_tok"] + c["n_shared_experts"]
    return experts * 3 * 2 * D * F + 2 * D * c["n_routed_experts"]


def token_matmul_flops(c: Dict, stack: str = STACK) -> int:
    return dense.attn_matmul_flops(c) + moe_flops(c)
