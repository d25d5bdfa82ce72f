"""The dense family (StarCoder2-style blocks): what the harness must know
of its shape.

A family file defines, importing nothing from the program:

``leaves(c)``
    the weight tree, one ``(path, shape, init, layers)`` per leaf in a fixed
    order (``bench/weights.py`` draws leaf ``i`` from the ``i``-th key).
    ``init`` is a float std, ``"ones"`` or ``"zeros"``. ``layers`` is None
    for a leaf held once, else the length of the stack ``path[0]`` the leaf
    is stacked in (``shape`` then leaves out the layer axis); every leaf of
    a stack gives the same length. A stack is a run of layers of one shape.
``arch(c)``
    the family's keyword arguments of the program's ``ArchConfig``
    (``bench/program.py`` adds the name, the family, the dtypes and the
    quantization).
``token_matmul_flops(c, stack)``, ``attention_flops(c, n_keys, stack)``
    the operations one token requires in one layer of ``stack``: the weight
    matmuls, and scores plus the weighted sum over ``n_keys`` keys
    (``bench/flops.py`` sums them over the stacks).

Dense blocks: pre-norm, GQA attention, a GELU or SwiGLU MLP, all in one
stack ``layers`` of ``num_hidden_layers``.
"""

from __future__ import annotations

from typing import Dict, List

STACK = "layers"


def norm_leaves(c: Dict):
    """A norm's (leaf name, init) pairs."""
    if c["norm_type"] == "layernorm":
        return (("scale", "ones"), ("bias", "zeros"))
    return (("scale", "ones"),)


def top_leaves(c: Dict) -> List:
    """The leaves held once: embedding, final norm, head (vocabulary padded
    to a multiple of 16, as the program pads it)."""
    D, V = c["hidden_size"], c["vocab_size"]
    Vp = (V + 15) // 16 * 16
    out = [(("embed",), (Vp, D), 0.02, None)]
    out += [(("final_ln", k), (D,), v, None) for k, v in norm_leaves(c)]
    out.append((("lm_head",), (D, Vp), D ** -0.5, None))
    return out


def attn_block_leaves(c: Dict, stack: str, n: int) -> List:
    """Both norms and the attention projections of a stack of ``n``
    layers."""
    D, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    out = [((stack, norm, k), (D,), v, n) for norm in ("ln1", "ln2")
           for k, v in norm_leaves(c)]
    for name, shape in (("wq", (D, H * hd)), ("wk", (D, KV * hd)),
                        ("wv", (D, KV * hd)), ("wo", (H * hd, D))):
        out.append(((stack, "attn", name), shape, shape[0] ** -0.5, n))
    return out


def mlp_leaves(c: Dict, stack: str, n: int, width: int) -> List:
    """The MLP of a stack of ``n`` layers: SwiGLU where the activation is
    SiLU, else GELU."""
    D = c["hidden_size"]
    names = (("wi", (D, width)), ("wg", (D, width)), ("wo", (width, D))) \
        if c["hidden_act"] == "silu" else \
        (("wi", (D, width)), ("wo", (width, D)))
    return [((stack, "mlp", name), shape, shape[0] ** -0.5, n)
            for name, shape in names]


def leaves(c: Dict) -> List:
    L = c["num_hidden_layers"]
    return (top_leaves(c) + attn_block_leaves(c, STACK, L)
            + mlp_leaves(c, STACK, L, c["intermediate_size"]))


def arch(c: Dict) -> Dict:
    return dict(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        act="swiglu" if c["hidden_act"] == "silu" else "gelu",
        norm=c["norm_type"], rope_kind="rope",
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"])


def attn_matmul_flops(c: Dict) -> int:
    """Per token, per layer: the q, k, v and o projections."""
    D, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return 2 * D * (2 * H * hd + 2 * KV * hd)


def mlp_flops(c: Dict, width: int) -> int:
    mats = 3 if c["hidden_act"] == "silu" else 2
    return mats * 2 * c["hidden_size"] * width


def token_matmul_flops(c: Dict, stack: str = STACK) -> int:
    """Every layer is alike, so ``stack`` only names the one stack."""
    return attn_matmul_flops(c) + mlp_flops(c, c["intermediate_size"])


def attention_flops(c: Dict, n_keys: int, stack: str = STACK) -> int:
    return 2 * 2 * c["num_attention_heads"] * c["head_dim"] * n_keys
