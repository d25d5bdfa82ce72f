"""A model family's shape lives in ``bench/families/<family>.py``: the
committed families draw the same weights and build the same ArchConfig as
before the move, and a new family joins the benchmark as new files alone."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import _bench_path  # noqa: F401
from smoke_sizes import smoke_config
from bench import common, program, weights
from repro.configs.base import ArchConfig

SEED = 2 ** 40 + 3

# Pinned from commit c39328e, before the families moved out of
# bench/weights.py: sha256 over every leaf's bytes in tree order at
# SEED, and the leaf list (that commit's ``stacked`` True written as the
# stack's length, 2 at smoke sizes; False as None).
DIGEST = {
    "dense": "f813b85cc82f57a7d1c8a63f168a536d70d6407c8fe8a94fd3d1875eea233d47",
    "moe": "d364a62c8d31ad19006327393e764c06fd0ab5926e1daee72da269ec61d634b8",
}
_ATTN = {"dense": [
    (("layers", "ln1", "scale"), (64,), "ones", 2),
    (("layers", "ln1", "bias"), (64,), "zeros", 2),
    (("layers", "ln2", "scale"), (64,), "ones", 2),
    (("layers", "ln2", "bias"), (64,), "zeros", 2),
    (("layers", "attn", "wq"), (64, 64), 0.125, 2),
    (("layers", "attn", "wk"), (64, 32), 0.125, 2),
    (("layers", "attn", "wv"), (64, 32), 0.125, 2),
    (("layers", "attn", "wo"), (64, 64), 0.125, 2),
], "moe": [
    (("layers", "ln1", "scale"), (64,), "ones", 2),
    (("layers", "ln2", "scale"), (64,), "ones", 2),
    (("layers", "attn", "wq"), (64, 64), 0.125, 2),
    (("layers", "attn", "wk"), (64, 64), 0.125, 2),
    (("layers", "attn", "wv"), (64, 64), 0.125, 2),
    (("layers", "attn", "wo"), (64, 64), 0.125, 2),
]}
LEAVES = {
    "dense": [
        (("embed",), (256, 64), 0.02, None),
        (("final_ln", "scale"), (64,), "ones", None),
        (("final_ln", "bias"), (64,), "zeros", None),
        (("lm_head",), (64, 256), 0.125, None),
        *_ATTN["dense"],
        (("layers", "mlp", "wi"), (64, 128), 0.125, 2),
        (("layers", "mlp", "wo"), (128, 64), 0.08838834764831845, 2),
    ],
    "moe": [
        (("embed",), (256, 64), 0.02, None),
        (("final_ln", "scale"), (64,), "ones", None),
        (("lm_head",), (64, 256), 0.125, None),
        *_ATTN["moe"],
        (("layers", "moe", "router"), (64, 4), 0.125, 2),
        (("layers", "moe", "wi"), (4, 64, 32), 0.125, 2),
        (("layers", "moe", "wg"), (4, 64, 32), 0.125, 2),
        (("layers", "moe", "wo"), (4, 32, 64), 0.1767766952966369, 2),
        (("layers", "moe", "shared", "wi"), (64, 32), 0.125, 2),
        (("layers", "moe", "shared", "wg"), (64, 32), 0.125, 2),
        (("layers", "moe", "shared", "wo"), (32, 64), 0.1767766952966369, 2),
    ],
}
# every ArchConfig field that differs from its default, at c39328e
ARCH = {
    "starcoder2-15b-s10": dict(
        name="starcoder2-15b-s10", family="dense", n_layers=10, d_model=6144,
        n_heads=48, n_kv=4, d_ff=24576, vocab=49152, head_dim=128,
        act="gelu", norm="layernorm", rope_theta=100000.0,
        param_dtype="bfloat16"),
    "deepseek-moe-16b-s7": dict(
        name="deepseek-moe-16b-s7", family="moe", n_layers=7, d_model=2048,
        n_heads=16, n_kv=16, d_ff=1408, vocab=102400, n_experts=64,
        n_shared_experts=2, topk=6, head_dim=128, param_dtype="bfloat16"),
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree.leaves(tree):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_weights_and_leaves_are_the_pinned_ones(family):
    c = smoke_config(family)
    assert weights.leaves(c) == LEAVES[family]
    assert weights.stacks(c) == {"layers": 2}
    assert digest(weights.model_params(c, SEED)) == DIGEST[family]


@pytest.mark.parametrize("name", sorted(ARCH))
def test_arch_is_the_pinned_one(name):
    assert program.arch(common.config(name)) == ArchConfig(**ARCH[name])
    assert program.arch(common.config(name), "serve") == \
        ArchConfig(**ARCH[name], quantize="serve")


def test_a_stack_of_two_lengths_is_refused(monkeypatch):
    fam = type(sys)("uneven")
    fam.leaves = lambda c: [(("layers", "a"), (2,), 1.0, 2),
                            (("layers", "b"), (2,), 1.0, 3)]
    monkeypatch.setattr(common, "family", lambda name: fam)
    with pytest.raises(ValueError, match="'layers'"):
        weights.stacks({"name": "uneven", "family": "uneven"})


TOY_FAMILY = '''"""Toy family: dense attention throughout, a leading stack of
first_k_dense_replace dense-MLP layers, then MoE layers."""
from bench.families import dense, moe


def leaves(c):
    k, L = c["first_k_dense_replace"], c["num_hidden_layers"]
    return (dense.top_leaves(c)
            + dense.attn_block_leaves(c, "dense_layers", k)
            + dense.mlp_leaves(c, "dense_layers", k, c["intermediate_size"])
            + dense.attn_block_leaves(c, "layers", L - k)
            + moe.moe_leaves(c, "layers", L - k))


def arch(c):
    return moe.arch(c)


def token_matmul_flops(c, stack):
    ffn = dense.mlp_flops(c, c["intermediate_size"]) \\
        if stack == "dense_layers" else moe.moe_flops(c)
    return dense.attn_matmul_flops(c) + ffn


attention_flops = dense.attention_flops
'''

TOY_REFERENCE = '''"""Toy family reference: a stub, the toy family is never served."""


def score(c, seed, seqs, n_prompt):
    raise NotImplementedError("the toy family has no reference")
'''

TOY_CHECK = '''import json
import numpy as np
import jax
from bench import common, flops, program, weights

SEED = 2 ** 35 + 11
c = common.config("toy-moe")
full = jax.tree.map(lambda x: np.asarray(x, np.float32),
                    weights.model_params(c, SEED))
mismatched = []
for stack, n in [(None, 1)] + list(weights.stacks(c).items()):
    for l in range(n):
        one = weights.layer_params(c, SEED, None if stack is None else l,
                                   stack=stack)
        want = full if stack is None else jax.tree.map(
            lambda x: x[l], full[stack])
        for path, x in jax.tree_util.tree_leaves_with_path(one):
            y = want
            for k in path:
                y = y[k.key]
            if not np.array_equal(x, y):
                mismatched.append(f"{stack}/{l}/{path}")
cfg = program.arch(c)
print(json.dumps({
    "stacks": weights.stacks(c), "mismatched": mismatched,
    "dense_wi": list(full["dense_layers"]["mlp"]["wi"].shape),
    "moe_wi": list(full["layers"]["moe"]["wi"].shape),
    "decode_flops": flops.decode_flops(c, 10),
    "arch": [type(cfg).__name__, cfg.family, cfg.n_layers, cfg.d_ff,
             cfg.n_experts]}))
'''


def test_a_family_joins_as_files_alone(tmp_path):
    bm = common.benchmark()
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(common.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    c = smoke_config("moe")
    c.update(name="toy-moe", family="toy", num_hidden_layers=3,
             first_k_dense_replace=1, torch_dtype="float32")
    new = {tmp_path / "bench" / "families" / "toy.py": TOY_FAMILY,
           tmp_path / "bench" / "references" / "toy.py": TOY_REFERENCE,
           tmp_path / "bench" / "configs" / "toy-moe.json": json.dumps(c),
           tmp_path / "check_toy.py": TOY_CHECK}
    for path, text in new.items():
        assert path not in before
        path.write_text(text)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(common.ROOT / "src"))
    out = subprocess.run([sys.executable, "check_toy.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["stacks"] == {"dense_layers": 1, "layers": 2}
    assert got["mismatched"] == []
    assert got["dense_wi"] == [1, 64, 128] and got["moe_wi"] == [2, 4, 64, 32]
    # per layer: attention projections 2*64*(2*64 + 2*64); the dense MLP
    # 3*2*64*128; the MoE layer's 2 routed + 1 shared experts 3*3*2*64*32
    # and its router 2*64*4; attention over 10 keys 4*4*16*10; head 2*64*256
    attn, keys = 2 * 64 * 256, 4 * 4 * 16 * 10
    assert got["decode_flops"] == (
        1 * (attn + 3 * 2 * 64 * 128 + keys)
        + 2 * (attn + 3 * 3 * 2 * 64 * 32 + 2 * 64 * 4 + keys)
        + 2 * 64 * 256)
    assert got["arch"] == ["ArchConfig", "toy", 3, 32, 4]
    for path, data in before.items():
        assert path.read_bytes() == data, path
