"""The plain references against the program's own forward, on the CPU at
smoke widths, in float32; and the benchmark's weight tree against the
program's initializer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _bench_path  # noqa: F401
from smoke_sizes import smoke_config
from bench import common, program, weights
from repro.models import init_model
from repro.models import serve as SV


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_reference_matches_program_forward(family):
    c = smoke_config(family)
    c["torch_dtype"] = "float32"
    cfg = program.arch(c)
    params = weights.model_params(c, 7)
    seq = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = SV.prefill_with_cache(params, cfg,
                                       {"tokens": jnp.asarray(seq)[None]})
    got = np.asarray(got[0, :, :256], np.float32)
    want = common.reference(family).logits(c, 7, seq)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_weight_tree_is_the_programs(family):
    c = smoke_config(family)
    cfg = program.arch(c)
    want = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: weights.model_params(c, 0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_reference_layers_redraw_the_served_weights():
    c = smoke_config("moe")
    full = weights.model_params(c, 2 ** 40 + 3)
    one = weights.layer_params(c, 2 ** 40 + 3, 1)
    top = weights.layer_params(c, 2 ** 40 + 3, None)
    np.testing.assert_array_equal(
        np.asarray(full["layers"]["moe"]["wi"][1], np.float32),
        np.asarray(one["moe"]["wi"]))
    np.testing.assert_array_equal(np.asarray(full["embed"], np.float32),
                                  np.asarray(top["embed"]))
    other = weights.layer_params(c, 3, 1)
    assert not np.array_equal(np.asarray(one["moe"]["wi"]),
                              np.asarray(other["moe"]["wi"]))
