"""Compile the serving path's kernels and steps for a described TPU v5e chip.

Nothing here runs on a chip: each test lowers a program at TinyLlama's
published widths for one device of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler (Pallas kernels with ``interpret=False``),
which refuses what interpret mode accepts — unaligned tiles, too much VMEM,
programs that do not fit the device. Step programs keep the published
widths and vocabulary but cut depth to two layers, and are the greedy
programs: the engine's sampler adds XLA sort and select ops over the
vocabulary, which compile in the same way and take most of the compile time.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.distributed.sharding import device_mesh
from repro.models import init_model, steps as ST
from repro.serving.store import make_store

HBM_BYTES = 16 * 2**30            # one v5e chip
SLOTS, MAX_SEQ, BLOCK, BUCKET = 8, 1024, 16, 512
CFG = get_config("tinyllama-1.1b").replace(n_layers=2)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the model code places values on the mesh it is traced under
    with shd.use_mesh(device_mesh(topo.devices[0])):
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    return total


@pytest.fixture(scope="module")
def params(one_chip):
    shapes = jax.eval_shape(lambda k: init_model(CFG, k),
                            jax.random.PRNGKey(0))
    return _on(one_chip, shapes)


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
def test_paged_attention_kernel_compiles(one_chip, pool_dtype):
    """The engine's pools are bf16 (``kv_cache_dtype``); f32 is the
    reference case."""
    from repro.kernels.paged_attention import paged_decode_attention
    H, KV, hd, MB = 32, 4, 64, 64
    NB = SLOTS * MB + 1
    f32, i32, kv = jnp.float32, jnp.int32, jnp.dtype(pool_dtype)
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((SLOTS, H, hd), f32),
        jax.ShapeDtypeStruct((NB, BLOCK, KV, hd), kv),
        jax.ShapeDtypeStruct((NB, BLOCK, KV, hd), kv),
        jax.ShapeDtypeStruct((SLOTS, MB), i32),
        jax.ShapeDtypeStruct((SLOTS,), i32)))
    compiled = paged_decode_attention.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_paged_native_decode_step_compiles(one_chip, params, use_kernel):
    store = make_store(CFG, SLOTS, MAX_SEQ, backend="paged",
                       block_size=BLOCK, native=True)
    cache = _on(one_chip, jax.eval_shape(store.decode_cache))
    batch = _on(one_chip, {
        "tokens": jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
        "active": jax.ShapeDtypeStruct((SLOTS,), jnp.bool_)})
    step = jax.jit(ST.make_paged_decode_step(CFG, use_kernel=use_kernel),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache, batch).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    _fits(compiled)


def test_fused_prefill_step_compiles(one_chip, params):
    # width 1: the Engine prefills every admitted request alone
    tokens, last = _on(one_chip, (
        jax.ShapeDtypeStruct((1, BUCKET), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32)))
    step = jax.jit(ST.make_prefill_with_cache_step(CFG))
    compiled = step.lower(params, tokens, last).compile()
    _fits(compiled)
