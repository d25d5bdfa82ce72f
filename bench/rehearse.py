"""Compile a configuration's serving programs for a described TPU v5e chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <config> [<config> ...]

No chip is needed and nothing runs: for each configuration file it compiles
the benchmark's jitted weight maker, the engine's prefill step at every
bucket the configuration's mixes can reach, and its decode step, all for
one chip of a described ``v5e:2x2`` topology, and prints
``memory_analysis()`` of each beside the bytes the weights and the cache
hold. A program that does not fit the chip's memory, or that its compiler
refuses, fails here at no chip time. A compile that passes is not a chip
run.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

GIB = 2 ** 30


def rehearse(name: str, topo) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from bench import common, harness, program, weights
    from repro.distributed import sharding as shd
    from repro.serving.engine import _jitted_steps
    from repro.serving.sampling import stack_params
    from repro.serving.scheduler import default_buckets
    from repro.models import serve as SV

    chip = SingleDeviceSharding(topo.devices[0])
    c = common.config(name)
    cfg = program.arch(c)
    dep = c["deployment"]

    def spec(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    def show(what, compiled, extra=0):
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"[rehearse] {name} {what}: args "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, out "
              f"{m.output_size_in_bytes / GIB:.2f} GiB, alias "
              f"{m.alias_size_in_bytes / GIB:.2f} GiB, temp "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB; with what else is "
              f"live {(total + extra) / GIB:.2f} GiB", flush=True)

    key = spec(common.seed_key(0))
    params = jax.eval_shape(lambda: weights.model_params(c, 0))
    wbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    show("weights", weights.build_fn(c).lower(key).compile())
    mesh = shd.device_mesh(topo.devices[0])
    with shd.use_mesh(mesh), mesh:
        prefill, _, _, decode, _ = _jitted_steps(cfg, "contiguous")
        cache = jax.eval_shape(lambda: SV.init_cache(
            cfg, dep["max_slots"], dep["max_seq_len"], per_slot_index=True))
        cbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        print(f"[rehearse] {name}: weights {wbytes / GIB:.2f} GiB, cache "
              f"{cbytes / GIB:.2f} GiB", flush=True)
        p = spec(params)
        lens = set()
        for mix in {w["traffic"] for w in common.benchmark()["workloads"]
                    if w["config"] == name}:
            m = common.mix(mix)
            lens.update(harness.warmup_lengths(
                default_buckets(dep["max_seq_len"]),
                m["prompt_len"]["min"], m["prompt_len"]["max"]))
        for n in sorted(lens):
            b = next(x for x in default_buckets(dep["max_seq_len"]) if x >= n)
            smp = spec(stack_params([None], np.zeros((1, cfg.vocab_padded),
                                                     bool)))
            comp = prefill.lower(
                p, jax.ShapeDtypeStruct((1, b), jnp.int32, sharding=chip),
                jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip),
                smp).compile()
            show(f"prefill bucket {b}", comp, cbytes)
        B = dep["max_slots"]
        batch = spec({"tokens": jnp.zeros((B, 1), jnp.int32),
                      "active": jnp.zeros((B,), bool),
                      "sampling": stack_params(
                          [None] * B, np.zeros((B, cfg.vocab_padded), bool))})
        show("decode", decode.lower(p, spec(cache), batch).compile())


def main() -> None:
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in sys.argv[1:]:
        rehearse(name, topo)


if __name__ == "__main__":
    main()
