"""The serving path's host spans and device named scopes.

  * spans   — a smoke Router traced by ``jax.profiler`` emits every program
              span (``serving/metrics.py`` ``span``): the Router's step,
              pump and poll, the Engine's step, admission, prefill wait,
              seed and decode with its three phases nested inside it, the
              OPQ worker's put, run and block, and one ``python.gc`` span
              per collection; the prefill-wait and seed counters are fed by
              their spans; the hooks a trace relies on are installed once
  * scopes  — the lowered decode and prefill steps of a dense and a MoE
              config carry the ``attn``, ``mlp`` or ``moe`` (``router``,
              ``experts``, ``shared``), ``logits`` and ``sample`` scopes
              (``models/layers.py`` ``scoped``)
"""

import collections
import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.models import init_model
from repro.serving import EngineConfig, Router, RouterConfig
from repro.serving.engine import _jitted_steps
from repro.serving.sampling import stack_params

CFG = get_config("tinyllama-1.1b").smoke()
MOE_CFG = get_config("deepseek-moe-16b").smoke()

SPANS = ("router.step", "router.pump", "router.poll", "engine.step",
         "engine.admit", "engine.prefill_wait", "engine.seed",
         "engine.decode", "engine.decode.prepare", "engine.decode.wait",
         "engine.decode.emit", "opq.put", "opq.run", "opq.block",
         "python.gc")
PHASES = ("engine.decode.prepare", "engine.decode.wait", "engine.decode.emit")


def _traced_spans(tmp_path):
    """Serve three requests through a one-host Router under the profiler
    (after a warm-up that compiles every shape); returns the router and
    {(plane, line): [(name, start_ns, end_ns)]} of the program's spans."""
    from jax.profiler import ProfileData
    r = Router(CFG, init_model(CFG, jax.random.PRNGKey(0)),
               EngineConfig(max_slots=2, max_seq_len=32),
               RouterConfig(n_hosts=1))
    rng = np.random.default_rng(0)

    def serve():
        for n in (5, 9, 12):
            r.submit(rng.integers(0, CFG.vocab, n, dtype=np.int32), 4)
        r.run_until_complete()

    serve()
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    out = collections.defaultdict(list)
    for i, plane in enumerate(ProfileData.from_file(path).planes):
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in SPANS:
                    out[(i, j)].append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    return r, out


def test_serving_emits_every_span_nested(tmp_path):
    r, by_line = _traced_spans(tmp_path)
    events = [e for evs in by_line.values() for e in evs]
    assert {n for n, _, _ in events} == set(SPANS)
    assert sum(n == "python.gc" for n, _, _ in events) >= 1
    decodes = 0
    for evs in by_line.values():
        for name, s, e in evs:
            if name != "engine.decode":
                continue
            decodes += 1
            inside = [n for n, a, b in evs if n in PHASES and s <= a
                      and b <= e]
            assert sorted(inside) == sorted(PHASES)
    phases = [x for x in events if x[0] in PHASES]
    assert decodes and len(phases) == 3 * decodes
    # the counters the serve CLI reports are fed by their spans
    m = r.engines[0].metrics
    assert m.prefill_wait_s > 0 and m.seed_write_s > 0
    assert "mean_rpc_us" not in r.transports[0].metrics.summary()
    r.close()


def test_trace_hooks_are_installed_once():
    """Engines install the GC span callback once per process, and key the
    compile cache on op metadata: a cached executable compiled by other
    code would otherwise bring that code's scopes into the device trace."""
    from repro.serving.metrics import _GcSpans, trace_hooks
    trace_hooks()
    trace_hooks()
    assert sum(isinstance(cb, _GcSpans) for cb in gc.callbacks) == 1
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def _lowered(cfg, step):
    mesh = shd.device_mesh(jax.devices()[0])
    with shd.use_mesh(mesh), mesh:
        prefill, _, _, decode, _ = _jitted_steps(cfg, "contiguous")
        params = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
        if step == "prefill":
            return prefill.lower(
                params, jax.ShapeDtypeStruct((1, 32), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32),
                stack_params([None], np.zeros((1, cfg.vocab_padded), bool)))
        from repro.models import serve as SV
        cache = jax.eval_shape(lambda: SV.init_cache(
            cfg, 2, 64, per_slot_index=True))
        batch = {"tokens": jnp.zeros((2, 1), jnp.int32),
                 "active": jnp.zeros((2,), bool),
                 "sampling": stack_params(
                     [None] * 2, np.zeros((2, cfg.vocab_padded), bool))}
        return decode.lower(params, cache, batch)


@pytest.mark.parametrize("family", ["dense", "moe"])
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_steps_carry_named_scopes(family, step):
    cfg = CFG if family == "dense" else MOE_CFG
    text = _lowered(cfg, step).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    scopes = ["attn", "logits", "sample"] + (
        ["mlp"] if family == "dense"
        else ["moe", "moe/router", "moe/experts", "moe/shared"])
    for scope in scopes:
        assert any(re.search(rf"(^|/){scope}/", n) for n in names), scope
