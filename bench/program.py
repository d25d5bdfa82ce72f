"""The benchmark's whole seam with the program under test.

Nothing else under ``bench/`` imports ``repro``. The seam is: ``ArchConfig``,
``EngineConfig`` and ``Router`` construction, ``Router.submit``/``step``/
``has_work``/``close``, the ``RouterRequest`` fields ``tokens``,
``token_ts`` and ``done``, the engine's ``completed`` list,
``scheduler.active`` and ``scheduler.buckets``, the engine ``Request``
fields ``prompt`` and ``metrics.admitted_s``, the clock ``metrics.now``, the Tensorizer W8A8 pass
(the control), and the step builders' XLA module names in the device trace
(``jit_prefill_step``, ``jit_decode_step``).
"""

from __future__ import annotations

import sys
from typing import Dict

from bench import common
from bench.common import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.configs.base import ArchConfig  # noqa: E402
from repro.serving.engine import EngineConfig  # noqa: E402
from repro.serving.metrics import now  # noqa: E402,F401
from repro.serving.router import Router, RouterConfig  # noqa: E402

# XLA module names of the jitted serving steps (models/steps.py builders)
PREFILL_MODULE = "jit_prefill_step"
DECODE_MODULE = "jit_decode_step"


def arch(c: Dict, quantize: str = "off") -> ArchConfig:
    """A configuration file as the program's ArchConfig: the family's fields
    (``bench/families/<family>.py`` ``arch``) and the common ones."""
    return ArchConfig(
        name=c["name"], family=c["family"],
        dtype=c["torch_dtype"], param_dtype=c["torch_dtype"],
        kv_cache_dtype=c["kv_cache_dtype"], quantize=quantize,
        **common.family(c["family"]).arch(c))


def router(c: Dict, params, quantize: str = "off") -> Router:
    """One in-process host on the first device, the serve CLI's path:
    Router -> InProcessTransport -> Engine, every implementation choice at
    the program's default."""
    dep = c["deployment"]
    ecfg = EngineConfig(max_slots=dep["max_slots"],
                        max_seq_len=dep["max_seq_len"],
                        max_queue=dep.get("max_queue", 64))
    return Router(arch(c, quantize), params, ecfg, RouterConfig(n_hosts=1))


def engine_requests(r: Router):
    """The engine-side Request objects of the router's host that were
    admitted: finished ones and those still in a slot."""
    e = r.engines[0]
    return list(e.completed) + list(e.scheduler.active.values())


def quantize_w8a8(params):
    """The program's own lower-precision serving path (Tensorizer W8A8 over
    the serve CLI's predicate), jitted with the bf16 tree donated."""
    import jax
    from repro.core import tensorizer as tz
    from repro.launch.serve import _quant_predicate
    return jax.jit(lambda p: tz.quantize_params(p, predicate=_quant_predicate),
                   donate_argnums=0)(params)
