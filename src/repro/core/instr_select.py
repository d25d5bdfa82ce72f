"""Instruction selection — the paper's Table-1 methodology, made a live component.

GPETPU measured OPS (ops/sec) and RPS (results/sec) per Edge TPU instruction
(paper §3.2, Eqs. 1-3) and rewrote algorithms to use the highest-RPS
instruction: on that hardware conv2D beat FullyConnected by 25x in RPS, so GEMM
was lowered onto strided conv2D (§7.1.2).

Here the same table is (re-)measured on the actual backend by
``benchmarks/table1_ops.py`` and cached per ``device_kind`` (a table measured
on one device kind is never read on another); ``best_gemm_lowering``
consumes it.
On TPU v5e the ordering *inverts* (matmul is the MXU-native op; conv lowers to
matmul with layout overhead) — the framework discovers that from data rather
than assuming it, exactly as the paper did.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp

_CACHE_ENV = "REPRO_INSTR_TABLE"
_DEFAULT_CACHE = os.path.join(os.path.dirname(__file__), "_instr_table.json")
_tables: Dict[str, Dict[str, Dict[str, float]]] = {}   # device_kind -> table


def measure_op(fn: Callable, *args, iters: int = 30) -> Dict[str, float]:
    """OPS / RPS via the paper's two-run differencing (Eqs. 1-2): run the op
    ``iters`` and ``2*iters`` times; the difference cancels transfer/setup time."""
    f = jax.jit(fn)
    out = f(*args)
    jax.block_until_ready(out)  # compile + warm

    def run(n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    t1, t2 = run(iters), run(2 * iters)
    dt = max(t2 - t1, 1e-9)
    n_results = int(jnp.size(out))
    return {
        "ops_per_s": iters / dt,                    # Eq. 1
        "results_per_s": iters * n_results / dt,    # Eq. 2
    }


def build_table(size: int = 256, iters: int = 20) -> Dict[str, Dict[str, float]]:
    """Measure every GPETPU instruction (paper Table 1) on this backend."""
    from repro.core import instr as I

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (size, size), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (size, size), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (size,), jnp.float32)
    k3 = jax.random.normal(jax.random.PRNGKey(3), (3, 3), jnp.float32)

    cases = {
        "conv2D": (I.conv2d_quant, (a, k3)),
        "FullyConnected": (I.fully_connected_quant, (v, b)),
        "sub": (I.sub_quant, (a, b)),
        "add": (I.add_quant, (a, b)),
        "mul": (I.mul_quant, (a, b)),
        "crop": (lambda x: I.crop_fp(x, size // 2, size // 2), (a,)),
        "ext": (lambda x: I.ext_fp(x), (a,)),
        "mean": (I.mean_quant, (a,)),
        "max": (I.max_quant, (a,)),
        "tanh": (I.tanh_quant, (a,)),
        "ReLu": (I.relu_quant, (a,)),
        # GEMM lowerings measured head-to-head for best_gemm_lowering
        "gemm_fully_connected": (lambda x, y: _gemm_fc(x, y), (a, b)),
        "gemm_conv2d": (lambda x, y: _gemm_conv(x, y), (a, b)),
    }
    table = {}
    for name, (fn, args) in cases.items():
        table[name] = measure_op(fn, *args, iters=iters)
    return table


def _gemm_fc(a, b):
    from repro.core import gemm

    return gemm.gemm_fully_connected(a, b)


def _gemm_conv(a, b):
    from repro.core import gemm

    return gemm.gemm_conv2d(a, b)


def get_table(refresh: bool = False) -> Dict[str, Dict[str, float]]:
    """The measured table of the default device's kind: from memory, else
    from the cache file's entry for that kind, else measured now and added
    to the file."""
    kind = jax.devices()[0].device_kind
    if kind in _tables and not refresh:
        return _tables[kind]
    path = os.environ.get(_CACHE_ENV, _DEFAULT_CACHE)
    stored: Dict[str, Dict] = {}
    if os.path.exists(path):
        with open(path) as f:
            stored = json.load(f)
    if kind not in stored or refresh:
        stored[kind] = build_table()
        try:
            with open(path, "w") as f:
                json.dump(stored, f, indent=1)
        except OSError:
            pass
    _tables[kind] = stored[kind]
    return _tables[kind]


def best_gemm_lowering() -> str:
    """Pick the GEMM lowering with the highest measured RPS (paper §7.1.3)."""
    t = get_table()
    fc = t.get("gemm_fully_connected", {}).get("results_per_s", 0.0)
    cv = t.get("gemm_conv2d", {}).get("results_per_s", 0.0)
    return "fully_connected" if fc >= cv else "conv2d"
