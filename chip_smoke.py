#!/usr/bin/env python3
"""Bring-up smoke test: the serving path on a TPU, at full model width.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: the Router phase only

Serves ``tinyllama-1.1b`` (22 layers, d_model 2048, random weights from a
seed) through the entry points a user calls:

  A  HTTP front, float weights: ``serve_api`` over a paged, block-native
     Engine (8 slots, max_seq_len 1024). Eight concurrent completions
     (prompts of 128 and 512 tokens, 32 new tokens each, one of them
     SSE-streamed) must each return 32 tokens with ``finish_reason ==
     "length"``, equal to a direct Engine run of the same requests one at a
     time.
  B  int8 weights + the Pallas paged-attention kernel: the kernel's output
     against the jnp gather reference at these widths, then the same traffic
     straight on the Engine, all submitted at once, equal to one at a time,
     with the OPQ flag audit showing one prefill per request and no replay
     decodes.
  --four-chips  a Router with four in-process hosts, one per chip: 16 greedy
     requests over 4 sessions, submitted at once, with a mid-run
     ``drain(0)``; each host's params and cache live on its own chip, and the
     stitched streams equal one engine on chip 0 serving the same segments.

It refuses to run without a TPU (exit 1, no result line). Each phase prints
its facts on its own line: device kind and count, jax/jaxlib/libtpu
versions, compile and wall seconds, and the device's peak bytes in use.
These are smoke facts, not benchmark metrics. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``.

The phases are plain functions of an ``ArchConfig``, so the tests run them
at ``smoke()`` width on the CPU (tests/test_chip_smoke.py); only ``main``
insists on the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import http.client
import importlib.metadata
import json
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.core import tensorizer as tz  # noqa: E402
from repro.kernels.paged_attention import paged_decode_attention  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.distributed.sharding import device_mesh  # noqa: E402
from repro.launch.serve import _quant_predicate  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.serving import Engine, EngineConfig, serve_api  # noqa: E402
from repro.serving.router import Router, RouterConfig  # noqa: E402
from repro.serving.scheduler import bucket_for  # noqa: E402

ARCH = "tinyllama-1.1b"
SEED = 0
# the kernel's online softmax is not bit-exact to the full-row reference;
# either side may round an f32 matmul operand to bf16 on the MXU
KERNEL_TOL = 1e-2
ROUTER_REQUESTS = 16
ROUTER_SESSIONS = 4
ROUTER_GEN = 24


@dataclasses.dataclass(frozen=True)
class Traffic:
    """The requests a phase sends and the engine geometry serving them."""

    n_requests: int = 8
    prompt_lens: Tuple[int, ...] = (128, 512)
    gen: int = 32
    slots: int = 8
    max_seq_len: int = 1024
    block: int = 16


TRAFFIC = Traffic()
# one prompt length: each host compiles its own programs for its own chip
ROUTER_TRAFFIC = Traffic(prompt_lens=(128,))


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ------------------------------------------------------------------ facts

class CompileClock:
    """Sums jax's backend-compile durations while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


def _versions() -> str:
    out = []
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out.append(f"{pkg} {importlib.metadata.version(pkg)}")
        except importlib.metadata.PackageNotFoundError:
            out.append(f"{pkg} absent")
    return ", ".join(out)


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def report(phase: str, clock: CompileClock, wall_s: float, devices,
           extra: str = "") -> None:
    d = devices[0]
    peaks = " ".join(f"{dev.id}:{_peak_bytes(dev)}" for dev in devices)
    log(f"phase {phase}: device {d.platform} {d.device_kind} x"
        f"{len(jax.devices())} | {_versions()} | compile_s {clock.seconds} "
        f"({clock.count} compiles) | wall_s {wall_s} | "
        f"peak_bytes_in_use {peaks}" + (f" | {extra}" if extra else ""))


# ------------------------------------------------------------------ traffic

def make_prompts(cfg: ArchConfig, traffic: Traffic,
                 n: int = 0) -> List[np.ndarray]:
    rng = np.random.default_rng(SEED)
    lens = traffic.prompt_lens
    return [rng.integers(0, cfg.vocab, (lens[i % len(lens)],), dtype=np.int32)
            for i in range(n or traffic.n_requests)]


def engine_config(traffic: Traffic, **kw) -> EngineConfig:
    return EngineConfig(max_slots=traffic.slots,
                        max_seq_len=traffic.max_seq_len,
                        cache_backend="paged", paged_native=True,
                        block_size=traffic.block, **kw)


def one_at_a_time(engine: Engine, prompts, gen: int) -> List[List[int]]:
    out = []
    for p in prompts:
        req = engine.submit(p, gen, strict=True)
        engine.run_until_complete()
        _check_finished(req.tokens, req.finish_reason, gen,
                        f"request {req.id}")
        out.append(list(req.tokens))
    return out


def _check_finished(tokens: List[int], reason, gen: int, what: str) -> None:
    if len(tokens) != gen or reason != "length":
        raise AssertionError(f"{what}: {len(tokens)} tokens, finish "
                             f"{reason!r}; want {gen}, 'length'")


def concurrent(engine: Engine, prompts, gen: int) -> List[List[int]]:
    """Submit every request at once: they are admitted together and decode
    in one shared batch."""
    reqs = [engine.submit(p, gen, strict=True) for p in prompts]
    engine.run_until_complete()
    for r in reqs:
        _check_finished(r.tokens, r.finish_reason, gen, f"request {r.id}")
    return [list(r.tokens) for r in reqs]


def audit_flags(engine: Engine, prompts, rounds: int) -> Dict[str, int]:
    """OPQ flag audit after ``rounds`` passes of ``prompts``: one prefill
    instruction per admitted request, one decode instruction per decode
    step, nothing else (no replay decodes)."""
    stats = engine.stats()
    flags = stats["opq"]["flags"]
    want = {}
    for p in prompts:
        flag = f"prefill/{bucket_for(len(p), engine.scheduler.buckets)}"
        want[flag] = want.get(flag, 0) + rounds
    if {f: c for f, c in flags.items() if f != "decode"} != want:
        raise AssertionError(f"OPQ instructions {flags}, want prefills "
                             f"{want} and decode")
    if flags["decode"] != stats["decode_steps"]:
        raise AssertionError(
            f"{flags['decode']} decode instructions for "
            f"{stats['decode_steps']} decode steps")
    return flags


def _complete(port: int, prompt, gen: int, stream: bool):
    """One POST /v1/completions; returns (tokens, finish_reason)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        body = {"prompt": [int(t) for t in prompt], "max_new_tokens": gen,
                "stream": stream}
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise AssertionError(f"HTTP {resp.status}: {resp.read()!r}")
        if not stream:
            out = json.loads(resp.read())
            return out["tokens"], out["finish_reason"]
        tokens, reason = [], None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data == "[DONE]":
                break
            event = json.loads(data)
            if "error" in event:
                raise AssertionError(f"SSE error event: {event['error']}")
            if event.get("done"):
                reason = event["finish_reason"]
                if event["n_tokens"] != len(tokens):
                    raise AssertionError("SSE token count mismatch")
            else:
                if event["index"] != len(tokens):
                    raise AssertionError("SSE events out of order")
                tokens.append(event["token"])
        return tokens, reason
    finally:
        conn.close()


def http_completions(engine: Engine, prompts, gen: int):
    """The prompts through ``serve_api``, one client thread each, all sent
    at once. Request 0 streams (SSE)."""
    srv = serve_api(engine, port=0)
    results: List = [None] * len(prompts)
    errors: List[BaseException] = []

    def client(i):
        try:
            results[i] = _complete(srv.port, prompts[i], gen, stream=i == 0)
        except BaseException as exc:  # re-raised in the main thread below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1200)
    finally:
        srv.close()
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads) or None in results:
        raise AssertionError("an HTTP completion did not return")
    for i, (tokens, reason) in enumerate(results):
        _check_finished(tokens, reason, gen, f"HTTP request {i}")
    return [list(t) for t, _ in results]


# ------------------------------------------------------------------ phases

def assert_same(got: List[List[int]], want: List[List[int]],
                what: str) -> None:
    """Token streams must be equal; names each differing (request, first
    differing token)."""
    if got != want:
        bad = [(i, next((j for j, (a, b) in enumerate(zip(g, w)) if a != b),
                        min(len(g), len(w))))
               for i, (g, w) in enumerate(zip(got, want)) if g != w]
        raise AssertionError(f"{what} at (request, token) {bad}")


def phase_http(cfg: ArchConfig, params, traffic: Traffic = TRAFFIC) -> Dict:
    """Phase A: float weights behind the HTTP front, concurrent arrivals
    against the same requests run directly one at a time."""
    prompts = make_prompts(cfg, traffic)
    engine = Engine(cfg, params, engine_config(traffic))
    try:
        direct = one_at_a_time(engine, prompts, traffic.gen)
        served = http_completions(engine, prompts, traffic.gen)
        assert_same(served, direct, "HTTP tokens differ from the "
                    "one-at-a-time engine run")
        flags = audit_flags(engine, prompts, rounds=2)
    finally:
        engine.close()
    return {"requests": len(prompts), "tokens": sum(map(len, served)),
            "flags": flags}


def gather_reference(q, k_pool, v_pool, tables, index):
    """Plain jnp paged attention: gather each slot's blocks, full-row
    softmax, f32 at the highest matmul precision."""
    B, H, hd = q.shape
    _, bs, KV, _ = k_pool.shape
    S = tables.shape[1] * bs
    hi = jax.lax.Precision.HIGHEST
    k = jnp.take(k_pool, tables.reshape(-1), axis=0).reshape(B, S, KV, hd)
    v = jnp.take(v_pool, tables.reshape(-1), axis=0).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q, k, precision=hi) * hd ** -0.5
    s = jnp.where(jnp.arange(S)[None, None, :] <= index[:, None, None],
                  s, -1e30)
    return jnp.einsum("bhk,bkhd->bhd", jax.nn.softmax(s, axis=-1), v,
                      precision=hi)


def kernel_case(cfg: ArchConfig, traffic: Traffic):
    """Kernel inputs at the engine's widths and KV dtype: one query per
    slot, each slot leasing a random number of blocks of its table, plus
    the null block."""
    rng = np.random.default_rng(SEED)
    B, H, KV, hd = traffic.slots, cfg.n_heads, cfg.n_kv, cfg.hd
    bs = traffic.block
    MB = traffic.max_seq_len // bs
    NB = B * MB + 1

    def bf16_normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16).astype(
            jnp.float32)

    tables = np.zeros((B, MB), np.int32)
    index = np.zeros((B,), np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for b in range(B):
        n_lease = int(rng.integers(1, MB + 1))
        tables[b, :n_lease] = [free.pop() for _ in range(n_lease)]
        index[b] = int(rng.integers(0, n_lease * bs))
    kv = jnp.dtype(cfg.kv_cache_dtype)
    return (bf16_normal(B, H, hd), bf16_normal(NB, bs, KV, hd).astype(kv),
            bf16_normal(NB, bs, KV, hd).astype(kv), jnp.asarray(tables),
            jnp.asarray(index))


def check_kernel(cfg: ArchConfig, traffic: Traffic) -> float:
    """The Pallas kernel against the gather reference; returns the max
    absolute difference (asserted <= KERNEL_TOL)."""
    case = kernel_case(cfg, traffic)
    got = paged_decode_attention(*case,
                                 interpret=jax.default_backend() != "tpu")
    err = float(jnp.max(jnp.abs(got - gather_reference(*case))))
    if not err <= KERNEL_TOL:
        raise AssertionError(f"paged kernel vs gather reference: max abs "
                             f"diff {err} > {KERNEL_TOL}")
    return err


def quantize(cfg: ArchConfig, params):
    """Tensorizer W8A8 serving weights, as ``launch/serve.py --quantize
    serve`` makes them."""
    return (cfg.replace(quantize="serve"),
            tz.quantize_params(params, predicate=_quant_predicate))


def phase_kernel(cfg: ArchConfig, params, traffic: Traffic = TRAFFIC) -> Dict:
    """Phase B: int8 weights, float KV cache, the Pallas paged kernel.
    ``cfg``/``params`` are already quantized (:func:`quantize`)."""
    err = check_kernel(cfg, traffic)
    prompts = make_prompts(cfg, traffic)
    engine = Engine(cfg, params, engine_config(traffic, paged_kernel=True))
    try:
        direct = one_at_a_time(engine, prompts, traffic.gen)
        assert_same(concurrent(engine, prompts, traffic.gen), direct,
                    "concurrent kernel-path tokens differ from one at a time")
        flags = audit_flags(engine, prompts, rounds=2)
    finally:
        engine.close()
    return {"kernel_max_abs_diff": err, "requests": len(prompts),
            "flags": flags}


def phase_router(cfg: ArchConfig, params, devices,
                 traffic: Traffic = ROUTER_TRAFFIC) -> Dict:
    """Four in-process Router hosts, host i on devices[i]. All requests are
    submitted at once over four sessions; two tokens in, ``drain(0)`` hands
    host 0's streams off mid-decode. Every stream must equal one engine on
    devices[0]: an undisturbed stream its one-at-a-time run, a handed-off
    stream its first W tokens followed by the continuation (prompt + those
    W tokens, admitted fresh) on that engine. Whether a handed-off stream
    also equals its undrained run is reported, not asserted: re-prefilled
    positions are not bit-identical to decoded ones (ROADMAP Design 10)."""
    prompts = make_prompts(cfg, traffic, n=ROUTER_REQUESTS)
    ref = Engine(cfg, params, engine_config(traffic),
                 mesh=device_mesh(devices[0]))
    try:
        undrained = one_at_a_time(ref, prompts, ROUTER_GEN)
        router = Router(cfg, params, engine_config(traffic),
                        RouterConfig(n_hosts=len(devices),
                                     handoff_threshold=ROUTER_GEN - 4))
        try:
            reqs = [router.submit(p, ROUTER_GEN,
                                  session=str(i % ROUTER_SESSIONS),
                                  strict=True)
                    for i, p in enumerate(prompts)]
            deadline = time.monotonic() + 900
            while not all(len(r.tokens) >= 2 for r in reqs):
                router.step()
                if time.monotonic() > deadline:
                    raise AssertionError("requests were never admitted")
            router.drain(0)
            cuts = {i: len(r.tokens) for i, r in enumerate(reqs)
                    if len(r.hosts) > 1}
            router.run_until_complete()
            for i, engine in enumerate(router.engines):
                placed = {d for leaf in jax.tree.leaves(
                    (engine.params, engine.store.cache))
                    for d in leaf.devices()}
                if engine.device != devices[i] or placed != {devices[i]}:
                    raise AssertionError(f"host {i} lives on {placed}, want "
                                         f"{devices[i]}")
            for r in reqs:
                _check_finished(r.tokens, r.finish_reason, ROUTER_GEN,
                                f"router request {r.id}")
            got = [list(r.tokens) for r in reqs]
        finally:
            router.close()
        if not cuts:
            raise AssertionError("drain(0) handed no stream to another host")
        want = list(undrained)
        for i, w in cuts.items():
            cont = np.concatenate([prompts[i],
                                   np.asarray(undrained[i][:w], np.int32)])
            want[i] = undrained[i][:w] + one_at_a_time(ref, [cont],
                                                       ROUTER_GEN - w)[0]
    finally:
        ref.close()
    assert_same(got, want, "router streams differ from one engine on chip 0")
    return {"requests": len(reqs), "handoffs": cuts,
            "handoffs_equal_undrained": sum(got[i] == undrained[i]
                                            for i in cuts)}


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the Router phase, one host per chip on "
                         "four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    cfg = get_config(ARCH)
    log(f"model {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv} kv, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, params {cfg.param_dtype}, seed {SEED}")
    params = init_model(cfg, jax.random.PRNGKey(SEED))

    if args.four_chips:
        with CompileClock() as clock:
            t0 = time.perf_counter()
            facts = phase_router(cfg, params, devices[:4])
            report("router-4", clock, time.perf_counter() - t0, devices[:4],
                   f"{facts['requests']} requests, handed off by drain(0) "
                   f"(request: tokens before) {facts['handoffs']}, tokens "
                   f"== one engine on chip 0, handed-off streams equal to "
                   f"undrained: {facts['handoffs_equal_undrained']}")
    else:
        with CompileClock() as clock:
            t0 = time.perf_counter()
            facts = phase_http(cfg, params)
            report("A http", clock, time.perf_counter() - t0, devices[:1],
                   f"{facts['requests']} requests, {facts['tokens']} tokens, "
                   f"HTTP (concurrent) == one at a time, flags "
                   f"{facts['flags']}")
        qcfg, qparams = quantize(cfg, params)
        del params
        gc.collect()
        with CompileClock() as clock:
            t0 = time.perf_counter()
            facts = phase_kernel(qcfg, qparams)
            report("B int8+kernel", clock, time.perf_counter() - t0,
                   devices[:1],
                   f"kernel max abs diff {facts['kernel_max_abs_diff']} "
                   f"(tol {KERNEL_TOL}), concurrent == one at a time, "
                   f"flags {facts['flags']}")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
