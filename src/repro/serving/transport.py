"""HostTransport: the Router<->host boundary as a real protocol.

PR 5's Router fronted "hosts" that were in-process Engine objects — every
placement call was a Python attribute access, so the fleet could never
survive a host process dying, and fleet throughput never measured real
process parallelism. This module extracts the complete Router->host call
surface into the :class:`HostTransport` protocol and provides two backends:

  * :class:`InProcessTransport` — today's behavior, now just one
    implementation: an :class:`EngineHost` wrapping an Engine in the same
    address space. The Router drives the engine one step per fleet
    iteration through ``pump()``.
  * :class:`SubprocessTransport` — one OS process per host running the
    ``serving/host_main.py`` worker loop, speaking length-prefixed
    msgpack-or-JSON frames over an AF_UNIX socket. The worker FREE-RUNS
    its engine between requests (the step loop is driven by the worker,
    not the caller), which is only correct because the engine is
    batch-invariant and greedy/seeded decode is a pure function of the
    token prefix — the async fleet emits streams bit-identical to a
    synchronous single engine (tests/test_transport.py).

Failure semantics: every RPC carries a ``seq`` number; replies with a
stale seq (duplicated or late frames) are discarded. Idempotent calls
(door predicates, polls, stats, probes) retry a bounded number of times
with a FRESH seq on timeout; non-idempotent calls (submit, evict,
preempt) never retry — a failure raises :class:`TransportError` and the
Router marks the host LOST, re-places its queued work, and re-admits its
in-flight streams as continuations from the tokens already harvested.
Tokens only count as emitted once the Router has polled them, so a
SIGKILLed worker loses only un-harvested tokens — which determinism
regenerates exactly, never double-emitting (the crash-tolerance half of
the bit-identity invariant).

Workers rebuild their model deterministically from a *model spec*
(arch name + smoke/quantize/overrides + init seed) instead of shipping
parameter pytrees over the wire: ``init_model(cfg, PRNGKey(seed))`` is
bit-reproducible on a given backend, so parent and worker hold identical
weights by construction.
"""

from __future__ import annotations

import base64
import dataclasses
import itertools
import json
import os
import socket
import struct
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Dict, List, Optional, Protocol, Sequence

import jax
import numpy as np

from repro.distributed.sharding import device_mesh
from repro.serving.engine import (
    Engine, EngineConfig, QueueFull, Request, RequestState,
)
from repro.serving.metrics import TransportMetrics, now
from repro.serving.sampling import (
    SamplingParams, sampling_from_wire, sampling_to_wire,
)

try:                                   # optional: CI installs jax/numpy/pytest
    import msgpack                     # only — frames fall back to JSON
except ImportError:                    # pragma: no cover - environment-dependent
    msgpack = None

__all__ = [
    "TransportError", "HostTransport", "EngineHost", "InProcessTransport",
    "SubprocessTransport", "Channel", "build_inproc_fleet",
    "build_model_spec", "realize_model_spec",
    "engine_cfg_to_wire", "engine_cfg_from_wire", "QueueFull",
]

MAX_FRAME_BYTES = 64 * 1024 * 1024     # sanity bound on one frame


class TransportError(Exception):
    """Host-level transport failure: timeout, dropped connection, dead
    worker. Distinct from application errors a healthy host returns (those
    re-raise as their original exception type) — the Router's cue to mark
    the host LOST and re-place its work."""


# --------------------------------------------------------------------- codec

def _sanitize(x):
    """Python/numpy tree -> plain JSON/msgpack-able tree (ndarrays as
    dtype/shape/b64 triples, numpy scalars as Python scalars)."""
    if isinstance(x, np.ndarray):
        return {"__nd__": True, "dtype": str(x.dtype),
                "shape": list(x.shape),
                "b64": base64.b64encode(
                    np.ascontiguousarray(x).tobytes()).decode("ascii")}
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.floating):
        return float(x)
    if isinstance(x, np.bool_):
        return bool(x)
    if isinstance(x, dict):
        return {k: _sanitize(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_sanitize(v) for v in x]
    return x


def _restore(x):
    if isinstance(x, dict):
        if x.get("__nd__"):
            arr = np.frombuffer(base64.b64decode(x["b64"]),
                                dtype=np.dtype(x["dtype"]))
            return arr.reshape([int(s) for s in x["shape"]]).copy()
        return {k: _restore(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_restore(v) for v in x]
    return x


def default_codec() -> str:
    return "msgpack" if msgpack is not None else "json"


def encode_frame(obj, codec: Optional[str] = None) -> bytes:
    """Object -> one frame body: 1 codec byte + payload."""
    tree = _sanitize(obj)
    codec = codec or default_codec()
    if codec == "msgpack":
        return b"M" + msgpack.packb(tree, use_bin_type=True)
    return b"J" + json.dumps(tree).encode()


def decode_frame(body: bytes):
    """Inverse of :func:`encode_frame` — dispatches on the codec byte, so a
    JSON peer can decode a msgpack sender's frames only when msgpack is
    importable locally (both ends of an AF_UNIX socket share the env)."""
    if body[:1] == b"M":
        if msgpack is None:
            raise TransportError("received a msgpack frame but msgpack is "
                                 "not importable here")
        return _restore(msgpack.unpackb(body[1:], raw=False,
                                        strict_map_key=False))
    return _restore(json.loads(body[1:].decode()))


class Channel:
    """Length-prefixed frames over a stream socket. The seam the transport
    fault-injection tests wrap (a flaky channel drops/duplicates/delays
    frames here without touching the protocol logic above it)."""

    def __init__(self, sock: socket.socket, codec: Optional[str] = None):
        self.sock = sock
        self.codec = codec or default_codec()

    def send(self, obj) -> None:
        body = encode_frame(obj, self.codec)
        try:
            self.sock.sendall(struct.pack(">I", len(body)) + body)
        except OSError as e:
            raise TransportError(f"frame send failed: {e}") from e

    def recv(self, timeout: Optional[float] = None):
        try:
            self.sock.settimeout(timeout)
            head = self._read_exact(4)
            (n,) = struct.unpack(">I", head)
            if n > MAX_FRAME_BYTES:
                raise TransportError(f"frame of {n} bytes exceeds the "
                                     f"{MAX_FRAME_BYTES} bound")
            return decode_frame(self._read_exact(n))
        except socket.timeout as e:
            raise TransportError(
                f"frame recv timed out after {timeout}s") from e
        except OSError as e:
            raise TransportError(f"frame recv failed: {e}") from e

    def _read_exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise TransportError("connection closed (EOF)")
            buf += chunk
        return buf

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


# ---------------------------------------------------------------- wire forms

def engine_cfg_to_wire(ecfg: Optional[EngineConfig]) -> Dict:
    """EngineConfig -> plain dict. The ``draft`` ArchConfig is dropped —
    the worker rebuilds it from the model spec's ``draft`` entry (configs
    are named registry entries, not wire payloads)."""
    d = dataclasses.asdict(ecfg or EngineConfig())
    d.pop("draft", None)
    if d.get("buckets") is not None:
        d["buckets"] = [int(b) for b in d["buckets"]]
    return d


def engine_cfg_from_wire(d: Dict, draft_cfg=None) -> EngineConfig:
    d = dict(d)
    if d.get("buckets") is not None:
        d["buckets"] = tuple(int(b) for b in d["buckets"])
    return EngineConfig(**d, draft=draft_cfg)


def build_model_spec(arch: str, *, smoke: bool = True, quantize: str = "off",
                     overrides: Optional[Dict] = None, seed: int = 0,
                     draft_arch: Optional[str] = None) -> Dict:
    """The deterministic model recipe a worker rebuilds its params from:
    registry arch name, smoke scaling, ArchConfig field overrides, the
    Tensorizer quantize mode, and the init PRNG seed. Same spec + same
    backend => bit-identical weights in every process."""
    spec = {"arch": arch, "smoke": bool(smoke), "quantize": quantize,
            "overrides": dict(overrides or {}), "seed": int(seed)}
    if draft_arch:
        spec["draft"] = {"arch": draft_arch, "smoke": bool(smoke),
                         "seed": int(seed)}
    return spec


def _build_cfg(entry: Dict):
    from repro.configs import get_config
    cfg = get_config(entry["arch"])
    if entry.get("smoke", True):
        cfg = cfg.smoke()
    overrides = entry.get("overrides") or {}
    if overrides:
        cfg = cfg.replace(**overrides)
    return cfg


def realize_model_spec(spec: Dict):
    """Model spec -> (cfg, params, draft_cfg, draft_params), exactly the
    objects the CLI path builds (launch/serve.py): smoke-scaled registry
    config + overrides, ``init_model(cfg, PRNGKey(seed))``, and — with
    ``quantize='serve'`` — the same Tensorizer W8A8 pass over the same
    predicate. Must run inside a mesh context."""
    import jax
    from repro.models import init_model
    cfg = _build_cfg(spec)
    quantize = spec.get("quantize", "off")
    if quantize != "off":
        cfg = cfg.replace(quantize=quantize)
    params = init_model(cfg, jax.random.PRNGKey(int(spec.get("seed", 0))))
    if quantize == "serve":
        from repro import tensorizer as tz
        from repro.launch.serve import _quant_predicate
        params = tz.quantize_params(params, predicate=_quant_predicate)
    draft_cfg = draft_params = None
    if spec.get("draft"):
        draft_cfg = _build_cfg(spec["draft"])
        draft_params = init_model(
            draft_cfg, jax.random.PRNGKey(int(spec["draft"].get("seed", 0))))
    return cfg, params, draft_cfg, draft_params


# ----------------------------------------------------------------- protocol

class HostTransport(Protocol):
    """The complete Router->host call surface. ``poll`` is the harvest
    primitive: cursor-based (tokens already received per request), so it is
    idempotent and a duplicated/retried poll can never double-deliver a
    token. ``submit``/``evict_queued``/``preempt`` mutate and are never
    retried."""

    kind: str
    metrics: TransportMetrics

    def would_accept(self, prompt_len: int, max_new_tokens: int) -> bool: ...
    def lease_headroom(self, prompt_len: int, max_new_tokens: int) -> bool: ...
    def load(self) -> int: ...
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               sampling: Optional[SamplingParams] = None,
               stop_history: Sequence[int] = (),
               want_logprobs: Optional[int] = None) -> Optional[int]: ...
    def pump(self) -> None: ...
    def poll(self, cursors: Dict[int, int],
             drop: Sequence[int] = ()) -> Dict[int, Dict]: ...
    def has_work(self) -> bool: ...
    def evict_queued(self, ids: Sequence[int]) -> List[int]: ...
    def inflight(self) -> List[Dict]: ...
    def preempt(self, req_id: int) -> Optional[Dict]: ...
    def ship_blocks(self, req_id: int) -> Optional[Dict]: ...
    def recv_blocks(self, entry: Dict) -> Optional[int]: ...
    def ack_ship(self, payload_id: str) -> bool: ...
    def embed(self, prompt: Sequence[int]) -> Dict: ...
    def stats(self) -> Dict: ...
    def probe(self) -> bool: ...
    def close(self) -> None: ...


class EngineHost:
    """Server-side host logic shared by BOTH backends: an Engine plus the
    ownership map of caller-submitted requests. InProcessTransport calls it
    directly; host_main.py calls it behind the RPC loop — identical
    behavior on either side of the process boundary by construction."""

    def __init__(self, engine: Engine):
        self.engine = engine
        self._by_id: Dict[int, Request] = {}
        # cross-host block shipping state (prefill/decode disaggregation):
        # outbound entries keyed by payload id (and by request id, so a
        # retried ship_blocks returns the SAME cursor-named entry), plus the
        # inbound dedup map a retried recv_blocks resolves against — a
        # duplicated frame can therefore never double-import a payload
        self._shipped: Dict[str, Dict] = {}
        self._ship_pid: Dict[int, str] = {}
        self._imported: Dict[str, int] = {}

    def would_accept(self, prompt_len: int, max_new_tokens: int) -> bool:
        return bool(self.engine.would_accept(prompt_len, max_new_tokens))

    def lease_headroom(self, prompt_len: int, max_new_tokens: int) -> bool:
        return bool(self.engine.lease_headroom(prompt_len, max_new_tokens))

    def load(self) -> int:
        sched = self.engine.scheduler
        return sched.queue_depth + sched.n_active

    def submit(self, prompt, max_new_tokens, sampling=None, stop_history=(),
               want_logprobs=None) -> Optional[int]:
        req = self.engine.submit(
            np.asarray(prompt, np.int32), int(max_new_tokens),
            sampling=sampling, stop_history=tuple(stop_history),
            want_logprobs=want_logprobs)
        if req is None:
            return None
        self._by_id[req.id] = req
        return req.id

    def pump(self) -> None:
        if self.engine.has_work():
            self.engine.step()

    def poll(self, cursors: Dict[int, int],
             drop: Sequence[int] = ()) -> Dict[int, Dict]:
        """Token deltas for the caller's live requests: everything past each
        request's cursor, plus done/finish_reason once finished. A request's
        final tokens and its done flag always travel in the SAME delta (the
        engine appends and finishes synchronously), so a crash can only lose
        them together — which re-decoding regenerates exactly. ``drop`` lets
        the caller forget fully-harvested requests."""
        for rid in drop:
            self._by_id.pop(int(rid), None)
        out: Dict[int, Dict] = {}
        for rid, n in cursors.items():
            req = self._by_id.get(int(rid))
            if req is None:
                continue
            n = int(n)
            d: Dict = {"t": [int(t) for t in req.tokens[n:]],
                       # emission timestamps (monotonic epoch, shared across
                       # processes on Linux): a free-running worker's tokens
                       # arrive in bursts, so harvest times measure the
                       # caller's poll cadence — these measure the engine's
                       "ts": [float(v) for v in req.token_ts[n:]]}
            if req.want_logprobs is not None:
                d["lp"] = [float(v) for v in req.logprobs[n:]]
                d["tl"] = [[[int(t), float(v)] for t, v in row]
                           for row in req.top_logprobs[n:]]
            if req.done:
                d["done"] = True
                d["reason"] = req.finish_reason
            out[int(rid)] = d
        return out

    def has_work(self) -> bool:
        return self.engine.has_work()

    def evict_queued(self, ids: Sequence[int]) -> List[int]:
        """Pull the queue; caller-owned requests (``ids``) come back as ids
        for re-placement elsewhere, anything else (direct engine submits)
        re-enqueues untouched — the same Request object, so a direct
        caller's handle still completes here."""
        own = {int(i) for i in ids}
        evicted: List[int] = []
        for req in self.engine.evict_queued():
            if req.id in own:
                self._by_id.pop(req.id, None)
                evicted.append(req.id)
            else:
                req.state = RequestState.QUEUED
                self.engine.scheduler.enqueue(req)
        return evicted

    def inflight(self) -> List[Dict]:
        return [{"id": req.id, "generated": len(req.tokens)}
                for _, req in sorted(self.engine.scheduler.active.items())
                if req.id in self._by_id]

    def preempt(self, req_id: int) -> Optional[Dict]:
        """Preempt one in-flight request and return its authoritative wire
        form (full segment tokens — a free-running worker may be ahead of
        the caller's last poll). None when the request already finished
        between the caller's snapshot and now (the next poll reports it)."""
        try:
            req = self.engine.preempt(int(req_id))
        except KeyError:
            return None
        self._by_id.pop(int(req_id), None)
        return req.to_wire()

    def ship_blocks(self, req_id: int) -> Optional[Dict]:
        """Export one in-flight request's stream state AND its exact cache
        blocks as a ship entry (``{"payload_id", "wire", "payload"}``) for a
        decode host to adopt. Idempotent by construction: the entry is cached
        under the request id, so a retried ship returns the same cursor-named
        payload — combined with ``recv_blocks``'s dedup, a duplicated frame
        can never double-import. The blocks stay on the engine's export
        ledger (unreusable, unfreed) until ``ack_ship``. None when the
        request already finished here (the next poll reports it)."""
        pid = self._ship_pid.get(int(req_id))
        if pid is not None:
            return self._shipped[pid]
        try:
            req, payload = self.engine.extract_seeded(int(req_id))
        except KeyError:
            return None
        self._by_id.pop(int(req_id), None)
        entry = {"payload_id": payload["payload_id"],
                 "wire": req.to_wire(), "payload": payload}
        self._shipped[entry["payload_id"]] = entry
        self._ship_pid[int(req_id)] = entry["payload_id"]
        return entry

    def recv_blocks(self, entry: Dict) -> Optional[int]:
        """Adopt a shipped entry: lease a slot, import the payload's cache
        bits (validated before any device write), and continue the stream
        with zero prefill dispatches. Dedup on the cursor-named payload id —
        a retried recv of an already-imported payload returns the SAME local
        request id instead of importing twice. Returns None when refused
        (no free slot / lease backpressure: the shipper falls back to
        re-prefill continuation); raises ValueError on a corrupt payload."""
        pid = str(entry["payload_id"])
        if pid in self._imported:
            return self._imported[pid]
        wire = entry["wire"]
        req = self.engine.submit_seeded(
            wire["prompt"], int(wire["max_new_tokens"]), wire["tokens"],
            entry["payload"],
            sampling=sampling_from_wire(wire.get("sampling")),
            stop_history=tuple(int(t) for t in wire.get("stop_history") or ()),
            want_logprobs=wire.get("want_logprobs"),
            logprobs=wire.get("logprobs") or (),
            top_logprobs=wire.get("top_logprobs") or ())
        if req is None:
            return None
        self._imported[pid] = req.id
        self._by_id[req.id] = req
        return req.id

    def ack_ship(self, payload_id: str) -> bool:
        """Settle one outbound ship: release the export ledger's block hold
        and drop the cached entry. Called on BOTH outcomes — successful
        import (the blocks live on the decode host now) and fallback (the
        re-prefill continuation owns the stream). Idempotent."""
        pid = str(payload_id)
        entry = self._shipped.pop(pid, None)
        if entry is not None:
            self._ship_pid.pop(int(entry["wire"]["id"]), None)
        return bool(self.engine.release_exported(pid))

    def embed(self, prompt) -> Dict:
        return self.engine.embed(np.asarray(prompt, np.int32))

    def stats(self) -> Dict:
        out = dict(self.engine.stats())
        # the fleet sustained-rate span needs the raw first/last token
        # timestamps, which EngineMetrics.summary() does not carry — ship
        # them in the wire stats (time.monotonic shares an epoch across
        # processes on Linux, so cross-process spans are comparable)
        out["first_token_s"] = self.engine.metrics.first_token_s
        out["last_token_s"] = self.engine.metrics.last_token_s
        return out

    def probe(self) -> bool:
        return True

    def close(self) -> None:
        self.engine.close()


# ------------------------------------------------------------- in-process

class InProcessTransport:
    """Today's fleet, behind the protocol: host calls are Python calls,
    timed through the same TransportMetrics so the subprocess backend's RPC
    overhead is measured against a real baseline. ``pump`` drives one
    engine step — with no worker process, the caller is the step loop."""

    kind = "in-process"

    def __init__(self, host: EngineHost):
        self.host = host
        self.metrics = TransportMetrics()

    @property
    def engine(self) -> Engine:
        return self.host.engine

    def _timed(self, fn, *args, **kwargs):
        t0 = now()
        try:
            return fn(*args, **kwargs)
        finally:
            self.metrics.observe(now() - t0)

    def would_accept(self, prompt_len, max_new_tokens):
        return self._timed(self.host.would_accept, prompt_len, max_new_tokens)

    def lease_headroom(self, prompt_len, max_new_tokens):
        return self._timed(self.host.lease_headroom, prompt_len,
                           max_new_tokens)

    def load(self):
        return self._timed(self.host.load)

    def submit(self, prompt, max_new_tokens, sampling=None, stop_history=(),
               want_logprobs=None):
        return self._timed(self.host.submit, prompt, max_new_tokens,
                           sampling=sampling, stop_history=stop_history,
                           want_logprobs=want_logprobs)

    def pump(self):
        self.host.pump()

    def poll(self, cursors, drop=()):
        return self._timed(self.host.poll, cursors, drop)

    def has_work(self):
        return self.host.has_work()

    def evict_queued(self, ids):
        return self._timed(self.host.evict_queued, ids)

    def inflight(self):
        return self._timed(self.host.inflight)

    def preempt(self, req_id):
        return self._timed(self.host.preempt, req_id)

    def ship_blocks(self, req_id):
        return self._timed(self.host.ship_blocks, req_id)

    def recv_blocks(self, entry):
        return self._timed(self.host.recv_blocks, entry)

    def ack_ship(self, payload_id):
        return self._timed(self.host.ack_ship, payload_id)

    def embed(self, prompt):
        return self._timed(self.host.embed, prompt)

    def stats(self):
        return self._timed(self.host.stats)

    def probe(self):
        return True

    def close(self):
        self.host.close()


def build_inproc_fleet(cfg, params, engine_cfg: Optional[EngineConfig] = None,
                       n_hosts: int = 1, *,
                       draft_params=None) -> List[InProcessTransport]:
    """N in-process hosts, host ``i`` on device ``i mod n_devices`` — one
    chip per host on a multi-chip machine, all on the one device otherwise.
    Hosts on the same device share compiled steps via the engine's
    _jitted_steps cache. The default Router fleet."""
    devices = jax.devices()
    return [
        InProcessTransport(EngineHost(
            Engine(cfg, params, engine_cfg, draft_params=draft_params,
                   mesh=device_mesh(devices[i % len(devices)]))))
        for i in range(n_hosts)]


# ------------------------------------------------------------- subprocess

WORKER_ON_TPU = (
    "subprocess host workers cannot run on a TPU machine: a chip belongs to "
    "one process, and this one already holds it. Serve several chips from "
    "one process instead — the in-process Router puts one host on each "
    "chip (--hosts N without --host-procs).")

# ops safe to retry after a timeout: read-only predicates and cursor-based
# reads. submit/evict/preempt mutate — a lost reply leaves the mutation's
# fate unknown, so they surface TransportError instead of retrying (the
# Router treats that as a lost host and re-places from harvested state).
# The block-shipping trio mutates but is retry-safe by protocol design:
# ship_blocks caches its cursor-named entry per request, recv_blocks dedups
# on the payload id, and ack_ship releases idempotently — a retried frame
# replays to the same state it left.
_IDEMPOTENT_OPS = frozenset({
    "would_accept", "lease_headroom", "load", "has_work", "poll",
    "inflight", "stats", "probe", "embed",
    "ship_blocks", "recv_blocks", "ack_ship",
})


class SubprocessTransport:
    """One OS process per host: spawns ``python -m repro.serving.host_main``
    connected over an AF_UNIX socket, ships the model spec + engine config
    in an init frame, then speaks the framed RPC protocol. The worker
    free-runs its engine between requests; ``pump`` is therefore a no-op.

    ``connect_timeout_s`` bounds worker boot (imports + init_model);
    ``request_timeout_s`` bounds each RPC — generous by default because a
    worker mid-XLA-compile blocks its loop for seconds on first traffic.
    """

    kind = "subprocess"

    def __init__(self, model_spec: Dict,
                 engine_cfg: Optional[EngineConfig] = None, *,
                 connect_timeout_s: float = 300.0,
                 request_timeout_s: float = 300.0,
                 retries: int = 2):
        if jax.default_backend() == "tpu":
            raise RuntimeError(WORKER_ON_TPU)
        self.model_spec = dict(model_spec)
        self.ecfg = engine_cfg or EngineConfig()
        self.request_timeout_s = request_timeout_s
        self.retries = retries
        self.metrics = TransportMetrics()
        self._seq = itertools.count(1)
        self._closed = False
        self._tmpdir = tempfile.mkdtemp(prefix="rhost")
        path = os.path.join(self._tmpdir, "s")
        listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        listener.bind(path)
        listener.listen(1)
        listener.settimeout(connect_timeout_s)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.serving.host_main",
             "--socket", path],
            env=self._worker_env())
        try:
            conn, _ = listener.accept()
        except socket.timeout:
            self._reap()
            raise TransportError(
                f"worker (pid {self.proc.pid}) did not connect within "
                f"{connect_timeout_s}s")
        finally:
            listener.close()
        self.chan = Channel(conn)
        # init is a regular seq'd request so the reply path is uniform, but
        # with the boot timeout: the worker only answers after building the
        # model (imports + init_model + optional quantize)
        ready = self._call("init",
                           {"model_spec": self.model_spec,
                            "engine_cfg": engine_cfg_to_wire(engine_cfg)},
                           timeout=connect_timeout_s)
        self.pid = int(ready["pid"])

    @staticmethod
    def _worker_env() -> Dict[str, str]:
        import repro
        env = dict(os.environ)
        src_dir = str(Path(repro.__file__).resolve().parents[1])
        existing = env.get("PYTHONPATH")
        # the worker imports this same checkout, so its compile cache
        # (core/compile_cache.py) resolves to the parent's directory
        env["PYTHONPATH"] = (src_dir + os.pathsep + existing
                             if existing else src_dir)
        return env

    # --------------------------------------------------------------- rpc

    def _call(self, op: str, args: Optional[Dict] = None,
              timeout: Optional[float] = None):
        if self._closed:
            raise TransportError(f"transport to pid {self.pid} is closed")
        timeout = self.request_timeout_s if timeout is None else timeout
        attempts = 1 + (self.retries if op in _IDEMPOTENT_OPS else 0)
        last: Optional[TransportError] = None
        for attempt in range(attempts):
            seq = next(self._seq)
            t0 = now()
            try:
                self.chan.send({"seq": seq, "op": op, "args": args or {}})
                deadline = t0 + timeout
                while True:
                    reply = self.chan.recv(timeout=max(deadline - now(),
                                                       0.001))
                    # a retried call's earlier reply (or a duplicated
                    # frame) carries a stale seq: discard, keep reading
                    if reply.get("seq") == seq:
                        break
            except TransportError as e:
                self.metrics.errors += 1
                last = e
                if attempt + 1 < attempts:
                    self.metrics.retries += 1
                    continue
                raise TransportError(
                    f"rpc {op!r} to worker pid {self.pid} failed after "
                    f"{attempts} attempt(s): {e}") from e
            self.metrics.observe(now() - t0)
            if reply.get("ok"):
                return reply.get("val")
            # application error from a healthy host: re-raise in kind
            etype, msg = reply.get("etype"), reply.get("err", "")
            if etype == "ValueError":
                raise ValueError(msg)
            if etype == "KeyError":
                raise KeyError(msg)
            raise RuntimeError(f"remote {etype or 'error'}: {msg}")
        raise last  # pragma: no cover - loop always raises/returns

    # ---------------------------------------------------------- protocol

    def would_accept(self, prompt_len, max_new_tokens):
        return bool(self._call("would_accept", {"plen": int(prompt_len),
                                                "gen": int(max_new_tokens)}))

    def lease_headroom(self, prompt_len, max_new_tokens):
        return bool(self._call("lease_headroom",
                               {"plen": int(prompt_len),
                                "gen": int(max_new_tokens)}))

    def load(self):
        return int(self._call("load"))

    def submit(self, prompt, max_new_tokens, sampling=None, stop_history=(),
               want_logprobs=None):
        val = self._call("submit", {
            "prompt": [int(t) for t in prompt],
            "gen": int(max_new_tokens),
            "sampling": sampling_to_wire(sampling),
            "stop_history": [int(t) for t in stop_history],
            "want_logprobs": want_logprobs,
        })
        return None if val is None else int(val)

    def pump(self):
        pass                           # the worker's loop steps the engine

    def poll(self, cursors, drop=()):
        val = self._call("poll", {
            "cursors": {int(k): int(v) for k, v in cursors.items()},
            "drop": [int(i) for i in drop],
        }) or {}
        # JSON frames stringify int dict keys; normalize either way
        return {int(k): v for k, v in val.items()}

    def has_work(self):
        return bool(self._call("has_work"))

    def evict_queued(self, ids):
        return [int(i) for i in
                (self._call("evict_queued",
                            {"ids": [int(i) for i in ids]}) or [])]

    def inflight(self):
        return list(self._call("inflight") or [])

    def preempt(self, req_id):
        return self._call("preempt", {"id": int(req_id)})

    def ship_blocks(self, req_id):
        return self._call("ship_blocks", {"id": int(req_id)})

    def recv_blocks(self, entry):
        val = self._call("recv_blocks", {"entry": entry})
        return None if val is None else int(val)

    def ack_ship(self, payload_id):
        return bool(self._call("ack_ship", {"payload_id": str(payload_id)}))

    def embed(self, prompt):
        val = self._call("embed", {"prompt": [int(t) for t in prompt]})
        return {"embedding": np.asarray(val["embedding"]),
                "logits": np.asarray(val["logits"])}

    def stats(self):
        return self._call("stats")

    def probe(self) -> bool:
        """Liveness: False for a dead/unreachable worker, never raises."""
        if self._closed or self.proc.poll() is not None:
            return False
        try:
            return bool(self._call("probe", timeout=5.0))
        except TransportError:
            return False

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            if self.proc.poll() is None:
                self.chan.send({"seq": next(self._seq), "op": "shutdown",
                                "args": {}})
                self.chan.recv(timeout=5.0)   # let the worker ack + exit
        except TransportError:
            pass
        self.chan.close()
        self._reap()
        try:
            os.unlink(os.path.join(self._tmpdir, "s"))
            os.rmdir(self._tmpdir)
        except OSError:
            pass

    def _reap(self, grace_s: float = 5.0) -> None:
        """No orphans: wait briefly, then terminate, then kill."""
        try:
            self.proc.wait(timeout=grace_s)
            return
        except subprocess.TimeoutExpired:
            pass
        self.proc.terminate()
        try:
            self.proc.wait(timeout=2.0)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
