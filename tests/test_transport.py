"""HostTransport invariants (serving/transport.py, serving/host_main.py):

  * protocol isolation — the Router speaks only HostTransport: no direct
    engine attribute access anywhere in router.py (grep-asserted), and the
    default in-process fleet behaves exactly as before (test_router.py runs
    unchanged)
  * codec        — msgpack and JSON frames round-trip the full wire surface
    (ndarrays, nested dicts, int keys normalized across JSON stringification)
  * bit-identity — a seeded, staggered, mid-run-drained fleet over
    SubprocessTransport (real OS processes, free-running workers) emits
    streams bit-identical to a single in-process engine serving the same
    requests one at a time — dense and int8-KV cache formats
  * crash safety — SIGKILL of one worker mid-decode: the router marks the
    host LOST, re-admits its streams as continuations from the harvested
    tokens, and the final streams are STILL bit-identical (determinism
    regenerates exactly the tokens that died un-polled; nothing
    double-emits)
  * fault injection — dropped/duplicated/timed-out frames through a flaky
    channel: idempotent calls retry with fresh seqs and discard stale
    replies; non-idempotent calls surface TransportError instead of
    retrying
  * TOCTOU       — a host whose door closes between would_accept and submit
    is skipped and the next candidate takes the request (no spurious
    fleet-level rejection)
"""

import os
import pathlib
import signal
import time

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models import init_model
from repro.serving import (
    Engine, EngineConfig, Router, RouterConfig, SamplingParams,
)
from repro.serving import transport as tp
from repro.serving.transport import (
    Channel, EngineHost, InProcessTransport, SubprocessTransport,
    TransportError, build_inproc_fleet, build_model_spec, decode_frame,
    encode_frame, engine_cfg_from_wire, engine_cfg_to_wire,
)

CFG = get_config("tinyllama-1.1b").smoke()
RNG = np.random.default_rng(7)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, jax.random.PRNGKey(0))


def _prompts(lens, cfg=CFG, rng=None):
    rng = RNG if rng is None else rng
    return [rng.integers(0, cfg.vocab, (l,), dtype=np.int32) for l in lens]


def _sampling(i):
    """Mixed traffic: even requests sample (per-request seed), odd greedy."""
    if i % 2 == 0:
        return SamplingParams(temperature=0.8, top_k=40, seed=100 + i)
    return None


def _sequential(params, prompts, gens, samplings, cfg=CFG, **ecfg_kw):
    """Reference: one in-process engine, one request at a time."""
    kw = dict(max_slots=2, max_seq_len=48)
    kw.update(ecfg_kw)
    eng = Engine(cfg, params, EngineConfig(**kw))
    outs = []
    for p, g, sp in zip(prompts, gens, samplings):
        req = eng.submit(p, g, sampling=sp)
        eng.run_until_complete()
        outs.append(list(req.tokens))
    eng.close()
    return outs


# --------------------------------------------------------------------- codec

@pytest.mark.parametrize("codec", ["json"] + (["msgpack"] if tp.msgpack else []))
def test_codec_round_trip(codec):
    obj = {
        "ints": [1, 2, 3], "f": 1.5, "none": None, "flag": True,
        "nested": {"deep": {"arr": np.arange(6, dtype=np.int32).reshape(2, 3)}},
        "f32": np.float32(2.5), "i64": np.int64(9),
        "emb": np.linspace(0, 1, 5, dtype=np.float32),
    }
    out = decode_frame(encode_frame(obj, codec))
    assert out["ints"] == [1, 2, 3] and out["f"] == 1.5
    assert out["none"] is None and out["flag"] is True
    nd = out["nested"]["deep"]["arr"]
    assert isinstance(nd, np.ndarray) and nd.dtype == np.int32
    np.testing.assert_array_equal(nd, np.arange(6).reshape(2, 3))
    np.testing.assert_allclose(out["emb"], np.linspace(0, 1, 5), rtol=0)
    # the codec byte dispatches per frame, so mixed peers interoperate
    assert encode_frame({}, "json")[:1] == b"J"


def test_engine_cfg_wire_round_trip():
    ecfg = EngineConfig(max_slots=3, max_seq_len=32, buckets=(16, 32),
                        cache_backend="paged", block_size=8, n_blocks=9,
                        prefix_cache=True)
    back = engine_cfg_from_wire(engine_cfg_to_wire(ecfg))
    assert back == ecfg
    # the draft ArchConfig never crosses the wire: workers rebuild it from
    # the model spec's registry name
    spec_ecfg = EngineConfig(speculative=True, spec_k=3, draft=CFG)
    wire = engine_cfg_to_wire(spec_ecfg)
    assert "draft" not in wire
    rebuilt = engine_cfg_from_wire(wire, draft_cfg=CFG)
    assert rebuilt.draft == CFG and rebuilt.spec_k == 3


def test_request_wire_form(params):
    eng = Engine(CFG, params, EngineConfig(max_slots=1, max_seq_len=32))
    req = eng.submit(_prompts([5])[0], 4,
                     sampling=SamplingParams(temperature=0.5, seed=3,
                                             stop=((7, 8),)),
                     want_logprobs=2)
    eng.run_until_complete()
    wire = decode_frame(encode_frame(req.to_wire()))     # through the codec
    assert wire["tokens"] == list(req.tokens)
    assert wire["sampling"]["seed"] == 3
    assert wire["sampling"]["stop"] == [[7, 8]]
    assert len(wire["logprobs"]) == len(req.tokens)
    assert all(len(row) >= 2 for row in wire["top_logprobs"])
    eng.close()


# -------------------------------------------------------- protocol isolation

def test_router_speaks_only_the_transport_protocol():
    """The refactor's structural guarantee: router.py contains no direct
    engine attribute access — every host interaction goes through
    HostTransport, so swapping in-process for subprocess hosts cannot change
    router behavior."""
    src = (pathlib.Path(__file__).parent.parent
           / "src/repro/serving/router.py").read_text()
    for forbidden in ("repro.serving.engine", "Engine(", ".scheduler",
                      ".opq", ".store", ".completed[", "run_engine"):
        assert forbidden not in src, (
            f"router.py reaches around the transport protocol: {forbidden!r}")


def test_default_fleet_is_in_process(params):
    router = Router(CFG, params, EngineConfig(max_slots=2, max_seq_len=32),
                    RouterConfig(n_hosts=2))
    assert [t.kind for t in router.transports] == ["in-process"] * 2
    assert len(router.engines) == 2                  # debug surface intact
    r = router.submit(_prompts([5])[0], 4)
    router.run_until_complete()
    assert len(r.tokens) == 4 and r.done
    s = router.stats()["router"]
    assert [t["kind"] for t in s["transport"]] == ["in-process"] * 2
    assert all(t["rpcs"] > 0 for t in s["transport"])
    router.close()


def test_engine_host_poll_is_cursor_idempotent(params):
    """poll never re-emits: identical cursors return identical deltas, and
    advancing the cursor excludes exactly the harvested prefix. done rides
    the same delta as the final tokens."""
    host = EngineHost(Engine(CFG, params,
                             EngineConfig(max_slots=1, max_seq_len=32)))
    eid = host.submit(_prompts([5])[0], 4)
    while host.has_work():
        host.pump()
    d1 = host.poll({eid: 0})
    d2 = host.poll({eid: 0})                        # duplicated poll
    assert d1 == d2 and len(d1[eid]["t"]) == 4      # same answer, no re-emit
    assert d1[eid]["done"] and d1[eid]["reason"] == "length"
    tail = host.poll({eid: 3})
    assert tail[eid]["t"] == d1[eid]["t"][3:]       # cursor slices the tail
    host.poll({}, drop=[eid])
    assert host.poll({eid: 0}) == {}                # forgotten after drop
    host.close()


# --------------------------------------------------------------- TOCTOU door

class _FlakyDoor:
    """Transport wrapper whose door lies once: would_accept says yes but the
    next submit returns None (the race where capacity vanishes between the
    probe and the submit)."""

    def __init__(self, inner):
        self.inner = inner
        self.deny_submits = 0
        self.denied = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def submit(self, *args, **kwargs):
        if self.deny_submits > 0:
            self.deny_submits -= 1
            self.denied += 1
            return None
        return self.inner.submit(*args, **kwargs)


def test_submit_revalidates_and_falls_through(params):
    fleet = build_inproc_fleet(CFG, params,
                               EngineConfig(max_slots=2, max_seq_len=32),
                               n_hosts=2)
    flaky = _FlakyDoor(fleet[0])
    router = Router(transports=[flaky, fleet[1]])
    flaky.deny_submits = 1
    r = router.submit(_prompts([5])[0], 4, session="x")
    assert r is not None and flaky.denied == 1
    assert r.hosts == [1]                            # fell through to host 1
    s = router.stats()["router"]
    assert s["placed"] == 1 and s["rejected"] == 0
    # when EVERY candidate's door closes, the fleet-level contract holds
    flaky.deny_submits = 10
    router2 = Router(transports=[flaky])
    assert router2.submit(_prompts([5])[0], 4) is None
    assert router2.stats()["router"]["rejected"] == 1
    router.close()


# ----------------------------------------------------- loss recovery (fast)

class _Breakable:
    """Transport wrapper that starts raising TransportError on command —
    the in-process stand-in for a dead worker, driving the router's LOST
    path without subprocess cost."""

    def __init__(self, inner):
        self.inner = inner
        self.broken = False

    def __getattr__(self, name):
        attr = getattr(self.inner, name)
        if not callable(attr) or name == "close":
            return attr
        def wrapped(*args, **kwargs):
            if self.broken:
                raise TransportError("injected host failure")
            return attr(*args, **kwargs)
        return wrapped


def test_lost_host_streams_recover_bit_identically(params):
    prompts = _prompts([6, 5, 7, 4])
    gens = [10, 9, 8, 10]
    samplings = [_sampling(i) for i in range(4)]
    sequential = _sequential(params, prompts, gens, samplings)

    fleet = build_inproc_fleet(CFG, params,
                               EngineConfig(max_slots=2, max_seq_len=48),
                               n_hosts=2)
    breakable = _Breakable(fleet[0])
    router = Router(transports=[breakable, fleet[1]],
                    router_cfg=RouterConfig(handoff_threshold=0))
    reqs = []
    for i in range(4):
        reqs.append(router.submit(prompts[i], gens[i], session=str(i % 2),
                                  sampling=samplings[i], strict=True))
        router.step()
    assert {r.hosts[0] for r in reqs} == {0, 1}     # both hosts held work
    host0_reqs = [r for r in reqs if r.hosts[0] == 0]
    assert any(len(r.tokens) > 0 for r in host0_reqs)   # mid-decode...
    breakable.broken = True                             # ...and now it dies
    router.run_until_complete()

    assert [list(r.tokens) for r in reqs] == sequential   # bit-identical
    s = router.stats()["router"]
    assert s["lost"] == [0] and s["hosts_lost"] == 1
    assert s["recovered"] >= len(host0_reqs)
    assert all(r.hosts[-1] == 1 for r in host0_reqs)      # re-admitted on 1
    router.close()


# ---------------------------------------------------- subprocess: real fleet
#
# These tests use a scaled-up smoke model (~4 ms/decode-step): a 96-token
# generation is a ~0.4 s window on a free-running worker, so a drain or a
# SIGKILL issued right after submit reliably lands mid-decode. Sequence
# positions stay <= 128 — the envelope where the engine's
# prefill-with-cache == decode-replay bit invariant is proven (longer
# continuations can round differently under XLA; see ROADMAP).
#
# Streams that get preempted mid-decode (drained or killed, i.e. re-prefilled
# as continuations at a timing-dependent point) are GREEDY here: sampled
# continuations re-roll a Gumbel-perturbed argmax on each step, which can
# flip on the tiny prefill-vs-decode logit epsilon at these shapes even
# inside the envelope — the same pre-existing engine hole tracked in
# ROADMAP, amplified. Sampled streams still run in every fleet, pinned to
# the surviving host, and must match the sequential reference exactly.
# Each test draws prompts from its own fixed rng (seeds 6/4/0 below) so
# the token streams are identical regardless of which other tests ran
# first. Those seeds are NOT folklore: tests/_seed_verify.py sweeps the
# greedy continuation space of each pinned (config, seed) pair — cutting
# the stream at continuation points and re-admitting prompt + prefix, the
# exact re-prefill these tests exercise at timing-dependent W — and
# tests/test_disagg.py::test_pinned_transport_seeds_verified keeps that
# sweep in the suite. Re-pin a seed only if it passes the harness:
#   PYTHONPATH=src python tests/_seed_verify.py --big --seed <n> --gen 96

BIG = dict(n_layers=4, d_model=256, n_heads=8, n_kv=4, d_ff=1024,
           vocab=512, head_dim=32)
BIG_CFG = CFG.replace(**BIG)


def _spawn_fleet(n, ecfg, overrides=None):
    spec = build_model_spec("tinyllama-1.1b", smoke=True, seed=0,
                            overrides=dict(BIG, **(overrides or {})))
    fleet = []
    try:
        for _ in range(n):
            fleet.append(SubprocessTransport(spec, ecfg))
    except Exception:
        for t in fleet:
            t.close()
        raise
    return fleet


def _warm(fleet):
    """Run one tiny greedy request on each worker so every process compiles
    its prefill + decode executables up front. Without this, an RPC to a
    still-compiling host can stall the parent long enough for a
    free-running victim to finish its whole generation before a drain or a
    kill lands — batch invariance means the warmup requests change no other
    stream."""
    rng = np.random.default_rng(99)
    for t in fleet:
        eid = t.submit(_prompts([4], rng=rng)[0], 2)
        deadline = time.monotonic() + 300
        while not t.poll({eid: 0}).get(eid, {}).get("done"):
            assert time.monotonic() < deadline, "warmup never finished"
            time.sleep(0.01)
        t.poll({}, drop=[eid])


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_subprocess_fleet_bit_identical_to_sequential(kv_dtype):
    """THE transport invariant: a seeded, staggered, mid-run-drained fleet
    of real OS processes — workers free-running their engines, tokens
    arriving through framed RPC polls — emits streams bit-identical to a
    single in-process engine serving the same requests sequentially."""
    cfg = BIG_CFG.replace(kv_cache_dtype=kv_dtype)
    params = init_model(cfg, jax.random.PRNGKey(0))   # same seed as workers
    prompts = _prompts([6, 9, 4, 7], cfg=cfg, rng=np.random.default_rng(6))
    # long second generation: the drain must land while it is mid-decode on
    # a free-running worker, so the handoff really crosses the wire
    gens = [48, 96, 8, 6]
    samplings = [_sampling(i) for i in range(4)]
    sequential = _sequential(params, prompts, gens, samplings, cfg=cfg,
                             max_seq_len=128)

    ecfg = EngineConfig(max_slots=2, max_seq_len=128)
    fleet = _spawn_fleet(2, ecfg, overrides={"kv_cache_dtype": kv_dtype})
    _warm(fleet)
    router = Router(transports=fleet,
                    router_cfg=RouterConfig(handoff_threshold=0))
    reqs = []
    for i in range(4):
        reqs.append(router.submit(prompts[i], gens[i], session=str(i % 2),
                                  sampling=samplings[i],
                                  want_logprobs=2 if i == 0 else None,
                                  strict=True))
        router.step()
    # drain the host holding session "1" (the greedy streams) once req 1 is
    # verifiably mid-decode there — the handoff crosses the wire for real
    victim = reqs[1].hosts[0]
    assert reqs[0].hosts[0] != victim       # sampled streams live elsewhere
    deadline = time.monotonic() + 120
    while not 0 < len(reqs[1].tokens) < reqs[1].max_new_tokens:
        router.step()
        assert time.monotonic() < deadline, "req 1 never got mid-decode"
    router.drain(victim)                    # mid-run drain: handoff on wire
    router.run_until_complete()

    assert [list(r.tokens) for r in reqs] == sequential
    assert len(reqs[1].hosts) > 1                     # the handoff happened
    # logprobs survive the transport (and any handoff) aligned with tokens
    assert len(reqs[0].logprobs) == len(reqs[0].tokens)
    # rows carry the engine's fixed top-K; the API layer truncates to `want`
    assert all(len(row) >= 2 for row in reqs[0].top_logprobs)
    s = router.stats()
    assert s["router"]["drains"] == 1 and s["router"]["handoffs"] >= 1
    assert s["router"]["hosts_lost"] == 0
    assert [t["kind"] for t in s["router"]["transport"]] == ["subprocess"] * 2
    assert s["fleet"]["tokens_generated"] >= sum(gens)    # fleet really ran
    router.close()
    assert all(t.proc.poll() is not None for t in fleet)  # no orphans


def test_sigkill_mid_decode_recovers_bit_identically():
    """Hard host death: SIGKILL one worker while it decodes. The router
    detects the loss on the next RPC, re-places the dead host's streams as
    continuations from the harvested tokens, and the final streams match
    the sequential reference exactly — the un-harvested tokens died with
    the process and were regenerated, never double-emitted."""
    params = init_model(BIG_CFG, jax.random.PRNGKey(0))
    prompts = _prompts([6, 5, 7, 4], rng=np.random.default_rng(4))
    # long generations so the SIGKILL lands while the victim is mid-decode
    gens = [96, 80, 96, 80]
    samplings = [_sampling(i) for i in range(4)]
    sequential = _sequential(params, prompts, gens, samplings, cfg=BIG_CFG,
                             max_seq_len=128)

    fleet = _spawn_fleet(2, EngineConfig(max_slots=2, max_seq_len=128))
    _warm(fleet)
    router = Router(transports=fleet,
                    router_cfg=RouterConfig(handoff_threshold=0))
    reqs = []
    for i in range(4):
        reqs.append(router.submit(prompts[i], gens[i], session=str(i % 2),
                                  sampling=samplings[i], strict=True))
    # kill the host holding session "1" — the greedy streams (see the
    # section comment: preempted streams stay greedy)
    victim = reqs[1].hosts[0]
    victim_reqs = [r for r in reqs if r.hosts[0] == victim]
    survivor = next(h for h in (0, 1) if h != victim)
    assert victim_reqs and len(victim_reqs) < 4       # both hosts hold work
    victim_pid = fleet[victim].pid
    deadline = time.monotonic() + 120
    while not any(0 < len(r.tokens) < r.max_new_tokens for r in victim_reqs):
        router.step()                # harvest until the victim is mid-decode
        assert time.monotonic() < deadline, "victim never got mid-decode"
    os.kill(victim_pid, signal.SIGKILL)
    router.run_until_complete()

    assert [list(r.tokens) for r in reqs] == sequential   # bit-identical
    s = router.stats()["router"]
    assert s["lost"] == [victim] and s["hosts_lost"] == 1
    assert s["recovered"] >= 1
    assert all(r.hosts[-1] == survivor for r in victim_reqs)
    router.close()
    assert all(t.proc.poll() is not None for t in fleet)  # victim reaped too


def test_flaky_frames_retry_and_error_semantics():
    """Frame-level fault injection on a live worker channel: a dropped
    reply retries an idempotent call (fresh seq, counted); a duplicated /
    stale-seq frame is discarded, not misdelivered; a dropped reply on a
    NON-idempotent call raises TransportError instead of retrying."""
    fleet = _spawn_fleet(1, EngineConfig(max_slots=2, max_seq_len=32))
    t = fleet[0]
    chan = t.chan
    real_recv = chan.recv

    # 1) dropped reply -> idempotent retry succeeds
    state = {"drops": 1}
    def dropping_recv(timeout=None):
        if state["drops"] > 0:
            state["drops"] -= 1
            raise TransportError("injected drop")
        return real_recv(timeout)
    chan.recv = dropping_recv
    assert t.load() == 0                       # retried transparently
    assert t.metrics.retries == 1 and t.metrics.errors == 1
    chan.recv = real_recv

    # 2) duplicated/stale frame -> seq filter discards it
    state2 = {"extra": 1}
    def duplicating_recv(timeout=None):
        if state2["extra"] > 0:
            state2["extra"] -= 1
            return {"seq": -12345, "ok": True, "val": 987654}   # stale junk
        return real_recv(timeout)
    chan.recv = duplicating_recv
    assert t.would_accept(4, 4) is True        # not 987654
    chan.recv = real_recv

    # 3) dropped reply on submit (non-idempotent) -> TransportError, and the
    # transport records the error without inventing a retry
    errors_before = t.metrics.errors
    def always_drop(timeout=None):
        raise TransportError("injected drop")
    chan.recv = always_drop
    with pytest.raises(TransportError):
        t.submit(_prompts([4])[0], 4)
    assert t.metrics.errors == errors_before + 1
    chan.recv = real_recv
    # the worker itself is fine — the dropped reply was consumed by the seq
    # filter of the next call, and service continues
    assert t.probe() is True
    t.close()


def test_lost_host_never_double_emits_over_flaky_transport():
    """Router + flaky subprocess: break the channel under a live stream;
    the host goes LOST and the stream re-admits elsewhere. The recovered
    stream must equal the sequential reference exactly — in particular no
    token appears twice even though the dead host had generated (and we had
    harvested) a prefix of it."""
    params = init_model(BIG_CFG, jax.random.PRNGKey(0))
    prompts = _prompts([6, 5], rng=np.random.default_rng(0))
    gens = [96, 24]
    # both greedy: req 0 is the preempted stream, and req 1 could land on
    # the same host as req 0 under load ties, so it must survive a re-prefill
    samplings = [None, None]
    sequential = _sequential(params, prompts, gens, samplings, cfg=BIG_CFG,
                             max_seq_len=128)

    fleet = _spawn_fleet(2, EngineConfig(max_slots=2, max_seq_len=128))
    _warm(fleet)
    router = Router(transports=fleet,
                    router_cfg=RouterConfig(handoff_threshold=0))
    reqs = [router.submit(prompts[i], gens[i], session=str(i),
                          sampling=samplings[i], strict=True)
            for i in range(2)]
    victim = reqs[0].hosts[0]
    deadline = time.monotonic() + 120
    while not 0 < len(reqs[0].tokens) < reqs[0].max_new_tokens:
        router.step()                          # harvest a real prefix first
        assert time.monotonic() < deadline
    harvested_prefix = list(reqs[0].tokens)
    fleet[victim].chan.sock.close()            # frames now fail, proc lives
    router.run_until_complete()

    assert [list(r.tokens) for r in reqs] == sequential
    assert reqs[0].tokens[:len(harvested_prefix)] == harvested_prefix
    s = router.stats()["router"]
    assert s["hosts_lost"] == 1 and s["recovered"] >= 1
    router.close()
    assert all(t.proc.poll() is not None for t in fleet)
