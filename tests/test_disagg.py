"""Prefill/decode disaggregation invariants (serving/store.py export/import,
serving/transport.py ship RPCs, serving/router.py role split) plus the
preemption-seed verification harness (tests/_seed_verify.py):

  * seed harness — every continuation point W of the pinned reference
    streams is clean (re-admitting ``prompt + tokens[:W]`` regenerates the
    exact remaining stream), so the fallback tests below cannot pass by
    luck of the cut point; a tamper self-test proves the sweep has teeth
  * bit-identity — a ``prefill:1,decode:1`` fleet serving a staggered mix
    emits streams bit-identical to a single engine serving the requests
    one at a time, for dense AND int8 serving (``quantize="serve"``, whose
    per-row activation calibration in models/layers.pdot is what makes the
    shipped continuation admission-pattern invariant)
  * role purity — zero prefill instructions dispatch on the decode host
    (OPQ flag audit): ships land as imports, never as re-prefills
  * fault injection — a dropped ship_blocks reply retries and reuses the
    SAME cached export entry (no double export/import); a corrupted payload
    is refused by checksum and the stream falls back to re-prefill,
    bit-identical — never silently corrupt; a backpressured decode host
    parks the ship and the retry lands it
  * counters — preempting/exporting a stream takes back its host's
    prefix_hits contribution and eviction takes back admissions_deferred,
    so fleet-summed counters count each logical admission once
  * conservation — property test over a two-store ship lifecycle: on BOTH
    pools, free + referenced + cached-unreferenced partitions the blocks
    after every operation, with exported-but-unacked blocks held referenced
    by the export ledger (never freed, never re-leased) until the ack
"""

import sys
from pathlib import Path

import numpy as np
import pytest

import jax

sys.path.insert(0, str(Path(__file__).parent))
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

import _seed_verify as sv

from repro.configs import get_config
from repro.core import tensorizer as tz
from repro.launch.serve import _quant_predicate
from repro.models import init_model
from repro.serving import Engine, EngineConfig, PagedKVStore, Router, RouterConfig
from repro.serving.router import parse_disaggregate
from repro.serving.transport import build_inproc_fleet

CFG = get_config("tinyllama-1.1b").smoke()
RNG = np.random.default_rng(7)
ROLES = parse_disaggregate("prefill:1,decode:1", 2)


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, jax.random.PRNGKey(0))


def _pecfg(**kw):
    base = dict(max_slots=4, max_queue=16, max_seq_len=64,
                cache_backend="paged", block_size=8, paged_native=True)
    base.update(kw)
    return EngineConfig(**base)


def _prompts(lens, rng=None):
    rng = RNG if rng is None else rng
    return [rng.integers(0, CFG.vocab, (l,), dtype=np.int32) for l in lens]


def _sequential(cfg, params, prompts, gens, ecfg):
    """Reference: one engine, one request at a time."""
    eng = Engine(cfg, params, ecfg)
    outs = []
    for p, g in zip(prompts, gens):
        req = eng.submit(p, g, strict=True)
        eng.run_until_complete()
        outs.append(list(req.tokens))
    eng.close()
    return outs


def _prefill_issued(flags):
    return sum(n for f, n in flags.items()
               if f.startswith(("prefill", "draft_prefill")))


# ================================================================ seed harness

def test_continuation_sweep_all_points_clean():
    """The harness's core guarantee at smoke scale: EVERY continuation
    point of a greedy stream is clean — cutting at W and re-admitting
    ``prompt + tokens[:W]`` regenerates the exact remaining stream. This is
    the property the router's re-prefill fallback (host loss, failed ship)
    silently relies on at arbitrary, load-dependent cut points."""
    params = init_model(CFG, jax.random.PRNGKey(0))
    prompt = _prompts([6], rng=np.random.default_rng(21))[0]
    report = sv.assert_clean_continuations(
        CFG, params, prompt, 10,
        ecfg_kw=dict(max_slots=2, max_seq_len=32))
    assert report.clean == list(range(1, 10))
    assert report.ranges() == [(1, 9)]


def test_continuation_sweep_has_teeth():
    """Self-test: a tampered continuation token at one cut point must be
    flagged at exactly that W with the right first-divergence index — a
    sweep that cannot fail would verify nothing."""
    params = init_model(CFG, jax.random.PRNGKey(0))
    prompt = _prompts([6], rng=np.random.default_rng(21))[0]
    base = sv.run_stream(CFG, params, prompt, 8,
                         ecfg_kw=dict(max_slots=2, max_seq_len=32))

    def tamper(w, cont):
        return ([(cont[0] + 1) % CFG.vocab] + cont[1:]) if w == 3 else cont

    report = sv.sweep_continuations(
        CFG, params, prompt, 8, baseline=base,
        ecfg_kw=dict(max_slots=2, max_seq_len=32),
        cut_points=(2, 3, 4), _tamper=tamper)
    assert report.divergent == [(3, 3)]
    assert report.clean == [2, 4]
    assert not report.all_clean
    with pytest.raises(AssertionError, match="divergent cut points"):
        sv.assert_clean_continuations(
            CFG, params, prompt, 8, baseline=base,
            ecfg_kw=dict(max_slots=2, max_seq_len=32),
            cut_points=(3,), _tamper=tamper)


@pytest.mark.slow
def test_pinned_transport_seeds_verified():
    """The prompt seeds tests/test_transport.py pins (6/4/0) are those whose
    greedy streams stitch cleanly at EVERY continuation cut point: its
    drain, SIGKILL and flaky-channel tests re-admit those streams at
    timing-dependent W. Verify each through the harness — the sweep any
    future re-pin must pass."""
    big = CFG.replace(n_layers=4, d_model=256, n_heads=8, n_kv=4,
                      d_ff=1024, vocab=512, head_dim=32)
    bparams = init_model(big, jax.random.PRNGKey(0))
    # (seed, prompt lengths, greedy (prompt index, gen) pairs, KV dtypes)
    pinned = [
        (6, (6, 9, 4, 7), ((1, 96), (3, 6)), ("bfloat16", "int8")),
        (4, (6, 5, 7, 4), ((1, 80), (3, 80)), ("bfloat16",)),
        (0, (6, 5), ((0, 96), (1, 24)), ("bfloat16",)),
    ]
    for seed, lens, streams, kv_dtypes in pinned:
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, big.vocab, (n,), dtype=np.int32)
                   for n in lens]
        for kv in kv_dtypes:
            for i, gen in streams:
                sv.assert_clean_continuations(
                    big.replace(kv_cache_dtype=kv), bparams, prompts[i],
                    gen, ecfg_kw=dict(max_slots=2, max_seq_len=128))


# ============================================================== bit-identity

def _serve_disagg(cfg, params, prompts, gens, ecfg, *, stagger=3,
                  wrap_src=None, wrap_dst=None):
    """Serve a staggered mix on an in-process prefill:1,decode:1 fleet.
    ``wrap_src``/``wrap_dst`` optionally wrap the prefill/decode host
    transports (fault injection). Returns (tokens, router_stats,
    decode_host_flags)."""
    fleet = build_inproc_fleet(cfg, params, ecfg, 2)
    if wrap_src:
        wrap_src(fleet[ROLES.index("prefill")])
    if wrap_dst:
        wrap_dst(fleet[ROLES.index("decode")])
    router = Router(transports=fleet,
                    router_cfg=RouterConfig(handoff_threshold=2,
                                            roles=ROLES))
    reqs = []
    for i, (p, g) in enumerate(zip(prompts, gens)):
        reqs.append(router.submit(p, g, session=str(i), strict=True))
        for _ in range(stagger):
            router.step()
    router.run_until_complete()
    s = router.stats()
    flags = dict(s["per_host"][ROLES.index("decode")]["opq"]["flags"])
    router.close()
    return [list(r.tokens) for r in reqs], s["router"], flags


def test_disagg_dense_bit_identical_and_prefill_free(params):
    """Role-split serving is unobservable in the tokens: the staggered
    disaggregated mix equals one-at-a-time single-engine serving exactly,
    with every long stream shipped and ZERO prefill instructions on the
    decode host — continuation by block import, never by re-prefill."""
    prompts = _prompts([12, 24, 9, 17])
    gens = [24, 16, 24, 16]
    ecfg = _pecfg()
    want = _sequential(CFG, params, prompts, gens, ecfg)
    toks, rstats, flags = _serve_disagg(CFG, params, prompts, gens, ecfg)
    assert toks == want
    assert rstats["ships"] >= 1 and rstats["ship_fallbacks"] == 0
    assert _prefill_issued(flags) == 0, flags


def test_disagg_int8_bit_identical(params):
    """The same mix under serving quantization: regression for the per-row
    activation calibration in models/layers.pdot — with per-TENSOR scales a
    slot's numerics shifted with its decode batchmates, so the disaggregated
    (differently-batched) continuation diverged from the single engine.
    Per-row scales make the whole staggered, shipped mix bit-identical."""
    cfg_q = CFG.replace(quantize="serve")
    params_q = tz.quantize_params(params, predicate=_quant_predicate)
    prompts = _prompts([12, 24, 9])
    gens = [20, 14, 20]
    ecfg = _pecfg()
    want = _sequential(cfg_q, params_q, prompts, gens, ecfg)
    toks, rstats, flags = _serve_disagg(cfg_q, params_q, prompts, gens, ecfg)
    assert toks == want
    assert rstats["ships"] >= 1
    assert _prefill_issued(flags) == 0, flags


# ============================================================ fault injection

def test_ship_rpc_idempotent_no_double_import(params):
    """Transport-level ship semantics under retry: a re-called ship_blocks
    returns the SAME cached entry; a re-delivered recv_blocks of that entry
    dedups on the payload id and returns the SAME local request id (one
    import, not two); a re-sent ack_ship is a no-op. This is what makes the
    whole trio safe for the channel's idempotent-retry policy."""
    ecfg = _pecfg(max_slots=2, max_seq_len=32)
    fleet = build_inproc_fleet(CFG, params, ecfg, 2)
    src, dst = fleet
    eid = src.submit(_prompts([10])[0], 12)
    while not (src.poll({eid: 0}).get(eid) or {}).get("t"):
        src.pump()
    entry = src.ship_blocks(eid)
    assert entry is not None
    again = src.ship_blocks(eid)
    assert again is entry                       # cached, not re-exported
    nid = dst.recv_blocks(entry)
    assert nid is not None
    assert dst.recv_blocks(entry) == nid        # dedup on payload id
    assert dst.engine.metrics.imported_slots == 1
    assert src.ack_ship(entry["payload_id"]) is True
    assert src.ack_ship(entry["payload_id"]) is False     # idempotent
    while dst.has_work():
        dst.pump()
    assert dst.poll({nid: 0})[nid].get("done")
    for t in fleet:
        t.close()


def test_corrupt_ship_payload_falls_back_bit_identically(params):
    """Bit-flip every shipped payload in flight: the importer's checksum
    refuses it (ValueError, slot unwound) and the router falls back to
    re-prefill continuation on the prefill host. The streams still finish
    bit-identical to the single engine — a broken wire can cost latency,
    never correctness, and corruption is never silent."""
    prompts = _prompts([12, 9])
    gens = [20, 20]
    ecfg = _pecfg()
    want = _sequential(CFG, params, prompts, gens, ecfg)

    def corrupt(t):
        orig = t.ship_blocks

        def bad_ship(req_id):
            entry = orig(req_id)
            if entry is not None:
                name = sorted(entry["payload"]["leaves"])[0]
                leaf = np.array(entry["payload"]["leaves"][name], copy=True)
                flat = leaf.reshape(-1).view(np.uint8)
                flat[0] ^= 0xFF
                entry["payload"]["leaves"][name] = leaf
            return entry

        t.ship_blocks = bad_ship

    toks, rstats, flags = _serve_disagg(CFG, params, prompts, gens, ecfg,
                                        wrap_src=corrupt)
    assert toks == want
    assert rstats["ship_fallbacks"] >= 1 and rstats["ships"] == 0
    # the fallback re-prefills on the PREFILL host: the decode host stays
    # prefill-free even on the failure path
    assert _prefill_issued(flags) == 0, flags


def test_backpressured_ship_parks_and_retries(params):
    """A decode host that transiently refuses imports (slot/lease race —
    recv_blocks returns None) parks the ship; the router retries it and the
    stream lands by import, not fallback, still bit-identical."""
    prompts = _prompts([12, 9])
    gens = [20, 20]
    ecfg = _pecfg()
    want = _sequential(CFG, params, prompts, gens, ecfg)

    def flaky(t):
        orig = t.recv_blocks
        state = {"refusals": 3}

        def refusing(entry):
            if state["refusals"] > 0:
                state["refusals"] -= 1
                return None
            return orig(entry)

        t.recv_blocks = refusing

    toks, rstats, flags = _serve_disagg(CFG, params, prompts, gens, ecfg,
                                        wrap_dst=flaky)
    assert toks == want
    assert rstats["ships"] >= 1 and rstats["ship_fallbacks"] == 0
    assert _prefill_issued(flags) == 0, flags


# ================================================================== counters

def test_preempt_and_evict_reconcile_admission_counters(params):
    """Regression for the double-count: a preempted (or exported) stream's
    prefix_hits contribution leaves with it, and an evicted queued request
    takes its admissions_deferred mark along — whichever host re-admits
    counts afresh, so fleet sums count one logical admission once. A stream
    that COMPLETES keeps its host's counts."""
    # 6 usable blocks: one 16+16-token stream leases 4, so a second one's
    # admission must defer on the lease even with a slot free
    ecfg = _pecfg(max_slots=2, max_seq_len=32, n_blocks=7,
                  prefix_cache=True)
    eng = Engine(CFG, params, ecfg)
    prompt = _prompts([16])[0]
    # cold run commits the prefix; the rerun's lease walks the trie
    r0 = eng.submit(prompt, 4, strict=True)
    eng.run_until_complete()
    assert r0.done and eng.metrics.prefix_hits == 0
    r1 = eng.submit(prompt, 8, strict=True)
    eng.step()
    assert eng.metrics.prefix_hits == 1
    eng.preempt(r1.id)
    assert eng.metrics.prefix_hits == 0          # contribution unwound
    # same via the export path
    r2 = eng.submit(prompt, 8, strict=True)
    eng.step()
    assert eng.metrics.prefix_hits == 1
    _, payload = eng.extract_seeded(r2.id)
    assert eng.metrics.prefix_hits == 0
    eng.release_exported(payload["payload_id"])

    # deferral reconciliation: exhaust the pool so admission defers, then
    # evict the queued request — the deferral leaves with it
    big = _prompts([16])[0]
    ra = eng.submit(big, 16, strict=True)
    rb = eng.submit(big[::-1].copy(), 16, strict=True)
    deadline = 200
    while eng.metrics.admissions_deferred == 0 and deadline:
        eng.step()
        deadline -= 1
    assert eng.metrics.admissions_deferred == 1
    evicted = eng.evict_queued()
    assert [r.id for r in evicted] == [rb.id]
    assert eng.metrics.admissions_deferred == 0  # mark left with the request
    eng.run_until_complete()
    assert ra.done
    eng.close()


# =============================================================== conservation

def _census_ok(store: PagedKVStore):
    """Free / referenced / cached-unreferenced partition the pool, and the
    refcounts reconcile with slot leases PLUS the export ledger — an
    exported-but-unacked block is referenced (unfreed, unreusable)."""
    from collections import Counter
    c = store.debug_block_census()
    everything = c["free"] + c["referenced"] + c["cached_unreferenced"]
    assert len(everything) == len(set(everything)), c
    assert sorted(everything) == list(range(1, store.n_blocks)), c
    holds = Counter(b for bs in store._leased.values() for b in bs)
    holds.update(b for bs in store._exported.values() for b in bs)
    assert sorted(holds) == c["referenced"]
    for b, n in holds.items():
        assert store._ref[b] == n, (b, n, store._ref[b])


@settings(deadline=None, max_examples=10)
@given(st.integers(min_value=0, max_value=1 << 30))
def test_block_conservation_across_ship_lifecycle(seed):
    """Random lease/commit/export/import/ack/fallback/retire traffic over
    TWO small pools (the shipping pair): after EVERY operation both pools
    partition exactly into free / referenced / cached-unreferenced, blocks
    on the export ledger stay referenced until the ack (in-flight ships are
    never freed or re-leased — the store's fresh-lease assert arms that),
    and a released payload frees exactly the blocks nothing else holds."""
    rng = np.random.default_rng(seed)
    cfg = get_config("tinyllama-1.1b").smoke()
    mk = lambda: PagedKVStore(cfg, n_slots=3, max_seq_len=16, block_size=4,
                              n_blocks=12, prefix_cache=True)
    A, B = mk(), mk()
    in_flight = []                 # exported from A, not yet imported/acked
    imported = []                  # payload ids imported into B, unacked
    pid_counter = [0]
    for _ in range(80):
        op = int(rng.integers(0, 6))
        if op == 0:                              # lease on A (maybe commit)
            slot = int(rng.integers(0, 3))
            if slot not in A._leased:
                plen = int(rng.integers(1, 13))
                gen = int(rng.integers(1, 17 - plen))
                tokens = rng.integers(0, 3, (plen,), dtype=np.int32)
                if A.lease(slot, plen, gen, tokens=tokens) and \
                        int(rng.integers(0, 2)):
                    A.commit_prefix(slot)
        elif op == 1:                            # export a leased A slot
            leased = sorted(set(A._leased))
            if leased:
                slot = int(rng.choice(leased))
                # stamp a valid length so the payload carries real blocks
                # (bounded by the lease, as any real decode position is)
                cap = len(A._leased[slot]) * 4
                n_valid = int(rng.integers(0, cap + 1))
                A.cache = dict(A.cache,
                               index=A.cache["index"].at[slot].set(n_valid))
                pid_counter[0] += 1
                pid = f"p{pid_counter[0]}"
                in_flight.append((pid, A.export_blocks(slot,
                                                       payload_id=pid)))
        elif op == 2 and in_flight:              # import into B
            pid, payload = in_flight.pop(int(rng.integers(len(in_flight))))
            free = [s for s in range(3) if s not in B._leased]
            if free and B.lease(free[0], 8, 8):
                try:
                    B.import_blocks(free[0], payload)
                    imported.append(pid)
                except ValueError:
                    B.reset(free[0])
                    A.release_exported(pid)      # corrupt: fall back
            else:
                in_flight.append((pid, payload))  # refused: park
        elif op == 3:                            # ack an imported ship
            if imported:
                assert A.release_exported(imported.pop()) is True
        elif op == 4 and in_flight:              # fallback without import
            pid, _ = in_flight.pop(int(rng.integers(len(in_flight))))
            assert A.release_exported(pid) is True
        else:                                    # retire someone somewhere
            store = A if int(rng.integers(0, 2)) else B
            leased = sorted(set(store._leased))
            if leased:
                store.reset(int(rng.choice(leased)))
        _census_ok(A)
        _census_ok(B)
        # double-ack is always a no-op
        assert A.release_exported("nonexistent") is False
    # settle everything: acks for all in-flight ships, resets everywhere
    for pid, _ in in_flight:
        assert A.release_exported(pid) is True
    for pid in imported:
        A.release_exported(pid)
    for store in (A, B):
        for slot in sorted(set(store._leased)):
            store.reset(slot)
        _census_ok(store)
        c = store.debug_block_census()
        assert c["referenced"] == []             # nothing leaks at the end
