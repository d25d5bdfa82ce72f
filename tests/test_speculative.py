"""Speculative draft-verify decode invariants (serving/engine.py
``_spec_decode_once`` + the window verify path in models/serve.py and the
store rollback machinery in serving/store.py):

  * bit-identity — greedy acceptance makes the speculative stream equal to
                   plain decode TOKEN FOR TOKEN and, at retire time, CACHE
                   BIT FOR CACHE BIT, across dense / int8-KV / MoE targets
                   and contiguous / paged-bridge / paged-native backends —
                   a bad draft costs speed, never correctness
  * stops        — EOS and length stops landing MID-WINDOW retire the slot
                   at the stop position: nothing past the stop is emitted
                   or left in the cache, and the overshoot scrub has teeth
                   (forgetting it is detected by the cache-bit check)
  * interplay    — speculative x prefix-cache warm hit, x router drain /
                   handoff, and with a RECURRENT draft (snapshot-selection
                   rollback) all stay bit-identical to plain decode
  * lockstep     — the draft store tracks the target store's per-slot write
                   position through admission, variable advancement,
                   preemption, and retire (token bit-identity alone cannot
                   see draft drift: greedy acceptance is draft-agnostic)
  * conservation — the paged block census survives random accept/reject/
                   retire lifecycles under variable per-slot advancement
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_config
from repro.models import init_model
from repro.serving import Engine, EngineConfig, PagedKVStore
from repro.serving.router import Router, RouterConfig
from repro.serving.store import RecurrentStateStore, pristine_value

CFG = get_config("tinyllama-1.1b").smoke()
MOE_CFG = get_config("moonshot-v1-16b-a3b").smoke()
XLSTM_CFG = get_config("xlstm-125m").smoke()
RNG = np.random.default_rng(23)


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def moe_params():
    return init_model(MOE_CFG, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def bad_draft_params():
    """A draft that disagrees with the target almost everywhere — the
    zero-acceptance worst case (every round advances each slot by 1)."""
    return init_model(CFG, jax.random.PRNGKey(7))


def _spec_kw(draft_cfg=None, k=3):
    return dict(speculative=True, spec_k=k, draft=draft_cfg or CFG)


class SnapshotEngine(Engine):
    """Engine that captures each request's cache row AT RETIRE, before the
    slot reset scrubs it — the cache-bit half of the spec==plain invariant.
    Rows are masked to the slot's leased extent (prompt + max_new): cells
    past the lease read through the shared null block (paged) or untouched
    free-row space, which two runs are free to differ on because no request
    can ever observe them."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.rows = {}

    def _retire(self, slot):
        req = self.scheduler.active[slot]
        ext = len(req.prompt) + req.max_new_tokens
        view = self.store.gather_view()
        self.rows[req.id] = {
            n: np.asarray(leaf[slot] if n == "index" else leaf[:, slot, :ext])
            for n, leaf in view.items()}
        super()._retire(slot)


def _assert_rows_equal(ra, rb):
    assert set(ra) == set(rb)
    for name in ra:
        np.testing.assert_array_equal(ra[name], rb[name], err_msg=name)


def _serve(eng, prompts, gens, stagger=0):
    """Submit every prompt (optionally stepping between submissions so
    arrivals join a mid-flight batch), run to completion, return streams."""
    reqs = []
    for p, g in zip(prompts, gens):
        reqs.append(eng.submit(p, g, strict=True))
        for _ in range(stagger):
            eng.step()
    eng.run_until_complete()
    return reqs, [list(r.tokens) for r in reqs]


def _traffic(vocab, lens=(6, 12, 9), gens=(6, 4, 5)):
    return ([RNG.integers(0, vocab, (n,), dtype=np.int32) for n in lens],
            list(gens))


# ===========================================================================
# bit-identity: tokens AND retire-time cache bits, across formats/backends
# ===========================================================================

@pytest.mark.parametrize("family,kv_dtype,backend_kw,draft", [
    ("dense", "bfloat16", {}, "good"),
    ("dense", "bfloat16", {}, "bad"),
    ("dense", "int8", {}, "good"),
    ("moe", "bfloat16", {}, "good"),
    ("dense", "bfloat16",
     dict(cache_backend="paged", block_size=8), "good"),
    ("dense", "bfloat16",
     dict(cache_backend="paged", block_size=8, paged_native=True), "bad"),
], ids=["contig-good", "contig-bad", "int8-good", "moe-good",
        "paged-bridge-good", "paged-native-bad"])
def test_spec_matches_plain_tokens_and_cache_bits(
        family, kv_dtype, backend_kw, draft, params, moe_params,
        bad_draft_params):
    """The load-bearing invariant: a speculative engine serving staggered
    traffic emits the same streams AND leaves the same retire-time cache
    bits as plain decode — for float-KV, int8-per-token-scale, and MoE
    targets over every store backend, with both a perfect draft (full
    acceptance) and a disagreeing one (every window rejected)."""
    base = MOE_CFG if family == "moe" else CFG
    cfg = base.replace(kv_cache_dtype=kv_dtype)
    tgt_params = moe_params if family == "moe" else params
    # the "good" draft is the target model itself (full acceptance); the
    # "bad" one shares the architecture but not the weights (full rejection)
    dparams = tgt_params if draft == "good" else bad_draft_params
    kw = dict(max_slots=2, max_seq_len=32, **backend_kw)
    prompts, gens = _traffic(cfg.vocab)

    plain = SnapshotEngine(cfg, tgt_params, EngineConfig(**kw))
    preqs, ptoks = _serve(plain, prompts, gens, stagger=1)
    plain.close()

    spec = SnapshotEngine(cfg, tgt_params,
                          EngineConfig(**kw, **_spec_kw(base)),
                          draft_params=dparams)
    sreqs, stoks = _serve(spec, prompts, gens, stagger=1)
    spec.close()

    assert stoks == ptoks
    for pr, sr in zip(preqs, sreqs):
        _assert_rows_equal(plain.rows[pr.id], spec.rows[sr.id])
    # speculation actually speculated: a perfect draft buys multi-token
    # rounds (steps/decode-token < 1), a hostile one degrades to 1/round
    decoded = sum(gens) - len(gens)
    if draft == "good":
        assert spec.metrics.decode_steps < decoded
        assert spec.metrics.accepted_tokens > 0
    else:
        assert spec.metrics.accepted_tokens == 0
        assert all(length == 1 for length in spec.metrics.accept_hist)


def test_spec_staggered_equals_sequential(params):
    """Batch-join invariance survives variable per-slot advancement: slots
    at different depths sharing a verify window emit exactly what each
    request gets when served alone."""
    prompts, gens = _traffic(CFG.vocab, lens=(6, 12, 9), gens=(7, 4, 6))
    kw = dict(max_slots=2, max_seq_len=32, **_spec_kw())
    seq = Engine(CFG, params, EngineConfig(**kw), draft_params=params)
    solo = []
    for p, g in zip(prompts, gens):
        r = seq.submit(p, g, strict=True)
        seq.run_until_complete()
        solo.append(list(r.tokens))
    seq.close()

    stag = Engine(CFG, params, EngineConfig(**kw), draft_params=params)
    _, stoks = _serve(stag, prompts, gens, stagger=1)
    stag.close()
    assert stoks == solo


def test_spec_prefix_cache_warm_hit_bit_identical(params):
    """Speculative decode over a WARM prefix-cache hit: the suffix-only
    admission seeds both caches, then draft-verify rounds advance through
    COW-forked blocks — tokens and retire bits equal plain decode's."""
    preamble = RNG.integers(0, CFG.vocab, (16,), dtype=np.int32)
    prompt = np.concatenate(
        [preamble, RNG.integers(0, CFG.vocab, (4,), dtype=np.int32)])
    kw = dict(max_slots=2, max_seq_len=32, cache_backend="paged",
              block_size=8, prefix_cache=True)

    def serve_hit(ecfg, dparams=None):
        eng = SnapshotEngine(CFG, params, ecfg, draft_params=dparams)
        eng.submit(preamble, 4, strict=True)          # seeds the trie
        eng.run_until_complete()
        req = eng.submit(prompt, 8, strict=True)
        eng.run_until_complete()
        assert eng.stats()["prefix_hits"] >= 1        # the hit actually hit
        row = eng.rows[req.id]
        eng.close()
        return list(req.tokens), row

    ptoks, prow = serve_hit(EngineConfig(**kw))
    stoks, srow = serve_hit(EngineConfig(**kw, **_spec_kw()), params)
    assert stoks == ptoks
    _assert_rows_equal(prow, srow)


# ===========================================================================
# EOS / length stops landing mid-window
# ===========================================================================

def _pick_mid_window_eos(full):
    """A token whose FIRST occurrence in the stream sits strictly inside an
    accepted window (stream index not a multiple of W=4): stopping there
    forces a truncated round, not a window-boundary retire."""
    return next((i, int(t)) for i, t in enumerate(full)
                if 0 < i < len(full) - 1 and i % 4 != 0
                and full.index(t) == i)


@pytest.mark.parametrize("backend_kw", [
    {}, dict(cache_backend="paged", block_size=8, paged_native=True),
], ids=["contig", "paged-native"])
def test_eos_mid_window_retires_at_stop(backend_kw, params):
    """An EOS inside the accepted window retires the slot AT the stop: no
    token past EOS is emitted, nothing past it survives in the cache (the
    retire row equals plain-with-EOS bit for bit), and for the paged store
    every freed generation block comes back scrubbed."""
    prompt = RNG.integers(0, CFG.vocab, (8,), dtype=np.int32)
    kw = dict(max_slots=2, max_seq_len=32, **backend_kw)

    probe = Engine(CFG, params, EngineConfig(**kw))
    r = probe.submit(prompt, 10, strict=True)
    probe.run_until_complete()
    full = list(r.tokens)
    probe.close()
    stop, eos = _pick_mid_window_eos(full)

    plain = SnapshotEngine(CFG, params, EngineConfig(**kw, eos_id=eos))
    rp = plain.submit(prompt, 10, strict=True)
    plain.run_until_complete()
    plain.close()

    spec = SnapshotEngine(CFG, params, EngineConfig(**kw, eos_id=eos,
                                                    **_spec_kw()),
                          draft_params=params)
    rs = spec.submit(prompt, 10, strict=True)
    spec.run_until_complete()

    assert list(rs.tokens) == list(rp.tokens) == full[:stop + 1]
    assert rs.tokens[-1] == eos and eos not in rs.tokens[:-1]
    # the stop round truncated mid-window (emitted stop % 4 < W tokens)
    assert spec.metrics.accept_hist.get(stop % 4, 0) >= 1
    _assert_rows_equal(plain.rows[rp.id], spec.rows[rs.id])
    if backend_kw:
        # with every slot retired, all blocks but the shared null block (0,
        # the write sink for out-of-lease redirects) must be back to the
        # pristine fill — freed mid-window blocks included
        store = spec.store
        assert not store._leased
        for name, leaf in store.cache.items():
            if name in ("index", "tables"):
                continue
            assert np.all(np.asarray(leaf[:, 1:]) == pristine_value(name)), \
                name
    spec.close()


@pytest.mark.parametrize("backend_kw", [
    {}, dict(cache_backend="paged", block_size=8, paged_native=True),
], ids=["contig", "paged-native"])
def test_eos_overshoot_scrub_has_teeth(backend_kw, params):
    """The rejected-position scrub is load-bearing for the cache-bit
    invariant: replay the would-be bug (rollback updates indices but
    FORGETS to scrub past the stop) and the retire-row comparison must
    catch the leaked draft K/V — proof an overshoot would be detected."""
    prompt = RNG.integers(0, CFG.vocab, (8,), dtype=np.int32)
    kw = dict(max_slots=2, max_seq_len=32, **backend_kw)

    probe = Engine(CFG, params, EngineConfig(**kw))
    r = probe.submit(prompt, 10, strict=True)
    probe.run_until_complete()
    stop, eos = _pick_mid_window_eos(list(r.tokens))
    probe.close()

    plain = SnapshotEngine(CFG, params, EngineConfig(**kw, eos_id=eos))
    rp = plain.submit(prompt, 10, strict=True)
    plain.run_until_complete()
    plain.close()

    spec = SnapshotEngine(CFG, params, EngineConfig(**kw, eos_id=eos,
                                                    **_spec_kw()),
                          draft_params=params)
    forgot = spec.store.rollback

    def no_scrub(slots, new_index, positions):
        # indices advance correctly, but every scrub position is replaced
        # by the out-of-range pad — nothing gets cleaned
        forgot(slots, new_index,
               np.full_like(np.asarray(positions), spec.ecfg.max_seq_len))

    spec.store.rollback = no_scrub
    rs = spec.submit(prompt, 10, strict=True)
    spec.run_until_complete()
    spec.close()
    with pytest.raises(AssertionError):
        _assert_rows_equal(plain.rows[rp.id], spec.rows[rs.id])


# ===========================================================================
# interplay: router drain/handoff, recurrent draft
# ===========================================================================

def test_spec_session_survives_router_drain(params):
    """Drain handoff between SPECULATIVE engines mid-generation: the
    preempted continuation re-admits (target + draft caches re-seeded from
    prompt + tokens-so-far) and the stitched stream equals an undrained
    speculative serve — which other tests pin to plain decode."""
    ecfg = EngineConfig(max_slots=1, max_seq_len=32, **_spec_kw())
    prompt = RNG.integers(0, CFG.vocab, (12,), dtype=np.int32)

    ref = Engine(CFG, params, ecfg, draft_params=params)
    r0 = ref.submit(prompt, 10, strict=True)
    ref.run_until_complete()
    ref.close()

    router = Router(CFG, params, ecfg,
                    RouterConfig(n_hosts=2, handoff_threshold=0),
                    draft_params=params)
    r = router.submit(prompt, 10, session="a", strict=True)
    for _ in range(2):
        router.step()
    router.drain(r.hosts[0])                      # preempt mid-generation
    while router.has_work():
        router.step()
    assert router.stats()["router"]["handoffs"] >= 1
    assert len(r.hosts) > 1
    assert r.tokens == list(r0.tokens)            # bit-identical stitched
    router.close()


def test_recurrent_draft_bit_identical_and_lockstep(params):
    """A RECURRENT draft (state snapshots instead of K/V rollback) drives
    the same stream as plain decode, and its per-slot write position stays
    in lockstep with the target store at every step — token bit-identity
    alone cannot see draft drift, so lockstep is asserted directly."""
    assert XLSTM_CFG.vocab == CFG.vocab
    dparams = init_model(XLSTM_CFG, jax.random.PRNGKey(3))
    prompts, gens = _traffic(CFG.vocab, lens=(6, 11), gens=(8, 5))
    kw = dict(max_slots=2, max_seq_len=32)

    plain = Engine(CFG, params, EngineConfig(**kw))
    _, ptoks = _serve(plain, prompts, gens, stagger=1)
    plain.close()

    spec = Engine(CFG, params,
                  EngineConfig(**kw, **_spec_kw(XLSTM_CFG, k=2)),
                  draft_params=dparams)
    reqs = [spec.submit(p, g, strict=True) for p, g in zip(prompts, gens)]
    while spec.scheduler.has_work():
        spec.step()
        for slot in spec.scheduler.active:
            assert (spec.draft_store.slot_index(slot)
                    == spec.store.slot_index(slot))
    spec.close()
    assert [list(r.tokens) for r in reqs] == ptoks


def test_adopt_selected_picks_per_slot_snapshot():
    """RecurrentStateStore.adopt_selected unit: with snapshots filled by
    their list position, each slot's row must come out equal to its sel
    index — the per-slot gather over the stacked snapshot axis that
    implements recurrent-draft rollback."""
    store = RecurrentStateStore(XLSTM_CFG, n_slots=3, max_seq_len=8)
    snaps = [jax.tree.map(lambda leaf, i=i: jnp.full_like(leaf, i),
                          store.cache) for i in range(4)]
    sel = [2, 0, 3]
    store.adopt_selected(snaps, sel)
    for name, leaf in store.cache.items():
        arr = np.asarray(leaf)
        for slot, s in enumerate(sel):
            row = arr[slot] if name == "index" else arr[:, slot]
            assert np.all(row == s), (name, slot, s)


# ===========================================================================
# lockstep + conservation under the full lifecycle
# ===========================================================================

def test_spec_lifecycle_lockstep_preempt_conservation(params):
    """Speculative engine over the paged prefix-cache backend with a
    mid-run preemption: after EVERY step the block census partitions the
    pool, the draft store tracks the target store per slot, and the
    device-side write position agrees with host arithmetic
    (prompt + generated - 1). Completed streams still match plain decode;
    the preempted stream is a prefix of its plain serve."""
    sys.path  # noqa: B018  (keep flake quiet about the shim import above)
    from test_prefix_cache import _census_ok

    ecfg = EngineConfig(max_slots=2, max_seq_len=32, cache_backend="paged",
                        block_size=8, prefix_cache=True, **_spec_kw())
    eng = Engine(CFG, params, ecfg, draft_params=params)
    prompts, gens = _traffic(CFG.vocab, lens=(6, 11, 8), gens=(8, 5, 7))
    reqs = [eng.submit(p, g, strict=True) for p, g in zip(prompts, gens)]
    preempted = None
    steps = 0
    while eng.scheduler.has_work():
        eng.step()
        steps += 1
        _census_ok(eng.store)
        for slot, req in eng.scheduler.active.items():
            assert (eng.draft_store.slot_index(slot)
                    == eng.store.slot_index(slot))
            assert (eng.store.slot_index(slot)
                    == len(req.prompt) + req.metrics.n_generated - 1)
        if steps == 2 and preempted is None and eng.scheduler.active:
            victim = next(iter(eng.scheduler.active.values()))
            preempted = eng.preempt(victim.id)
            _census_ok(eng.store)
    eng.close()

    plain = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    for req, prompt, gen in zip(reqs, prompts, gens):
        ref = plain.submit(prompt, gen, strict=True)
        plain.run_until_complete()
        if preempted is not None and req.id == preempted.id:
            got = list(req.tokens)              # cut short mid-generation
            assert got == list(ref.tokens)[:len(got)]
        else:
            assert list(req.tokens) == list(ref.tokens)
    plain.close()


@settings(deadline=None, max_examples=10)
@given(st.integers(min_value=0, max_value=1 << 30))
def test_block_conservation_under_variable_advancement(seed):
    """Property test: random lease / speculative-rollback / retire / drain
    sequences — with rollback plans whose scrub windows overshoot both the
    lease and the sequence bound, exactly as variable per-slot advancement
    produces them — keep the free / referenced / cached-unreferenced pool
    partition exact after every operation."""
    from test_prefix_cache import _census_ok

    rng = np.random.default_rng(seed)
    cfg = get_config("tinyllama-1.1b").smoke()
    store = PagedKVStore(cfg, n_slots=3, max_seq_len=16, block_size=4,
                         n_blocks=10, prefix_cache=True)
    k = 3
    extents = {}
    _census_ok(store)
    for _ in range(60):
        op = int(rng.integers(0, 5))
        if op in (0, 3):                          # lease (+ maybe commit)
            slot = int(rng.integers(0, 3))
            if slot in store._leased:
                continue
            plen = int(rng.integers(1, 13))
            gen = int(rng.integers(1, 17 - plen))
            tokens = rng.integers(0, 3, (plen,), dtype=np.int32)
            if store.lease(slot, plen, gen, tokens=tokens):
                extents[slot] = (plen, plen + gen)
                if op == 0:
                    store.commit_prefix(slot)
        elif op == 1:                             # retire one leased slot
            leased = sorted(store._leased)
            if leased:
                s = int(rng.choice(leased))
                store.reset(s)
                extents.pop(s, None)
        elif op == 4:                             # speculative rollback
            leased = sorted(store._leased)
            if not leased:
                continue
            slots = np.full((3,), 3, np.int64)    # pad: dropped
            new_index = np.zeros((3,), np.int64)
            scrub = np.full((3, k), 16, np.int64)
            for s in leased:
                plen, ext = extents[s]
                p = int(rng.integers(plen - 1, ext))
                emit = int(rng.integers(1, k + 2))
                slots[s] = s
                new_index[s] = min(p + emit, ext)
                # deliberately overshoots the lease and max_seq_len: the
                # null-block redirect must absorb it
                scrub[s] = p + emit + np.arange(k)
            store.rollback(slots, new_index, scrub)
        else:                                     # drain
            for s in sorted(store._leased):
                store.reset(s)
            extents.clear()
        _census_ok(store)
    for s in sorted(store._leased):
        store.reset(s)
    _census_ok(store)


# ===========================================================================
# dispatch-shape audit + metrics reconciliation + config validation
# ===========================================================================

def test_spec_opq_flags_and_metrics_reconcile(params):
    """A speculative engine's OPQ flag set is exactly {prefill, draft
    prefill, draft decode, verify} — no plain decode sneaks in — with
    counts that reconcile against the metrics, and the token counters are
    accepted-token based: steps per decode token lands strictly below 1
    with a perfect draft."""
    kw = dict(max_slots=2, max_seq_len=32, **_spec_kw())
    eng = Engine(CFG, params, EngineConfig(**kw), draft_params=params)
    prompts, gens = _traffic(CFG.vocab, lens=(6, 12), gens=(7, 5))
    reqs, _ = _serve(eng, prompts, gens)
    s = eng.stats()
    eng.close()

    flags = s["opq"]["flags"]
    assert set(flags) == {"prefill/16", "draft_prefill/16",
                          "draft_decode", "verify"}
    assert flags["verify"] == s["decode_steps"] == s["spec_rounds"]
    assert flags["draft_decode"] == s["draft_steps"]
    assert s["draft_steps"] == (eng.ecfg.spec_k + 1) * s["spec_rounds"]

    # token accounting reconciles: every emitted token counted once
    assert s["tokens_generated"] == sum(r.metrics.n_generated for r in reqs)
    decoded = s["tokens_generated"] - s["completed"]     # minus first tokens
    slot_rounds = sum(s["accept_hist"].values())
    assert decoded == s["accepted_tokens"] + slot_rounds
    assert s["proposed_tokens"] == eng.ecfg.spec_k * slot_rounds
    assert s["acceptance_rate"] == pytest.approx(
        s["accepted_tokens"] / s["proposed_tokens"])
    assert s["decode_steps"] < decoded           # the whole point


def test_plain_engine_flag_set_unchanged(params):
    """Guard: a NON-speculative engine's dispatch shapes are untouched by
    the spec machinery — prefill flags of the one bucket plus plain
    decode, nothing draft- or verify-shaped."""
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    prompts, gens = _traffic(CFG.vocab, lens=(6, 12), gens=(4, 4))
    _serve(eng, prompts, gens)
    flags = eng.stats()["opq"]["flags"]
    eng.close()
    assert set(flags) == {"prefill/16", "decode"}


def test_spec_config_validation(params):
    base = dict(max_slots=1, max_seq_len=32)
    with pytest.raises(ValueError, match="draft model"):
        Engine(CFG, params, EngineConfig(**base, speculative=True))
    with pytest.raises(ValueError, match="speculative=False"):
        Engine(CFG, params, EngineConfig(**base, draft=CFG))
    with pytest.raises(ValueError, match="spec_k"):
        Engine(CFG, params,
               EngineConfig(**base, **_spec_kw(k=0)), draft_params=params)
    with pytest.raises(ValueError, match="vocab"):
        Engine(CFG, params,
               EngineConfig(**base, speculative=True,
                            draft=CFG.replace(vocab=CFG.vocab * 2)),
               draft_params=params)
    with pytest.raises(ValueError, match="TARGET"):
        Engine(XLSTM_CFG, params,
               EngineConfig(**base, **_spec_kw()), draft_params=params)
    with pytest.raises(ValueError, match="paged_kernel|kernel"):
        Engine(CFG, params,
               EngineConfig(**base, cache_backend="paged", paged_native=True,
                            paged_kernel=True, **_spec_kw()),
               draft_params=params)
