"""Continuous-batching engine invariants (serving/engine.py):

  * isolation   — a request's tokens never leak into another slot: staggered
                  mixed-traffic outputs are BIT-IDENTICAL to one-at-a-time
                  sequential decoding of the same requests (dense AND MoE)
  * fused admission — seeding is ONE prefill forward + ONE batched slot write
                  per bucket (asserted via OPQ instruction flags, zero replay
                  decodes), and the seeded cache + generated tokens are
                  bit-identical to the PR-1 B=1 prompt-replay seeding (the
                  reference replay seeder lives HERE, not in src/)
  * slot reuse  — retired slots are re-leased without reallocating the cache
  * metrics     — engine counters reconcile with per-request token counts
  * admission   — the bounded queue and the per-slot sequence budget reject
  * int8 KV     — the slot store carries the Tensorizer int8 cache scales
  * MoE         — routing is per-request isolated: idle slots are masked out
                  of the expert-capacity cumsum, prefill routes row-isolated
  * SlotStore   — the cache sits behind the pluggable store protocol
                  (serving/store.py): paged decode is bit-identical to
                  contiguous (dense + int8-KV + MoE), block-pool exhaustion
                  is admission backpressure (never corruption), and the
                  recurrent backend serves ssm/hybrid families with pristine
                  slot reset (no state leaks across leases)
"""

import types
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.models import serve as SV
from repro.models import steps as ST
from repro.serving import (
    ContiguousKVStore, Engine, EngineConfig, KVSlotManager, PagedKVStore,
    QueueFull, RecurrentStateStore, bucket_for, default_buckets,
    format_memory_stats, make_store,
)

CFG = get_config("tinyllama-1.1b").smoke()
MOE_CFG = get_config("moonshot-v1-16b-a3b").smoke()
XLSTM_CFG = get_config("xlstm-125m").smoke()
HYBRID_CFG = get_config("zamba2-7b").smoke()
RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def params():
    return init_model(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def moe_params():
    return init_model(MOE_CFG, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def xlstm_params():
    return init_model(XLSTM_CFG, jax.random.PRNGKey(2))


def _prompts(lens, cfg=CFG):
    return [RNG.integers(0, cfg.vocab, (l,), dtype=np.int32) for l in lens]


def _sequential(params, prompts, gens, cfg=CFG, **ecfg_kw):
    """Reference: same engine, one request at a time, drained in between."""
    eng = Engine(cfg, params, EngineConfig(max_slots=2, max_seq_len=32, **ecfg_kw))
    outs = []
    for p, g in zip(prompts, gens):
        req = eng.submit(p, g)
        eng.run_until_complete()
        outs.append(list(req.tokens))
    eng.close()
    return outs


def _staggered(params, prompts, gens, cfg=CFG, **ecfg_kw):
    """Mixed traffic: two joins mid-flight, the rest queued behind them."""
    eng = Engine(cfg, params, EngineConfig(max_slots=2, max_seq_len=32, **ecfg_kw))
    reqs = [eng.submit(prompts[0], gens[0])]
    eng.step()
    reqs.append(eng.submit(prompts[1], gens[1]))
    eng.step()
    for p, g in zip(prompts[2:], gens[2:]):
        reqs.append(eng.submit(p, g))
    eng.run_until_complete()
    outs = [list(r.tokens) for r in reqs]
    eng.close()
    return outs


class _ReplaySeededEngine(Engine):
    """The PR-1 admission reference: first token from the bucketed prefill,
    but slot caches seeded by replaying the prompt token-by-token through the
    B=1 decode step (O(prompt_len) forwards — the path fused admission
    deleted). Kept in tests only, as the bit-identity oracle."""

    def __init__(self, cfg, params, engine_cfg=None, **kw):
        super().__init__(cfg, params, engine_cfg, **kw)
        self._replay = jax.jit(ST.make_decode_step(cfg))
        self._replay_template = SV.init_cache(cfg, 1, self.ecfg.max_seq_len)

    def _seed_admitted(self, slot, req, kv):
        del kv                               # fused prefill K/V ignored
        rc = self._replay_template
        for t in req.prompt:
            _, rc = self._replay(
                self.params, rc, {"tokens": jnp.asarray([[int(t)]], jnp.int32)})
        self.kv.write_slot(slot, rc, n_valid=len(req.prompt))


def _pure_sequential_decode(cfg, params, prompt, gen, max_seq):
    """Single-request decoding with no engine at all: feed the prompt through
    the B=1 decode step, then greedy-decode ``gen`` tokens."""
    dec = jax.jit(ST.make_decode_step(cfg))
    cache = SV.init_cache(cfg, 1, max_seq)
    for t in prompt:
        tok, cache = dec(params, cache, {"tokens": jnp.asarray([[int(t)]], jnp.int32)})
    out = [int(tok[0])]
    while len(out) < gen:
        tok, cache = dec(params, cache,
                         {"tokens": jnp.asarray([[out[-1]]], jnp.int32)})
        out.append(int(tok[0]))
    return out


def test_staggered_arrivals_match_sequential_exactly(params):
    """The headline invariant: requests joining/leaving the in-flight batch
    mid-decode produce exactly the tokens they would produce alone."""
    prompts = _prompts([5, 9, 4, 7])
    gens = [6, 5, 8, 3]
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    reqs = [eng.submit(prompts[0], gens[0])]
    eng.step()                                   # r0 decoding alone
    reqs.append(eng.submit(prompts[1], gens[1]))  # joins mid-flight
    eng.step()
    reqs.append(eng.submit(prompts[2], gens[2]))  # queues (slots full) then joins
    reqs.append(eng.submit(prompts[3], gens[3]))
    eng.run_until_complete()
    staggered = [list(r.tokens) for r in reqs]

    sequential = _sequential(params, prompts, gens)
    assert staggered == sequential               # bit-identical, not allclose
    eng.close()


def test_no_cross_slot_leakage_same_prompt(params):
    """Two identical prompts decoding simultaneously in different slots must
    produce identical streams (any cross-slot read would desync them)."""
    p = _prompts([6])[0]
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    r1 = eng.submit(p, 8)
    r2 = eng.submit(p, 8)
    eng.run_until_complete()
    assert r1.tokens == r2.tokens
    assert r1.metrics.n_generated == 8
    eng.close()


def test_slot_reuse_without_reallocation(params):
    """More requests than slots: retired slots are re-leased, the cache pytree
    is allocated exactly once, and shapes never change."""
    prompts = _prompts([4, 5, 6, 4, 5])
    gens = [3, 4, 2, 5, 3]
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    shape0 = jax.tree.map(lambda l: l.shape, eng.kv.cache)
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    eng.run_until_complete()
    assert eng.kv.alloc_count == 1
    assert jax.tree.map(lambda l: l.shape, eng.kv.cache) == shape0
    assert [r.tokens for r in reqs] == _sequential(params, prompts, gens)
    eng.close()


def test_retired_slot_is_scrubbed(params):
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    eng.submit(_prompts([6])[0], 4)
    eng.run_until_complete()
    # slots free again, and the RETIRED slot's row is back to pristine zeros
    # (idle slots write their own rows during decode — that's fine, admission
    # overwrites the entire leased row — but a retired row must be scrubbed)
    assert eng.scheduler.n_active == 0 and len(eng.scheduler.free) == 2
    assert eng.kv.slot_index(0) == 0
    np.testing.assert_array_equal(np.asarray(eng.kv.cache["k"][:, 0]), 0)
    np.testing.assert_array_equal(np.asarray(eng.kv.cache["v"][:, 0]), 0)
    eng.close()


def test_metrics_reconcile(params):
    prompts = _prompts([4, 6, 5])
    gens = [3, 6, 4]
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    eng.run_until_complete()
    s = eng.stats()
    assert s["completed"] == s["submitted"] == 3
    assert s["tokens_generated"] == sum(r.metrics.n_generated for r in reqs)
    assert s["tokens_generated"] == sum(gens)
    assert s["prefill_tokens"] == sum(len(p) for p in prompts)
    assert all(len(r.tokens) == r.metrics.n_generated for r in reqs)
    assert all(r.metrics.ttft_s is not None and r.metrics.ttft_s >= 0 for r in reqs)
    assert all(r.metrics.finish_s >= r.metrics.first_token_s for r in reqs)
    # every generated token beyond each request's prefill token came from a
    # batched decode step
    assert s["decode_steps"] >= max(gens) - 1
    # the OPQ runtime saw the work: params stay resident -> affinity hits
    assert s["opq"]["issued"] > 0 and s["opq"]["affinity_hits"] > 0
    eng.close()


def test_admission_control(params):
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_queue=2,
                                           max_seq_len=32))
    assert eng.submit(_prompts([4])[0], 40) is None      # over seq budget
    assert eng.submit([], 4) is None                     # empty prompt
    ok1 = eng.submit(_prompts([4])[0], 4)
    ok2 = eng.submit(_prompts([4])[0], 4)
    assert ok1 is not None and ok2 is not None
    assert eng.submit(_prompts([4])[0], 4) is None       # queue full
    with pytest.raises(QueueFull):
        eng.submit(_prompts([4])[0], 4, strict=True)
    assert eng.stats()["rejected"] == 4
    eng.run_until_complete()
    assert eng.stats()["completed"] == 2
    # untracked OPQ dispatch: no step results retained across the run
    assert len(eng.opq._task_futures) == 0
    eng.close()


def test_single_slot_engine_reuses_cleanly(params):
    """n_slots=1 regression: the pristine-row snapshot must be a real copy —
    a full-extent slice aliases the cache buffer, which donation deletes."""
    prompts = _prompts([5, 7])
    gens = [4, 3]
    eng = Engine(CFG, params, EngineConfig(max_slots=1, max_seq_len=16))
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    eng.run_until_complete()
    assert [r.metrics.n_generated for r in reqs] == gens
    assert eng.kv.alloc_count == 1
    eng.close()


def test_engine_rejects_bucket_wider_than_slot_rows(params):
    """A bucket wider than max_seq_len could admit prompts whose fused K/V
    block can't be scattered into the slot rows — rejected at construction."""
    with pytest.raises(ValueError, match="exceeds"):
        Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                         buckets=(64,)))


def test_admission_rejects_prompt_over_largest_bucket(params):
    """Custom buckets capping below max_seq_len must reject at submit(), not
    wedge the scheduler mid-admission after a slot was leased."""
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                           buckets=(8,)))
    assert eng.submit(_prompts([12])[0], 4) is None      # 12 > bucket cap 8
    ok = eng.submit(_prompts([6])[0], 4)
    assert ok is not None
    eng.run_until_complete()
    assert ok.metrics.n_generated == 4
    eng.close()


def test_int8_kv_slot_store(params):
    """int8 KV cache config: the slot store carries per-token scale planes
    and the engine still decodes staggered == sequential."""
    cfg8 = CFG.replace(kv_cache_dtype="int8")
    params8 = init_model(cfg8, jax.random.PRNGKey(0))
    mgr = make_store(cfg8, n_slots=2, max_seq_len=16, backend="contiguous")
    assert mgr.cache["k"].dtype == np.int8
    assert "k_scale" in mgr.cache and "v_scale" in mgr.cache

    prompts = _prompts([4, 6])
    gens = [4, 3]
    eng = Engine(cfg8, params8, EngineConfig(max_slots=2, max_seq_len=16))
    r0 = eng.submit(prompts[0], gens[0])
    eng.step()
    r1 = eng.submit(prompts[1], gens[1])          # staggered join
    eng.run_until_complete()
    staggered = [list(r0.tokens), list(r1.tokens)]
    eng.close()

    eng2 = Engine(cfg8, params8, EngineConfig(max_slots=2, max_seq_len=16))
    seq = []
    for p, g in zip(prompts, gens):
        r = eng2.submit(p, g)
        eng2.run_until_complete()
        seq.append(list(r.tokens))
    eng2.close()
    assert staggered == seq


@pytest.mark.parametrize("family,kv_dtype", [
    ("dense", "bfloat16"), ("dense", "int8"), ("moe", "bfloat16"),
])
def test_fused_seeding_bit_identical_to_replay(params, moe_params, family, kv_dtype):
    """The fused-admission guarantee: seeding a slot from the prefill's K/V
    block produces (a) the bit-identical cache state and (b) the bit-identical
    generated tokens of the PR-1 B=1 prompt-replay seeding — for the float and
    the int8-KV (per-token scales) cache formats, and for MoE (where dropless
    row-isolated prefill routing makes a batched prompt route exactly as the
    one-token-at-a-time replay did) — and both equal decoding the request with
    no engine at all."""
    base, params = (CFG, params) if family == "dense" else (MOE_CFG, moe_params)
    cfg = base.replace(kv_cache_dtype=kv_dtype)
    prompts = _prompts([5, 9, 4])
    gens = [6, 4, 7]
    ecfg = EngineConfig(max_slots=2, max_seq_len=32)
    eng_f = Engine(cfg, params, ecfg)
    eng_r = _ReplaySeededEngine(cfg, params, ecfg)
    reqs_f = [eng_f.submit(p, g) for p, g in zip(prompts, gens)]
    reqs_r = [eng_r.submit(p, g) for p, g in zip(prompts, gens)]
    eng_f._admit()
    eng_r._admit()
    # freshly admitted rows: the batched fused scatter leaves the cache
    # bit-equal to per-slot replay writes (pad tails scrubbed to pristine)
    for name in eng_f.kv.cache:
        np.testing.assert_array_equal(
            np.asarray(eng_f.kv.cache[name]), np.asarray(eng_r.kv.cache[name]),
            err_msg=f"cache leaf {name!r} diverged ({kv_dtype})")
    eng_f.run_until_complete()
    eng_r.run_until_complete()
    toks_f = [list(r.tokens) for r in reqs_f]
    assert toks_f == [list(r.tokens) for r in reqs_r]
    assert toks_f == [_pure_sequential_decode(cfg, params, p, g, 32)
                      for p, g in zip(prompts, gens)]
    eng_f.close()
    eng_r.close()


def test_admission_is_one_forward_per_bucket_no_replay(params):
    """Dispatch-shape audit via OPQ instruction flags: an admission round
    issues exactly ONE prefill instruction per admitted request (each at
    width 1, same-bucket arrivals included) and ZERO replay decodes —
    seeding is O(1) dispatches in prompt length."""
    eng = Engine(CFG, params, EngineConfig(max_slots=4, max_seq_len=32))
    for l, g in ((3, 4), (9, 3), (20, 5)):       # buckets: 16, 16, 32
        eng.submit(_prompts([l])[0], g)
    eng.step()
    flags = eng.stats()["opq"]["flags"]
    assert flags["prefill/16"] == 2              # two prompts, one forward each
    assert flags["prefill/32"] == 1
    eng.run_until_complete()
    s = eng.stats()
    flags = s["opq"]["flags"]
    # the complete run's instruction ledger: per-request prefills and batched
    # decode steps, nothing else — the replay instruction class is extinct
    assert set(flags) == {"prefill/16", "prefill/32", "decode"}
    assert sum(c for f, c in flags.items()
               if f.startswith("prefill/")) == s["prefill_batches"] == 3
    assert flags["decode"] == s["decode_steps"]
    eng.close()


def test_moe_staggered_matches_sequential(moe_params):
    """MoE serving carries the dense bit-identity guarantee now: idle slots
    are masked out of the expert-capacity cumsum at decode and fused prefill
    routes row-isolated, so requests joining/leaving mid-flight decode exactly
    as if served one at a time."""
    prompts = _prompts([5, 9, 4, 7])
    gens = [6, 5, 8, 3]
    eng = Engine(MOE_CFG, moe_params, EngineConfig(max_slots=2, max_seq_len=32))
    reqs = [eng.submit(prompts[0], gens[0])]
    eng.step()                                    # r0 decoding alone
    reqs.append(eng.submit(prompts[1], gens[1]))  # joins mid-flight
    eng.step()
    reqs.append(eng.submit(prompts[2], gens[2]))
    reqs.append(eng.submit(prompts[3], gens[3]))
    eng.run_until_complete()
    staggered = [list(r.tokens) for r in reqs]
    assert staggered == _sequential(moe_params, prompts, gens, cfg=MOE_CFG)
    eng.close()


def test_moe_idle_mask_restores_isolation(moe_params):
    """Teeth for the capacity-masking fix, at the apply_moe level: four
    identical tokens all pick the same experts, so with shared capacity
    ceil(4*topk/E*cf) = 3 the last row's expert traffic is dropped on the
    floor. With its three batchmates masked idle, the survivor routes exactly
    as the first row does alone."""
    from repro.models import moe as MOE
    p = jax.tree.map(lambda l: l[0], moe_params["layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 1, MOE_CFG.d_model), jnp.float32)
    x4 = jnp.broadcast_to(x, (4, 1, MOE_CFG.d_model))
    y_shared, _ = MOE.apply_moe(p, x4, MOE_CFG)
    y_masked, _ = MOE.apply_moe(p, x4, MOE_CFG,
                                active=jnp.asarray([False, False, False, True]))
    y_first, _ = MOE.apply_moe(p, x4, MOE_CFG,
                               active=jnp.asarray([True, False, False, False]))
    # the lone active row routes identically wherever it sits in the batch
    np.testing.assert_array_equal(np.asarray(y_masked[3]), np.asarray(y_first[0]))
    # and shared capacity really was the failure mode being fixed: without the
    # mask, row 3 lost its routed experts to its (identical) batchmates
    assert not np.array_equal(np.asarray(y_shared[3]), np.asarray(y_masked[3]))
    # serving decode is dropless: even with every batchmate ACTIVE and
    # colliding on the same experts (worst case for the old shared capacity
    # of 3), each token routes exactly as it does alone
    y_active, _ = MOE.apply_moe(p, x4, MOE_CFG, active=jnp.ones((4,), bool))
    np.testing.assert_array_equal(np.asarray(y_active[3]), np.asarray(y_first[0]))


def test_bucketing_bounds_prefill_shapes(params):
    """Prompts of many lengths compile at most len(buckets) prefill shapes:
    each admission prefills alone at width 1, so same-step same-bucket
    arrivals run the one (1, bucket) program twice."""
    assert default_buckets(48) == (16, 32, 48)
    assert default_buckets(32) == (16, 32)
    assert bucket_for(5, (16, 32)) == 16 and bucket_for(17, (16, 32)) == 32
    with pytest.raises(ValueError):
        bucket_for(33, (16, 32))
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    for l in (3, 9):                              # both land in the 16-bucket
        eng.submit(_prompts([l])[0], 2)
    eng.step()
    assert eng.stats()["prefill_batches"] == 2    # one width-1 forward each
    eng.run_until_complete()
    eng.close()


# ===========================================================================
# SlotStore protocol: paged KV + recurrent-state backends
# ===========================================================================

def _leaf_rows(cache, slot):
    """Flatten a (possibly nested) cache pytree to {path: slot-row array}."""
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    out = {}
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        out[name] = leaf[slot] if "index" in name else leaf[:, slot]
    return out


def test_make_store_backend_selection():
    assert isinstance(make_store(CFG, 2, 32), ContiguousKVStore)
    assert isinstance(make_store(CFG, 2, 32, backend="paged"), PagedKVStore)
    assert isinstance(make_store(XLSTM_CFG, 2, 32), RecurrentStateStore)
    with pytest.raises(ValueError, match="dense-family"):
        make_store(XLSTM_CFG, 2, 32, backend="paged")
    with pytest.raises(ValueError, match="ssm/hybrid"):
        make_store(CFG, 2, 32, backend="recurrent")
    with pytest.raises(ValueError, match="divide"):
        make_store(CFG, 2, 32, backend="paged", block_size=12)
    with pytest.raises(ValueError, match="unknown cache backend"):
        make_store(CFG, 2, 32, backend="mmap")


def test_kvslotmanager_shim_warns():
    """Direct KVSlotManager use is deprecated but still works (it IS the
    contiguous backend underneath)."""
    with pytest.warns(DeprecationWarning, match="make_store"):
        mgr = KVSlotManager(CFG, n_slots=2, max_seq_len=16)
    assert isinstance(mgr, ContiguousKVStore)
    with warnings.catch_warnings():
        warnings.simplefilter("error")            # the store itself is clean
        make_store(CFG, 2, 16, backend="contiguous")


@pytest.mark.parametrize("family,kv_dtype,block_size", [
    ("dense", "bfloat16", 8), ("dense", "int8", 8), ("moe", "bfloat16", 16),
])
def test_paged_decode_bit_identical_to_contiguous(
        params, moe_params, family, kv_dtype, block_size):
    """The paged-backend contract: the same staggered token stream served
    through block-paged KV produces bit-identical tokens to contiguous rows —
    for float, int8-per-token-scale, and MoE cache formats — and the seeded
    cache contents agree on every valid position."""
    base, p = (CFG, params) if family == "dense" else (MOE_CFG, moe_params)
    cfg = base.replace(kv_cache_dtype=kv_dtype)
    prompts = _prompts([5, 9, 4, 7])
    gens = [6, 5, 8, 3]

    eng_c = Engine(cfg, p, EngineConfig(max_slots=2, max_seq_len=32))
    eng_p = Engine(cfg, p, EngineConfig(max_slots=2, max_seq_len=32,
                                        cache_backend="paged",
                                        block_size=block_size))
    for e in (eng_c, eng_p):
        for pr, g in zip(prompts, gens):
            e.submit(pr, g)
        e._admit()
    # freshly admitted rows agree bit-for-bit on every valid position
    view_c = eng_c.store.gather_view()
    view_p = eng_p.store.gather_view()
    for slot, req in eng_c.scheduler.active.items():
        n = len(req.prompt)
        for name in ("k", "v", "k_scale", "v_scale"):
            if name not in view_c:
                continue
            np.testing.assert_array_equal(
                np.asarray(view_c[name][:, slot, :n]),
                np.asarray(view_p[name][:, slot, :n]),
                err_msg=f"seeded leaf {name!r} diverged (slot {slot})")

    toks_c = _staggered(p, prompts, gens, cfg=cfg)
    toks_p = _staggered(p, prompts, gens, cfg=cfg, cache_backend="paged",
                        block_size=block_size)
    assert toks_c == toks_p                       # bit-identical, not allclose
    eng_c.close()
    eng_p.close()


def test_paged_fused_seeding_bit_identical_to_replay(params):
    """The fused==replay guarantee holds per backend: a paged store seeded by
    the B=1 replay reference path (write_slot through the block tables)
    generates the same tokens as fused admission."""
    prompts = _prompts([5, 9, 4])
    gens = [6, 4, 7]
    ecfg = EngineConfig(max_slots=2, max_seq_len=32, cache_backend="paged",
                        block_size=8)
    eng_f = Engine(CFG, params, ecfg)
    eng_r = _ReplaySeededEngine(CFG, params, ecfg)
    reqs_f = [eng_f.submit(p, g) for p, g in zip(prompts, gens)]
    reqs_r = [eng_r.submit(p, g) for p, g in zip(prompts, gens)]
    eng_f.run_until_complete()
    eng_r.run_until_complete()
    assert [r.tokens for r in reqs_f] == [r.tokens for r in reqs_r]
    eng_f.close()
    eng_r.close()


def test_paged_pool_exhaustion_is_backpressure_not_corruption(params):
    """A block pool sized for 2 concurrent requests with 4 slots free: the
    scheduler defers the overflow at the queue head (FIFO intact) until
    retires free blocks — every request completes with tokens bit-identical
    to the contiguous backend, and the pool drains back to fully free."""
    prompts = _prompts([8, 8, 8, 8])
    # 2 blocks per request (8 prompt + 8 gen, block 8); pool holds 4 blocks
    eng = Engine(CFG, params, EngineConfig(max_slots=4, max_seq_len=16,
                                           cache_backend="paged",
                                           block_size=8, n_blocks=5))
    reqs = [eng.submit(p, 8) for p in prompts]
    eng.step()
    s = eng.stats()
    assert s["cache"]["blocks_used"] == 4 and s["cache"]["blocks_free"] == 0
    assert eng.scheduler.n_active == 2            # two admitted, two held back
    assert s["admissions_deferred"] >= 1
    eng.run_until_complete()
    s = eng.stats()
    assert s["completed"] == 4
    assert s["cache"]["blocks_free"] == s["cache"]["blocks_total"] == 4

    eng_c = Engine(CFG, params, EngineConfig(max_slots=4, max_seq_len=16))
    reqs_c = [eng_c.submit(p, 8) for p in prompts]
    eng_c.run_until_complete()
    assert [r.tokens for r in reqs] == [r.tokens for r in reqs_c]
    eng.close()
    eng_c.close()


def test_paged_pool_exhaustion_evicts_cached_prefixes_before_refusal(params):
    """Pool exhaustion while cached prefixes sit unreferenced: the lease
    must LRU-evict them to unblock admission instead of refusing. With the
    pool sized so a retired request's cached prompt block is the only spare
    capacity, a non-matching follow-up request admits only if eviction
    fires — without it, this exact traffic is the zero-active admission
    livelock the engine raises on."""
    pa, pb = _prompts([8, 8])
    # 2 usable blocks of 8; each request needs 2 (8 prompt + 8 gen). After A
    # retires, its prompt block stays CACHED -> only 1 block is free.
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=16,
                                           cache_backend="paged",
                                           block_size=8, n_blocks=3,
                                           prefix_cache=True))
    ra = eng.submit(pa, 8, strict=True)
    eng.run_until_complete()
    ms = eng.store.memory_stats()
    assert ms["prefix_cached_blocks"] == 1 and ms["blocks_free"] == 1
    # B shares no prefix with A: it needs 2 fresh blocks RIGHT NOW, and the
    # router-facing signal must already count the evictable cached block
    assert eng.lease_headroom(8, 8)
    rb = eng.submit(pb, 8, strict=True)
    eng.run_until_complete()                      # no livelock, no deferral
    s = eng.stats()
    assert s["completed"] == 2
    assert s["admissions_deferred"] == 0
    assert eng.store.prefix_evictions == 1        # the cached block made room

    eng_c = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=16))
    toks_c = []
    for p in (pa, pb):
        r = eng_c.submit(p, 8, strict=True)
        eng_c.run_until_complete()
        toks_c.append(r.tokens)
    assert [ra.tokens, rb.tokens] == toks_c       # eviction never skews bits
    eng.close()
    eng_c.close()


def test_paged_request_that_can_never_fit_is_rejected_not_livelocked(params):
    """A request needing more blocks than the whole pool holds must bounce at
    submit() — deferring it would park it at the queue head forever, spinning
    run_until_complete and starving everything behind it."""
    # pool: 2 usable blocks of 8 -> 16 tokens total; request needs 24
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                           cache_backend="paged",
                                           block_size=8, n_blocks=3))
    assert eng.submit(_prompts([16])[0], 8) is None
    with pytest.raises(QueueFull):
        eng.submit(_prompts([16])[0], 8, strict=True)
    assert eng.stats()["rejected"] == 2
    # a fitting request behind the rejection still serves normally
    ok = eng.submit(_prompts([8])[0], 8)
    eng.run_until_complete()
    assert ok.metrics.n_generated == 8
    eng.close()


def test_ssm_staggered_matches_sequential(xlstm_params):
    """The headline invariant, extended to the recurrent family: xlstm
    requests joining/leaving the in-flight batch mid-decode produce exactly
    the tokens they would produce served one at a time."""
    prompts = _prompts([5, 9, 4, 7], cfg=XLSTM_CFG)
    gens = [6, 5, 8, 3]
    staggered = _staggered(xlstm_params, prompts, gens, cfg=XLSTM_CFG)
    sequential = _sequential(xlstm_params, prompts, gens, cfg=XLSTM_CFG)
    assert staggered == sequential               # bit-identical, not allclose


def test_recurrent_slot_reset_has_teeth(xlstm_params):
    """A retired xlstm slot never leaks state into the next lease: the row is
    restored to the pristine pattern (incl. the non-zero mLSTM/sLSTM
    stabilizer sentinels) immediately at retire, and a request served through
    the reused slot decodes exactly as on a fresh engine."""
    prompts = _prompts([6, 9], cfg=XLSTM_CFG)
    eng = Engine(XLSTM_CFG, xlstm_params,
                 EngineConfig(max_slots=1, max_seq_len=32))
    r0 = eng.submit(prompts[0], 5)
    eng.run_until_complete()
    assert r0.metrics.n_generated == 5
    # slot 0's row is bit-equal to a never-used store's (M_INIT / 1e-6 /
    # -1e30 sentinels included — zeros would NOT be pristine here)
    fresh = make_store(XLSTM_CFG, 1, 32, backend="recurrent")
    got, want = _leaf_rows(eng.store.cache, 0), _leaf_rows(fresh.cache, 0)
    for name in want:
        np.testing.assert_array_equal(
            np.asarray(got[name]), np.asarray(want[name]),
            err_msg=f"retired slot leaf {name} not pristine")
    # the re-leased slot serves exactly like a fresh engine
    r1 = eng.submit(prompts[1], 5)
    eng.run_until_complete()
    eng2 = Engine(XLSTM_CFG, xlstm_params,
                  EngineConfig(max_slots=1, max_seq_len=32))
    r1_fresh = eng2.submit(prompts[1], 5)
    eng2.run_until_complete()
    assert r1.tokens == r1_fresh.tokens
    eng.close()
    eng2.close()


def test_hybrid_serves_end_to_end():
    """zamba2 (mamba conv/ssm state + shared-attention KV rows) serves through
    the same engine via the recurrent backend, staggered == sequential."""
    hp = init_model(HYBRID_CFG, jax.random.PRNGKey(3))
    prompts = _prompts([5, 9, 4], cfg=HYBRID_CFG)
    gens = [4, 3, 5]
    staggered = _staggered(hp, prompts, gens, cfg=HYBRID_CFG)
    sequential = _sequential(hp, prompts, gens, cfg=HYBRID_CFG)
    assert staggered == sequential


# ===========================================================================
# block-native paged decode + chunked long-prompt admission
# ===========================================================================

@pytest.mark.parametrize("family,kv_dtype", [
    ("dense", "bfloat16"), ("dense", "int8"), ("moe", "bfloat16"),
])
def test_paged_native_decode_bit_identical_to_bridge(
        params, moe_params, family, kv_dtype):
    """The block-native contract: decode attending over the pool through the
    block tables (no gather view) produces bit-identical tokens to the
    gather-bridge path — for float, int8-per-token-scale, and MoE cache
    formats — the bridge stays available as the reference oracle, and native
    mode's peak decode working set is the pool alone
    (memory_stats decode_view_bytes == 0)."""
    base, p = (CFG, params) if family == "dense" else (MOE_CFG, moe_params)
    cfg = base.replace(kv_cache_dtype=kv_dtype)
    prompts = _prompts([5, 9, 4, 7])
    gens = [6, 5, 8, 3]
    toks_b = _staggered(p, prompts, gens, cfg=cfg, cache_backend="paged",
                        block_size=8)
    toks_n = _staggered(p, prompts, gens, cfg=cfg, cache_backend="paged",
                        block_size=8, paged_native=True)
    assert toks_n == toks_b                       # bit-identical, not allclose

    # working-set accounting: bridge reports the transient view, native 0
    eng_b = Engine(cfg, p, EngineConfig(max_slots=2, max_seq_len=32,
                                        cache_backend="paged", block_size=8))
    eng_n = Engine(cfg, p, EngineConfig(max_slots=2, max_seq_len=32,
                                        cache_backend="paged", block_size=8,
                                        paged_native=True))
    for e in (eng_b, eng_n):
        e.submit(prompts[0], gens[0])
        e.step()
    ms_b, ms_n = eng_b.stats()["cache"], eng_n.stats()["cache"]
    assert ms_b["decode_view_bytes"] > 0
    assert ms_n["decode_view_bytes"] == 0
    assert ms_n["bytes"] == ms_b["bytes"]         # same resident pool
    # seeded + decoded cache contents agree on every valid position
    view_b, view_n = eng_b.store.gather_view(), eng_n.store.gather_view()
    for slot, req in eng_b.scheduler.active.items():
        n = eng_b.store.slot_index(slot)
        for name in ("k", "v", "k_scale", "v_scale"):
            if name not in view_b:
                continue
            np.testing.assert_array_equal(
                np.asarray(view_b[name][:, slot, :n]),
                np.asarray(view_n[name][:, slot, :n]),
                err_msg=f"native cache leaf {name!r} diverged ({kv_dtype})")
    eng_b.close()
    eng_n.close()


def test_paged_native_requires_paged_backend(params):
    with pytest.raises(ValueError, match="paged"):
        Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                         paged_native=True))
    with pytest.raises(ValueError, match="paged_native"):
        Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                         cache_backend="paged",
                                         paged_kernel=True))
    with pytest.raises(ValueError, match="paged"):
        make_store(CFG, 2, 32, backend="contiguous", native=True)


def test_paged_kernel_refuses_int8_kv(params):
    """The Pallas kernel reads a float pool: an int8 KV cache is refused at
    construction instead of quietly falling back to the jnp contraction."""
    with pytest.raises(ValueError, match="int8"):
        Engine(CFG.replace(kv_cache_dtype="int8"), params,
               EngineConfig(max_slots=2, max_seq_len=32,
                            cache_backend="paged", paged_native=True,
                            paged_kernel=True))


def test_engine_runs_on_one_device(params, mesh):
    """An Engine takes the ambient one-device mesh and pins its params and
    cache there; a mesh of several devices is refused."""
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32))
    assert eng.device == mesh.devices.flat[0]
    placed = {d for leaf in jax.tree.leaves((eng.params, eng.store.cache))
              for d in leaf.devices()}
    assert placed == {eng.device}
    assert eng.opq.devices == [eng.device]
    eng.close()
    wide = types.SimpleNamespace(size=2, shape={"data": 2})
    with pytest.raises(ValueError, match="one device"):
        Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32),
               mesh=wide)


def test_compile_cache_dir_follows_the_environment(monkeypatch):
    from repro.core.compile_cache import CHECKOUT_CACHE, ENV, compile_cache_dir
    monkeypatch.setenv(ENV, "/elsewhere/cache")
    assert compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv(ENV)
    assert compile_cache_dir() == str(CHECKOUT_CACHE)
    assert CHECKOUT_CACHE.parent == Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("family,kv_dtype", [
    ("dense", "bfloat16"), ("dense", "int8"), ("moe", "bfloat16"),
])
def test_chunked_prefill_bit_identical_to_fused(params, moe_params, family,
                                                kv_dtype):
    """The chunked-admission guarantee: prompts admitted through the chunked
    prefill scan (fixed-width chunks attending over everything already
    written) produce the bit-identical first token, seeded cache, and decode
    continuation of the single-shot fused prefill — for float and int8-KV
    cache formats, and for MoE (row-isolated dropless routing makes a
    token's expert assignment independent of which chunk carried it)."""
    base, p = (CFG, params) if family == "dense" else (MOE_CFG, moe_params)
    cfg = base.replace(kv_cache_dtype=kv_dtype)
    prompts = _prompts([5, 9, 4, 20])             # buckets 16, 16, 16, 32
    gens = [6, 5, 8, 3]
    ecfg_f = EngineConfig(max_slots=2, max_seq_len=32)
    ecfg_c = EngineConfig(max_slots=2, max_seq_len=32, prefill_chunk=8)
    eng_f = Engine(cfg, p, ecfg_f)
    eng_c = Engine(cfg, p, ecfg_c)
    reqs_f = [eng_f.submit(pr, g) for pr, g in zip(prompts, gens)]
    reqs_c = [eng_c.submit(pr, g) for pr, g in zip(prompts, gens)]
    eng_f._admit()
    eng_c._admit()
    # freshly admitted rows bit-equal on every leaf (pad tails included)
    for name in eng_f.kv.cache:
        np.testing.assert_array_equal(
            np.asarray(eng_f.kv.cache[name]), np.asarray(eng_c.kv.cache[name]),
            err_msg=f"chunk-seeded cache leaf {name!r} diverged ({kv_dtype})")
    eng_f.run_until_complete()
    eng_c.run_until_complete()
    assert ([list(r.tokens) for r in reqs_c]
            == [list(r.tokens) for r in reqs_f])  # bit-identical, not allclose
    # the audit trail shows chunked instructions carried the wide buckets
    flags = eng_c.stats()["opq"]["flags"]
    assert any(f.startswith("prefill_chunked/") for f in flags)
    eng_f.close()
    eng_c.close()


def test_long_prompt_admits_via_chunking(params):
    """The admission cap lifts: a prompt wider than every fused bucket is
    rejected by the single-shot engine but admits through chunk-multiple
    buckets when prefill_chunk is set — and decodes exactly the tokens of
    serving it alone through an unconstrained engine."""
    long_prompt = _prompts([20])[0]
    eng_nochunk = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                                   buckets=(8,)))
    assert eng_nochunk.submit(long_prompt, 5) is None    # 20 > max bucket 8
    eng_nochunk.close()

    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                           buckets=(8,), prefill_chunk=8))
    r = eng.submit(long_prompt, 5)
    assert r is not None                                  # > max bucket: admits
    eng.run_until_complete()
    assert r.tokens == _pure_sequential_decode(CFG, params, long_prompt, 5, 32)
    eng.close()

    # chunked + paged-native compose: the long prompt seeds block layout
    eng_p = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                             buckets=(8,), prefill_chunk=8,
                                             cache_backend="paged",
                                             block_size=8, paged_native=True))
    rp = eng_p.submit(long_prompt, 5)
    eng_p.run_until_complete()
    assert rp.tokens == r.tokens
    assert eng_p.stats()["cache"]["decode_view_bytes"] == 0
    eng_p.close()


def test_chunked_prefill_rejects_bad_config(params):
    with pytest.raises(ValueError, match="prefill_chunk"):
        Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                         prefill_chunk=64))   # > max_seq_len
    with pytest.raises(ValueError, match="recurrent"):
        xp = init_model(XLSTM_CFG, jax.random.PRNGKey(2))
        Engine(XLSTM_CFG, xp, EngineConfig(max_slots=2, max_seq_len=32,
                                           prefill_chunk=8))
    with pytest.raises(ValueError, match="mrope"):
        Engine(CFG.replace(rope_kind="mrope"), params,
               EngineConfig(max_slots=2, max_seq_len=32, prefill_chunk=8))


def test_paged_lease_batches_table_uploads(params):
    """Regression (store.py lease): leases mutate only the host table mirror;
    the device copy uploads ONCE per admission round when decode next needs
    it — not once per lease."""
    store = make_store(CFG, 4, 32, backend="paged", block_size=8)
    assert store.table_uploads == 0
    for slot in range(3):
        assert store.lease(slot, 8, 8)
    assert store.table_uploads == 0               # three leases, zero uploads
    store.decode_cache()
    assert store.table_uploads == 1               # one batched upload
    store.decode_cache()
    assert store.table_uploads == 1               # clean: no re-upload
    assert store.lease(3, 8, 8)
    store.gather_view()
    assert store.table_uploads == 2
    # the device copy the sync produced matches the host mirror
    np.testing.assert_array_equal(np.asarray(store.cache["tables"]),
                                  store._tables)

    # engine-level: a 3-request admission round costs one upload, and a
    # full serving run stays at one upload per admission round
    eng = Engine(CFG, params, EngineConfig(max_slots=4, max_seq_len=32,
                                           cache_backend="paged",
                                           block_size=8))
    for pr in _prompts([5, 9, 4]):
        eng.submit(pr, 4)
    eng.step()
    assert eng.store.table_uploads == 1
    eng.run_until_complete()
    assert eng.store.table_uploads == 1           # no further admission rounds
    eng.close()


def test_engine_zero_progress_raises_immediately(params):
    """Satellite regression (engine.py run_until_complete): a queue head
    deferred by the store lease while zero slots are active can never make
    progress — the engine must raise a diagnostic immediately instead of
    spinning max_steps no-op iterations."""
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                           cache_backend="paged",
                                           block_size=8))
    # simulate fits/lease drift: fits admits at submit, lease then refuses
    eng.store.lease = lambda *a, **kw: False
    req = eng.submit(_prompts([8])[0], 4)
    assert req is not None
    with pytest.raises(RuntimeError, match="livelock") as ei:
        eng.run_until_complete()
    # the diagnostic names the stuck request and the pool state
    assert f"request {req.id}" in str(ei.value)
    assert "blocks_free" in str(ei.value)
    eng.close()


def test_paged_fits_boundary_pool_smaller_than_slot_table(params):
    """fits() clamps against min(n_blocks - 1, blocks_per_slot): with a pool
    SMALLER than one slot's table, a request needing exactly the whole pool
    (n_blocks - 1 blocks) must admit, one block more must bounce at submit —
    the line that keeps submit-reject and lease-defer from drifting into the
    livelock fits() exists to prevent."""
    # blocks_per_slot = 32/8 = 4, pool = 3 usable blocks < 4
    store = make_store(CFG, 2, 32, backend="paged", block_size=8, n_blocks=4)
    assert store.fits(16, 8)                      # 3 blocks == n_blocks - 1
    assert store.lease(0, 16, 8)                  # and lease agrees
    store.reset(0)
    assert not store.fits(17, 8)                  # 4 blocks > pool: reject
    assert not store.fits(32, 0)                  # whole table, pool too small

    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                           cache_backend="paged",
                                           block_size=8, n_blocks=4))
    assert eng.submit(_prompts([17])[0], 8) is None       # can never lease
    ok = eng.submit(_prompts([16])[0], 8)                 # exactly the pool
    assert ok is not None
    eng.run_until_complete()                      # completes, no livelock
    assert ok.metrics.n_generated == 8
    eng.close()


def test_paged_fits_boundary_table_caps_below_pool():
    """The other side of the clamp: a pool larger than one slot's table must
    still reject requests wider than the table (they could never be mapped),
    even with plenty of free blocks."""
    # blocks_per_slot = 2, pool = 8 usable blocks
    store = make_store(CFG, 2, 16, backend="paged", block_size=8, n_blocks=9)
    assert store.fits(8, 8)                       # 2 blocks == table width
    assert not store.fits(16, 8)                  # 3 blocks > table width
    assert store.lease(0, 8, 8)
    assert not store.lease(1, 16, 8)              # lease agrees with fits


try:
    from hypothesis import given, settings as hyp_settings, strategies as hyp_st
except ImportError:                                    # clean container
    from _hypothesis_fallback import (
        given, settings as hyp_settings, st as hyp_st)


@hyp_settings(max_examples=5, deadline=None)   # each example builds 14 stores
@given(hyp_st.integers(min_value=0, max_value=2**31 - 1))
def test_pristine_equals_init_cache_every_family_leaf(seed):
    """Property: ``pristine_value``/``_PRISTINE`` (store.py) is bit-equal to
    ``models/serve.py init_cache``'s empty fill for EVERY leaf of EVERY
    servable family's store — and a slot retired after arbitrary payload
    writes is restored to exactly that pattern, including the paged backend's
    block scrub. Guards the two definitions of "empty" against drift."""
    from repro.serving.store import pristine_value

    rng = np.random.default_rng(seed)
    cases = [
        (CFG, "contiguous"), (CFG.replace(kv_cache_dtype="int8"), "contiguous"),
        (MOE_CFG, "contiguous"), (CFG, "paged"),
        (CFG.replace(kv_cache_dtype="int8"), "paged"),
        (XLSTM_CFG, "recurrent"), (HYBRID_CFG, "recurrent"),
    ]
    for cfg, backend in cases:
        store = make_store(cfg, 2, 16, backend=backend, block_size=8)
        fresh = jax.tree_util.tree_flatten_with_path(store.cache)[0]
        # 1) a fresh alloc is the pristine pattern everywhere
        for path, leaf in fresh:
            name = _leaf_name_str(path)
            if name == "tables":
                continue
            np.testing.assert_array_equal(
                np.asarray(leaf),
                np.full(leaf.shape, pristine_value(name), leaf.dtype),
                err_msg=f"{cfg.family}/{backend} init leaf {name!r} is not "
                        f"the pristine_value fill")
        # 2) write a random payload into slot 0, retire, compare to fresh
        store.lease(0, 8, 8)

        def junk_row(path, l):
            name = _leaf_name_str(path)
            if name in ("index", "tables"):
                return jnp.zeros((1,), jnp.int32)      # ignored by write_slot
            return jnp.asarray(rng.integers(1, 5, (l.shape[0], 1) + l.shape[2:])
                               .astype(l.dtype))

        src = jax.tree_util.tree_map_with_path(junk_row, store.cache)
        store.write_slot(0, src, n_valid=8)
        store.reset(0)
        ref = make_store(cfg, 2, 16, backend=backend, block_size=8)
        got = jax.tree_util.tree_flatten_with_path(store.cache)[0]
        want = jax.tree_util.tree_flatten_with_path(ref.cache)[0]
        for (path, g), (_, w) in zip(got, want):
            name = _leaf_name_str(path)
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w),
                err_msg=f"{cfg.family}/{backend} leaf {name!r} not pristine "
                        f"after retire (seed {seed})")


def _leaf_name_str(path) -> str:
    for p in reversed(path):
        key = getattr(p, "key", getattr(p, "name", ""))
        if key:
            return str(key)
    return ""


def test_memory_stats_surface(params):
    """memory_stats flows from the store through engine.stats() to the
    human-readable report line."""
    eng = Engine(CFG, params, EngineConfig(max_slots=2, max_seq_len=32,
                                           cache_backend="paged",
                                           block_size=8))
    eng.submit(_prompts([6])[0], 4)
    eng.step()
    ms = eng.stats()["cache"]
    assert ms["backend"] == "paged" and ms["blocks_used"] > 0
    assert ms["bytes"] == eng.store.nbytes() > 0
    line = format_memory_stats(ms)
    assert "paged" in line and "blocks" in line
    eng.run_until_complete()
    assert eng.stats()["cache"]["blocks_used"] == 0
    eng.close()
    contiguous = format_memory_stats(make_store(CFG, 2, 32).memory_stats())
    assert "contiguous" in contiguous