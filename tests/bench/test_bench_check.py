"""The correctness check's counts and the statistics the metric readers
share: a refused request is lost and reads as an infinite time to the first
token, never as a missing one."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _bench_path  # noqa: F401
from smoke_sizes import smoke_config
from bench import harness, stats
from bench.references import moe


def _rec(phase="window", rejected=False, tokens=(), ts=(), due=0.0):
    return harness.Record(phase=phase, due_s=due,
                          prompt=np.arange(8, dtype=np.int32),
                          max_new_tokens=4, rejected=rejected,
                          tokens=list(tokens), token_ts=list(ts))


def test_refused_and_silent_window_requests_are_lost():
    recs = [_rec(rejected=True), _rec(), _rec(tokens=[1], ts=[0.5]),
            _rec(phase="lead", rejected=True)]
    checks, scored = harness.check(smoke_config("dense"), 1, recs)
    assert checks["lost"] == {"value": 2, "limit": 0}
    assert scored == [] and not harness.passed(checks)


def test_percentile_keeps_an_infinite_value():
    ttft = [stats.ttft_s(r) for r in
            [_rec(rejected=True)] + [_rec(tokens=[1], ts=[0.1 * i])
                                     for i in range(1, 20)]]
    # one refusal in twenty lies in the top 5%: the tail is infinite
    assert math.isinf(stats.percentile(ttft, 95))
    assert stats.percentile(ttft, 90) == pytest.approx(1.9)
    assert stats.percentile(ttft, 50) == pytest.approx(1.1)
    assert stats.percentile([], 50) is None


def test_router_margin_is_the_kth_over_the_next_logit():
    c = smoke_config("moe")
    rng = np.random.default_rng(0)
    D, E, F = c["hidden_size"], c["n_routed_experts"], \
        c["moe_intermediate_size"]
    lp = {"moe": {"router": rng.normal(size=(D, E)).astype(np.float32),
                  "wi": np.zeros((E, D, F), np.float32),
                  "wg": np.zeros((E, D, F), np.float32),
                  "wo": np.zeros((E, F, D), np.float32),
                  "shared": {"wi": np.zeros((D, F), np.float32),
                             "wg": np.zeros((D, F), np.float32),
                             "wo": np.zeros((F, D), np.float32)}}}
    h = rng.normal(size=(5, D)).astype(np.float32)
    _, margin = moe.moe_ffn(jax.tree.map(jnp.asarray, lp), jnp.asarray(h), c)
    s = np.sort(h @ lp["moe"]["router"], -1)[:, ::-1]
    k = c["num_experts_per_tok"]
    np.testing.assert_allclose(np.asarray(margin), s[:, k - 1] - s[:, k],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_score_rows_per_served_token(family):
    from bench import common
    c = smoke_config(family)
    seqs = [np.arange(1, 20, dtype=np.int32), np.arange(3, 12,
                                                        dtype=np.int32)]
    rows = common.reference(family).score(c, 3, seqs, [12, 5])
    assert [len(r["gap"]) for r in rows] == [7, 4]
    assert all(np.all(r["gap"] >= 0) for r in rows)
    if family == "moe":
        assert [len(r["router_margin"]) for r in rows] == [7, 4]
        assert all(np.all(r["router_margin"] >= 0) for r in rows)
    else:
        assert all("router_margin" not in r for r in rows)
