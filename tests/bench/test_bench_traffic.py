"""The traffic generator: repeatable by seed, the stated distributions, and
the same work for every seed."""
import numpy as np
import pytest

import _bench_path  # noqa: F401
from bench import common, traffic


@pytest.mark.parametrize("mix", ["chat", "longin"])
def test_same_seed_same_requests(mix):
    a = traffic.requests(common.mix(mix), 3.0, 20, 49152, 2 ** 40 + 11, 5, 60)
    b = traffic.requests(common.mix(mix), 3.0, 20, 49152, 2 ** 40 + 11, 5, 60)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.phase, x.due_s, x.max_new_tokens) == \
            (y.phase, y.due_s, y.max_new_tokens)
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", ["chat", "longin"])
def test_seeds_differ_in_order_not_in_work(mix):
    a = traffic.requests(common.mix(mix), 3.0, 20, 49152, 5, 5, 60)
    b = traffic.requests(common.mix(mix), 3.0, 20, 49152, 6, 5, 60)
    for phase in ("lead", "window", "tail"):
        pa = [x for x in a if x.phase == phase]
        pb = [x for x in b if x.phase == phase]
        assert sorted(len(x.prompt) for x in pa) == \
            sorted(len(x.prompt) for x in pb)
        assert sorted(x.max_new_tokens for x in pa) == \
            sorted(x.max_new_tokens for x in pb)
        # every gap between arrivals is one of the phase's stratified gaps
        g = traffic.gaps(3.0, len(pa))
        for reqs in (pa, pb):
            d = np.diff([x.due_s for x in reqs])
            assert np.all(np.min(np.abs(d[:, None] - g[None, :]), 1) < 1e-9)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_window_count_span_and_phases():
    reqs = traffic.requests(common.mix("chat"), 4.0, 30, 49152, 1, 7.5, 60)
    win = [x for x in reqs if x.phase == "window"]
    assert len(win) == 120
    assert all(7.5 <= x.due_s < 37.5 for x in win)
    assert [x.due_s for x in reqs] == sorted(x.due_s for x in reqs)


def test_chat_lengths_follow_the_mix():
    m = common.mix("chat")
    p = traffic.lengths(m["prompt_len"], 2000)
    o = traffic.lengths(m["output_len"], 2000)
    assert p.min() >= 128 and p.max() <= 3584
    assert o.min() >= 16 and o.max() <= 512
    assert abs(np.median(p) - 512) <= 2
    assert abs(np.median(o) - 128) <= 2
    # lognormal: the share above the median times e**sigma is 1 - Phi(1)
    assert abs(np.mean(p > 512 * np.exp(0.8)) - 0.1587) < 0.01


def test_longin_lengths_follow_the_mix():
    m = common.mix("longin")
    o = traffic.lengths(m["output_len"], 4900)
    assert o.min() == 16 and o.max() == 64
    counts = np.bincount(o)[16:65]
    assert counts.max() - counts.min() <= 1       # uniform over 16..64
    p = traffic.lengths(m["prompt_len"], 2000)
    assert p.min() >= 512 and p.max() <= 2048
    assert abs(np.median(p) - 1024) <= 2


def test_gaps_are_exponential_at_the_rate():
    g = traffic.gaps(5.0, 4000)
    assert abs(g.sum() - 800.0) < 1e-6
    assert abs(np.median(g) - np.log(2) / 5.0) < 1e-3


def test_every_block_takes_one_value_from_each_stratum():
    rng = np.random.default_rng(3)
    v = np.arange(64.0)
    out = traffic.arrange(v, rng, block=8)
    assert sorted(out) == list(v)
    for b in range(8):
        assert sorted(out[8 * b:8 * b + 8] // 8) == list(range(8))
    assert not np.array_equal(out, traffic.arrange(v, rng, block=8))
