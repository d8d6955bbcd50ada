"""The traffic generator is a pure function of (mix, seed), keeps the mix's
bounds, and gives every seed the same sizes in the same order."""

import pytest

from benchmark import traffic_gen

MIX = traffic_gen.load_mix("chat_c64")
SEEDS = [0, 7, 2**31 + 11, 3_000_000_019]


def draw(seed, n):
    src = traffic_gen.RequestSource(MIX, seed, 163840)
    return [src.next() for _ in range(n)]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(seed):
    assert draw(seed, 40) == draw(seed, 40)


@pytest.mark.parametrize("seed", SEEDS)
def test_bounds_and_ids(seed):
    p, o = MIX["prompt_tokens"], MIX["output_tokens"]
    for ids, n_out in draw(seed, 300):
        assert p["min"] <= len(ids) <= p["max"]
        assert o["min"] <= n_out <= o["max"]
        assert min(ids) >= 0 and max(ids) < 163840


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_every_seed_gets_the_same_sizes_in_the_same_order(seed):
    n = MIX["distinct_sizes"]
    sizes = traffic_gen.request_sizes(MIX)
    a = draw(SEEDS[0], 2 * n)
    b = draw(seed, 2 * n)
    sa = [(len(i), o) for i, o in a]
    assert sa == [(len(i), o) for i, o in b]
    # each pass sends the whole set once, the second in another order
    assert sorted(sa[:n]) == sorted(sa[n:]) == sorted(sizes) and sa[:n] != sa[n:]
    # the ids are the seed's own
    assert a[0][0][:8] != b[0][0][:8]


def test_sizes_follow_the_mix():
    sizes = traffic_gen.request_sizes(MIX)
    prompts = sorted(p for p, _ in sizes)
    outs = sorted(o for _, o in sizes)
    assert abs(prompts[len(prompts) // 2] - 256) <= 8 and len(sizes) == 64
    assert abs(outs[len(outs) // 2] - 64) <= 2
    assert prompts[0] >= 64 and prompts[-1] == 1024
    assert outs[0] >= 16 and outs[-1] == 192
    # a longest context fits a slot: 20 pages of 64 tokens
    assert max(p + o for p, o in sizes) <= 20 * 64


def test_quantiles_hand_worked():
    # median 100, sigma 0: every quantile is the median
    assert list(traffic_gen.lognormal_quantiles(4, 100, 0.0, 1, 1000)) == [100] * 4
    # two quantiles at z = -0.6745, +0.6745 (the quartiles), sigma 1
    q = traffic_gen.lognormal_quantiles(2, 100, 1.0, 1, 1000)
    assert list(q) == [51, 196]
