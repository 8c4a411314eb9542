"""Traffic is a pure function of its file and the seed; every seed sends
the same multiset of sizes, block by block."""

import math
from collections import Counter

import pytest

from chipbench import registry, traffic
from chipbench.stats import (
    percentile, spread, spread_leaving_one_out,
)

MIXES = ["chat-c64", "chat-c64-think", "chat-c128", "rehearsal"]


@pytest.mark.parametrize("mix", MIXES)
def test_pure_function_of_file_and_seed(mix):
    spec = traffic.load(mix)
    a = traffic.requests(spec, 2_147_483_999, 300)
    b = traffic.requests(spec, 2_147_483_999, 300)
    c = traffic.requests(spec, 7, 300)
    assert a == b and a != c
    assert traffic.prompt_text(3, 5, 40) == traffic.prompt_text(3, 5, 40)
    assert traffic.prompt_text(3, 5, 40) != traffic.prompt_text(3, 6, 40)
    assert len(traffic.prompt_text(2**31 + 11, 0, 123).encode()) == 123


@pytest.mark.parametrize("mix", MIXES)
def test_two_seeds_send_the_same_multiset_block_by_block(mix):
    spec = traffic.load(mix)
    block = spec["block"]
    a = traffic.requests(spec, 1, 3 * block)
    b = traffic.requests(spec, 2**31 + 5, 3 * block)
    whole = lambda r: (r["prompt_tokens"], r["output_tokens"], r["think_s"])
    first = Counter(map(whole, a[:block]))
    for i in range(3):
        sl = slice(i * block, (i + 1) * block)
        assert Counter(map(whole, a[sl])) == first
        assert Counter(map(whole, b[sl])) == first
    assert [r["prompt_tokens"] for r in a] != [r["prompt_tokens"] for r in b]
    # the pairing is drawn once, and at random: not sorted against each other
    by_prompt = sorted(a[:block], key=lambda r: r["prompt_tokens"])
    outs = [r["output_tokens"] for r in by_prompt]
    assert outs != sorted(outs) and outs != sorted(outs, reverse=True)


def test_lengths_keep_to_their_range_and_mean():
    spec = traffic.load("chat-c64-think")
    reqs = traffic.requests(spec, 11, 640)
    p = [r["prompt_tokens"] for r in reqs]
    o = [r["output_tokens"] for r in reqs]
    assert 128 <= min(p) and max(p) <= 2048
    assert 32 <= min(o) and max(o) <= 384
    lu = registry.load("distributions", "log_uniform")
    assert abs(sum(p) / len(p) - lu.mean(low=128, high=2048)) < 10
    think = [r["think_s"] for r in reqs]
    assert abs(sum(think) / len(think) - 4.0) < 0.2
    assert max(p) + max(o) + 64 < 4096  # fits --max-model-len


def test_distributions():
    lu = registry.load("distributions", "log_uniform")
    assert lu.quantile(0.0, low=64, high=1024) == 64
    assert math.isclose(lu.quantile(1.0, low=64, high=1024), 1024)
    assert math.isclose(lu.quantile(0.5, low=64, high=1024), 256)
    ex = registry.load("distributions", "exponential")
    assert math.isclose(ex.quantile(1 - math.exp(-1), mean=4.0), 4.0)
    assert registry.load("distributions", "constant").quantile(0.3, value=0) == 0


def test_percentile_is_numpys():
    import numpy as np

    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    for q in (0, 10, 50, 90, 95, 100):
        assert math.isclose(percentile(xs, q), float(np.percentile(xs, q)))
    assert percentile([4.0], 95) == 4.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_spread_is_the_quartile_distance_over_the_median():
    import statistics

    xs = [100.0, 101.0, 99.0, 102.0, 98.0, 110.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert math.isclose(spread(xs), (q3 - q1) / statistics.median(xs))
    # one far-off run is left out where that narrows the spread
    assert spread_leaving_one_out(xs) < spread(xs)
    assert math.isclose(
        spread_leaving_one_out(xs), spread([100.0, 101.0, 99.0, 102.0, 98.0])
    )
    assert spread_leaving_one_out([1.0, 1.1, 0.9]) == spread([1.0, 1.1, 0.9])
