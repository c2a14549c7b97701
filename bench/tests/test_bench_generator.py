"""The traffic generator: deterministic per seed, the same sizes at the
same places for every seed in another order, and sizes that follow the
mix's stated distributions, tails included."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import generator, spec  # noqa: E402

BIG_SEED = 2 ** 31 + 12345
MIX = spec.mix("batch-backlog")
KEYS = ["prompt_tokens", "output_tokens"]


def lengths(reqs, key):
    return [len(r.prompt) if key == "prompt_tokens" else r.max_new
            for r in reqs]


def test_same_seed_same_traffic():
    a = generator.generate(MIX, BIG_SEED, 1000, 60)
    b = generator.generate(MIX, BIG_SEED, 1000, 60)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new and np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("key", KEYS)
def test_every_seed_offers_the_same_sizes(key):
    a = generator.generate(MIX, 1, 1000, 60)
    b = generator.generate(MIX, BIG_SEED, 1000, 60)
    assert sorted(lengths(a, key)) == sorted(lengths(b, key))
    assert lengths(a, key) != lengths(b, key)


@pytest.mark.parametrize("key", KEYS)
def test_every_seed_puts_the_same_sizes_at_each_place(key):
    """The seed orders only the neighbours of each stratum, so every run's
    window serves the same work."""
    g = MIX["stratum"]
    a = np.array(lengths(generator.generate(MIX, 1, 1000, 60), key))
    b = np.array(lengths(generator.generate(MIX, BIG_SEED, 1000, 60), key))
    n = len(a) // g * g
    assert np.array_equal(np.sort(a[:n].reshape(-1, g), axis=1),
                          np.sort(b[:n].reshape(-1, g), axis=1))
    # the neighbours are a stratum: one or two quantiles apart, no more
    q = np.rint(generator.quantiles(MIX[key], MIX["block"]))
    rank = np.searchsorted(q, a[:MIX["block"]]).reshape(-1, g)
    assert (rank.max(axis=1) - rank.min(axis=1)).max() < 2 * g


@pytest.mark.parametrize("key", KEYS)
def test_each_stretch_of_a_block_holds_short_and_long(key):
    """The strata's fixed order spreads every quarter of a block over the
    whole range of sizes, so no seed's window serves only short or only
    long requests."""
    b = MIX["block"]
    got = np.array(lengths(generator.generate(MIX, 5, 1000, 60), key))[:b]
    median = np.median(got)
    for quarter in got.reshape(4, -1):
        assert 0.3 < np.mean(quarter > median) < 0.7
        assert quarter.max() > np.percentile(got, 75)
        assert quarter.min() < np.percentile(got, 25)


def test_a_block_is_whole_strata():
    with pytest.raises(ValueError):
        generator.block_layout(MIX["prompt_tokens"], 10, 3, 0.5)


@pytest.mark.parametrize("key", KEYS)
def test_each_block_holds_the_quantiles_of_the_mix(key):
    """Every block of ``block`` requests holds the stratified quantiles of
    the stated lognormal, and at 100 a block they reach the top clip."""
    reqs = generator.generate(MIX, 3, 1000, 60)
    b, sp = MIX["block"], MIX[key]
    got = np.array(lengths(reqs, key))
    want = np.rint(generator.quantiles(sp, b))
    for i in range(0, (len(got) // b) * b, b):
        assert np.array_equal(np.sort(got[i:i + b]), want)
    assert got.max() == sp["max"] and got.min() >= sp["min"]


def test_quantiles_are_the_distribution():
    sp = MIX["prompt_tokens"]
    q = generator.quantiles(sp, 1001)
    assert q[500] == pytest.approx(sp["median"])
    inner = np.log(q[(q > sp["min"]) & (q < sp["max"])])
    q25, q75 = np.percentile(inner, [25, 75])
    assert (q75 - q25) / 1.349 == pytest.approx(sp["sigma"], rel=0.1)


def test_unknown_distributions_are_refused():
    with pytest.raises(ValueError):
        generator.quantiles({"dist": "zipf"}, 4)
    with pytest.raises(ValueError):
        generator.generate(dict(MIX, kind="open"), 1, 1000, 60)


def test_backlog_outlasts_the_run():
    reqs = generator.generate(MIX, 9, 1000, 60)
    assert len(reqs) >= MIX["backlog_requests_per_s"] * 60 \
        + 2 * MIX["max_batch"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= \
        generator.max_len(MIX)
