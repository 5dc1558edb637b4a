"""The traffic generator: deterministic by seed, and its mixes follow the
distributions they state."""
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import traffic

MIXES = ("chat-sysprompt",)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_other_tokens(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 2 ** 31 + 5, 1000)
    b = traffic.generate(mix, 2 ** 31 + 5, 1000)
    c = traffic.generate(mix, 17, 1000)
    assert [(r.prompt, r.max_new, r.due_s) for r in a] == \
        [(r.prompt, r.max_new, r.due_s) for r in b]
    # sizes and arrivals are the mix's own; token ids follow the seed
    assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s) for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert all(0 <= t < 1000 for r in a for t in r.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_their_lognormals(name):
    mix = traffic.load_mix(name)
    _, prompt, output, _ = traffic.sizes(mix)
    for got, spec in ((prompt, mix["prompt_tokens"]),
                      (output, mix["output_tokens"])):
        assert got.min() >= spec["min"] and got.max() <= spec["max"]
        assert abs(np.median(got) / spec["median"] - 1) < 0.1
        inner = got[(got > spec["min"]) & (got < spec["max"])]
        sigma = np.std(np.log(inner))
        # clipping trims the tails, so the spread can only shrink
        assert 0.6 * spec["sigma"] < sigma <= 1.05 * spec["sigma"]


def test_shared_prefixes_are_zipf_popular_and_arrivals_poisson():
    mix = traffic.load_mix("chat-sysprompt")
    prefix, _, _, unit = traffic.sizes(mix)
    share = np.bincount(prefix, minlength=4) / len(prefix)
    want = 1 / np.arange(1, 5)
    want /= want.sum()
    assert np.all(np.abs(share - want) < 0.06), share
    gaps = np.diff(np.concatenate([[0.0], unit]))
    assert abs(gaps.mean() - 1.0) < 0.12
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15   # exponential
    reqs = traffic.generate(mix, 3, 5000, rate=2.0)
    assert reqs[10].due_s == pytest.approx(unit[10] / 2.0)
    for r in reqs:
        head = r.prompt[:r.prefix_tokens]
        assert r.prefix_tokens == 1024 and 0 <= r.prefix_id < 4
        assert head == reqs[[x.prefix_id for x in reqs].index(
            r.prefix_id)].prompt[:1024]


def test_warm_up_stream_shares_sizes_and_prefixes_only():
    mix = traffic.load_mix("chat-sysprompt")
    a = traffic.generate(mix, 2 ** 31 + 5, 5000)
    b = traffic.generate(mix, 2 ** 31 + 5, 5000, stream=1)
    assert [(len(r.prompt), r.max_new, r.due_s, r.prefix_id) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s, r.prefix_id) for r in b]
    assert all(x.prompt[:1024] == y.prompt[:1024] for x, y in zip(a, b))
    assert all(x.prompt[1024:] != y.prompt[1024:] for x, y in zip(a, b))
