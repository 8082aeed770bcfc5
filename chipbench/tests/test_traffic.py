"""The traffic generator: deterministic by seed, in range, the same work
for every seed, and data it cannot read refused."""
import itertools

import numpy as np
import pytest

from chipbench import traffic


def take(mix, seed, n):
    return list(itertools.islice(traffic.plan(mix, seed, 1000), n))


def test_deterministic_by_seed():
    mix = traffic.load_mix("steady")
    a, b = take(mix, 7, 40), take(mix, 7, 40)
    assert a == b
    assert [r.prompt for r in take(mix, 8, 40)] != [r.prompt for r in a]


def test_lengths_in_range_and_tokens_in_vocab():
    mix = traffic.load_mix("steady")
    reqs = take(mix, 3, 200)
    for r in reqs:
        assert mix["prompt"]["lo"] <= len(r.prompt) <= mix["prompt"]["hi"]
        assert mix["output"]["lo"] <= r.max_new <= mix["output"]["hi"]
        assert 0 <= min(r.prompt) and max(r.prompt) < 1000


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11, 3 * 2 ** 40 + 5])
def test_every_seed_gets_the_same_work(seed):
    mix = traffic.load_mix("steady")
    n = mix["block"]
    ref = take(mix, 0, n)
    got = take(mix, seed, n)
    assert sorted(len(r.prompt) for r in got) == sorted(len(r.prompt) for r in ref)
    assert sorted(r.max_new for r in got) == sorted(r.max_new for r in ref)
    assert [len(r.prompt) for r in got] != [len(r.prompt) for r in ref]


def test_backlog_lengths_follow_the_loguniform():
    mix = traffic.load_mix("steady")
    lens = np.asarray([len(r.prompt) for r in take(mix, 5, mix["block"])])
    # stratified quantiles: the median of a log-uniform 32..256 is 90.5
    assert abs(np.median(lens) - 90.5) <= 3
    assert lens.min() >= 32 and lens.max() <= 256


@pytest.mark.parametrize("bad", [{"arrivals": "open_loop"},
                                 {"prompt": {"dist": "lognormal"}}])
def test_unknown_arrivals_or_lengths_are_refused(bad):
    mix = dict(traffic.load_mix("steady"), **bad)
    with pytest.raises(ValueError):
        take(mix, 1, 1)
