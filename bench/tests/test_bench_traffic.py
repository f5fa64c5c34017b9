"""The generator: deterministic in the seed, the stated distributions, and
the same set of sizes for every seed."""

import json
import os

import numpy as np
import pytest

import traffic as T

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    return T.load_mix(os.path.join(HERE, "traffic", name + ".json"))


def test_closed_plan_deterministic_and_stratified():
    m = mix("decode-1k")
    a = T.closed_plan(m, clients=8, seed=2**33 + 5, vocab=32000, max_len=1024)
    b = T.closed_plan(m, clients=8, seed=2**33 + 5, vocab=32000, max_len=1024)
    c = T.closed_plan(m, clients=8, seed=7, vocab=32000, max_len=1024)
    flat = lambda p: [(r.prompt, r.max_new, r.warm) for q in p for r in q]
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)
    body = lambda p: [r for q in p for r in q if not r.warm]
    for p in (a, c):
        lens = [len(r.prompt) for r in body(p)]
        outs = [r.max_new for r in body(p)]
        assert min(lens) >= 64 and max(lens) <= 128
        assert min(outs) >= 128 and max(outs) <= 896
        assert abs(np.mean(outs) - 512) < 5
        assert all(len(r.prompt) + r.max_new <= 1024 for q in p for r in q)
    # the same multiset of sizes, in another order
    assert sorted(len(r.prompt) for r in body(a)) == \
        sorted(len(r.prompt) for r in body(c))
    assert sorted(r.max_new for r in body(a)) == \
        sorted(r.max_new for r in body(c))
    # one warm-start request per client, first
    assert all(q[0].warm and not any(r.warm for r in q[1:]) for q in a)


def test_open_plan_rate_and_lengths():
    m = mix("prefill-2k")
    a = T.open_plan(m, rate=5.0, seconds=60, seed=3, vocab=32000,
                    max_len=2304)
    b = T.open_plan(m, rate=5.0, seconds=60, seed=4, vocab=32000,
                    max_len=2304)
    n = int(np.ceil(5.0 * (m["ramp_s"] + 60)))
    assert len(a) == len(b) == n
    at = np.array([r.at for r in a])
    assert at[0] == 0 and np.all(np.diff(at) >= 0)
    assert at[-1] == pytest.approx(n / 5.0, rel=0.05)
    lens = np.array([len(r.prompt) for r in a])
    assert lens.min() >= 256 and lens.max() <= 2048
    assert np.median(lens) == pytest.approx(768, rel=0.05)
    assert all(r.max_new == 16 for r in a)
    assert sorted(lens) == sorted(len(r.prompt) for r in b)
    # the mix fixes the order: the seed draws only the token ids
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.at for r in a] == [r.at for r in b]
    seeded = T.open_plan(dict(m, order="seed"), rate=5.0, seconds=60,
                         seed=4, vocab=32000, max_len=2304)
    assert [len(r.prompt) for r in seeded] != [len(r.prompt) for r in b]
    assert sorted(len(r.prompt) for r in seeded) == sorted(lens)
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_ppf():
    u = T.quantiles(4)
    assert list(T.ppf({"dist": "uniform", "min": 0, "max": 3}, u)) == \
        [0, 1, 2, 3]
    assert list(T.ppf({"dist": "fixed", "value": 9}, u)) == [9] * 4
    with pytest.raises(ValueError):
        T.ppf({"dist": "zipf"}, u)


def test_plan_refuses_what_does_not_fit():
    m = dict(mix("decode-1k"), warm_start=False)
    with pytest.raises(ValueError, match="does not fit"):
        T.closed_plan(m, clients=2, seed=0, vocab=100, max_len=512)
