"""Seeded traffic: the same seed gives the same schedule; every seed gives
the same work in another order."""

import numpy as np

import pytest

from traffic.generate import (generator, lognormal_lengths, make_requests,
                              poisson_gaps)
from traffic.generators.lognormal_poisson import arrange

MIX = {"generator": "lognormal_poisson", "rate_per_s": 2.0,
       "prompt": {"mean": 330, "sigma": 0.9, "min": 4, "max": 3072},
       "output": {"mean": 240, "sigma": 0.9, "min": 1, "max": 1024},
       "temperature": 0.0}


def _key(plan):
    return [(p.index, p.due_s, tuple(p.prompt), p.max_new_tokens)
            for p in plan]


def test_same_seed_same_schedule():
    big = 2 ** 31 + 12345
    a = make_requests(MIX, seed=big, duration_s=60, vocab=151936)
    b = make_requests(MIX, seed=big, duration_s=60, vocab=151936)
    assert _key(a) == _key(b)
    c = make_requests(MIX, seed=big + 1, duration_s=60, vocab=151936)
    assert _key(a) != _key(c)


def test_every_seed_carries_the_same_work():
    plans = [make_requests(MIX, seed=s, duration_s=60, vocab=1000)
             for s in (1, 2, 3)]
    for plan in plans:
        assert len(plan) == 120                      # rate x duration
        assert plan[0].due_s == 0.0 and plan[-1].due_s < 60.0
        assert all(a.due_s <= b.due_s for a, b in zip(plan, plan[1:]))
    lens = [sorted(len(p.prompt) for p in plan) for plan in plans]
    outs = [sorted(p.max_new_tokens for p in plan) for plan in plans]
    assert lens[0] == lens[1] == lens[2]
    assert outs[0] == outs[1] == outs[2]
    assert [p.max_new_tokens for p in plans[0]] != \
        [p.max_new_tokens for p in plans[1]]


def test_every_seed_puts_the_same_work_into_the_window():
    mix = dict(MIX, lead_in_s=40.0, lead_in_seed=3, block_requests=10)
    plans = [make_requests(mix, seed=s, duration_s=70, vocab=1000)
             for s in (1, 2, 2 ** 31 + 5)]
    windows = []
    for plan in plans:
        assert [p.index for p in plan] == list(range(140))
        assert all(a.due_s <= b.due_s for a, b in zip(plan, plan[1:]))
        window = [p for p in plan if p.due_s >= 40.0]
        assert len(window) == 60                     # rate x window
        assert window[0].due_s == 40.0 and window[-1].due_s < 70.0
        windows.append((sorted(len(p.prompt) for p in window),
                        sorted(p.max_new_tokens for p in window)))
    assert windows[0] == windows[1] == windows[2]
    # the lead-in is arranged alike for every seed; its token ids differ
    lead = [[p for p in plan if p.due_s < 40.0] for plan in plans]
    shape = [[(p.due_s, len(p.prompt), p.max_new_tokens) for p in ps]
             for ps in lead]
    assert len(lead[0]) == 80 and shape[0] == shape[1] == shape[2]
    assert lead[0][0].prompt != lead[1][0].prompt
    assert [p.max_new_tokens for p in plans[0][80:]] != \
        [p.max_new_tokens for p in plans[1][80:]]


def test_blocks_take_every_stratum():
    values = np.arange(53.0)
    out = arrange(values, np.random.default_rng(2 ** 31 + 9), 5)
    assert sorted(out) == list(values)
    runs = np.split(out, np.cumsum([11, 11, 11, 10]))
    for run in runs:
        # one value from each stratum of 5 neighbours in sorted order
        assert len({int(v) // 5 for v in run if v < 50}) == 10


def test_lengths_follow_the_mix():
    n = 4000
    out = lognormal_lengths(MIX["output"], n)
    assert out.min() >= 1 and out.max() <= 1024
    # clipping at 1,024 trims the tail a little below the mean of 240
    assert 215 < out.mean() < 245
    gaps = poisson_gaps(2.0, n)
    assert abs(gaps.sum() - n / 2.0) < 1e-6
    # exponential: the median gap is ln 2 / rate
    assert abs(np.median(gaps) - np.log(2) / 2.0) < 1e-3


@pytest.mark.parametrize("name", ["no_such_generator", "../generate", ""])
def test_generator_found_by_file_name(name):
    assert callable(generator("lognormal_poisson"))
    with pytest.raises(ValueError, match="unknown traffic generator"):
        make_requests(dict(MIX, generator=name), seed=1, duration_s=10,
                      vocab=100)
