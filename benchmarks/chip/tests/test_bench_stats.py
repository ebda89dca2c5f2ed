"""Percentiles over all samples, and the client-side times they are taken
over: TTFT from the due time, censored first tokens, inter-token gaps."""

import numpy as np
import pytest

from serve_loop import Record, itls_s, tokens_in, ttfts_s
from stats import cv, percentile
from traffic.generate import Planned


def test_percentile_interpolates_over_every_sample():
    xs = [float(x) for x in range(1, 11)]       # 1..10
    # (n-1) * q/100 = 9 * 0.9 = 8.1: 9 + 0.1 * (10 - 9)
    assert percentile(xs, 90) == pytest.approx(9.1)
    assert percentile(xs, 50) == pytest.approx(5.5)
    assert percentile(xs, 0) == 1.0 and percentile(xs, 100) == 10.0
    rng = np.random.default_rng(0)
    ys = rng.lognormal(0, 1, 501).tolist()
    for q in (50, 90, 95, 99):
        assert percentile(ys, q) == pytest.approx(np.percentile(ys, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        percentile([], 50)


def test_cv():
    assert cv([2.0, 2.0, 2.0]) == 0.0
    assert cv([1.0, 3.0]) == pytest.approx(0.5)    # pstdev 1 over mean 2
    assert cv([5.0]) is None


def _rec(due, tokens):
    r = Record(Planned(0, due, [1, 2], len(tokens), 0.0), "r")
    r.token_s = list(tokens)
    return r


def test_ttft_is_timed_from_the_due_time_and_censored_at_the_end():
    served = _rec(1.0, [1.25, 1.5])      # submitted late or not: due counts
    waiting = _rec(2.0, [])              # no first token by the end
    late = _rec(3.0, [7.0])              # first token after the end
    assert ttfts_s([served, waiting, late], end_s=5.0) == pytest.approx(
        [0.25, 3.0, 2.0])


def test_gaps_and_tokens_count_only_inside_the_window():
    r = _rec(0.0, [0.5, 1.2, 1.5, 2.1, 3.5])
    # window [1, 3]: tokens at 1.2, 1.5, 2.1 -> gaps 0.3, 0.6
    assert itls_s([r], 1.0, 3.0) == pytest.approx([0.3, 0.6])
    assert tokens_in([r, _rec(0.0, [2.5])], 1.0, 3.0) == 4
