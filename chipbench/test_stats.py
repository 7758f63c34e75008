"""Percentiles, time per output token and window arithmetic on known
samples."""
import numpy as np
import pytest

from chipbench import stats as S


def test_percentile_matches_numpy_linear():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 25, 50, 90, 95, 100):
        assert S.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_ten_samples_beyond_the_percentile():
    assert S.min_samples(95) == 200
    assert S.min_samples(50) == 20


def test_tpot_counts_tokens_after_the_first_event():
    # events at 1.0 (4 tokens), 1.2 (4), 1.4 (2); window covers all
    chunks = [(1.0, 4), (1.2, 4), (1.4, 2)]
    assert S.tpot_s(chunks, 0.0, 2.0) == pytest.approx(0.4 / 6)
    # the window clips the first event away
    assert S.tpot_s(chunks, 1.1, 2.0) == pytest.approx(0.2 / 2)
    assert S.tpot_s(chunks, 1.3, 2.0) is None


def test_tokens_in_window_is_half_open():
    chunks = [(1.0, 4), (2.0, 3), (3.0, 5)]
    assert S.tokens_in_window(chunks, 1.0, 3.0) == 7
    assert S.tokens_in_window(chunks, 0.0, 10.0) == 12


def test_quartile_spread_uses_python_quantiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = 10.75, 12.5, 14.25
    assert S.quartile_spread(vals) == pytest.approx((q3 - q1) / q2)
