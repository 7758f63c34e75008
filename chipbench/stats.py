"""Arithmetic over one run's request records: percentiles, time per
output token, tokens inside the measured window, and the spread of a
set of runs. Pure Python, so the tests check it on known samples."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence, Tuple

# A percentile is reported only where at least this many samples lie
# beyond it (choosing-metrics guide, section 1): the 95th percentile
# needs 200 samples, the median 20.
SAMPLES_BEYOND = 10


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default method)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def min_samples(q: float) -> int:
    """Fewest samples for which SAMPLES_BEYOND lie above the q-th
    percentile."""
    return math.ceil(SAMPLES_BEYOND / (1.0 - q / 100.0) - 1e-9)


def tpot_s(chunks: Sequence[Tuple[float, int]], t0: float,
           t1: float) -> Optional[float]:
    """Time per output token of one request over the tokens it received
    inside [t0, t1). ``chunks`` are (arrival time, tokens) of each
    streamed event. Tokens of one event arrive together, so the rate is
    the time from the first event to the last over the tokens that came
    after the first event. None with fewer than two events inside."""
    inside = [(t, n) for t, n in chunks if t0 <= t < t1 and n > 0]
    if len(inside) < 2:
        return None
    after_first = sum(n for _, n in inside[1:])
    return (inside[-1][0] - inside[0][0]) / after_first


def tokens_in_window(chunks: Sequence[Tuple[float, int]], t0: float,
                     t1: float) -> int:
    return sum(n for t, n in chunks if t0 <= t < t1)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and the third quartile as a share of
    the median, with Python's statistics.quantiles (n=4)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
