"""The trace reduction on a small synthetic trace: busy union, idle
share, kernel time and gaps named by the open host span."""
from types import SimpleNamespace as NS

import pytest

from chipbench import trace as TR

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def synthetic():
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step", 0, 100)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 10, 20),            # 10..30
            ev("paged_decode_kernel", 25, 15),  # 25..40 (overlaps)
            ev("fusion.2", 60, 10),            # 60..70
            ev("paged_decode_kernel", 95, 20),  # 95..115, clipped at 110
        ]),
    ])
    host = NS(name="/host:CPU", lines=[
        NS(name="main", events=[ev(TR.WINDOW, 5, 105)]),   # 5..110
        NS(name="worker", events=[ev("step.short", 40, 30),  # 40..70
                                  ev("gateway.submit", 72, 20)]),
    ])
    return [device, host]


def test_busy_union_and_window():
    r = TR.reduce(synthetic(), {"paged_decode": "paged_decode"})
    # busy: 10..40 (30) + 60..70 (10) + 95..110 (15)
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["window_s"] == pytest.approx(0.105)


def test_kernel_time_is_clipped_to_the_window():
    r = TR.reduce(synthetic(), {"paged_decode": "paged_decode"})
    assert r["kernel_s"]["paged_decode"] == pytest.approx(0.030)
    assert r["kernel_calls"]["paged_decode"] == 2


def test_gaps_are_named_by_the_open_host_span():
    r = TR.reduce(synthetic(), {"paged_decode": "paged_decode"})
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    # 5..10 no span; 40..60 inside step.short; 70..95: midpoint 82.5
    # inside gateway.submit
    assert gaps["no host span"] == pytest.approx(0.005)
    assert gaps["step.short"] == pytest.approx(0.020)
    assert gaps["gateway.submit"] == pytest.approx(0.025)
    assert sum(gaps.values()) == pytest.approx(0.105 - 0.055)


def test_no_window_or_no_device_gives_nothing():
    dev, host = synthetic()
    assert TR.reduce([dev], {}) is None
    assert TR.reduce([host], {}) is None
