"""Due-time latency and percentile arithmetic of the open-loop generator,
on a fake clock."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.latency import OpenLoop, percentile, poisson_arrivals  # noqa: E402


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def drive(offsets, service, stall_at=None, stall=0.0):
    """Serve each request `service` s after it is submitted; the request
    at index `stall_at` blocks the generator for `stall` s first."""
    clock = FakeClock()
    loop = OpenLoop(clock=clock, sleep=clock.sleep)

    def submit(i, due):
        if i == stall_at:
            clock.t += stall
        loop.complete(i, clock() + service)

    loop.run(offsets, submit)
    return loop


def test_bench_percentile_matches_linear_interpolation():
    v = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(v, 50) == 3.0
    assert percentile(v, 99) == pytest.approx(4.96)
    assert percentile(np.arange(101.0), 99) == pytest.approx(99.0)
    assert np.isnan(percentile([], 50))


def test_bench_latency_counts_from_due_time():
    offsets = np.arange(200) * 0.01
    loop = drive(offsets, service=0.002)
    np.testing.assert_allclose(loop.latencies(), 0.002)
    np.testing.assert_allclose(loop.lags(), 0.0)


def test_bench_stall_raises_p99_and_generator_lag():
    offsets = np.arange(200) * 0.01
    calm = drive(offsets, service=0.002)
    # the generator loses 0.5 s at request 150: the 50 requests due in that
    # half second are submitted late, and their latency counts the wait
    stalled = drive(offsets, service=0.002, stall_at=150, stall=0.5)
    lat = stalled.latencies()
    assert percentile(lat, 50) == pytest.approx(0.002)
    assert percentile(lat, 99) > 0.4
    assert percentile(stalled.lags(), 99) > 0.4
    assert percentile(calm.latencies(), 99) == pytest.approx(0.002)
    assert lat[150] == pytest.approx(0.502)
    assert lat[199] == pytest.approx(0.012)


def test_bench_poisson_arrivals_fixed_count_and_seeded():
    a = poisson_arrivals(1000.0, 2.0, np.random.default_rng(3))
    b = poisson_arrivals(1000.0, 2.0, np.random.default_rng(3))
    c = poisson_arrivals(1000.0, 2.0, np.random.default_rng(4))
    assert a.shape == c.shape == (2000,)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 2.0
    # exponential-looking gaps with mean 1/rate
    assert np.mean(np.diff(a)) == pytest.approx(1e-3, rel=0.05)


def test_bench_unanswered_requests_stay_pending():
    clock = FakeClock()
    loop = OpenLoop(clock=clock, sleep=clock.sleep)
    loop.run([0.0, 0.1, 0.2], lambda i, due: None)
    assert loop.pending() == 3
    loop.complete(1)
    assert loop.pending() == 2
    assert loop.latencies().shape == (1,)
