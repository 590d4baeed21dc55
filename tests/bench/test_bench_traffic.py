"""The open-loop schedule and the clock of the serve window."""
import pathlib
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import arrivals, clouds  # noqa: E402

BIG = 2**31 + 977


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_schedule_is_a_function_of_the_seed(seed):
    a = arrivals.poisson_due(100.0, 20.0, seed, 1)
    b = arrivals.poisson_due(100.0, 20.0, seed, 1)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 2000 and a[0] == 0.0
    assert np.all(np.diff(a) >= 0) and a[-1] < 20.0


def test_every_seed_offers_the_same_gaps_in_another_order():
    a = arrivals.poisson_gaps(50.0, 10.0, 1, 3)
    b = arrivals.poisson_gaps(50.0, 10.0, BIG, 3)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))
    # One fixed order, rotated: the bursts are the same in every run.
    assert any(np.array_equal(np.roll(a, k), b) for k in range(len(a)))
    assert not np.array_equal(a, arrivals.poisson_gaps(50.0, 10.0, 1, 4))
    np.testing.assert_allclose(
        np.diff(arrivals.poisson_due(50.0, 10.0, BIG, 3)), b[:-1],
        atol=1e-12)


def test_clouds_are_a_function_of_the_seed():
    spec = {"points": 1000, "pool": 2, "footprint_m": 1.0, "height_m": 3.0,
            "objects": 4, "object_size_m": [0.1, 0.3], "noise_m": 0.01,
            "floor_share": 0.2}
    a, la = clouds.pool(BIG, spec)
    b, lb = clouds.pool(BIG, spec)
    c, _ = clouds.pool(BIG + 1, spec)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(la, lb)
    assert not np.array_equal(a, c)
    assert a.dtype == np.float32 and a.shape == (2, 1000, 3)
    xy = a[0, :, :2]
    np.testing.assert_allclose(xy.max(0) - xy.min(0), [1.0, 1.0], atol=0.02)


class SlowEngine:
    """Answers every request at once, each ``step`` taking ``cost`` s."""

    def __init__(self, cost):
        self.cost, self.queue, self.next = cost, [], 0

    def submit(self, coords, now=None, dim0=0):
        self.queue.append(self.next)
        self.next += 1
        return self.next - 1

    def step(self, now=None):
        if not self.queue:
            return []
        time.sleep(self.cost)
        done, self.queue = self.queue, []
        return done

    def take(self, rid, default=None):
        return np.zeros((4, 2))


def test_latency_is_counted_from_the_due_time():
    from bench.drivers import serve
    due = np.array([0.0, 0.01, 0.02, 0.2])
    t0, done_at, late, results, _ = serve.window(
        SlowEngine(0.1), np.zeros((1, 4, 3)), due, np.zeros(4, int), {3},
        0.02, 0.3)
    lat = arrivals.latencies(due, t0, done_at)
    assert np.all(np.isfinite(done_at)) and set(results) == {3}
    # The first step holds the engine for 0.1 s, so the second request,
    # due at 0.01, is submitted late and waits for it: its latency counts
    # that wait (more than the 0.1 s its own step takes).
    assert lat[0] >= 0.1 and lat[1] >= 0.1 + 0.09
    assert late[1] >= 0.08
