"""Open-loop arrival schedules.

A Poisson stream of ``rate`` requests per second over ``seconds``: the gaps
are the ``count`` quantiles of the exponential distribution, put in one
order drawn from the traffic file's ``order_seed``, and the run's seed
rotates that sequence.  Every seed therefore offers the same gaps, and the
same bursts, in another order: the tail of the latencies measures the
system and not the luck of one draw.  The first request is due at the
start of the window.
"""
from __future__ import annotations

import numpy as np

from bench import clouds


def poisson_gaps(rate: float, seconds: float, seed: int,
                 order_seed: int) -> np.ndarray:
    """The ``rate * seconds`` gaps of the stream, in the seed's order."""
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = clouds.rng_for(order_seed, 101).permutation(-np.log1p(-q) / rate)
    return np.roll(gaps, int(clouds.rng_for(seed, 101).integers(count)))


def poisson_due(rate: float, seconds: float, seed: int,
                order_seed: int) -> np.ndarray:
    """Due times in seconds from the window's start, ascending, all below
    ``seconds``: request i is due after the first i gaps."""
    gaps = poisson_gaps(rate, seconds, seed, order_seed)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    # The quantiles sum to a little under count / rate; scale the schedule
    # to end inside the window whatever the order.
    span = due[-1] + gaps[-1]
    return due * (seconds / span) if span > seconds else due


def latencies(due_s: np.ndarray, t0: float, done_at: np.ndarray):
    """Latency of each request from the moment it was due (``t0`` is the
    window's start on the same clock as ``done_at``)."""
    return done_at - (t0 + due_s)
