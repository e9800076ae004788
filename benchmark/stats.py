"""The yardstick's arithmetic.  Standard library only."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it.  No interpolation,
    so a reported tail is a latency some request really had."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[min(len(xs), rank) - 1]


def median(values) -> float:
    """The middle value; of an even count, the mean of the middle two."""
    xs = sorted(values)
    if not xs:
        raise ValueError("median of an empty sample")
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2.0


def mean(values) -> float:
    """The arithmetic mean.  The statistic for readings that fall into
    modes, where the median jumps from one mode to the other as their
    counts change by one (audit passes with one or two full garbage
    collections inside them); the mean moves by a share of the gap."""
    xs = list(values)
    if not xs:
        raise ValueError("mean of an empty sample")
    return math.fsum(xs) / len(xs)


def spread(values) -> float:
    """Distance between the quartiles over the median: the driver's measure
    of how far runs of one cell disagree."""
    xs = sorted(values)
    q1 = percentile(xs, 25)
    q3 = percentile(xs, 75)
    return (q3 - q1) / median(xs)


def open_loop_latencies(due: list, done: list) -> list:
    """Latency of each answered request, timed FROM THE TIME IT WAS DUE and
    not from the time it was sent: the wait a stall imposes on the requests
    behind it counts.  ``done[i]`` is None for a request never answered."""
    return [b - a for a, b in zip(due, done) if b is not None]


def lateness(due: list, sent: list) -> list:
    """How late each request left the generator against its schedule."""
    return [max(0.0, s - d) for d, s in zip(due, sent) if s is not None]


def arrival_times(rng, n: int, seconds: float) -> list:
    """``n`` arrival times in [0, seconds): a Poisson process conditioned on
    its count.  The count is fixed by the rate so that every seed offers the
    same amount of work; the times are drawn from the seed."""
    return sorted(rng.random() * seconds for _ in range(n))
