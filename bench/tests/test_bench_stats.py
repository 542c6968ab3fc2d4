"""Percentiles in which a request that never got its answer is a miss."""
import math

from bench.stats import beyond, percentile


def test_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 90) == 90
    assert percentile(xs, 99) == 99
    assert percentile(xs, 100) == 100
    assert percentile([7.0], 90) == 7.0
    assert beyond(xs, 90) == 10


def test_misses_count_above_every_served_request():
    xs = [0.1] * 85 + [math.inf] * 15
    assert percentile(xs, 50) == 0.1
    assert percentile(xs, 85) == 0.1
    assert percentile(xs, 90) == math.inf
    assert percentile([math.inf, 1.0, 2.0], 50) == 2.0
