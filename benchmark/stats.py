"""The benchmark's own arithmetic on samples: percentiles, the spread the
bounds are set from, and latencies measured from when a request was due."""

import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics; a missing sample is +inf and sorts last."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def quartile_spread(values):
    """Distance between the first and third quartile as a share of the
    median — the spread a bound is five times of."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def latencies_from_due(due, done, missing_at=math.inf):
    """Per-request latency in seconds measured from when each request was
    DUE (not from when the generator managed to send it); ``done[i]`` None
    means it never completed: it counts as missing, done at ``missing_at``
    (+inf, or the end of the run's drain)."""
    return [(missing_at if d is None else d) - t for t, d in zip(due, done)]
