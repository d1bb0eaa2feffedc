"""Serving scheduler (serving/scheduler.py): 95th percentile of the
``queue_wait`` span of the requests submitted during the traced stretch."""

from benchmark import stats
from benchmark.metrics._spans import durations_ms


def read(facts):
    waits = durations_ms(facts.get("spans"), "queue_wait")
    return stats.percentile(waits, 95) if waits else None
