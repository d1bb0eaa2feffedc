"""Serving engine (serving/engine.py): median host milliseconds of one
decode tick (the ``decode`` span, one per tick) in the traced stretch."""

import statistics

from benchmark.metrics._spans import durations_ms


def read(facts):
    ticks = durations_ms(facts.get("spans"), "decode", distinct="tick")
    return statistics.median(ticks) if ticks else None
