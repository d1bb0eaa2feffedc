"""Serving engine (serving/engine.py): median host milliseconds of one
prefill call (the ``prefill`` span, one per admitted batch)."""

import statistics


def read(facts):
    seen = {}
    for s in facts.get("spans") or ():
        if s.get("name") == "prefill":
            seen[s.get("mono_us")] = float(s["dur_ms"])
    return statistics.median(seen.values()) if seen else None
