"""Shared by the readers that sum device time over a list of Fluid op
types (with their gradients), from the traced run's table by type."""

from benchmark.metrics import _scopes


def seconds_per_step(facts, types):
    """Device seconds a traced step of the first chip spends under the
    Fluid scopes of ``types`` and of their ``_grad`` ops; None when there
    is no traced run to read or none of the types ran (a program that has
    no such op)."""
    got = _scopes.reading(facts)
    if not got or not got["device"]:
        return None
    rows = got["device"]["by_type"]
    found = [rows[t]["s"] for base in types for t in (base, base + "_grad")
             if t in rows]
    return sum(found) / got["steps"] if found else None
