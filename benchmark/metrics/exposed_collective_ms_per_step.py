"""Mesh runtime (parallel/): the part of the collective time per traced
step during which no compute operation ran on that device."""


def read(facts):
    trace, steps = facts.get("trace"), facts.get("traced_steps")
    if not trace or not steps or facts.get("chips", 1) < 2:
        return None
    return trace["exposed_collective_s"] / steps * 1e3
