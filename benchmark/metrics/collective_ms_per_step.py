"""Mesh runtime (parallel/): milliseconds of collective operations per
traced step on a device (start..done spans of asynchronous collectives and
synchronous collective ops), averaged over the chips."""


def read(facts):
    trace, steps = facts.get("trace"), facts.get("traced_steps")
    if not trace or not steps or facts.get("chips", 1) < 2:
        return None
    return trace["collective_s"] / steps * 1e3
