"""Op kernels (ops/moe.py): the least time the experts' grouped products
could take — required operations over the token-expert pairs the step's
counter reports as computed, forward and backward, and the held matrices'
least bytes — over the device time of ``moe_expert_ffn`` and
``moe_expert_ffn_grad`` per traced step (which also holds the gather, the
weighted combine and the backward's recomputation: all of it is what the
grouped products cost here)."""

from benchmark.metrics import _types


def read(facts):
    floor = facts.get("expert_matmul_floor_s")
    s = _types.seconds_per_step(facts, ("moe_expert_ffn",))
    if not floor or not s:
        return None
    return 100.0 * floor / s
