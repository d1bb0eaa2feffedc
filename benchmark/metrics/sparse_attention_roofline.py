"""Op kernels (ops/attention.py, ops/pallas/streamed_attention.py): the
least time the step's attention could take — required operations over the
SELECTED (query, key) pairs only, forward and backward, and least bytes,
from the configuration's flops module (the generator puts the floor in
``facts``) — over the device time of ``fused_attention`` and
``fused_attention_grad`` per traced step."""

from benchmark.metrics import _types


def read(facts):
    floor = facts.get("sparse_attention_floor_s")
    s = _types.seconds_per_step(facts, ("fused_attention",))
    if not floor or not s:
        return None
    return 100.0 * floor / s
