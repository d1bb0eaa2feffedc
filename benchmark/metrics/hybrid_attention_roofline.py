"""Op kernels (ops/attention.py, ops/pallas/streamed_attention.py): the least
time the step's differential attention could take — required operations over
the (query, key) pairs that COUNT (a window layer's ``t - window < s <= t``,
a full and a cross layer's ``s <= t``), keys 64 and values 128 wide, both
softmax maps of a pair, forward and backward, and least bytes, from the
configuration's flops module (the generator puts the floor in ``facts``) —
over the device time of ``fused_attention`` and ``fused_attention_grad`` per
traced step: the streamed kernels' share of their roofline on two query heads
a key/value head, with and without a window."""

from benchmark.metrics import _types


def read(facts):
    floor = facts.get("hybrid_attention_floor_s")
    s = _types.seconds_per_step(facts, ("fused_attention",))
    if not floor or not s:
        return None
    return 100.0 * floor / s
