"""Op kernels (ops/attention.py, ops/pallas/streamed_attention.py): the least
time the step's grouped-query attention could take under a mix of masks —
each layer's required operations over the (query, key) pairs that COUNT under
its own mask (a window layer's ``t - window < s <= t``, a full layer's ``s <=
t``), 128-wide keys and values, every query head, forward and backward, and
its least bytes, from the configuration's flops module
(``mixed_attention_floor_seconds``; the generator puts the floor in
``facts``) — over the device time of ``fused_attention`` and
``fused_attention_grad`` per traced step: the streamed kernels' share of
their roofline on eight query heads a key/value head, three window layers to
one full layer."""

from benchmark.metrics import _types


def read(facts):
    floor = facts.get("mixed_attention_floor_s")
    s = _types.seconds_per_step(facts, ("fused_attention",))
    if not floor or not s:
        return None
    return 100.0 * floor / s
