"""Op kernels (ops/attention.py, ops/pallas/streamed_attention.py): the
least time the step's attention could take — required operations over ALL
causal (query, key) pairs with keys and values 128 wide, forward and
backward, and least bytes, of every application of a block (layers x
passes), from the configuration's flops module (the generator puts the
floor in ``facts``) — over the device time of ``fused_attention`` and
``fused_attention_grad`` per traced step: the streamed kernels' share of
their roofline on plain 128 / 128 heads."""

from benchmark.metrics import _types


def read(facts):
    floor = facts.get("plain_attention_floor_s")
    s = _types.seconds_per_step(facts, ("fused_attention",))
    if not floor or not s:
        return None
    return 100.0 * floor / s
