"""Op kernels (ops/attention.py, ops/pallas/streamed_attention.py): the
least time the step's latent attention could take — required operations
over ALL causal (query, key) pairs with 192-wide keys and 128-wide values,
forward and backward, and least bytes, every block's, from the
configuration's flops module (the generator puts the floor in ``facts``) —
over the device time of ``fused_attention`` and ``fused_attention_grad`` per
traced step: the streamed kernels' share of their roofline on plain heads."""

from benchmark.metrics import _types


def read(facts):
    floor = facts.get("latent_attention_floor_s")
    s = _types.seconds_per_step(facts, ("fused_attention",))
    if not floor or not s:
        return None
    return 100.0 * floor / s
