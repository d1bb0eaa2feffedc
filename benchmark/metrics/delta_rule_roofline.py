"""Op kernels (ops/gated_delta_rule.py): the least time the step's
``gated_delta_rule`` ops could take, forward and backward, every
delta-attention layer's — the larger of the chunk products' operations over
the matrix unit's peak and the bytes ANY implementation of the op must read
and write, each array once in the dtype the op is handed it (``q``, ``k``,
``v``, the gate's and the output gate's pre-activations in and their five
gradients out, bf16 under mixed precision; ``out`` and its gradient, float32;
``beta`` and its gradient), over the memory's, from the configuration's flops
module (``rule_flops``, ``rule_least_bytes``; the generator puts the floor in
``facts``) — over the device time of ``gated_delta_rule`` and
``gated_delta_rule_grad`` per traced step.  The op's scope holds the L2
norms, the decays and the gated head-wise norm as well as the rule, and so
does the floor's byte count.  The floor is the bytes': a low share is what the
chunk algebra's elementwise work, its float32 products in three bf16 passes
(``Precision.HIGH``) and the sequential pass over the chunks cost."""

from benchmark.metrics import _types


def read(facts):
    floor = facts.get("delta_rule_floor_s")
    s = _types.seconds_per_step(facts, ("gated_delta_rule",))
    if not floor or not s:
        return None
    return 100.0 * floor / s
