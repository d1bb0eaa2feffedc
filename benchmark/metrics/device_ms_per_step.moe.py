"""Op kernels (ops/moe.py): device milliseconds per traced step of the
routed experts — router (``moe_router``), dispatch layout
(``moe_dispatch``), the grouped products with their gather and weighted
combine (``moe_expert_ffn``), and their gradients."""

from benchmark.metrics import _types


def read(facts):
    s = _types.seconds_per_step(
        facts, ("moe_router", "moe_dispatch", "moe_expert_ffn"))
    return None if s is None else s * 1e3
