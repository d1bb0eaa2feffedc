"""Op kernels (ops/gated_delta_rule.py, ops/state_space.py): device
milliseconds per traced step of a delta-attention layer's own operations —
the gated delta rule (``gated_delta_rule``), the short causal depthwise
convolutions before it (``causal_conv1d``) and their gradients.  The layer's
projections count under ``matmul``.  benchmark/trace/fluid_groups.json holds
no group for the types (they fall to ``elementwise``)."""

from benchmark.metrics import _types


def read(facts):
    s = _types.seconds_per_step(facts, ("gated_delta_rule", "causal_conv1d"))
    return None if s is None else s * 1e3
