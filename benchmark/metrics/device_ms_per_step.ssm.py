"""Op kernels (ops/state_space.py, ops/pallas/selective_scan.py): device
milliseconds per traced step of a state-space layer's own operations — the
selective scan (``selective_scan``), the short causal depthwise convolution
before it (``causal_conv1d``) and their gradients.  The layer's projections
count under ``matmul``.  benchmark/trace/fluid_groups.json holds no group for
the types (they fall to ``elementwise``)."""

from benchmark.metrics import _types


def read(facts):
    s = _types.seconds_per_step(facts, ("selective_scan", "causal_conv1d"))
    return None if s is None else s * 1e3
