"""Op kernels (ops/): device milliseconds per traced step of the first
chip's compute operations whose innermost Fluid scope
(paddle_tpu/registry.py, ``fluid_scope_name``) is in the group ``optimizer``
of benchmark/trace/fluid_groups.json — adam and the other update ops, and the scale / increment / elementwise ops whose output is an optimizer accumulator, the learning-rate schedule or a clip."""

from benchmark.metrics import _scopes


def read(facts):
    return _scopes.group_ms_per_step(facts, "optimizer")
