"""Op kernels (ops/): device milliseconds per traced step of the first
chip's compute operations whose innermost Fluid scope
(paddle_tpu/registry.py, ``fluid_scope_name``) is in the group ``norm``
of benchmark/trace/fluid_groups.json — layer_norm, batch_norm and their gradients."""

from benchmark.metrics import _scopes


def read(facts):
    return _scopes.group_ms_per_step(facts, "norm")
