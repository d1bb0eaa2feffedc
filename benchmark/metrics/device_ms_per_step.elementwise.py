"""Op kernels (ops/): device milliseconds per traced step of the first
chip's compute operations whose innermost Fluid scope
(paddle_tpu/registry.py, ``fluid_scope_name``) is in the group ``elementwise``
of benchmark/trace/fluid_groups.json — every other scoped op: elementwise_add, relu, reshape, transpose, scale, sum, cast, dropout, ...."""

from benchmark.metrics import _scopes


def read(facts):
    return _scopes.group_ms_per_step(facts, "elementwise")
