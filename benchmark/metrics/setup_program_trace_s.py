"""Step lowering (executor.StepPath._lower and the cold call): seconds from
a Program to its jaxpr, over the programs lowered in set-up — ``analyze``,
``trace_program`` with its wrappers, and jax tracing the step function,
where every op's lowering runs and the Pallas kernels are traced (the
records' ``analyze_s + program_trace_s + jax_trace_s``; ``kernel_trace_s``
is inside the last)."""

from benchmark.metrics import _setup


def read(facts):
    return _setup.seconds(facts, "analyze_s", "program_trace_s",
                          "jax_trace_s")
