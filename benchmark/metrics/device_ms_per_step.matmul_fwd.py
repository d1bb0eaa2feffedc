"""Op kernels (ops/math.py): device milliseconds per traced step of the
first chip's operations that hold a forward product — part ``fwd`` of
``mul``, ``matmul`` and ``dequant_matmul`` — found by the ``dot`` /
``convolution`` INSIDE each operation and the program's own count of the
work (benchmark/metrics/_products.py), not by the operation's root as
``device_ms_per_step.matmul`` is."""

from benchmark.metrics import _products


def read(facts):
    return _products.part_ms_per_step(facts, "fwd")
