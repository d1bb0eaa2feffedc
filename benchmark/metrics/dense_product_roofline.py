"""Op kernels (ops/math.py): the least time one chip's share of the step's
dense products could take — summed over the parts the program noted where it
lowered them (``op_work``: forward, dX, dW of every ``mul`` / ``matmul`` /
``dequant_matmul``), each the larger of its flops over the bf16 peak and its
least bytes over the HBM peak (benchmark/peaks.py) — over the device time
per traced step of the operations that hold those products
(benchmark/metrics/_products.py, which also logs the table by shape)."""

from benchmark.metrics import _products


def read(facts):
    return _products.roofline(facts)
