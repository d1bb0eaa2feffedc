"""Step lowering (backward.py, optimizer.py, contrib/mixed_precision.py):
seconds the whole-program passes of build — ``append_backward``,
``Optimizer.minimize``, the mixed-precision mark — spent on the programs
lowered in set-up (each record's ``build_s``, from the ``build/<pass>``
spans' clock).  Layer-by-layer construction is not in it."""

from benchmark.metrics import _setup


def read(facts):
    return _setup.seconds(facts, "build_s")
