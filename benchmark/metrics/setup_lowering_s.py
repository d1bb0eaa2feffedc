"""Step lowering (the cold call): seconds jax took from the step's jaxpr to
its MLIR module, the Mosaic lowerings of its Pallas kernels included, over
the programs lowered in set-up (the records' ``lowering_s``: jax's
``jaxpr_to_mlir_module_duration`` of the step's own module)."""

from benchmark.metrics import _setup


def read(facts):
    return _setup.seconds(facts, "lowering_s")
