"""Op kernels (ops/norm.py): device milliseconds per traced step of the
RMSNorms (``rms_norm``) and their gradients (``rms_norm_grad``) that stay
operations of their own — a sandwich-norm block has four a application, and
a looped model one more a pass; a norm XLA fuses into the product beside
it counts under the product, not here.  benchmark/trace/fluid_groups.json
holds no group for the type (it falls to ``elementwise``)."""

from benchmark.metrics import _types


def read(facts):
    s = _types.seconds_per_step(facts, ("rms_norm",))
    return None if s is None else s * 1e3
