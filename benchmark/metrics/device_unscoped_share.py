"""Op kernels (ops/): share of the first chip's device-busy time in
non-collective operations under no Fluid scope — the tracing's own gauge.
What the guard, the health probe, the PRNG key and the transfers around a
step add lands here; on a program without the scopes it reads 100."""

from benchmark.metrics import _scopes


def read(facts):
    got = _scopes.reading(facts)
    if not got or not got["device"] or not got["device"]["busy_s"]:
        return None
    return 100.0 * got["device"]["unscoped_s"] / got["device"]["busy_s"]
