"""Compile caches (compile_cache.py): seconds until the step's executable
was there — XLA's compile, or the persistent cache's read of it — over the
programs lowered in set-up (the records' ``executable_s``: jax's
``backend_compile_duration`` of the step's own module).  A few seconds when
read; tens when the cache no longer held it."""

from benchmark.metrics import _setup


def read(facts):
    return _setup.seconds(facts, "executable_s")
