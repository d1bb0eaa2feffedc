"""Step lowering (executor.py, parallel/parallel_executor.py): median host
milliseconds of one call into the executor's ``run`` — the benchmark's
span around the call, which returns before the device finishes."""

import statistics


def read(facts):
    spans = facts.get("dispatch_s")
    return statistics.median(spans) * 1e3 if spans else None
