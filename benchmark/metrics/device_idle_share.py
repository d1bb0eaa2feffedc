"""Device (TPU v5e): share of the traced window in which no operation ran
on the device — 1 minus the union of the device-operation intervals over
the window, from the profiler's trace.  Read as ``device_idle_share.train``
and ``device_idle_share.serve``."""


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
