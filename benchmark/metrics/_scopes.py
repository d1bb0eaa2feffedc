"""Shared by the readers of device time under Fluid names and of the
executors' host spans (benchmark/trace/scopes.py).

A reader gets ``facts`` and nothing else, so the trace is found under
``harness.trace_dir``'s fixed layout, and only when the run was traced
(``facts["trace"]`` and ``facts["traced_steps"]``): handed empty facts
every reader returns None.  The file is read once per run; the first
reader to ask also logs the full table by Fluid op type and the largest
operations, on lines of their own before the result line."""

import collections
import os

from benchmark.trace import scopes

_READ = {}


def _log(msg):
    print("[benchmark scopes] " + msg, flush=True)


def reading(facts):
    """{"steps", "device": scopes.device_table of the first chip or None,
    "host": (median self s, median wait s) or None}, or None when there is
    no traced run to read."""
    steps = facts.get("traced_steps")
    if not facts.get("trace") or not steps:
        return None
    path = scopes.find_newest()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _READ:
        _READ.clear()
        try:
            _READ[key] = _read(path, steps)
        except (OSError, ImportError, ValueError) as e:
            _log("cannot read %s: %s: %s" % (path, type(e).__name__, e))
            _READ[key] = None
    return _READ[key]


def _read(path, steps):
    trace = scopes.load(path)
    rules = scopes.load_groups()
    chips = sorted(i for i, d in trace["devices"].items() if d["ops"])
    device = None
    if chips:
        first = trace["devices"][chips[0]]
        device = scopes.device_table(first["ops"], rules)
        device["modules"] = collections.Counter(
            name for name, _, _ in first["modules"])
    host = scopes.host_medians(scopes.host_steps(trace["host"]))
    _log("%s: %d traced steps, first chip %s" % (
        os.path.relpath(path, scopes.ROOT), steps,
        chips[0] if chips else "none"))
    if device:
        _report(device, steps)
    if host:
        _log("host: self %.3f ms + wait %.3f ms a step (medians over the "
             "%s spans)" % (host[0] * 1e3, host[1] * 1e3, scopes.STEP_SPAN))
    return {"steps": steps, "device": device, "host": host}


def _report(device, steps):
    busy = device["busy_s"] or 1.0
    _log("compiled programs run (XLA Modules): " + ", ".join(
        "%s x%d" % kv for kv in sorted(device["modules"].items())))

    def row(name, s):
        return "%-34s %9.3f ms/step %6.2f%%" % (
            name, s / steps * 1e3, 100.0 * s / busy)
    _log("device busy %.3f ms a step; by group:" % (busy / steps * 1e3))
    for g, s in device["groups"].items():
        _log("  " + row(g, s))
    _log("  " + row("(unscoped)", device["unscoped_s"]))
    _log("  " + row("(collective)", device["collective_s"]))
    _log("by Fluid op type (XLA's flops and bytes_accessed summed; "
         "TFLOP/s and GB/s over the type's device time):")
    for t, r in sorted(device["by_type"].items(), key=lambda kv: -kv[1]["s"]):
        per = r["s"] or 1.0
        _log("  %s %-11s %6d ops/step  %8.2f TFLOP/s %8.1f GB/s" % (
            row(t, r["s"]), r["group"], r["count"] // steps,
            r["flops"] / per / 1e12, r["bytes"] / per / 1e9))
    _log("by XLA's hlo_category, compute operations: " + ", ".join(
        "%s %.3f" % (c or "(none)", s / steps * 1e3)
        for c, s in device["by_category"].items()) + " ms/step")
    _log("largest operations:")
    for name, s in device["top"]:
        _log("  " + row(name, s))
    if device["unscoped_top"]:
        _log("largest operations under no Fluid scope:")
        for name, s in device["unscoped_top"][:10]:
            _log("  " + row(name, s))
    if device["unnamed_types"]:
        _log("Fluid types fluid_groups.json does not name (counted under "
             "its default group): %s" % ", ".join(device["unnamed_types"]))


def group_ms_per_step(facts, group):
    got = reading(facts)
    if not got or not got["device"]:
        return None
    return got["device"]["groups"][group] / got["steps"] * 1e3
