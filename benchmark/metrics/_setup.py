"""Shared by the readers of set-up's phases: the program's compile records
(``paddle_tpu.compile_cache.compile_log()``, one a lowering: the startup
program's, the step's), as they stand when the readers run.  A correct run
lowers nothing in its window (``compiles_in_window``), so these are the
records closed before it.

A reader gets ``facts`` and nothing else; handed facts of no traced run
(``facts["trace"]``) every reader returns None — a CPU ``--check`` yields
no time, and its counts are of the window — and so it does over a program
that keeps no such log.  The first reader to ask also logs one row per
record and the ``outside`` bucket (what jax compiled for nobody's step: the
plain reference, eager programs), on lines of their own before the result
line."""

_LOGGED = []


def _log(msg):
    print("[benchmark setup] " + msg, flush=True)


def records(facts):
    """The closed compile records, or None when there is nothing to read."""
    if not facts.get("trace"):
        return None
    try:
        from paddle_tpu import compile_cache
        recs = compile_cache.compile_log()
    except (ImportError, AttributeError):
        return None
    if not _LOGGED:
        _LOGGED.append(True)
        _report(recs, compile_cache.outside_compiles())
    return recs or None


def _report(recs, outside):
    for r in recs:
        _log("%s ops %d cause %s trace_cache %s | build %.3f analyze %.3f "
             "program_trace %.3f jax_trace %.3f (kernel traces %d: %.3f) "
             "lowering %.3f executable %.3f %s | first_call %.3f "
             "unaccounted %.3f s"
             % (r["name"], r["ops"], r["cause"], r["trace_cache"],
                r["build_s"], r["analyze_s"], r["program_trace_s"],
                r["jax_trace_s"], r["kernel_traces"], r["kernel_trace_s"],
                r["lowering_s"], r["executable_s"], r["executable"],
                r["first_call_s"], r["unaccounted_s"]))
        if r["build"]:
            _log("%s build: %s" % (r["name"], ", ".join(
                "%s %.3f" % kv for kv in sorted(r["build"].items()))))
    _log("outside (in no record, in no metric): " + (", ".join(
        "%s %d events %.3f s" % (phase, o["events"], o["seconds"])
        for phase, o in sorted(outside.items())) or "nothing"))


def seconds(facts, *fields):
    """The sum of ``fields`` over the records, or None."""
    recs = records(facts)
    if recs is None:
        return None
    return sum(r[f] for r in recs for f in fields)
