"""Program-level cost & memory attribution (ISSUE 5 tentpole).

The monitor's StepStats answer "how fast is the run going"; this module
answers "which compiled program is spending the time and the HBM".  At
the one compile each (program, feed signature) already pays — the cold
dispatch in ``Executor.run`` / ``ParallelExecutor.run`` — the executor
calls :func:`capture` with the jitted step and its concrete arguments.
Capture AOT-compiles via ``jit.lower(args).compile()``, reads the
compiled module's ``cost_analysis()`` (flops, bytes accessed) and
``memory_analysis()`` (argument/output/temp/generated-code/alias bytes),
and hands the executable back to the executor, which dispatches every
step of that signature through it — so the capture IS the one compile,
**zero additional lowerings or backend compiles** (jax's AOT and jit
call paths do NOT share a backend-compile cache, so compiling through
the jit call and separately analyzing would pay the XLA pipeline
twice).  The AOT call path costs a few microseconds over the C++ jit
fast path, paid only while capture is enabled (monitor on, or the
preflight explicitly forced).

Profiles land in a process-global registry keyed by
``compile_cache.program_fingerprint`` + feed signature.  Per-program
*step accounting* (steps, wall clock, examples) accumulates via
:func:`note_step`, fed from ``monitor.record_step``; :func:`report_rows`
joins the two into the per-program table (flops, bytes, peak HBM, steps,
wall-clock share, ground-truth MFU from the compiler's own flop count —
the ``est_mfu`` heuristic's replacement) that ``tools/program_report.py``
renders from a live registry or a JSONL log.

**HBM preflight**: before the first dispatch of a newly compiled
program, the estimated peak device memory (arguments + outputs + temps +
generated code - aliased/donated) is compared against the device's
reported capacity (``device.memory_stats()['bytes_limit']``, overridable
via ``FLAGS_preflight_hbm_bytes``).  Over capacity →
``warnings.warn`` with the per-buffer-class breakdown, or
:class:`PreflightOOMError` under ``FLAGS_preflight_oom=strict`` —
instead of letting XLA OOM mid-run.
"""

import contextlib
import threading
import time
import warnings

__all__ = [
    "PreflightOOMError", "ProgramProfile", "capture_enabled", "capture",
    "store_compiled", "get", "profiles", "note_step", "accounting",
    "probe_accounting", "probe_active", "probe_totals", "summary_for",
    "report_rows", "render_table", "reset", "reset_accounting",
    "DEVICE_PEAKS", "bf16_peak_tflops",
]

# Published per-chip peaks keyed by jax's ``device_kind`` — the one table
# behind every MFU / roofline denominator in the repo (program report,
# bench.py, chip_smoke.py).  A device that is not listed yields NO MFU,
# never a default: one assumed peak for any device is how a virtual CPU
# mesh once printed an MFU against a TPU's peak.
# Source: Google Cloud TPU documentation, "TPU v5e" (system architecture).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbps": 819.0},
}


def bf16_peak_tflops(device_kind):
    """bf16 matmul peak (TFLOP/s) for ``device_kind``; None = unknown
    device, no MFU."""
    row = DEVICE_PEAKS.get(device_kind)
    return row["bf16_tflops"] if row else None


_mu = threading.Lock()
# (fingerprint, feed_sig, fetch_names, trace_flags, kind, partition) ->
# ProgramProfile: different fetch sets — and different trace-time flag
# choices (kernel selection etc., mirroring compile_cache.trace_key) —
# lower the same program+feeds to different XLA modules with different
# flops/bytes, so both are part of the identity.  ``partition`` is the
# executor's mesh/sharding identity: the same program compiled
# replicated and fsdp-sharded has per-device argument/peak-HBM bytes
# differing by ~N, and the two must not clobber each other's slot
# (the replicated-vs-fsdp A/B rung is exactly this pattern).
_profiles = {}
_acct = {}          # fingerprint -> {steps, wall_s, examples, kind}
# auto-tuner probe dispatches accumulate HERE, never in _acct: a probe
# of the same fingerprint the run later trains steady-state must not
# blend its wall clock into the steady row's share/MFU
_acct_probe = {}
_warned = set()     # (fingerprint, feed_sig, partition) preflight warns issued


class PreflightOOMError(RuntimeError):
    """Estimated peak device memory exceeds capacity
    (``FLAGS_preflight_oom=strict``)."""


class ProgramProfile:
    """One compiled (program, feed signature, fetch set)'s cost/memory
    profile, as captured from the XLA compiled module's own accounting."""

    __slots__ = ("fingerprint", "feed_sig", "fetch_names", "kind", "ts",
                 "cost", "flops",
                 "bytes_accessed", "argument_bytes", "output_bytes",
                 "temp_bytes", "generated_code_bytes", "alias_bytes",
                 "peak_hbm_bytes", "device", "device_kind", "partition")

    def __init__(self, fingerprint, feed_sig, kind, cost=None, flops=0.0,
                 bytes_accessed=0.0, argument_bytes=0, output_bytes=0,
                 temp_bytes=0, generated_code_bytes=0, alias_bytes=0,
                 peak_hbm_bytes=0, device=None, fetch_names=(),
                 partition=None, device_kind=None):
        self.fingerprint = fingerprint
        self.feed_sig = tuple(feed_sig)
        self.fetch_names = tuple(fetch_names)
        self.kind = kind
        self.partition = partition
        self.ts = time.time()
        self.cost = dict(cost or {})
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.argument_bytes = int(argument_bytes)
        self.output_bytes = int(output_bytes)
        self.temp_bytes = int(temp_bytes)
        self.generated_code_bytes = int(generated_code_bytes)
        self.alias_bytes = int(alias_bytes)
        self.peak_hbm_bytes = int(peak_hbm_bytes)
        self.device = device
        self.device_kind = device_kind

    def breakdown(self):
        """Per-buffer-class bytes, the preflight diagnostic's currency."""
        return {"argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "temp_bytes": self.temp_bytes,
                "generated_code_bytes": self.generated_code_bytes,
                "alias_bytes": self.alias_bytes,
                "peak_hbm_bytes": self.peak_hbm_bytes}

    def as_dict(self):
        d = {"fingerprint": self.fingerprint,
             "kind": self.kind,
             "feed_sig": [[n, list(s), dt] for n, s, dt in self.feed_sig],
             "fetch_names": list(self.fetch_names),
             "flops": self.flops,
             "bytes_accessed": self.bytes_accessed,
             "device": self.device,
             "device_kind": self.device_kind,
             "partition": str(self.partition) if self.partition else None}
        d.update(self.breakdown())
        return d


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------

def _flag(name, default):
    from .. import flags

    try:
        return flags.flag(name)
    except KeyError:
        return default


def _preflight_mode():
    v = str(_flag("preflight_oom", "auto")).strip().lower()
    if v in ("off", "0", "false", "no", "none", ""):
        return "off"
    if v == "strict":
        return "strict"
    return "auto" if v == "auto" else "warn"


def capture_enabled():
    """Whether the executors should capture profiles at the cold
    dispatch.  True when the monitor is on, or when the operator forced
    the HBM preflight (``FLAGS_preflight_oom=warn|strict``) on an
    unmonitored run.  Checked only on compile steps, never per warm
    step: an unmonitored, un-preflighted process runs the executors'
    unmodified jit path."""
    from . import enabled

    return enabled() or _preflight_mode() in ("warn", "strict")


def capture(fingerprint, feed_sig, jit_fn, args, device=None,
            kind="executor", fetch_names=(), partition=None):
    """AOT-compile the step this (jitted fn, concrete args) maps to,
    profile it, and run the HBM preflight — called by the executors at
    the cold dispatch, *before* the step executes.  The returned
    ``jax.stages.Compiled`` is THE executable for this signature: the
    executor dispatches every step of it through the returned object, so
    the one compile that was always going to happen simply happens here
    — where its ``cost_analysis()``/``memory_analysis()`` are readable —
    instead of inside the jit call.  Zero additional lowerings or
    backend compiles; the per-step cost is the AOT call path's few
    microseconds over the C++ jit fast path, paid only while capture is
    enabled.

    Returns the Compiled executable.  A compile failure (Mosaic, HBM,
    an unsupported op) propagates from HERE, the step's one compile —
    swallowing it would pay the failing compile a second time inside the
    jit call and report it from the wrong place.  Raises
    :class:`PreflightOOMError` under ``FLAGS_preflight_oom=strict`` when
    the memory estimate exceeds capacity; failures of the cost/memory
    *analyses* never break the step.
    """
    compiled = jit_fn.lower(*args).compile()
    prof = store_compiled(fingerprint, feed_sig, compiled, device=device,
                          kind=kind, fetch_names=fetch_names,
                          partition=partition)
    if prof is not None:
        _preflight(prof, device)
    return compiled


def store_compiled(fingerprint, feed_sig, compiled, device=None,
                   kind="executor", fetch_names=(), partition=None):
    """Extract cost/memory analyses from a ``jax.stages.Compiled`` and
    store the profile (shared by :func:`capture` and the explicit
    ``Executor.cost_analysis`` fallback path).  No preflight here."""
    cost = {}
    try:
        cost = dict(compiled.cost_analysis() or {})
    except Exception:  # noqa: BLE001
        pass
    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {k: int(getattr(ma, k + "_size_in_bytes", 0) or 0)
                   for k in ("argument", "output", "temp",
                             "generated_code", "alias")}
    except Exception:  # noqa: BLE001
        pass
    if not cost and not mem:
        return None
    # donated (aliased) buffers are counted in both arguments and
    # outputs but occupy one allocation; generated code (constants,
    # scratch tables) lives in HBM too
    peak = (mem.get("argument", 0) + mem.get("output", 0)
            + mem.get("temp", 0) + mem.get("generated_code", 0)
            - mem.get("alias", 0))
    prof = ProgramProfile(
        fingerprint, feed_sig, kind, cost=cost,
        flops=cost.get("flops", 0.0) or 0.0,
        bytes_accessed=cost.get("bytes accessed", 0.0) or 0.0,
        argument_bytes=mem.get("argument", 0),
        output_bytes=mem.get("output", 0),
        temp_bytes=mem.get("temp", 0),
        generated_code_bytes=mem.get("generated_code", 0),
        alias_bytes=mem.get("alias", 0),
        peak_hbm_bytes=max(0, peak),
        device=str(getattr(device, "platform", device) or "") or None,
        device_kind=getattr(device, "device_kind", None),
        fetch_names=fetch_names, partition=partition)
    with _mu:
        _profiles[(fingerprint, prof.feed_sig, prof.fetch_names,
                   _trace_flags(), kind, partition)] = prof
    from . import log_event

    log_event(dict(prof.as_dict(), event="program_profile", ts=prof.ts))
    return prof


# ---------------------------------------------------------------------------
# HBM preflight
# ---------------------------------------------------------------------------

def _device_capacity(device):
    """Device memory capacity in bytes: ``FLAGS_preflight_hbm_bytes``
    when set (tests, or backends that misreport), else the backend's
    ``memory_stats()['bytes_limit']``; None = unknown (preflight skips)."""
    override = int(_flag("preflight_hbm_bytes", 0))
    if override > 0:
        return override
    if device is None:
        return None
    try:
        ms = device.memory_stats()
    except Exception:  # noqa: BLE001 — a backend without memory stats
        return None
    if not ms:
        return None
    return ms.get("bytes_limit") or None


def _fmt_mib(n):
    """Adaptive byte formatting (toy CPU-test programs are KiB-scale,
    real steps GiB-scale; '0.0 MiB' helps neither)."""
    n = int(n)
    if n >= 1 << 30:
        return "%.2f GiB" % (n / (1 << 30))
    if n >= 1 << 20:
        return "%.1f MiB" % (n / (1 << 20))
    if n >= 1 << 10:
        return "%.1f KiB" % (n / (1 << 10))
    return "%d B" % n


def _preflight(prof, device):
    mode = _preflight_mode()
    if mode == "off":
        return
    # "auto" = ride along on monitor-gated captures in warn mode
    if mode == "auto":
        mode = "warn"
    cap = _device_capacity(device)
    if not cap or prof.peak_hbm_bytes <= cap:
        return
    msg = ("HBM preflight: program %s (%s) estimated peak device memory "
           "%s exceeds capacity %s — arguments %s + outputs %s + temps "
           "%s + generated code %s - aliased(donated) %s"
           % (prof.fingerprint[:12], prof.kind,
              _fmt_mib(prof.peak_hbm_bytes), _fmt_mib(cap),
              _fmt_mib(prof.argument_bytes), _fmt_mib(prof.output_bytes),
              _fmt_mib(prof.temp_bytes),
              _fmt_mib(prof.generated_code_bytes),
              _fmt_mib(prof.alias_bytes)))
    from . import enabled, log_event, registry

    if enabled():
        registry().counter("monitor/preflight_oom").inc()
        log_event({"event": "preflight_oom", "ts": time.time(),
                   "fingerprint": prof.fingerprint, "mode": mode,
                   "capacity_bytes": int(cap),
                   "breakdown": prof.breakdown()})
    if mode == "strict":
        raise PreflightOOMError(msg)
    key = (prof.fingerprint, prof.feed_sig, prof.partition)
    with _mu:
        if key in _warned:
            return
        _warned.add(key)
    warnings.warn(msg, stacklevel=3)


# ---------------------------------------------------------------------------
# registry access + step accounting
# ---------------------------------------------------------------------------

def _trace_flags():
    """Trace-time flag choices baked into a lowering (the same tuple
    compile_cache.trace_key carries): two kernel-selection variants of
    one program must not share a profile slot."""
    from .. import compile_cache

    return compile_cache.trace_flag_values()


def get(fingerprint, feed_sig=None, kind="executor", fetch_names=(),
        partition=None):
    """Profile for (fingerprint, feed_sig, fetch_names, current trace
    flags, kind, partition); with ``feed_sig=None`` the most recently
    captured profile for the fingerprint regardless of signature/fetch
    set/flags/kind/partition."""
    with _mu:
        if feed_sig is not None:
            return _profiles.get((fingerprint, tuple(feed_sig),
                                  tuple(fetch_names), _trace_flags(),
                                  kind, partition))
        best = None
        for key, p in _profiles.items():
            if key[0] == fingerprint and (best is None or p.ts >= best.ts):
                best = p
        return best


def profiles():
    with _mu:
        return list(_profiles.values())


# auto-tuner probe window depth: steps recorded while a probe window is
# open tag their accounting entries, so a tuner's throwaway candidate
# dispatches never blend into the per-program report's wall-share/MFU
# rows (the same program fingerprint later running steady-state clears
# the tag — "probe" means probe-ONLY)
_probe_depth = [0]


@contextlib.contextmanager
def probe_accounting():
    """Mark the dynamic extent of an auto-tuner probe: every step
    recorded inside is PROBE work.  Re-entrant (nested tuners)."""
    with _mu:
        _probe_depth[0] += 1
    try:
        yield
    finally:
        with _mu:
            _probe_depth[0] -= 1


def probe_active():
    """Whether an auto-tuner probe window is open (see
    :func:`probe_accounting`)."""
    return _probe_depth[0] > 0


def note_step(fingerprint, step_seconds, examples, kind="executor"):
    """Fold one completed step into the per-program accounting (called
    from ``monitor.record_step`` when a fingerprint is attached).
    Steps inside a :func:`probe_accounting` window land in a SEPARATE
    probe bucket — a tuner probing the very fingerprint the run then
    trains steady-state must not blend its candidates' wall clock into
    the steady row."""
    with _mu:
        acct = _acct_probe if probe_active() else _acct
        a = acct.get(fingerprint)
        if a is None:
            a = acct[fingerprint] = {"steps": 0, "wall_s": 0.0,
                                     "examples": 0, "kind": kind}
        a["steps"] += 1
        a["wall_s"] += float(step_seconds or 0.0)
        a["examples"] += int(examples or 0)
        a["kind"] = kind


def accounting():
    """Steady-state step accounting (probe work excluded; see
    :func:`probe_totals`)."""
    with _mu:
        return {fp: dict(a) for fp, a in _acct.items()}


def probe_totals():
    """The tuner-probe accounting bucket, keyed like
    :func:`accounting`."""
    with _mu:
        return {fp: dict(a) for fp, a in _acct_probe.items()}


def summary_for(fingerprint):
    """Compact profile + accounting summary for one program — the
    watchdog attaches this for the last dispatched program so a stall
    report names the suspect."""
    if not fingerprint:
        return None
    prof = get(fingerprint)
    with _mu:
        a = dict(_acct.get(fingerprint) or {})
    out = {"fingerprint": fingerprint[:12]}
    if a:
        out.update({"steps": a["steps"],
                    "wall_s": round(a["wall_s"], 3)})
    if prof is not None:
        out.update({"flops": prof.flops,
                    "bytes_accessed": prof.bytes_accessed,
                    "peak_hbm_bytes": prof.peak_hbm_bytes})
    return out


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def _lowering_columns(records):
    """{fp12: the report's columns from that program's compile records}:
    how often it was lowered and why (``first,feed_signature*2`` — a
    retrace storm reads as its cause), what set-up paid for it, phase
    by phase, summed over the records, and what its dense products must do
    a step (``op_work`` of its newest record that holds one: ``{"<op
    type>:<part>": [flops, least bytes]}``)."""
    out = {}
    for r in records:
        c = out.setdefault(r["fingerprint"], {
            "lowerings": 0, "causes": {}, "build_s": 0.0, "trace_s": 0.0,
            "lowering_s": 0.0, "executable_s": 0.0, "op_work": {}})
        if r.get("op_work"):
            c["op_work"] = {}
            for row in r["op_work"]:
                total = c["op_work"].setdefault("%s:%s" % (row[1], row[2]),
                                                [0, 0])
                total[0] += row[3]
                total[1] += row[4]
        c["lowerings"] += 1
        c["causes"][r["cause"]] = c["causes"].get(r["cause"], 0) + 1
        c["build_s"] += r["build_s"]
        c["trace_s"] += r["analyze_s"] + r["program_trace_s"] \
            + r["jax_trace_s"]
        c["lowering_s"] += r["lowering_s"]
        c["executable_s"] += r["executable_s"]
    for c in out.values():
        c["cause"] = ",".join(k if n == 1 else "%s*%d" % (k, n)
                              for k, n in c.pop("causes").items())
    return out


def report_rows(peak_tflops=None, profiles_by_fp=None, acct_by_fp=None,
                probe_acct_by_fp=None, compile_records=None):
    """Join profiles + step accounting + compile records into per-program
    report rows, sorted by wall-clock share.  ``profiles_by_fp``/
    ``acct_by_fp``/``probe_acct_by_fp``/``compile_records`` override the
    live registry and ``compile_cache.compile_log()`` (the JSONL-replay
    path of ``tools/program_report.py``).

    Tuner-probe work (the separate :func:`probe_totals` bucket) renders
    as its OWN rows flagged ``probe=True`` — excluded from the
    wall-share denominator and the MFU column, so throwaway candidate
    dispatches never dilute the steady-state attribution the report
    exists for (even when they share a fingerprint with steady rows).

    The MFU denominator is the explicit ``peak_tflops`` when given, else
    the :data:`DEVICE_PEAKS` row of the device that compiled the
    program; a program from a device not in the table gets no MFU."""
    if acct_by_fp is None:
        acct_by_fp = accounting()
        if probe_acct_by_fp is None:
            probe_acct_by_fp = probe_totals()
    probe_acct_by_fp = probe_acct_by_fp or {}
    if profiles_by_fp is None:
        profiles_by_fp = {}
        for p in profiles():
            cur = profiles_by_fp.get(p.fingerprint)
            if cur is None or p.ts >= cur.ts:
                profiles_by_fp[p.fingerprint] = p
    if compile_records is None:
        from .. import compile_cache

        compile_records = compile_cache.compile_log()
    lowered = _lowering_columns(compile_records)
    fps = set(acct_by_fp) | set(profiles_by_fp)
    total_wall = sum((acct_by_fp.get(fp) or {}).get("wall_s", 0.0)
                     for fp in fps)

    def _row(fp, a, p, probe):
        steps = int(a.get("steps", 0))
        wall = float(a.get("wall_s", 0.0))
        row = {"fingerprint": fp, "fp12": fp[:12],
               "kind": a.get("kind") or (p.kind if p is not None else ""),
               "steps": steps, "wall_s": round(wall, 6),
               "wall_share": 0.0 if probe else round(wall / total_wall, 4)
               if total_wall > 0 else 0.0,
               "examples": int(a.get("examples", 0)),
               "flops_per_step": float(p.flops) if p is not None else None,
               "bytes_per_step": float(p.bytes_accessed)
               if p is not None else None,
               "peak_hbm_bytes": int(p.peak_hbm_bytes)
               if p is not None else None}
        row.update(lowered.get(fp[:12]) or {
            "lowerings": 0, "cause": "", "build_s": None, "trace_s": None,
            "lowering_s": None, "executable_s": None, "op_work": {}})
        if probe:
            row["probe"] = True
            row["mfu"] = None
        else:
            peak = peak_tflops or (
                bf16_peak_tflops(p.device_kind) if p is not None else None)
            row["mfu"] = round(p.flops * steps / wall / (peak * 1e12), 4) \
                if peak and wall > 0 and p.flops else None
        return row

    rows = [_row(fp, acct_by_fp.get(fp) or {}, profiles_by_fp.get(fp),
                 False) for fp in fps]
    rows += [_row(fp, a, profiles_by_fp.get(fp), True)
             for fp, a in probe_acct_by_fp.items()]
    rows.sort(key=lambda r: (-r["wall_s"], r["fingerprint"]))
    return rows


def render_table(rows):
    """Fixed-width text table of :func:`report_rows` output (shared by
    the CLI and in-process reporting)."""
    hdr = "%-12s %-10s %8s %10s %7s %12s %12s %10s %7s" \
          " %8s %8s %8s %8s  %s" % (
              "program", "executor", "steps", "wall(s)", "share",
              "GFLOP/step", "GB/step", "peakHBM", "MFU",
              "build(s)", "trace(s)", "lower(s)", "exec(s)", "lowered")
    hdr += "  products' work a step (TFLOP/least GB by op:part)"
    lines = [hdr, "-" * len(hdr)]

    def secs(v):
        return "%.3f" % v if v is not None else "-"
    for r in rows:
        kind = ("probe:" + (r["kind"] or "?")) if r.get("probe") \
            else (r["kind"] or "?")
        lines.append("%-12s %-10s %8d %10.3f %6.1f%% %12s %12s %10s %7s"
                     " %8s %8s %8s %8s  %-7s  %s" % (
            r["fp12"], kind[:10], r["steps"], r["wall_s"],
            100.0 * r["wall_share"],
            "%.3f" % (r["flops_per_step"] / 1e9)
            if r["flops_per_step"] is not None else "-",
            "%.4f" % (r["bytes_per_step"] / 1e9)
            if r["bytes_per_step"] is not None else "-",
            _fmt_mib(r["peak_hbm_bytes"])
            if r["peak_hbm_bytes"] is not None else "-",
            "%.3f" % r["mfu"] if r["mfu"] is not None else "-",
            secs(r.get("build_s")), secs(r.get("trace_s")),
            secs(r.get("lowering_s")), secs(r.get("executable_s")),
            r.get("cause") or "-",
            " ".join("%s %.4g/%.4g" % (k, f / 1e12, b / 1e9) for k, (f, b)
                     in sorted((r.get("op_work") or {}).items())) or "-"))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def reset_accounting():
    """Drop step accounting but keep captured profiles (they are compile
    artifacts, still valid across a monitor enable/disable flip)."""
    with _mu:
        _acct.clear()
        _acct_probe.clear()


def reset():
    """Drop everything (tests)."""
    with _mu:
        _profiles.clear()
        _acct.clear()
        _acct_probe.clear()
        _warned.clear()
