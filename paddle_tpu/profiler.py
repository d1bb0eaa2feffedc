"""Profiler: host spans + device (XLA) tracing with chrome-trace export.

Parity: reference ``platform/profiler.{h,cc}`` (RecordEvent spans wrapping
every op run), ``platform/device_tracer`` (CUPTI kernel timestamps),
``tools/timeline.py`` (chrome://tracing export), and the Python context
managers ``fluid/profiler.py:221`` — TPU-native: device-side tracing
delegates to ``jax.profiler`` (XPlane/TensorBoard), host-side named spans
are collected here and exported as chrome-trace JSON directly.

One span, three sinks.  Every ``RecordEvent`` / ``mark_event`` is also a
``jax.profiler.TraceAnnotation("pt/<name>")``: whenever ANY jax profiler
trace is being taken (``start_profiler(trace_dir=...)``, a bare
``jax.profiler.start_trace``, the benchmark's traced run) the span lands on
the calling thread's line of the ``/host:CPU`` plane of the ``.xplane.pb``,
on the device trace's time base.  The ``pt/`` prefix is the annotation's
only: the Python list (chrome export, ``tools/trace_summary.py``) and the
monitor's ``span/<name>`` histograms keep the bare name.
"""

import contextlib
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

from . import monitor

__all__ = [
    "RecordEvent", "record_event", "mark_event", "build_pass", "profiler",
    "start_profiler", "stop_profiler", "reset_profiler", "is_profiling",
    "export_chrome_tracing", "summarize_events", "cuda_profiler",
    "npu_profiler",
]

_state = threading.local()
_events = []
_events_lock = threading.Lock()
_enabled = [False]
_jax_trace_dir = [None]
# tid -> thread name at the time the thread last emitted an event, for
# the chrome-trace M-phase thread_name metadata (dispatch/prefetch
# worker threads are labeled in the timeline instead of raw tids)
_thread_names = {}
# what a span or mark is called in a jax profiler trace: "pt/" + its name
ANNOTATION_PREFIX = "pt/"


def _now_us():
    return time.perf_counter_ns() / 1000.0


def _append_event(name, ts, dur, args=None):
    tid = threading.get_ident()
    ev = {
        "name": name,
        "ts": ts,
        "dur": dur,
        "ph": "X",
        "pid": os.getpid(),
        "tid": tid,
    }
    if args:
        ev["args"] = args
    with _events_lock:
        _thread_names[tid] = threading.current_thread().name
        _events.append(ev)


def is_profiling():
    """True while a profiler session is active (the executors use this
    to decide whether span correlation args are worth computing)."""
    return _enabled[0]


class RecordEvent:
    """RAII span (reference profiler.h:89 RecordEvent).

    ``__enter__`` LATCHES the profiler/monitor enabled states: a span
    that straddles ``stop_profiler`` is kept (it was started under the
    session and measures real work of it), a span started while both are
    disabled skips timing entirely — ``__exit__`` never re-decides
    post-hoc.  Completed spans double-publish into the monitor's
    ``span/<name>`` histograms whenever the monitor is on, so the two
    observability layers agree with or without a profiler session.

    Independently of both, the span is a ``TraceAnnotation("pt/<name>")``:
    TraceMe decides by itself (one atomic load) whether a jax trace is
    being taken.  With nothing on, a span costs one object and two C calls
    (PERF.md has the nanoseconds) — keep it out of per-op loops.
    """

    def __init__(self, name, args=None):
        """``args`` (optional dict) lands in the chrome-trace event's
        ``args`` field — the executors tag their dispatch/compile spans
        with ``{run_id, fingerprint, step}`` so the trace, the JSONL
        log, and /metrics can be correlated per program."""
        self.name = name
        self.args = args
        self.t0 = None
        self._prof = False
        self._mon = False
        self._annotation = TraceAnnotation(ANNOTATION_PREFIX + name)

    def __enter__(self):
        self._prof = _enabled[0]
        self._mon = monitor.enabled()
        if self._prof or self._mon:
            self.t0 = _now_us()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        if self.t0 is None:
            return False
        dur = _now_us() - self.t0
        if self._prof:
            _append_event(self.name, self.t0, dur, self.args)
        if self._mon:
            # args ride along so the goodput ledger sees the producer's
            # bucket hint (executors tag their cold/warm step spans)
            monitor.observe_span(self.name, dur, self.args)
        self.t0 = None
        return False


record_event = RecordEvent


@contextlib.contextmanager
def build_pass(program, name):
    """One whole-program pass of build over ``program`` (``append_backward``,
    ``minimize``, ``mixed_precision``): a ``build/<name>`` span, and the
    pass's own seconds — less those of a pass nested in it, as
    ``append_backward`` is in ``minimize`` — added to ``program._build_s``,
    which the program's first compile record takes as its ``build_s``
    (``compile_cache.open_record``)."""
    outer = getattr(_state, "nested_ns", None)
    _state.nested_ns = 0
    t0 = time.perf_counter_ns()
    try:
        with RecordEvent("build/" + name):
            yield
    finally:
        whole = time.perf_counter_ns() - t0
        own = whole - _state.nested_ns
        _state.nested_ns = None if outer is None else outer + whole
        program._build_s[name] = program._build_s.get(name, 0.0) + own / 1e9


def mark_event(name):
    """Instantaneous event (zero-duration span): cache hits/misses and
    other point occurrences, countable in the summary and visible in the
    chrome trace next to the ``RecordEvent`` spans.  Double-publishes as
    a ``mark/<name>`` monitor counter when the monitor is on."""
    with TraceAnnotation(ANNOTATION_PREFIX + name):
        pass
    if monitor.enabled():
        monitor.mark(name)
    if not _enabled[0]:
        return
    _append_event(name, _now_us(), 0.0)


def start_profiler(state="All", trace_dir=None):
    """state ∈ {CPU, GPU, All} for parity; device tracing uses
    jax.profiler when a trace_dir is given.

    With a ``trace_dir`` one session leaves TWO files, on two clocks:
    ``stop_profiler``'s ``profile_path`` is the chrome-trace JSON of the
    host spans alone, on ``time.perf_counter``; ``<trace_dir>/plugins/
    profile/<time>/*.xplane.pb`` is jax's trace — device operations under
    their Fluid scopes and module names AND the same host spans as
    ``pt/<name>`` annotations, on the profiler's own clock.  The two clocks
    share no origin: correlate host with device in the ``.xplane.pb``."""
    _enabled[0] = True
    if trace_dir and state in ("GPU", "All"):
        import jax

        _jax_trace_dir[0] = trace_dir
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    _enabled[0] = False
    if _jax_trace_dir[0]:
        import jax

        jax.profiler.stop_trace()
        _jax_trace_dir[0] = None
    if profile_path:
        export_chrome_tracing(profile_path)
    _print_summary(sorted_key)


def reset_profiler():
    with _events_lock:
        _events.clear()


def export_chrome_tracing(path):
    """Write collected host spans as chrome://tracing JSON
    (tools/timeline.py parity).  M-phase metadata events label the
    process and every emitting thread (main loop, prefetch producers,
    monitor threads) so the timeline shows names instead of raw tids."""
    with _events_lock:
        events = list(_events)
        tnames = dict(_thread_names)
    pids = sorted({e["pid"] for e in events})
    # the run correlation id rides in the process metadata AND the
    # top-level metadata dict, matching the run_id each JSONL record and
    # the /metrics exposition carry — one id across all three sinks
    meta = [{"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": "paddle_tpu",
                      "run_id": monitor.run_id()}} for pid in pids]
    for (pid, tid) in sorted({(e["pid"], e["tid"]) for e in events}):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid,
                     "args": {"name": tnames.get(tid, "tid-%d" % tid)}})
    trace_meta = {"run_id": monitor.run_id()}
    gp = monitor.goodput_ledger()
    if gp.steps:
        # the run's wall-clock attribution rides in the trace metadata,
        # so a shipped trace carries its own goodput summary alongside
        # the spans it was derived from
        trace_meta["goodput"] = gp.summary()
    # request lanes (ISSUE 17): buffered trace spans render one lane
    # per request under a 'serving requests' process group — same
    # perf_counter timebase as the host spans, so the exported file
    # opens in Perfetto with requests aligned against the dispatches
    # that served them
    try:
        tr_events, tr_meta = monitor.tracing.chrome_events()
    except Exception:  # noqa: BLE001 — export never fails on telemetry
        tr_events, tr_meta = [], []
    payload = {"traceEvents": meta + tr_meta + events + tr_events,
               "displayTimeUnit": "ms", "metadata": trace_meta}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


def summarize_events(events, sorted_key=None, top=50):
    """Per-name total/calls/avg/max table over chrome-trace events (the
    ``X``-phase ones; ``dur`` in microseconds).  Shared by the live
    ``stop_profiler`` summary and the offline ``tools/trace_summary.py``
    CLI, so both print the identical format.  ``top`` caps the row
    count.  Tolerates foreign traces: events missing ``dur`` (counter/
    instant events re-exported as X) count as zero-duration."""
    totals = {}
    for e in events:
        if not isinstance(e, dict) or e.get("ph", "X") != "X" \
                or "name" not in e:
            continue
        dur = e.get("dur", 0.0) or 0.0
        t = totals.setdefault(e["name"], [0.0, 0, 0.0])
        t[0] += dur
        t[1] += 1
        t[2] = max(t[2], dur)
    rows = [
        (name, tot / 1000.0, cnt, tot / cnt / 1000.0, mx / 1000.0)
        for name, (tot, cnt, mx) in totals.items()
    ]
    key = {"total": 1, "calls": 2, "ave": 3, "max": 4}.get(sorted_key, 1)
    rows.sort(key=lambda r: r[key], reverse=True)
    lines = ["%-40s %12s %8s %12s %12s" % ("Event", "total(ms)", "calls",
                                           "avg(ms)", "max(ms)")]
    for name, tot, cnt, avg, mx in rows[:top]:
        lines.append("%-40s %12.3f %8d %12.3f %12.3f"
                     % (name, tot, cnt, avg, mx))
    return "\n".join(lines)


def _print_summary(sorted_key=None):
    with _events_lock:
        events = list(_events)
    if not events:
        return
    print(summarize_events(events, sorted_key))


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             trace_dir=None):
    """Context manager parity with fluid.profiler.profiler (profiler.py:221)."""
    reset_profiler()
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):
    """Reference nvprof hook (profiler.py:39); on TPU this aliases to the
    jax trace-based profiler."""
    with profiler():
        yield


npu_profiler = cuda_profiler
