"""Compilation caching: persistent XLA cache + program-fingerprint trace cache.

Two layers, addressing the two costs a repeated step shape pays:

* **Persistent XLA compilation cache** (``enable_persistent_cache``): the
  jax/XLA on-disk executable cache, keyed by HLO fingerprint.  Survives
  process restarts — bench-ladder rungs, chip runs, and training restarts
  with the same program+signature skip XLA's optimization pipeline and
  deserialize the executable instead.  Metadata (scope names, source
  lines) is NOT part of jax's key; the module name is, and ``name_step``
  derives it from the program's fingerprint, so a renamed Fluid variable
  or op never reads another tree's names back (only a respelling of
  ``registry.fluid_scope_name`` itself would: clear the directory then).
  Where it lives is decided by
  ONE resolver, ``persistent_cache_dir``: ``JAX_COMPILATION_CACHE_DIR`` when
  the environment sets it (nothing in code overrides that), else
  ``FLAGS_compile_cache_dir``, else — for chip entry points only — one
  fixed directory inside the checkout.  A bare ``import paddle_tpu``
  turns nothing on.
* **Process-global trace cache** (``lookup``/``store``): re-tracing is a
  host-side cost the XLA cache cannot amortize (jaxpr building walks every
  op's compute function).  Executors cache their jitted step callables here
  keyed by a *structural* program fingerprint, so a second Executor /
  ParallelExecutor instance over the same program (bench reruns inside one
  process, evaluator clones, tests) reuses the traced+jitted callable and
  performs zero lowerings.

``stats()`` exposes hit/miss/lowering counters.  ``compile_log()`` holds
one record per lowering — which step program, why it was lowered, whether
the trace cache had it, and what each phase of its first call cost (build
passes, program trace, jax trace with the Pallas kernels' traces inside,
jaxpr -> MLIR, executable compiled or read from the persistent cache) —
written on the cold path only: ``executor.StepPath`` opens it when an
executor misses its own cache and closes it when the cold call returns,
and jax's own ``jax.monitoring`` durations land in the record open on the
calling thread (everything else in ``outside_compiles()``).
``count_compiles()`` is the one "nothing new was lowered or compiled"
counter (this module's lowerings next to jax's own compile events), shared
by the tests and ``chip_smoke.py``.
"""

import collections
import contextlib
import hashlib
import os
import threading
import time

from . import monitor
from .profiler import _append_event, _now_us, is_profiling

__all__ = [
    "program_fingerprint", "program_label", "name_step", "trace_key",
    "trace_flag_values", "lookup",
    "store", "stats", "reset_stats", "clear", "note_kernel_body",
    "note_kernel_trace", "note_op_work", "open_record", "note_phase",
    "close_record",
    "compile_log", "outside_compiles",
    "count_compiles", "persistent_cache_dir", "enable_persistent_cache",
    "rescope_persistent_cache", "CHECKOUT_CACHE_DIR",
]


def trace_flag_values():
    """Values of every FLAGS_* knob that alters the traced jaxpr (whether
    the ops' rules may pick Pallas kernels, BN variance form, the guard and
    the probe).  Every key under which a trace/compiled step is cached —
    the executors' per-instance keys AND the trace-cache keys here — must
    include this tuple, or set_flags between runs serves a stale trace."""
    from . import flags, guardian
    from .monitor import health

    # the guardian's in-graph skip guard wraps the traced step (extra
    # ok fetch + state selects), so its enablement is part of the jaxpr
    # identity: flipping FLAGS_guardian re-lowers instead of serving an
    # unguarded (or guarded) stale trace.  Same for the health probe
    # (extra grad fetches + the stats reduction); its CADENCE is host-
    # side publication only and deliberately not keyed.
    return (flags.flag("pallas_kernels"), flags.flag("bn_two_pass"),
            guardian.skip_guard_enabled(), health.probe_enabled())


_mu = threading.Lock()
# LRU of jitted step entries: the jitted callables keep their traced
# programs alive, so the cache is bounded (a bench ladder lowers dozens
# of programs, not thousands)
_MAX_ENTRIES = 64
_TRACE_CACHE = collections.OrderedDict()
_STATS = {"trace_hits": 0, "trace_misses": 0, "lowerings": 0}
# lowering counts per short program fingerprint: a retrace storm in the
# stats/StepStats names WHICH program is churning, not just that one is
_LOWERINGS_BY_FP = {}
# "<op type>:<body>" -> times traced: which compute body ("pallas",
# "xla", "ring") an op with a hand-written alternative lowered to
_KERNEL_BODIES = {}
# kernel -> {"sites": calls that reached it, "traces": jaxprs it made}
_KERNEL_TRACES = {}
_persistent_dir = [None]
_persistent_base = [None]     # resolved dir, before any world scoping


# ---------------------------------------------------------------------------
# program fingerprint
# ---------------------------------------------------------------------------

def program_fingerprint(program):
    """Stable structural digest of a Program: every block's ops (type,
    slot bindings, attrs) and vars (shape/dtype/persistability), plus the
    seed and AMP policy.  Cached on the program keyed by ``_version`` so
    the per-step cost is one attribute read; structural mutation (op
    append/insert, rename) bumps ``_version`` and re-hashes."""
    # memo key carries the AMP policy too: bf16_program_guard swaps
    # _amp_policy WITHOUT a structural mutation (no _version bump), and
    # serving the fp32 trace under the guard would silently drop AMP
    amp = getattr(program, "_amp_policy", None)
    memo_key = (program._version, None if amp is None else repr(amp))
    cached = getattr(program, "_fp_cache", None)
    if cached is not None and cached[0] == memo_key:
        return cached[1]
    h = hashlib.sha1()
    try:
        h.update(program.to_json().encode())
    except (TypeError, ValueError):
        # an op attr that doesn't serialize (sub-block handle, callable):
        # fall back to repr, which is stable within the process
        for blk in program.blocks:
            for op in blk.ops:
                h.update(repr((op.type, sorted(op.inputs.items()),
                               sorted(op.outputs.items()),
                               sorted((k, repr(v))
                                      for k, v in op.attrs.items()))
                              ).encode())
            for n, v in blk.vars.items():
                h.update(repr((n, v.shape, str(v.dtype), v.persistable,
                               v.lod_level)).encode())
        h.update(repr(program.random_seed).encode())
    if amp is not None:
        h.update(repr(amp).encode())
    fp = h.hexdigest()
    program._fp_cache = (memo_key, fp)
    return fp


def program_label(program):
    """What a compiled program is called in a profiler trace: the
    program's own ``_label`` (``serving/decoder.py`` names its prefill
    and decode-tick programs) or the first 8 hex digits of its
    fingerprint.  A label is not structure: it enters neither the
    fingerprint nor any cache key of this module."""
    label = getattr(program, "_label", None)
    return label or program_fingerprint(program)[:8]


def name_step(fn, kind, program):
    """Name the traced step function before it is jitted, so the compiled
    module reads ``jit_pt_<kind>_<label>`` in the device trace's ``XLA
    Modules`` line instead of ``jit_fn`` (``kind``: ``exe`` for Executor,
    ``pe`` for ParallelExecutor).  Returns ``fn``."""
    fn.__name__ = fn.__qualname__ = step_name(kind, program)
    return fn


def step_name(kind, program):
    """``pt_<kind>_<label>``: the step function's name, which jax's trace
    event carries as it is and its lowering and compile events as
    ``jit(<name>)``."""
    return "pt_%s_%s" % (kind, program_label(program))


def trace_key(program, feed_sig, state_sig, fetch_names, *extras):
    """Key for the process-global trace cache.  ``state_sig`` must carry
    the state names (the scope-dependent half of the lowering); ``extras``
    carries executor-specific trace-time choices (platform, donation,
    mesh/sharding identity, kernel-selection flags)."""
    return (program_fingerprint(program), tuple(feed_sig),
            tuple(state_sig), tuple(fetch_names)) + tuple(extras)


# ---------------------------------------------------------------------------
# trace cache
# ---------------------------------------------------------------------------

def lookup(key):
    """The cached entry or None; which of the two it was is the
    ``trace_cache`` field of the record open on the calling thread."""
    with _mu:
        entry = _TRACE_CACHE.get(key)
        if entry is not None:
            _TRACE_CACHE.move_to_end(key)
        _STATS["trace_hits" if entry is not None else "trace_misses"] += 1
    rec = getattr(_open, "record", None)
    if rec is not None:
        rec["trace_cache"] = "hit" if entry is not None else "miss"
    return entry


def store(key, entry):
    with _mu:
        _STATS["lowerings"] += 1
        if key and isinstance(key[0], str):
            fp12 = key[0][:12]   # trace_key leads with the fingerprint
            _LOWERINGS_BY_FP[fp12] = _LOWERINGS_BY_FP.get(fp12, 0) + 1
        _TRACE_CACHE[key] = entry
        _TRACE_CACHE.move_to_end(key)
        while len(_TRACE_CACHE) > _MAX_ENTRIES:
            _TRACE_CACHE.popitem(last=False)
    return entry


def stats():
    """Counters since process start (or the last ``reset_stats``).
    ``hit_ratio`` (hits / lookups, 0.0 before the first lookup) is the
    StepStats field: a warm steady-state loop sits at ~1.0 and a retrace
    storm (shape churn, program mutation) drags it visibly down;
    ``compile_log()`` says which program was lowered again, and why."""
    with _mu:
        out = dict(_STATS)
        out["lowerings_by_program"] = dict(_LOWERINGS_BY_FP)
        out["kernel_bodies"] = dict(_KERNEL_BODIES)
        out["kernel_traces"] = {k: dict(n) for k, n in _KERNEL_TRACES.items()}
    lookups = out["trace_hits"] + out["trace_misses"]
    out["hit_ratio"] = round(out["trace_hits"] / lookups, 4) if lookups \
        else 0.0
    out["entries"] = len(_TRACE_CACHE)
    out["persistent_dir"] = _persistent_dir[0]
    return out


def reset_stats():
    """Zero the counters and forget the records, the calling thread's open
    one too (a lowering that raised leaves one: closed later it would land
    in a log that was cleared since)."""
    with _mu:
        for k in _STATS:
            _STATS[k] = 0
        _LOWERINGS_BY_FP.clear()
        _KERNEL_BODIES.clear()
        _KERNEL_TRACES.clear()
        _LOG.clear()
        _OUTSIDE.clear()
    _open.record = None


def note_kernel_body(op_type, body):
    """Record, at trace time, which compute body an op with a Pallas (or
    ring) alternative lowered to.  A requested kernel that its
    ``supported()`` gate rejects gives way to the XLA reference; this
    counter (``stats()["kernel_bodies"]``) is what tells the two apart
    afterwards.  ``<type>:stored`` / ``:inline`` count the sites whose
    outputs the step keeps behind a barrier, or not, by the definition's
    ``stored`` rule (``registry.compute_op``)."""
    key = "%s:%s" % (op_type, body)
    with _mu:
        _KERNEL_BODIES[key] = _KERNEL_BODIES.get(key, 0) + 1


def note_kernel_trace(kernel, counter, seconds=0.0):
    """Count, at trace time, what a kernel that traces its ``pallas_call``s
    once a signature did (``ops/pallas/streamed_attention.py``):
    ``"sites"`` — a call reached it, ``"traces"`` — the call's signature was
    new and its jaxpr was made, in ``seconds``.
    ``stats()["kernel_traces"][kernel]`` holds both counts: a step program
    of six blocks reads 18 sites and 3 traces, and sites == traces says the
    memo never engaged.  The seconds go to the record open on the calling
    thread (``kernel_trace_s``, a part of its ``jax_trace_s``)."""
    with _mu:
        counts = _KERNEL_TRACES.setdefault(kernel, {"sites": 0, "traces": 0})
        counts[counter] += 1
    if counter == "traces":
        rec = getattr(_open, "record", None)
        if rec is None:
            _outside("kernel_trace", seconds)
        else:
            rec["kernel_traces"] += 1
            rec["kernel_trace_s"] += seconds


def note_op_work(scope_name, op_type, part, flops, least_bytes, shape):
    """Note, while a step is traced for lowering, what one part of an op's
    dense products must do (an op definition's ``work`` rule, asked by
    ``registry.compute_op`` where the op's scope is opened): ``part`` is
    ``fwd``, ``dx`` or ``dw``, ``flops`` 2·M·K·N of the flattened product
    ``shape`` = (M, K, N), ``least_bytes`` each operand and the result once,
    in the dtypes the body was handed — GLOBAL shapes under a mesh (the
    record's ``batch_shards`` says over how many devices the batch axis is
    split).  One plain tuple a part in the open record's ``op_work``; with
    no record open (eager programs, the reference) nothing is kept."""
    rec = getattr(_open, "record", None)
    if rec is not None:
        rec["op_work"].append((
            scope_name, op_type, part, int(flops), int(least_bytes),
            tuple(int(d) for d in shape)))


def clear():
    """Drop every cached trace (tests; frees the traced programs)."""
    with _mu:
        _TRACE_CACHE.clear()


# ---------------------------------------------------------------------------
# the compile record
# ---------------------------------------------------------------------------

# One plain dict per lowering, newest last; bounded like the trace cache
# (a retrace storm keeps its latest rows, ``lowerings_by_program`` its count)
_LOG = collections.deque(maxlen=256)
# phase -> [events, seconds] of what jax traced, lowered and compiled with
# no record open to take it: the benchmark's plain reference, eager
# ``jax.random.key`` / ``fold_in`` programs, ``device_put``s
_OUTSIDE = {}
# .record: the record open on this thread; .read / .wrote: the persistent
# cache answered / was written to since this thread's last backend compile
_open = threading.local()

_CAUSES = ("first", "feed_signature", "program_changed", "other_key")


def lowered_before(program):
    """Whether this process has lowered the program's fingerprint (since
    the last ``reset_stats``): the line between a record's ``cause``
    ``first`` and the others."""
    with _mu:
        return program_fingerprint(program)[:12] in _LOWERINGS_BY_FP


def open_record(executor, kind, program, cause, batch_shards=1):
    """Open the calling thread's compile record: ``executor`` is the
    step path's name (``executor`` / ``parallel_executor``), ``kind`` its
    label in the module name, ``cause`` one of ``first`` (fingerprint never
    lowered in this process), ``feed_signature``, ``program_changed`` (same
    program object, new ``_version``), ``other_key`` (flags, placement,
    scope, fetch list).  The program's ``build_s`` — what the whole-program
    passes of build spent on it (``profiler.build_pass``) — moves into its
    first record.  ``op_work`` fills while the step is traced
    (``note_op_work``); ``batch_shards`` is over how many devices the
    placement splits the batch axis.  A record still open on the thread
    (its cold call never came) is closed first, with ``first_call_s`` 0."""
    assert cause in _CAUSES, cause
    _listen()
    if getattr(_open, "record", None) is not None:
        close_record(None)
    build = dict(getattr(program, "_build_s", ()))
    if build:
        program._build_s.clear()
    _open.record = {
        "name": step_name(kind, program), "executor": executor,
        "fingerprint": program_fingerprint(program)[:12],
        "ops": sum(len(b.ops) for b in program.blocks),
        "cause": cause, "trace_cache": "miss", "start_us": _now_us(),
        "build_s": sum(build.values(), 0.0), "build": build,
        "analyze_s": 0.0, "program_trace_s": 0.0,
        "jax_trace_s": 0.0, "kernel_trace_s": 0.0, "kernel_traces": 0,
        "lowering_s": 0.0, "executable_s": 0.0, "executable": "none",
        "first_call_s": 0.0, "unaccounted_s": 0.0,
        "op_work": [], "batch_shards": int(batch_shards),
        # (phase, end on the profiler's clock, seconds) as jax reported
        # them, and whether jax is inside the step function's own trace
        "_spans": [], "_tracing": False}


def note_phase(field, since_ns):
    """Add the seconds since ``since_ns`` (``time.perf_counter_ns``) to
    ``field`` of the record open on this thread; returns now, for the
    next phase."""
    now = time.perf_counter_ns()
    rec = getattr(_open, "record", None)
    if rec is not None:
        rec[field] += (now - since_ns) / 1e9
    return now


_TICKS = float(2 ** 30)
_PHASE_SECONDS = ("analyze_s", "program_trace_s", "jax_trace_s",
                  "kernel_trace_s", "lowering_s", "executable_s",
                  "first_call_s")


def close_record(call_ns, op_work=None):
    """Close the calling thread's record (None when there is none) and
    append it to the log: ``call_ns`` is when the cold call began
    (``time.perf_counter_ns``; None = there was no cold call),
    ``unaccounted_s`` what of the call jax reported no phase for;
    ``op_work`` is the trace-cache entry's list, for a record under which
    nothing was traced again.  Under a profiler session
    the phases jax reported are appended to its events as
    ``<executor>/jax_trace``, ``/mlir_lowering`` and ``/executable`` spans,
    back-dated from the moment each duration arrived; with the monitor's
    JSONL log on, the record is one ``compile_record`` event there."""
    rec = getattr(_open, "record", None)
    if rec is None:
        return None
    _open.record = None
    if op_work and not rec["op_work"]:
        rec["op_work"] = list(op_work)
    if call_ns is not None:
        rec["first_call_s"] = (time.perf_counter_ns() - call_ns) / 1e9
    # every phase in whole 2^-30 s (~1 ns): a sum of them is then exact in
    # whatever order a reader adds them
    for field in _PHASE_SECONDS:
        rec[field] = round(rec[field] * _TICKS) / _TICKS
    if call_ns is not None:
        rec["unaccounted_s"] = rec["first_call_s"] - (
            rec["jax_trace_s"] + rec["lowering_s"] + rec["executable_s"])
    spans = rec.pop("_spans")
    del rec["_tracing"]
    if is_profiling():
        # the enclosing <executor>/compile span carries the fingerprint
        tags = {"module": rec["name"], "cause": rec["cause"]}
        for phase, end_us, seconds in spans:
            _append_event("%s/%s" % (rec["executor"], phase),
                          end_us - seconds * 1e6, seconds * 1e6, dict(tags))
    with _mu:
        _LOG.append(rec)
    monitor.log_event(dict(rec, event="compile_record", ts=time.time()))
    return rec


def compile_log():
    """The closed records, oldest first, as plain dicts (copies)."""
    with _mu:
        return [dict(rec, build=dict(rec["build"]),
                     op_work=list(rec["op_work"])) for rec in _LOG]


def outside_compiles():
    """{phase: {"events", "seconds"}} of what jax traced, lowered and
    compiled (or read) that belongs to no record."""
    with _mu:
        return {phase: {"events": n, "seconds": s}
                for phase, (n, s) in _OUTSIDE.items()}


def _outside(phase, seconds):
    with _mu:
        slot = _OUTSIDE.setdefault(phase, [0, 0.0])
        slot[0] += 1
        slot[1] += seconds


# ---------------------------------------------------------------------------
# jax's own compile events
# ---------------------------------------------------------------------------

# jax.monitoring events -> counter names.  The duration events fire once
# per jaxpr->MLIR lowering and once per backend compile request (a
# persistent-cache hit still fires the latter, with the short retrieval
# time); the plain events count persistent-cache traffic.
_JAX_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jax_lowerings", "jax_lowering_seconds"),
    "/jax/core/compile/backend_compile_duration":
        ("jax_backend_compiles", "jax_backend_compile_seconds"),
}
_JAX_EVENTS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
}
_JAX_COUNTS = dict.fromkeys(
    [n for pair in _JAX_DURATION_EVENTS.values() for n in pair]
    + list(_JAX_EVENTS.values()), 0)
# the duration events a record takes, by the span each becomes
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_RECORD_PHASES = {
    _TRACE_EVENT: ("jax_trace", "jax_trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("mlir_lowering", "lowering_s"),
    "/jax/core/compile/backend_compile_duration":
        ("executable", "executable_s"),
}
# inside a backend compile: the persistent cache answered, or was written
# to (where there is none the compile fires neither: "uncached")
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"
_listening = [False]


def _listen():
    """Register the jax.monitoring listeners once per process — at the
    first record, the first ``count_compiles()`` or when the persistent
    cache is turned on, whichever comes first (they stay; they run
    only when jax traces, lowers or compiles something).  The counters
    are process-global on purpose: the serving loop compiles on its own
    thread, which thread-local counters miss.  A duration whose
    ``fun_name`` is that of the record open on the calling thread is the
    record's; a trace event that arrives while jax is inside the step
    function's own trace is a ``jax.jit`` nested in it, already inside the
    step's seconds, and counts nowhere; everything else is ``outside``."""
    with _mu:
        if _listening[0]:
            return
        _listening[0] = True
    import jax.monitoring

    def on_duration(event, duration, fun_name=None, **_):
        names = _JAX_DURATION_EVENTS.get(event)
        if names is not None:
            with _mu:
                _JAX_COUNTS[names[0]] += 1
                _JAX_COUNTS[names[1]] += duration
        if event == _CACHE_READ_EVENT:
            _open.read = True
            return
        if event not in _RECORD_PHASES:
            return
        phase, field = _RECORD_PHASES[event]
        rec = getattr(_open, "record", None)
        how = None
        if phase == "executable":
            how = "read" if getattr(_open, "read", False) else \
                "compiled" if getattr(_open, "wrote", False) else "uncached"
            _open.read = _open.wrote = False
        if rec is not None and fun_name in (rec["name"],
                                            "jit(%s)" % rec["name"]):
            rec[field] += duration
            rec["_spans"].append((phase, _now_us(), duration))
            if how is not None:
                rec["executable"] = how
            if event == _TRACE_EVENT:
                rec["_tracing"] = False
        elif not (event == _TRACE_EVENT and rec is not None
                  and rec["_tracing"]):
            _outside(phase if how is None else phase + "_" + how, duration)

    def on_scalar(event, value, fun_name=None, **_):
        # jax's elapsed-time events report their start as a scalar
        rec = getattr(_open, "record", None)
        if event == _TRACE_EVENT and rec is not None \
                and fun_name == rec["name"]:
            rec["_tracing"] = True

    def on_event(event, **_):
        name = _JAX_EVENTS.get(event)
        if name is not None:
            with _mu:
                _JAX_COUNTS[name] += 1
        if event == _CACHE_WRITE_EVENT:
            _open.wrote = True

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_scalar_listener(on_scalar)
    jax.monitoring.register_event_listener(on_event)


def _compile_counts():
    with _mu:
        out = dict(_JAX_COUNTS)
        out["lowerings"] = _STATS["lowerings"]
    return out


@contextlib.contextmanager
def count_compiles():
    """Count what was lowered and compiled inside the block, on any
    thread.  Yields a callable returning the deltas since entry (live
    inside the block, frozen at its exit): ``lowerings`` (this module's
    trace-cache stores — a Program traced to a new step function),
    ``jax_lowerings`` / ``jax_backend_compiles`` (jax's own jaxpr->MLIR
    and backend-compile events, with their ``*_seconds``), and
    ``persistent_cache_hits`` / ``_misses``.  A warm step shows zeros in
    all of the first three."""
    _listen()
    before = _compile_counts()
    frozen = {}

    def delta():
        return dict(frozen) or {
            k: v - before[k] for k, v in _compile_counts().items()}
    try:
        yield delta
    finally:
        frozen.update(delta())


# ---------------------------------------------------------------------------
# persistent XLA compilation cache
# ---------------------------------------------------------------------------

# The one fixed location chip entry points (chip_smoke.py, bench.py and
# its rung children) use when the environment names none: inside the
# checkout, gitignored, never a temporary name, pid or time — the path
# is part of jax's cache key, so a directory that moves never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache")


def persistent_cache_dir(requested=None, chip_entry=False):
    """THE resolver for where the persistent compilation cache lives.

    1. ``JAX_COMPILATION_CACHE_DIR`` set: there, and nowhere else — the
       flag and any CLI option lose to it.
    2. ``requested`` (``FLAGS_compile_cache_dir`` / ``--compile_cache_dir``).
    3. ``chip_entry``: :data:`CHECKOUT_CACHE_DIR`.
    4. None — off.  This is what a bare ``import paddle_tpu`` gets (the
       test suite relies on a cold cache: warm multi-device CPU
       executables were found nondeterministic).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if requested:
        return requested
    return CHECKOUT_CACHE_DIR if chip_entry else None


def _known_world_size():
    """The jax process count, WITHOUT initializing the backend: only
    consulted when ``parallel.distributed`` is already imported and
    reports the world joined (probing ``jax.process_count()`` directly
    would initialize the backend, which must not happen at flag-import
    time, before a later ``jax.distributed.initialize``)."""
    import sys

    dist = sys.modules.get("paddle_tpu.parallel.distributed")
    if dist is not None and dist.is_initialized():
        import jax

        return jax.process_count()
    return 1


def rescope_persistent_cache():
    """Re-point the persistent cache at a world-scoped subdirectory
    (``world_<N>``) once the process count is known — called by
    ``parallel.distributed.init_distributed`` AFTER the jax runtime
    joined the world (covering caches enabled BEFORE the join; caches
    enabled after it scope themselves in ``enable_persistent_cache``).
    Single-process runs keep the directory itself, so an elastic-resume
    survivor restarts warm off the solo entries while never
    deserializing a multi-process executable: an N-process module
    embeds cross-process collective wiring and silently computes
    garbage in any other world shape (found by the cluster drill)."""
    base = _persistent_base[0]
    if base:
        _apply_persistent_dir(base)


def enable_persistent_cache(cache_dir=None, chip_entry=False):
    """Turn on jax's on-disk executable cache in the directory
    :func:`persistent_cache_dir` resolves (None = off); returns it.
    Thresholds are zeroed so even small modules cache: the win case is
    many small-to-medium modules recompiled across rung subprocesses
    and chip calls.  In a ``jax.distributed`` world the entries land in
    a ``world_<N>`` subdirectory — see ``rescope_persistent_cache``.
    A process that keeps its executables also hears what jax compiles from
    here on (``_listen``): what it compiles before its first step — a
    benchmark's plain reference — is then in ``outside_compiles()``."""
    cache_dir = _apply_persistent_dir(
        persistent_cache_dir(cache_dir, chip_entry=chip_entry))
    if cache_dir:
        _listen()
    return cache_dir


def _apply_persistent_dir(cache_dir):
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as jax_cc)

    _persistent_base[0] = cache_dir
    if cache_dir:
        n = _known_world_size()
        if n > 1:
            cache_dir = os.path.join(cache_dir, "world_%d" % n)
    _persistent_dir[0] = cache_dir
    jax_cc.set_cache_dir(cache_dir)
    if cache_dir:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax binds the cache to the directory (or to "disabled") at the
    # first compile; reset so a directory set after that takes effect
    jax_cc.reset_cache()
    return cache_dir
