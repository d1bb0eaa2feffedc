"""Global runtime flags — the gflags/env-whitelist analog.

Parity: the reference defines C++ gflags next to each subsystem
(``FLAGS_check_nan_inf`` in ``framework/operator.cc:31``,
``FLAGS_benchmark`` in ``framework/executor.cc:396``,
``FLAGS_cpu_deterministic``) and re-exports an env-settable whitelist at
import time (``python/paddle/fluid/__init__.py:112-126`` →
``core.init_gflags``).  Here flags are a typed registry: each flag has a
declared type and default, is overridable from the environment at import
(``FLAGS_<name>=...``) and at runtime via ``set_flags``/``get_flags``.

TPU-native semantics of the debugging flags:

* ``check_nan_inf`` — after every executor step, block on the step's
  outputs and verify finiteness of all floating fetches and written-back
  state; raise naming the first offending variable.  (The reference
  checks every op's outputs inside the interpreter loop,
  ``operator.cc:717``; under whole-program jit the step boundary is the
  observable granularity.)
* ``debug_nans`` — op-level localization: enables ``jax_debug_nans``,
  which re-runs a nan-producing jitted step op-by-op to point at the
  guilty primitive.  Finer-grained but globally intrusive; separate
  from ``check_nan_inf`` so the cheap step-level check doesn't flip
  global jax config.
* ``cpu_deterministic`` — forces deterministic XLA reductions
  (``--xla_cpu_enable_fast_math=false`` analog) via jax config.
* ``benchmark`` — per-step wall-clock logging in the executors.

Robustness families (ISSUE 8): the ``FLAGS_guardian_*`` family
configures the training-run guardian (``guardian.py``: in-graph NaN/Inf
skip guard, loss spike/plateau detection, skip -> rollback -> abort
recovery ladder with budgets, quarantine directory, watchdog-stall
escalation) and the ``FLAGS_fault_*`` family installs deterministic
fault-injection drills (``fault.py``: seed/step-indexed schedules for
NaN vars, poisoned batches, dispatch delay/failure, mid-save kills)
from a spec string — each flag is documented at its registration below.
"""

import os
import threading

__all__ = ["set_flags", "get_flags", "register_flag", "pinned"]

_mu = threading.Lock()
_FLAGS = {}
_TYPES = {}
# flags the OPERATOR set explicitly (env override at import, or
# set_flags with the default pin=True): the auto-tuner's decisions
# (autotune.py) defer to pinned flags — an explicit user choice always
# beats a tuned one.  Internal machinery that flips flags on the user's
# behalf without expressing a preference (the tuner's own A/B arms)
# passes pin=False.
_PINNED = set()


def register_flag(name, default, typ=None, on_set=None):
    """Declare a flag.  Env var ``FLAGS_<name>`` overrides the default
    at registration (import) time, like core.init_gflags."""
    typ = typ or type(default)
    _TYPES[name] = (typ, on_set)
    val = default
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        val = _parse(env, typ)
        _PINNED.add(name)
    _FLAGS[name] = val
    if on_set is not None and env is not None:
        on_set(val)


def _parse(s, typ):
    if typ is bool:
        return s.strip().lower() in ("1", "true", "yes", "on")
    return typ(s)


def set_flags(flags, pin=True):
    """set_flags({'FLAGS_check_nan_inf': True}) — accepts both the
    FLAGS_-prefixed spelling (reference API) and the bare name.

    ``pin=True`` (the default) marks each flag as an explicit operator
    choice (see :func:`pinned`): the auto-tuner never overrides a
    pinned flag.  ``pin=False`` is for machinery — the tuner's own A/B
    arms, restore-after paths — that sets values without expressing a
    preference."""
    with _mu:
        for k, v in flags.items():
            name = k[6:] if k.startswith("FLAGS_") else k
            if name not in _FLAGS:
                raise KeyError("unknown flag %r" % k)
            typ, on_set = _TYPES[name]
            v = _parse(v, typ) if isinstance(v, str) else typ(v)
            prev = _FLAGS[name]
            _FLAGS[name] = v
            if on_set is not None:
                try:
                    on_set(v)
                except Exception:
                    # a raising validator (guardian_policy, fault_spec,
                    # ...) must not leave the rejected value readable
                    # via flag().  Commit-then-rollback (not validate-
                    # first) because reconcile-style hooks re-read
                    # their own flag (_on_monitor_change).
                    _FLAGS[name] = prev
                    raise
            if pin:
                _PINNED.add(name)


def get_flags(names):
    """get_flags('FLAGS_check_nan_inf') or a list; returns dict."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for k in names:
        name = k[6:] if k.startswith("FLAGS_") else k
        if name not in _FLAGS:
            raise KeyError("unknown flag %r" % k)
        out[k] = _FLAGS[name]
    return out


def flag(name):
    """Fast internal accessor."""
    return _FLAGS[name]


def pinned(name):
    """Whether the operator set this flag explicitly (env override at
    import, or ``set_flags`` with the default ``pin=True``).  The
    auto-tuner (``autotune.py``) consults this before applying any
    flag-backed decision: a pinned flag always wins over the tuner."""
    name = name[6:] if name.startswith("FLAGS_") else name
    if name not in _FLAGS:
        raise KeyError("unknown flag %r" % name)
    return name in _PINNED


def _restore_pins(mapping):
    """Restore a saved {name: was_pinned} snapshot (the tuner's A/B
    arms save pins, flip flags unpinned, and put the world back)."""
    with _mu:
        for name, was in mapping.items():
            (_PINNED.add if was else _PINNED.discard)(name)


def _on_debug_nans(val):
    import jax

    jax.config.update("jax_debug_nans", bool(val))


def _on_cpu_deterministic(val):
    import jax

    # deterministic reductions: disable non-deterministic fast paths
    jax.config.update("jax_default_matmul_precision",
                      "highest" if val else None)


register_flag("check_nan_inf", False, bool)
# whether the ops' own rules may pick Pallas kernels (ops/pallas/
# kernel_allowed).  False is the operator's switch against a kernel that
# miscompiles on a new runtime: every op then lowers to its XLA body
register_flag("pallas_kernels", True, bool)
# rbg counter PRNG for in-graph randomness (dropout masks etc.):
# cheaper random bits on TPU than the default threefry; different (but
# still deterministic-per-seed) random streams.  Fetch-synced A/B on the
# bench transformer: +34% tokens/s (threefry dropout masks were ~25% of
# the step) — the bench enables it; default off for stream stability.
register_flag("fast_prng", False, bool)
# exact two-pass batch_norm variance (E[(x-mean)^2]) instead of the
# default fused one-pass E[x^2]-E[x]^2 form; costs one extra full
# activation read per BN (see ops/norm.py)
register_flag("bn_two_pass", False, bool)


def _on_compile_cache_dir(val):
    from . import compile_cache

    compile_cache.enable_persistent_cache(val)


register_flag("debug_nans", False, bool, _on_debug_nans)
register_flag("benchmark", False, bool)
# persistent XLA compilation cache directory ("" = none requested):
# repeated program+signature shapes across bench rungs and restarts
# deserialize the compiled executable instead of re-running the XLA
# pipeline.  JAX_COMPILATION_CACHE_DIR, when set, wins over this flag
# (compile_cache.persistent_cache_dir is the one resolver)
register_flag("compile_cache_dir", "", str, _on_compile_cache_dir)
# async-dispatch window: how many steps the host may run ahead of the
# device before blocking on the oldest in-flight step's fetches
# (return_numpy=False paths).  Bounds host run-ahead and device-buffer
# liveness; syncs happen only at window edges.
register_flag("max_inflight_steps", 8, int)
register_flag("cpu_deterministic", False, bool, _on_cpu_deterministic)
# accepted for API parity; memory is managed by XLA (VERDICT #1):
register_flag("eager_delete_tensor_gb", -1.0, float)
register_flag("fraction_of_gpu_memory_to_use", 0.92, float)


def _on_monitor_change(_val):
    # one reconcile hook for the whole FLAGS_monitor* family: the
    # monitor re-reads every flag and starts/stops/reconfigures only the
    # components whose config changed
    from . import monitor

    monitor._reconcile()


# always-on telemetry (monitor/): the master switch...
register_flag("monitor", False, bool, _on_monitor_change)
# ...and the exporter knobs — setting any of the log dir, the port, or
# the console interval implies the switch: a rotating JSONL
# StepStats/event log directory ("" = off),
register_flag("monitor_log_dir", "", str, _on_monitor_change)
# a Prometheus-style /metrics HTTP endpoint (0 = off),
register_flag("monitor_port", 0, int, _on_monitor_change)
# and a periodic one-line console summary interval (0 = off).
register_flag("monitor_console_seconds", 0.0, float, _on_monitor_change)
# The watchdog's stall window CONFIGURES but does not imply (its default
# is non-zero): with the monitor on and no step completed for this long,
# dump queue states + heartbeats + last span to stderr and the event log
# (0 = watchdog off)
register_flag("monitor_stall_seconds", 120.0, float, _on_monitor_change)


def _on_trace_change(_val):
    from .monitor import tracing

    tracing._reconcile()


# per-request distributed tracing (monitor/tracing.py): span trees over
# the serving lifecycle + cluster RPC.  Independent of FLAGS_monitor —
# spans always land in the in-process buffer; a JSONL twin is written
# whenever FLAGS_monitor_log_dir is also set.
register_flag("trace", False, bool, _on_trace_change)


def _on_fleet_telemetry_change(_val):
    from .monitor import aggregate

    aggregate._reconcile()


def _on_health_change(_val):
    from .monitor import health

    health._reconcile()


# model-health telemetry (monitor/health.py): with it on, the executors
# lower steps with an in-graph per-layer probe (grad L2 norm, param
# norm, update/param ratio, non-finite count as one extra fetch) and
# stash per-step NaN-provenance replay contexts.  Baked into the traced
# jaxpr — flipping it re-keys the trace caches.  Disabled cost is zero
# health calls (module-global bool; A/B test-enforced) and the seeded
# training trajectory is bit-identical with the flag on or off.
register_flag("health", False, bool, _on_health_change)
# host-side publication cadence for the probe: the stats are computed
# on-device every step (fused, no sync), but gauges + model_health
# JSONL records publish every Nth step — the only host sync the probe
# adds
register_flag("health_every", 10, int, _on_health_change)


# fleet telemetry plane (monitor/aggregate.py): each ClusterMember ships
# a MetricDigest on its existing heartbeat; the master merges digests
# into fleet-level series, straggler verdicts, and SLO alerts.  Off by
# default — the disabled path is one module-global bool read.
register_flag("fleet_telemetry", False, bool, _on_fleet_telemetry_change)
# digest byte budget per heartbeat: over it, oldest step samples and
# lowest-traffic histograms decimate (counted in fleet/digest_truncated)
# so a fat digest never delays lease renewal
register_flag("fleet_digest_bytes", 16384, int, _on_fleet_telemetry_change)


def _on_preflight_oom(val):
    # validate at set time: a typo ("stric") silently downgrading the
    # hard-fail mode to a warning would defeat the operator's intent
    allowed = ("auto", "warn", "strict", "off", "0", "false", "no",
               "none", "")
    if str(val).strip().lower() not in allowed:
        raise ValueError(
            "FLAGS_preflight_oom must be one of auto/warn/strict/off, "
            "got %r" % (val,))


# HBM preflight (monitor/program_profile.py): before the first dispatch
# of a newly compiled program, compare its estimated peak device memory
# (from the compiled module's own memory_analysis) against device
# capacity.  "auto" (default) rides along whenever the monitor is on
# (profile capture is monitor-gated) and warns; "warn"/"strict" force
# capture + preflight even on unmonitored runs, warning or raising
# PreflightOOMError instead of letting XLA OOM mid-run; "off" disables
# the check (profiles still capture while the monitor is on).
register_flag("preflight_oom", "auto", str, _on_preflight_oom)
# capacity override in bytes for the preflight (0 = use the device's
# memory_stats()['bytes_limit']; useful in tests and on backends that
# misreport capacity)
register_flag("preflight_hbm_bytes", 0, int)


def _on_guardian_policy(val):
    # validate at set time: a typo'd rung ("rolback") silently dropping
    # rollback from the ladder would defeat the operator's intent
    bad = {t.strip() for t in str(val).split(",") if t.strip()} \
        - {"skip", "rollback", "abort"}
    if bad:
        raise ValueError(
            "FLAGS_guardian_policy tokens must be among "
            "skip/rollback/abort, got %s" % sorted(bad))


def _on_guardian_spike_action(val):
    if str(val).strip() not in ("warn", "rollback", "off"):
        raise ValueError(
            "FLAGS_guardian_spike_action must be warn/rollback/off, "
            "got %r" % (val,))


# Training-run guardian (guardian.py): the master switch.  With it on,
# the contrib Trainer installs a Guardian by default, both executors
# feed it every step, and — when the policy ladder includes "skip" —
# steps are lowered with the in-graph NaN/Inf guard (non-finite fetched
# losses suppress the state update on-device).  Flipping it re-keys the
# trace caches (the guard is baked into the jaxpr).  Disabled cost is
# one flag/module-global read per step (A/B test-enforced).
register_flag("guardian", False, bool)
# the recovery ladder, ordered mildest-first: "skip" (in-graph drop of
# the offending update + batch quarantine), "rollback" (restore the
# newest clean TrainState and replay), "abort" (typed
# GuardianAbortError once the rollback budget is spent).  Comma-joined
# subset of skip/rollback/abort.
register_flag("guardian_policy", "skip,rollback,abort", str,
              _on_guardian_policy)
# rolling-window size for the loss spike/plateau detector (median+MAD
# over the last N finite losses)
register_flag("guardian_window", 32, int)
# spike threshold: |loss - median| / (1.4826*MAD) above this z-score is
# an anomaly (robust z; 8 is far out on any well-behaved loss curve)
register_flag("guardian_zmax", 8.0, float)
# consecutive in-graph-skipped steps before the ladder escalates to
# rollback (a burst of bad batches is data trouble, not a blip)
register_flag("guardian_max_skips", 8, int)
# rollback attempts before GuardianAbortError — the bound that turns
# "recover forever" into a typed failure
register_flag("guardian_max_rollbacks", 2, int)
# where quarantined batches (offending feed + signature + run_id) are
# written for repro ("" = record the signature in the event log only;
# the contrib Trainer defaults this to <checkpoint_dir>/quarantine)
register_flag("guardian_quarantine_dir", "", str)
# what a detected loss spike does: "warn" (event+counter only),
# "rollback" (escalate like a non-finite loss), "off"
register_flag("guardian_spike_action", "warn", str,
              _on_guardian_spike_action)
# plateau detector window (0 = off): no median improvement across the
# last N losses publishes a guardian_plateau event (advisory only)
register_flag("guardian_plateau_steps", 0, int)
# consecutive watchdog stall windows before the guardian arms a typed
# abort (0 = never escalate stalls)
register_flag("guardian_stall_escalations", 3, int)


def _on_fault_spec(val):
    # install drills straight from the environment/set_flags: the
    # env-var entry point that makes a fault drill runnable against any
    # existing script (FLAGS_fault_spec="nan_var:fc_0.w_0@5;..." ).
    # install_from_spec REPLACES the previous spec's hooks, so the
    # installed fault state always mirrors the flag value; an empty
    # value disarms a previously set spec (nothing to disarm — and no
    # reason to import fault — if fault.py was never imported).
    if not str(val).strip():
        import sys
        fault = sys.modules.get(__name__.rsplit(".", 1)[0] + ".fault")
        if fault is not None and hasattr(fault, "install_from_spec"):
            fault.install_from_spec("")
        return
    from . import fault

    if not hasattr(fault, "install_from_spec"):
        # registration-time env override while fault.py is mid-import
        # (fault -> flags -> this hook): fault installs the env spec
        # itself at the end of its module body
        return
    fault.install_from_spec(val)


# Profile-guided auto-configuration (autotune.py): where bench.py writes
# its TunedConfig artifacts ("" = nowhere)
register_flag("autotune_dir", "", str)
# device-memory ceiling override in bytes for the tuner's batch-size
# probe (0 = fall back to FLAGS_preflight_hbm_bytes, then the device's
# memory_stats()['bytes_limit']).  The probe rejects candidates by the
# compiled module's own peak-HBM ESTIMATE against this ceiling — never
# by an OOM crash — which is what makes the ladder testable on CPU
# with a fake limit.
register_flag("autotune_hbm_bytes", 0, int)
# checkpoint-cadence overhead budget (CheckFreq-style): the tuner picks
# the smallest save interval whose measured on-step checkpoint cost
# stays under this fraction of compute
register_flag("autotune_overhead_budget", 0.035, float)
def _on_quantize_mode(val):
    if str(val).strip() not in ("", "off", "weight_only", "dynamic"):
        raise ValueError(
            "FLAGS_quantize_mode must be one of ''/off/weight_only/"
            "dynamic, got %r" % (val,))


# Quantized inference (transpiler.quantize_inference + autotune.
# tune_quantization): an explicit mode is the operator's choice — the
# accuracy-gated tuner records it as pinned and never measures over it
# ("off" pins full precision; "" leaves the decision to the tuner)
register_flag("quantize_mode", "", str, _on_quantize_mode)
# accuracy budget for the quantization gate: the tuner only keeps a
# quantized program whose eval delta (relative L1 over the A/B fetches)
# stays under this fraction; rejections are recorded as TunedConfig
# evidence and full precision is kept
register_flag("quantize_accuracy_budget", 0.02, float)
# seed for probabilistic fault schedules (prob=...): two runs with the
# same seed inject at identical steps.  Registered BEFORE fault_spec:
# an env-set spec installs schedules at import, which read this flag.
register_flag("fault_seed", 0, int)
# deterministic fault-injection drills (fault.py), installed from a
# spec string: family:arg@schedule[;...] — see fault.install_from_spec
# for the grammar and drill families (nan_var, poison_batch, delay,
# fail_dispatch, kill_save)
register_flag("fault_spec", "", str, _on_fault_spec)
