"""Profile-guided auto-configuration (ISSUE 9 tentpole).

PERF.md is a graveyard of hand-measured config decisions — b512 not
b1024, 4 bucket bounds not 6, checkpoint cadence picked by eye — while the
compiler's own cost/memory accounting per program has been free at
runtime since the program-profile work (``monitor/program_profile.py``:
XLA ``cost_analysis``/``memory_analysis`` captured at the one compile
each signature already pays).  This module closes the loop: an
auto-tuner that searches the config space using that machinery instead
of blind timing sweeps.

Four knobs, four decision procedures (each a PURE function of
measurements, so the policy is unit-testable without a device):

* **batch size** (:func:`run_batch_ladder` / :func:`tune_batch_size`) —
  geometric probe upward.  Each rung pays exactly ONE compile (the
  ``Executor.cost_analysis`` explicit compile, which seeds the AOT
  dispatch slot, so the measured window that follows adds zero backend
  compiles), whose ``memory_analysis`` peak-HBM estimate rejects
  over-capacity rungs BEFORE any dispatch could OOM; once two rungs'
  peaks are known, the next rung's peak is PROJECTED (linear in batch)
  and an over-ceiling projection stops the ladder without even the
  probe compile.  Surviving rungs get a short measured
  step-time window; the ladder stops when seconds-per-example regresses
  (the PERF.md b512-not-b1024 shape: amortization plateaus, HBM-pressure
  scheduling takes over).
* **bucket bounds** (:func:`choose_bucket_bounds`) — pick K bounds from
  an observed length histogram maximizing real-token fill, restricted
  to hardware-friendly multiples FIRST (the PERF.md r4 finding: six
  finer-but-ragged bounds measured WORSE than four MXU-friendly ones
  despite higher fill — raggedness loses more on the MXU than padding).
* **pipeline schedule + microbatch count** (:func:`decide_pipeline` /
  :func:`tune_pipeline`) — for programs whose ``pipeline_region`` ops
  run pipelined on a ``pp`` mesh: measure a short step window per
  (schedule, microbatches) candidate, reject candidates whose compiled
  peak-HBM estimate exceeds the ceiling (1F1B's M-independent
  activation memory is exactly what unlocks the larger-M rungs GPipe
  cannot afford), pick the fastest, and tie-break near-equal timings by
  the schedule table's exact bubble fraction then memory bound
  (``parallel.pipeline.schedule_stats``).  An explicit
  ``BuildStrategy.pipeline_schedule`` is a user pin the tuner records
  and respects.
* **checkpoint interval** (:func:`decide_checkpoint_interval`) —
  CheckFreq-style: the smallest interval whose measured on-step cost
  (snapshot, plus the full write in sync mode) stays under the overhead
  budget (default ``FLAGS_autotune_overhead_budget`` = 3.5%), bounded
  below by the async write's drain time so a save never backs up into
  the next snapshot; the guardian's measured rollback replay cost rides
  along as evidence (smaller intervals bound the replay — the formula
  already picks the smallest budget-feasible interval).

Decisions are recorded as a :class:`TunedConfig` artifact (JSON:
decision, evidence, probe measurements, run_id/fingerprints) consumed
by ``bench.py --autotune`` and ``contrib.Trainer(autotune=...)``, and
every decision publishes ``autotune/*`` monitor counters plus
``autotune_decision`` JSONL events so tuning is observable like
everything else.

**Rejection mechanism**: the batch ladder's ceiling is the preflight
HBM *estimate* (``FLAGS_autotune_hbm_bytes`` override, else
``FLAGS_preflight_hbm_bytes``, else the device's
``memory_stats()['bytes_limit']``) — candidates are rejected by the
compiler's own memory analysis before any dispatch, never by an OOM
crash.  That is what makes the probe testable on CPU with a fake limit.

**Pinning**: every tuned decision defers to an explicit user choice.
Flags set from the environment or via ``set_flags`` are *pinned*
(``flags.pinned()``); :meth:`TunedConfig.apply` skips pinned knobs and
records the skip in the decision trail.
"""

import contextlib
import json
import math
import os
import time

import numpy as np

__all__ = [
    "TunedConfig", "hbm_ceiling", "batch_ladder", "project_peak_hbm",
    "run_batch_ladder", "token_fill", "choose_bucket_bounds",
    "decide_checkpoint_interval", "tune_batch_size",
    "tune_checkpoint_interval", "measure_step_window",
    "decide_pipeline", "tune_pipeline",
    "decide_quantization", "tune_quantization",
]

# knobs of artifacts older runs wrote that nothing reads any more
_RETIRED_KNOBS = ("attention_kernel", "quant_kernel")


def _flag(name, default):
    from . import flags

    try:
        return flags.flag(name)
    except KeyError:
        return default


def _event(record):
    from . import monitor

    ev = record.get("event")
    if ev == "autotune_decision":
        monitor.count("autotune/decisions")
    elif ev == "autotune_probe":
        monitor.count("autotune/probes")
    record.setdefault("ts", time.time())
    monitor.log_event(record)


# ---------------------------------------------------------------------------
# pure decision functions
# ---------------------------------------------------------------------------

def batch_ladder(start=32, max_batch=4096, factor=2):
    """Geometric candidate ladder: start, start*factor, ... <= max_batch."""
    start = max(1, int(start))
    out = []
    b = start
    while b <= max_batch:
        out.append(b)
        nxt = int(b * factor)
        b = nxt if nxt > b else b + 1
    return out


def project_peak_hbm(pairs, batch):
    """Project a candidate batch's estimated peak HBM from measured
    (batch, peak_bytes) pairs by least-squares linear fit — peak memory
    is affine in batch (activations/temps scale, params don't).  Needs
    >= 2 distinct batches; returns None otherwise."""
    pts = [(float(b), float(p)) for b, p in pairs if p]
    if len({b for b, _ in pts}) < 2:
        return None
    xs = np.array([b for b, _ in pts])
    ys = np.array([p for _, p in pts])
    slope, intercept = np.polyfit(xs, ys, 1)
    return float(intercept + slope * float(batch))


def run_batch_ladder(ladder, hbm_limit, probe_fn, measure_fn,
                     regress_tol=0.05, headroom=0.9):
    """The batch-size decision procedure, pure in its callbacks.

    ``probe_fn(batch) -> estimated peak HBM bytes (or None)`` — one
    compile's memory analysis; ``measure_fn(batch) -> measured seconds
    per step`` — a short dispatch window over the already-compiled
    executable.  ``hbm_limit`` of None/0 disables the memory gate.

    Walks ``ladder`` upward.  A rung whose PROJECTED peak (linear fit
    over the rungs already probed) exceeds ``headroom * hbm_limit``
    stops the ladder without its probe compile; a rung whose probed
    estimate exceeds the ceiling stops it before any dispatch; a rung
    whose measured seconds-per-example regresses more than
    ``regress_tol`` over the best-so-far stops it after its window.

    Returns the decision dict: ``chosen`` (best seconds-per-example
    among surviving rungs, None if none survived), per-candidate
    statuses and measurements, and the ceiling used.
    """
    limit = float(hbm_limit) if hbm_limit else None
    ceiling = limit * float(headroom) if limit else None
    candidates = []
    peaks = []                      # (batch, probed peak) pairs
    best = None                     # (s_per_example, batch, step_s)
    for b in ladder:
        cand = {"batch": int(b)}
        if ceiling is not None:
            projected = project_peak_hbm(peaks, b)
            if projected is not None and projected > ceiling:
                cand.update(status="rejected_projected_hbm",
                            projected_peak_hbm_bytes=int(projected))
                candidates.append(cand)
                break
            peak = probe_fn(b)
            if peak:
                cand["peak_hbm_bytes"] = int(peak)
                peaks.append((b, peak))
                if peak > ceiling:
                    cand["status"] = "rejected_hbm"
                    candidates.append(cand)
                    break
        else:
            peak = probe_fn(b)
            if peak:
                cand["peak_hbm_bytes"] = int(peak)
                peaks.append((b, peak))
        step_s = measure_fn(b)
        spe = step_s / float(b)
        cand.update(step_s=round(step_s, 6),
                    s_per_example=spe, status="ok")
        candidates.append(cand)
        if best is not None and spe > best[0] * (1.0 + regress_tol):
            cand["status"] = "regressed"
            break
        if best is None or spe < best[0]:
            best = (spe, int(b), step_s)
    decision = {
        "knob": "batch_size",
        "chosen": best[1] if best else None,
        "candidates": candidates,
        "hbm_limit_bytes": int(limit) if limit else None,
        "headroom": headroom,
        "regress_tol": regress_tol,
        "evidence": "hbm_preflight_estimate+measured_step_window",
    }
    if best:
        decision["chosen_s_per_example"] = best[0]
        decision["chosen_step_s"] = round(best[2], 6)
    return decision


def _length_counts(lengths):
    """Normalize a length sample ({len: count} dict or iterable of
    ints) to a sorted (length, count) list."""
    if isinstance(lengths, dict):
        items = [(int(n), int(c)) for n, c in lengths.items() if c > 0]
    else:
        lengths = list(lengths)
        if lengths and isinstance(lengths[0], tuple):
            # already a (length, count) pairing (internal re-entry)
            items = [(int(n), int(c)) for n, c in lengths if c > 0]
        else:
            counts = {}
            for n in lengths:
                counts[int(n)] = counts.get(int(n), 0) + 1
            items = list(counts.items())
    if not items or min(n for n, _ in items) < 1:
        raise ValueError("lengths must be a non-empty sample of "
                         "positive ints")
    return sorted(items)


def token_fill(lengths, bounds):
    """Real-token fill fraction of a bound set over an observed length
    histogram: each sample pads to the smallest bound >= its length
    (samples above the top bound clamp to it — a real reader would
    truncate or reject).  fill = real tokens / padded tokens."""
    counts = _length_counts(lengths)
    bounds = sorted(int(b) for b in bounds)
    if not bounds:
        raise ValueError("bounds must be non-empty")
    real = padded = 0
    for n, c in counts:
        b = next((b for b in bounds if b >= n), bounds[-1])
        real += min(n, b) * c
        padded += b * c
    return real / float(padded)


def choose_bucket_bounds(lengths, k=4, multiple=16, max_len=None):
    """Pick up to ``k`` bucket bounds maximizing real-token fill over an
    observed length histogram, restricted to multiples of ``multiple``
    (hardware-friendly sizes FIRST, fill-optimal second — the PERF.md
    r4 ruling: bounds {16,32,48,64} beat six finer ragged bounds whose
    higher fill lost to poor MXU tiling).  The top bound always covers
    ``max_len`` (default: the sample's max).  Solved exactly by DP over
    the sorted candidates (optimal histogram partition) — polynomial in
    max_len/multiple, so long-context bound sets stay cheap."""
    counts = _length_counts(lengths)
    sample_max = counts[-1][0]
    max_len = int(max_len or sample_max)
    if max_len < sample_max:
        raise ValueError("max_len %d below the sample's max length %d"
                         % (max_len, sample_max))
    multiple = max(1, int(multiple))
    top = int(math.ceil(max_len / float(multiple))) * multiple
    cands = list(range(multiple, top + 1, multiple))
    k = max(1, min(int(k), len(cands)))
    # maximizing fill = minimizing padded tokens, which decomposes over
    # the chosen bounds: lengths in (prev_bound, bound] pad to bound.
    # DP over sorted candidates (optimal histogram partition, O(n^2 k))
    # — a long-context max_len yields a hundred-plus candidates, where
    # the naive subset enumeration explodes combinatorially.
    n = len(cands)
    pref = [0] * (n + 1)          # samples with length <= cands[i-1]
    it = iter(counts)
    cur = next(it, None)
    for i, c in enumerate(cands):
        pref[i + 1] = pref[i]
        while cur is not None and cur[0] <= c:
            pref[i + 1] += cur[1]
            cur = next(it, None)

    def seg(h, i):
        # padded tokens of lengths in (cands[h-1], cands[i-1]] at bound
        # cands[i-1]; h == 0 means "no smaller bound chosen"
        return (pref[i] - pref[h]) * cands[i - 1]

    INF = float("inf")
    dp = [[INF] * (k + 1) for _ in range(n + 1)]    # dp[i][j]: i-th
    parent = [[0] * (k + 1) for _ in range(n + 1)]  # cand is j-th bound
    for i in range(1, n + 1):
        dp[i][1] = seg(0, i)
        for j in range(2, min(k, i) + 1):
            for h in range(j - 1, i):
                cost = dp[h][j - 1] + seg(h, i)
                if cost < dp[i][j]:
                    dp[i][j] = cost
                    parent[i][j] = h
    best_j = min(range(1, k + 1), key=lambda j: dp[n][j])
    bounds = []
    i, j = n, best_j
    while j >= 1:
        bounds.append(cands[i - 1])
        i, j = parent[i][j], j - 1
    bounds.reverse()
    best_fill = token_fill(counts, bounds)
    return {"knob": "bucket_bounds",
            "chosen": bounds,
            "fill": round(best_fill, 4),
            "k": k, "multiple": multiple, "top_bound": top,
            "candidates_considered": len(cands),
            "pad_to_max_fill": round(token_fill(counts, [top]), 4),
            "evidence": "length_histogram_fill"}


def decide_checkpoint_interval(step_s, snapshot_s, save_s=0.0,
                               budget=None, async_save=True,
                               replay_step_s=None, min_interval=1,
                               max_interval=100000):
    """CheckFreq-style checkpoint cadence from measured costs.

    ``step_s``: measured steady-state step seconds; ``snapshot_s``: the
    synchronous device->host snapshot cost (the only on-step cost of an
    async save); ``save_s``: the full serialize+fsync+commit write span
    (on-step only in sync mode, but the async drain bound below needs
    it either way); ``budget``: max fraction of compute spent on
    checkpointing (default ``FLAGS_autotune_overhead_budget``).

    interval = the SMALLEST step count such that (a) on-step cost per
    interval stays under budget and (b) the async write drains inside
    the interval (a write slower than the interval's compute backs up
    into the next snapshot and the drain lands on the step path).
    Monotone non-decreasing in every measured cost.  ``replay_step_s``
    (default ``step_s``) prices the worst-case rollback replay of one
    interval — evidence for the guardian, not a constraint: the formula
    already picks the smallest budget-feasible interval, which is also
    the recovery-optimal one.
    """
    step_s = float(step_s)
    if step_s <= 0:
        raise ValueError("step_s must be positive")
    snapshot_s = max(0.0, float(snapshot_s))
    save_s = max(0.0, float(save_s))
    if budget is None:
        budget = float(_flag("autotune_overhead_budget", 0.035))
    budget = float(budget)
    if budget <= 0:
        raise ValueError("budget must be positive")
    on_step_cost = snapshot_s + (0.0 if async_save else save_s)
    interval = int(math.ceil(on_step_cost / (budget * step_s)))
    drain = int(math.ceil(save_s / step_s)) if async_save else 0
    interval = max(int(min_interval), interval, drain)
    interval = min(interval, int(max_interval))
    replay_step_s = float(replay_step_s if replay_step_s is not None
                          else step_s)
    return {"knob": "checkpoint_interval",
            "chosen": interval,
            "step_s": round(step_s, 6),
            "snapshot_s": round(snapshot_s, 6),
            "save_s": round(save_s, 6),
            "async_save": bool(async_save),
            "budget": budget,
            "overhead_frac": round(
                on_step_cost / (interval * step_s), 6),
            "drain_bound_steps": drain,
            "worst_case_replay_s": round(interval * replay_step_s, 6),
            "evidence": "measured_checkpoint_spans"}


# ---------------------------------------------------------------------------
# TunedConfig artifact
# ---------------------------------------------------------------------------

class TunedConfig:
    """The tuner's output artifact: a list of decisions with their
    evidence, serialized as JSON.  ``bench.py --autotune`` embeds it in
    the bench artifact; ``contrib.Trainer(autotune=...)`` consumes it;
    ``tools/autotune_report.py`` renders it for humans."""

    VERSION = 1

    def __init__(self, decisions=None, meta=None):
        self.decisions = list(decisions or [])
        self.meta = dict(meta or {})
        self.meta.setdefault("version", self.VERSION)
        if "run_id" not in self.meta:
            from . import monitor

            self.meta["run_id"] = monitor.run_id()
        self.meta.setdefault("created_ts", time.time())

    # -- content -------------------------------------------------------
    def add(self, decision, fingerprint=None, source="measured"):
        """Append one decision dict (the output of a decide_*/tune_*
        call), stamped with provenance."""
        d = dict(decision)
        if fingerprint:
            d["fingerprint"] = fingerprint
        d.setdefault("source", source)
        self.decisions.append(d)
        _event({"event": "autotune_decision", "knob": d.get("knob"),
                "chosen": d.get("chosen"),
                "source": d.get("source"),
                "fingerprint": d.get("fingerprint")})
        return d

    def get(self, knob):
        """The LAST decision for ``knob`` (latest wins), or None."""
        for d in reversed(self.decisions):
            if d.get("knob") == knob:
                return d
        return None

    def value(self, knob, default=None):
        d = self.get(knob)
        if d is None:
            return default
        return d.get("chosen", default)

    def as_dict(self):
        return {"meta": dict(self.meta),
                "decisions": [dict(d) for d in self.decisions]}

    # -- persistence ---------------------------------------------------
    def save(self, path):
        """Atomic JSON write; returns ``path``."""
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.as_dict(), f, indent=2, sort_keys=True)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path):
        with open(path) as f:
            doc = json.load(f)
        return cls(decisions=doc.get("decisions", []),
                   meta=doc.get("meta", {}))

    # -- application ---------------------------------------------------
    def apply(self):
        """Walk the decisions for the process, RESPECTING pins: a flag
        the user set explicitly (env or ``set_flags``) always wins over
        the tuner.  Returns a list of (knob, outcome) pairs — outcome is
        "pinned" (user override wins), "advisory" (knobs like batch size
        that callers read from the artifact rather than a flag) or
        "ignored" (a knob this program no longer has: an artifact is
        input from outside, and one written by an older run may still
        hold an ``attention_kernel`` or ``quant_kernel`` ruling — kernels
        are chosen by the ops' own rules now)."""
        from . import flags

        outcomes = []
        for d in self.decisions:
            knob = d.get("knob")
            if knob in _RETIRED_KNOBS:
                outcome = "ignored"
            elif knob == "quantization" and flags.pinned("quantize_mode"):
                outcome = "pinned"
            else:
                # read from the artifact by its consumer (the serving
                # engines' quantization, the Trainer's checkpoint
                # interval), not a flag; recorded so the trail is complete
                outcome = "advisory"
            outcomes.append((knob, outcome))
        _event({"event": "autotune_applied",
                "outcomes": [list(o) for o in outcomes]})
        return outcomes


# ---------------------------------------------------------------------------
# measurement drivers
# ---------------------------------------------------------------------------

def hbm_ceiling(device=None):
    """The tuner's device-memory ceiling in bytes:
    ``FLAGS_autotune_hbm_bytes`` when set (tests, CPU drills with a
    fake limit), else ``FLAGS_preflight_hbm_bytes``, else the device's
    own ``memory_stats()['bytes_limit']``; None = no gate (CPU backends
    usually report nothing)."""
    override = int(_flag("autotune_hbm_bytes", 0))
    if override > 0:
        return override
    from .monitor.program_profile import _device_capacity

    return _device_capacity(device)


def measure_step_window(exe, program, feed, fetch_list, steps=4,
                        warmup=1, scope=None):
    """Seconds per step over a short fetch-synced dispatch window.  The
    feed is staged on device once; the window dispatches through the
    executor's already-seeded AOT executable (``cost_analysis`` seeds
    it), so the window itself performs zero compiles."""
    import jax

    dev = exe.place.jax_device()
    staged = {k: jax.device_put(np.asarray(v), dev)
              for k, v in feed.items()}
    last = None
    for _ in range(max(0, warmup)):
        last = exe.run(program, feed=staged, fetch_list=fetch_list,
                       scope=scope, return_numpy=False)
    if last is not None:
        np.asarray(last[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        last = exe.run(program, feed=staged, fetch_list=fetch_list,
                       scope=scope, return_numpy=False)
    np.asarray(last[0])       # fetch-sync: true completion of the chain
    return (time.perf_counter() - t0) / float(steps)


@contextlib.contextmanager
def _probe_run(place):
    """A fresh scope + executor whose steps are tagged as PROBE work:
    the program-profile accounting marks probe-only signatures so the
    tuner's throwaway candidates never blend into the per-program
    report's wall-share/MFU rows (the A/B-rung pollution bug, fixed at
    the accounting layer)."""
    from . import scope as _scope
    from .executor import Executor
    from .monitor import program_profile

    s = _scope.Scope()
    with _scope.scope_guard(s), program_profile.probe_accounting():
        yield Executor(place), s


def tune_batch_size(main_program, startup_program, make_feed, fetch,
                    place, ladder=None, start=32, max_batch=4096,
                    probe_steps=4, warmup_steps=1, regress_tol=0.05,
                    headroom=0.9, config=None):
    """Tune the batch size for one program: run the geometric ladder
    with the HBM-preflight gate and short measured windows (see
    :func:`run_batch_ladder` for the policy).  ``make_feed(batch)``
    builds the feed dict at a candidate batch; the program itself is
    batch-agnostic (feed shapes pick the jit signature).

    Compiles exactly once per probed rung (the ``cost_analysis``
    explicit compile, which seeds the AOT dispatch slot the measured
    window reuses) — zero backend compiles beyond the declared ladder.
    Appends the decision to ``config`` when given; returns it."""
    from . import compile_cache
    from .executor import _coerce_feed
    from .framework import Variable
    from .monitor import program_profile

    fetch_list = [fetch]
    fetch_names = (fetch.name if isinstance(fetch, Variable)
                   else str(fetch),)
    fp = compile_cache.program_fingerprint(main_program)
    block = main_program.global_block()
    with _probe_run(place) as (exe, scope):
        exe.run(startup_program, scope=scope)
        dev = place.jax_device()
        limit = hbm_ceiling(dev)

        def probe_fn(b):
            feed = make_feed(b)
            exe.cost_analysis(main_program, feed, fetch_list,
                              scope=scope)
            # look the profile up by THIS rung's exact feed signature
            # (the executor's own coercion included): a warm registry
            # would otherwise serve the newest-captured profile — some
            # other batch's peak — and poison the ladder
            names = sorted(feed)
            sig = tuple(
                (n, tuple(v.shape), str(v.dtype)) for n, v in
                ((n, _coerce_feed(block, n, feed[n])) for n in names))
            prof = program_profile.get(fp, sig, kind="executor",
                                       fetch_names=fetch_names)
            peak = prof.peak_hbm_bytes if prof is not None else None
            _event({"event": "autotune_probe", "knob": "batch_size",
                    "batch": int(b), "peak_hbm_bytes": peak,
                    "fingerprint": fp[:12]})
            return peak

        def measure_fn(b):
            # probe_fn already ran for this rung (the ladder always
            # probes before it measures): the signature is compiled and
            # the AOT dispatch slot is seeded, so the window performs
            # zero additional compiles
            feed = make_feed(b)
            return measure_step_window(exe, main_program, feed,
                                       fetch_list, steps=probe_steps,
                                       warmup=warmup_steps, scope=scope)

        decision = run_batch_ladder(
            ladder or batch_ladder(start, max_batch), limit,
            probe_fn, measure_fn, regress_tol=regress_tol,
            headroom=headroom)
    if config is not None:
        config.add(decision, fingerprint=fp[:12])
    else:
        _event({"event": "autotune_decision", "knob": "batch_size",
                "chosen": decision["chosen"], "fingerprint": fp[:12]})
    return decision


def _span_mean(name):
    """Mean of a ``span/<name>`` monitor histogram, or None."""
    from . import monitor

    h = monitor.registry().get("span/" + name)
    if h is None or not getattr(h, "count", 0):
        return None
    return h.sum / h.count


def tune_checkpoint_interval(step_s=None, snapshot_s=None, save_s=None,
                             budget=None, async_save=True,
                             replay_step_s=None, manager=None,
                             config=None):
    """Checkpoint cadence from MEASURED costs: explicit arguments win;
    otherwise the manager's own cost samples
    (``TrainStateCheckpointManager.measured_costs()``), then the
    monitor's ``span/checkpoint/{snapshot,save}`` histograms; ``step_s``
    falls back to the StepStats mean.  Raises when no step-time
    measurement exists (there is nothing profile-guided about a
    guess)."""
    costs = manager.measured_costs() if manager is not None else {}
    if snapshot_s is None:
        snapshot_s = costs.get("snapshot_s")
    if snapshot_s is None:
        snapshot_s = _span_mean("checkpoint/snapshot")
    if save_s is None:
        save_s = costs.get("save_s")
    if save_s is None:
        save_s = _span_mean("checkpoint/save")
    if snapshot_s is None and save_s is None:
        # zero-cost inputs would compute interval=1 (checkpoint every
        # step) from NO evidence — the opposite of the budget's intent
        raise ValueError(
            "tune_checkpoint_interval: no measured checkpoint cost "
            "(pass snapshot_s/save_s, or complete at least one save "
            "through the manager / a monitored run first)")
    if step_s is None:
        from . import monitor

        summ = monitor.step_stats().summary() or {}
        step_s = summ.get("mean_step_seconds")
    if not step_s:
        raise ValueError(
            "tune_checkpoint_interval: no measured step time (pass "
            "step_s, or run some monitored steps first)")
    decision = decide_checkpoint_interval(
        step_s, snapshot_s or 0.0, save_s or 0.0, budget=budget,
        async_save=async_save, replay_step_s=replay_step_s)
    if manager is not None and costs:
        decision["measured_saves"] = costs.get("n", 0)
    if config is not None:
        config.add(decision)
    else:
        _event({"event": "autotune_decision",
                "knob": "checkpoint_interval",
                "chosen": decision["chosen"]})
    return decision


# ---------------------------------------------------------------------------
# pipeline schedule + microbatch tuning
# ---------------------------------------------------------------------------

def decide_pipeline(candidates, tol=0.03):
    """Pure pipeline-schedule policy over measured candidates.

    ``candidates``: dicts with ``schedule``, ``microbatches``,
    ``step_s`` (None/absent = not measured), ``bubble_fraction``,
    ``in_flight``, and optionally ``rejected`` (HBM gate).  Picks the
    fastest measured candidate; everything within ``tol`` of it
    tie-breaks by (bubble fraction, in-flight memory bound, smaller M)
    — schedule accounting settles what timing noise cannot."""
    ok = [c for c in candidates
          if not c.get("rejected") and c.get("step_s")]
    if not ok:
        raise ValueError(
            "decide_pipeline: no measured candidate survived "
            "(all rejected by the HBM gate or unmeasured)")
    best = min(ok, key=lambda c: c["step_s"])
    near = [c for c in ok if c["step_s"] <= best["step_s"] * (1 + tol)]
    near.sort(key=lambda c: (c.get("bubble_fraction", 1.0),
                             c.get("in_flight", 1 << 30),
                             c["microbatches"]))
    chosen = near[0]
    return {"knob": "pipeline",
            "chosen": {"schedule": chosen["schedule"],
                       "microbatches": int(chosen["microbatches"])},
            "candidates": [dict(c) for c in candidates],
            "evidence": "measured_step_window"}


def tune_pipeline(main_program, startup_program, feed, fetch, mesh,
                  build_strategy=None, schedules=None,
                  microbatch_candidates=None, probe_steps=3,
                  warmup_steps=1, tol=0.03, headroom=0.9, config=None):
    """Choose the pipeline schedule and microbatch count for a program
    with ``pipeline_region`` ops on ``mesh``'s ``pp`` axis, the same
    way the batch ladder works: one compile per candidate, a short
    measured step window through the ParallelExecutor, the compiled
    peak-HBM estimate as a pre-dispatch rejection gate
    (:func:`hbm_ceiling` — CPU-testable with a fake limit), and the
    schedule table's exact bubble accounting as evidence and
    tie-breaker.  Decisions land in ``config`` (TunedConfig) with the
    full candidate table.

    Pin semantics: an explicit ``build_strategy.pipeline_schedule`` is
    the user's choice — recorded as a pinned decision, never measured
    over."""
    from . import compile_cache
    from . import scope as _scope
    from .framework import Variable
    from .monitor import program_profile
    from .parallel.mesh import AXIS_PP
    from .parallel.parallel_executor import ParallelExecutor
    from .parallel.pipeline import SCHEDULES, schedule_stats
    from .parallel.strategy import BuildStrategy

    bs = build_strategy or BuildStrategy()
    fp = compile_cache.program_fingerprint(main_program)
    pp = 1
    if AXIS_PP in mesh.axis_names:
        pp = mesh.devices.shape[mesh.axis_names.index(AXIS_PP)]
    region_stages = [int(op.attrs["stages"])
                     for op in main_program.global_block().ops
                     if op.type == "pipeline_region"]
    if pp <= 1 or not region_stages:
        raise ValueError(
            "tune_pipeline: program has no pipeline_region ops that "
            "would run pipelined on this mesh (pp=%d, regions=%d)"
            % (pp, len(region_stages)))

    if bs.pipeline_schedule is not None:
        decision = {"knob": "pipeline",
                    "chosen": {"schedule": bs.pipeline_schedule,
                               "microbatches":
                               bs.pipeline_microbatches},
                    "evidence": "pinned",
                    "candidates": []}
        if config is not None:
            config.add(decision, fingerprint=fp[:12], source="pinned")
        return decision

    batch = max((int(np.shape(v)[0]) for v in feed.values()
                 if np.ndim(v) >= 1), default=0)

    def _engages(sched):
        # mirrors the lowering's engagement test (pipeline_region's
        # pp_ok): a candidate that would silently run the SEQUENTIAL
        # fallback must never be measured as if it pipelined (its
        # bubble stats would be fabricated and could win the
        # tie-break).  Interleaved engages at any v >= 1 there.
        if sched == "interleaved":
            return all(sc % pp == 0 for sc in region_stages)
        return all(sc == pp for sc in region_stages)

    if schedules is None:
        schedules = [sc for sc in ("gpipe", "1f1b") if _engages(sc)]
        # the default list adds interleaved only when it brings v > 1
        # chunks per device — v == 1 is gpipe with a wrap edge, a
        # wasted compile to measure by default (an explicit
        # schedules=['interleaved'] still may)
        if all(sc % pp == 0 and sc // pp > 1 for sc in region_stages):
            schedules.append("interleaved")
        elif not schedules and _engages("interleaved"):
            # mixed region stage counts (some v == 1): interleaved is
            # the only schedule that pipelines them all — measure it
            # even though part of it degenerates to a wrapped gpipe
            schedules.append("interleaved")
        if not schedules:
            raise ValueError(
                "tune_pipeline: no schedule runs the program's "
                "pipeline regions (stages=%s) pipelined on this mesh "
                "(pp=%d)" % (region_stages, pp))
    for s in schedules:
        if s not in SCHEDULES:
            raise ValueError("unknown schedule %r" % s)
    if microbatch_candidates is None:
        microbatch_candidates = [m for m in (pp, 2 * pp, 4 * pp)
                                 if batch and batch % m == 0]
    if not microbatch_candidates:
        raise ValueError(
            "tune_pipeline: no microbatch candidate divides the batch "
            "(%d) — pass microbatch_candidates" % batch)

    limit = hbm_ceiling(mesh.devices.flat[0])
    fetch_list = [fetch]
    fetch_name = fetch.name if isinstance(fetch, Variable) else str(fetch)
    candidates = []
    with program_profile.probe_accounting():
        for sched in schedules:
            for m in microbatch_candidates:
                # every non-viable combination is RECORDED, never
                # silently skipped: the artifact's candidate table must
                # cover the searched space
                if sched == "interleaved" and m % pp:
                    candidates.append(
                        {"schedule": sched, "microbatches": int(m),
                         "rejected": "microbatches %% pp != 0 "
                                     "(interleaved groups of %d)" % pp})
                    continue
                if not _engages(sched):
                    candidates.append(
                        {"schedule": sched, "microbatches": int(m),
                         "rejected": "not pipelined on this mesh "
                                     "(stages=%s, pp=%d)"
                                     % (region_stages, pp)})
                    continue
                stats = [schedule_stats(
                    sched, pp, m, s // pp if sched == "interleaved"
                    else 1) for s in region_stages]
                cand = {"schedule": sched, "microbatches": int(m),
                        "bubble_fraction": round(
                            sum(st["idle_units"] for st in stats)
                            / max(1, sum(st["total_units"]
                                         for st in stats)), 4),
                        "in_flight": max(st["in_flight"]
                                         for st in stats)}
                cbs = BuildStrategy()
                for attr, val in vars(bs).items():
                    setattr(cbs, attr, val)
                cbs.pipeline_schedule = sched
                cbs.pipeline_microbatches = int(m)
                scope = _scope.Scope()
                try:
                    with _scope.scope_guard(scope):
                        from .executor import CPUPlace, Executor
                        Executor(CPUPlace()).run(startup_program,
                                                 scope=scope)
                        pe = ParallelExecutor(
                            loss_name=fetch_name, mesh=mesh,
                            build_strategy=cbs,
                            main_program=main_program, scope=scope)
                        # the profile registry keys by (fingerprint,
                        # feed sig, partition) — NOT by schedule — so a
                        # warm trace cache (a second tune call) serves
                        # a stale peak from some other candidate.  Only
                        # a capture that happened DURING this
                        # candidate's cold dispatch is evidence.
                        prof_before = program_profile.get(fp)
                        for _ in range(max(1, warmup_steps)):
                            pe.run(feed=feed, fetch_list=fetch_list)
                        prof = program_profile.get(fp)
                        peak = prof.peak_hbm_bytes \
                            if prof is not None \
                            and prof is not prof_before else None
                        cand["peak_hbm_bytes"] = peak
                        if limit and peak and peak > headroom * limit:
                            cand["rejected"] = "peak_hbm %d > %.0f" % (
                                peak, headroom * limit)
                        else:
                            t0 = time.perf_counter()
                            for _ in range(probe_steps):
                                out = pe.run(feed=feed,
                                             fetch_list=fetch_list)
                            np.asarray(out[0])
                            cand["step_s"] = round(
                                (time.perf_counter() - t0)
                                / probe_steps, 6)
                except Exception as e:  # noqa: BLE001 — a failed
                    # candidate is evidence, not a tuner crash
                    cand["rejected"] = "error: %s" % str(e)[:160]
                _event({"event": "autotune_probe", "knob": "pipeline",
                        "schedule": sched, "microbatches": int(m),
                        "step_s": cand.get("step_s"),
                        "rejected": cand.get("rejected"),
                        "fingerprint": fp[:12]})
                candidates.append(cand)
    decision = decide_pipeline(candidates, tol=tol)
    decision["mesh_pp"] = int(pp)
    if config is not None:
        config.add(decision, fingerprint=fp[:12])
    else:
        _event({"event": "autotune_decision", "knob": "pipeline",
                "chosen": decision["chosen"], "fingerprint": fp[:12]})
    return decision


# ---------------------------------------------------------------------------
# quantized execution: accuracy-gated program A/B (ISSUE 14)
# ---------------------------------------------------------------------------

def eval_delta(reference, outputs):
    """Relative-L1 accuracy delta between two fetch lists: the
    quantization gate's eval metric (0 = bit-identical; scale-free, so
    one budget covers logits and probabilities alike)."""
    num = den = 0.0
    for r, o in zip(reference, outputs):
        r = np.asarray(r, np.float64)
        o = np.asarray(o, np.float64)
        num += float(np.abs(o - r).sum())
        den += float(np.abs(r).sum())
    return num / (den + 1e-12)


def decide_quantization(fp_step_s, candidates, budget,
                        min_speedup=1.0, batch=None):
    """Pure quantization policy over measured candidates.

    ``candidates``: dicts with ``mode``, ``accuracy_delta``, ``step_s``
    (or ``rejected`` for a candidate that failed outright).  A candidate
    survives only when its accuracy delta is under ``budget`` AND it is
    at least ``min_speedup`` faster than full precision — otherwise
    full precision is kept (``chosen`` None).  Rejections stay in the
    candidate table as evidence."""
    fp_step_s = float(fp_step_s)
    ok = []
    cands = [dict(c) for c in candidates]
    for c in cands:
        if c.get("rejected"):
            continue
        delta = float(c.get("accuracy_delta", np.inf))
        step_s = float(c.get("step_s") or 0.0)
        speedup = fp_step_s / step_s if step_s > 0 else 0.0
        c["speedup_vs_fp"] = round(speedup, 4)
        if delta > float(budget):
            c["status"] = "rejected_accuracy"
            continue
        if speedup < float(min_speedup):
            c["status"] = "rejected_slower"
            continue
        c["status"] = "ok"
        ok.append(c)
    chosen = min(ok, key=lambda c: c["step_s"]) if ok else None
    decision = {"knob": "quantization",
                "chosen": chosen["mode"] if chosen else None,
                "fp_step_s": round(fp_step_s, 6),
                "accuracy_budget": float(budget),
                "min_speedup": float(min_speedup),
                "candidates": cands,
                "evidence": "measured_ab_window+eval_delta"}
    if batch:
        decision["fp_tok_s"] = round(batch / fp_step_s, 2)
    if chosen:
        decision["accuracy_delta"] = chosen["accuracy_delta"]
        decision["chosen_step_s"] = chosen["step_s"]
        if batch:
            decision["chosen_tok_s"] = round(batch / chosen["step_s"], 2)
    return decision


def tune_quantization(main_program, scope, feed, fetch_list, place,
                      modes=("weight_only", "dynamic"), budget=None,
                      probe_steps=4, warmup_steps=1, min_speedup=1.0,
                      candidates=None, config=None):
    """Accuracy-gated quantization A/B for one inference program: run
    the full-precision program as the reference, build (or accept) a
    quantized candidate per mode via the ``quantize_inference`` pass
    over the SAME scope, and keep the fastest candidate whose measured
    eval delta stays under ``budget``
    (``FLAGS_quantize_accuracy_budget``) — otherwise full precision is
    kept, with every rejection recorded as TunedConfig evidence.

    ``candidates`` optionally supplies prepared ``(mode, program)``
    pairs (the corruption drills inject broken scales this way);
    the default builds them with the pass.  A pinned
    ``FLAGS_quantize_mode`` is the operator's choice — recorded, never
    measured over."""
    from . import compile_cache, flags
    from .executor import Executor
    from .monitor import program_profile

    if budget is None:
        budget = float(_flag("quantize_accuracy_budget", 0.02))
    fp = compile_cache.program_fingerprint(main_program)
    if flags.pinned("quantize_mode"):
        mode = str(flags.flag("quantize_mode") or "off")
        decision = {"knob": "quantization",
                    "chosen": None if mode in ("", "off") else mode,
                    "accuracy_budget": float(budget),
                    "evidence": "pinned", "candidates": []}
        if config is not None:
            config.add(decision, fingerprint=fp[:12], source="pinned")
        return decision

    batch = max((int(np.shape(v)[0]) for v in feed.values()
                 if np.ndim(v) >= 1), default=0)
    with program_profile.probe_accounting():
        # shared scope, no donation: the quantized candidates read the
        # same master weights the reference program does
        exe = Executor(place, donate_state=False)
        ref = [np.asarray(r) for r in exe.run(
            main_program, feed=feed, fetch_list=fetch_list, scope=scope)]
        fp_step_s = measure_step_window(
            exe, main_program, feed, fetch_list, steps=probe_steps,
            warmup=warmup_steps, scope=scope)
        if candidates is None:
            from .transpiler.quantize_pass import quantize_inference

            candidates = [(mode, quantize_inference(
                main_program, scope=scope, mode=mode)) for mode in modes]
        cands = []
        for mode, qprog in candidates:
            cand = {"mode": mode}
            try:
                outs = exe.run(qprog, feed=feed, fetch_list=fetch_list,
                               scope=scope)
                cand["accuracy_delta"] = round(eval_delta(ref, outs), 6)
                step_s = measure_step_window(
                    exe, qprog, feed, fetch_list, steps=probe_steps,
                    warmup=warmup_steps, scope=scope)
                cand["step_s"] = round(step_s, 6)
                if batch:
                    cand["tok_s"] = round(batch / step_s, 2)
            except Exception as e:  # noqa: BLE001 — a failed candidate
                cand["rejected"] = "error: %s" % str(e)[:160]  # is
                # evidence, not a tuner crash
            _event({"event": "autotune_probe", "knob": "quantization",
                    "mode": mode,
                    "accuracy_delta": cand.get("accuracy_delta"),
                    "step_s": cand.get("step_s"),
                    "rejected": cand.get("rejected"),
                    "fingerprint": fp[:12]})
            cands.append(cand)
    decision = decide_quantization(fp_step_s, cands, budget,
                                   min_speedup=min_speedup, batch=batch)
    if config is not None:
        config.add(decision, fingerprint=fp[:12])
    else:
        _event({"event": "autotune_decision", "knob": "quantization",
                "chosen": decision["chosen"], "fingerprint": fp[:12]})
    return decision


# ---------------------------------------------------------------------------
# serving decode tuners (ISSUE 16): int8 KV gate + speculation k
# ---------------------------------------------------------------------------

def tune_kv_quantization(build_spec, prompts, place=None,
                         max_new_tokens=8, budget=None, min_speedup=0.0,
                         config=None):
    """Accuracy gate for int8 KV pages, riding ``tune_quantization``'s
    discipline: drive the SAME weights (same build seed/prefix, fresh
    scope each) through a f32-KV paged engine as the reference and an
    int8-KV paged engine as the candidate, compare the per-step greedy
    logits with :func:`eval_delta`, and keep int8 KV only when the
    delta stays under ``budget`` (``FLAGS_quantize_accuracy_budget``).
    A rejection is recorded as TunedConfig evidence, exactly like a
    rejected weight-quantization candidate.

    ``build_spec(kv_dtype)`` -> a paged DecoderSpec (``kv_dtype`` is
    ``None`` for the f32 reference, ``"int8"`` for the candidate).
    ``min_speedup`` defaults to 0: int8 KV is an HBM-capacity knob
    (half the pool bytes), not a latency knob — it must not LOSE
    accuracy, but it does not have to win time."""
    import time as _time

    from .executor import CPUPlace
    from .serving.engine import GenerationEngine

    if budget is None:
        budget = float(_flag("quantize_accuracy_budget", 0.02))
    place = place or CPUPlace()

    def _drive(kv_dtype):
        spec = build_spec(kv_dtype)
        eng = GenerationEngine(spec, place=place,
                               max_new_tokens=max_new_tokens,
                               timeout_s=600.0, record_logits=True)
        try:
            t0 = _time.monotonic()
            outs = [eng.submit(p).result(1200) for p in prompts]
            wall = _time.monotonic() - t0
        finally:
            eng.close()
        toks = sum(len(o["tokens"]) for o in outs)
        logits = [row for o in outs for row in o["logits"]]
        tokens = [tuple(o["tokens"]) for o in outs]
        return logits, tokens, wall / max(toks, 1)

    ref_logits, ref_tokens, fp_step_s = _drive(None)
    cand = {"mode": "kv_int8"}
    try:
        q_logits, q_tokens, q_step_s = _drive("int8")
        cand["accuracy_delta"] = round(eval_delta(ref_logits, q_logits),
                                       6)
        cand["step_s"] = round(q_step_s, 6)
        cand["greedy_tokens_match"] = q_tokens == ref_tokens
    except Exception as e:  # noqa: BLE001 — evidence, not a crash
        cand["rejected"] = "error: %s" % str(e)[:160]
    _event({"event": "autotune_probe", "knob": "kv_quantization",
            "mode": "kv_int8",
            "accuracy_delta": cand.get("accuracy_delta"),
            "step_s": cand.get("step_s"),
            "rejected": cand.get("rejected")})
    decision = decide_quantization(fp_step_s, [cand], budget,
                                   min_speedup=min_speedup)
    decision["knob"] = "kv_quantization"
    decision["evidence"] = "paged_generation_ab+eval_delta"
    if config is not None:
        config.add(decision)
    else:
        _event({"event": "autotune_decision", "knob": "kv_quantization",
                "chosen": decision["chosen"]})
    return decision


def tune_speculation_k(make_engine, prompts, candidates=(None, 2, 4),
                       config=None):
    """Learn the speculative-decoding ``k`` for a workload: drive the
    same prompt set through ``make_engine(k)`` for each candidate
    (``None`` = speculation off, the baseline) and keep the fastest in
    decode tokens/second.  Greedy invariance is part of the gate: a
    candidate whose outputs differ from the baseline is rejected
    regardless of speed (speculative decoding must be a pure latency
    transform).  The workload decides — a weak draft (low acceptance)
    makes every k>1 SLOWER than the baseline and the tuner keeps
    ``None``."""
    import time as _time

    baseline_tokens = None
    cands = []
    for k in candidates:
        cand = {"k": k}
        try:
            eng = make_engine(k)
            try:
                t0 = _time.monotonic()
                outs = [eng.submit(p).result(1200) for p in prompts]
                wall = _time.monotonic() - t0
                toks = sum(len(o["tokens"]) for o in outs)
                tokens = [tuple(o["tokens"]) for o in outs]
                snap = eng.metrics.paged_snapshot()
            finally:
                eng.close()
            cand["tok_s"] = round(toks / max(wall, 1e-9), 2)
            cand["acceptance_rate"] = snap.get("spec_acceptance_rate")
            if k is None:
                baseline_tokens = tokens
            elif baseline_tokens is not None \
                    and tokens != baseline_tokens:
                cand["rejected"] = "greedy_outputs_diverged"
        except Exception as e:  # noqa: BLE001
            cand["rejected"] = "error: %s" % str(e)[:160]
        _event({"event": "autotune_probe", "knob": "speculation_k",
                "k": k, "tok_s": cand.get("tok_s"),
                "acceptance_rate": cand.get("acceptance_rate"),
                "rejected": cand.get("rejected")})
        cands.append(cand)
    ok = [c for c in cands if not c.get("rejected")
          and c.get("tok_s")]
    best = max(ok, key=lambda c: c["tok_s"]) if ok else None
    decision = {"knob": "speculation_k",
                "chosen": best["k"] if best else None,
                "candidates": cands,
                "evidence": "measured_generation_window"}
    if best:
        decision["chosen_tok_s"] = best["tok_s"]
    if config is not None:
        config.add(decision)
    else:
        _event({"event": "autotune_decision", "knob": "speculation_k",
                "chosen": decision["chosen"]})
    return decision
