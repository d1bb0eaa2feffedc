"""Serving engines: continuous-batching execution over compiled
inference programs.

Two engines share the scheduler/metrics substrate:

* :class:`InferenceEngine` — one-shot forward serving of any saved
  inference model (``io.save_inference_model`` artifact or a live
  program+scope).  Requests are single examples; the loop admits them
  into **fixed slot batches** (one compiled signature per length
  bucket — the first batch per bucket pays the compile, every later
  batch is a single dispatch through the program-profile AOT path the
  executor already runs), pads sequences to bucket bounds, and fans the
  batched fetches back out per request.
* :class:`GenerationEngine` — prefill/decode serving of a
  :class:`~.decoder.DecoderSpec`: admitted prompts prefill into
  recycled cache slots (scattered ``kv_cache_write``), then a single
  compiled decode step advances EVERY active slot one token per
  iteration with donated in-place cache updates; finished slots are
  refilled between decode steps without draining the batch.

Request health is guardian-shaped: per-request timeouts expire queued
work and evict wedged decodes, and a request whose forward produces
non-finite outputs is quarantined (npz + sidecar, same format as the
guardian's poisoned batches) and failed with
:class:`~.scheduler.PoisonedRequestError` — the engine itself never
dies from one bad request."""

import threading

import numpy as np

from .. import io as fluid_io
from ..executor import Executor, default_place
from ..monitor import tracing
from ..profiler import RecordEvent
from ..scope import Scope, scope_guard
from .kv_cache import OutOfPagesError
from .metrics import ServingMetrics
from .scheduler import (ContinuousBatchingScheduler, PoisonedRequestError,
                        RequestTimeoutError)

__all__ = ["InferenceEngine", "GenerationEngine"]


def _load_tuned(tuned_config):
    """Resolve a TunedConfig (path or object) and apply it — the PR-7
    artifact is where serving reads its admitted batch size, bucket
    bounds, per-shape kernel rulings, and quantization ruling from."""
    if tuned_config is None:
        return None
    from .. import autotune

    tuned = (autotune.TunedConfig.load(tuned_config)
             if isinstance(tuned_config, str) else tuned_config)
    tuned.apply()
    return tuned


def _resolve_quantize(quantize, tuned):
    """The engine's quantization mode: an explicit ``quantize`` kwarg
    wins; else a TunedConfig ``quantization`` ruling (the accuracy-gated
    ``tune_quantization`` decision — ``chosen`` None means the gate kept
    full precision); None = off."""
    if quantize is None and tuned is not None:
        d = tuned.get("quantization")
        quantize = d.get("chosen") if d else None
    if not quantize:
        return None
    return "weight_only" if quantize is True else str(quantize)


def _finite_row(arrays, i, slots):
    """Whether request row ``i`` of every float fetch is finite."""
    for a in arrays:
        a = np.asarray(a)
        row = a[i] if a.ndim >= 1 and a.shape[0] == slots else a
        if np.issubdtype(row.dtype, np.floating) and \
                not np.isfinite(row).all():
            return False
    return True


class _EngineBase:
    """Loop-thread plumbing shared by both engines."""

    def __init__(self):
        self._thread = None
        self._stop = threading.Event()

    def _register_monitor(self):
        """Track the engine for watchdog dumps (weakly held): a stall
        report names the in-flight requests, not just the program."""
        from .. import monitor

        monitor.track(self)

    def _running_state(self, slot):
        return "prefill"

    def monitor_state(self):
        """The watchdog's in-flight request view: every queued/running
        request with its trace_id, age, and lifecycle state."""
        now = self._sched._clock()
        reqs = []
        for r in self._sched.pending():
            reqs.append({"id": r.id,
                         "trace_id": r.trace.trace_id
                         if r.trace is not None else None,
                         "state": "queued",
                         "age_s": round(now - r.arrival, 3)})
        for slot, r in sorted(self._sched.running().items()):
            reqs.append({"id": r.id,
                         "trace_id": r.trace.trace_id
                         if r.trace is not None else None,
                         "state": self._running_state(slot),
                         "age_s": round(now - r.arrival, 3)})
        return {"kind": "serving_engine", "name": self.metrics.name,
                "requests": reqs}

    def load_report(self):
        """The load/SLO snapshot a fleet replica's heartbeat carries
        (``serving.fleet``): queue depth + occupancy from the
        scheduler, latency percentiles from the SLO window.  Cheap and
        lock-light — it rides every lease renewal."""
        sched = self._sched
        pct = self.metrics.percentiles()
        return {"queue_depth": sched.queue_depth(),
                "busy_slots": sched.busy_slots(),
                "occupancy": round(sched.occupancy(), 4),
                "p50_ms": (round(pct["p50_s"] * 1e3, 3)
                           if pct["p50_s"] is not None else None),
                "p99_ms": (round(pct["p99_s"] * 1e3, 3)
                           if pct["p99_s"] is not None else None)}

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="serving-loop", daemon=True)
            self._thread.start()
        return self

    def close(self):
        """Stop the loop and fail everything still in flight."""
        self._stop.set()
        self._sched.close()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _publish_expired(self, expired):
        for r in expired:
            self.metrics.note_failure(r, r._error, status="expired")

    def _loop(self):
        """Run iterations until close(); ANY iteration failure is
        contained — a dead loop thread would strand every queued caller
        in result(), so the engine logs and keeps serving."""
        import sys
        import time as _time

        while not self._stop.is_set():
            try:
                self._loop_once()
            except Exception as e:  # noqa: BLE001 — the loop must live
                print("[serving] loop iteration failed: %r" % e,
                      file=sys.stderr, flush=True)
                _time.sleep(0.05)


class InferenceEngine(_EngineBase):
    """Continuous-batching server over one inference program.

    ``model_dir`` loads a ``save_inference_model`` artifact into a
    private scope; alternatively pass a live
    ``(program, feed_names, fetch_vars, scope)``.  ``slots`` is the
    fixed admission batch (default: the TunedConfig ``batch_size``
    decision, else 8); ``bucket_bounds`` pads variable-length sequence
    feeds (default: the TunedConfig ``bucket_bounds`` decision, else
    unbucketed fixed shapes)."""

    def __init__(self, model_dir=None, program=None, feed_names=None,
                 fetch_vars=None, scope=None, place=None, slots=None,
                 bucket_bounds=None, tuned_config=None, timeout_s=30.0,
                 quarantine_dir=None, name="serving", start=True,
                 quantize=None):
        super().__init__()
        self.place = default_place(place)
        self._exe = Executor(self.place, donate_state=False)
        if model_dir is not None:
            scope = Scope()
            with scope_guard(scope):
                program, feed_names, fetch_vars = \
                    fluid_io.load_inference_model(model_dir, self._exe)
        if program is None or scope is None:
            raise ValueError(
                "InferenceEngine needs model_dir or a live "
                "(program, feed_names, fetch_vars, scope)")
        self._program = program
        self._feed_names = list(feed_names)
        self._fetch_vars = list(fetch_vars)
        self._scope = scope
        tuned = _load_tuned(tuned_config)
        # int8 execution: explicit kwarg or the TunedConfig ruling.  A
        # save_inference_model artifact that was ALREADY quantized
        # (dequant_matmul ops + @INT8 persistables) loads cold with no
        # work here; the pass is for live programs / fp artifacts.
        self.quantize_mode = _resolve_quantize(quantize, tuned)
        if self.quantize_mode:
            from ..transpiler.quantize_pass import quantize_inference

            self._program = program = quantize_inference(
                program, scope=scope, mode=self.quantize_mode)
            self._fetch_vars = [
                program.global_block().var(v.name if hasattr(v, "name")
                                           else v)
                for v in self._fetch_vars]
        if slots is None:
            slots = int(tuned.value("batch_size") or 0) if tuned else 0
            slots = slots or 8
        if bucket_bounds is None and tuned is not None:
            bucket_bounds = tuned.value("bucket_bounds")
        self.slots = int(slots)
        # feed classification from the program's own var shapes: two
        # leading dynamic dims = padded sequence (bucket the time dim)
        block = program.global_block()
        self._seq_feeds = set()
        self._len_feeds = {n for n in self._feed_names
                           if n.endswith("@LEN")}
        for n in self._feed_names:
            if n.endswith("@LEN"):
                continue
            v = block._find_var_recursive(n)
            shape = tuple(v.shape or ()) if v is not None else ()
            if len(shape) >= 2 and shape[0] in (-1, None) \
                    and shape[1] in (-1, None):
                self._seq_feeds.add(n)
        # fetches whose row layout carries the padded time dim: trimmed
        # back to each request's true length before fan-out, so engine
        # outputs match direct (unpadded) dispatch shapes
        self._seq_fetches = set()
        for j, v in enumerate(self._fetch_vars):
            shape = tuple(getattr(v, "shape", None) or ())
            if len(shape) >= 2 and shape[0] in (-1, None) \
                    and shape[1] in (-1, None):
                self._seq_fetches.add(j)
        if self._seq_feeds and not bucket_bounds:
            bucket_bounds = [2 ** i for i in range(3, 11)]
        self._sched = ContinuousBatchingScheduler(
            self.slots, bucket_bounds, default_timeout_s=timeout_s,
            trace_kind="infer")
        self.metrics = ServingMetrics(name=name,
                                      quarantine_dir=quarantine_dir)
        self._register_monitor()
        if start:
            self.start()

    # -- client side ---------------------------------------------------
    def submit(self, feed, timeout_s=None, rows=1):
        """Enqueue one request: a single example (arrays without the
        batch dim; sequence feeds are [T, ...]) or — with ``rows`` > 1 —
        a client micro-batch whose arrays carry a leading [rows, ...]
        dim (the predictor's Run unit); micro-batches from concurrent
        clients co-batch into one dispatch.  Returns the request
        future."""
        for n in feed:
            if n not in self._feed_names and not n.endswith("@LEN"):
                raise ValueError(
                    "input %r is not a feed target (expected %s)"
                    % (n, self._feed_names))
        missing = [n for n in self._feed_names
                   if n not in feed and not n.endswith("@LEN")]
        if missing:
            raise ValueError("missing inputs: %s" % missing)
        if rows > 1 and (self._seq_feeds or self._len_feeds):
            raise ValueError(
                "multi-row requests are fixed-shape only; submit "
                "variable-length sequences (or models with @LEN "
                "companions) one example per request")
        length = 0
        for n in self._seq_feeds:
            length = max(length, int(np.shape(feed[n])[0]))
        req = self._sched.submit(dict(feed), length=length,
                                 timeout_s=timeout_s, rows=rows)
        self.metrics.note_submit(req, self._sched.queue_depth())
        return req

    def run(self, feed, timeout=None):
        """Synchronous submit+wait; returns the per-request fetch list
        (ordered like the saved fetch targets)."""
        return self.submit(feed).result(timeout)

    @property
    def feed_names(self):
        return list(self._feed_names)

    # -- loop side -----------------------------------------------------
    def _loop_once(self):
        plan, expired = self._sched.admit()
        self._publish_expired(expired)
        if plan is None:
            self._sched.wait_for_work(timeout=0.05)
            return
        try:
            self._run_batch(plan)
        except Exception as e:  # noqa: BLE001 — a failed batch must
            for r in plan.requests:           # not kill the engine
                if r.done():     # already served/decided mid-batch
                    continue
                self._sched.fail(r, e)
                self.metrics.note_failure(r, e)

    def _pad_seq(self, arr, bucket):
        t = arr.shape[0]
        if bucket is None or t == bucket:
            return arr
        pad = [(0, bucket - t)] + [(0, 0)] * (arr.ndim - 1)
        return np.pad(arr, pad)

    def _run_batch(self, plan):
        reqs = plan.requests
        n_rows = sum(r.rows for r in reqs)
        self.metrics.note_admit(plan, n_rows / float(self.slots),
                                self._sched.queue_depth())
        traced = [r for r in reqs if r.trace is not None]
        for r in traced:
            r.trace.admitted(plan.bucket, self._sched.queue_depth(),
                             r is not reqs[0])
        feed = {}
        for name in self._feed_names:
            if name.endswith("@LEN"):
                base = name[:-len("@LEN")]
                # sequence requests are single-row (submit enforces it)
                lens = [int(r.payload.get(
                    name, np.shape(r.payload[base])[0])) for r in reqs]
                lens += [lens[0]] * (self.slots - n_rows)
                feed[name] = np.asarray(lens, "int32")
                continue
            rows = []
            for r in reqs:
                a = np.asarray(r.payload[name])
                if name in self._seq_feeds:
                    a = self._pad_seq(a, plan.bucket)
                rows.append(a if r.rows > 1 else a[None])
            batch = np.concatenate(rows)
            if n_rows < self.slots:
                # fixed slot batches: pad with copies of row 0 so every
                # bucket compiles exactly one signature
                batch = np.concatenate(
                    [batch, np.repeat(batch[:1], self.slots - n_rows, 0)])
            feed[name] = batch
        t0 = tracing.now_us() if traced else 0.0
        with RecordEvent("serving/batch",
                         args={"batch": len(reqs), "rows": n_rows,
                               "bucket": plan.bucket}):
            outs = self._exe.run(self._program, feed=feed,
                                 fetch_list=self._fetch_vars,
                                 scope=self._scope)
        if traced:
            dur = tracing.now_us() - t0
            for r in traced:
                r.trace.note_batch(
                    t0, dur, r.slot, len(reqs), plan.bucket,
                    (plan.bucket - r.length) if plan.bucket else 0)
        outs = [np.asarray(o) for o in outs]
        off = 0
        for req in reqs:
            lo, hi = off, off + req.rows
            off = hi
            ok = all(_finite_row(outs, i, self.slots)
                     for i in range(lo, hi))
            if not ok:
                self.metrics.quarantine(req, feed=req.payload)
                err = PoisonedRequestError(
                    "request %s produced non-finite outputs and was "
                    "quarantined" % req.id)
                self._sched.fail(req, err, status="quarantined")
                self.metrics.note_failure(req, err, status="quarantined")
                continue
            result = []
            for j, o in enumerate(outs):
                if o.ndim < 1 or o.shape[0] != self.slots:
                    result.append(o)
                    continue
                row = o[lo:hi] if req.rows > 1 else o[lo]
                if j in self._seq_fetches and req.length \
                        and req.rows == 1 and row.ndim >= 1 \
                        and row.shape[0] == plan.bucket:
                    # trim the bucket padding back off the time dim —
                    # the caller's contract is the direct-dispatch shape
                    row = row[:req.length]
                result.append(row)
            if self._sched.complete(req, result):
                self.metrics.note_complete(req,
                                           extra={"batch": len(reqs)})


class GenerationEngine(_EngineBase):
    """Prefill/decode continuous batching over a
    :class:`~.decoder.DecoderSpec`.

    The decode step is ONE compiled program over every cache slot —
    inactive slots ride along masked (their writes land at position 0 of
    a free slot, overwritten by the next prefill) — so slot recycling
    changes host bookkeeping only, never the compiled signature.
    Sampling is greedy argmax (deterministic; the decode-vs-recompute
    parity contract is test-enforced)."""

    def __init__(self, spec, place=None, scope=None, eos_id=None,
                 max_new_tokens=32, timeout_s=60.0, bucket_bounds=None,
                 tuned_config=None, quarantine_dir=None,
                 name="serving", record_logits=False, start=True,
                 quantize=None, draft_spec=None):
        super().__init__()
        self.spec = spec
        self.place = default_place(place)
        self.eos_id = eos_id
        self.max_new_tokens = int(max_new_tokens)
        self.record_logits = bool(record_logits)
        # prefill keeps buffers alive (shared weights, occasional runs);
        # decode donates so the per-step cache update is in place
        self._exe_prefill = Executor(self.place, donate_state=False)
        self._exe_decode = Executor(self.place, donate_state=True)
        fresh_scope = scope is None
        if fresh_scope:
            scope = Scope()
            spec.init_scope(self._exe_prefill, scope)
        self._scope = scope
        tuned = _load_tuned(tuned_config)
        # int8 decode: the per-slot working set is weight-read-bound,
        # so int8 weights shrink it 4x vs the f32 masters.  The pass
        # rewrites all three programs over the SHARED scope (one int8
        # copy per weight name).
        self.quantize_mode = _resolve_quantize(quantize, tuned)
        if self.quantize_mode:
            self.spec = spec = spec.quantize(scope,
                                             mode=self.quantize_mode)
        # paged KV: the engine owns the host-side page allocator and the
        # [slots, max_pages] table it feeds both paged programs.  Unheld
        # table entries carry the OUT-OF-BOUNDS sentinel (num_pages):
        # writes routed through them DROP at the scatter, so a freed or
        # never-filled slot riding the fixed decode batch can never
        # corrupt another request's live pages.
        self.paged = bool(getattr(spec, "paged", False))
        self._alloc = spec.cache.make_allocator() if self.paged else None
        self._table = (np.full(
            (spec.slots, spec.cache.max_pages_per_slot),
            spec.cache.num_pages, "int32") if self.paged else None)
        # speculative decoding: a small fixed-region draft model shares
        # the serving scope; the target verifies spec_k tokens per
        # dispatch through its verify program
        self.draft_spec = draft_spec
        if draft_spec is not None:
            if spec.verify_program is None:
                raise ValueError(
                    "speculative decoding needs a spec built with "
                    "spec_k (no verify program present)")
            if getattr(draft_spec, "paged", False):
                raise ValueError(
                    "the draft model uses the fixed-region cache (it "
                    "is small by design; paging it buys nothing)")
            if draft_spec.slots != spec.slots \
                    or draft_spec.vocab_size != spec.vocab_size \
                    or draft_spec.max_len < spec.max_len:
                raise ValueError(
                    "draft spec must match the target's slots/vocab "
                    "and cover its max_len")
            if fresh_scope:
                draft_spec.init_scope(self._exe_prefill, scope)
        if bucket_bounds is None and tuned is not None:
            bucket_bounds = tuned.value("bucket_bounds")
        if not bucket_bounds:
            bucket_bounds, b = [], 8
            while b < spec.max_len:
                bucket_bounds.append(b)
                b *= 2
            bucket_bounds.append(spec.max_len)
        if self.paged:
            ps = spec.cache.page_size
            for b in bucket_bounds:
                if b % ps:
                    raise ValueError(
                        "bucket bound %d is not page-aligned (page_size "
                        "%d) — paged prefill scatters whole pages"
                        % (b, ps))
        self._sched = ContinuousBatchingScheduler(
            spec.slots, bucket_bounds, default_timeout_s=timeout_s,
            admission_gate=self._page_gate if self.paged else None,
            trace_kind="generate")
        self.metrics = ServingMetrics(name=name,
                                      quarantine_dir=quarantine_dir)
        self._active = {}             # slot -> decode state dict
        self._ticks = 0               # decode ticks served (trace attr)
        self._register_monitor()
        if start:
            self.start()

    def _running_state(self, slot):
        return "decode" if slot in self._active else "prefill"

    # -- paged-KV bookkeeping ------------------------------------------
    def _page_gate(self, req, picked):
        """Admission gate: admit only when the pool can cover this
        request's WORST CASE (no sharing assumed — intra-batch aliases
        and prefix hits only widen the margin) on top of what this
        admission already picked.  A refused request stays queued."""
        reserved = sum(
            self._alloc.pages_needed(len(r.payload["prompt"]),
                                     r.payload["max_new"])
            for r in picked)
        need = self._alloc.pages_needed(len(req.payload["prompt"]),
                                        req.payload["max_new"])
        ok = need <= self._alloc.free_pages() - reserved
        if not ok and req.trace is not None:
            # exhaustion back-pressure: the page_wait span opens at the
            # FIRST refusal and closes at the eventual grant
            req.trace.page_refused()
        return ok

    def _free_pages(self, slot):
        """Release every page ref a slot holds — called on EVERY
        terminal path (complete, expire, quarantine, prefill/decode
        failure, close); the leak regression test drives each."""
        if self._alloc is None:
            return 0
        freed = self._alloc.release(slot)
        self._table[slot, :] = self.spec.cache.num_pages
        self.metrics.note_kv_pages(self._alloc.pages_in_use(),
                                   self._alloc.free_pages())
        return freed

    # -- client side ---------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=None, timeout_s=None):
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens or self.max_new_tokens)
        if len(prompt) + max_new > self.spec.max_len:
            raise ValueError(
                "prompt %d + max_new_tokens %d exceeds the cache "
                "capacity %d" % (len(prompt), max_new, self.spec.max_len))
        req = self._sched.submit(
            {"prompt": prompt, "max_new": max_new},
            length=len(prompt), timeout_s=timeout_s)
        self.metrics.note_submit(req, self._sched.queue_depth())
        return req

    def generate(self, prompt_ids, max_new_tokens=None, timeout=None):
        """Synchronous generation; returns the result dict
        ``{"tokens": [...generated ids...], "prompt_len": int}`` (plus
        per-step ``logits`` rows under ``record_logits``)."""
        return self.submit(prompt_ids, max_new_tokens).result(timeout)

    # -- loop side -----------------------------------------------------
    def _loop_once(self):
        plan, expired = self._sched.admit()
        self._publish_expired(expired)
        if plan is not None:
            try:
                self._prefill(plan)
            except Exception as e:  # noqa: BLE001
                for r in plan.requests:
                    if r.done():
                        continue
                    self._active.pop(r.slot, None)
                    self._free_pages(r.slot)
                    self._sched.fail(r, e)
                    self.metrics.note_failure(r, e)
        self._evict_expired_running()
        if self._active:
            try:
                self._decode_step()
            except Exception as e:  # noqa: BLE001 — fail the batch,
                for slot in list(self._active):    # keep the engine
                    st = self._active.pop(slot)
                    self._free_pages(slot)
                    self._sched.fail(st["req"], e)
                    self.metrics.note_failure(st["req"], e)
        elif plan is None:
            self._sched.wait_for_work(timeout=0.05)

    def _evict_expired_running(self):
        for req in self._sched.expired_running():
            self._active.pop(req.slot, None)
            # the timeout-expired generation goes terminal HERE: its KV
            # pages (and any prefix-page refs) free immediately, not at
            # slot-reuse time — a wedged decode must not pin pool pages
            self._free_pages(req.slot)
            err = RequestTimeoutError(
                "request %s evicted mid-decode after its timeout "
                "budget" % req.id)
            self._sched.fail(req, err, status="expired")
            self.metrics.note_failure(req, err, status="expired")

    def _prefill(self, plan):
        spec = self.spec
        reqs = plan.requests
        head = reqs[0]
        for r in reqs:
            if r.trace is not None:
                r.trace.admitted(plan.bucket,
                                 self._sched.queue_depth(),
                                 r is not head)
        if self.paged:
            # page allocation pre-pass: aliases shared prefix pages,
            # takes fresh ones for the rest.  The admission gate sized
            # this against the free list, so exhaustion here means the
            # gate's invariant broke — fail THAT request, keep the batch.
            kept = []
            for r in reqs:
                try:
                    pages, shared = self._alloc.alloc_for_prompt(
                        r.slot, r.payload["prompt"],
                        r.payload["max_new"])
                except OutOfPagesError as e:
                    self._sched.fail(r, e)
                    self.metrics.note_failure(r, e)
                    continue
                self._table[r.slot, :] = spec.cache.num_pages
                self._table[r.slot, :len(pages)] = pages
                full = len(r.payload["prompt"]) // spec.cache.page_size
                self.metrics.note_prefix_cache(shared, full - shared)
                if r.trace is not None:
                    r.trace.pages_granted(len(pages), shared,
                                          self._alloc.pages_in_use(),
                                          self._alloc.free_pages())
                kept.append(r)
            self.metrics.note_kv_pages(self._alloc.pages_in_use(),
                                       self._alloc.free_pages())
            reqs = kept
            if not reqs:
                return
        n, t, p = len(reqs), plan.bucket, spec.slots
        self.metrics.note_admit(plan, self._sched.occupancy(),
                                self._sched.queue_depth())
        tok = np.zeros((p, t, 1), "int64")
        lens = np.zeros((p,), "int32")
        slots = np.zeros((p,), "int32")
        for i, r in enumerate(reqs):
            prompt = r.payload["prompt"]
            tok[i, :len(prompt), 0] = prompt
            lens[i] = len(prompt)
            slots[i] = r.slot
        # fixed-signature padding: duplicate row 0 INCLUDING its slot —
        # the duplicate write re-writes identical content, a no-op
        for i in range(n, p):
            tok[i], lens[i], slots[i] = tok[0], lens[0], slots[0]
        pos = np.broadcast_to(
            np.arange(t, dtype="int64")[None, :, None], (p, t, 1)).copy()
        feed = {"tok": tok, "tok@LEN": lens, "pos": pos, "slot": slots,
                "wpos": np.zeros((p,), "int32")}
        if self.paged:
            feed["page_table"] = self._table
        traced = [r for r in reqs if r.trace is not None]
        pt0 = tracing.now_us() if traced else 0.0
        with RecordEvent("serving/prefill",
                         args={"batch": n, "bucket": t}):
            (logits,) = self._exe_prefill.run(
                spec.prefill_program, feed=feed,
                fetch_list=[spec.prefill_logits], scope=self._scope)
            if self.draft_spec is not None:
                # the draft shares the admitted batch: same prompts into
                # its own (fixed-region) cache, logits unused
                dfeed = dict(feed)
                dfeed.pop("page_table", None)
                self._exe_prefill.run(
                    self.draft_spec.prefill_program, feed=dfeed,
                    fetch_list=[self.draft_spec.prefill_logits],
                    scope=self._scope)
        if traced:
            pdur = tracing.now_us() - pt0
            for r in traced:
                r.trace.note_prefill(pt0, pdur, r.slot, n, t,
                                     t - len(r.payload["prompt"]))
        logits = np.asarray(logits)
        for i, r in enumerate(reqs):
            row = logits[i, int(lens[i]) - 1]
            if not np.isfinite(row).all():
                self._quarantine(r, reason="non-finite prefill logits")
                continue
            nxt = int(np.argmax(row))
            st = {"req": r, "generated": [nxt], "pos": int(lens[i]),
                  "max_new": r.payload["max_new"], "logits": []}
            if self.record_logits:
                st["logits"].append(row.copy())
            if self._finished(st, nxt):
                self._complete(r.slot, st)
            else:
                self._active[r.slot] = st

    def _decode_step(self):
        if self.draft_spec is not None:
            return self._speculative_step()
        spec = self.spec
        s = spec.slots
        tok = np.zeros((s, 1, 1), "int64")
        pos = np.zeros((s, 1, 1), "int64")
        wpos = np.zeros((s,), "int32")
        clen = np.ones((s,), "int32")
        for slot, st in self._active.items():
            tok[slot, 0, 0] = st["generated"][-1]
            pos[slot, 0, 0] = st["pos"]
            wpos[slot] = st["pos"]
            clen[slot] = st["pos"] + 1
        feed = {"tok": tok, "pos": pos, "wpos": wpos, "cache_len": clen}
        if self.paged:
            feed["page_table"] = self._table
        traced = any(st["req"].trace is not None
                     for st in self._active.values())
        t0 = tracing.now_us() if traced else 0.0
        with RecordEvent("serving/decode_step",
                         args={"active": len(self._active)}):
            (logits,) = self._exe_decode.run(
                spec.decode_program, feed=feed,
                fetch_list=[spec.decode_logits], scope=self._scope)
        logits = np.asarray(logits)
        self._ticks += 1
        if traced:
            # every rider pays (and is attributed) the full tick: the
            # batch is one dispatch, each request was waiting on it
            dur = tracing.now_us() - t0
            for slot, st in self._active.items():
                if st["req"].trace is not None:
                    st["req"].trace.note_decode(t0, dur, slot,
                                                self._ticks,
                                                len(self._active))
        self.metrics.note_decode_step(len(self._active),
                                      self._sched.occupancy())
        for slot in list(self._active):
            st = self._active[slot]
            row = logits[slot, 0]
            if not np.isfinite(row).all():
                self._active.pop(slot)
                self._quarantine(st["req"],
                                 reason="non-finite decode logits")
                continue
            nxt = int(np.argmax(row))
            st["generated"].append(nxt)
            st["pos"] += 1
            if self.record_logits:
                st["logits"].append(row.copy())
            if self._finished(st, nxt):
                self._active.pop(slot)
                self._complete(slot, st)

    def _speculative_step(self):
        """One speculative round: the draft proposes ``k-1`` tokens
        (sequential single-token steps on the SMALL model), the target
        rules on all of them in ONE ``spec_k``-token verify dispatch,
        and the host accepts the longest matching prefix plus the
        target's own next token (correction or bonus).  Greedy outputs
        are IDENTICAL to the non-speculative path by construction:
        every emitted token is the argmax of a target logits row, and
        verify row ``j`` conditions only on tokens the target already
        ruled valid.  Rollback of rejected draft positions is free —
        they sit past the slot's valid length, stale-masked by
        ``cache_len``, overwritten by the next round's writes (both
        caches)."""
        spec, draft = self.spec, self.draft_spec
        s, k = spec.slots, spec.spec_k
        last = np.zeros((s,), "int64")
        base = np.zeros((s,), "int32")
        for slot, st in self._active.items():
            last[slot] = st["generated"][-1]
            base[slot] = st["pos"]
        toks = np.zeros((s, k), "int64")
        toks[:, 0] = last
        cur = last.copy()
        traced = any(st["req"].trace is not None
                     for st in self._active.values())
        t0 = tracing.now_us() if traced else 0.0
        with RecordEvent("serving/speculative_step",
                         args={"active": len(self._active), "k": k}):
            for j in range(k - 1):
                wp = base + j
                dfeed = {"tok": cur.reshape(s, 1, 1),
                         "pos": wp.astype("int64").reshape(s, 1, 1),
                         "wpos": wp.astype("int32"),
                         "cache_len": (wp + 1).astype("int32")}
                (dl,) = self._exe_decode.run(
                    draft.decode_program, feed=dfeed,
                    fetch_list=[draft.decode_logits], scope=self._scope)
                cur = np.asarray(dl)[:, 0].argmax(-1).astype("int64")
                toks[:, j + 1] = cur
            pos = base[:, None].astype("int64") + np.arange(k, dtype="int64")
            vfeed = {"tok": toks.reshape(s, k, 1),
                     "pos": pos.reshape(s, k, 1),
                     "wpos": base.astype("int32"),
                     "cache_len": (base + k).astype("int32")}
            if self.paged:
                vfeed["page_table"] = self._table
            (vl,) = self._exe_decode.run(
                spec.verify_program, feed=vfeed,
                fetch_list=[spec.verify_logits], scope=self._scope)
        vl = np.asarray(vl)                       # [s, k, V]
        greedy = vl.argmax(-1)                    # [s, k]
        self._ticks += 1
        dur = (tracing.now_us() - t0) if traced else 0.0
        n_active = len(self._active)
        self.metrics.note_decode_step(len(self._active),
                                      self._sched.occupancy())
        for slot in list(self._active):
            st = self._active[slot]
            if not np.isfinite(vl[slot]).all():
                self._active.pop(slot)
                self._quarantine(st["req"],
                                 reason="non-finite verify logits")
                continue
            accepted = 0
            while accepted < k - 1 and \
                    int(toks[slot, accepted + 1]) == \
                    int(greedy[slot, accepted]):
                accepted += 1
            self.metrics.note_speculation(accepted, k - 1)
            if st["req"].trace is not None:
                st["req"].trace.note_decode(t0, dur, slot, self._ticks,
                                            n_active,
                                            spec_accepted=accepted,
                                            spec_proposed=k - 1)
            emitted = [int(toks[slot, j + 1]) for j in range(accepted)]
            emitted.append(int(greedy[slot, accepted]))
            for j, t in enumerate(emitted):
                st["generated"].append(t)
                st["pos"] += 1
                if self.record_logits:
                    st["logits"].append(vl[slot, j].copy())
                if self._finished(st, t):
                    self._active.pop(slot)
                    self._complete(slot, st)
                    break

    def _finished(self, st, last_tok):
        return (len(st["generated"]) >= st["max_new"]
                or (self.eos_id is not None and last_tok == self.eos_id))

    def _complete(self, slot, st):
        req = st["req"]
        self._free_pages(slot)
        result = {"tokens": list(st["generated"]),
                  "prompt_len": len(req.payload["prompt"])}
        if self.record_logits:
            result["logits"] = st["logits"]
        if not self._sched.complete(req, result):
            return      # cancelled by close() while its batch ran
        extra = {"generated": len(st["generated"])}
        if self.paged or self.draft_spec is not None:
            extra.update(self.metrics.paged_snapshot())
        self.metrics.note_complete(req, extra=extra)
        self.metrics._count("generated_tokens", "generated_tokens_total",
                            len(st["generated"]))

    def _quarantine(self, req, reason):
        self._free_pages(req.slot)
        self.metrics.quarantine(
            req, feed={"prompt": np.asarray(req.payload["prompt"])},
            reason=reason)
        err = PoisonedRequestError(
            "request %s: %s (quarantined)" % (req.id, reason))
        self._sched.fail(req, err, status="quarantined")
        self.metrics.note_failure(req, err, status="quarantined")

    def close(self):
        super().close()
        # in-flight generations were failed by the scheduler's close;
        # their pages go with them
        if self._alloc is not None:
            for slot in list(self._alloc._slot_pages):
                self._free_pages(slot)
        self._active.clear()
