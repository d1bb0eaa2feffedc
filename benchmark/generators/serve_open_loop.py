"""Traffic generator ``serve_open_loop``: independent users sending to one
``GenerationEngine`` on a schedule, whether or not earlier requests have
finished.

Parameters (the mix's data file): ``rate_per_s`` (fixed; found once by a
sweep, never searched here), exponential gaps and log-normal ``prompt`` and
``output`` lengths (tokens of the user's part; ``output`` is the request's
``max_new_tokens``, and with no end-of-sequence id that many are served),
``shared_prefix_share`` of the requests start with one shared system prompt
of ``shared_prefix_tokens`` (whole pages) put before the user's part, the
engine's ``slots`` and page-aligned prefill ``buckets``, ``fill_seconds`` of
the same arrival process before the window so that the window starts on a
full engine, and ``drain_seconds`` allowed after it.

The schedule — which request is due when, how long its prompt and its
answer are, whether it starts with the shared prefix — is the distributions'
quantiles put in an order drawn from the mix's ``schedule_seed``: it is part
of the mix, the same in every run, and the window always has ``round(rate x
seconds)`` requests due.  In this engine the order IS the work (one long
prompt stalls every request behind it: six seeds that only reordered the
same multiset spread the median latency by 21%, my chip run, PR 23), so
``--seed`` changes what cannot change the work: the weights, the prompts'
token ids and the shared prefix.
Requests are timed from when they were DUE; how late the generator sent
them is reported.  ``--trace 1`` profiles ``profile_seconds`` inside the
window and switches the program's request tracing on for that stretch.
"""

import gc
import math
import statistics
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness, stats, weights
from benchmark.trace import reduce as trace_reduce

_NORMAL = statistics.NormalDist()


def quantile_lengths(dist, n):
    """``n`` lengths at the distribution's evenly spaced quantiles."""
    if dist["dist"] != "lognormal":
        raise ValueError("unknown length distribution %r" % dist["dist"])
    mu = math.log(dist["median"])
    out = []
    for i in range(n):
        x = math.exp(mu + dist["sigma"] * _NORMAL.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["lo"]), dist["hi"])))
    return out


def quantile_gaps(n, span_s):
    """``n`` exponential inter-arrival gaps at evenly spaced quantiles,
    scaled so that all ``n`` arrivals fall inside ``span_s`` seconds."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s * n / (n + 1.0) / sum(gaps)
    return [g * scale for g in gaps]


def make_requests(traffic, vocab, seed, n, span_s, start_s, rng=None,
                  order=None):
    """``n`` requests due inside [start_s, start_s + span_s): dicts with
    ``due`` (seconds from the generator's start), ``prompt`` (token ids),
    ``max_new``.  ``order`` draws the schedule (the mix's), ``rng`` the
    token ids (the seed's); the same for the same seed."""
    rng = rng or np.random.default_rng(seed)
    order = order or np.random.default_rng(traffic.get("schedule_seed", 0))
    if n == 0:
        return []
    user = order.permutation(quantile_lengths(traffic["prompt"], n))
    outs = order.permutation(quantile_lengths(traffic["output"], n))
    gaps = order.permutation(quantile_gaps(n, span_s))
    n_shared = int(round(traffic["shared_prefix_share"] * n))
    shared = order.permutation(np.arange(n) < n_shared)
    prefix = np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, 0x5A]).integers(
            2, vocab, traffic["shared_prefix_tokens"]).tolist()
    due = start_s + np.cumsum(gaps)
    reqs = []
    for i in range(n):
        body = rng.integers(2, vocab, int(user[i])).tolist()
        prompt = (prefix + body) if shared[i] else body
        prompt = prompt[:traffic["prompt"]["hi"]]
        reqs.append({"due": float(due[i]), "prompt": prompt,
                     "max_new": int(outs[i]), "shared": bool(shared[i])})
    return reqs


class Collector(threading.Thread):
    """Stamps each request's completion on the benchmark's clock by
    polling its future (the engine exposes no completion callback)."""

    def __init__(self, clock, period_s=0.002):
        super().__init__(name="bm-collector", daemon=True)
        self.clock, self.period_s = clock, period_s
        self._lock = threading.Lock()
        self._open, self.done_at = {}, {}
        self._halt = threading.Event()

    def watch(self, index, future):
        with self._lock:
            self._open[index] = future

    def outstanding(self):
        with self._lock:
            return len(self._open)

    def run(self):
        while not self._halt.is_set():
            with self._lock:
                items = list(self._open.items())
            now = self.clock()
            for index, fut in items:
                if fut.done():
                    self.done_at[index] = now
                    with self._lock:
                        del self._open[index]
            time.sleep(self.period_s)

    def stop(self):
        self._halt.set()
        self.join(10)


def served_logit_gap(ref, cfg, w, prompt, tokens, mm=None):
    """Over one finished request: the widest gap by which a served token's
    logit lies below the reference's best, the reference run once over the
    prompt with its served tokens.  With ``mm`` (the control) it reads
    instead the gap of the token the lower precision puts first."""
    seq = list(prompt) + list(tokens)
    n, pad = len(seq), -(-len(seq) // 128) * 128
    pad = min(pad, cfg["max_len"])
    ids = jnp.asarray([seq + [0] * (pad - n)], jnp.int32)
    lens = jnp.asarray([n], jnp.int32)
    rows = slice(len(prompt) - 1, n - 1)
    logits = _declm(ref, cfg, None)(w, ids, lens)[0, rows]
    if mm is None:
        chosen = jnp.asarray(tokens, jnp.int32)
    else:
        chosen = jnp.argmax(_declm(ref, cfg, mm)(w, ids, lens)[0, rows], -1)
    picked = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
    return float(jnp.max(jnp.max(logits, -1) - picked)), len(tokens)


_JITS = {}


def _declm(ref, cfg, mm):
    key = (id(ref), mm, tuple(sorted((k, v) for k, v in cfg.items()
                                     if isinstance(v, (int, float, str)))))
    if key not in _JITS:
        m = mm or ref.f32_matmul
        _JITS[key] = jax.jit(
            lambda w, ids, lens: ref.declm_logits(w, ids, lens, cfg, m))
    return _JITS[key]


def pick_sample(finished, k, seed):
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(len(r["prompt"])
                                             + len(r["tokens"])))
    rest = order[1:]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xC4])
    take = rng.permutation(len(rest))[:max(0, k - 1)]
    return [order[0]] + [rest[i] for i in take]


def build(ctx, seed):
    """(engine, reference module, weight spec) with the seed's weights."""
    cfg, traffic = ctx.cfg, ctx.traffic
    ref = harness.load_reference(cfg["reference"], ctx.root)
    model_mod = harness.load_module("models", cfg["builder"], ctx.root)
    spec = ref.declm_param_spec(cfg)
    engine, _ = model_mod.build_engine(
        cfg, traffic, ctx.devices[0], weights.make_weights(spec, seed))
    # warm exactly the cell's shapes: one prefill per bucket and the
    # decode step
    warm_rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0x77])
    for b in traffic["buckets"]:
        engine.submit(warm_rng.integers(2, cfg["vocab_size"], b).tolist(),
                      max_new_tokens=2).result(600)
    return engine, ref, spec


def schedule(ctx, seed, rate, fill, seconds):
    """The fill's requests then the window's, and how many of each."""
    traffic, vocab = ctx.traffic, ctx.cfg["vocab_size"]
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(traffic.get("schedule_seed", 0))
    n_fill = int(round(rate * fill))
    n_win = max(1, int(round(rate * seconds)))
    reqs = (make_requests(traffic, vocab, seed, n_fill, fill, 0.0, rng, order)
            + make_requests(traffic, vocab, seed, n_win, seconds, fill, rng,
                            order))
    return reqs, n_fill, n_win


def drive(ctx, engine, reqs, n_fill, n_win, fill, seconds, trace=False):
    """Send ``reqs`` on their schedule, wait for the window's to finish
    (at most ``drain_seconds``), and return what happened on the
    benchmark's clock."""
    traffic = ctx.traffic
    clock = time.perf_counter
    collector = Collector(clock)
    collector.start()
    sent, futures, tracer = {}, {}, None
    before = engine.metrics.paged_snapshot()
    with harness.count_compiles() as cc:
        g0 = clock()
        t0 = g0 + fill
        if trace:
            tracer = _Tracer(ctx, t0 + min(2.0, seconds / 4),
                             traffic["profile_seconds"], clock)
            tracer.start()
        open_at_start = None
        for i, r in enumerate(reqs):
            wait = g0 + r["due"] - clock()
            if wait > 0:
                time.sleep(wait)
            if i == n_fill:
                open_at_start = collector.outstanding()
            futures[i] = engine.submit(r["prompt"],
                                       max_new_tokens=r["max_new"])
            sent[i] = clock() - g0
            collector.watch(i, futures[i])
        while clock() < t0 + seconds:
            time.sleep(0.005)
        open_at_close = collector.outstanding()
        deadline = t0 + seconds + traffic["drain_seconds"]
        while collector.outstanding() and clock() < deadline:
            time.sleep(0.005)
        if tracer is not None:
            tracer.join(120)
    open_at_end = collector.outstanding()
    collector.stop()
    end = clock()
    after = engine.metrics.paged_snapshot()
    done_at = collector.done_at
    finished, failed, done, tokens_in_window = [], 0, [], 0
    for i, r in enumerate(reqs):
        ok, res = False, None
        if i in done_at:
            try:
                res = futures[i].result(0)
                ok = len(res["tokens"]) == r["max_new"] \
                    and res["prompt_len"] == len(r["prompt"])
            except Exception as e:  # noqa: BLE001 - a failed request counts
                ctx.log("request %d failed: %r" % (i, e))
        t_done = done_at.get(i) if ok else None
        if ok and t0 <= t_done < t0 + seconds:
            tokens_in_window += r["max_new"]
        if i >= n_fill:
            done.append(t_done)
            if ok:
                finished.append({"prompt": r["prompt"],
                                 "tokens": res["tokens"]})
            else:
                failed += 1
    lat = stats.latencies_from_due(
        [g0 + r["due"] for r in reqs[n_fill:]], done, missing_at=end)
    in_window = sum(1 for i in range(n_fill, len(reqs)) if i in done_at
                    and done_at[i] < t0 + seconds)
    return {"t0": t0, "lat": lat, "failed": failed, "finished": finished,
            "tokens_in_window": tokens_in_window,
            "late": [sent[i] - reqs[i]["due"]
                     for i in range(n_fill, len(reqs))],
            "compiles": harness.n_compiles(cc()),
            "hits": after["prefix_hits"] - before["prefix_hits"],
            "misses": after["prefix_misses"] - before["prefix_misses"],
            "done_in_window_share": in_window / float(n_win),
            "open_at_end": open_at_end, "open_at_start": open_at_start,
            "open_at_close": open_at_close, "tracer": tracer}


def sample_gap(ctx, ref, spec, seed, finished, mm=None):
    """(widest served-token gap, tokens compared, requests compared, the
    longest's length) over the seeded sample of finished requests."""
    w = weights.make_weights(spec, seed)
    sample = pick_sample(finished, ctx.traffic["check_requests"], seed)
    worst, n_tok = 0.0, 0
    for r in sample:
        gap, n = served_logit_gap(ref, ctx.cfg, w, r["prompt"], r["tokens"],
                                  mm)
        worst, n_tok = max(worst, gap), n_tok + n
    longest = (len(sample[0]["prompt"]) + len(sample[0]["tokens"])
               if sample else 0)
    return (worst if sample else math.inf), n_tok, len(sample), longest


def run(ctx):
    cfg, traffic = ctx.cfg, ctx.traffic
    checks = harness.Checks(ctx.log)
    rate = traffic["rate_per_s"]
    if not rate:
        raise harness.Refused("the mix states no rate_per_s")
    seconds = traffic["check_seconds"] if ctx.check else ctx.seconds
    fill = traffic["fill_seconds"]
    reqs, n_fill, n_win = schedule(ctx, ctx.seed, rate, fill, seconds)
    engine, ref, spec = build(ctx, ctx.seed)
    try:
        got = drive(ctx, engine, reqs, n_fill, n_win, fill, seconds,
                    trace=ctx.trace)
        leaks = None
        try:
            if not got["open_at_end"]:
                leaks = len(engine._alloc.check_leaks())
        except AttributeError:
            pass
        peak = harness.memory_peak_bytes(ctx.devices[:1])
    finally:
        engine.close()
    del engine
    gc.collect()
    checks.add("requests_failed", float(got["failed"]), 0.0,
               note="%d due in the window" % n_win)
    checks.add("compiles_in_window", float(got["compiles"]), 0.0)
    if leaks is not None:
        checks.add("page_leaks", float(leaks), 0.0)

    # -- the plain reference, after the engine's state is freed ---------------
    t_ref = time.perf_counter()
    gap, n_tok, n_req, longest = sample_gap(ctx, ref, spec, ctx.seed,
                                            got["finished"])
    checks.add("served_logit_gap", gap, cfg["limits"]["served_logit_gap"],
               note="%d served tokens of %d requests, longest %d tokens"
               % (n_tok, n_req, longest))
    ctx.log("plain reference over the sample took %.2f s, after the window"
            % (time.perf_counter() - t_ref))

    tracer, lat, late = got["tracer"], got["lat"], got["late"]
    facts = {"kind": "serve", "compiles_in_window": got["compiles"],
             "generator_late_s": late, "prefix_hits": got["hits"],
             "prefix_misses": got["misses"], "memory_peak_bytes": peak,
             "slots": traffic["slots"],
             "spans": tracer.spans if tracer else None,
             "trace": tracer.summary if tracer else None}
    out = {"correct": checks.ok(), "attempted": n_win,
           "failed": got["failed"], "window_start": got["t0"],
           "reference_s": 0.0, "memory_peak_bytes": peak, "facts": facts,
           "end_to_end": {}}
    if ctx.check:
        return out
    p50, p95 = stats.percentile(lat, 50), stats.percentile(lat, 95)
    if not ctx.trace:
        ctx.log("serve: %d requests due in %.1f s at %.3f/s, %d failed, "
                "%.3f of them done inside the window; latency from due p50 "
                "%.1f ms p95 %.1f ms (%d samples, %d beyond the 95th); %d "
                "output tokens completed in the window; generator late p95 "
                "%.2f ms; prefix pages hit %d of %d"
                % (n_win, seconds, rate, got["failed"],
                   got["done_in_window_share"], p50 * 1e3, p95 * 1e3,
                   len(lat), stats.samples_beyond(len(lat), 95),
                   got["tokens_in_window"],
                   stats.percentile(late, 95) * 1e3, got["hits"],
                   got["hits"] + got["misses"]))
    out["end_to_end"] = {
        "request_latency_p50_ms": p50 * 1e3,
        "request_latency_p95_ms": p95 * 1e3,
        "serve_tokens_per_s": got["tokens_in_window"] / seconds}
    return out


def sweep(ctx, rates, seconds):
    """Find the knee once: one engine, each rate in turn for ``seconds``
    (after the mix's fill), the queue drained between rates.  Prints one
    row per rate.  The knee is the highest rate the engine sustains: no
    more requests open when the window closes than when it opened (within
    Little's-law noise), and the second half's median latency no worse
    than the first half's."""
    engine, _, _ = build(ctx, ctx.seed)
    rows = []
    try:
        ctx.log("peak bytes after warm-up %d"
                % harness.memory_peak_bytes(ctx.devices[:1]))
        for rate in rates:
            fill = ctx.traffic["fill_seconds"]
            reqs, n_fill, n_win = schedule(ctx, ctx.seed, rate, fill, seconds)
            got = drive(ctx, engine, reqs, n_fill, n_win, fill, seconds)
            half = len(got["lat"]) // 2
            row = {"rate": rate, "due": n_win,
                   "open_at_start": got["open_at_start"],
                   "open_at_close": got["open_at_close"],
                   "p50_first_half_ms": stats.percentile(
                       got["lat"][:half], 50) * 1e3,
                   "p50_second_half_ms": stats.percentile(
                       got["lat"][half:], 50) * 1e3,
                   "done_in_window_share": got["done_in_window_share"],
                   "failed": got["failed"],
                   "p50_ms": stats.percentile(got["lat"], 50) * 1e3,
                   "p95_ms": stats.percentile(got["lat"], 95) * 1e3,
                   "tokens_per_s": got["tokens_in_window"] / seconds,
                   "late_p95_ms": stats.percentile(got["late"], 95) * 1e3}
            if ctx.check:       # a CPU run yields counts, never a time
                row = {k: row[k] for k in ("rate", "due", "failed",
                                           "open_at_start", "open_at_close",
                                           "done_in_window_share")}
            rows.append(row)
            ctx.log("sweep %s" % row)
    finally:
        engine.close()
    return rows


def readings(ctx, seeds, seconds, kinds):
    """For setting the limit: per seed, a short window at the cell's own
    load, then the sound reading (the served tokens' widest gap) and, for
    each precision in ``kinds``, the control's (the gap of the token the
    lower precision puts first, at the same prompts and tokens)."""
    rows = []
    for seed in seeds:
        rate, fill = ctx.traffic["rate_per_s"], ctx.traffic["fill_seconds"]
        reqs, n_fill, n_win = schedule(ctx, seed, rate, fill, seconds)
        engine, ref, spec = build(ctx, seed)
        try:
            got = drive(ctx, engine, reqs, n_fill, n_win, fill, seconds)
        finally:
            engine.close()
        del engine
        gc.collect()
        sound = sample_gap(ctx, ref, spec, seed, got["finished"])
        row = {"seed": seed, "sound_gap": sound[0], "tokens": sound[1],
               "requests": sound[2], "longest": sound[3],
               "failed": got["failed"]}
        for kind in kinds:
            row["control_gap_" + kind] = sample_gap(
                ctx, ref, spec, seed, got["finished"],
                ref.lowp_matmul(kind))[0]
        rows.append(row)
        ctx.log("readings %s" % row)
    return rows


class _Tracer(threading.Thread):
    """Profiles ``length_s`` seconds of the window from a thread of its
    own (stopping a trace takes seconds; the generator must not wait),
    with the program's request tracing on for the same stretch."""

    def __init__(self, ctx, start_at, length_s, clock):
        super().__init__(name="bm-tracer", daemon=True)
        self.ctx, self.start_at, self.length_s = ctx, start_at, length_s
        self.clock, self.spans, self.summary = clock, None, None

    def run(self):
        from paddle_tpu.monitor import tracing

        time.sleep(max(0.0, self.start_at - self.clock()))
        tdir = harness.trace_dir(self.ctx)
        tracing.reset()
        tracing.enable()
        options = jax.profiler.ProfileOptions()
        # the Python tracer stalls a host-bound engine for tens of seconds
        # when the trace is written; the runtime's own host events stay
        options.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=options)
        time.sleep(self.length_s)
        jax.profiler.stop_trace()
        tracing.disable()
        self.spans = tracing.spans()
        tracing.reset()
        try:
            self.summary = trace_reduce.summarize(
                trace_reduce.load(trace_reduce.find_xplane(tdir)), 1)
        except (ValueError, FileNotFoundError) as e:
            self.ctx.log("trace reduction found nothing: %s" % e)
