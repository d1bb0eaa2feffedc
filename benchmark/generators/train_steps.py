"""Traffic generator ``train_steps``: seeded padded NMT batches through one
compiled training step, a fresh host batch every step.

Parameters (the mix's data file): ``rows`` x ``seq`` padded positions a
side, real lengths spread evenly over ``min_len``..``seq`` (every batch
holds the SAME multiset of lengths in a seeded order, so each step and each
seed does the same work), ``pool`` distinct batches made in set-up and
cycled, the loss fetched every ``fetch_every`` steps and at the window's
end, ``mesh`` for a data-parallel cell, ``profile_steps`` traced steps in a
``--trace 1`` run.
"""

import gc
import math
import statistics
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.trace import reduce as trace_reduce


def make_batches(traffic, vocab, seed):
    """``pool`` batches {src, tgt, lbl, src_len, tgt_len} as numpy arrays;
    the same for the same seed."""
    rng = np.random.default_rng(seed)
    rows, seq, lo = traffic["rows"], traffic["seq"], traffic["min_len"]
    lens = (lo + (np.arange(rows) * (seq - lo + 1)) // rows).astype("int32")
    out = []
    for _ in range(traffic["pool"]):
        tgt = rng.integers(2, vocab, (rows, seq), dtype=np.int64)
        out.append({
            "src": rng.integers(2, vocab, (rows, seq), dtype=np.int64),
            "tgt": tgt, "lbl": np.roll(tgt, -1, axis=1),
            "src_len": rng.permutation(lens),
            "tgt_len": rng.permutation(lens)})
    return out


def real_tokens(batch):
    return int(batch["src_len"].sum() + batch["tgt_len"].sum())


def leaf_norms(tree):
    return {k: float(v) for k, v in jax.device_get(
        _norms(tree)).items()}


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def leaf_gaps(got, want):
    """Per leaf: the gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of
    that leaf or of the median leaf, whichever is larger — some gradients
    are all but zero."""
    med = statistics.median(want.values())
    return {leaf: abs(got[leaf] - ref) / max(ref, med)
            for leaf, ref in want.items()}


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))


def worst_leaf_gap(got, want):
    """(the worst leaf's gap, that leaf)."""
    gaps = leaf_gaps(got, want)
    for leaf, gap in gaps.items():
        if not math.isfinite(gap):
            return math.inf, leaf
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def rms_leaf_gap(got, want):
    """Root mean square of the leaves' gaps: steady from seed to seed
    where the worst leaf, a widest gap, swings by its nature."""
    gaps = list(leaf_gaps(got, want).values())
    return math.sqrt(sum(g * g for g in gaps) / len(gaps))


def reference_readings(ref, cfg, batches, w, mm, steps):
    """What the plain reference gives over the first ``steps`` steps:
    each loss, per-leaf norm of the first gradient, per-leaf norm of the
    parameters' change after the last step."""
    p, state, losses, grad_norms = w, ref.adam_init(w), [], None
    for k in range(steps):
        b = {n: jnp.asarray(v, jnp.int32) for n, v in batches[k].items()}
        loss, grad = ref.encdec_loss_and_grad(
            p, b, cfg, cfg["reference_block_rows"], mm)
        losses.append(float(loss))
        if k == 0:
            grad_norms = leaf_norms(grad)
            first_grad = jax.device_get(grad)     # waits on the host
        p, state = ref.adam_step(p, grad, state, cfg)
        del grad
    update_norms = leaf_norms({n: p[n] - w[n] for n in w})
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, "first_grad": first_grad}


def program_readings(model, feeds, w0, beta1, steps, want_grad):
    """The same readings from the program, through the window's own call:
    the first gradient is worked out from Adam's first moment after one
    step (m1 = (1 - beta1) g); ``grad_errors`` are the norms of its
    difference from the reference's first gradient, leaf by leaf."""
    losses, grad_norms, grad_errors = [], None, None
    for k in range(steps):
        losses.append(float(np.asarray(model.step(feeds[k])).ravel()[0]))
        if k == 0:
            m1 = {n: jnp.asarray(v)
                  for n, v in model.state("_moment1_0").items()}
            grad_norms = {n: v / (1.0 - beta1)
                          for n, v in leaf_norms(m1).items()}
            grad_errors = grad_error_norms(m1, 1.0 - beta1, want_grad)
            del m1
    now = {n: jnp.asarray(v) for n, v in model.state().items()}
    # leaf by leaf: one leaf's copy on the chip at a time
    update_norms = {n: _diff_norm(now[n], jax.device_put(
        w0[n], now[n].sharding)) for n in w0}
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_errors": grad_errors,
            "update_norms": {n: float(v) for n, v in
                             jax.device_get(update_norms).items()}}


def grad_error_norms(got, scale, want):
    """{leaf: || got / scale - want ||}, one leaf of ``want`` (host
    arrays) on the chip at a time."""
    out = {n: _diff_norm(got[n] / scale,
                         jax.device_put(want[n], got[n].sharding))
           for n in want}
    return {n: float(v) for n, v in jax.device_get(out).items()}


def rel_error_rms(errors, want_norms):
    """Root mean square over the leaves of the norm of the difference
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = statistics.median(want_norms.values())
    rel = [errors[n] / max(ref, med) for n, ref in want_norms.items()]
    return math.sqrt(sum(r * r for r in rel) / len(rel))


def compare(prog, want, limits, checks):
    checks.add("grad_rel_error_rms",
               rel_error_rms(prog["grad_errors"], want["grad_norms"]),
               limits["grad_rel_error_rms"])
    for k, (a, b) in enumerate(zip(prog["losses"], want["losses"])):
        checks.add("loss_rel_gap.step%d" % (k + 1), abs(a - b) / abs(b),
                   limits["loss_rel_gap"],
                   note="program %.6f reference %.6f" % (a, b))
    for what, key in (("grad_norm_gap", "grad_norms"),
                      ("update_norm_gap", "update_norms")):
        checks.add(what + "_rms", rms_leaf_gap(prog[key], want[key]),
                   limits[what + "_rms"])
        gap, leaf = worst_leaf_gap(prog[key], want[key])
        checks.add(what, gap, limits[what], note="worst leaf %s" % leaf)


def run(ctx):
    cfg, traffic = dict(ctx.cfg), ctx.traffic
    cfg["max_len"] = traffic["seq"]
    ref = harness.load_reference(cfg["reference"], ctx.root)
    flops = harness.load_module("flops", cfg["flops"], ctx.root)
    model_mod = harness.load_module("models", cfg["builder"], ctx.root)
    chips = ctx.chips
    devices = ctx.devices[:chips]
    checks = harness.Checks(ctx.log)
    ref_steps = 3

    batches = make_batches(traffic, cfg["vocab_size"], ctx.seed)
    tokens_per_step = real_tokens(batches[0])
    spec = ref.encdec_param_spec(cfg)

    # -- the plain reference first, before the program's state is made ----
    t_ref = time.perf_counter()
    w = weights.make_weights(spec, ctx.seed)
    want = reference_readings(ref, cfg, batches, w, ref.f32_matmul,
                              ref_steps)
    reference_s = time.perf_counter() - t_ref
    ctx.log("plain reference: %d steps in %.2f s, before the program's "
            "state is made; peak bytes so far %d"
            % (ref_steps, reference_s, harness.memory_peak_bytes(devices)))
    # the seeded weights wait on the host while the program runs, so the
    # chip holds the program's state and nothing of the reference's
    w0 = jax.device_get(w)
    gc.collect()

    # -- one object: the compiled step with its state ------------------------
    model = model_mod.build_train(cfg, traffic, devices)
    model.set_weights(w)
    del w
    feeds = [model.make_feed(b) for b in batches]
    prog = program_readings(model, feeds, w0, cfg["adam_beta1"], ref_steps,
                            want.pop("first_grad"))
    del w0
    compare(prog, want, cfg["limits"], checks)
    pad = flops.padded_flops(cfg, traffic["rows"], traffic["seq"])
    floor_s, bound = flops.step_floor_seconds(
        cfg, batches[0]["src_len"], batches[0]["tgt_len"], ctx.peaks, chips
    ) if ctx.peaks else (None, None)

    if ctx.check:
        # no chip: a fixed number of steps, counts only, never a time
        with harness.count_compiles() as cc:
            losses = [float(np.asarray(model.step(
                feeds[(ref_steps + i) % len(feeds)])).ravel()[0])
                for i in range(traffic["check_steps"])]
        checks.add("losses_finite", float(sum(
            not math.isfinite(v) for v in losses)), 0.0)
        checks.add("compiles_in_window", float(harness.n_compiles(cc())), 0.0)
        model.close()
        return {"correct": checks.ok(), "attempted": len(losses),
                "failed": sum(not math.isfinite(v) for v in losses),
                "end_to_end": {}, "facts": {
                    "kind": "train", "compiles_in_window": harness.n_compiles(cc()),
                    "tokens_per_step": tokens_per_step,
                    "padded_flops_per_step": pad}}

    # -- the window -----------------------------------------------------------
    np.asarray(model.step(feeds[ref_steps % len(feeds)]))      # settle
    k, steps, fetched, dispatch = ref_steps + 1, 0, [], []
    trace_at = 10 if ctx.trace else None
    traced_steps, summary = 0, None
    loss = None
    with harness.count_compiles() as cc:
        t0 = time.perf_counter()
        while True:
            ts = time.perf_counter()
            if ts - t0 >= ctx.seconds:
                break
            if steps == trace_at:
                np.asarray(loss)
                tdir = harness.trace_dir(ctx)
                jax.profiler.start_trace(tdir)
                for _ in range(traffic["profile_steps"]):
                    with jax.profiler.TraceAnnotation("bm/train_step"):
                        loss = model.step(feeds[k % len(feeds)])
                    k += 1
                    steps += 1
                    traced_steps += 1
                with jax.profiler.TraceAnnotation("bm/fetch_loss"):
                    fetched.append(float(np.asarray(loss).ravel()[0]))
                jax.profiler.stop_trace()
                summary = trace_reduce.summarize(
                    trace_reduce.load(trace_reduce.find_xplane(tdir)), chips)
                continue
            loss = model.step(feeds[k % len(feeds)])
            dispatch.append(time.perf_counter() - ts)
            k += 1
            steps += 1
            if steps % traffic["fetch_every"] == 0:
                fetched.append(float(np.asarray(loss).ravel()[0]))
        fetched.append(float(np.asarray(loss).ravel()[0]))
        t1 = time.perf_counter()
    window_s = t1 - t0
    compiles = harness.n_compiles(cc())
    bad = sum(not math.isfinite(v) for v in fetched)
    checks.add("losses_not_finite", float(bad), 0.0,
               note="%d losses fetched, last %.4f" % (len(fetched),
                                                      fetched[-1]))
    checks.add("compiles_in_window", float(compiles), 0.0)
    rate = steps * tokens_per_step / window_s
    step_s = window_s / steps
    if not ctx.trace:
        ctx.log("train: %d steps in %.3f s, %.5f s/step, %d real tokens "
                "a step; model-FLOP/s utilisation %.4f on the padded "
                "positions (%.3e FLOP a step), %.4f on the real tokens; "
                "step floor %.5f s (%s-bound)"
                % (steps, window_s, step_s, tokens_per_step,
                   pad / step_s / (chips * ctx.peaks["bf16_flops"]), pad,
                   floor_s / step_s, floor_s, bound))
    peak = harness.memory_peak_bytes(devices)
    model.close()
    return {
        "correct": checks.ok(), "attempted": steps, "failed": bad,
        "window_start": t0, "reference_s": reference_s,
        "end_to_end": {"train_tokens_per_s": rate},
        "memory_peak_bytes": peak,
        "facts": {
            "kind": "train", "dispatch_s": dispatch,
            "compiles_in_window": compiles, "trace": summary,
            "traced_steps": traced_steps, "step_floor_s": floor_s,
            "step_bound": bound, "memory_peak_bytes": peak,
            "chips": chips},
    }


def readings(ctx, seeds, seconds, kinds):
    """For setting the limits: per seed, in one process and with no
    window, the program's three readings against the plain reference's,
    and the control's (the reference with its products in ``kinds[0]``)
    against the same.  ``seconds`` is unused: these readings need no
    window."""
    cfg, traffic = dict(ctx.cfg), ctx.traffic
    cfg["max_len"] = traffic["seq"]
    ref = harness.load_reference(cfg["reference"], ctx.root)
    model = harness.load_module("models", cfg["builder"],
                                ctx.root).build_train(
        cfg, traffic, ctx.devices[:ctx.chips])
    spec, rows = ref.encdec_param_spec(cfg), []

    def gaps(got, want):
        return {"grad_rel_error_rms": rel_error_rms(got["grad_errors"],
                                                    want["grad_norms"]),
                "loss_rel_gap": max(abs(a - b) / abs(b) for a, b in zip(
                    got["losses"], want["losses"])),
                "grad_norm_gap": worst_leaf_gap(got["grad_norms"],
                                                want["grad_norms"])[0],
                "grad_norm_gap_rms": rms_leaf_gap(got["grad_norms"],
                                                  want["grad_norms"]),
                "update_norm_gap": worst_leaf_gap(got["update_norms"],
                                                  want["update_norms"])[0],
                "update_norm_gap_rms": rms_leaf_gap(got["update_norms"],
                                                    want["update_norms"])}
    for seed in seeds:
        batches = make_batches(dict(traffic, pool=3), cfg["vocab_size"], seed)
        w0 = weights.make_weights(spec, seed)
        want = reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
        ctl = reference_readings(ref, cfg, batches, w0,
                                 ref.lowp_matmul(kinds[0]), 3)
        ctl["grad_errors"] = grad_error_norms(
            {n: jnp.asarray(v) for n, v in ctl.pop("first_grad").items()},
            1.0, want["first_grad"])
        model.reset()
        model.set_weights({n: jnp.copy(v) for n, v in w0.items()})
        w0 = jax.device_get(w0)
        feeds = [model.make_feed(b) for b in batches]
        prog = program_readings(model, feeds, w0, cfg["adam_beta1"], 3,
                                want["first_grad"])
        row = {"seed": seed, "sound": gaps(prog, want),
               "control": gaps(ctl, want)}
        rows.append(row)
        ctx.log("readings %s" % row)
    model.close()
    return rows
