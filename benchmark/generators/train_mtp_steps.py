"""Traffic generator ``train_mtp_steps``: seeded whole-document batches
through one compiled training step of a decoder-only language model with a
multi-token-prediction module, a fresh host batch every step.

Parameters (the mix's data file) as ``train_lm_steps``': ``rows`` documents
of ``seq`` tokens a step — each drawn ``seq + 2`` ids long, so that the
next token (``lbl``) and the next but one (``lbl2``, the module's label)
exist at every position: no padding, no packing, no wrapped label — ids
uniform over the configuration's ``vocab_size`` (the held slice of the
vocabulary) from ``--seed``; ``pool`` distinct batches made in set-up and
cycled; the loss and the step's counters fetched every ``fetch_every`` steps
— each fetch point's arrays read to the host one fetch point later — and
at the window's end; ``profile_steps`` traced steps in a ``--trace 1`` run.
``train_tokens_per_s`` counts ``rows * seq`` a step: the module's positions
are the same tokens.  Every seed: the same shapes, other ids.

What ``correct`` compares (``train_steps``' six numbers, over BOTH losses,
and two of this kind's own): the three total losses ``L_main + w L_mtp``
and the three ``L_mtp``, the first gradient leaf by leaf (norm of the
difference, gap of norms), the update after three steps;
``routed_pairs_gap`` — the token-expert pairs the program's routers sent to
the held experts in each check step against the plain reference's count,
which a bf16 residual stream moves by the pairs at rank 8 / 9 — and
``dropped_token_pairs`` — pairs routed to a held expert that the grouped
products did not compute, which a dropless layer keeps at 0.
"""

import gc
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.generators.train_lm_steps import dropped_pairs, seeded_weights
from benchmark.generators.train_steps import (
    _diff_norm, compare, grad_error_norms, leaf_norms, rel_error_rms,
    rms_leaf_gap, worst_leaf_gap)
from benchmark.trace import reduce as trace_reduce

STATS = ("pairs_routed", "pairs_computed", "max_expert_tokens", "mtp_loss")


def make_batches(traffic, vocab, seed):
    """``pool`` batches {tok, lbl, lbl2} [rows, seq] as numpy arrays, cut
    from documents two ids longer; the same for the same seed."""
    rng = np.random.default_rng(seed)
    seq, out = traffic["seq"], []
    for _ in range(traffic["pool"]):
        doc = rng.integers(0, vocab, (traffic["rows"], seq + 2),
                           dtype=np.int64)
        out.append({"tok": doc[:, :seq], "lbl": doc[:, 1:seq + 1],
                    "lbl2": doc[:, 2:]})
    return out


def reference_readings(ref, cfg, batches, w0, mm, steps):
    """What the plain reference gives over the first ``steps`` steps from
    the host weights ``w0``: each total loss and each ``L_mtp``, the pairs
    routed to the held experts, per-leaf norm of the first gradient,
    per-leaf norm of the parameters' change after the last step.  Between
    steps Adam's moments wait on the host: a float32 step of this size
    leaves the chip no room for them."""
    p = {n: jnp.asarray(v) for n, v in w0.items()}
    state, losses, mtp, pairs, grad_norms = None, [], [], [], None
    for k in range(steps):
        b = {n: jnp.asarray(v, jnp.int32) for n, v in batches[k].items()}
        loss, m, n, grad = ref.loss_and_grad(
            p, b, cfg, cfg["reference_block_rows"], mm)
        losses.append(float(loss))
        mtp.append(float(m))
        pairs.append(float(n))
        if k == 0:
            grad_norms = leaf_norms(grad)
            first_grad = jax.device_get(grad)
        state = ref.adam_init(p) if state is None else {
            "m": jax.device_put(state["m"]), "v": jax.device_put(state["v"]),
            "t": state["t"]}
        p, state = ref.adam_step(p, grad, state, cfg)
        del grad
        if k + 1 < steps:
            state = {"m": jax.device_get(state["m"]),
                     "v": jax.device_get(state["v"]), "t": state["t"]}
    del state
    update_norms = {n: float(_diff_norm(p[n], jnp.asarray(w0[n])))
                    for n in grad_norms}
    return {"losses": losses, "mtp_losses": mtp, "pairs_routed": pairs,
            "grad_norms": grad_norms, "update_norms": update_norms,
            "first_grad": first_grad}


def read_out(out):
    """(loss, {counter: value}) of one step's fetches, on the host."""
    return (float(np.asarray(out[0]).ravel()[0]),
            dict(zip(STATS, np.asarray(out[1]).tolist())))


def program_readings(model, feeds, w0, beta1, steps, want_grad):
    """The same readings from the program, through the window's own call:
    the first gradient is worked out from Adam's first moment after one
    step (m1 = (1 - beta1) g).  ``stats`` holds every check step's
    counters."""
    losses, stats, grad_norms, grad_errors = [], [], None, None
    for k in range(steps):
        loss, st = read_out(model.step(feeds[k]))
        losses.append(loss)
        stats.append(st)
        if k == 0:
            m1 = {n: jnp.asarray(v) for n, v in
                  model.state(want_grad, "_moment1_0").items()}
            grad_norms = {n: v / (1.0 - beta1)
                          for n, v in leaf_norms(m1).items()}
            grad_errors = grad_error_norms(m1, 1.0 - beta1, want_grad)
            del m1
    now = model.state(want_grad)
    update_norms = {n: _diff_norm(now[n], jax.device_put(
        w0[n], now[n].sharding)) for n in want_grad}
    return {"losses": losses, "mtp_losses": [s["mtp_loss"] for s in stats],
            "pairs_routed": [s["pairs_routed"] for s in stats],
            "grad_norms": grad_norms, "grad_errors": grad_errors,
            "stats": stats,
            "update_norms": {n: float(v) for n, v in
                             jax.device_get(update_norms).items()}}


def mtp_loss_gap(got, want):
    return max(abs(a - b) / abs(b) for a, b in zip(got["mtp_losses"],
                                                   want["mtp_losses"]))


def routed_pairs_gap(got, want):
    return max(abs(a - b) / max(b, 1.0) for a, b in zip(
        got["pairs_routed"], want["pairs_routed"]))


def compare_module(prog, want, limits, checks):
    """The module's loss beside the total, and the two counts."""
    checks.add("loss_rel_gap.mtp", mtp_loss_gap(prog, want),
               limits["loss_rel_gap"],
               note="program %s reference %s" % (
                   ["%.6f" % v for v in prog["mtp_losses"]],
                   ["%.6f" % v for v in want["mtp_losses"]]))
    checks.add("routed_pairs_gap", routed_pairs_gap(prog, want),
               limits["routed_pairs_gap"],
               note="program %s reference %s" % (prog["pairs_routed"],
                                                 want["pairs_routed"]))
    if "stats" in prog:
        checks.add("dropped_token_pairs",
                   float(dropped_pairs(prog["stats"])),
                   limits["dropped_token_pairs"],
                   note="computed %s" % [s["pairs_computed"]
                                         for s in prog["stats"]])


def run(ctx):
    cfg, traffic = dict(ctx.cfg), ctx.traffic
    ref = harness.load_reference(cfg["reference"], ctx.root)
    flops = harness.load_module("flops", cfg["flops"], ctx.root)
    model_mod = harness.load_module("models", cfg["builder"], ctx.root)
    devices = ctx.devices[:ctx.chips]
    checks = harness.Checks(ctx.log)
    ref_steps = 3
    rows, seq = traffic["rows"], traffic["seq"]
    batches = make_batches(traffic, cfg["vocab_size"], ctx.seed)
    tokens_per_step = rows * seq

    # -- the plain reference first, before the program's state is made ----
    # (the seeded weights wait on the host: the float32 reference and then
    # the program each get the chip to themselves)
    w0 = seeded_weights(ref.param_spec(cfg), cfg, ctx.seed)
    t_ref = time.perf_counter()
    want = reference_readings(ref, cfg, batches, w0, ref.f32_matmul,
                              ref_steps)
    reference_s = time.perf_counter() - t_ref
    ctx.log("plain reference: %d steps in %.2f s, before the program's "
            "state is made; peak bytes so far %d"
            % (ref_steps, reference_s, harness.memory_peak_bytes(devices)))
    gc.collect()

    # -- one object: the compiled step with its state ------------------------
    model = model_mod.build_train(cfg, traffic, devices)
    model.set_weights(w0)
    feeds = [model.make_feed(b) for b in batches]
    prog = program_readings(model, feeds, w0, cfg["adam_beta1"], ref_steps,
                            want.pop("first_grad"))
    del w0
    compare(prog, want, cfg["limits"], checks)
    compare_module(prog, want, cfg["limits"], checks)
    del want

    def step(k):
        return model.step(feeds[k % len(feeds)])

    if ctx.check:
        # no chip: a fixed number of steps, counts only, never a time
        with harness.count_compiles() as cc:
            outs = [read_out(step(ref_steps + i))
                    for i in range(traffic["check_steps"])]
        bad = sum(not math.isfinite(l) or not math.isfinite(s["mtp_loss"])
                  for l, s in outs)
        checks.add("losses_finite", float(bad), 0.0)
        checks.add("dropped_token_pairs.window",
                   float(dropped_pairs([s for _, s in outs])),
                   cfg["limits"]["dropped_token_pairs"])
        checks.add("compiles_in_window", float(harness.n_compiles(cc())), 0.0)
        model.close()
        return {"correct": checks.ok(), "attempted": len(outs), "failed": bad,
                "end_to_end": {}, "facts": {
                    "kind": "train",
                    "compiles_in_window": harness.n_compiles(cc()),
                    "tokens_per_step": tokens_per_step,
                    "step_stats": outs[-1][1]}}

    # -- the window -----------------------------------------------------------
    np.asarray(step(ref_steps)[0])                                 # settle
    k, steps, fetched, stats, dispatch = ref_steps + 1, 0, [], [], []
    trace_at = 10 if ctx.trace else None
    traced_steps, summary = 0, None
    # a fetch point's arrays are read one fetch point LATER, when that step
    # is long done: reading them at once would drain the dispatch window
    out = due = None

    def fetch(out):
        loss, st = read_out(out)
        fetched.append(loss)
        stats.append(st)
    with harness.count_compiles() as cc:
        t0 = time.perf_counter()
        while True:
            ts = time.perf_counter()
            if ts - t0 >= ctx.seconds:
                break
            if steps == trace_at:
                np.asarray(out[0])
                tdir = harness.trace_dir(ctx)
                jax.profiler.start_trace(tdir)
                for _ in range(traffic["profile_steps"]):
                    with jax.profiler.TraceAnnotation("bm/train_step"):
                        out = step(k)
                    k += 1
                    steps += 1
                    traced_steps += 1
                with jax.profiler.TraceAnnotation("bm/fetch_loss"):
                    fetch(out)
                jax.profiler.stop_trace()
                summary = trace_reduce.summarize(
                    trace_reduce.load(trace_reduce.find_xplane(tdir)),
                    ctx.chips)
                continue
            out = step(k)
            dispatch.append(time.perf_counter() - ts)
            k += 1
            steps += 1
            if steps % traffic["fetch_every"] == 0:
                if due is not None:
                    fetch(due)
                due = out
        if due is not None and due is not out:
            fetch(due)
        fetch(out)
        t1 = time.perf_counter()
    window_s = t1 - t0
    compiles = harness.n_compiles(cc())
    bad = sum(not math.isfinite(v) for v in fetched) + sum(
        not math.isfinite(s["mtp_loss"]) for s in stats)
    checks.add("losses_not_finite", float(bad), 0.0,
               note="%d fetched, last %.4f (module's %.4f)" % (
                   len(fetched), fetched[-1], stats[-1]["mtp_loss"]))
    checks.add("dropped_token_pairs.window", float(dropped_pairs(stats)),
               cfg["limits"]["dropped_token_pairs"])
    checks.add("compiles_in_window", float(compiles), 0.0)
    rate = steps * tokens_per_step / window_s
    step_s = window_s / steps
    mean = {n: sum(s[n] for s in stats) / len(stats) for n in STATS}
    n_dense, n_expert = flops.blocks(cfg)
    held = cfg["n_routed_experts_held"]
    floor_s, bound = flops.step_floor_seconds(
        cfg, rows, seq, mean["pairs_computed"], ctx.peaks, ctx.chips)
    per_expert = mean["pairs_computed"] / (n_expert * held)
    ctx.log("train: %d steps in %.3f s, %.5f s/step, %d tokens a step; "
            "step floor %.5f s (%s-bound), %.4f of the step; a step computed "
            "%.0f token-expert pairs over %d expert blocks (fullest held "
            "expert %.0f tokens, mean %.1f); the module's loss %.4f"
            % (steps, window_s, step_s, tokens_per_step, floor_s, bound,
               floor_s / step_s, mean["pairs_computed"], n_expert,
               mean["max_expert_tokens"], per_expert, mean["mtp_loss"]))
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
            "chips": ctx.chips, "step_stats": mean,
            "expert_load_max_over_mean": mean["max_expert_tokens"] / max(
                per_expert, 1e-9),
            "latent_attention_floor_s": (n_dense + n_expert)
            * flops.kernel_floor_seconds(
                flops.attention_flops(cfg, rows, seq),
                flops.attention_least_bytes(cfg, rows, seq), ctx.peaks),
            "expert_matmul_floor_s": flops.kernel_floor_seconds(
                flops.expert_flops(cfg, mean["pairs_computed"]),
                flops.expert_least_bytes(cfg), ctx.peaks)},
    }


def gaps(got, want):
    """Every number ``correct`` compares, of ``got`` against ``want``."""
    return {"grad_rel_error_rms": rel_error_rms(got["grad_errors"],
                                                want["grad_norms"]),
            "loss_rel_gap": max(
                max(abs(a - b) / abs(b) for a, b in zip(
                    got["losses"], want["losses"])),
                mtp_loss_gap(got, want)),
            "grad_norm_gap": worst_leaf_gap(got["grad_norms"],
                                            want["grad_norms"])[0],
            "grad_norm_gap_rms": rms_leaf_gap(got["grad_norms"],
                                              want["grad_norms"]),
            "update_norm_gap": worst_leaf_gap(got["update_norms"],
                                              want["update_norms"])[0],
            "update_norm_gap_rms": rms_leaf_gap(got["update_norms"],
                                                want["update_norms"]),
            "routed_pairs_gap": routed_pairs_gap(got, want)}


def control_readings(ref, cfg, batches, w0, want, kind):
    """The control's readings in the program's place: the reference with
    its products in ``kind``."""
    ctl = reference_readings(ref, cfg, batches, w0, ref.lowp_matmul(kind), 3)
    ctl["grad_errors"] = grad_error_norms(
        {n: jnp.asarray(v) for n, v in ctl.pop("first_grad").items()},
        1.0, want["first_grad"])
    return ctl


def readings(ctx, seeds, seconds, kinds):
    """For setting the limits: per seed, in one process and with no
    window, the program's readings against the plain reference's, and the
    control's (the reference with its products in ``kinds[0]``) against
    the same.  ``seconds`` is unused."""
    cfg, traffic = dict(ctx.cfg), ctx.traffic
    ref = harness.load_reference(cfg["reference"], ctx.root)
    model = harness.load_module("models", cfg["builder"],
                                ctx.root).build_train(
        cfg, traffic, ctx.devices[:ctx.chips])
    spec, out = ref.param_spec(cfg), []
    for seed in seeds:
        model.release()
        gc.collect()
        batches = make_batches(dict(traffic, pool=3), cfg["vocab_size"], seed)
        w0 = seeded_weights(spec, cfg, seed)
        want = reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
        ctl = control_readings(ref, cfg, batches, w0, want, kinds[0])
        gc.collect()
        model.reset()
        model.set_weights(w0)
        feeds = [model.make_feed(b) for b in batches]
        prog = program_readings(model, feeds, w0, cfg["adam_beta1"], 3,
                                want["first_grad"])
        row = {"seed": seed, "sound": gaps(prog, want),
               "control": gaps(ctl, want),
               "dropped_token_pairs": dropped_pairs(prog["stats"]),
               "stats": prog["stats"][0]}
        out.append(row)
        ctx.log("readings %s" % row)
    model.close()
    return out
