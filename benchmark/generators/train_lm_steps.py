"""Traffic generator ``train_lm_steps``: seeded whole-document batches
through one compiled training step of a decoder-only language model, a
fresh host batch every step.

Parameters (the mix's data file): ``rows`` documents of ``seq`` tokens a
step, every position real (no padding, no packing), ids uniform over the
configuration's ``vocab_size`` (the held slice of the vocabulary) from
``--seed``, labels the next token; ``pool`` distinct batches made in set-up
and cycled; the loss and the step's counters fetched every ``fetch_every``
steps — each fetch point's arrays read to the host one fetch point later,
so that no fetch drains the dispatch window — and at the window's end;
``profile_steps`` traced steps in a ``--trace 1`` run.  Every seed: the same shapes, other ids.

What ``correct`` compares (``train_steps``' six numbers and two of this
kind's own): the three losses, the first gradient leaf by leaf (norm of
the difference, gap of norms), the update after three steps;
``selected_overlap`` — of the keys the program's first layer selected in
the first step, the share the plain reference selected too — and
``dropped_token_pairs`` — token-expert pairs routed to a held expert that
the grouped products did not compute, which a dropless layer keeps at 0.
"""

import gc
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.generators.train_steps import (
    _diff_norm, compare, grad_error_norms, leaf_norms, rel_error_rms,
    rms_leaf_gap, worst_leaf_gap)
from benchmark.trace import reduce as trace_reduce

STATS = ("pairs_routed", "pairs_computed", "max_expert_tokens",
         "selected_share")


def make_batches(traffic, vocab, seed):
    """``pool`` batches {tok, lbl} [rows, seq] as numpy arrays; the same
    for the same seed."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(traffic["pool"]):
        tok = rng.integers(0, vocab, (traffic["rows"], traffic["seq"]),
                           dtype=np.int64)
        out.append({"tok": tok, "lbl": np.roll(tok, -1, axis=1)})
    return out


def seeded_weights(spec, cfg, seed):
    """The seeded weights on the host, each leaf named in the
    configuration's ``init_scale`` times its factor: the one set of arrays
    the reference and the program both get."""
    w = weights.make_weights(spec, seed)
    for name, factor in cfg.get("init_scale", {}).items():
        w[name] = w[name] * factor
    return jax.device_get(w)


def unpack_selected(words, tk):
    """The program's packed key mask (int32 [B, T, W]; key s is bit
    ``(s % 4096) // 128`` of word ``(s // 4096) * 128 + s % 128``) as bool
    [B, T, tk]."""
    w = np.ascontiguousarray(words).view(np.uint32)
    tiles = w.shape[-1] // 128
    w = w.reshape(w.shape[:-1] + (tiles, 1, 128))
    bits = (w >> np.arange(32, dtype=np.uint32)[:, None]) & np.uint32(1)
    return bits.reshape(w.shape[:-3] + (tiles * 4096,))[..., :tk].astype(bool)


def overlap(got, want):
    """Share of ``got``'s selected keys that ``want`` selected too."""
    return float(np.sum(got & want)) / max(float(np.sum(got)), 1.0)


def reference_readings(ref, cfg, batches, w0, mm, steps):
    """What the plain reference gives over the first ``steps`` steps from
    the host weights ``w0``: each loss, per-leaf norm of the first gradient,
    per-leaf norm of the parameters' change after the last step, and the
    first layer's selected keys in the first step.  Between steps Adam's
    moments wait on the host: a float32 step of this size leaves the chip
    no room for them."""
    p = {n: jnp.asarray(v) for n, v in w0.items()}
    selected = np.asarray(ref.first_selection(
        p, jnp.asarray(batches[0]["tok"], jnp.int32), cfg, mm))
    state, losses, grad_norms = None, [], None
    for k in range(steps):
        b = {n: jnp.asarray(v, jnp.int32) for n, v in batches[k].items()}
        loss, grad = ref.loss_and_grad(p, b, cfg, cfg["reference_block_rows"],
                                       mm)
        losses.append(float(loss))
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
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, "first_grad": first_grad,
            "selected": selected}


def program_readings(model, feeds, w0, beta1, steps, want_grad, seq):
    """The same readings from the program, through the window's own call:
    the first gradient is worked out from Adam's first moment after one
    step (m1 = (1 - beta1) g).  ``stats`` holds every check step's
    counters."""
    losses, stats, grad_norms, grad_errors, selected = [], [], None, None, None
    for k in range(steps):
        loss, st, sel = model.step(feeds[k])
        losses.append(float(np.asarray(loss).ravel()[0]))
        stats.append(dict(zip(STATS, np.asarray(st).tolist())))
        if k == 0:
            selected = unpack_selected(np.asarray(sel), seq)
            m1 = {n: jnp.asarray(v) for n, v in
                  model.state(want_grad, "_moment1_0").items()}
            grad_norms = {n: v / (1.0 - beta1)
                          for n, v in leaf_norms(m1).items()}
            grad_errors = grad_error_norms(m1, 1.0 - beta1, want_grad)
            del m1
        del sel
    now = model.state(want_grad)
    update_norms = {n: _diff_norm(now[n], jax.device_put(
        w0[n], now[n].sharding)) for n in want_grad}
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_errors": grad_errors, "stats": stats, "selected": selected,
            "update_norms": {n: float(v) for n, v in
                             jax.device_get(update_norms).items()}}


def dropped_pairs(stats):
    return max(abs(s["pairs_routed"] - s["pairs_computed"]) for s in stats)


def compare_selection(prog, want, limits, checks):
    share = overlap(prog["selected"], want["selected"])
    checks.add("selected_overlap", 1.0 - share,
               1.0 - limits["selected_overlap_min"],
               note="1 - overlap; overlap %.5f, at least %s"
               % (share, limits["selected_overlap_min"]))
    checks.add("dropped_token_pairs", float(dropped_pairs(prog["stats"])),
               limits["dropped_token_pairs"],
               note="routed %s computed %s" % (
                   [s["pairs_routed"] for s in prog["stats"]],
                   [s["pairs_computed"] for s in prog["stats"]]))


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
    spec = ref.param_spec(cfg)

    # -- the plain reference first, before the program's state is made ----
    # (the seeded weights wait on the host: the float32 reference and then
    # the program each get the chip to themselves)
    w0 = seeded_weights(spec, cfg, ctx.seed)
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
                            want.pop("first_grad"), seq)
    del w0
    compare(prog, want, cfg["limits"], checks)
    compare_selection(prog, want, cfg["limits"], checks)
    del want, prog["selected"]

    if ctx.check:
        # no chip: a fixed number of steps, counts only, never a time
        with harness.count_compiles() as cc:
            outs = [model.step(feeds[(ref_steps + i) % len(feeds)])
                    for i in range(traffic["check_steps"])]
            losses = [float(np.asarray(o[0]).ravel()[0]) for o in outs]
            stats = [dict(zip(STATS, np.asarray(o[1]).tolist()))
                     for o in outs]
        checks.add("losses_finite", float(sum(
            not math.isfinite(v) for v in losses)), 0.0)
        checks.add("dropped_token_pairs.window", float(dropped_pairs(stats)),
                   cfg["limits"]["dropped_token_pairs"])
        checks.add("compiles_in_window", float(harness.n_compiles(cc())), 0.0)
        model.close()
        return {"correct": checks.ok(), "attempted": len(losses),
                "failed": sum(not math.isfinite(v) for v in losses),
                "end_to_end": {}, "facts": {
                    "kind": "train",
                    "compiles_in_window": harness.n_compiles(cc()),
                    "tokens_per_step": tokens_per_step,
                    "step_stats": stats[-1]}}

    # -- the window -----------------------------------------------------------
    np.asarray(model.step(feeds[ref_steps % len(feeds)])[0])      # settle
    k, steps, fetched, stats, dispatch = ref_steps + 1, 0, [], [], []
    trace_at = 10 if ctx.trace else None
    traced_steps, summary = 0, None
    # a fetch point's arrays are read one fetch point LATER, when that step
    # is long done: reading them at once would drain the dispatch window
    # and leave the chip idle for as long as the host takes to dispatch the
    # next step — two runs on a busy host read 1.7% and 2.4% fewer tokens/s
    # for the same device work a step (my chip runs, PR 26)
    out = due = None

    def fetch(out):
        fetched.append(float(np.asarray(out[0]).ravel()[0]))
        stats.append(dict(zip(STATS, np.asarray(out[1]).tolist())))
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
                        out = model.step(feeds[k % len(feeds)])
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
            out = model.step(feeds[k % len(feeds)])
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
    bad = sum(not math.isfinite(v) for v in fetched)
    checks.add("losses_not_finite", float(bad), 0.0,
               note="%d losses fetched, last %.4f" % (len(fetched),
                                                      fetched[-1]))
    checks.add("dropped_token_pairs.window", float(dropped_pairs(stats)),
               cfg["limits"]["dropped_token_pairs"])
    checks.add("compiles_in_window", float(compiles), 0.0)
    rate = steps * tokens_per_step / window_s
    step_s = window_s / steps
    mean = {n: sum(s[n] for s in stats) / len(stats) for n in STATS}
    n_layer, held = cfg["num_hidden_layers"], cfg["num_local_experts"]
    floor_s, bound = flops.step_floor_seconds(
        cfg, rows, seq, mean["pairs_computed"], ctx.peaks, ctx.chips)
    sel_pairs = rows * flops.selected_pairs(seq, cfg["sa_config"]["topk"])
    ctx.log("train: %d steps in %.3f s, %.5f s/step, %d tokens a step; "
            "step floor %.5f s (%s-bound), %.4f of the step; a step computed "
            "%.0f token-expert pairs over %d layers (fullest held expert "
            "%.0f tokens, mean %.1f), selected %.4f of the causal pairs"
            % (steps, window_s, step_s, tokens_per_step, floor_s, bound,
               floor_s / step_s, mean["pairs_computed"], n_layer,
               mean["max_expert_tokens"],
               mean["pairs_computed"] / (n_layer * held),
               mean["selected_share"]))
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
                mean["pairs_computed"] / (n_layer * held), 1e-9),
            "sparse_attention_floor_s": n_layer * flops.kernel_floor_seconds(
                flops.attention_flops(cfg, sel_pairs),
                flops.attention_least_bytes(cfg, rows, seq), ctx.peaks),
            "expert_matmul_floor_s": flops.kernel_floor_seconds(
                flops.expert_flops(cfg, mean["pairs_computed"]),
                flops.expert_least_bytes(cfg), ctx.peaks)},
    }


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
                                                    want["update_norms"]),
                "selected_overlap": overlap(got["selected"],
                                            want["selected"])}
    for seed in seeds:
        model.release()
        gc.collect()
        batches = make_batches(dict(traffic, pool=3), cfg["vocab_size"], seed)
        w0 = seeded_weights(spec, cfg, seed)
        want = reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
        ctl = reference_readings(ref, cfg, batches, w0,
                                 ref.lowp_matmul(kinds[0]), 3)
        ctl["grad_errors"] = grad_error_norms(
            {n: jnp.asarray(v) for n, v in ctl.pop("first_grad").items()},
            1.0, want["first_grad"])
        gc.collect()
        model.reset()
        model.set_weights(w0)
        feeds = [model.make_feed(b) for b in batches]
        prog = program_readings(model, feeds, w0, cfg["adam_beta1"], 3,
                                want["first_grad"], traffic["seq"])
        row = {"seed": seed, "sound": gaps(prog, want),
               "control": gaps(ctl, want),
               "dropped_token_pairs": dropped_pairs(prog["stats"]),
               "stats": prog["stats"][0]}
        out.append(row)
        ctx.log("readings %s" % row)
    model.close()
    return out
