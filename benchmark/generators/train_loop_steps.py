"""Traffic generator ``train_loop_steps``: seeded whole-document batches
through one compiled training step of a looped decoder-only language model
(one stack of blocks run several times over the same weights, an exit gate
after each pass), a fresh host batch every step.

Parameters (the mix's data file) as ``train_lm_steps``': ``rows`` documents
of ``seq`` tokens a step — each drawn ``seq + 1`` ids long, so that the next
token exists at every position: no padding, no packing, no wrapped label —
ids uniform over the configuration's ``vocab_size`` (the held slice of the
vocabulary) from ``--seed``; ``pool`` distinct batches made in set-up and
cycled; the loss and the step's counters fetched every ``fetch_every`` steps
— each fetch point's arrays read to the host one fetch point later — and at
the window's end; ``profile_steps`` traced steps in a ``--trace 1`` run.
``train_tokens_per_s`` counts ``rows * seq`` a step, however many passes
the model makes over them.  Every seed: the same shapes, other ids.

What ``correct`` compares (``train_steps``' six numbers and two of this
kind's own): the three exit-gated losses, the first gradient leaf by leaf
(norm of the difference, gap of norms) — every weight's is its passes' sum
— the update after three steps; ``loss_rel_gap.passes`` — each pass's mean
cross entropy in each check step against the plain reference's — and
``exit_mass_gap`` — each pass's mean exit mass against the reference's, as
a difference of shares.
"""

import gc
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.generators.train_lm_steps import seeded_weights
from benchmark.generators.train_steps import (
    _diff_norm, compare, grad_error_norms, leaf_norms, rel_error_rms,
    rms_leaf_gap, worst_leaf_gap)
from benchmark.trace import reduce as trace_reduce


def make_batches(traffic, vocab, seed):
    """``pool`` batches {tok, lbl} [rows, seq] as numpy arrays, cut from
    documents one id longer; the same for the same seed."""
    rng = np.random.default_rng(seed)
    seq, out = traffic["seq"], []
    for _ in range(traffic["pool"]):
        doc = rng.integers(0, vocab, (traffic["rows"], seq + 1),
                           dtype=np.int64)
        out.append({"tok": doc[:, :seq], "lbl": doc[:, 1:]})
    return out


def reference_readings(ref, cfg, batches, w0, mm, steps, use=None):
    """What the plain reference gives over the first ``steps`` steps from
    the host weights ``w0``: each loss, each pass's mean cross entropy and
    mean exit mass, per-leaf norm of the first gradient, per-leaf norm of
    the parameters' change after the last step.  ``use`` is the
    reference's: the passes whose uses of the weights its gradient sums."""
    p = {n: jnp.asarray(v) for n, v in w0.items()}
    state, losses, pass_losses, masses, grad_norms = None, [], [], [], None
    for k in range(steps):
        b = {n: jnp.asarray(v, jnp.int32) for n, v in batches[k].items()}
        loss, ce, mass, grad = ref.loss_and_grad(
            p, b, cfg, cfg["reference_block_rows"], mm, use)
        losses.append(float(loss))
        pass_losses.append(np.asarray(ce).tolist())
        masses.append(np.asarray(mass).tolist())
        if k == 0:
            grad_norms = leaf_norms(grad)
            first_grad = jax.device_get(grad)
        state = ref.adam_init(p) if state is None else state
        p, state = ref.adam_step(p, grad, state, cfg)
        del grad
    del state
    update_norms = {n: float(_diff_norm(p[n], jnp.asarray(w0[n])))
                    for n in grad_norms}
    return {"losses": losses, "pass_losses": pass_losses,
            "exit_masses": masses, "grad_norms": grad_norms,
            "update_norms": update_norms, "first_grad": first_grad}


def read_out(out, names):
    """(loss, {counter: value}) of one step's fetches, on the host."""
    return (float(np.asarray(out[0]).ravel()[0]),
            dict(zip(names, np.asarray(out[1]).tolist())))


def program_readings(model, feeds, w0, beta1, steps, want_grad, names):
    """The same readings from the program, through the window's own call:
    the first gradient is worked out from Adam's first moment after one
    step (m1 = (1 - beta1) g).  ``stats`` holds every check step's
    counters."""
    losses, stats, grad_norms, grad_errors = [], [], None, None
    for k in range(steps):
        loss, st = read_out(model.step(feeds[k]), names)
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
    half = len(names) // 2
    return {"losses": losses,
            "pass_losses": [[s[n] for n in names[:half]] for s in stats],
            "exit_masses": [[s[n] for n in names[half:]] for s in stats],
            "grad_norms": grad_norms, "grad_errors": grad_errors,
            "stats": stats,
            "update_norms": {n: float(v) for n, v in
                             jax.device_get(update_norms).items()}}


def pass_loss_gap(got, want):
    return max(abs(a - b) / abs(b)
               for g, w in zip(got["pass_losses"], want["pass_losses"])
               for a, b in zip(g, w))


def exit_mass_gap(got, want):
    return max(abs(a - b)
               for g, w in zip(got["exit_masses"], want["exit_masses"])
               for a, b in zip(g, w))


def compare_passes(prog, want, limits, checks):
    """Each pass's loss and exit mass beside the total."""
    checks.add("loss_rel_gap.passes", pass_loss_gap(prog, want),
               limits["loss_rel_gap"],
               note="step 1: program %s reference %s" % (
                   ["%.6f" % v for v in prog["pass_losses"][0]],
                   ["%.6f" % v for v in want["pass_losses"][0]]))
    checks.add("exit_mass_gap", exit_mass_gap(prog, want),
               limits["exit_mass_gap"],
               note="step 1: program %s reference %s" % (
                   ["%.5f" % v for v in prog["exit_masses"][0]],
                   ["%.5f" % v for v in want["exit_masses"][0]]))


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
    names = list(model.main.step_stats[1])         # the step's counters
    model.set_weights(w0)
    feeds = [model.make_feed(b) for b in batches]
    prog = program_readings(model, feeds, w0, cfg["adam_beta1"], ref_steps,
                            want.pop("first_grad"), names)
    del w0
    compare(prog, want, cfg["limits"], checks)
    compare_passes(prog, want, cfg["limits"], checks)
    del want

    def step(k):
        return model.step(feeds[k % len(feeds)])

    def not_finite(loss, st):
        return sum(not math.isfinite(v) for v in [loss] + list(st.values()))

    if ctx.check:
        # no chip: a fixed number of steps, counts only, never a time
        with harness.count_compiles() as cc:
            outs = [read_out(step(ref_steps + i), names)
                    for i in range(traffic["check_steps"])]
        bad = sum(not_finite(l, s) > 0 for l, s in outs)
        checks.add("losses_finite", float(bad), 0.0)
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
        loss, st = read_out(out, names)
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
    bad = sum(not_finite(l, s) > 0 for l, s in zip(fetched, stats))
    checks.add("losses_not_finite", float(bad), 0.0,
               note="%d fetched, last %.4f (the passes' %s)" % (
                   len(fetched), fetched[-1],
                   ["%.4f" % stats[-1][n] for n in names[:len(names) // 2]]))
    checks.add("compiles_in_window", float(compiles), 0.0)
    rate = steps * tokens_per_step / window_s
    step_s = window_s / steps
    mean = {n: sum(s[n] for s in stats) / len(stats) for n in names}
    floor_s, bound = flops.step_floor_seconds(cfg, rows, seq, ctx.peaks,
                                              ctx.chips)
    ctx.log("train: %d steps in %.3f s, %.5f s/step, %d tokens a step; "
            "step floor %.5f s (%s-bound), %.4f of the step; %d applications "
            "of a block a step; mean exit mass by pass %s"
            % (steps, window_s, step_s, tokens_per_step, floor_s, bound,
               floor_s / step_s, flops.applications(cfg),
               ["%.4f" % mean[n] for n in names[len(names) // 2:]]))
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
            "plain_attention_floor_s": flops.applications(cfg)
            * flops.kernel_floor_seconds(
                flops.attention_flops(cfg, rows, seq),
                flops.attention_least_bytes(cfg, rows, seq), ctx.peaks)},
    }


def gaps(got, want):
    """Every number ``correct`` compares, of ``got`` against ``want``."""
    return {"grad_rel_error_rms": rel_error_rms(got["grad_errors"],
                                                want["grad_norms"]),
            "loss_rel_gap": max(abs(a - b) / abs(b) for a, b in zip(
                got["losses"], want["losses"])),
            "loss_rel_gap.passes": pass_loss_gap(got, want),
            "exit_mass_gap": exit_mass_gap(got, want),
            "grad_norm_gap": worst_leaf_gap(got["grad_norms"],
                                            want["grad_norms"])[0],
            "grad_norm_gap_rms": rms_leaf_gap(got["grad_norms"],
                                              want["grad_norms"]),
            "update_norm_gap": worst_leaf_gap(got["update_norms"],
                                              want["update_norms"])[0],
            "update_norm_gap_rms": rms_leaf_gap(got["update_norms"],
                                                want["update_norms"])}


def in_program_place(got, want):
    """The readings of a reference run put in the program's place: its
    first gradient against the plain reference's, leaf by leaf."""
    got["grad_errors"] = grad_error_norms(
        {n: jnp.asarray(v) for n, v in got.pop("first_grad").items()}, 1.0,
        want["first_grad"])
    return got


def control_readings(ref, cfg, batches, w0, want, kind):
    """The control's readings in the program's place: the reference with
    its products in ``kind``."""
    return in_program_place(reference_readings(
        ref, cfg, batches, w0, ref.lowp_matmul(kind), 3), want)


# The faults the limits stand against, each planted in the plain reference
# (float32: no rounding beside it) and that run put in the program's
# place, as the control is.  name -> (cfg, w0) -> (cfg, w0, use): what of
# the reference's inputs the fault changes.
FAULTS = {
    # the last pass's use of every weight is missing from its gradient's sum
    "weight_use_missing": lambda cfg, w0: (
        cfg, w0, tuple(range(1, cfg["total_ut_steps"]))),
    # the stack is run P-1 times: the last pass's loss and exit mass fall
    # to the pass before it
    "pass_left_out": lambda cfg, w0: (
        dict(cfg, total_ut_steps=cfg["total_ut_steps"] - 1), w0, None),
    # the loss without - beta H(p)
    "entropy_term_dropped": lambda cfg, w0: (
        dict(cfg, exit_beta=0.0), w0, None),
    # the gate's weight left at zero, not the seeded one: every gate reads
    # one half
    "gate_weight_unset": lambda cfg, w0: (
        cfg, dict(w0, **{"gate.w": np.zeros_like(w0["gate.w"])}), None),
    # the other decoders' rotary base, 1e4 for 1e6
    "rotary_base_default": lambda cfg, w0: (
        dict(cfg, rope_theta=10000), w0, None),
    # the state comes back as it went
    "state_unchanged": lambda cfg, w0: (
        dict(cfg, learning_rate=0.0), w0, None),
}


def fault_readings(ref, cfg, batches, w0, want, fault):
    """One planted fault's readings in the program's place."""
    cfg, w0, use = FAULTS[fault](cfg, w0)
    return in_program_place(reference_readings(
        ref, cfg, batches, w0, ref.f32_matmul, 3, use), want)


def checks_failed(got, want, limits):
    """The names of the checks that ``got``, in the program's place, fails
    against ``want``: through the comparison that decides ``correct``."""
    checks = harness.Checks(lambda line: None)
    compare(got, want, limits, checks)
    compare_passes(got, want, limits, checks)
    return sorted(r[0] for r in checks.rows if not r[3])


def readings(ctx, seeds, seconds, kinds):
    """For setting the limits: per seed, in one process and with no
    window, the program's readings against the plain reference's, and the
    control's (the reference with its products in ``kinds[0]``) against
    the same, with the checks the control fails; on the first seed each
    planted fault's too.  ``seconds`` is unused."""
    cfg, traffic = dict(ctx.cfg), ctx.traffic
    ref = harness.load_reference(cfg["reference"], ctx.root)
    model = harness.load_module("models", cfg["builder"],
                                ctx.root).build_train(
        cfg, traffic, ctx.devices[:ctx.chips])
    spec, out = ref.param_spec(cfg), []
    names = list(model.main.step_stats[1])
    for seed in seeds:
        model.release()
        gc.collect()
        batches = make_batches(dict(traffic, pool=3), cfg["vocab_size"], seed)
        w0 = seeded_weights(spec, cfg, seed)
        want = reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
        ctl = control_readings(ref, cfg, batches, w0, want, kinds[0])
        row = {"seed": seed, "control": gaps(ctl, want),
               "control_fails": checks_failed(ctl, want, cfg["limits"])}
        del ctl
        if seed == seeds[0]:
            row["faults"] = {}
            for fault in FAULTS:
                got = fault_readings(ref, cfg, batches, w0, want, fault)
                row["faults"][fault] = {
                    "gaps": gaps(got, want),
                    "fails": checks_failed(got, want, cfg["limits"])}
                del got
        gc.collect()
        model.reset()
        model.set_weights(w0)
        feeds = [model.make_feed(b) for b in batches]
        prog = program_readings(model, feeds, w0, cfg["adam_beta1"], 3,
                                want["first_grad"], names)
        row.update(sound=gaps(prog, want),
                   sound_fails=checks_failed(prog, want, cfg["limits"]),
                   stats=prog["stats"][0])
        out.append(row)
        ctx.log("readings %s" % row)
    model.close()
    return out
