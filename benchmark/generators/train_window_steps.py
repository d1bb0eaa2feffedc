"""Traffic generator ``train_window_steps``: seeded whole-document batches
through one compiled training step of a decoder-only language model whose
grouped-query attention differs by layer — a window of the nearest keys under
the plain rotation, or every causal key under YaRN's — over routed experts of
which this chip holds a share; a fresh host batch every step.

Parameters (the mix's data file) as ``train_loop_steps``': ``rows`` documents
of ``seq`` tokens a step — each drawn ``seq + 1`` ids long, so that the next
token exists at every position: no padding, no packing, no wrapped label —
ids uniform over the configuration's ``vocab_size`` (the held slice of the
vocabulary) from ``--seed``; ``pool`` distinct batches made in set-up and
cycled; the loss and the step's counters fetched every ``fetch_every`` steps
— each fetch point's arrays read to the host one fetch point later — and at
the window's end; ``profile_steps`` traced steps in a ``--trace 1`` run.
Every seed: the same shapes, other ids and other weights.

What ``correct`` compares (``train_steps``' six numbers and three of this
kind's own): the three losses, the first gradient leaf by leaf (norm of the
difference, gap of norms), the update after three steps;
``routed_pairs_gap`` and ``dropped_token_pairs`` as ``train_mtp_steps``
takes them — the token-expert pairs the program's routers sent to the held
experts against the plain reference's count, and pairs routed to a held
expert that the grouped products did not compute, which a dropless layer
keeps at 0 — and ``mixer_context_gap`` — the norm of the difference between
the attention half's output (``ctx``, what ``Wo`` reads) and the plain
reference's over the reference's norm, the larger of the first window layer's
and the first full layer's, the largest of the three steps; the program's
``ctx`` are fetches of the executable the window runs.
"""

import gc
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.generators.train_lm_steps import (          # noqa: F401
    dropped_pairs, seeded_weights)
from benchmark.generators.train_loop_steps import (        # noqa: F401
    in_program_place, make_batches, read_out)
from benchmark.generators.train_mtp_steps import routed_pairs_gap
from benchmark.generators.train_steps import (
    _diff_norm, compare, grad_error_norms, leaf_norms, rel_error_rms,
    rms_leaf_gap, worst_leaf_gap)
from benchmark.trace import reduce as trace_reduce

# the step's counters in the order the program declares them
# (``models.sparse_moe_decoder.WINDOW_STEP_STATS``), under this kind's names
STATS = ("pairs_routed", "pairs_computed", "max_expert_tokens",
         "window_pair_share")


def reference_readings(ref, cfg, batches, w0, mm, steps):
    """What the plain reference gives over the first ``steps`` steps from
    the host weights ``w0``: each loss, each step's compared ``ctx`` (on the
    host) and pairs routed to the held experts, per-leaf norm of the first
    gradient, per-leaf norm of the parameters' change after the last step.
    Between steps Adam's moments wait on the host: a float32 step of this
    size leaves the chip little room for them."""
    p = {n: jnp.asarray(v) for n, v in w0.items()}
    state, losses, contexts, pairs, grad_norms = None, [], [], [], None
    for k in range(steps):
        b = {n: jnp.asarray(v, jnp.int32) for n, v in batches[k].items()}
        loss, ctx, n, grad = ref.loss_and_grad(
            p, b, cfg, cfg["reference_block_rows"], mm)
        losses.append(float(loss))
        contexts.append(np.asarray(ctx))
        pairs.append(float(n))
        del ctx
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
    return {"losses": losses, "contexts": contexts, "pairs_routed": pairs,
            "grad_norms": grad_norms, "update_norms": update_norms,
            "first_grad": first_grad}


def program_readings(model, feeds, w0, beta1, steps, want_grad):
    """The same readings from the program, through the window's own call:
    the first gradient is worked out from Adam's first moment after one
    step (m1 = (1 - beta1) g).  ``stats`` holds every check step's
    counters, ``contexts`` every check step's compared ``ctx`` as float32
    on the host, [layers, rows, T, H * Dh]."""
    losses, stats, contexts, grad_norms, grad_errors = [], [], [], None, None
    for k in range(steps):
        out = model.step(feeds[k])
        loss, st = read_out(out, STATS)
        losses.append(loss)
        stats.append(st)
        contexts.append(np.stack([np.asarray(c).astype(np.float32)
                                  for c in out[2:]]))
        del out
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
    return {"losses": losses, "contexts": contexts, "stats": stats,
            "pairs_routed": [s["pairs_routed"] for s in stats],
            "grad_norms": grad_norms, "grad_errors": grad_errors,
            "update_norms": {n: float(v) for n, v in
                             jax.device_get(update_norms).items()}}


def context_gaps(got, want):
    """Per compared layer (the first window layer, the first full layer):
    the norm of the difference of ``ctx`` over the reference's norm, the
    largest of the steps."""
    return [max(float(np.linalg.norm(a[i] - b[i])
                      / max(np.linalg.norm(b[i]), 1e-30))
                for a, b in zip(got["contexts"], want["contexts"]))
            for i in range(len(want["contexts"][0]))]


def compare_kind(prog, want, limits, checks):
    """This kind's own numbers: the two counts and the attention halves'
    outputs."""
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
    per_layer = context_gaps(prog, want)
    checks.add("mixer_context_gap", max(per_layer),
               limits["mixer_context_gap"],
               note="first window layer, first full layer: %s" % (
                   ["%.6g" % g for g in per_layer],))


def _floors(flops, cfg, rows, seq, pairs, peaks):
    return {
        "mixed_attention_floor_s": flops.mixed_attention_floor_seconds(
            cfg, rows, seq, peaks),
        "expert_matmul_floor_s": flops.kernel_floor_seconds(
            flops.expert_flops(cfg, pairs),
            flops.expert_least_bytes(cfg), peaks)}


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
    compare_kind(prog, want, cfg["limits"], checks)
    ctx.log("step 1 counters: program %s; reference pairs_routed %.0f"
            % (prog["stats"][0], want["pairs_routed"][0]))
    del want, prog["contexts"]

    def step(k):
        # the loss and the counters alone: the two ``ctx`` (64 MiB each at
        # the cell's size) are the set-up checks' and are let go at once
        return model.step(feeds[k % len(feeds)])[:2]

    def not_finite(loss, st):
        return sum(not math.isfinite(v) for v in [loss] + list(st.values()))

    if ctx.check:
        # no chip: a fixed number of steps, counts only, never a time
        with harness.count_compiles() as cc:
            outs = [read_out(step(ref_steps + i), STATS)
                    for i in range(traffic["check_steps"])]
        bad = sum(not_finite(l, s) > 0 for l, s in outs)
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
        loss, st = read_out(out, STATS)
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
               note="%d fetched, last %.4f" % (len(fetched), fetched[-1]))
    checks.add("dropped_token_pairs.window", float(dropped_pairs(stats)),
               cfg["limits"]["dropped_token_pairs"])
    checks.add("compiles_in_window", float(compiles), 0.0)
    rate = steps * tokens_per_step / window_s
    step_s = window_s / steps
    mean = {n: sum(s[n] for s in stats) / len(stats) for n in STATS}
    floor_s, bound = flops.step_floor_seconds(
        cfg, rows, seq, mean["pairs_computed"], ctx.peaks, ctx.chips)
    per_expert = mean["pairs_computed"] / (
        cfg["num_hidden_layers"] * cfg["num_experts_held"])
    ctx.log("train: %d steps in %.3f s, %.5f s/step, %d tokens a step; "
            "step floor %.5f s (%s-bound), %.4f of the step; mean counters "
            "%s (a held expert %.1f tokens)"
            % (steps, window_s, step_s, tokens_per_step, floor_s, bound,
               floor_s / step_s, {n: "%.6g" % v for n, v in mean.items()},
               per_expert))
    peak = harness.memory_peak_bytes(devices)
    window_scopes = list(model.window_scopes)
    model.close()
    return {
        "correct": checks.ok(), "attempted": steps, "failed": bad,
        "window_start": t0, "reference_s": reference_s,
        "end_to_end": {"train_tokens_per_s": rate},
        "memory_peak_bytes": peak,
        "facts": dict(_floors(flops, cfg, rows, seq, mean["pairs_computed"],
                              ctx.peaks), **{
            "kind": "train", "dispatch_s": dispatch,
            "compiles_in_window": compiles, "trace": summary,
            "traced_steps": traced_steps, "step_floor_s": floor_s,
            "step_bound": bound, "memory_peak_bytes": peak,
            "chips": ctx.chips, "step_stats": mean,
            "window_attention_scopes": window_scopes,
            "expert_load_max_over_mean": mean["max_expert_tokens"] / max(
                per_expert, 1e-9)}),
    }


def gaps(got, want):
    """Every number ``correct`` compares, of ``got`` against ``want``."""
    per_layer = context_gaps(got, want)
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
            "routed_pairs_gap": routed_pairs_gap(got, want),
            "mixer_context_gap": max(per_layer),
            "context_gaps": per_layer}


def control_readings(ref, cfg, batches, w0, want, kind):
    """The control's readings in the program's place: the reference with
    its products in ``kind``."""
    return in_program_place(reference_readings(
        ref, cfg, batches, w0, ref.lowp_matmul(kind), 3), want)


# The faults the limits stand against, each planted in the plain reference
# (float32: no rounding beside it) and that run put in the program's
# place, as the control is.  name -> cfg -> cfg: what of the reference's
# configuration the fault changes (``fault`` is read by the reference).
FAULTS = {
    # a window layer reads one key beyond its edge: t - window <= s
    "window_one_key_wide": lambda cfg: dict(cfg, fault="window_one_key_wide"),
    # the first window layer reads every causal key
    "window_ignored_in_one_layer": lambda cfg: dict(
        cfg, fault="window_ignored_in_one_layer"),
    # the full layer rotates by the plain law: no blend by parts
    "full_plain_rotation": lambda cfg: dict(cfg, fault="full_plain_rotation"),
    # the attention factor on the query's cos and sin alone: a score
    # carries it once, not squared
    "factor_on_query_alone": lambda cfg: dict(
        cfg, fault="factor_on_query_alone"),
    # the ramp runs from c(beta_slow) down to c(beta_fast), untruncated
    "ramp_ends_swapped": lambda cfg: dict(cfg, fault="ramp_ends_swapped"),
    # query head j reads K/V head j % Hkv, not j // (H / Hkv)
    "kv_head_by_remainder": lambda cfg: dict(
        cfg, fault="kv_head_by_remainder"),
    # the chosen experts weigh by their softmax scores as they are
    "weights_not_renormalised": lambda cfg: dict(
        cfg, fault="weights_not_renormalised"),
    # the state comes back as it went
    "state_unchanged": lambda cfg: dict(cfg, learning_rate=0.0),
}


def fault_readings(ref, cfg, batches, w0, want, fault):
    """One planted fault's readings in the program's place."""
    return in_program_place(reference_readings(
        ref, FAULTS[fault](cfg), batches, w0, ref.f32_matmul, 3), want)


def checks_failed(got, want, limits):
    """The names of the checks that ``got``, in the program's place, fails
    against ``want``: through the comparison that decides ``correct``."""
    checks = harness.Checks(lambda line: None)
    compare(got, want, limits, checks)
    compare_kind(got, want, limits, checks)
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
        gc.collect()
        model.reset()
        model.set_weights(w0)
        feeds = [model.make_feed(b) for b in batches]
        prog = program_readings(model, feeds, w0, cfg["adam_beta1"], 3,
                                want["first_grad"])
        row.update(sound=gaps(prog, want),
                   sound_fails=checks_failed(prog, want, cfg["limits"]),
                   dropped_token_pairs=dropped_pairs(prog["stats"]),
                   stats=prog["stats"][0])
        out.append(row)
        ctx.log("readings %s" % row)
        # a seed's weights, first gradient, contexts and feeds are GBs on
        # the host: let them go before the next seed's are made
        del batches, w0, want, feeds, prog
    model.close()
    return out
