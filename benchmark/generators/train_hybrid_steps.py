"""Traffic generator ``train_hybrid_steps``: seeded whole-document batches
through one compiled training step of a decoder-hybrid-decoder language
model (state-space layers, differential attention under a window, a
cross-decoder that reads one layer's keys, values and scan output, a tied
head), a fresh host batch every step.

Parameters (the mix's data file) as ``train_loop_steps``': ``rows`` documents
of ``seq`` tokens a step — each drawn ``seq + 1`` ids long, so that the next
token exists at every position: no padding, no packing, no wrapped label —
ids uniform over the configuration's ``vocab_size`` (the held slice of the
vocabulary) from ``--seed``; ``pool`` distinct batches made in set-up and
cycled; the loss and the step's counters fetched every ``fetch_every`` steps
— each fetch point's arrays read to the host one fetch point later — and at
the window's end; ``profile_steps`` traced steps in a ``--trace 1`` run.
Every seed: the same shapes, other ids and other weights.

The weights are this generator's (``seeded_weights``): every matrix N(0,
``initializer_range``), and the initialisations the configuration's
``assumed`` states for the state-space layer and for lambda.

What ``correct`` compares (``train_steps``' six numbers and two of this
kind's own): the three losses, the first gradient leaf by leaf (norm of the
difference, gap of norms) — the tied table's is the lookup's plus the
head's, and the shared keys', values' and memory's pass through two layers
— the update after three steps (the two gaps of NORMS over the resolved
leaves: see ``ONE_SCALAR_LEAVES``); ``tied_table_grad_error`` — the norm of the
difference of the tied table's first gradient over the reference's norm (one
leaf of some sixty: the leaves' root mean square hardly sees it) — and
``scan_state_gap`` — the norm of the difference between the state-space
layer's final state and the plain reference's over the reference's norm,
the largest of the three steps.
"""

import functools
import gc
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.generators.train_loop_steps import (       # noqa: F401
    in_program_place, make_batches, read_out)
from benchmark.generators.train_steps import (
    _diff_norm, grad_error_norms, leaf_norms, rel_error_rms, rms_leaf_gap,
    worst_leaf_gap)
from benchmark.trace import reduce as trace_reduce


def _leaf(key, shape, init, cfg):
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "normal":
        return jax.random.normal(key, shape, jnp.float32) \
            * cfg["initializer_range"]
    if init == "lambda":
        return jax.random.normal(key, shape, jnp.float32) * 0.1
    if init == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[1] + 1, dtype=jnp.float32)), shape)
    if init == "dt_bias":
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        return step + jnp.log(-jnp.expm1(-step))    # softplus^-1(step)
    raise ValueError("unknown init %r" % (init,))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, items, rng):
    keys = jax.random.split(key, len(items))
    return {name: _leaf(keys[i], shape, init, {"initializer_range": rng})
            for i, (name, shape, init) in enumerate(items)}


def seeded_weights(spec, cfg, seed):
    """{name: float32 array on the host}, the same for the same seed: the
    one set of arrays the reference and the program both get."""
    items = tuple((n, tuple(s), i) for n, (s, i) in spec.items())
    return jax.device_get(_make(weights.seed_key(seed, 1), items,
                                float(cfg["initializer_range"])))


def reference_readings(ref, cfg, batches, w0, mm, steps):
    """What the plain reference gives over the first ``steps`` steps from
    the host weights ``w0``: each loss, each step's final states and
    counters, per-leaf norm of the first gradient, per-leaf norm of the
    parameters' change after the last step."""
    p = {n: jnp.asarray(v) for n, v in w0.items()}
    state, losses, states, stats, grad_norms = None, [], [], [], None
    for k in range(steps):
        b = {n: jnp.asarray(v, jnp.int32) for n, v in batches[k].items()}
        loss, s_t, st, grad = ref.loss_and_grad(
            p, b, cfg, cfg["reference_block_rows"], mm)
        losses.append(float(loss))
        states.append(np.asarray(s_t))
        stats.append(np.asarray(st).tolist())
        if k == 0:
            grad_norms = leaf_norms(grad)
            first_grad = jax.device_get(grad)
        state = ref.adam_init(p) if state is None else state
        p, state = ref.adam_step(p, grad, state, cfg)
        del grad
    del state
    update_norms = {n: float(_diff_norm(p[n], jnp.asarray(w0[n])))
                    for n in grad_norms}
    return {"losses": losses, "states": states, "stats": stats,
            "grad_norms": grad_norms, "update_norms": update_norms,
            "first_grad": first_grad}


def program_readings(model, feeds, w0, beta1, steps, want_grad, names):
    """The same readings from the program, through the window's own call:
    the first gradient is worked out from Adam's first moment after one
    step (m1 = (1 - beta1) g)."""
    losses, stats, states, grad_norms, grad_errors = [], [], [], None, None
    for k in range(steps):
        out = model.step(feeds[k])
        loss, st = read_out(out, names)
        losses.append(loss)
        stats.append(st)
        states.append(np.array(out[2], copy=True))
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
    return {"losses": losses, "states": states, "stats": stats,
            "grad_norms": grad_norms, "grad_errors": grad_errors,
            "update_norms": {n: float(v) for n, v in
                             jax.device_get(update_norms).items()}}


# A differential layer's four lambda vectors get ONE scalar's gradient each:
# d loss / d lam times a fixed vector (lam = exp(lq1 . lk1) - exp(lq2 . lk2)
# + lam0).  Where that scalar comes out near zero — in bf16 a1 - lam a2
# cancels most of both maps — its relative error has no bound, and Adam, which
# steps by m / sqrt(v), turns a changed sign into a change of the update's
# NORM of order one (my chip runs, PR 38: 0.0018 .. 0.163 over 30 sound runs,
# every large one a lambda vector).  A gap of norms over such a leaf says
# nothing of the program, so the two gaps of norms are taken over the other,
# resolved leaves; ``grad_rel_error_rms`` — the norm of the DIFFERENCE
# against the larger of the leaf's and the median leaf's norm, which a
# near-zero scalar cannot blow up — holds the lambda vectors as every leaf.
ONE_SCALAR_LEAVES = (".attn.lq1", ".attn.lk1", ".attn.lq2", ".attn.lk2")


def resolved(norms):
    return {n: v for n, v in norms.items()
            if not n.endswith(ONE_SCALAR_LEAVES)}


def norm_gaps(got, want):
    """{check: value} and the worst leaves of the two gaps of norms — of the
    first gradient and of the update — over the resolved leaves."""
    out, worst = {}, {}
    for what, key in (("grad_norm_gap", "grad_norms"),
                      ("update_norm_gap", "update_norms")):
        ref = resolved(want[key])
        out[what + "_rms"] = rms_leaf_gap(got[key], ref)
        out[what], worst[what] = worst_leaf_gap(got[key], ref)
    return out, worst


def compare(prog, want, limits, checks):
    """``train_steps.compare`` with the gaps of norms over the resolved
    leaves."""
    checks.add("grad_rel_error_rms",
               rel_error_rms(prog["grad_errors"], want["grad_norms"]),
               limits["grad_rel_error_rms"])
    for k, (a, b) in enumerate(zip(prog["losses"], want["losses"])):
        checks.add("loss_rel_gap.step%d" % (k + 1), abs(a - b) / abs(b),
                   limits["loss_rel_gap"],
                   note="program %.6f reference %.6f" % (a, b))
    gaps_, worst = norm_gaps(prog, want)
    for what, value in gaps_.items():
        checks.add(what, value, limits[what],
                   note=("worst leaf %s" % worst[what]) if what in worst
                   else "")


def scan_state_gap(got, want):
    """The final states' difference over the reference's norm, the largest
    of the steps (a reference state is [rows, E, N])."""
    return max(float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
               for a, b in zip(got["states"], want["states"]))


TIED = "tok_emb"


def tied_table_grad_error(got, want):
    """The norm of the difference of the tied table's first gradient — the
    lookup's scattered rows plus the head's product — over the
    reference's norm."""
    return got["grad_errors"][TIED] / want["grad_norms"][TIED]


def compare_state(prog, want, limits, checks):
    checks.add("tied_table_grad_error", tied_table_grad_error(prog, want),
               limits["tied_table_grad_error"])
    checks.add("scan_state_gap", scan_state_gap(prog, want),
               limits["scan_state_gap"],
               note="step 1 RMS: program %.6g reference %.6g" % (
                   float(np.sqrt(np.mean(np.square(prog["states"][0])))),
                   float(np.sqrt(np.mean(np.square(want["states"][0]))))))


def _floors(flops, cfg, rows, seq, peaks):
    return {
        "selective_scan_floor_s": flops.kernel_floor_seconds(
            flops.scan_flops(cfg, rows, seq),
            flops.scan_least_bytes(cfg, rows, seq), peaks),
        "hybrid_attention_floor_s": flops.kernel_floor_seconds(
            flops.attention_flops(cfg, rows, seq),
            flops.attention_least_bytes(cfg, rows, seq), peaks)}


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
    compare_state(prog, want, cfg["limits"], checks)
    ctx.log("step 1 counters: program %s; reference scan_state_rms %.6g "
            "memory_rms %.6g diff_lambda %.6g"
            % (prog["stats"][0], *want["stats"][0]))
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
               note="%d fetched, last %.4f" % (len(fetched), fetched[-1]))
    checks.add("compiles_in_window", float(compiles), 0.0)
    rate = steps * tokens_per_step / window_s
    step_s = window_s / steps
    mean = {n: sum(s[n] for s in stats) / len(stats) for n in names}
    floor_s, bound = flops.step_floor_seconds(cfg, rows, seq, ctx.peaks,
                                              ctx.chips)
    ctx.log("train: %d steps in %.3f s, %.5f s/step, %d tokens a step; "
            "step floor %.5f s (%s-bound), %.4f of the step; mean counters %s"
            % (steps, window_s, step_s, tokens_per_step, floor_s, bound,
               floor_s / step_s,
               {n: "%.6g" % v for n, v in mean.items()}))
    peak = harness.memory_peak_bytes(devices)
    model.close()
    return {
        "correct": checks.ok(), "attempted": steps, "failed": bad,
        "window_start": t0, "reference_s": reference_s,
        "end_to_end": {"train_tokens_per_s": rate},
        "memory_peak_bytes": peak,
        "facts": dict(_floors(flops, cfg, rows, seq, ctx.peaks), **{
            "kind": "train", "dispatch_s": dispatch,
            "compiles_in_window": compiles, "trace": summary,
            "traced_steps": traced_steps, "step_floor_s": floor_s,
            "step_bound": bound, "memory_peak_bytes": peak,
            "chips": ctx.chips, "step_stats": mean}),
    }


def gaps(got, want):
    """Every number ``correct`` compares, of ``got`` against ``want``."""
    return {"grad_rel_error_rms": rel_error_rms(got["grad_errors"],
                                                want["grad_norms"]),
            "loss_rel_gap": max(abs(a - b) / abs(b) for a, b in zip(
                got["losses"], want["losses"])),
            "scan_state_gap": scan_state_gap(got, want),
            "tied_table_grad_error": tied_table_grad_error(got, want),
            **norm_gaps(got, want)[0]}


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
    # the window one block of reference rows too wide
    "window_off_by_block": lambda cfg: dict(
        cfg, sliding_window=cfg["sliding_window"]
        + cfg["reference_block_rows"]),
    # plain attention: the second softmax map never subtracted
    "lambda_dropped": lambda cfg: dict(cfg, fault="lambda_dropped"),
    # the memory taken AFTER the gate silu(z)
    "memory_after_gate": lambda cfg: dict(cfg, fault="memory_after_gate"),
    # cross-attention over keys and values of its OWN input
    "cross_own_keys": lambda cfg: dict(cfg, fault="cross_own_keys"),
    # the convolution's taps one step ahead: the last reads the future
    "conv_tap_ahead": lambda cfg: dict(cfg, fault="conv_tap_ahead"),
    # the head's use of the table gives it no gradient
    "head_untied": lambda cfg: dict(cfg, fault="head_untied"),
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
    compare_state(got, want, limits, checks)
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
