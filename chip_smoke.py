"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments, run from the root of the checkout on a machine
with a TPU (``python chip_smoke.py``).  It drives the repo's main path
once at the full width of Transformer-base — a trainer that takes a few
steps through ``fluid.Executor`` (and ``fluid.ParallelExecutor`` when four
chips are visible), every selectable Pallas kernel through Mosaic, and the
serving engine answering a few requests — and checks what comes out by the
repo's own means.  Weights are random from a seed; all data is generated
from seeds; nothing downloads.

It never runs on the CPU: no TPU, or a device that is not in the peaks
table, is an error (exit code 2, no result line).  A phase that fails
raises — nothing is caught and carried on — so standard output carries a
result only when every phase passed.  That result is the last (and only)
line of standard output, one JSON object of exactly this shape, the device
as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything else goes to standard error: progress, and before the result a
``[chip_smoke] report {...}`` line with the versions, the cache directory,
which kernel bodies were lowered, and per phase ``seconds`` /
``compile_seconds`` / persistent-cache hits, ending with ``"claim": null``.

A CPU run of the *tests* yields correctness and counts; the step times this
script prints are information labelled with the device, not a benchmark.
"""

import functools
import gc
import importlib.metadata
import json
import math
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import compile_cache, recordio, serving
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import transformer as tfm
from paddle_tpu.monitor import program_profile
from paddle_tpu.ops import loss as loss_ops
from paddle_tpu.ops import attention, attention_xla, moe, sparse_select
from paddle_tpu.ops import state_space
from paddle_tpu.ops.activation import rotary_tables
from paddle_tpu.ops.pallas import packed_attention as pa
from paddle_tpu.ops.pallas import selective_scan
from paddle_tpu.ops.pallas import streamed_attention as sa
from paddle_tpu.ops.pallas import topk_select
from paddle_tpu.parallel import make_mesh

# Transformer-base, the scored configuration of bench.py's transformer
# rung: full width, full depth.  ``REF_LAYERS`` is the depth of the two
# auxiliary builds (the float32 TPU-vs-CPU reference and the T=4096 ring
# step) — same widths, depth cut to keep the script inside its time limit.
WIDTH = dict(n_head=8, d_model=512, d_inner=2048)
VOCAB, SEQ, BATCH, LAYERS = 32000, 64, 256, 6
REF_LAYERS, REF_BATCH = 2, 8
TRAIN_STEPS, MESH_STEPS = 12, 6
RING_SEQ = 4096
# the long-document cell's selection: 2048 of 8192 keys a query, scored by
# 16 indexer heads of 64
SELECT_SEQ, SELECT_K, INDEX_HEADS, INDEX_DIM = 8192, 2048, 16, 64
# plain heads the streamed kernels' check runs: more than a grid step serves
PLAIN_HEADS = 16
SEED = 90
# the parameter whose movement (with both of its Adam moments) is checked
WATCHED_PARAM = "dec_logits.w_0"

# serving: the decoder LM at the same widths
SERVE = dict(vocab_size=VOCAB, max_len=1024, slots=16, n_layer=LAYERS, **WIDTH)
PAGE, MAX_NEW = 16, 32
PROMPT_LENS = (5, 12, 20, 33, 33, 47, 60, 90)   # two share one full page
BUCKETS = (32, 128)                             # page-aligned prefill pads

# stated tolerances (in brackets: what the v5e bring-up runs measured)
TOL_FIRST_LOSS = 1.0        # |loss_1 - ln(vocab)| at random init [0.017]
TOL_F32_TPU_VS_CPU = 1e-3   # float32 first-step loss, chip vs host [2e-5]
TOL_MESH_VS_1CHIP = 1e-3    # same seed, (2,2) mesh vs one chip [9e-6]
TOL_RING_VS_PLAIN = 1e-3    # ring attention vs the plain step [6e-6]
TOL_SERVE_LOGITS = 1e-2     # paged vs fixed logits, x max|logit| [3e-3 abs]
# kernel vs XLA reference, relative to max|reference|: matmul-bearing
# kernels see the MXU's bf16 passes even for float32 operands
TOL_KERNEL = {"matmul": 2e-2, "float32": 1e-4}


def log(msg):
    # progress goes to stderr: standard output carries the result line only
    print("[chip_smoke] " + msg, file=sys.stderr, flush=True)


def result_line(devices, ok=True):
    """The one line standard output carries: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``), the device as JAX reports it."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(devices[0].platform),
                   "kind": str(devices[0].device_kind),
                   "count": len(devices)}})


def die(msg):
    """No device to run on: message on stderr, exit code 2, no result."""
    print("chip_smoke: " + msg, file=sys.stderr, flush=True)
    sys.exit(2)


# ---------------------------------------------------------------------------
# the Transformer-base train program and its seeded feeds
# ---------------------------------------------------------------------------

def build_transformer(seq, n_layer, dropout, amp, vocab=VOCAB):
    """(main, startup, loss) for the NMT Transformer with Adam +
    noam_decay, optionally under bf16 mixed precision."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        src = fluid.layers.data("src_word", shape=[1], dtype="int64",
                                lod_level=1)
        tgt = fluid.layers.data("tgt_word", shape=[1], dtype="int64",
                                lod_level=1)
        lbl = fluid.layers.data("lbl_word", shape=[1], dtype="int64",
                                lod_level=1)
        loss, _ = tfm.transformer(src, tgt, lbl, seq, seq, vocab, vocab,
                                  n_layer=n_layer, dropout_rate=dropout,
                                  **WIDTH)
        opt = fluid.optimizer.Adam(
            learning_rate=fluid.layers.noam_decay(WIDTH["d_model"], 4000),
            beta1=0.9, beta2=0.997, epsilon=1e-9)
        (mixed_precision.decorate(opt) if amp else opt).minimize(loss)
    return main, startup, loss


def train_feed(step, batch, seq, vocab=VOCAB):
    rng = np.random.RandomState(1000 + step)
    src = rng.randint(2, vocab, (batch, seq, 1)).astype("int64")
    tgt = rng.randint(2, vocab, (batch, seq, 1)).astype("int64")
    src_len = rng.randint(seq // 2, seq + 1, (batch,)).astype("int32")
    tgt_len = rng.randint(seq // 2, seq + 1, (batch,)).astype("int32")
    return {"src_word": src, "src_word@LEN": src_len,
            "tgt_word": tgt, "tgt_word@LEN": tgt_len,
            "lbl_word": np.roll(tgt, -1, axis=1), "lbl_word@LEN": tgt_len}


def assert_on_device(scope, devices):
    """Every state array in the scope lives on exactly ``devices``."""
    want = set(devices)
    for name, val in scope.items():
        if not isinstance(val, jax.Array):
            raise AssertionError("%s is a %s, not a device array"
                                 % (name, type(val).__name__))
        if set(val.devices()) != want:
            raise AssertionError("%s lives on %s, expected %s"
                                 % (name, val.devices(), want))


def loss_hex(values):
    return [np.float32(v).tobytes().hex() for v in values]


def seeded_steps(run, loss, steps, batch, seq):
    """``steps`` seeded train steps through ``run(feed=, fetch_list=)``,
    each ended by fetching the loss.  Returns (losses, step seconds, what
    was lowered or compiled AFTER the first step — the warm-up)."""
    losses, secs = [], []

    def step(i):
        t0 = time.perf_counter()
        (val,) = run(feed=train_feed(i, batch, seq), fetch_list=[loss])
        losses.append(float(np.asarray(val).ravel()[0]))
        secs.append(time.perf_counter() - t0)
    step(0)
    with compile_cache.count_compiles() as new:
        for i in range(1, steps):
            step(i)
    return losses, secs, new()


def run_train(place, main, startup, loss, steps, batch, seq):
    """Startup + ``steps`` seeded steps on a fresh scope.  Returns
    (losses, step seconds, compile counts after warm-up, scope, snapshot
    of the watched parameter and its optimizer state after startup)."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        # startup runs on its own executor: each run() folds the
        # executor's step counter into the PRNG key, and the mesh phase
        # (whose ParallelExecutor never runs the startup) must see the
        # same dropout masks at the same step
        fluid.Executor(place).run(startup)
        exe = fluid.Executor(place)
        init = {n: np.array(v, copy=True) for n, v in scope.items()
                if n.startswith(WATCHED_PARAM)}
        losses, secs, new = seeded_steps(
            functools.partial(exe.run, main), loss, steps, batch, seq)
    return losses, secs, new, scope, init


def assert_no_new_compiles(new, what):
    for k in ("lowerings", "jax_lowerings", "jax_backend_compiles"):
        if new[k]:
            raise AssertionError("%s: %d new %s after warm-up (%s)"
                                 % (what, new[k], k, new))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device():
    devs = jax.devices()
    if devs[0].platform != "tpu":
        die("no TPU: jax.devices() = %s. This script never runs on the "
            "CPU; run it on the chip (chiprun -- python chip_smoke.py)."
            % (devs,))
    kind = devs[0].device_kind
    peaks = program_profile.DEVICE_PEAKS.get(kind)
    if peaks is None:
        die("device_kind %r is not in program_profile.DEVICE_PEAKS (%s): "
            "add its published peaks before measuring on it"
            % (kind, sorted(program_profile.DEVICE_PEAKS)))
    log("device: %d x %s, peaks %s; recordio native codec: %s"
        % (len(devs), kind, peaks, recordio.native_available()))
    return {"peaks": peaks}


def kernel_bodies(op_prefix):
    return {k: n for k, n in compile_cache.stats()["kernel_bodies"].items()
            if k.startswith(op_prefix)}


def attention_bodies():
    return kernel_bodies("fused_attention")


def bodies_since(before, op_prefix):
    """The bodies ops of ``op_prefix`` lowered to since ``before``."""
    return {k: n - before.get(k, 0)
            for k, n in kernel_bodies(op_prefix).items()
            if n != before.get(k, 0)}


def assert_packed(before, attentions, what):
    """Every attention of the program traced since ``before`` took the
    packed short-sequence kernel, forward and gradient, and no other
    body."""
    got = bodies_since(before, "fused_attention")
    want = {"fused_attention:packed": attentions,
            "fused_attention_grad:packed": attentions}
    if got != want:
        raise AssertionError("%s: attention bodies %s, expected %s"
                             % (what, got, want))


def phase_train_1chip():
    place = fluid.TPUPlace(0)
    dev = place.jax_device()
    main, startup, loss = build_transformer(SEQ, LAYERS, 0.1, amp=True)
    bodies = attention_bodies()
    losses, secs, new, scope, init = run_train(
        place, main, startup, loss, TRAIN_STEPS, BATCH, SEQ)
    log("train_1chip losses: %s" % " ".join("%.4f" % v for v in losses))
    # encoder self, decoder self, decoder cross: three a layer
    assert_packed(bodies, 3 * LAYERS, "train_1chip")
    assert_on_device(scope, [dev])
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError("non-finite loss: %s" % losses)
    if abs(losses[0] - math.log(VOCAB)) > TOL_FIRST_LOSS:
        raise AssertionError("first loss %.4f not within %.1f of ln(%d)"
                             % (losses[0], TOL_FIRST_LOSS, VOCAB))
    # a parameter and both of its Adam moments moved and are finite
    moved = sorted(init)
    if not (any("moment1" in n for n in moved)
            and any("moment2" in n for n in moved)
            and WATCHED_PARAM in moved):
        raise AssertionError("parameter/moment vars not found: %s" % moved)
    for name in moved:
        end = np.asarray(scope.var(name), dtype=np.float32)
        if not np.isfinite(end).all():
            raise AssertionError("%s is not finite after training" % name)
        if np.array_equal(end, init[name].astype(np.float32)):
            raise AssertionError("%s did not change in %d steps"
                                 % (name, TRAIN_STEPS))
    assert_no_new_compiles(new, "train_1chip")
    # the seeded-trajectory contract: the same seeded steps from a fresh
    # scope, in the same process, give the bit-identical loss sequence
    del scope
    again, _, new2, scope2, _ = run_train(
        place, main, startup, loss, TRAIN_STEPS, BATCH, SEQ)
    if loss_hex(again) != loss_hex(losses):
        raise AssertionError("seeded rerun diverged:\n%s\n%s"
                             % (losses, again))
    assert_no_new_compiles(new2, "train_1chip rerun")
    del scope2
    median = float(np.median(secs[1:]))
    log("train_1chip: median step %.4f s on %s (ended by fetching the "
        "loss; information, not a metric)" % (median, dev.device_kind))
    f32 = f32_tpu_vs_cpu(place)
    return {"losses": losses, "median_step_seconds": round(median, 5),
            "step_device": dev.device_kind, "f32_tpu_vs_cpu": f32}


def f32_tpu_vs_cpu(place):
    """A float32, dropout-0, batch-8 build of the same widths: one step on
    the chip and one on the host CPU, from identical initial values, must
    give the same loss."""
    main, startup, loss = build_transformer(SEQ, REF_LAYERS, 0.0, amp=False)
    cpu = fluid.CPUPlace()
    if cpu.jax_device().platform != "cpu":
        raise AssertionError("CPUPlace resolved to %s" % cpu.jax_device())
    seed_scope = fluid.Scope()
    with fluid.scope_guard(seed_scope):
        fluid.Executor(cpu).run(startup)
    init = {n: np.array(v, copy=True) for n, v in seed_scope.items()}
    feed = train_feed(0, REF_BATCH, SEQ)
    out = {}
    for name, where in (("tpu", place), ("cpu", cpu)):
        scope = fluid.Scope()
        for n, v in init.items():
            scope.set_var(n, v)
        with fluid.scope_guard(scope):
            (val,) = fluid.Executor(where).run(main, feed=feed,
                                               fetch_list=[loss])
        out[name] = float(np.asarray(val).ravel()[0])
    log("f32 first-step loss: tpu %.6f cpu %.6f" % (out["tpu"], out["cpu"]))
    if not abs(out["tpu"] - out["cpu"]) <= TOL_F32_TPU_VS_CPU:
        raise AssertionError("float32 first-step loss differs: %s (tol %g)"
                             % (out, TOL_F32_TPU_VS_CPU))
    return out


# -- kernels ------------------------------------------------------------------

def mosaic_jit(fn, *args):
    """jit ``fn`` and prove its lowered module carries the Mosaic custom
    call — a quiet hand-over to XLA cannot pass as the kernel."""
    jitted = jax.jit(fn)
    if "tpu_custom_call" not in jitted.lower(*args).as_text():
        raise AssertionError("%s lowered without a tpu_custom_call" % fn)
    return jitted


def close(got, want, tol, what):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError("%s: non-finite kernel output" % what)
    err = float(np.max(np.abs(got - want)))
    bound = tol * max(1.0, float(np.max(np.abs(want))))
    if err > bound:
        raise AssertionError("%s: max error %g over bound %g"
                             % (what, err, bound))
    return err


def check_kernel(name, kernel, reference, args, diff, tol):
    """Forward (and backward over ``args[:diff]``) of ``kernel`` compiled
    by Mosaic against ``reference`` compiled by XLA."""
    errs = {"fwd": close(mosaic_jit(kernel, *args)(*args),
                         jax.jit(reference)(*args), tol, name + " fwd")}
    if diff:
        out = jax.eval_shape(reference, *args)
        ct = jax.random.normal(jax.random.key(99), out.shape, jnp.float32)

        def scalar(f):
            return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * ct)
        argnums = tuple(range(diff))
        got = mosaic_jit(jax.grad(scalar(kernel), argnums), *args)(*args)
        want = jax.jit(jax.grad(scalar(reference), argnums))(*args)
        errs["bwd"] = [close(g, w, tol, "%s bwd[%d]" % (name, i))
                       for i, (g, w) in enumerate(zip(got, want))]
    log("kernel %s: %s" % (name, errs))
    return errs


def same_bits_in_every_body(name, args, words, window=None):
    """The streamed kernels at the rules' own heads a grid step — the fused
    backward's own beside the forward's — against ONE head a step, and the
    fused backward against the dQ and dK/dV kernels: out, log-sum-exp, dQ,
    dK and dV bit for bit where a key block's additions arrive in the same
    order (plain heads: a head's arithmetic does not depend on which heads
    share its step; a group whose heads share a step in both bodies), dK
    and dV to bf16's last place where a group's heads are split over steps
    in one body alone."""
    ct = normal(7, args[0].shape[:3] + args[2].shape[3:], jnp.bfloat16)

    def kernels(q, k, v, ct):
        out, lse = sa.forward(q, k, v, words, True, None, False, window)
        return (out, lse) + sa.backward(q, k, v, words, out, lse, ct, True,
                                        None, False, window)

    def run(forwards, backwards):
        rules = sa._heads_per_step, sa._fused_heads_per_step
        sa._heads_per_step = forwards or rules[0]
        sa._fused_heads_per_step = backwards or rules[1]
        try:
            return mosaic_jit(lambda *a: kernels(*a), *args, ct)(*args, ct)
        finally:
            sa._heads_per_step, sa._fused_heads_per_step = rules
    body, heads = sa.grad_step(*args)
    if body != "streamed_fused":
        raise AssertionError("%s: the backward's body is %s" % (name, body))
    got = run(None, None)
    plain = args[0].shape[1] == args[1].shape[1]
    others = {"the two kernels": run(None, lambda *a: None)}
    if plain:
        others["one head a step"] = run(lambda *a: (1, 1), lambda *a: (1, 1))
    exact = plain or heads == sa.step_heads(*args)
    for other, want in others.items():
        for what, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if what in ("dk", "dv") and not exact:
                close(a, b, 2.0 ** -7, "%s %s against %s" % (name, what,
                                                              other))
            elif not np.array_equal(a, b):
                raise AssertionError("%s: %s differs from %s"
                                     % (name, what, other))
    return "bit-equal" if exact else "dq bit-equal"


def plain_heads_through_the_op(h, t, dk, dv, rope_theta=None):
    """``h`` plain heads through ``fused_attention`` and its gradient op in
    a program: the trace takes the streamed kernels, forward and backward,
    — the backward by the fused kernel — and says how many heads a grid
    step serves (``streamed_step:<K/V heads>x<query heads of each>``
    forward, ``streamed_grad_step:..`` backward) — several, and not all
    ``h``.  With
    ``rope_theta`` the program turns q and k by ``rotary_embedding``
    (rotate-half, the whole head) before the kernels, as a decoder block
    does.  Out and the three gradients against the XLA body."""
    def rotate(x):
        return x if rope_theta is None else to_bhtd(
            fluid.layers.rotary_embedding(to_bhtd(x), theta=rope_theta))

    def to_bhtd(x):
        return fluid.layers.transpose(x, perm=[0, 2, 1, 3])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, k, v = (fluid.layers.data(n, shape=[h, t, w], dtype="float32")
                   for n, w in (("q", dk), ("k", dk), ("v", dv)))
        for x in (q, k, v):
            x.stop_gradient = False
        o = fluid.layers.fused_attention(rotate(q), rotate(k), v, causal=True,
                                         scale=dk ** -0.5)
        fluid.append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(o, o)))
    args = [normal(i, (1, h, t, w), jnp.float32)
            for i, w in enumerate((dk, dk, dv))]
    noted = ("fused_attention", "streamed_step", "streamed_grad_step")
    before = {p: kernel_bodies(p) for p in noted}
    got = fluid.Executor(fluid.TPUPlace(0)).run(
        main, feed=dict(zip("qkv", map(np.asarray, args))),
        fetch_list=[o, "q@GRAD", "k@GRAD", "v@GRAD"])
    bodies, step, grad_step = (bodies_since(before[p], p) for p in noted)
    kh, gh = sa.step_heads(*args)
    body, heads = sa.grad_step(*args)
    if bodies != {"fused_attention:streamed": 1,
                  "fused_attention_grad:streamed_fused": 1} \
            or step != {"streamed_step:%dx%d" % (kh, gh): 1} \
            or grad_step != {"streamed_grad_step:%dx%d" % heads: 1} \
            or body != "streamed_fused" or not 1 < kh < h:
        raise AssertionError("plain heads through the op: bodies %s, step "
                             "%s, backward %s" % (bodies, step, grad_step))

    def turned(x):
        if rope_theta is None:
            return x
        cos, sin = rotary_tables(t, dk, rope_theta)
        half = jnp.concatenate([-x[..., dk // 2:], x[..., :dk // 2]], -1)
        return x * cos + half * sin

    def reference(q, k, v):
        return attention_xla.reference_attention(
            turned(q), turned(k), v, None, None, True, 0.0, dk ** -0.5)
    want = (jax.jit(reference)(*args),) + jax.jit(jax.grad(
        lambda *a: jnp.sum(reference(*a) ** 2), (0, 1, 2)))(*args)
    errs = [close(g, w, TOL_KERNEL["matmul"], "plain heads through the op")
            for g, w in zip(got, want)]
    log("plain heads through the op (%d x %d/%d, T %d, rotary %s): %s, "
        "backward %s %s, errors %s" % (h, dk, dv, t, rope_theta, step, body,
                                       grad_step, errs))
    return sorted(step)[0] + " " + sorted(grad_step)[0]


def latent_projections_through_the_op(h, t, nope, rope, dv, rope_theta):
    """A latent block's attention over its projections' own outputs — Q [1,
    t, h * (nope + rope)], the key/value projection's [1, t, h * (nope +
    dv)], the shared key part [1, t, rope] — through ``fused_attention`` and
    its gradient op in a program: the trace takes the streamed kernels in
    place, forward and the fused backward, several heads a grid step and not
    all ``h``.  Out, dQ, dKV and dKShared against the op's XLA body, which
    is the composition a model wrote before (split, rotate, broadcast, join,
    transpose, the plain attention)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, kv, kr = (fluid.layers.data(n, shape=[t, w], dtype="float32")
                     for n, w in (("q", h * (nope + rope)),
                                  ("kv", h * (nope + dv)), ("kr", rope)))
        for x in (q, kv, kr):
            x.stop_gradient = False
        o = fluid.layers.fused_attention(
            q, kv, causal=True, scale=(nope + rope) ** -0.5, n_head=h,
            v_dim=dv, k_shared=kr, rope_theta=rope_theta)
        fluid.append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(o, o)))
    args = [normal(i, (1, t, w), jnp.float32) * 0.5
            for i, w in enumerate((h * (nope + rope), h * (nope + dv), rope))]
    noted = ("fused_attention", "streamed_step", "streamed_grad_step")
    before = {p: kernel_bodies(p) for p in noted}
    got = fluid.Executor(fluid.TPUPlace(0)).run(
        main, feed=dict(zip(("q", "kv", "kr"), map(np.asarray, args))),
        fetch_list=[o, "q@GRAD", "kv@GRAD", "kr@GRAD"])
    bodies, step, grad_step = (bodies_since(before[p], p) for p in noted)
    forward, backward = sa.in_place_step(args[0], args[1], h, dv)
    if bodies != {"fused_attention:streamed_inplace": 1,
                  "fused_attention_grad:streamed_fused_inplace": 1} \
            or step != {"streamed_step:%dx1" % forward: 1} \
            or grad_step != {"streamed_grad_step:%dx1" % backward: 1} \
            or not 1 < forward < h:
        raise AssertionError("latent projections through the op: bodies %s, "
                             "step %s, backward %s"
                             % (bodies, step, grad_step))
    attrs = {"causal": True, "n_head": h, "v_dim": dv,
             "scale": (nope + rope) ** -0.5}
    if rope_theta is not None:
        attrs["rope_theta"] = rope_theta

    def reference(q, kv, kr):
        return attention._in_place_reference(
            {"Q": [q], "K": [kv], "KShared": [kr]}, attrs, None, None, True,
            0.0, attrs["scale"])[0]
    want = (jax.jit(reference)(*args),) + jax.jit(jax.grad(
        lambda *a: jnp.sum(reference(*a) ** 2), (0, 1, 2)))(*args)
    errs = [close(g, w, TOL_KERNEL["matmul"],
                  "latent projections through the op")
            for g, w in zip(got, want)]
    log("latent projections through the op (%d x [%d | %d] / %d, T %d, "
        "rotary %s): %s, backward %s, errors %s"
        % (h, nope, rope, dv, t, rope_theta, step, grad_step, errs))
    return sorted(step)[0] + " " + sorted(grad_step)[0]


def grouped_projections_through_the_op(h, hk, t, d, window, **rope):
    """A grouped-head block's attention over its three projections' own
    outputs — Q [1, t, h * d], K and V [1, t, hk * d] — through
    ``fused_attention`` and its gradient op in a program, the rotation
    (``rope``: the layer's ``rope_*`` keywords) inside the op: the trace
    takes the streamed kernels in place, forward and the fused backward.
    Out, dQ, dK and dV against the op's XLA body, which is the composition
    a model wrote before (view as heads, rotate, transpose, the plain
    attention)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, k, v = (fluid.layers.data(n, shape=[t, w], dtype="float32")
                   for n, w in (("q", h * d), ("k", hk * d), ("v", hk * d)))
        for x in (q, k, v):
            x.stop_gradient = False
        o = fluid.layers.fused_attention(
            q, k, v, causal=True, scale=d ** -0.5, n_head=h, window=window,
            **rope)
        fluid.append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(o, o)))
    args = [normal(i, (1, t, w), jnp.float32) * 0.5
            for i, w in enumerate((h * d, hk * d, hk * d))]
    before = kernel_bodies("fused_attention")
    got = fluid.Executor(fluid.TPUPlace(0)).run(
        main, feed=dict(zip("qkv", map(np.asarray, args))),
        fetch_list=[o, "q@GRAD", "k@GRAD", "v@GRAD"])
    bodies = bodies_since(before, "fused_attention")
    if bodies != {"fused_attention:streamed_inplace": 1,
                  "fused_attention_grad:streamed_fused_inplace": 1}:
        raise AssertionError("grouped projections through the op: bodies %s"
                             % bodies)
    op = next(op for op in main.global_block().ops
              if op.type == "fused_attention")
    law = attention._grouped_parts(
        {"Q": [args[0]], "K": [args[1]], "V": [args[2]]}, op.attrs)[-1]

    def reference(q, k, v):
        return attention._merge_heads(attention_xla.reference_attention(
            *attention._split_heads(q, k, v, h, hk, law), None, None, True,
            0.0, d ** -0.5, None, False, window))
    want = (jax.jit(reference)(*args),) + jax.jit(jax.grad(
        lambda *a: jnp.sum(reference(*a) ** 2), (0, 1, 2)))(*args)
    errs = [close(g, w, TOL_KERNEL["matmul"],
                  "grouped projections through the op")
            for g, w in zip(got, want)]
    step = "%dx%d %dx%d" % (sa.step_heads(*args, h)
                            + sa.grad_step(*args, h)[1])
    log("grouped projections through the op (%d / %d x %d, T %d, window %s, "
        "%s): %s, errors %s" % (h, hk, d, t, window, rope, step, errs))
    return step


def normal(seed, shape, dtype):
    return jax.random.normal(jax.random.key(seed), shape,
                             jnp.float32).astype(dtype)


def grouped_experts_through_the_op(name, total, chunks):
    """``moe_expert_ffn`` and its gradient at the long-document cell's shape
    — 8192 tokens of 2048, eight of ``total`` experts a token, the 16 held
    ones of width 768 at tiles of 640 rows, bf16 — through the op's compute
    as a TPU trace lowers it (the grouped kernels: the note says so) against
    the loop body on the same chip: ``Out``, ``Pairs`` and the five
    gradients.  Of 128 experts ~8.2 k pairs are held, the cell's own load,
    ONE chunk of tiles; of 32, ~32.8 k pairs, which the kernels walk in
    ``chunks`` chunks, each after the first continuing what the chunks
    before it left of ``Out``, ``dX`` and an expert's weight gradients."""
    from paddle_tpu.ops.pallas import grouped_experts
    from paddle_tpu.registry import ComputeContext

    n, d, f, held, k, tile = 8192, 2048, 768, 16, 8, 640
    idx = jnp.argsort(jax.random.uniform(jax.random.key(20), (n, total)),
                      axis=-1)[:, :k].astype(jnp.int32)
    layout = moe.dispatch_layout(idx, 0, held, tile)
    ins = {"X": normal(21, (n, d), jnp.bfloat16),
           "TopkWeight": jax.random.uniform(jax.random.key(22), (n, k)),
           "GRAD::Out": normal(23, (n, d), jnp.bfloat16)}
    for i, (slot, shape) in enumerate((("Gate", (d, f)), ("Up", (d, f)),
                                       ("Down", (f, d)))):
        ins[slot] = (0.02 * normal(24 + i, (held,) + shape, jnp.float32)
                     ).astype(jnp.bfloat16)
    ins = {slot: [v] for slot, v in ins.items()}
    ins.update({slot: [layout[slot]] for slot in moe._LAYOUT})

    def both(platform):
        ctx = ComputeContext(key=jax.random.key(0), platform=platform)
        return lambda ins: (moe._ffn_compute(ins, {"tile": tile}, ctx, 0),
                            moe._ffn_grad_compute(ins, {"tile": tile}, ctx,
                                                  0))
    before = kernel_bodies("moe_expert_ffn")
    got, got_grad = mosaic_jit(both("tpu"), ins)(ins)
    bodies = bodies_since(before, "moe_expert_ffn")
    if set(bodies) != {"moe_expert_ffn:grouped",
                       "moe_expert_ffn_grad:grouped"}:      # no "loop"
        raise AssertionError("the expert ops lowered to %s" % bodies)
    want, want_grad = jax.jit(both("cpu"))(ins)      # the loop: no Pallas
    pairs, live = float(got["Pairs"][0]), int(layout["NumTiles"][0])
    if pairs != float(want["Pairs"][0]) or pairs != float(
            jnp.sum(layout["Counts"])) \
            or abs(pairs / (n * k * held / total) - 1) > 0.15:
        raise AssertionError("%s computed %s pairs" % (name, pairs))
    if -(-live // grouped_experts._chunk_tiles(held)) != chunks:
        raise AssertionError("%s: %d live tiles are not %d chunks"
                             % (name, live, chunks))
    tol = TOL_KERNEL["matmul"]
    errs = {"fwd": close(got["Out"], want["Out"], tol, name + " fwd"),
            "bwd": [close(got_grad[s][0], want_grad[s][0], tol,
                          "%s %s" % (name, s))
                    for s in ("GRAD::X", "GRAD::TopkWeight", "GRAD::Gate",
                              "GRAD::Up", "GRAD::Down")],
            "pairs": pairs, "tiles": live, "bodies": bodies}
    log("kernel %s: %s" % (name, errs))
    return errs


def selective_scan_kernels(b, t, e, n, chunk=64):
    """A state-space layer's scan at the hybrid cell's shape — ``[b, t, e]``
    channels, ``n`` states a channel, float32 — through the chunked Pallas
    kernels against the op's XLA body: the output, the final state, the
    chunks' starting states, and all six gradients."""
    if not selective_scan.supported((b, t, e), n, chunk):
        raise AssertionError("selective_scan.supported rejects the shape")
    delta = jax.nn.softplus(normal(1, (b, t, e), jnp.float32) - 3.0)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (e, n))
    args = (delta, normal(2, (b, t, e), jnp.float32), a,
            normal(3, (b, t, n), jnp.float32),
            normal(4, (b, t, n), jnp.float32),
            normal(5, (e,), jnp.float32))
    dy = normal(6, (b, t, e), jnp.float32)

    def kernels(*args):
        y, state, starts = selective_scan.forward(*args[:6], chunk, False)
        return (y, state, starts) + selective_scan.backward(
            *args[:6], starts, args[6], chunk, False)

    def body(*args):
        (y, state, starts), vjp = jax.vjp(
            lambda *a: state_space.scan_xla(*a, chunk), *args[:6])
        return (y, state, starts) + vjp((args[6], jnp.zeros_like(state),
                                         jnp.zeros_like(starts)))
    kernel = mosaic_jit(kernels, *args, dy)
    got, want = kernel(*args, dy), jax.jit(body)(*args, dy)
    names = ("y", "state", "starts", "d_delta", "dx", "dA", "dB", "dC", "dD")
    errs = {what: close(g, w, TOL_KERNEL["float32"], "selective_scan " + what)
            for what, g, w in zip(names, got, want)}

    def ms(fn):
        jax.block_until_ready(fn(*args, dy))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(*args, dy)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / 5 * 1e3, 3)
    errs["ms_per_call"] = {"chunked": ms(kernel)}
    log("kernel selective_scan [%d, %d, %d] x %d: %s (ms: forward + backward, "
        "host clock over 5 calls; information, not a metric)"
        % (b, t, e, n, errs))
    return errs


def gated_delta_rule_kernels(b, t, h, d, chunk=64):
    """The delta-attention recurrence at the delta-attention cell's heads —
    ``[b, t, h, d]`` bf16 operands as a mixed-precision step hands them over
    — through both ops' compute as a TPU trace lowers it (the kernels of
    ``ops/pallas/gated_delta_rule.py``: the note says so) against the XLA
    body on the same chip: ``Out``, ``State``, ``Starts`` and the nine
    gradients by relative norm (the float32 ones to three bf16 passes'
    rounding, the bf16 ones to their last bit)."""
    from paddle_tpu.ops import gated_delta_rule as gdr
    from paddle_tpu.registry import ComputeContext

    bf = jnp.bfloat16
    ins = {"Q": normal(1, (b, t, h, d), bf), "K": normal(2, (b, t, h, d), bf),
           "V": normal(3, (b, t, h, d), bf), "G": normal(4, (b, t, h, d), bf),
           "Beta": jax.nn.sigmoid(normal(5, (b, t, h), jnp.float32)),
           "ALog": 0.5 * normal(6, (h,), jnp.float32),
           "DtBias": 0.5 * normal(7, (h, d), jnp.float32),
           "OutGate": normal(8, (b, t, h, d), bf),
           "OutNorm": 1 + 0.1 * normal(9, (d,), jnp.float32)}
    ins = {slot: [v] for slot, v in ins.items()}
    dout = normal(10, (b, t, h, d), jnp.float32)
    attrs = {"chunk": chunk, "scale": d ** -0.5, "epsilon": 1e-5}

    def both(platform):
        ctx = ComputeContext(key=jax.random.key(0), platform=platform)

        def run(ins, dout):
            out = gdr._compute(ins, attrs, ctx, 0)
            return out, gdr._grad_compute(dict(
                ins, **{"Out::Starts": [out["Starts"]],
                        "GRAD::Out": [dout]}), attrs, ctx, 0)
        return run
    before = kernel_bodies("gated_delta_rule")
    got, got_grad = mosaic_jit(both("tpu"), ins, dout)(ins, dout)
    bodies = bodies_since(before, "gated_delta_rule")
    if set(bodies) != {"gated_delta_rule:chunked",
                       "gated_delta_rule_grad:chunked"}:    # no "xla"
        raise AssertionError("the delta rule's ops lowered to %s" % bodies)
    want, want_grad = jax.jit(both("cpu"))(ins, dout)   # the XLA body

    def gap(a, w, bound, what):
        a, w = (np.asarray(x, np.float32).ravel() for x in (a, w))
        err = float(np.linalg.norm(a - w) / np.linalg.norm(w))
        if not err <= bound:
            raise AssertionError("gated_delta_rule %s: %g of the XLA body's "
                                 "norm off, over %g" % (what, err, bound))
        return err
    errs = {s: gap(got[s], want[s], 1e-4, s)
            for s in ("Out", "State", "Starts")}
    errs.update({s: gap(got_grad[s][0], want_grad[s][0],
                        2e-3 if want_grad[s][0].dtype == bf else 2e-4, s)
                 for s in want_grad})
    errs["bodies"] = bodies
    log("kernel gated_delta_rule: %s" % errs)
    return errs


def phase_kernels():
    out = {}

    def packed(name, tk, causal):
        """The short-sequence kernel over the projections' layout
        [B, T, H*D] at the scored batch, bf16, padding + in-kernel
        dropout, against the XLA body over split heads."""
        h, d = WIDTH["n_head"], WIDTH["d_model"] // WIDTH["n_head"]
        if not pa.supported((BATCH, h, SEQ, d), (BATCH, h, tk, d),
                            jnp.bfloat16):
            raise AssertionError("packed_attention.supported rejects "
                                 + name)
        klen = jnp.arange(BATCH, dtype=jnp.int32) % (tk // 2) + tk // 2
        seed = jnp.uint32(1234)

        def split(x):
            return x.reshape(BATCH, -1, h, d).transpose(0, 2, 1, 3)

        def reference(q, k, v):
            o = attention_xla.reference_attention(
                split(q), split(k), split(v), klen, seed, causal, 0.1, None)
            return o.transpose(0, 2, 1, 3).reshape(q.shape)
        out[name] = check_kernel(
            name,
            lambda q, k, v: pa.packed_attention(q, k, v, klen, seed, None,
                                                h, causal, 0.1, None, False),
            reference,
            [normal(i, (BATCH, t, h * d), jnp.bfloat16)
             for i, t in enumerate((SEQ, tk, tk))], 3, TOL_KERNEL["matmul"])

    packed("packed_attention_self", SEQ, False)
    packed("packed_attention_causal", SEQ, True)
    packed("packed_attention_cross", 2 * SEQ, False)

    def streamed(name, h, hk, t, d, keep, dv=None, window=None):
        """The long-document kernels (K/V streamed by blocks, several heads
        a grid step — a key/value head's query group, or some of the plain
        heads and not all — the packed selection when ``keep`` keys a query
        are selected, values ``dv`` wide) at a causal shape of several
        block pairs, bf16: forward, dQ, dK and dV against the XLA body over
        the same selection; under ``window`` over each query's nearest
        ``window`` keys."""
        dv = d if dv is None else dv
        if not sa.supported((1, h, t, d), (1, hk, t, d), jnp.bfloat16, True,
                            False, 0.0, dv):
            raise AssertionError("streamed_attention.supported rejects "
                                 + name)
        words = None if keep is None else sparse_select.pack_key_mask(
            sparse_select.topk_key_mask(normal(8, (1, t, t), jnp.float32),
                                        keep))
        args = [normal(i, (1, n, t, w), jnp.bfloat16)
                for i, (n, w) in enumerate(((h, d), (hk, d), (hk, dv)))]
        kh, gh = sa.step_heads(*args)
        if not (1 < gh if h > hk else 1 < kh < hk):
            raise AssertionError("%s: a grid step serves %d x %d heads"
                                 % (name, kh, gh))
        out[name] = check_kernel(
            name,
            lambda q, k, v: sa.streamed_attention(q, k, v, words, True,
                                                  None, False, window),
            lambda q, k, v: attention_xla.reference_attention(
                q, k, v, None, None, True, 0.0, None, words, False, window),
            args, 3, TOL_KERNEL["matmul"])
        out[name]["bodies"] = same_bits_in_every_body(name, args, words,
                                                      window)

    # eight query heads a key/value head (one grid step serves all eight),
    # four by four blocks of 512, a quarter of the keys selected
    streamed("streamed_attention_grouped", 16, 2, 2048, 128, 512)
    # latent attention as training computes it: plain heads, 192-wide keys
    # over 128-wide values, no selection; the rule's own 8 heads a grid step,
    # each with its own K/V block, dK and dV, and two blocks of heads; the
    # same bits as one head a step
    h = PLAIN_HEADS
    streamed("streamed_attention_latent", h, h, 2048, 192, None, 128)
    out["streamed_attention_latent"]["step"] = plain_heads_through_the_op(
        h, 2048, 192, 128)
    # ... and as a latent block hands it over since PR 48: the projections'
    # outputs where they lie, the rotation inside the op; and without one
    out["streamed_attention_in_place"] = {
        "step": latent_projections_through_the_op(h, 2048, 128, 64, 128,
                                                  3.2e7),
        "step_no_rotation": latent_projections_through_the_op(
            h, 2048, 128, 64, 128, None)}
    # ... and a grouped-head block's three projections since PR 50: eight
    # query heads a K/V head under a window that crosses three key blocks
    # and YaRN's law with its factor; plain heads with the plain law
    out["streamed_attention_grouped_in_place"] = {
        "step_window": grouped_projections_through_the_op(
            16, 2, 2048, 128, 700, rope_theta=5e5, rope_scale=1.2772588722,
            rope_freq_scaling={"factor": 16.0, "original_length": 512.0,
                               "beta_fast": 32.0, "beta_slow": 1.0}),
        "step_plain": grouped_projections_through_the_op(
            8, 8, 2048, 128, None, rope_theta=1e6)}
    # the looped decoder's geometry: 16 plain heads, keys and values both
    # 128 wide, rotary on the whole head, T = 4096
    out["streamed_attention_plain_128"] = {"step": plain_heads_through_the_op(
        16, 4096, 128, 128, rope_theta=1e6)}
    # differential attention's shape: two query heads a key/value head, keys
    # 64 wide (half a lane tile) over values 128 wide, T = 4096 in eight
    # blocks, under a 512-key window (two of a row's blocks run) and without
    streamed("streamed_attention_window", 20, 10, 4096, 64, None, 128, 512)
    streamed("streamed_attention_pairs", 20, 10, 4096, 64, None, 128)
    out["selective_scan"] = selective_scan_kernels(1, 4096, 5120, 16)
    out["gated_delta_rule"] = gated_delta_rule_kernels(1, 2048, 8, 128)
    out["grouped_experts"] = grouped_experts_through_the_op(
        "grouped_experts", 128, 1)
    out["grouped_experts_chunks"] = grouped_experts_through_the_op(
        "grouped_experts_chunks", 32, 4)
    return {"max_errors": out}


# -- learned sparse selection ---------------------------------------------------

def phase_select_keys():
    """``select_topk_keys`` at the long-document cell's shape — scores
    [1, 8192, 8192] from an indexer, the 2048 highest causal keys a query —
    through the executor: the op takes its Pallas body (one Mosaic kernel
    that reads each score once) and its words EQUAL the XLA body's, which
    is the definition.  Then the same scores coarsened until every row is
    cut inside a run of ties, so that Mosaic also runs the index passes."""
    t, k = SELECT_SEQ, SELECT_K
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = fluid.layers.data("index_q", shape=[t, INDEX_HEADS, INDEX_DIM],
                              dtype="float32")
        key = fluid.layers.data("index_k", shape=[t, INDEX_DIM],
                                dtype="float32")
        w = fluid.layers.data("index_w", shape=[t, INDEX_HEADS],
                              dtype="float32")
        selected, share = fluid.layers.select_keys(
            q, key, w, k, scale=INDEX_DIM ** -0.5)
    scores = main.global_block().ops[-1].input("X")[0]
    feed = {name: np.asarray(normal(seed, (1, t) + shape, jnp.float32))
            for seed, (name, shape) in enumerate((
                ("index_q", (INDEX_HEADS, INDEX_DIM)),
                ("index_k", (INDEX_DIM,)), ("index_w", (INDEX_HEADS,))), 11)}
    before = kernel_bodies("select_topk_keys")
    words, share, x = fluid.Executor(fluid.TPUPlace(0)).run(
        main, feed=feed, fetch_list=[selected, share, scores],
        return_numpy=False)
    bodies = bodies_since(before, "select_topk_keys")
    if bodies != {"select_topk_keys:pallas": 1}:
        raise AssertionError("select_topk_keys bodies %s, expected the "
                             "Pallas one" % bodies)
    if not topk_select.supported(x.shape, x.dtype):
        raise AssertionError("topk_select.supported rejects %s" % (x.shape,))

    def xla_body(x):
        sel = sparse_select.topk_key_mask(x, k, True)
        return sparse_select.pack_key_mask(sel), jnp.sum(sel, axis=-1)
    xla = jax.jit(xla_body)
    kernel = mosaic_jit(
        lambda x: topk_select.select_topk_words(x, k, True, False), x)

    def same(got, want, what):
        differ = int(np.sum(np.asarray(got) != np.asarray(want)))
        if differ:
            raise AssertionError("%s: %d of %d differ from the XLA body's"
                                 % (what, differ, got.size))
    want_words, want_count = xla(x)
    same(words, want_words, "select_topk_keys words")
    pairs = t * (t + 1) // 2
    want_share = float(np.asarray(want_count, np.float64).sum()) / pairs
    if abs(float(np.asarray(share)[0]) - want_share) > 1e-6:
        raise AssertionError("share %r, the XLA body's %r"
                             % (np.asarray(share), want_share))
    coarse = jnp.round(x * 4.0) / 4.0
    got = kernel(coarse)
    want = xla(coarse)
    same(got[0], want[0], "tied scores: words")
    same(got[1][..., 0], want[1], "tied scores: counts")

    def ms(fn, arg):
        jax.block_until_ready(fn(arg))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(arg)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / 5 * 1e3, 3)
    timing = {"pallas": ms(kernel, x), "pallas_tied": ms(kernel, coarse),
              "xla": ms(xla, x)}
    log("select_topk_keys [1, %d, %d] k=%d: bodies %s, share %.4f, ms a call "
        "%s (host clock over 5 calls; information, not a metric)"
        % (t, t, k, bodies, want_share, timing))
    return {"bodies": bodies, "selected_share": round(want_share, 6),
            "ms_per_call": timing}


# the hybrid cell's lookup: ids a step, the tied table's rows and width
EMBEDDING_GRAD = (4096, 25008, 2560)


def phase_embedding_grad():
    """``layers.embedding`` + ``append_backward`` at the hybrid cell's tied
    table — 4096 ids into ``[25,008, 2560]`` float32 — through the executor
    on the chip: the dense gradient lowers to the sorted-segment kernel
    (``kernel_bodies``), and its rows are the scattered add's, bit for bit
    (float32 sums of the same addends in the same order)."""
    from paddle_tpu.ops.pallas import embedding_grad as eg

    n, v, d = EMBEDDING_GRAD
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data("ids", shape=[n, 1], dtype="int64")
        weight = fluid.layers.data("weight", shape=[n, d], dtype="float32")
        emb = fluid.layers.embedding(
            ids, size=[v, d], param_attr=fluid.ParamAttr(name="smoke_table"))
        loss = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(emb, weight))
        fluid.backward.append_backward(loss)
    rows = np.random.RandomState(5).randint(0, v, (1, n, 1)).astype("int64")
    rows[0, :64, 0] = 7                       # one row takes 64 addends
    gout = normal(21, (1, n, d), jnp.float32)
    before = kernel_bodies("lookup_table_grad")
    exe = fluid.Executor(fluid.TPUPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got, = exe.run(main, feed={"ids": rows, "weight": np.asarray(gout)},
                       fetch_list=["smoke_table@GRAD"], return_numpy=False)
    bodies = bodies_since(before, "lookup_table_grad")
    if bodies != {"lookup_table_grad:segment": 1}:
        raise AssertionError("lookup_table_grad bodies %s, expected the "
                             "segment one" % bodies)
    flat = jnp.asarray(rows.reshape(-1), jnp.int32)
    xla = jax.jit(lambda i, g: jnp.zeros((v, d), jnp.float32).at[i].add(g))
    kernel = mosaic_jit(lambda i, g: eg.embedding_grad(i, g, v), flat,
                        gout[0])
    want = xla(flat, gout[0])
    differ = int(jnp.sum(got != want)) + int(jnp.sum(
        kernel(flat, gout[0]) != want))
    if differ:
        raise AssertionError("lookup_table_grad: %d elements differ from "
                             "the scattered add's" % differ)

    def ms(fn):
        jax.block_until_ready(fn(flat, gout[0]))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(flat, gout[0])
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / 5 * 1e3, 3)
    timing = {"segment": ms(kernel), "xla": ms(xla)}
    log("lookup_table_grad %d rows into [%d, %d]: bodies %s, ms a call %s "
        "(host clock over 5 calls; information, not a metric)"
        % (n, v, d, bodies, timing))
    return {"bodies": bodies, "ms_per_call": timing}


# transformer_base's head: target positions a step, model width, vocabulary
HEAD_GRAD = (16384, 512, 32000)


def phase_head_grad():
    """A bf16-AMP head at ``transformer_base``'s shape — ``[16384, 512]``
    into 32,000 columns with a bias, uniform smoothing 0.1, a fifth of the
    rows masked out of the loss — through the executor on the chip, twice:
    the chain rule's kernel (``mul_grad:head_fused``) and the three ops one
    by one (``mul_grad:head_by_op``).  dX, dW and db of the two agree to
    the products' bf16 rounding."""
    n, d, v = HEAD_GRAD

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", shape=[d], dtype="float32")
            x.stop_gradient = False
            label = fluid.layers.data("label", shape=[1], dtype="int64")
            keep = fluid.layers.data("keep", shape=[1], dtype="float32")
            logits = fluid.layers.fc(x, size=v, name="smoke_head")
            cost = fluid.layers.softmax_with_cross_entropy(
                logits, label, label_smooth_eps=0.1)
            loss = fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(cost, keep))
            fluid.backward.append_backward(loss)
        main._amp_policy = mixed_precision.AMPPolicy()
        return main, startup
    rng = np.random.RandomState(3)
    feed = {"x": np.asarray(normal(31, (n, d), jnp.float32)),
            "label": rng.randint(0, v, (n, 1)).astype("int64"),
            "keep": ((rng.rand(n, 1) > 0.2) / n).astype("float32")}
    names = ["x@GRAD", "smoke_head.w_0@GRAD", "smoke_head.b_0@GRAD"]
    got, timing = {}, {}
    for body, platforms in (("head_fused", ("tpu",)), ("head_by_op", ())):
        loss_ops._HEAD_PLATFORMS = platforms
        compile_cache.clear()       # the patch is in no cache key
        try:
            main, startup = build()
            before = kernel_bodies("mul_grad")
            exe = fluid.Executor(fluid.TPUPlace(0))
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup)
                got[body] = exe.run(main, feed=feed, fetch_list=names)
                t0 = time.perf_counter()
                for _ in range(5):
                    out = exe.run(main, feed=feed, fetch_list=names,
                                  return_numpy=False)
                jax.block_until_ready(out)
                timing[body] = round((time.perf_counter() - t0) / 5 * 1e3, 3)
        finally:
            loss_ops._HEAD_PLATFORMS = ("tpu",)
        if bodies_since(before, "mul_grad") != {"mul_grad:" + body: 1}:
            raise AssertionError("the head's chain took %s, expected %s"
                                 % (bodies_since(before, "mul_grad"), body))
    errs = {}
    for name, a, b, tol in zip(names, got["head_fused"], got["head_by_op"],
                               (5e-3, 5e-3, 5e-4)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if not np.isfinite(a).all() or not np.any(b):
            raise AssertionError("%s: non-finite or all zero" % name)
        errs[name] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        if errs[name] > tol:
            raise AssertionError("%s: the kernel's is %g of the chain's "
                                 "norm away, over %g" % (name, errs[name],
                                                         tol))
    log("head_grad [%d, %d] x [%d, %d]: relative errors %s, ms a step %s "
        "(forward + backward, host clock over 5 steps; information, not a "
        "metric)" % (n, d, d, v, errs, timing))
    return {"rel_err": errs, "ms_per_step": timing}


# -- serving --------------------------------------------------------------------

def serve_prompts():
    rng = np.random.RandomState(7)
    shared = rng.randint(2, VOCAB, PAGE).tolist()    # one full page
    prompts = []
    for i, n in enumerate(PROMPT_LENS):
        body = rng.randint(2, VOCAB, n).tolist()
        # the two equal-length prompts share their whole first page
        prompts.append(shared + body[PAGE:] if n == 33 else body)
    return prompts


def run_engine(place, prefix, prompts, **build_kw):
    """Build one engine, answer every prompt, return (results, engine
    facts).  The engine is closed before returning."""
    spec = serving.build_decoder_lm(prefix=prefix, **SERVE, **build_kw)
    eng = serving.GenerationEngine(
        spec, place=place, max_new_tokens=MAX_NEW, timeout_s=900.0,
        bucket_bounds=list(BUCKETS), record_logits=True)
    try:
        outs = [r.result(900) for r in [eng.submit(p) for p in prompts]]
        # warm: two more requests in already-compiled buckets compile nothing
        with compile_cache.count_compiles() as n:
            outs += [r.result(900) for r in
                     [eng.submit(p) for p in prompts[:2]]]
        assert_no_new_compiles(n(), "serving " + prefix)
        assert_on_device(eng._scope, [place.jax_device()])
        fp12 = compile_cache.program_fingerprint(spec.decode_program)[:12]
        facts = {
            "decode_signatures": len(eng._exe_decode._cache),
            "decode_lowerings":
                compile_cache.stats()["lowerings_by_program"].get(fp12, 0),
            "leaks": eng._alloc.check_leaks() if spec.paged else [],
            "paged": eng.metrics.paged_snapshot() if spec.paged else None,
        }
    finally:
        eng.close()
    for o, p in zip(outs, prompts + prompts[:2]):
        if len(o["tokens"]) != MAX_NEW or o["prompt_len"] != len(p):
            raise AssertionError("request did not complete: %s" % o)
        if not all(np.isfinite(row).all() for row in o["logits"]):
            raise AssertionError("non-finite logits (%s)" % prefix)
    if facts["decode_signatures"] != 1 or facts["decode_lowerings"] != 1:
        raise AssertionError("%s: more than one decode lowering: %s"
                             % (prefix, facts))
    if facts["leaks"]:
        raise AssertionError("%s: page leaks %s" % (prefix, facts["leaks"]))
    return outs, facts


def same_stream(fixed, paged):
    """The paged engine's greedy stream against the fixed-region
    engine's, step by step: logits agree within ``TOL_SERVE_LOGITS``
    and the tokens are equal — except where the fixed engine's own
    top-two margin is inside that tolerance (random weights leave the
    argmax on near-ties, and the two cache layouts round differently on
    the MXU); past such a tie the contexts differ and the comparison
    stops.  Returns (steps compared, max logit error)."""
    worst = 0.0
    for i, (a, b) in enumerate(zip(fixed["logits"], paged["logits"])):
        bound = TOL_SERVE_LOGITS * max(1.0, float(np.max(np.abs(a))))
        err = float(np.max(np.abs(a - b)))
        worst = max(worst, err)
        if err > bound:
            raise AssertionError("step %d: paged logits off by %g (bound "
                                 "%g)" % (i, err, bound))
        if fixed["tokens"][i] != paged["tokens"][i]:
            top = np.sort(a)[-2:]
            if top[1] - top[0] > 2 * bound:
                raise AssertionError(
                    "step %d: tokens %d vs %d with a top-two margin of %g"
                    % (i, fixed["tokens"][i], paged["tokens"][i],
                       top[1] - top[0]))
            return i, worst
    return len(fixed["tokens"]), worst


def phase_serve_1chip():
    place = fluid.TPUPlace(0)
    prompts = serve_prompts()
    fixed, _ = run_engine(place, "smk_fixed", prompts)
    paged, facts = run_engine(place, "smk_paged", prompts, paged=True,
                              page_size=PAGE)
    streams = [same_stream(f, p) for f, p in zip(fixed, paged)]
    if not facts["paged"]["prefix_hits"] > 0:
        raise AssertionError("no prefix hit: %s" % facts["paged"])
    # int8 pages: token identity is not asserted (random weights put the
    # argmax on ties); completion, finite logits and no leaks are
    _, int8_facts = run_engine(place, "smk_int8", prompts, paged=True,
                               page_size=PAGE, kv_dtype="int8")
    log("serve_1chip: %d requests x3 engines, paged %s; paged-vs-fixed "
        "steps compared / max logit error per request: %s"
        % (len(prompts) + 2, facts["paged"], streams))
    return {"requests": len(prompts) + 2, "paged": facts["paged"],
            "int8_paged": int8_facts["paged"],
            "paged_vs_fixed": streams}


# -- four chips -------------------------------------------------------------------

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def mesh_executor(mesh, main, startup, loss, strategy):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.TPUPlace(0)).run(startup)
    pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                mesh=mesh, build_strategy=strategy,
                                scope=scope)
    return pe, scope


def bytes_in_use(devs):
    return [d.memory_stats()["bytes_in_use"] for d in devs]


def compiled_text(pe):
    (entry,) = pe._cache.values()
    if not entry.aot:
        raise AssertionError("no AOT executable captured (preflight off?)")
    (captured,) = entry.aot.values()
    return captured.as_text()


def phase_train_4chip(one_chip_first_loss):
    devs = jax.devices()
    if len(devs) < 4:
        return {"skipped": "%d chips" % len(devs)}
    devs = devs[:4]
    gc.collect()
    # the HBM preflight makes the executors compile through the AOT
    # capture path, whose executable (and its HLO text) stays readable
    fluid.set_flags({"FLAGS_preflight_oom": "warn"})
    main, startup, loss = build_transformer(SEQ, LAYERS, 0.1, amp=True)
    mesh = make_mesh((2, 2), ("dp", "tp"), devices=devs)
    strategy = fluid.BuildStrategy()
    strategy.sharding_rules = True
    pe, scope = mesh_executor(mesh, main, startup, loss, strategy)
    bodies = attention_bodies()
    with mesh:
        losses, _, new = seeded_steps(pe.run, loss, MESH_STEPS, BATCH, SEQ)
    log("train_4chip losses: %s" % " ".join("%.4f" % v for v in losses))
    # per shard under shard_map: batch over dp, four whole heads over tp
    assert_packed(bodies, 3 * LAYERS, "train_4chip")
    assert_no_new_compiles(new, "train_4chip")
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError("non-finite loss: %s" % losses)
    assert_on_device(scope, devs)
    # a tp-sharded weight holds half of its global shape per chip
    sharded = {n: s for n, s in pe.state_shardings().items()
               if "tp" in str(s.spec)}
    if not sharded:
        raise AssertionError("sharding_rules placed nothing on tp")
    name = sorted(sharded)[0]
    arr = scope.var(name)
    local = arr.addressable_shards[0].data.shape
    if int(np.prod(local)) * 2 != int(np.prod(arr.shape)):
        raise AssertionError("%s: shard %s is not half of %s"
                             % (name, local, arr.shape))
    in_use = bytes_in_use(devs)
    log("train_4chip bytes_in_use per chip: %s" % in_use)
    if max(in_use) > 2 * min(in_use):
        raise AssertionError("per-chip memory not within 2x: %s" % in_use)
    hlo = compiled_text(pe)
    found = [c for c in COLLECTIVES if c in hlo]
    if not found:
        raise AssertionError("no collective in the compiled module")
    if abs(losses[0] - one_chip_first_loss) > TOL_MESH_VS_1CHIP:
        raise AssertionError(
            "first-step loss %.6f on the mesh vs %.6f on one chip (tol %g)"
            % (losses[0], one_chip_first_loss, TOL_MESH_VS_1CHIP))
    del pe, scope
    ring = ring_step(devs)
    fluid.set_flags({"FLAGS_preflight_oom": "auto"})
    return {"losses": losses, "one_chip_first_loss": one_chip_first_loss,
            "tp_sharded_example": {name: list(local)},
            "bytes_in_use": in_use, "collectives": found, "ring": ring}


def ring_step(devs):
    """One float32 training step of the Transformer at T=4096 with ring
    attention on a (dp=1, sp=4) mesh, against the same step without the
    ring on one chip."""
    main, startup, loss = build_transformer(RING_SEQ, REF_LAYERS, 0.0,
                                            amp=False)
    feed = train_feed(0, 1, RING_SEQ)
    before = compile_cache.stats()["kernel_bodies"].get(
        "fused_attention:ring", 0)
    mesh = make_mesh((1, 4), ("dp", "sp"), devices=devs)
    pe, scope = mesh_executor(mesh, main, startup, loss,
                              fluid.BuildStrategy())
    with mesh:
        (val,) = pe.run(feed=feed, fetch_list=[loss])
    ring_loss = float(np.asarray(val).ravel()[0])
    engaged = compile_cache.stats()["kernel_bodies"].get(
        "fused_attention:ring", 0) - before
    if engaged < 1:
        raise AssertionError("ring attention did not engage")
    if "collective-permute" not in compiled_text(pe):
        raise AssertionError("ring step compiled without collective-permute")
    del pe, scope
    plain = fluid.Scope()
    with fluid.scope_guard(plain):
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        (val,) = exe.run(main, feed=feed, fetch_list=[loss])
    plain_loss = float(np.asarray(val).ravel()[0])
    log("ring step T=%d: ring %.6f plain %.6f (%d attention ops on the "
        "ring)" % (RING_SEQ, ring_loss, plain_loss, engaged))
    if not (math.isfinite(ring_loss)
            and abs(ring_loss - plain_loss) <= TOL_RING_VS_PLAIN):
        raise AssertionError("ring %.6f vs plain %.6f (tol %g)"
                             % (ring_loss, plain_loss, TOL_RING_VS_PLAIN))
    return {"seq": RING_SEQ, "ring_loss": ring_loss,
            "plain_loss": plain_loss, "attention_ops_on_ring": engaged}


# ---------------------------------------------------------------------------

def main():
    phases = {}

    def run(name, fn, *args):
        log("phase %s ..." % name)
        t0 = time.perf_counter()
        with compile_cache.count_compiles() as n:
            detail = fn(*args)
        c = n()
        phases[name] = dict(
            {"ok": True, "seconds": round(time.perf_counter() - t0, 2),
             "compile_seconds": round(c["jax_lowering_seconds"]
                                      + c["jax_backend_compile_seconds"], 2),
             "backend_compiles": c["jax_backend_compiles"],
             "persistent_cache_hits": c["persistent_cache_hits"]},
            **detail)
        log("phase %s ok in %.1f s (compile %.1f s, %d persistent-cache "
            "hits of %d compiles)"
            % (name, phases[name]["seconds"],
               phases[name]["compile_seconds"], c["persistent_cache_hits"],
               c["jax_backend_compiles"]))
        return detail

    run("device", phase_device)
    cache_dir = compile_cache.enable_persistent_cache(chip_entry=True)
    fluid.set_flags({"FLAGS_fast_prng": True})   # rbg, as the scored rung
    train = run("train_1chip", phase_train_1chip)
    run("kernels", phase_kernels)
    run("select_keys", phase_select_keys)
    run("embedding_grad", phase_embedding_grad)
    run("head_grad", phase_head_grad)
    run("serve_1chip", phase_serve_1chip)
    run("train_4chip", phase_train_4chip, train["losses"][0])

    report = {
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": cache_dir,
        "recordio_native": recordio.native_available(),
        "kernel_bodies": compile_cache.stats()["kernel_bodies"],
        "kernel_traces": compile_cache.stats()["kernel_traces"],
        "phases": phases,
        "claim": None,
    }
    log("report " + json.dumps(report))
    print(result_line(jax.devices()), flush=True)


if __name__ == "__main__":
    main()
