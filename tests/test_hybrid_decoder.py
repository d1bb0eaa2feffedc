"""What the decoder-hybrid-decoder (ISSUE 38) added, on the CPU at small
sizes: the selective scan and its gradient (the chunked Pallas kernels
interpreted, the XLA body) against a step-by-step ``lax.scan``; attention
under a window in every body against a dense masked softmax; the calls
WITHOUT a window traced to the kernels they traced to before there was one;
the hybrid program against the plain reference; tied tables and the
variables two layers read."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import hybrid_decoder as hd
from paddle_tpu.ops import attention as att
from paddle_tpu.ops import state_space
from paddle_tpu.ops import attention_xla as fa
from paddle_tpu.ops.pallas import selective_scan as ss
from paddle_tpu.ops.pallas import streamed_attention as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness                                   # noqa: E402
from benchmark.generators import train_hybrid_steps as gen      # noqa: E402

CELL = "phi4_mini_flash.train_reason_4k"


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a, jnp.float32) - b)
                 / max(float(jnp.linalg.norm(b)), 1e-30))


# ---- the selective scan ---------------------------------------------------------

def _scan_args(bt, t, e, n, seed=0):
    rng = np.random.default_rng(seed)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    delta = jax.nn.softplus(arr(bt, t, e) - 2.0)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (e, n))
    return (delta, arr(bt, t, e), a, arr(bt, t, n), arr(bt, t, n), arr(e)), \
        arr(bt, t, e)


def _step_by_step(delta, x, a, b, c, d):
    """The recurrence one step at a time: (y, the final state [B, E, N])."""
    def step(s, inp):
        dl, xt, bt, ct = inp
        s = jnp.exp(dl[..., None] * a) * s \
            + (dl * xt)[..., None] * bt[:, None, :]
        return s, jnp.sum(s * ct[:, None, :], -1) + d * xt
    s, ys = jax.lax.scan(
        step, jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (delta, x, b, c)))
    return jnp.moveaxis(ys, 0, 1), s


@pytest.mark.parametrize("body", ["chunked", "xla"])
@pytest.mark.parametrize("bt,t,e,n,chunk", [
    (2, 48, 256, 16, 16),        # three whole chunks, one backward slab
    (1, 40, 384, 16, 16),        # two and a half: the last chunk padded
    (1, 24, 128, 8, 8),          # one sublane tile of states
    (1, 7, 128, 16, 8),          # shorter than a chunk
])
def test_selective_scan_and_its_gradient_follow_the_recurrence(
        body, bt, t, e, n, chunk):
    """Both bodies — the chunked Pallas kernels (interpreted) and the XLA
    scan over chunks — give the step-by-step recurrence's output, final
    state and all six gradients in float32, at lengths that are and are not
    whole chunks; the chunk-start states are the recurrence's own."""
    args, dy = _scan_args(bt, t, e, n, seed=t)
    assert ss.supported((bt, t, e), n, chunk)
    (y0, s0), vjp = jax.vjp(_step_by_step, *args)
    want = vjp((dy, jnp.zeros_like(s0)))
    if body == "chunked":
        y, state, starts = ss.forward(*args, chunk, True)
        grads = ss.backward(*args, starts, dy, chunk, True)
    else:
        (y, state, starts), vjp = jax.vjp(
            lambda *a: state_space.scan_xla(*a, chunk), *args)
        grads = vjp((dy, jnp.zeros_like(state), jnp.zeros_like(starts)))
    assert starts.shape == (bt, -(-t // chunk), n, e)
    assert _rel(y, y0) < 1e-6 and _rel(state, s0) < 1e-6
    # the state a chunk starts on is the recurrence's after the steps before
    for ci in range(starts.shape[1]):
        if ci == 0:
            assert not np.asarray(starts[:, 0]).any()
        else:
            _, s = _step_by_step(*(v[:, :ci * chunk] if v.ndim == 3
                                   and v.shape[1] == t else v for v in args))
            assert _rel(jnp.swapaxes(starts[:, ci], 1, 2), s) < 1e-6
    for got, ref in zip(grads, want):
        assert got.shape == ref.shape
        assert _rel(got, ref) < 2e-6


def test_the_two_bodies_of_the_scan_agree_to_rounding():
    args, dy = _scan_args(1, 64, 256, 16, seed=3)
    y1, s1, st1 = ss.forward(*args, 16, True)
    y2, s2, st2 = state_space.scan_xla(*args, 16)
    np.testing.assert_allclose(y1, y2, rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(st1), np.asarray(st2))


def _scan_program(t, e, n, chunk, amp=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x, dl = (fluid.layers.data(nm, shape=[t, e], dtype="float32")
                 for nm in ("x", "dl"))
        b, c = (fluid.layers.data(nm, shape=[t, n], dtype="float32")
                for nm in ("b", "c"))
        a = fluid.layers.data("a", shape=[e, n], dtype="float32",
                              append_batch_size=False)
        d, bias = (fluid.layers.data(nm, shape=[e], dtype="float32",
                                     append_batch_size=False)
                   for nm in ("d", "bias"))
        for v in (x, dl, a, b, c, d, bias):
            v.stop_gradient = False
        y, state = fluid.layers.selective_scan(x, dl, a, b, c, d,
                                               delta_bias=bias, chunk=chunk)
        w = fluid.layers.data("w", shape=[t, e], dtype="float32")
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(y, w))
        grads = fluid.backward.calc_gradient(loss, [x, dl, a, b, c, d, bias])
    return main, [y, state] + list(grads)


@pytest.mark.parametrize("kernel", [False, True])
def test_the_scan_op_and_its_gradient_op_through_the_executor(monkeypatch,
                                                              kernel):
    """``layers.selective_scan`` through the executor: the step size's
    softplus and bias are the op's, the gradient op hands back all seven
    gradients; with the kernels as the body (interpreted here) the gradient
    op is the one backward kernel over the forward's own ``Starts``."""
    from paddle_tpu import compile_cache

    t, e, n, chunk = 24, 128, 16, 8
    if kernel:
        monkeypatch.setattr(state_space, "_KERNEL_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    args, w = _scan_args(2, t, e, n, seed=11)
    pre, x, a, b, c, d = args
    pre = pre - 1.0
    bias = jnp.linspace(-1.0, 0.5, e, dtype=jnp.float32)
    main, fetch = _scan_program(t, e, n, chunk)
    before = dict(compile_cache.stats()["kernel_bodies"])
    got = fluid.Executor(fluid.CPUPlace()).run(main, feed={
        "x": np.asarray(x), "dl": np.asarray(pre), "a": np.asarray(a),
        "b": np.asarray(b), "c": np.asarray(c), "d": np.asarray(d),
        "bias": np.asarray(bias), "w": np.asarray(w)}, fetch_list=fetch)
    bodies = {k: v - before.get(k, 0) for k, v in
              compile_cache.stats()["kernel_bodies"].items()
              if k.startswith("selective_scan") and v - before.get(k, 0)}
    body = "chunked" if kernel else "xla"
    assert bodies.get("selective_scan:" + body, 0) >= 1
    assert bodies.get("selective_scan_grad:" + body, 0) >= 1

    def plain(x, pre, a, b, c, d, bias):
        return _step_by_step(jax.nn.softplus(pre + bias), x, a, b, c, d)
    (y0, s0), vjp = jax.vjp(plain, x, pre, a, b, c, d, bias)
    want = [y0, s0] + list(vjp((w, jnp.zeros_like(s0))))
    for g, r in zip(got, want):
        assert _rel(g, r) < 3e-6
    if kernel:
        compile_cache.clear()


def test_the_scan_stays_float32_under_mixed_precision():
    from paddle_tpu.contrib import mixed_precision as mp

    lists = mp.AutoMixedPrecisionLists()
    assert lists.colour("selective_scan") == "black"
    assert lists.colour("causal_conv1d") is None
    # ... without renaming the programs that hold no such op
    assert "selective_scan" not in repr(mp.AMPPolicy())
    policy = mp.AMPPolicy()
    for op in ("selective_scan", "selective_scan_grad"):
        cast = policy.cast_inputs(op, {"Delta": [jnp.ones((2,), jnp.bfloat16)]})
        assert cast["Delta"][0].dtype == jnp.float32


@pytest.mark.parametrize("act,bias", [("silu", True), (None, False)])
def test_causal_conv1d_is_shifted_multiply_adds(act, bias):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 9, 6)).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xin = fluid.layers.data("x", shape=[9, 6], dtype="float32")
        xin.stop_gradient = False
        y = fluid.layers.causal_conv1d(
            xin, 4, act=act, param_attr=fluid.ParamAttr(name="w"),
            bias_attr=fluid.ParamAttr(name="b") if bias else False)
        gx, = fluid.backward.calc_gradient(fluid.layers.reduce_sum(y), [xin])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w = rng.normal(size=(4, 6)).astype("float32")
        b = rng.normal(size=(6,)).astype("float32")
        scope.set_var("w", jnp.asarray(w))
        if bias:
            scope.set_var("b", jnp.asarray(b))
        got, got_gx = exe.run(main, feed={"x": x}, fetch_list=[y, gx])

    def plain(x):
        out = np.zeros_like(x) + (b if bias else 0.0)
        for t in range(x.shape[1]):
            for j in range(4):
                if t - 3 + j >= 0:
                    out[:, t] += w[j] * x[:, t - 3 + j]
        return out
    want = plain(x)
    if act:
        want = want / (1.0 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # causal: the gradient of the LAST step's input comes from one tap
    if not act:
        np.testing.assert_allclose(got_gx[:, -1], np.broadcast_to(w[3], (2, 6)),
                                   rtol=1e-6)


# ---- attention under a window ------------------------------------------------------

H, HK, T, DK, DV = 4, 2, 384, 64, 128        # blocks of 128 keys


def _qkv(dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=s), dtype) for s in (
        (1, H, T, DK), (1, HK, T, DK), (1, HK, T, DV), (1, H, T, DV))]


def _dense(q, k, v, window):
    g = H // HK
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, g, 1)) * DK ** -0.5
    t = jnp.arange(T)
    keep = t[None, :] <= t[:, None]
    if window is not None:
        keep = keep & (t[:, None] - t[None, :] < window)
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v, g, 1))


WINDOWS = [50, 128, 200, T, 1000]    # under, one, over a block; >= T


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("body", ["reference", "streamed"])
def test_a_window_in_every_body_against_a_dense_masked_softmax(body, window):
    """Grouped heads, keys 64 and values 128 wide: forward and the three
    gradients of ``reference_attention`` (the XLA body) and of the streamed
    kernels (interpreted), for windows under, of and over a block of keys
    and at least the whole sequence."""
    q, k, v, do = _qkv()
    ref, vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, window), q, k, v)
    want = vjp(do)
    if body == "reference":
        out, vjp = jax.vjp(lambda q, k, v: fa.reference_attention(
            q, k, v, None, None, True, 0.0, DK ** -0.5, None, False, window),
            q, k, v)
        got = vjp(do)
    else:
        out, lse = sa.forward(q, k, v, None, True, DK ** -0.5, True, window)
        got = sa.backward(q, k, v, None, out, lse, do, True, DK ** -0.5,
                          True, window)
    assert _rel(out, ref) < 2e-6
    for g, w in zip(got, want):
        assert _rel(g, w) < 5e-6


@pytest.mark.parametrize("window,heads", [
    (50, (1, 2)), (128, (1, 2)), (200, (1, 2)), (200, (1, 1)), (300, (1, 2)),
    (None, (1, 2)), (None, (1, 1))])
def test_fused_backward_under_a_window_against_the_two_kernels(
        monkeypatch, window, heads):
    """Keys narrower than values (64 under 128), two query heads a K/V
    head, a window whose lower edge starts mid-block (50, 200, 300 of
    128-key blocks), on a block's edge, and none: the fused backward
    against ``jax.vjp`` of the XLA body and the dQ and dK/dV kernels — dQ to
    the bit, dK and dV to the bit where the pair shares a step."""
    from streamed_backward import check_fused_backward

    q, k, v, do = _qkv(seed=8)
    check_fused_backward(monkeypatch, q, k, v, do, None, True, DK ** -0.5,
                         window, heads, same_bits=heads == (1, 2))


@pytest.mark.parametrize("window", [T, 1000])
def test_a_window_of_the_whole_sequence_is_causal_bit_for_bit(window):
    q, k, v, do = _qkv(seed=2)
    plain = sa.forward(q, k, v, None, True, None, True)
    wide = sa.forward(q, k, v, None, True, None, True, window)
    for a, b in zip(plain, wide):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
            sa.backward(q, k, v, None, *plain, do, True, None, True),
            sa.backward(q, k, v, None, *wide, do, True, None, True, window)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(fa.reference_attention(q, k, v, None, None, True)),
        np.asarray(fa.reference_attention(q, k, v, None, None, True, 0.0,
                                          None, None, False, window)))


def test_a_window_with_a_selection_keeps_both():
    from paddle_tpu.ops import sparse_select

    q, k, v, do = _qkv(seed=4)
    rng = np.random.default_rng(4)
    words = sparse_select.pack_key_mask(sparse_select.topk_key_mask(
        jnp.asarray(rng.normal(size=(1, T, T)), jnp.float32), 96))
    out, lse = sa.forward(q, k, v, words, True, None, True, 200)
    want, wlse = fa.reference_attention(q, k, v, None, None, True, 0.0, None,
                                        words, True, 200)
    assert _rel(out, want) < 2e-6
    np.testing.assert_allclose(lse, wlse, rtol=1e-5, atol=1e-5)


def _window_program(window, amp_dtype="float32"):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = fluid.layers.data("q", shape=[H, T, DK], dtype=amp_dtype)
        k = fluid.layers.data("k", shape=[HK, T, DK], dtype=amp_dtype)
        v = fluid.layers.data("v", shape=[HK, T, DV], dtype=amp_dtype)
        do = fluid.layers.data("do", shape=[H, T, DV], dtype=amp_dtype)
        for x in (q, k, v):
            x.stop_gradient = False
        out = fluid.layers.fused_attention(q, k, v, causal=True,
                                           window=window)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, do))
        grads = fluid.backward.calc_gradient(loss, [q, k, v])
    return main, [out] + list(grads)


@pytest.mark.parametrize("streamed", [False, True])
@pytest.mark.parametrize("window", [50, 200])
def test_a_window_through_the_op_and_its_gradient_op(monkeypatch, streamed,
                                                     window):
    """``layers.fused_attention(window=)`` through the executor: the XLA
    body on the CPU, the streamed kernels (interpreted) where the op takes
    them; the op carries the attribute, keeps its log-sum-exp, and its
    gradient op runs the streamed backward on it."""
    from paddle_tpu import compile_cache

    if streamed:
        monkeypatch.setattr(att, "_STREAMED_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    main, fetch = _window_program(window)
    op, = [o for o in main.global_block().ops if o.type == "fused_attention"]
    assert op.attrs["window"] == window and op.attrs["keep_lse"]
    assert op.outputs.get("LSE")
    q, k, v, do = _qkv(seed=6)
    before = dict(compile_cache.stats()["kernel_bodies"])
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"q": np.asarray(q), "k": np.asarray(k),
                    "v": np.asarray(v), "do": np.asarray(do)},
        fetch_list=fetch)
    stats = compile_cache.stats()["kernel_bodies"]
    body = "streamed" if streamed else "xla"
    assert stats.get("fused_attention:" + body, 0) \
        > before.get("fused_attention:" + body, 0)
    if streamed:
        for note in ("fused_attention_grad:streamed_fused",
                     "streamed_grad_step:1x2"):
            assert stats.get(note, 0) > before.get(note, 0)
    ref, vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, window), q, k, v)
    for g, w in zip(got, (ref,) + vjp(do)):
        assert _rel(g, w) < 5e-6
    if streamed:
        compile_cache.clear()


def test_a_window_is_refused_where_it_means_nothing():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = fluid.layers.data("q", shape=[H, T, DK], dtype="float32")
        k = fluid.layers.data("k", shape=[HK, T, DK], dtype="float32")
        v = fluid.layers.data("v", shape=[HK, T, DV], dtype="float32")
        with pytest.raises(ValueError, match="window"):
            fluid.layers.fused_attention(q, k, v, causal=False, window=8)
        with pytest.raises(ValueError, match="window"):
            fluid.layers.fused_attention(q, k, v, causal=True, window=0)
        with pytest.raises(ValueError, match="window"):
            fluid.layers.fused_attention(q, k, v, causal=True, window=8,
                                         dropout_rate=0.1)
    with pytest.raises(ValueError, match="window"):
        sa.forward(*_qkv()[:3], None, False, None, True, 8)


def test_a_call_without_a_window_carries_no_attribute():
    """The three decoder cells' programs keep their text: an op without a
    window has no ``window`` attribute (an attribute is in the program's
    fingerprint, and with it in its compiled module's name)."""
    main, _ = _window_program(None)
    op, = [o for o in main.global_block().ops if o.type == "fused_attention"]
    assert "window" not in op.attrs


# The three decoder cells' streamed calls (the shapes their programs trace):
# q, k, v shapes, a selection or none.
CELL_CALLS = {
    "train_longdoc_8k": ((1, 32, 8192, 128), (1, 4, 8192, 128),
                         (1, 4, 8192, 128), True),
    "train_mtp_8k": ((1, 32, 8192, 192), (1, 32, 8192, 192),
                     (1, 32, 8192, 128), False),
    "train_loop_4k": ((1, 16, 4096, 128), (1, 16, 4096, 128),
                      (1, 16, 4096, 128), False),
}
# (operands, outputs, scratch arrays, pl.when branches, comparisons) of the
# forward, dQ and dK/dV kernels, as they were before there was a window
# (counted on the parent commit, fd289b7), and of the fused backward (PR 39:
# dQ's operands, all three outputs, both kernels' accumulators, two more
# branches for the resident dK and dV's first and last touch)
NO_WINDOW_KERNELS = {
    "train_longdoc_8k": [(4, 2, 4, 4, 14), (7, 1, 2, 4, 9), (7, 2, 3, 4, 9),
                         (7, 3, 4, 6, 13)],
    "train_mtp_8k": [(3, 2, 4, 4, 10), (6, 1, 2, 4, 5), (6, 2, 3, 4, 5),
                     (6, 3, 4, 6, 9)],
    "train_loop_4k": [(3, 2, 4, 4, 10), (6, 1, 2, 4, 5), (6, 2, 3, 4, 5),
                      (6, 3, 4, 6, 9)],
}


def _kernel_counts(call, operands, statics):
    """(operands, outputs, scratch, ``pl.when`` branches, comparisons) of
    the ONE ``pallas_call`` that ``call`` makes."""
    def bound(*given):
        given = iter(given)
        return call(*[None if x is None else next(given) for x in operands],
                    **statics)
    closed = jax.make_jaxpr(bound)(*[x for x in operands if x is not None])
    eqn, = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    mapping = eqn.params["grid_mapping"]
    kernel = eqn.params["jaxpr"]

    def count(jaxpr, names):
        n = 0
        for e in jaxpr.eqns:
            n += e.primitive.name in names
            for sub in jax.core.jaxprs_in_params(e.params):
                n += count(sub, names)
        return n
    return (mapping.num_inputs, mapping.num_outputs,
            mapping.num_scratch_operands, count(kernel, ("cond",)),
            count(kernel, ("lt", "le", "gt", "ge", "eq", "ne")))


@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_a_call_without_a_window_builds_the_kernels_it_built(cell):
    """One call of each decoder cell's shape, no window: the forward, dQ
    and dK/dV kernels have the operands, scratch, ``pl.when`` branches and
    comparisons they had before there was a window (the forward binds the
    signature it had: its statics are its own heads a step and no
    ``window``), and so has the fused backward; the same call WITH a window
    adds comparisons and no operand or scratch."""
    qs, ks, vs, selected = CELL_CALLS[cell]
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (qs, ks, vs))
    sel = jax.ShapeDtypeStruct((1, qs[2], qs[2] // 32), jnp.int32) \
        if selected else None
    statics = sa._statics(q, k, v, True, None, False)
    assert "window" not in statics
    do = jax.ShapeDtypeStruct(qs[:3] + vs[3:], jnp.bfloat16)
    col = jax.ShapeDtypeStruct(qs[:3] + (1,), jnp.float32)
    body, heads = sa.grad_step(q, k, v)
    assert body == "streamed_fused"
    fused = sa._statics(q, k, v, True, None, False, None, heads)
    assert "window" not in fused and fused["heads"] == heads
    calls = [(sa._forward, (sel, q, k, v), statics),
             (sa._dq, (sel, q, k, v, do, col, col), statics),
             (sa._dkv, (sel, q, k, v, do, col, col), statics),
             (sa._grad, (sel, q, k, v, do, col, col), fused)]
    got = [_kernel_counts(c, ops, st) for c, ops, st in calls]
    assert got == NO_WINDOW_KERNELS[cell]
    windowed = sa._statics(q, k, v, True, None, False, 512)
    assert windowed["window"] == 512
    for (c, ops, st), plain in zip(calls, got):
        with_window = _kernel_counts(c, ops, dict(st, window=512))
        assert with_window[:3] == plain[:3]
        assert with_window[4] > plain[4]


# ---- the hybrid program against the plain reference --------------------------------

def _cfg(**over):
    bench = harness.load_benchmark(ROOT)
    _, cfg, traffic = harness.resolve_cell(bench, CELL, tiny=True)
    return dict(cfg, **over), traffic


def _readings(cfg, traffic, seed):
    ref = harness.load_reference(cfg["reference"], ROOT)
    batches = gen.make_batches(traffic, cfg["vocab_size"], seed)
    w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
    want = gen.reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
    model = harness.load_module("models", cfg["builder"], ROOT).build_train(
        cfg, traffic, jax.devices()[:1])
    model.set_weights(w0)
    names = list(model.main.step_stats[1])
    prog = gen.program_readings(
        model, [model.make_feed(b) for b in batches], w0, cfg["adam_beta1"],
        3, want["first_grad"], names)
    model.close()
    return prog, want


@pytest.mark.parametrize("seed", [1, 3000000019])
def test_the_float32_program_is_the_reference(seed):
    """Float32, tiny sizes, seeded weights: the loss of three steps, EVERY
    leaf's first gradient (norm of the difference over the leaf's norm),
    the parameters' change after three Adam steps, the state-space layer's
    final state and the step's counters agree with the plain reference to
    float32 rounding."""
    cfg, traffic = _cfg(precision="float32")
    prog, want = _readings(cfg, traffic, seed)
    gaps = gen.gaps(prog, want)
    assert gaps["loss_rel_gap"] < 2e-6
    assert gaps["scan_state_gap"] < 2e-5
    assert gaps["update_norm_gap"] < 5e-5
    for leaf, norm in want["grad_norms"].items():
        assert prog["grad_errors"][leaf] <= 1e-4 * norm + 1e-9, leaf
    for got, ref in zip(prog["stats"], want["stats"]):
        assert got["window_pair_share"] == pytest.approx(
            hd.window_pair_share(traffic["seq"], cfg["sliding_window"]))
        np.testing.assert_allclose(
            [got[n] for n in hd.HYBRID_STEP_STATS[1:]], ref, rtol=1e-4)


@pytest.mark.parametrize("seed", [2, 7])
def test_the_mixed_precision_program_stays_inside_the_tiny_limits(seed):
    """bf16 mixed precision with the scan in float32: inside the band the
    configuration's ``tiny.limits`` state (set from 16 seeds between the
    sound largest and the fp8 control's smallest)."""
    cfg, traffic = _cfg()
    assert cfg["precision"] == "bf16_amp"
    prog, want = _readings(cfg, traffic, seed)
    assert gen.checks_failed(prog, want, cfg["limits"]) == []


# ---- tied tables and the variables two layers read -----------------------------------

def _tiny_program():
    cfg, traffic = _cfg(precision="float32")
    model = harness.load_module("models", cfg["builder"], ROOT).build_train(
        cfg, traffic, jax.devices()[:1])
    return cfg, traffic, model


def _sum_of(block, name):
    op, = [o for o in block.ops if o.type == "sum"
           and o.outputs["Out"] == [name + "@GRAD"]]
    return op.inputs["X"]


@pytest.mark.parametrize("body", ["xla", "segment"])
def test_the_tied_table_gets_the_lookups_rows_plus_the_heads_product(
        monkeypatch, body):
    """One parameter, two uses: the table's gradient is ONE ``sum`` of the
    lookup's scattered rows and the head's product; against the reference,
    the lookup's part alone is what an untied head leaves
    (``head_untied``) and the rest is the head's — with the lookup's rows
    added by the scattered add and by the sorted-segment kernel (interpreted
    here, where the tiny table's 97 x 64 need no whole tiles)."""
    from paddle_tpu import compile_cache
    from paddle_tpu.ops import manipulation

    if body == "segment":
        monkeypatch.setattr(manipulation, "segment_body",
                            lambda ctx, *shape: ctx.mesh is None)
    compile_cache.clear()
    before = dict(compile_cache.stats()["kernel_bodies"])
    cfg, traffic, model = _tiny_program()
    block = model.main.global_block()
    assert not any(v.name == "out_w" for v in block.all_parameters())
    parts = _sum_of(block, "tok_emb")
    assert len(parts) == 2
    makers = {n: [o.type for o in block.ops if n in o.output_arg_names][0]
              for n in parts}
    assert sorted(makers.values()) == ["lookup_table_grad", "matmul_grad"]
    ref = harness.load_reference(cfg["reference"], ROOT)
    seed = 5
    batches = gen.make_batches(traffic, cfg["vocab_size"], seed)
    w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
    model.set_weights(w0)
    # the first part is written under the total's own name (the ``sum``
    # overwrites it): the renamed part and the total are what can be read
    renamed, = [n for n in parts if n != "tok_emb@GRAD"]
    assert makers[renamed] == "lookup_table_grad"
    with fluid.scope_guard(model.scope):
        got = dict(zip([renamed, "tok_emb@GRAD"], model._exe.run(
            model.main, feed=model.make_feed(batches[0]),
            fetch_list=[renamed, "tok_emb@GRAD"])))
    p = {n: jnp.asarray(v) for n, v in w0.items()}
    b = {n: jnp.asarray(v, jnp.int32) for n, v in batches[0].items()}
    whole = ref.loss_and_grad(p, b, cfg, cfg["reference_block_rows"])[3]
    lookup = ref.loss_and_grad(p, b, dict(cfg, fault="head_untied"),
                               cfg["reference_block_rows"])[3]
    head = whole["tok_emb"] - lookup["tok_emb"]
    assert min(float(jnp.linalg.norm(head)),               # both are there
               float(jnp.linalg.norm(lookup["tok_emb"]))) > 0.0
    assert _rel(got[renamed], lookup["tok_emb"]) < 1e-4
    assert _rel(got["tok_emb@GRAD"], whole["tok_emb"]) < 1e-4
    assert _rel(got["tok_emb@GRAD"] - got[renamed], head) < 1e-4
    bodies = compile_cache.stats()["kernel_bodies"]
    assert bodies.get("lookup_table_grad:" + body, 0) \
        > before.get("lookup_table_grad:" + body, 0)
    model.close()
    compile_cache.clear()


def test_shared_keys_values_and_memory_sum_their_two_readers():
    """The full layer's K (each softmax map's) is read by its own layer and
    by the cross layer, its V by both maps of both, the Mamba layer's scan
    output by its own gate and by the gated memory unit: each variable's
    gradient is ONE ``sum`` of its readers' contributions, each from another
    reader's gradient op and none of them negligible.  (That the sums are the
    RIGHT gradients is the float32 program's agreement with the reference,
    leaf by leaf, above.)"""
    cfg, traffic, model = _tiny_program()
    block = model.main.global_block()
    atts = [o for o in block.ops if o.type == "fused_attention"]
    assert len(atts) == 6 and [bool(o.attrs.get("window")) for o in atts] \
        == [True, True, False, False, False, False]
    full, cross = atts[2:4], atts[4:6]
    for a, c in zip(full, cross):       # the cross layer reads the full's
        assert a.inputs["K"] == c.inputs["K"] and a.inputs["V"] == c.inputs["V"]
    assert full[0].inputs["V"] == full[1].inputs["V"]
    assert full[0].inputs["K"] != full[1].inputs["K"]
    scan, = [o for o in block.ops if o.type == "selective_scan"]
    memory = scan.outputs["Out"][0]
    readers = [o for o in block.ops if memory in o.input_arg_names
               and not o.type.endswith("_grad")]
    assert [o.type for o in readers][:2] == ["swiglu", "swiglu"]
    shared = {full[0].inputs["K"][0]: 2, full[1].inputs["K"][0]: 2,
              full[0].inputs["V"][0]: 4, memory: 2}
    # a variable's first part is written under the total's own name (the
    # ``sum`` overwrites it): the renamed parts and the total can be read,
    # and the first part is what the renamed ones leave of the total
    fetch, makers = [], {}
    for name, n in shared.items():
        parts = _sum_of(block, name)
        assert len(parts) == n and parts[0] == name + "@GRAD", name
        makers[name] = [[o for o in block.ops if p in o.output_arg_names][0]
                        for p in parts]
        assert len({id(o) for o in makers[name]}) == n      # n readers
        fetch += parts
    kinds = {name: sorted(o.type for o in ops) for name, ops in makers.items()}
    assert kinds[memory] == ["swiglu_grad", "swiglu_grad"]
    assert all(kinds[o.inputs[s][0]] == ["fused_attention_grad"] * n
               for o, s, n in ((full[0], "K", 2), (full[1], "K", 2),
                               (full[0], "V", 4)))
    seed = 9
    ref = harness.load_reference(cfg["reference"], ROOT)
    batches = gen.make_batches(traffic, cfg["vocab_size"], seed)
    model.set_weights(gen.seeded_weights(ref.param_spec(cfg), cfg, seed))
    with fluid.scope_guard(model.scope):
        got = dict(zip(fetch, model._exe.run(
            model.main, feed=model.make_feed(batches[0]), fetch_list=fetch)))
    for name in shared:
        total, *renamed = _sum_of(block, name)
        first = np.asarray(got[total], np.float64) - sum(
            np.asarray(got[p], np.float64) for p in renamed)
        for part in [first] + [got[p] for p in renamed]:
            assert np.abs(part).max() > 1e-3 * np.abs(got[total]).max(), name
    model.close()


def test_the_program_is_one_step_with_its_counters_and_layer_norms():
    cfg, traffic, model = _tiny_program()
    block = model.main.global_block()
    assert model.main.step_stats[1] == hd.HYBRID_STEP_STATS
    types = [o.type for o in block.ops]
    assert types.count("layer_norm") == 2 * len(cfg["layer_kinds"]) + 1
    assert types.count("selective_scan") == 1 == types.count(
        "selective_scan_grad")
    assert types.count("causal_conv1d") == 1
    assert "rotary_embedding" not in types          # no positional encoding
    names = sorted(p.name for p in block.all_parameters())
    ref = harness.load_reference(cfg["reference"], ROOT)
    assert names == sorted(ref.param_spec(cfg))
    assert hd.lambda_init(15) == pytest.approx(0.8 - 0.6 * np.exp(-4.5))
    model.close()
