"""The gated delta rule's Pallas kernels (``ops/pallas/gated_delta_rule.py``)
interpreted on the CPU, held to the op's XLA body — ``rule_xla`` between
``_prelude`` and ``_finish``, and ``jax.vjp`` of it, which is what
``rule_grad_xla`` computes group by group — and the op's rule for them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu.ops import gated_delta_rule as gdr
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import gated_delta_rule as kernels

D = 128
SCALE, EPS = D ** -0.5, 1e-5


def _inputs(t, h, dtype=jnp.float32, decay=1.0, seed=0, b=1):
    """The op's nine inputs, in ``_SLOTS``' order."""
    r = np.random.RandomState(seed)

    def normal(*shape):
        return jnp.asarray(r.randn(*shape), jnp.float32)
    q, k, v, g, gate = (normal(b, t, h, D) for _ in range(5))
    beta = jax.nn.sigmoid(normal(b, t, h))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
            (g * decay).astype(dtype), beta, normal(h) * 0.5,
            normal(h, D) * 0.5, gate.astype(dtype), 1 + 0.1 * normal(D))


def _xla(ins, chunk):
    *ops, gate, gain = gdr._prelude(*ins, SCALE)
    out, state, starts = gdr.rule_xla(*ops, chunk)
    return gdr._finish(out, gate, gain, EPS), state, starts


def _gap(a, b):
    a, b = (np.asarray(x, np.float32).ravel() for x in (a, b))
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _both(ins, chunk, heads, monkeypatch, seed=1):
    """``(the kernels' results, the XLA body's)``: the three outputs and the
    nine gradients under a random cotangent of ``Out``."""
    monkeypatch.setattr(kernels, "heads_per_step", lambda *a: heads)
    pallas.traced.cache_clear()
    b, t, h = ins[4].shape
    group = gdr._group(t // chunk)
    dout = jnp.asarray(np.random.RandomState(seed).randn(b, t, h, D),
                       jnp.float32)
    got = kernels.forward(*ins, chunk=chunk, group=group, scale=SCALE,
                          eps=EPS, interpret=True)
    grads = kernels.backward(*ins, dout, chunk=chunk, scale=SCALE, eps=EPS,
                             interpret=True)
    want, pull = jax.vjp(lambda *a: _xla(a, chunk), *ins)
    zeros = tuple(jnp.zeros_like(x) for x in want[1:])
    pallas.traced.cache_clear()
    return tuple(got) + tuple(grads), tuple(want) + tuple(
        pull((dout,) + zeros))


_NAMES = ("Out", "State", "Starts") + tuple("d" + s for s in gdr._SLOTS)


@pytest.mark.parametrize("t,chunk,heads,group", [
    (128, 64, 1, 2),        # one group of two chunks, a head a step
    (192, 32, 2, 6),        # two sub-blocks a chunk: one merge; two heads
    (272, 16, 2, 1),        # 17 chunks: every chunk a group of its own
])
def test_the_kernels_are_the_xla_body(t, chunk, heads, group, monkeypatch):
    assert gdr._group(t // chunk) == group
    got, want = _both(_inputs(t, 2), chunk, heads, monkeypatch)
    for name, a, w in zip(_NAMES, got, want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        if name == "Starts" and group == t // chunk:
            assert not np.asarray(a).any()      # one group: it starts on 0
            continue
        # three bf16 passes a product against the CPU's exact float32; ALog's
        # gradient is one number a head, a sum whose terms cancel
        assert _gap(a, w) < (3e-4 if name == "dALog" else 1e-4), name


def test_a_group_boundary_keeps_the_state_the_next_group_starts_on(
        monkeypatch):
    """Groups of more than one chunk and more than one group: ``Starts``
    holds the state at chunks 0 and 2 of 4, and the backward crosses the
    boundary with ``dS``."""
    monkeypatch.setattr(gdr, "GROUP", 2)
    got, want = _both(_inputs(128, 2, seed=3), 32, 2, monkeypatch)
    assert got[2].shape == (1, 2, 2, D, D)
    assert np.asarray(got[2][:, 1]).any()
    for name, a, w in zip(_NAMES, got, want):
        assert _gap(a, w) < (3e-4 if name == "dALog" else 1e-4), name


def test_bf16_operands_are_widened_inside_and_gradients_leave_as_they_came(
        monkeypatch):
    ins = _inputs(128, 2, jnp.bfloat16)
    got, want = _both(ins, 64, 2, monkeypatch)
    for name, a, w, x in zip(_NAMES[3:], got[3:], want[3:], ins):
        assert a.dtype == x.dtype, name
        # a bf16 result's last bit flips where the float32 behind it differs
        assert _gap(a, w) < 1e-3, name
    for a, w in zip(got[:3], want[:3]):
        assert a.dtype == jnp.float32 and _gap(a, w) < 1e-4


def test_a_chunk_that_decays_past_e_minus_88_and_rows_without_a_write(
        monkeypatch):
    """Log-decays of ~-8 a step: a chunk of 64 passes ``e^-500``, where
    ``exp(-G_j)`` would be ``inf``; and ``beta = 0`` rows, which write
    nothing.  No ``inf``, no ``nan``, and still the XLA body."""
    q, k, v, g, beta, alog, dt, gate, gain = _inputs(128, 2, decay=0.1)
    ins = (q, k, v, g + 6.0, beta.at[:, 5:40].set(0.0), alog + 1.0, dt, gate,
           gain)
    got, want = _both(ins, 64, 1, monkeypatch)
    g_rule = gdr._prelude(*ins, SCALE)[3]
    assert float(jnp.sum(g_rule[0, :64], 0).min()) < -200
    for name, a, w in zip(_NAMES, got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        if name in ("dG", "dALog", "dDtBias"):
            # nothing reaches past such a decay: these are ~1e-8 of the other
            # gradients in both bodies, each body's own rounding
            assert np.abs(np.asarray(a - w)).max() < {
                "dG": 1e-5, "dDtBias": 1e-3, "dALog": 1e-2}[name], name
        elif name != "Starts":
            assert _gap(a, w) < 2e-4, name


def test_a_gate_far_below_zero_still_decays(monkeypatch):
    """Gates of ~-17 under a rate of e^3: a step decays by ~e^-17 x 20, which
    ``log(1 + e^x)`` rounds to nothing (1 + 4e-8 is 1 in float32) and
    ``log1p`` keeps — over 272 steps the two are 1e-4 of the log-decay
    apart, and the kernels read what the XLA body's softplus reads."""
    q, k, v, g, beta, alog, dt, gate, gain = _inputs(272, 2, decay=0.3)
    ins = (q, k, v, g - 17.0, beta, alog * 0.1 + 3.0, dt * 0.1, gate, gain)
    g_rule = gdr._prelude(*ins, SCALE)[3]
    assert -2e-5 < float(g_rule.min()) and float(g_rule.max()) < -1e-8
    got, want = _both(ins, 16, 2, monkeypatch)
    lost = np.asarray(want[1]) * (np.exp(-float(jnp.sum(g_rule, 1).min())) - 1)
    assert np.abs(lost).max() > 20 * np.abs(np.asarray(got[1] - want[1])).max()
    for name, a, w in zip(_NAMES[:2], got, want):
        assert _gap(a, w) < 1e-4, name


def test_three_passes_by_hand_are_precision_high():
    """``_mm``'s hi/lo split against ``jnp.matmul(precision=HIGH)``: on the
    CPU that is exact float32, and three passes leave ~2^-16 of a term."""
    r = np.random.RandomState(0)
    a = jnp.asarray(r.randn(2, 64, 128), jnp.float32)
    b = jnp.asarray(r.randn(2, 128, 64), jnp.float32)
    want = jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    assert _gap(kernels._mm(a, b), want) < 2e-5
    one_pass = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    assert _gap(one_pass, want) > 1e-3
    # the transposed forms are the same products
    assert _gap(kernels._mm(a, jnp.swapaxes(b, 1, 2), kernels._NT),
                want) < 2e-5
    assert _gap(kernels._mm(jnp.swapaxes(a, 1, 2), b, kernels._TN),
                want) < 2e-5
    # and a cumulative sum is float32's own
    tri = jnp.tril(jnp.ones((64, 64), jnp.bfloat16))
    x = jnp.asarray(r.randn(2, 64, 128) * 100, jnp.float32)
    np.testing.assert_allclose(kernels._sum_along(x, tri), jnp.cumsum(x, 1),
                               rtol=1e-6, atol=1e-3)


@pytest.mark.parametrize("q_shape,chunk,taken", [
    ((1, 128, 4, 128), 64, True),
    ((1, 128, 32, 256), 32, True),
    ((1, 80, 4, 128), 64, False),       # a ragged tail: the XLA body pads
    ((1, 128, 4, 64), 64, False),       # half a lane tile
    ((1, 512, 4, 128), 256, False),     # a chunk wider than a lane tile
])
def test_supported_says_which_calls_the_kernels_take(q_shape, chunk, taken):
    assert kernels.supported(q_shape, q_shape, chunk) is taken
    if taken:
        hb = kernels.heads_per_step(q_shape[2], q_shape[3], q_shape[3], chunk)
        assert q_shape[2] % hb == 0


def _program(t, d):
    shapes = {"q": (1, t, 2, d), "k": (1, t, 2, d), "v": (1, t, 2, d),
              "g": (1, t, 2, d), "beta": (1, t, 2), "gate": (1, t, 2, d)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds = {n: fluid.layers.data(n, shape=list(s[1:]), dtype="float32")
                 for n, s in shapes.items()}
        for f in feeds.values():
            f.stop_gradient = False
        a_log, dt_bias = (fluid.layers.create_parameter(
            list(shape), "float32", attr=fluid.ParamAttr(name=n))
            for n, shape in (("a_log", (2,)), ("dt_bias", (2, d))))
        out = feeds["v"]
        for _ in range(2):      # two sites of each kernel, one trace
            out, _ = fluid.layers.gated_delta_rule(
                feeds["q"], feeds["k"], out, feeds["g"], feeds["beta"],
                a_log, dt_bias, feeds["gate"], d ** -0.5, chunk=64)
        fluid.optimizer.SGD(learning_rate=0.0).minimize(
            fluid.layers.reduce_sum(out))
    r = np.random.RandomState(0)
    feed = {n: r.randn(*s).astype("float32") for n, s in shapes.items()}
    feed["beta"] = 1 / (1 + np.exp(-feed["beta"]))
    return main, startup, feed, [out, "q@GRAD", "a_log@GRAD"]


@pytest.mark.parametrize("refusal", ["taken", "a_mesh", "the_flag_off",
                                     "dk_64", "a_ragged_tail", "the_cpu"])
def test_the_rule_picks_the_kernels_by_what_the_op_observes(
        refusal, monkeypatch, request):
    """Through the executor: ``:chunked`` for both ops where the rule holds
    and every site after the first reuses the kernel's one trace; ``:xla``
    for each refusal."""
    if refusal != "the_cpu":
        monkeypatch.setattr(gdr, "_KERNEL_PLATFORMS", ("tpu", "cpu"))
    if refusal == "the_flag_off":
        request.getfixturevalue("no_pallas")
    pallas.traced.cache_clear()
    compile_cache.clear()
    main, startup, feed, fetch = _program(
        80 if refusal == "a_ragged_tail" else 128,
        64 if refusal == "dk_64" else 128)

    def noted():
        stats = compile_cache.stats()
        traces = stats["kernel_traces"].get("gated_delta_rule", {})
        return [stats["kernel_bodies"].get(op + body, 0)
                for body in (":chunked", ":xla")
                for op in ("gated_delta_rule", "gated_delta_rule_grad")] \
            + [traces.get("traces", 0), traces.get("sites", 0)]
    with fluid.scope_guard(fluid.Scope()):
        before = noted()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if refusal == "a_mesh":
            class Meshed:
                platform, mesh = "cpu", object()
            ins = {s: [jnp.zeros((1, 128, 2, 128))] for s in "QV"}
            assert gdr._kernels(Meshed, "gated_delta_rule", ins, 64) is False
            assert gdr._kernels(Meshed, "gated_delta_rule_grad", ins,
                                64) is False
        else:
            got = exe.run(main, feed=feed, fetch_list=fetch)
            assert all(np.isfinite(np.asarray(x)).all() for x in got)
        moved = [a - b for a, b in zip(noted(), before)]
    if refusal == "taken":
        # three kernels, two sites of each every time the step is traced
        assert moved[:5] == [2, 2, 0, 0, 3]
        assert moved[5] >= 6 and moved[5] % 6 == 0
    else:
        assert moved == [0, 0] + [1 if refusal == "a_mesh" else 2] * 2 \
            + [0, 0]
    pallas.traced.cache_clear()
    compile_cache.clear()
