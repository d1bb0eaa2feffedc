"""What the latent-attention mixture-of-experts decoder with its
multi-token-prediction module (ISSUE 30) added, on the CPU at small sizes:
the router's scoring functions and selection-only bias, interleaved rotary,
``fused_attention`` with values narrower than the keys (XLA body against
the streamed kernels, interpreted), which attention calls keep their rows'
log-sum-exp, the expert layer's shares with the shared expert counted once,
and the tiny model's training against the plain reference."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from op_test import OpTest
from paddle_tpu import compile_cache
from paddle_tpu.ops import attention as att
from paddle_tpu.ops import moe
from paddle_tpu.ops import attention_xla as fa
from paddle_tpu.ops.pallas import streamed_attention as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, weights                        # noqa: E402

CELL = "joyai_llm_flash.train_mtp_8k"


def _rand(shape, seed=0, scale=1.0):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(
        "float32")


def _cfg(**over):
    bench = harness.load_benchmark(ROOT)
    _, cfg, traffic = harness.resolve_cell(bench, CELL, tiny=True)
    return dict(cfg, **over), traffic


# ---- moe_router ----------------------------------------------------------------

def _router(x, w, attrs, bias=None):
    ins = {"X": [x], "W": [w]}
    if bias is not None:
        ins["Bias"] = [bias]
    return moe._router_compute(ins, dict(attrs, top_k=2), None, 0)


def test_router_default_is_bit_equal_to_softmax_then_top_k():
    """No score function, no bias, no scale: the program text and the
    numbers of the softmax router are those of before this op grew."""
    x, w = jnp.asarray(_rand((64, 16), 1)), jnp.asarray(_rand((16, 8), 2))
    got = _router(x, w, {})
    p = jax.nn.softmax(jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST),
                       -1)
    top, idx = jax.lax.top_k(p, 2)
    assert np.array_equal(got["TopkIdx"], idx)
    assert np.array_equal(got["TopkWeight"],
                          top / jnp.sum(top, -1, keepdims=True))
    for name in ("softmax", "sigmoid"):
        assert np.array_equal(
            _router(x, w, {"score_func": name})["TopkWeight"],
            _router(x, w, {"score_func": name, "scale": 1.0})["TopkWeight"])


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_router_bias_moves_the_selection_and_not_the_weights(score):
    x, w = jnp.asarray(_rand((64, 16), 3)), jnp.asarray(_rand((16, 8), 4))
    attrs = {"score_func": score, "scale": 2.5}
    plain = _router(x, w, attrs)
    zero = _router(x, w, attrs, jnp.zeros((8,)))
    assert np.array_equal(plain["TopkIdx"], zero["TopkIdx"])
    np.testing.assert_allclose(plain["TopkWeight"], zero["TopkWeight"],
                               rtol=1e-6)
    np.testing.assert_allclose(plain["TopkWeight"].sum(-1), 2.5, rtol=1e-5)
    # a large bias on expert 5 puts it in every token's selection ...
    bias = jnp.zeros((8,)).at[5].set(10.0)
    got = _router(x, w, attrs, bias)
    assert bool(jnp.all(jnp.any(got["TopkIdx"] == 5, -1)))
    assert not bool(jnp.all(jnp.any(plain["TopkIdx"] == 5, -1)))
    # ... and the weights are still the UNBIASED scores of the chosen,
    # renormalised over them, times the scale
    z = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(z, -1) if score == "softmax" else jax.nn.sigmoid(z)
    top = jnp.take_along_axis(s, got["TopkIdx"], -1)
    np.testing.assert_allclose(
        got["TopkWeight"], 2.5 * top / top.sum(-1, keepdims=True), rtol=1e-5)


def test_router_bias_has_no_gradient_and_no_optimizer_state():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[16], dtype="float32")
        y, _, _ = fluid.layers.routed_experts(
            x, 8, 2, 8, held=2, first=2, tile=8, score_func="sigmoid",
            weight_scale=2.5, bias_attr=fluid.ParamAttr(name="bias"),
            router_attr=fluid.ParamAttr(name="router"),
            shared_width=8)
        fluid.optimizer.Adam(1e-3).minimize(fluid.layers.mean(y))
    with pytest.raises(ValueError, match="score_func"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            fluid.layers.routed_experts(x, 8, 2, 8, score_func="tanh")
    block = main.global_block()
    router = next(op for op in block.ops if op.type == "moe_router")
    assert router.inputs["Bias"] == ["bias"]
    assert router.attrs["score_func"] == "sigmoid"
    names = set(block.vars)
    assert "router@GRAD" in names and "bias@GRAD" not in names
    assert "router_moment1_0" in names and "bias_moment1_0" not in names
    assert not block.vars["bias"].trainable
    # the shared expert is plain ops: two products, the gate, one product
    types = [op.type for op in block.ops]
    assert types.count("swiglu") == 1 and types.count("mul") == 3
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        assert np.array_equal(np.asarray(scope.find_var("bias")), np.zeros(8))
        exe.run(main, feed={"x": _rand((32, 16), 5)}, fetch_list=[y])
        assert np.array_equal(np.asarray(scope.find_var("bias")), np.zeros(8))


# ---- rotary, interleaved ---------------------------------------------------------

def _np_rotary_pairs(x, theta):
    """Written out: pair j = (x[2j], x[2j+1]) turned by t * theta^(-2j/D)."""
    out = np.empty_like(x, dtype=np.float64)
    d = x.shape[-1]
    for t in range(x.shape[1]):
        for j in range(d // 2):
            a = t * theta ** (-2.0 * j / d)
            c, s = np.cos(a), np.sin(a)
            out[:, t, ..., 2 * j] = x[:, t, ..., 2 * j] * c \
                - x[:, t, ..., 2 * j + 1] * s
            out[:, t, ..., 2 * j + 1] = x[:, t, ..., 2 * j + 1] * c \
                + x[:, t, ..., 2 * j] * s
    return out


def _rotary_case():
    t = OpTest()
    x = _rand((2, 5, 3, 8), 3)
    t.op_type = "rotary_embedding"
    t.attrs = {"theta": 3.2e7, "interleaved": True}
    t.inputs = {"X": x}
    t.outputs = {"Out": _np_rotary_pairs(x, 3.2e7).astype("float32")}
    return t


def test_interleaved_rotary_against_a_written_out_rotation():
    _rotary_case().check_output(atol=1e-5)
    ref = harness.load_reference("latent_moe_decoder")
    x = _rand((5, 3, 8), 4)
    np.testing.assert_allclose(ref.rotary_pairs(jnp.asarray(x), 1e4),
                               _np_rotary_pairs(x[None], 1e4)[0],
                               rtol=1e-5, atol=1e-5)


def test_interleaved_rotary_gradient():
    _rotary_case().check_grad(["rotary_embedding__X"],
                              "rotary_embedding__Out",
                              max_relative_error=5e-3)


# ---- attention with values narrower than the keys ----------------------------------

def _latent_qkv(h=2, t=256, nope=128, rope=64, dv=128, seed=0):
    """q [1, h, t, nope + rope]; keys whose last ``rope`` columns are ONE
    shared part, as the model joins them; v [1, h, t, dv]."""
    q = jnp.asarray(_rand((1, h, t, nope + rope), seed, 0.5))
    k_nope = jnp.asarray(_rand((1, h, t, nope), seed + 1, 0.5))
    kr = jnp.asarray(_rand((1, 1, t, rope), seed + 2, 0.5))
    v = jnp.asarray(_rand((1, h, t, dv), seed + 3))
    return q, k_nope, kr, v


def _join(k_nope, kr):
    return jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr, k_nope.shape[:3] + kr.shape[3:])], -1)


@pytest.mark.parametrize("t,causal,h,a_step", [
    (256, True, 2, 2), (384, True, 2, 2), (384, False, 2, 2),
    # more than one block of heads a grid: 8 heads by 4, 6 by 3 and by 2
    (384, True, 8, 4), (384, False, 8, 4), (384, True, 6, 3),
    (384, True, 6, 2)])
def test_streamed_kernel_with_192_wide_keys_matches_the_xla_body(
        t, causal, h, a_step, monkeypatch):
    """Forward and the gradients with respect to q, each head's own key
    part, the SHARED rotary key part (the heads' sum) and v; ``a_step``
    plain heads a grid step (the VMEM budget is what that many take)."""
    q, k_nope, kr, v = _latent_qkv(h=h, t=t)
    scale = 192 ** -0.5
    assert sa.supported(q.shape, q.shape, jnp.float32, causal, False, 0.0,
                        128)
    block = sa._pick_blocks(t)
    monkeypatch.setattr(sa, "_VMEM_BUDGET", sa._step_bytes(
        1, block, block, 192, 4, 128, a_step))
    assert sa.step_heads(q, q, v) == (a_step, 1)

    def xla(q, k_nope, kr, v):
        return fa.reference_attention(q, _join(k_nope, kr), v, None, None,
                                      causal, 0.0, scale)

    def kernel(q, k_nope, kr, v):
        return sa.streamed_attention(q, _join(k_nope, kr), v, None, causal,
                                     scale, True)
    want = xla(q, k_nope, kr, v)
    assert want.shape == (1, h, t, 128)
    np.testing.assert_allclose(kernel(q, k_nope, kr, v), want, rtol=1e-5,
                               atol=1e-5)
    # the rows' log-sum-exp, which the backward reads: the forward's running
    # maximum and per-lane sums put together, to float32
    k = _join(k_nope, kr)
    np.testing.assert_allclose(
        sa.forward(q, k, v, None, causal, scale, True)[1],
        fa.reference_attention(q, k, v, None, None, causal, 0.0, scale, None,
                               True)[1], rtol=1e-6, atol=2e-6)
    ct = jnp.asarray(_rand(want.shape, 12))
    args = (q, k_nope, kr, v)
    want = jax.grad(lambda *a: jnp.sum(xla(*a) * ct), (0, 1, 2, 3))(*args)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * ct), (0, 1, 2, 3))(*args)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t,causal,h,a_step", [
    (384, True, 4, 1), (384, True, 4, 2), (384, True, 4, 4),
    (384, False, 4, 2), (640, True, 2, 2), (256, True, 2, 1)])
def test_fused_backward_with_192_wide_keys_against_the_two_kernels(
        t, causal, h, a_step, monkeypatch):
    """Keys wider than values (192 over 128), plain heads, ``a_step`` a grid
    step — each with its own K/V block and its own resident float32 dK
    ``[T, 192]`` and dV ``[T, 128]``: the fused backward against ``jax.vjp``
    of the XLA body and, bit for bit, the dQ and dK/dV kernels; three, five
    and two blocks a row."""
    from streamed_backward import check_fused_backward

    q, k_nope, kr, v = _latent_qkv(h=h, t=t)
    check_fused_backward(monkeypatch, q, _join(k_nope, kr), v,
                         jnp.asarray(_rand(v.shape, 12)), None, causal,
                         192 ** -0.5, heads=(a_step, 1))


@pytest.mark.parametrize("selected", [False, True])
@pytest.mark.parametrize("dk,dv", [(128, 128), (192, 128), (256, 256)])
@pytest.mark.parametrize("a_step", [1, 2, 4, 8])
def test_plain_heads_a_step_give_the_bits_of_one_head_a_step(
        a_step, dk, dv, selected, monkeypatch):
    """8 plain heads, ``a_step`` of them a grid step (the rule answers what
    the test tells it to), causal over one block pair of 256 (values one
    lane tile wide or two), with a selection or without: forward,
    log-sum-exp, dQ, dK and dV are, bit for
    bit, those of one head a step — a head's arithmetic does not depend on
    which heads share its step — and within the standing tolerance of the
    XLA body."""
    from paddle_tpu.ops import sparse_select as ss

    h, t, scale = 8, 256, dk ** -0.5
    q, k, v, ct = (jnp.asarray(_rand((1, h, t, w), i, 0.5))
                   for i, w in enumerate((dk, dk, dv, dv)))
    packed = ss.pack_key_mask(ss.topk_key_mask(
        jnp.asarray(_rand((1, t, t), 9)), 48, True)) if selected else None

    def kernels(n):
        monkeypatch.setattr(sa, "_heads_per_step", lambda *a: (n, 1))
        monkeypatch.setattr(sa, "_fused_heads_per_step", lambda *a: (n, 1))
        assert sa.step_heads(q, k, v) == (n, 1)
        assert sa.grad_step(q, k, v) == ("streamed_fused", (n, 1))
        out, lse = sa.forward(q, k, v, packed, True, scale, True)
        return (out, lse) + sa.backward(q, k, v, packed, out, lse, ct, True,
                                        scale, True)
    got, one = kernels(a_step), kernels(1)
    for a, b in zip(got, one):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def xla(q, k, v):
        return fa.reference_attention(q, k, v, None, None, True, 0.0, scale,
                                      packed, True)
    (out, lse), vjp = jax.vjp(xla, q, k, v)
    np.testing.assert_allclose(got[0], out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], lse, rtol=1e-5, atol=1e-5)
    for a, b in zip(got[2:], vjp((ct, jnp.zeros_like(lse)))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _latent_program(h, t, dk, dv, **kw):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = fluid.layers.data("q", shape=[h, t, dk], dtype="float32")
        k = fluid.layers.data("k", shape=[h, t, dk], dtype="float32")
        v = fluid.layers.data("v", shape=[h, t, dv], dtype="float32")
        for x in (q, k, v):
            x.stop_gradient = False
        out = fluid.layers.fused_attention(q, k, v, causal=True,
                                           scale=dk ** -0.5, **kw)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, out))
        fluid.append_backward(loss)
    return main, out


@pytest.mark.parametrize("body", ["xla", "streamed"])
def test_fused_attention_op_takes_values_narrower_than_keys(body,
                                                            monkeypatch):
    """Through the op and its gradient op: ``Out`` is [B, H, T, Dv]; the
    XLA body on the CPU, the streamed kernels (and their backward on the
    forward op's own log-sum-exp) when the test lets the CPU trace take
    them."""
    if body == "streamed":
        monkeypatch.setattr(att, "_STREAMED_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    q, k_nope, kr, v = _latent_qkv()
    k = _join(k_nope, kr)
    main, out = _latent_program(2, 256, 192, 128)
    op = next(o for o in main.global_block().ops
              if o.type == "fused_attention")
    assert op.attrs["keep_lse"] and op.outputs["LSE"]
    assert tuple(out.shape[1:]) == (2, 256, 128)

    def bodies():
        got = compile_cache.stats()["kernel_bodies"]
        # the notes: both plain heads in one grid step, each its own K/V
        # head, forward and backward; the last: calls that reached the
        # streamed kernels (the forward and the fused backward)
        return (got.get("fused_attention:" + body, 0),
                got.get("fused_attention_grad:streamed_fused", 0),
                got.get("streamed_step:2x1", 0),
                got.get("streamed_grad_step:2x1", 0),
                compile_cache.stats()["kernel_traces"].get(
                    "streamed_attention", {}).get("sites", 0))
    before = bodies()
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"q": np.asarray(q), "k": np.asarray(k),
                    "v": np.asarray(v)},
        fetch_list=[out, "q@GRAD", "k@GRAD", "v@GRAD"])
    after = bodies()
    assert tuple(x - y for x, y in zip(after, before)) == (
        (1, 1, 1, 1, 2) if body == "streamed" else (2, 0, 0, 0, 0))

    def dense(q, k, v):
        return fa.reference_attention(q, k, v, None, None, True, 0.0,
                                      192 ** -0.5)
    np.testing.assert_allclose(got[0], dense(q, k, v), rtol=1e-4, atol=1e-4)
    grads = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(got[1:], grads):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    compile_cache.clear()


# (H, Hkv, T, Dk, Dv) -> the answer of the tree before PR 45 (where the
# bound was the flash kernel's ``supported(bf16, max_seq=T)``), generated
# from that tree and frozen: every decoder cell's program carries the answer
# as ``keep_lse``, so it must not move with the code that states it
PLAIN_HEADS_TABLE = [
    ((16, 16, 512, 64, 64), False), ((16, 16, 2048, 64, 64), False),
    ((16, 16, 4096, 64, 64), False), ((16, 16, 8192, 64, 64), False),
    ((16, 16, 512, 128, 128), False), ((16, 16, 2048, 128, 128), False),
    ((16, 16, 2560, 128, 128), False), ((16, 16, 3072, 128, 128), False),
    ((16, 16, 3584, 128, 128), False), ((16, 16, 3712, 128, 128), True),
    ((16, 16, 4096, 128, 128), True),
    ((16, 16, 8192, 128, 128), True),
    ((16, 16, 512, 192, 128), True), ((16, 16, 2048, 192, 128), True),
    ((16, 16, 8192, 192, 128), True),
    ((16, 16, 1024, 256, 256), False), ((16, 16, 1536, 256, 256), False),
    ((16, 16, 1792, 256, 256), True), ((16, 16, 2048, 256, 256), True),
    ((32, 4, 8192, 128, 128), True), ((16, 16, 2000, 128, 128), False),
    ((16, 16, 4096, 96, 96), False),
]


@pytest.mark.parametrize("shape,parents", PLAIN_HEADS_TABLE,
                         ids=["x".join(map(str, s))
                              for s, _ in PLAIN_HEADS_TABLE])
def test_streams_plain_heads_answers_as_the_parent_did(shape, parents):
    h, hk, t, dk, dv = shape
    assert att.streams_plain_heads((2, h, t, dk), (2, hk, t, dk),
                                   (2, hk, t, dv), False, 0.0) == parents


def test_which_attention_calls_keep_their_log_sum_exp():
    """Shapes alone decide: values of another width, or plain-head
    self-attention the streamed kernel takes at a length past the inherited
    resident-K/V bound (``_resident_kv_fits``).  The calls the benchmark's
    other steps make keep the bodies they had: Transformer-base's (64-wide
    heads, padding masks, T = 64; cross attention) and anything short."""
    long, short = (1, 32, 8192, 128), (256, 8, 64, 64)
    assert att.streams_plain_heads(long, long, long, False, 0.0)
    assert att.streams_plain_heads((1, 32, 8192, 192), (1, 32, 8192, 192),
                                   long, False, 0.0)
    assert not att.streams_plain_heads(long, long, long, True, 0.0)
    assert not att.streams_plain_heads(long, long, long, False, 0.1)
    assert not att.streams_plain_heads(short, short, short, True, 0.0)
    assert not att.streams_plain_heads(short, short, short, False, 0.0)
    mid = (2, 8, 2048, 128)        # inside the inherited bound
    assert att._resident_kv_fits(2048, 2048, 128)
    assert not att.streams_plain_heads(mid, mid, mid, False, 0.0)
    cross = (2, 8, 8192, 128)
    assert not att.streams_plain_heads((2, 8, 128, 128), cross, cross,
                                       False, 0.0)
    for shape, marked in ((long, True), (mid, False)):
        main, _ = _latent_program(shape[1], shape[2], 128, 128)
        op = next(o for o in main.global_block().ops
                  if o.type == "fused_attention")
        assert bool(op.attrs.get("keep_lse")) == marked
        assert bool(op.outputs.get("LSE")) == marked
    # built by hand without the LSE output, unequal widths are refused
    with pytest.raises(ValueError, match="log-sum-exp"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            q = fluid.layers.data("q", shape=[2, 16, 24], dtype="float32")
            v = fluid.layers.data("v", shape=[2, 16, 16], dtype="float32")
            helper = fluid.layer_helper.LayerHelper("fused_attention")
            out = helper.create_variable_for_type_inference(dtype="float32")
            helper.append_op(type="fused_attention",
                             inputs={"Q": [q], "K": [q], "V": [v]},
                             outputs={"Out": [out]}, attrs={})


def test_streamed_supported_takes_half_tile_keys_over_whole_tile_values():
    shape = (1, 32, 8192, 192)
    assert sa.supported(shape, shape, jnp.bfloat16, True, False, 0.0, 128)
    assert not sa.supported(shape, shape, jnp.bfloat16, True, False, 0.0, 192)
    assert not sa.supported((1, 32, 8192, 96), (1, 32, 8192, 96),
                            jnp.bfloat16, True, False, 0.0, 128)
    # the VMEM count takes a 192-wide row for the two lane tiles it fills
    assert sa._step_bytes(1, 512, 512, 192, 2, 128) == sa._step_bytes(
        1, 512, 512, 256, 2, 128)
    assert sa._step_bytes(8, 512, 512, 128, 2) == sa._step_bytes(
        8, 512, 512, 128, 2, 128)


# ---- the op over the projections' own layout ---------------------------------------

def _projections(b, n, t, nope, rope, dv, seed=0, dtype="float32"):
    """(q, kv, kr, ct) as a latent block's three products write them and
    the result's cotangent."""
    return tuple(jnp.asarray(_rand((b, t, w), seed + i, s)).astype(dtype)
                 for i, (w, s) in enumerate((
                     (n * (nope + rope), 0.5), (n * (nope + dv), 0.5),
                     (rope, 0.5), (n * dv, 1.0))))


def _bodies(*names):
    got = compile_cache.stats()["kernel_bodies"]
    return tuple(got.get(n, 0) for n in names)


# (heads, T, nope, rope, dv, theta, heads a step forward, backward, batch)
IN_PLACE_CASES = [
    (2, 256, 128, 64, 128, 1e4, 2, 2, 1),
    (4, 384, 128, 64, 128, 3.2e7, 4, 2, 1),       # three blocks a row
    (8, 256, 128, 64, 128, 1e4, 8, 4, 2),         # two rows a batch
    (8, 256, 128, 64, 128, None, 8, 8, 1),        # no rotation (NoPE)
    (4, 256, 128, 64, 128, None, 2, 2, 2),
    # an odd count of heads a step: rope of whole tiles, heads one by one
    (3, 256, 128, 128, 128, 1e4, 3, 3, 1),
    (6, 256, 128, 128, 128, None, 3, 1, 1),
    # two tiles of nope: the odd head's columns shift across three
    (2, 256, 256, 64, 128, 1e4, 2, 2, 1),
]


@pytest.mark.parametrize("case", IN_PLACE_CASES,
                         ids=["-".join(map(str, c)) for c in IN_PLACE_CASES])
def test_op_over_the_projections_layout_against_the_composed_block(
        case, monkeypatch):
    """``fused_attention`` over Q ``[B, T, H * (nope + rope)]``, KV ``[B, T,
    H * (nope + dv)]`` and the shared key part, through the executor with
    the streamed kernels interpreted, against the block as Fluid ops composed
    it around the 4-D op (its streamed kernels too): ``Out``, ``LSE``, dQ,
    dKV — keys' and values' columns —, dKShared; which is the heads' sum of
    the joined keys' rotary gradient, turned back."""
    from streamed_backward import latent_attention_programs

    n, t, nope, rope, dv, theta, forward, backward, b = case
    monkeypatch.setattr(att, "_STREAMED_PLATFORMS", ("tpu", "cpu"))
    monkeypatch.setattr(sa, "_in_place_heads_per_step", lambda *a: forward)
    monkeypatch.setattr(sa, "_in_place_fused_heads_per_step",
                        lambda *a: backward)
    compile_cache.clear()
    composed, one = latent_attention_programs(n, t, nope, rope, dv, theta,
                                              (nope + rope) ** -0.5)
    types = [op.type for op in one[0].global_block().ops]
    assert not {"transpose", "expand", "concat", "split", "reshape",
                "rotary_embedding"} & set(types)
    feed = dict(zip(("q", "kv", "kr", "ct"), map(np.asarray, _projections(
        b, n, t, nope, rope, dv))))
    names = ("fused_attention:streamed_inplace",
             "fused_attention_grad:streamed_fused_inplace",
             "streamed_step:%dx1" % forward,
             "streamed_grad_step:%dx1" % backward, "fused_attention:xla")
    exe = fluid.Executor(fluid.CPUPlace())
    want = exe.run(composed[0], feed=feed, fetch_list=composed[1])
    before = _bodies(*names)
    got = exe.run(one[0], feed=feed, fetch_list=one[1])
    assert tuple(x - y for x, y in zip(_bodies(*names), before)) == (
        1, 1, 1, 1, 0)
    assert got[0].shape == (b, t, n * dv) and got[1].shape == (b, n, t, 1)
    for a, w, tol in zip(got, want, (1e-5, 1e-5, 1e-4, 1e-4, 1e-4)):
        assert a.shape == w.shape and a.dtype == w.dtype
        np.testing.assert_allclose(a, w, rtol=tol, atol=tol)
    joined = want[5][..., nope:].sum(1)                     # [B, T, rope]
    turned = got[4] if theta is None else _np_rotary_pairs(got[4], theta)
    np.testing.assert_allclose(turned, joined, rtol=1e-4, atol=1e-4)
    compile_cache.clear()


def test_in_place_kernels_round_where_the_composed_block_rounded():
    """In bf16: the queries' rotation rounded once, then the scaled operand
    of the product, as ``rotary_embedding`` and the 4-D kernel round them —
    the two forms' outputs differ by the order of float32 sums alone."""
    n, t, nope, rope, dv, theta = 4, 256, 128, 64, 128, 3.2e7
    q, kv, kr, ct = _projections(1, n, t, nope, rope, dv,
                                 dtype=jnp.bfloat16)
    scale = (nope + rope) ** -0.5
    krr = att._rotated(kr, theta)
    q4 = q.reshape(1, t, n, nope + rope)
    q4 = jnp.concatenate([q4[..., :nope], att._rotated(q4[..., nope:],
                                                       theta)], -1)
    kv4 = kv.reshape(1, t, n, nope + dv)
    k4 = jnp.concatenate([kv4[..., :nope], jnp.broadcast_to(
        krr[:, :, None], (1, t, n, rope))], -1)
    want, want_lse = sa.forward(*(x.transpose(0, 2, 1, 3) for x in
                                  (q4, k4, kv4[..., nope:])), None, True,
                                scale, True)
    got, lse = sa.forward_in_place(q, kv, krr, n, dv, theta, True, scale,
                                   True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)
    a = np.asarray(got, "float32").reshape(1, t, n, dv)
    w = np.asarray(want, "float32").transpose(0, 2, 1, 3)
    assert (a != w).mean() < 0.01             # a last bit here and there
    np.testing.assert_allclose(a, w, rtol=2 ** -7, atol=1e-4)


def _in_place_ins(n=2, t=256, nope=128, rope=64, dv=128, b=1, **attrs):
    q, kv, kr, _ = _projections(b, n, t, nope, rope, dv)
    return ({"Q": [q], "K": [kv], "KShared": [kr]},
            dict({"causal": True, "n_head": n, "v_dim": dv,
                  "rope_theta": 1e4}, **attrs))


def _tpu(**kw):
    from paddle_tpu.registry import ComputeContext

    return ComputeContext(key=jax.random.key(0), platform="tpu", **kw)


IN_PLACE_DECLINES = {
    "a_mesh": (dict(), dict(mesh=True), False, 0.0),
    "a_key_length": (dict(), dict(), True, 0.0),
    "dropout": (dict(), dict(), False, 0.1),
    "rope_no_half_tile": (dict(rope=32), dict(), False, 0.0),
    "rope_three_half_tiles": (dict(rope=192), dict(), False, 0.0),
    "nope_no_tile": (dict(nope=64), dict(), False, 0.0),
    "values_no_tile": (dict(dv=64), dict(), False, 0.0),
    "a_ragged_block": (dict(t=200), dict(), False, 0.0),
    "an_odd_head_of_half_tiles": (dict(n=3), dict(), False, 0.0),
}


@pytest.mark.parametrize("why", sorted(IN_PLACE_DECLINES))
def test_in_place_rule_declines(why):
    from paddle_tpu.parallel.mesh import make_mesh

    sizes, ctx, has_klen, rate = IN_PLACE_DECLINES[why]
    ins, attrs = _in_place_ins()
    assert att._in_place_applicable(_tpu(), ins, attrs, False, 0.0)
    if ctx:
        ctx = dict(mesh=make_mesh((2,), ("dp",)))
    ins, attrs = _in_place_ins(**sizes)
    assert not att._in_place_applicable(_tpu(**ctx), ins, attrs, has_klen,
                                        rate)


def test_in_place_rule_wants_a_tpu_a_shared_part_and_the_operators_leave(
        no_pallas):
    from paddle_tpu import flags
    from paddle_tpu.registry import ComputeContext

    ins, attrs = _in_place_ins()
    assert not att._in_place_applicable(_tpu(), ins, attrs, False, 0.0)
    flags.set_flags({"FLAGS_pallas_kernels": True})
    assert att._in_place_applicable(_tpu(), ins, attrs, False, 0.0)
    assert not att._in_place_applicable(
        ComputeContext(key=jax.random.key(0), platform="cpu"), ins, attrs,
        False, 0.0)
    ins.pop("KShared")
    assert not att._in_place_applicable(
        _tpu(), {"Q": [ins["Q"][0][..., :2 * 128]], "K": ins["K"]}, attrs,
        False, 0.0)
    # 32k tokens: not even two heads' resident gradients fit the budget
    q, kv = (jax.ShapeDtypeStruct((1, 32768, 32 * w), jnp.bfloat16)
             for w in (192, 256))
    assert sa.in_place_supported(q.shape, kv.shape, 64, 32, 128, False, 0.0)
    assert sa.in_place_step(q, kv, 32, 128) == (8, None)


@pytest.mark.parametrize("declined", ["cpu", "key_length", "small_widths"])
def test_declined_in_place_op_takes_the_xla_body_with_the_same_numbers(
        declined):
    """The op's definition: what the composed block computes, whatever made
    the rule decline — the CPU, a ``k_len``, widths off the tiles."""
    from streamed_backward import latent_attention_programs

    n, t, nope, rope, dv, theta = (4, 48, 16, 8, 16, 1e4) \
        if declined == "small_widths" else (2, 256, 128, 64, 128, 3.2e7)
    compile_cache.clear()
    q, kv, kr, ct = _projections(2, n, t, nope, rope, dv)
    names = ("fused_attention:xla", "fused_attention:streamed_inplace")
    before = _bodies(*names)
    if declined == "key_length":
        ctx = fluid.registry.ComputeContext(key=jax.random.key(0),
                                            platform="cpu")
        k_len = jnp.asarray([t, t - 50], jnp.int32)
        ins, attrs = ({"Q": [q], "K": [kv], "KShared": [kr],
                       "KLen": [k_len]},
                      {"causal": True, "n_head": n, "v_dim": dv,
                       "rope_theta": theta})
        got = att._fused_attention_compute(ins, attrs, ctx, 0)
        q4 = q.reshape(2, t, n, nope + rope)
        q4 = jnp.concatenate([q4[..., :nope],
                              att._rotated(q4[..., nope:], theta)], -1)
        kv4 = kv.reshape(2, t, n, nope + dv)
        k4 = jnp.concatenate([kv4[..., :nope], jnp.broadcast_to(
            att._rotated(kr, theta)[:, :, None], (2, t, n, rope))], -1)
        want, lse = fa.reference_attention(
            *(x.transpose(0, 2, 1, 3) for x in (q4, k4, kv4[..., nope:])),
            k_len, None, True, 0.0, None, None, True)
        np.testing.assert_allclose(
            got["Out"], want.transpose(0, 2, 1, 3).reshape(2, t, n * dv),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got["LSE"], lse, rtol=1e-5, atol=1e-5)
        assert tuple(x - y for x, y in zip(_bodies(*names), before)) == (1, 0)
        return
    composed, one = latent_attention_programs(n, t, nope, rope, dv, theta,
                                              (nope + rope) ** -0.5)
    feed = dict(zip(("q", "kv", "kr", "ct"), map(np.asarray,
                                                 (q, kv, kr, ct))))
    exe = fluid.Executor(fluid.CPUPlace())
    got = exe.run(one[0], feed=feed, fetch_list=one[1])
    # the forward, and the gradient op differentiating it
    assert tuple(x - y for x, y in zip(_bodies(*names), before)) == (2, 0)
    want = exe.run(composed[0], feed=feed, fetch_list=composed[1])
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, rtol=1e-5, atol=1e-5)
    compile_cache.clear()


def test_the_op_refuses_a_layout_it_cannot_read():
    def build(**kw):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            q = fluid.layers.data("q", shape=[16, 2 * 24], dtype="float32")
            kv = fluid.layers.data("kv", shape=[16, 2 * 32], dtype="float32")
            kr = fluid.layers.data("kr", shape=[16, 8], dtype="float32")
            args = dict(dict(q=q, k=kv, n_head=2, v_dim=16, k_shared=kr),
                        **kw)
            return fluid.layers.fused_attention(**args)
    assert tuple(build().shape[1:]) == (16, 32)
    for kw, match in ((dict(v_dim=None), "v_dim"),
                      (dict(v_dim=24), "do not fit together"),
                      (dict(k_shared=None), "do not fit together"),
                      (dict(window=4), "no selected or window"),
                      (dict(n_head=None), "projections' layout")):
        with pytest.raises(ValueError, match=match):
            build(**kw)


# ---- the layer's shares ------------------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """Four shares of a small layer (8 experts, two held each) through the
    program's ``routed_experts`` — sigmoid scores, a bias that moves the
    selection, the scale — with the shared expert in ONE of them: their sum
    is the reference's layer holding all 8 and the shared expert once."""
    cfg, _ = _cfg()
    ref = harness.load_reference(cfg["reference"])
    whole = dict(cfg, n_routed_experts_held=8, first_local_expert=0)
    spec = {}
    ref._expert_spec(spec, "l1.", whole)
    p = dict(weights.make_weights(spec, 3))
    p["l1.moe.bias"] = jnp.asarray(_rand((8,), 9, 0.3))
    d, f, k = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_per_tok"])
    x = _rand((64, d), 4)
    want, n_all = ref.experts(p, "l1.", jnp.asarray(x), whole, (8, 0),
                              ref.f32_matmul)
    assert int(n_all) == 64 * k
    no_bias = ref.experts(dict(p, **{"l1.moe.bias": jnp.zeros((8,))}), "l1.",
                          jnp.asarray(x), whole, (8, 0), ref.f32_matmul)[0]
    assert float(jnp.max(jnp.abs(no_bias - want))) > 1e-3   # the bias bites

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = fluid.layers.data("x", shape=[d], dtype="float32")
        outs, pairs = [], []
        for i in range(4):
            names = {m: "s%d.%s" % (i, m) for m in ("gate", "up", "down")}
            y, counts, n = fluid.layers.routed_experts(
                xv, 8, k, f, held=2, first=2 * i, tile=8,
                score_func="sigmoid",
                weight_scale=cfg["routed_scaling_factor"],
                router_attr=fluid.ParamAttr(name="router"),
                bias_attr=fluid.ParamAttr(name="bias"),
                gate_attr=fluid.ParamAttr(name=names["gate"]),
                up_attr=fluid.ParamAttr(name=names["up"]),
                down_attr=fluid.ParamAttr(name=names["down"]),
                shared_width=f if i == 0 else None,
                shared_attrs=tuple(fluid.ParamAttr(name="shared." + m)
                                   for m in ("gate", "up", "down")))
            outs.append(y)
            pairs.append(n)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        # copies: the executor gives a step's state buffers up to the step
        scope.set_var("router", jnp.array(p["l1.moe.router"]))
        scope.set_var("bias", jnp.array(p["l1.moe.bias"]))
        for m in ("gate", "up", "down"):
            scope.set_var("shared." + m, jnp.array(p["l1.moe.shared." + m]))
            for i in range(4):
                scope.set_var("s%d.%s" % (i, m), jnp.stack(
                    [p["l1.moe.e%d.%s" % (e, m)] for e in (2 * i, 2 * i + 1)]))
        got = exe.run(main, feed={"x": x}, fetch_list=outs + pairs)
    assert sum(int(n[0]) for n in got[4:]) == 64 * k      # every pair, once
    np.testing.assert_allclose(sum(got[:4]), want, rtol=1e-4, atol=1e-5)
    for i in range(4):
        one = ref.experts(p, "l1.", jnp.asarray(x), whole, (2, 2 * i),
                          ref.f32_matmul, shared=i == 0)[0]
        np.testing.assert_allclose(got[i], one, rtol=1e-4, atol=1e-5)


# ---- the model ---------------------------------------------------------------------

def _train(precision, seed=5):
    from benchmark.generators import train_mtp_steps as gen

    cfg, traffic = _cfg(precision=precision)
    ref = harness.load_reference(cfg["reference"])
    batches = gen.make_batches(traffic, cfg["vocab_size"], seed)
    w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
    want = gen.reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    model.set_weights(w0)
    feeds = [model.make_feed(b) for b in batches]
    prog = gen.program_readings(model, feeds, w0, cfg["adam_beta1"], 3,
                                want["first_grad"])
    return cfg, model, gen.gaps(prog, want), prog, want


def test_tiny_model_trains_like_the_plain_reference_in_float32():
    """Both losses over three steps, the first gradient leaf by leaf — the
    shared embedding's and head's are the two uses' sums — three Adam
    steps, the routed and the dropless counts: in float32 the program and
    the reference are the same mathematics."""
    cfg, model, gaps, prog, want = _train("float32")
    assert gaps["loss_rel_gap"] < 3e-6
    assert gaps["grad_rel_error_rms"] < 1e-5
    assert gaps["grad_norm_gap"] < 1e-5 and gaps["update_norm_gap"] < 1e-4
    assert gaps["routed_pairs_gap"] == 0
    assert all(s["pairs_routed"] == s["pairs_computed"] > 0
               for s in prog["stats"])
    # total = main + 0.3 module's: the module's loss is its own number
    assert all(0 < m < t for m, t in zip(prog["mtp_losses"],
                                         prog["losses"]))
    # every trainable leaf has a gradient; the routers' biases have
    # neither gradient nor Adam state, and did not move
    ref = harness.load_reference(cfg["reference"])
    spec = ref.param_spec(cfg)
    assert set(want["grad_norms"]) == {n for n in spec if not ref.frozen(n)}
    assert all(v > 0 for v in want["grad_norms"].values())
    for leaf in ("l1.moe.bias", "mtp.moe.bias"):
        assert leaf in spec and leaf not in want["grad_norms"]
        assert model.scope.find_var(leaf + "_moment1_0") is None
        assert not np.asarray(model.scope.find_var(leaf)).any()
    assert model.scope.find_var("mtp.eh_proj_moment1_0") is not None
    model.close()


def test_tiny_model_trains_like_the_plain_reference_in_bf16():
    """Under bf16 AMP, inside the tiny limits of ``correct``."""
    cfg, model, gaps, prog, _ = _train("bf16_amp")
    limits = cfg["limits"]
    for name, value in gaps.items():
        assert value <= limits[name], (name, value)
    assert gaps["grad_rel_error_rms"] > 1e-4              # bf16 did round
    assert all(s["pairs_routed"] == s["pairs_computed"] for s in
               prog["stats"])
    model.close()


def test_program_holds_one_embedding_one_head_and_two_losses():
    cfg, traffic = _cfg()
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    block = model.main.global_block()
    types = [op.type for op in block.ops]
    blocks = cfg["num_hidden_layers"] + 1                  # and the module
    experts = blocks - cfg["first_k_dense_replace"]
    assert types.count("fused_attention") == blocks
    assert types.count("fused_attention_grad") == blocks
    for t in ("moe_router", "moe_dispatch", "moe_expert_ffn",
              "moe_router_grad", "moe_expert_ffn_grad"):
        assert types.count(t) == experts, t
    assert types.count("swiglu") == blocks                 # dense or shared
    assert types.count("lookup_table") == 2
    assert types.count("softmax_with_cross_entropy") == 2
    tables = {op.inputs["W"][0] for op in block.ops
              if op.type == "lookup_table"}
    assert tables == {"tok_emb"}
    heads = [op for op in block.ops if op.type == "mul"
             and op.inputs["Y"] == ["out_w"]]
    assert len(heads) == 2
    for op in block.ops:
        if op.type == "fused_attention":
            # the three projections' outputs where they lie, the rotation
            # inside the op
            assert op.outputs["LSE"] and not op.inputs.get("V")
            assert op.inputs["KShared"] and op.attrs["rope_theta"] == 3.2e7
            assert op.attrs["n_head"] == cfg["num_attention_heads"]
            assert op.attrs["v_dim"] == cfg["v_head_dim"]
        if op.type == "moe_router":
            assert op.attrs["score_func"] == "sigmoid"
            assert op.attrs["scale"] == 2.5 and op.inputs["Bias"]
    # a latent block asks for nothing between its projections and the op:
    # from its first norm to its output projection, and back
    assert not {"rotary_embedding", "transpose", "expand"} & set(types)
    for prefix in ["l%d." % i for i in range(blocks - 1)] + ["mtp."]:
        first = next(i for i, op in enumerate(block.ops)
                     if op.inputs.get("Scale") == [prefix + "ln1.g"])
        last = next(i for i, op in enumerate(block.ops)
                    if op.inputs.get("Y") == [prefix + "attn.o"])
        assert sorted(set(types[first:last + 1])) == [
            "fused_attention", "mul", "rms_norm", "split"]
        first = next(i for i, op in enumerate(block.ops)
                     if op.type == "mul_grad"
                     and op.inputs.get("Y") == [prefix + "attn.o"])
        last = next(i for i, op in enumerate(block.ops)
                    if op.type == "rms_norm_grad"
                    and op.inputs.get("Scale") == [prefix + "ln1.g"])
        assert set(types[first:last + 1]) <= {
            "fused_attention_grad", "mul_grad", "rms_norm_grad",
            "split_grad", "concat", "sum"}
        assert types[first:last + 1].count("sum") <= 1    # the norm's input
    model.close()


def test_step_counters_carry_the_modules_loss(tmp_path):
    """Fetched to the host with the loss, the counters land in that step's
    StepStats record under ``LATENT_STEP_STATS``' names."""
    from paddle_tpu import monitor
    from paddle_tpu.models import sparse_moe_decoder as smd

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tok, lbl, lbl2 = (fluid.layers.data(n, shape=[32, 1], dtype="int64")
                          for n in ("tok", "lbl", "lbl2"))
        loss, stats = smd.latent_decoder_lm(
            tok, lbl, lbl2, 64, 2, 1, 32, smd.LatentSizes(4, 24, 16, 16, 8,
                                                          16),
            48, (2, 4, 1), 16, 2, 16, route_scale=2.5, expert_tile=8)
    assert main.step_stats == (stats.name, smd.LATENT_STEP_STATS)
    assert smd.LATENT_STEP_STATS[-1] == "mtp_loss"
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    t = np.random.RandomState(0).randint(0, 64, (2, 32, 1)).astype("int64")
    feed = {"tok": t, "lbl": t, "lbl2": t}
    monitor.enable(log_dir=str(tmp_path))
    try:
        total, st = exe.run(main, feed=feed, fetch_list=[loss, stats])
        exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        monitor.disable()
    import glob
    import json
    recs = [json.loads(line) for f in glob.glob(str(tmp_path / "*.jsonl"))
            for line in open(f)]
    steps = [r for r in recs if r.get("event") == "step_stats"]
    assert len(steps) == 2
    assert [steps[0][n] for n in smd.LATENT_STEP_STATS] == st.tolist()
    assert steps[0]["moe_pairs_routed"] == steps[0]["moe_pairs_computed"] > 0
    assert 0 < steps[0]["mtp_loss"] < float(total[0])
    assert not set(smd.LATENT_STEP_STATS) & set(steps[1])


def test_marked_plain_heads_keep_their_ring_under_an_sp_mesh(monkeypatch):
    """A ``keep_lse`` call on a sequence-parallel mesh is still ring
    attention (the streamed kernel has no per-shard lowering): the op
    answers with the ring's output and a log-sum-exp nobody reads, and the
    gradient op differentiates the ring."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.registry import ComputeContext

    calls = []
    ring = att._ring_attention
    monkeypatch.setattr(att, "_ring_attention",
                        lambda *a, **k: calls.append(1) or ring(*a, **k))
    ctx = ComputeContext(key=jax.random.key(0), platform="cpu",
                         mesh=make_mesh((2,), ("sp",)))
    ctx.sequence_parallel = True
    q, k, v = (jnp.asarray(_rand((1, 2, 16, 8), i)) for i in range(3))
    ins = {"Q": [q], "K": [k], "V": [v]}
    attrs = {"causal": True, "keep_lse": True}
    got = att._fused_attention_compute(ins, attrs, ctx, 0)
    assert calls and got["LSE"].shape == (1, 2, 16, 1)
    want = fa.reference_attention(q, k, v, None, None, True, 0.0, None)
    np.testing.assert_allclose(got["Out"], want, rtol=1e-5, atol=1e-5)
    ct = jnp.asarray(_rand(want.shape, 7))
    grads = att._fused_attention_grad_compute(
        dict(ins, **{"Out::Out": [got["Out"]], "Out::LSE": [got["LSE"]],
                     "GRAD::Out": [ct]}),
        dict(attrs, __fwd_type__="fused_attention"), ctx, 0)
    ref = jax.grad(lambda *a: jnp.sum(fa.reference_attention(
        *a, None, None, True, 0.0, None) * ct), (0, 1, 2))(q, k, v)
    for slot, b in zip(("Q", "K", "V"), ref):
        np.testing.assert_allclose(grads["GRAD::" + slot][0], b, rtol=1e-4,
                                   atol=1e-4)
