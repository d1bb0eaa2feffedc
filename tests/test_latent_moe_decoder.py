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
    assert types.count("rotary_embedding") == 2 * blocks
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
            assert op.attrs["keep_lse"] and op.outputs["LSE"]
        if op.type == "rotary_embedding":
            assert op.attrs["interleaved"] and op.attrs["theta"] == 3.2e7
        if op.type == "moe_router":
            assert op.attrs["score_func"] == "sigmoid"
            assert op.attrs["scale"] == 2.5 and op.inputs["Bias"]
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
