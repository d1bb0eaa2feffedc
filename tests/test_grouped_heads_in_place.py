"""``fused_attention`` over grouped heads where the projections wrote them
(ISSUE 50): rank-3 ``Q`` ``[B, T, H * D]``, ``K`` ``[B, T, Hkv * D]`` and a
``V`` ``[B, T, Hkv * Dv]``, the rotation inside the op.  On the CPU at small
sizes, every kernel interpreted: the op through the executor against the
composition of Fluid ops around the 4-D op that the decoders wrote before (a
window over three key blocks, plain heads, a selection behind a per-head
norm, YaRN's law with a scale, no rotation, a batch of two); where the two
forms round in bf16; the rule's cases and the bodies they name; a block of
each of the three decoders under bf16 AMP with the kernels against the XLA
body; and the three programs' text between ``ln1`` and ``attn.o``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import compile_cache, layers
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import looped_decoder, sparse_moe_decoder as smd
from paddle_tpu.ops import attention as att
from paddle_tpu.ops import sparse_select as ss
from paddle_tpu.ops.pallas import streamed_attention as sa
from paddle_tpu.registry import ComputeContext

from streamed_backward import grouped_attention_programs

YARN = {"factor": 16.0, "original_length": 64.0, "beta_fast": 32.0,
        "beta_slow": 1.0}


def _rand(shape, seed=0, scale=1.0):
    return (np.random.RandomState(seed).standard_normal(shape)
            * scale).astype("float32")


def _feed(b, n, hk, d, t, dv=None, selected=False, seed=0):
    dv = d if dv is None else dv
    feed = {name: _rand((b, t, w), seed + i, s) for i, (name, w, s) in
            enumerate((("q", n * d, 0.5), ("k", hk * d, 0.5),
                       ("v", hk * dv, 0.5), ("ct", n * dv, 1.0)))}
    if selected:
        sel = ss.topk_key_mask(jnp.asarray(_rand((b, t, t), seed + 9)), 48)
        feed["sel"] = np.asarray(ss.pack_key_mask(sel))
    return feed


def _bodies(*names):
    got = compile_cache.stats()["kernel_bodies"]
    return tuple(got.get(n, 0) for n in names)


def _run(program, feed):
    main, fetches, startup = program
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return exe.run(main, feed=feed, fetch_list=fetches)


# name: (heads, K/V heads, D, T, window, the rotation's law, a selection
# behind a per-head norm, batch, heads a step forward, backward)
GROUPED_CASES = {
    # eight query heads a K/V head, a window that crosses three key blocks
    "window_8_to_1": (8, 1, 128, 384, 200, {"theta": 1e4}, False, 1,
                      "1x8", "1x8"),
    "plain_8_heads": (8, 8, 128, 256, None, {"theta": 1e6}, False, 1,
                      "8x1", "8x1"),
    "selected_behind_a_head_norm": (4, 2, 128, 256, None, {"theta": 1e7},
                                    True, 1, "1x2", "1x2"),
    "yarn_with_a_scale": (4, 1, 128, 256, None,
                          {"theta": 5e5, "freq_scaling": YARN,
                           "scale": 1.2772588722239782}, False, 1,
                          "1x4", "1x4"),
    "no_rotation": (4, 2, 128, 256, 100, None, False, 1, "1x2", "1x2"),
    "a_batch_of_two": (4, 4, 128, 256, None, {"theta": 1e4}, False, 2,
                       "4x1", "4x1"),
    "values_two_tiles_wide": (4, 2, 128, 256, None, {"theta": 1e4}, False, 1,
                              "1x2", "1x2"),
}


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_op_over_grouped_heads_in_place_against_the_composed_block(
        case, monkeypatch):
    """``Out`` and ``LSE`` to 1e-5, dQ, dK and dV to 1e-4 in float32, the
    in-place bodies named once each and no other."""
    n, hk, d, t, window, law, selected, b, forward, backward = \
        GROUPED_CASES[case]
    dv = 256 if case == "values_two_tiles_wide" else d
    monkeypatch.setattr(att, "_STREAMED_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    composed, one = grouped_attention_programs(
        n, hk, d, t, window, law, selected, head_norm=selected, dv=dv)
    types = [op.type for op in one[0].global_block().ops]
    assert not {"transpose", "rotary_embedding", "reshape"} & set(types)
    feed = _feed(b, n, hk, d, t, dv, selected)
    want = _run(composed, feed)
    names = ("fused_attention:streamed_inplace",
             "fused_attention_grad:streamed_fused_inplace",
             "streamed_step:" + forward, "streamed_grad_step:" + backward,
             "fused_attention:streamed", "fused_attention:xla")
    before = _bodies(*names)
    got = _run(one, feed)
    assert tuple(x - y for x, y in zip(_bodies(*names), before)) == (
        1, 1, 1, 1, 0, 0)
    assert got[0].shape == (b, t, n * dv) and got[1].shape == (b, n, t, 1)
    for i, (a, w, tol) in enumerate(zip(got, want,
                                        (1e-5, 1e-5, 1e-4, 1e-4, 1e-4))):
        if i == 1 and w.shape != a.shape:
            continue        # short plain heads: the 4-D op kept no LSE
        assert a.shape == w.shape and a.dtype == w.dtype
        np.testing.assert_allclose(a, w, rtol=tol, atol=tol)
    compile_cache.clear()


@pytest.mark.parametrize("law", [None, {"theta": 1e4},
                                 {"theta": 5e5, "freq_scaling": YARN,
                                  "scale": 1.27}],
                         ids=["none", "plain", "yarn"])
def test_in_place_kernels_round_where_the_composed_block_rounded(law):
    """In bf16: a query's rotation rounded once, then the scaled operand of
    the product, as ``rotary_embedding`` and the 4-D kernel round them; dQ
    and dK are turned back from their float32 sums (one rounding fewer than
    the composition's).  Without a rotation the two forms' outputs agree to
    the bit."""
    n, hk, d, t, window = 8, 2, 128, 384, 200
    q, k, v, ct = (jnp.asarray(x, jnp.bfloat16)
                   for x in map(_feed(1, n, hk, d, t).get, "q k v ct".split()))
    heads, pull = jax.vjp(
        lambda q, k, v: att._split_heads(q, k, v, n, hk, law), q, k, v)
    want, want_lse = sa.forward(*heads, None, True, None, True, window)
    want_grads = pull(sa.backward(
        *heads, None, want, want_lse, ct.reshape(1, t, n, d).transpose(
            0, 2, 1, 3), True, None, True, window))
    rot = att._grouped_tables(q, d, law)
    got, lse = sa.forward(q, k, v, None, True, None, True, window, n, rot)
    dq, dk, dvalues = sa.backward(q, k, v, None, got, lse, ct, True, None,
                                  True, window, n, rot)
    assert got.dtype == dq.dtype == dk.dtype == jnp.bfloat16
    # a rotated operand's last bit moves a row's log-sum-exp in its fourth
    # place
    np.testing.assert_allclose(lse, want_lse, rtol=1e-3, atol=1e-3)
    pairs = [(got, att._merge_heads(want))] + list(
        zip((dq, dk, dvalues), want_grads))
    for i, (a, w) in enumerate(pairs):
        a, w = np.asarray(a, "float32"), np.asarray(w, "float32")
        if law is None and i == 0:
            np.testing.assert_array_equal(a, w)
            continue
        if law is None:     # the rows' delta is summed in another order
            assert (a != w).mean() < 0.02, i
            continue
        # a last bit here and there; dQ and dK, rounded once less, more often
        assert (a != w).mean() < (0.5 if i in (1, 2) else 0.02), i
        np.testing.assert_allclose(a, w, rtol=2 ** -6, atol=2e-3)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_rms_norm_is_the_norm_over_the_view_as_heads(dtype):
    """``rms_norm`` with a gain narrower than the last axis: one norm a
    group, forward and all three gradients against the op over the ``[B, T,
    H, D]`` view — in float32 to round-off (the groups' sums are products
    with their indicator at the highest precision)."""
    from paddle_tpu.ops import norm

    b, t, h, d = 2, 24, 4, 128
    x = jnp.asarray(_rand((b, t, h * d), 1), dtype)
    g = jnp.asarray(1 + 0.1 * _rand((d,), 2))
    ct = jnp.asarray(_rand((b, t, h * d), 3))

    def op(x, g, view):
        y = norm._rms_compute({"X": [x.reshape(view)], "Scale": [g]},
                              {"epsilon": 1e-6}, None, 0)["Y"]
        return y.reshape(b, t, h * d)
    grouped, pull = jax.vjp(lambda x, g: op(x, g, (b, t, h * d)), x, g)
    viewed, pull_view = jax.vjp(lambda x, g: op(x, g, (b, t, h, d)), x, g)
    assert grouped.dtype == x.dtype
    tol = 1e-5 if dtype == "float32" else 2 ** -7
    np.testing.assert_allclose(np.asarray(grouped, "float32"),
                               np.asarray(viewed, "float32"), rtol=tol,
                               atol=tol)
    for a, w in zip(pull(ct.astype(dtype)), pull_view(ct.astype(dtype))):
        np.testing.assert_allclose(np.asarray(a, "float32"),
                                   np.asarray(w, "float32"), rtol=10 * tol,
                                   atol=10 * tol)
    with pytest.raises(ValueError, match="whole divisor"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            layers.rms_norm(layers.data("x", shape=[8, 96], dtype="float32"),
                            group=64)


# ---- the rule ---------------------------------------------------------------

def _ins(n=8, hk=2, d=128, t=256, dv=None, dtype=jnp.bfloat16, b=1, **attrs):
    dv = d if dv is None else dv
    q, k, v = (jax.ShapeDtypeStruct((b, t, w), dtype)
               for w in (n * d, hk * d, hk * dv))
    return ({"Q": [q], "K": [k], "V": [v]},
            dict({"causal": True, "n_head": n, "rope_theta": 1e4}, **attrs))


def _tpu(**kw):
    return ComputeContext(key=jax.random.key(0), platform="tpu", **kw)


# name: (sizes and attributes, a mesh, a key length, dropout) -> the body
RULE_CASES = {
    "the_window_cell": (dict(n=32, hk=4, t=8192, window=1024), False, False,
                        0.0, "streamed_inplace"),
    "the_looped_cell": (dict(n=16, hk=16, t=4096), False, False, 0.0,
                        "streamed_inplace"),
    "no_rotation": (dict(rope_theta=None), False, False, 0.0,
                    "streamed_inplace"),
    "values_two_tiles_wide": (dict(dv=256), False, False, 0.0,
                              "streamed_inplace"),
    "keys_64_wide": (dict(d=64, dv=128), False, False, 0.0, "streamed"),
    "rotated_heads_two_tiles_wide": (dict(d=256), False, False, 0.0,
                                     "streamed"),
    "neighbouring_pairs": (dict(rope_interleaved=True), False, False, 0.0,
                           "streamed"),
    # not even one K/V head's resident gradients fit: the dQ + dK/dV pair
    "32k_tokens": (dict(n=8, hk=1, t=32768), False, False, 0.0, "streamed"),
    "values_64_wide": (dict(d=64), False, False, 0.0, "xla"),
    "a_mesh": (dict(), True, False, 0.0, "xla"),
    "a_key_length": (dict(), False, True, 0.0, "xla"),
    "dropout": (dict(), False, False, 0.1, "xla"),
    "a_ragged_block": (dict(t=200), False, False, 0.0, "xla"),
}


@pytest.mark.parametrize("why", sorted(RULE_CASES))
def test_grouped_rule_reads_only_what_the_op_observes(why):
    from paddle_tpu.parallel.mesh import make_mesh

    sizes, mesh, has_klen, rate, body = RULE_CASES[why]
    ctx = _tpu(mesh=make_mesh((2,), ("dp",))) if mesh else _tpu()
    assert att._grouped_body(ctx, *_ins(**sizes), has_klen, rate) == body


def test_grouped_rule_wants_a_tpu_and_the_operators_leave(no_pallas):
    from paddle_tpu import flags

    assert att._grouped_body(_tpu(), *_ins(), False, 0.0) == "xla"
    flags.set_flags({"FLAGS_pallas_kernels": True})
    assert att._grouped_body(_tpu(), *_ins(), False, 0.0) \
        == "streamed_inplace"
    cpu = ComputeContext(key=jax.random.key(0), platform="cpu")
    assert att._grouped_body(cpu, *_ins(), False, 0.0) == "xla"
    # the heads a step are the 4-D form's, from the same byte sums
    for sizes, forward, backward in ((dict(n=32, hk=4, t=8192), (1, 8), (1, 8)),
                                     (dict(n=16, hk=16, t=4096), (8, 1),
                                      (4, 1))):
        (q,), (k,), (v,) = (_ins(**sizes)[0][s] for s in "QKV")
        assert sa.step_heads(q, k, v, sizes["n"]) == forward
        assert sa.grad_step(q, k, v, sizes["n"]) == ("streamed_fused",
                                                     backward)


@pytest.mark.parametrize("declined", ["cpu", "keys_64_wide", "key_length",
                                      "ragged"])
def test_declined_grouped_op_takes_another_body_with_the_same_numbers(
        declined, monkeypatch):
    """The op's definition is the composition, whatever made the rule
    decline: the CPU (the XLA body), 64-wide keys over 128-wide values (the
    4-D kernels behind the op's own rotation and transposes), a ``k_len``,
    a length off the key blocks."""
    n, hk, d, dv, t, law = 4, 2, 128, 128, 256, {"theta": 3.2e7}
    names = ["fused_attention:xla", "fused_attention:streamed",
             "fused_attention_grad:streamed_fused",
             "fused_attention:streamed_inplace"]
    moved = (2, 0, 0, 0)         # the forward, and its gradient's own trace
    if declined == "keys_64_wide":
        d, moved = 64, (0, 1, 1, 0)
    if declined == "ragged":
        t = 200
    if declined != "cpu":
        monkeypatch.setattr(att, "_STREAMED_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    feed = _feed(2, n, hk, d, t, dv)
    if declined == "key_length":
        q, k, v = (jnp.asarray(feed[x]) for x in "qkv")
        k_len = jnp.asarray([t, t - 50], jnp.int32)
        before = _bodies(*names)
        got = att._fused_attention_compute(
            {"Q": [q], "K": [k], "V": [v], "KLen": [k_len]},
            {"causal": True, "n_head": n, "rope_theta": law["theta"]},
            ComputeContext(key=jax.random.key(0), platform="cpu"), 0)
        from paddle_tpu.ops import attention_xla as fa
        want, lse = fa.reference_attention(
            *att._split_heads(q, k, v, n, hk, law), k_len, None, True, 0.0,
            None, None, True)
        np.testing.assert_allclose(got["Out"], att._merge_heads(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got["LSE"], lse, rtol=1e-5, atol=1e-5)
        assert tuple(x - y for x, y in zip(_bodies(*names), before)) \
            == (1, 0, 0, 0)
        return
    composed, one = grouped_attention_programs(n, hk, d, t, None, law, dv=dv)
    before = _bodies(*names)
    got = _run(one, feed)
    assert tuple(x - y for x, y in zip(_bodies(*names), before)) == moved
    for a, w in zip(got, _run(composed, feed)):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4)
    compile_cache.clear()


def test_the_op_refuses_grouped_operands_it_cannot_read():
    def build(widths=(4 * 16, 2 * 16, 2 * 16), **kw):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            q, k, v = (layers.data(name, shape=[24, w], dtype="float32")
                       for name, w in zip("qkv", widths))
            return layers.fused_attention(**dict(dict(
                q=q, k=k, v=v, n_head=4, causal=True), **kw))
    assert tuple(build().shape[1:]) == (24, 64)
    assert tuple(build((64, 32, 2 * 24)).shape[1:]) == (24, 4 * 24)
    for kw, match in ((dict(n_head=3), "whole divisor"),
                      (dict(widths=(64, 48, 48)), "whole divisor"),
                      (dict(widths=(64, 32, 31)), "whole divisor"),
                      (dict(v_dim=16), "latent form"),
                      (dict(causal=False, window=4), "window"),
                      (dict(rope_scale=2.0), "belong to a rotation")):
        with pytest.raises(ValueError, match=match):
            build(**kw)
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = layers.data("q", shape=[4, 24, 16], dtype="float32")
        with pytest.raises(ValueError, match="projections' layout"):
            layers.fused_attention(q, q, q, rope_theta=1e4)


# ---- a block of each decoder, kernels against the XLA body, bf16 AMP ---------

def _keye_block(x):
    return smd.decoder_block(x, "l0.", 4, 2, 128, (4, 4, 0), 64, 2, 2, 64,
                             64, rope_theta=1e7, expert_tile=128)[0]


def _mellum_block(x):
    x, ctx, _, _ = smd._grouped_attention(
        x, "l0.", 8, 1, 128, (5e5, YARN, 1.2772588722239782), 1e-6,
        window=200)
    return smd._dense_half(x, "l0.", 512, 1e-6)


def _ouro_block(x):
    return looped_decoder.sandwich_block(x, "l0.", 4, 128, 512)


BLOCKS = {"selected_key_block": _keye_block, "window_block": _mellum_block,
          "sandwich_block": _ouro_block}


def _block_gradients(build, t, seed=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[t, 256], dtype="float32")
        loss = layers.mean(layers.square(build(x)))
        opt = mixed_precision.decorate(
            fluid.optimizer.SGD(learning_rate=0.0))
        _, grads = opt.minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed={"x": _rand((2, t, 256), 1)},
                      fetch_list=[loss] + [g for _, g in grads])
    types = [op.type for op in main.global_block().ops]
    return types, [p.name for p, _ in grads], got


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_a_decoder_block_under_bf16_amp_kernels_against_the_xla_body(
        block, monkeypatch):
    """Every leaf's gradient of one block with the in-place kernels
    (interpreted) within 8% — ``correct``'s limit — of the same program on
    the op's XLA body, the definition; the loss to bf16's last places."""
    t = 384
    compile_cache.clear()
    names = ("fused_attention:streamed_inplace",
             "fused_attention_grad:streamed_fused_inplace")
    before = _bodies(*names)
    types, leaves, want = _block_gradients(BLOCKS[block], t)
    assert _bodies(*names) == before and "fused_attention" in types
    monkeypatch.setattr(att, "_STREAMED_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    _, _, got = _block_gradients(BLOCKS[block], t)
    assert tuple(x - y for x, y in zip(_bodies(*names), before)) == (1, 1)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-2)
    assert len(leaves) >= 7
    for name, a, w in zip(leaves, got[1:], want[1:]):
        a, w = np.asarray(a, "float32"), np.asarray(w, "float32")
        assert np.linalg.norm(a - w) <= 0.08 * np.linalg.norm(w), name
    compile_cache.clear()


# ---- the programs' text ------------------------------------------------------

def _between(main, first, last):
    """The ops that make ``fused_attention``'s Q, K and V out of the block's
    first norm (a ``Selected`` input's makers are the indexer's), and the op
    that reads its result."""
    ops = main.global_block().ops
    maker = {name: op for op in ops for name in op.output_arg_names}
    attention = next(op for op in ops if op.type == "fused_attention")
    found, todo = [], [n for slot in "QKV" for n in attention.input(slot)]
    while todo:
        op = maker.get(todo.pop())
        if op is None or op in found:
            continue
        if op.type == "rms_norm" and first in op.input("Scale")[0]:
            continue
        found.append(op)
        todo += [n for n in op.input_arg_names if n in maker]
    reader = next(op for op in ops
                  if attention.output("Out")[0] in op.input_arg_names)
    assert last in reader.input_arg_names[1] and reader.type == "mul"
    return attention, found


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_nothing_stands_between_the_projections_and_the_op(block):
    """No ``rotary_embedding``, ``transpose`` or ``reshape`` between a
    block's ``ln1`` and ``attn.o`` (Keye's per-head norm is a grouped
    ``rms_norm`` over the projection as it lies); the three projections are
    the op's operands, its result ``attn.o``'s."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        BLOCKS[block](layers.data("x", shape=[256, 256], dtype="float32"))
    attention, found = _between(main, "ln1.g", "attn.o")
    types = sorted(op.type for op in found)
    if block == "selected_key_block":
        # the per-head norm of q and k, where they lie
        assert types == ["mul"] * 3 + ["rms_norm"] * 2
        assert all(main.global_block().var(op.input("Scale")[0]).shape
                   == (128,) for op in found if op.type == "rms_norm")
        # the indexer keeps its own rotation of 64-wide heads
        assert sum(op.type == "rotary_embedding"
                   for op in main.global_block().ops) == 2
        assert attention.input("Selected")
    else:
        assert types == ["mul"] * 3
        assert not {"rotary_embedding", "transpose", "reshape"} & {
            op.type for op in main.global_block().ops}
    shapes = [main.global_block().var(attention.input(s)[0]).shape
              for s in "QKV"]
    assert [len(s) for s in shapes] == [3, 3, 3]
    assert attention.attr("rope_theta") is not None
    assert attention.attr("n_head") == shapes[0][2] // 128


def test_the_pair_share_of_the_rank_3_program_at_the_cells_shape():
    """``attention_pair_share`` reads T off the operand's rank: 8192 tokens
    under three 1024-key windows and one full layer, 0.42577."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        q = layers.data("q", shape=[8192, 32 * 16], dtype="float32")
        k = layers.data("k", shape=[8192, 4 * 16], dtype="float32")
        for window in (1024, 1024, 1024, None):
            layers.fused_attention(q, k, k, causal=True, window=window,
                                   n_head=32, rope_theta=1e4)
    assert smd.attention_pair_share(main) == 57153024 / 134234112
    assert round(smd.attention_pair_share(main), 5) == 0.42577
