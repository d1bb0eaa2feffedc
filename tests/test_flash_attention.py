"""The XLA attention body, the fused_attention op and the packed kernel.

Parity oracle: a plain materialized softmax-attention (the reference's
``nets.scaled_dot_product_attention`` math, ``nets.py:323``) written here —
the XLA body (``ops/attention_xla.reference_attention``) must match it
forward and backward, under padding masks, causal masks, the suffix-causal
decode shape and dropout (the dropout mask is a counter hash, restated here
in numpy); the packed kernel (interpret mode on CPU) is held to the XLA
body."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.ops import attention_xla as ax


def _oracle(q, k, v, k_len=None, causal=False, scale=None, keep=None):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k)
    mask = jnp.ones((b, 1, tq, tk), bool)
    if k_len is not None:
        mask = jnp.arange(tk)[None, None, None, :] < k_len.reshape(b, 1, 1, 1)
    if causal:
        mask = mask & (jnp.arange(tq)[:, None] >=
                       jnp.arange(tk)[None, :])[None, None]
    s = jnp.where(mask, s, -1e30)
    y = jax.nn.softmax(s, axis=-1)
    y = jnp.where(mask, y, 0.0)
    if keep is not None:
        # downgrade_in_infer: dropped, not upscaled
        y = jnp.where(keep, y, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", y, v)


def _rand(shape, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype("float32"))


def _keep_by_hand(seed, b, h, tq, tk, rate):
    """The dropout hash in numpy: murmur3's finalizer over (query, key)
    position words and the seed offset by the (batch, head) index; the top
    24 bits against the rate."""
    u = np.uint32
    with np.errstate(over="ignore"):
        x = (np.arange(tq, dtype=u)[:, None] * u(0x85EBCA6B)) ^ \
            (np.arange(tk, dtype=u)[None, :] * u(0xC2B2AE35))
        x = x ^ (u(seed) + np.arange(b * h, dtype=u).reshape(b, h, 1, 1)
                 * u(0x9E3779B1))
        x = x ^ (x >> u(16))
        x = x * u(0x7FEB352D)
        x = x ^ (x >> u(15))
        x = x * u(0x846CA68B)
        x = x ^ (x >> u(16))
    return (x >> u(8)) >= u(int(rate * float(1 << 24)))


def _qkv(b, h, tq, tk, d):
    return _rand((b, h, tq, d), 0), _rand((b, h, tk, d), 1), \
        _rand((b, h, tk, d), 2)


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def _forward_case(tq, tk, causal):
    def run():
        q, k, v = _qkv(2, 3, tq, tk, 8)
        _close(ax.reference_attention(q, k, v, None, None, causal, 0.0, None),
               _oracle(q, k, v, causal=causal))
    return run


def _klen_padding():
    q, k, v = _qkv(3, 2, 16, 16, 8)
    k_len = jnp.asarray([16, 7, 1], jnp.int32)
    _close(ax.reference_attention(q, k, v, k_len, None, False, 0.0, None),
           _oracle(q, k, v, k_len=k_len))


def _fully_masked_rows():
    # a batch row with no valid key: zeros out, finite gradients
    q, k, v = _qkv(2, 1, 8, 8, 4)
    k_len = jnp.asarray([8, 0], jnp.int32)

    def f(q, k, v):
        return jnp.sum(ax.reference_attention(q, k, v, k_len, None, False,
                                              0.0, None) ** 2)
    out = ax.reference_attention(q, k, v, k_len, None, False, 0.0, None)
    assert np.all(np.asarray(out[1]) == 0.0)
    for g in jax.grad(f, argnums=(0, 1, 2))(q, k, v):
        assert np.isfinite(np.asarray(g)).all()


def _grad_case(causal, rate=0.0):
    def run():
        q, k, v = _qkv(2, 2, 16, 16, 8)
        k_len = jnp.asarray([16, 11], jnp.int32)
        w = _rand((2, 2, 16, 8), 3)   # nonuniform cotangent
        seed = jnp.asarray(1234, jnp.uint32) if rate else None
        keep = _keep_by_hand(1234, 2, 2, 16, 16, rate) if rate else None

        def f_body(q, k, v):
            return jnp.sum(w * ax.reference_attention(
                q, k, v, k_len, seed, causal, rate, None))

        def f_ref(q, k, v):
            return jnp.sum(w * _oracle(q, k, v, k_len=k_len, causal=causal,
                                       keep=keep))
        for a, b in zip(jax.grad(f_body, argnums=(0, 1, 2))(q, k, v),
                        jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)):
            _close(a, b, 2e-4)
    return run


def _dropout_mask():
    """The weights the body drops are the hash's, a seed apart they are
    others, and about ``rate`` of them go."""
    q, k, v = _qkv(2, 2, 16, 16, 8)
    rate = 0.4
    keep = _keep_by_hand(1234, 2, 2, 16, 16, rate)
    assert 0.25 < 1.0 - keep.mean() < 0.55
    out = ax.reference_attention(q, k, v, None, jnp.asarray(1234, jnp.uint32),
                                 False, rate)
    _close(out, _oracle(q, k, v, keep=keep), 1e-5)
    assert np.array_equal(
        np.asarray(ax._keep_mask(jnp.uint32(1234),
                                 jnp.arange(4, dtype=jnp.int32
                                            ).reshape(2, 2, 1, 1),
                                 jnp.arange(16)[:, None],
                                 jnp.arange(16)[None, :], rate)), keep)
    other = ax.reference_attention(q, k, v, None,
                                   jnp.asarray(1235, jnp.uint32), False, rate)
    assert not np.allclose(out, other)


def _suffix_case(tq, klen):
    """causal with Tq < Tk: queries are the LAST tq of the klen valid
    keys — parity against the sliced rows of a full-length causal call
    (the workaround this mask retires)."""
    def run():
        tk, b = 16, 2
        q_full, k, v = _qkv(b, 3, tk, tk, 8)
        k_len = jnp.asarray([klen] * b, jnp.int32)
        full = _oracle(q_full, k, v, k_len=k_len, causal=True)
        lo = klen - tq
        got = ax.reference_attention(q_full[:, :, lo:klen, :], k, v, k_len,
                                     None, True, 0.0, None)
        _close(got, full[:, :, lo:klen, :])
    return run


def _suffix_per_batch_lengths():
    """Single-token decode (Tq=1) with DIFFERENT valid lengths per batch
    row: each query sits at its own batch's position klen-1."""
    tk, b = 16, 3
    q_full, k, v = _qkv(b, 2, tk, tk, 8)
    k_len = jnp.asarray([16, 9, 1], jnp.int32)
    full = np.asarray(_oracle(q_full, k, v, k_len=k_len, causal=True))
    q_suf = jnp.stack([q_full[i, :, int(k_len[i]) - 1: int(k_len[i]), :]
                       for i in range(b)])
    want = np.stack([full[i, :, int(k_len[i]) - 1: int(k_len[i]), :]
                     for i in range(b)])
    _close(ax.reference_attention(q_suf, k, v, k_len, None, True, 0.0, None),
           want)


XLA_BODY_CASES = {
    "forward_16": _forward_case(16, 16, False),
    "forward_causal_16": _forward_case(16, 16, True),
    "forward_cross_24x40": _forward_case(24, 40, False),
    "forward_causal_64": _forward_case(64, 64, True),
    "klen_padding": _klen_padding,
    "fully_masked_rows_zero_and_grad_safe": _fully_masked_rows,
    "grad": _grad_case(False),
    "grad_causal": _grad_case(True),
    "dropout_mask_is_the_hash": _dropout_mask,
    "dropout_grad": _grad_case(False, 0.4),
    "suffix_causal_1_of_16": _suffix_case(1, 16),
    "suffix_causal_4_of_9": _suffix_case(4, 9),
    "suffix_causal_8_of_16": _suffix_case(8, 16),
    "suffix_causal_per_batch_lengths": _suffix_per_batch_lengths,
}


@pytest.mark.parametrize("case", sorted(XLA_BODY_CASES))
def test_xla_body_against_a_composition(case):
    """``reference_attention`` — the body every kernel is held to, and what
    the op lowers to wherever no rule takes the call — against the plain
    composition above: each mask shape, the fully-masked-row contract, the
    dropout hash's mask and its gradient."""
    XLA_BODY_CASES[case]()


def test_dropout_expectation_matches_infer_scale():
    """downgrade_in_infer: E[train dropout(y)] = (1-p)*y, which is exactly
    the (1-p) scale the op applies at eval — train/eval consistent."""
    q, k, v = _rand((1, 1, 32, 8), 0), _rand((1, 1, 32, 8), 1), \
        _rand((1, 1, 32, 8), 2)
    rate = 0.3
    outs = [ax.reference_attention(q, k, v, None,
                                   jnp.asarray(s, jnp.uint32), False, rate)
            for s in range(40)]
    mean = np.mean([np.asarray(o) for o in outs], axis=0)
    base = (1.0 - rate) * np.asarray(_oracle(q, k, v))
    np.testing.assert_allclose(mean, base, rtol=0.3, atol=0.12)


def _attention_program(use_fused, dropout_rate=0.0):
    """fused_attention op vs the manual matmul+softmax composition."""
    b, h, t, d = 2, 2, 8, 4
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = fluid.layers.data("q", shape=[h, t, d])
        k = fluid.layers.data("k", shape=[h, t, d])
        v = fluid.layers.data("vv", shape=[h, t, d])
        klen = fluid.layers.data("klen", shape=[], dtype="int32")
        if use_fused:
            out = fluid.layers.fused_attention(
                q, k, v, k_len=klen, causal=True,
                dropout_rate=dropout_rate)
        else:
            s = fluid.layers.matmul(q, k, transpose_y=True)
            s = fluid.layers.scale(s, scale=d ** -0.5)
            # padding_attn_bias/causal_mask take T from ref dim 1
            ref = fluid.layers.transpose(q, perm=[0, 2, 1, 3])  # [B,T,H,D]
            bias = fluid.layers.padding_attn_bias(klen, ref)
            s = fluid.layers.elementwise_add(s, bias)
            causal = fluid.layers.causal_mask(ref=ref)
            s = fluid.layers.elementwise_add(s, causal)
            w = fluid.layers.softmax(s)
            out = fluid.layers.matmul(w, v)
        rng = np.random.RandomState(7)
        feed = {"q": rng.randn(b, h, t, d).astype("float32"),
                "k": rng.randn(b, h, t, d).astype("float32"),
                "vv": rng.randn(b, h, t, d).astype("float32"),
                "klen": np.asarray([t, t - 3], "int32")}
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            return exe.run(feed=feed, fetch_list=[out])[0]


def test_fused_attention_op_matches_composition():
    fused = _attention_program(True)
    manual = _attention_program(False)
    np.testing.assert_allclose(fused, manual, rtol=1e-4, atol=1e-4)


def test_label_smooth_fused_matches_composition():
    n, c, eps = 6, 11, 0.1
    rng = np.random.RandomState(0)
    logits_np = rng.randn(n, c).astype("float32")
    label_np = rng.randint(0, c, (n, 1)).astype("int64")

    def run(fused):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            logits = fluid.layers.data("logits", shape=[c])
            label = fluid.layers.data("label", shape=[1], dtype="int64")
            if fused:
                loss = fluid.layers.softmax_with_cross_entropy(
                    logits, label, label_smooth_eps=eps)
            else:
                oh = fluid.layers.one_hot(label, depth=c)
                soft = fluid.layers.label_smooth(oh, epsilon=eps)
                loss = fluid.layers.softmax_with_cross_entropy(
                    logits, soft, soft_label=True)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(fluid.default_startup_program())
                return exe.run(feed={"logits": logits_np, "label": label_np},
                               fetch_list=[loss])[0]

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5, atol=1e-6)


def test_transformer_emits_fused_attention():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        from paddle_tpu.models import transformer as tfm
        src = fluid.layers.data("src_word", shape=[1], dtype="int64",
                                lod_level=1)
        tgt = fluid.layers.data("tgt_word", shape=[1], dtype="int64",
                                lod_level=1)
        lbl = fluid.layers.data("lbl_word", shape=[1], dtype="int64",
                                lod_level=1)
        cost, _ = tfm.transformer(src, tgt, lbl, 16, 16, 64, 64, n_layer=2,
                                  n_head=2, d_model=16, d_inner=32,
                                  dropout_rate=0.1)
        ops = [op.type for op in
               fluid.default_main_program().global_block().ops]
        # 2 enc self + 2 dec self + 2 cross = 6 fused attentions
        assert ops.count("fused_attention") == 6
        # the fused label-smoothing path: no [B, T, V] one_hot materialized
        assert "one_hot" not in ops


# ---------------------------------------------------------------------------
# suffix-query (bottom-aligned) causal masks: the KV-cache decode shape
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_suffix_causal_grad_parity():
    """Backward parity for the chunked-decode shape: grads of the suffix
    call equal the corresponding grads of the sliced full-length
    objective (rows outside the suffix contribute nothing); the forward
    parity cases above stay tier-1."""
    tk, tq, klen, b, h, d = 16, 4, 11, 2, 2, 8
    q_full = _rand((b, h, tk, d), 0)
    k = _rand((b, h, tk, d), 1)
    v = _rand((b, h, tk, d), 2)
    k_len = jnp.asarray([klen] * b, jnp.int32)
    w = _rand((b, h, tq, d), 3)
    lo = klen - tq

    def f_full(qf, k, v):
        out = _oracle(qf, k, v, k_len=k_len, causal=True)
        return jnp.sum(w * out[:, :, lo:klen, :])

    gq_full, gk_full, gv_full = jax.grad(f_full, (0, 1, 2))(q_full, k, v)
    q_suf = q_full[:, :, lo:klen, :]
    def f(q, k, v):
        return jnp.sum(w * ax.reference_attention(q, k, v, k_len, None, True,
                                                  0.0))
    gq, gk, gv = jax.grad(f, (0, 1, 2))(q_suf, k, v)
    np.testing.assert_allclose(gq, gq_full[:, :, lo:klen, :],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gk, gk_full, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gv, gv_full, rtol=2e-4, atol=2e-4)


def test_fused_attention_op_rejects_query_longer_than_keys():
    """Tq > Tk under causal stays a build-time error (a suffix cannot be
    longer than the sequence it suffixes); Tq < Tk now builds."""
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = fluid.layers.data("q", shape=[2, 8, 4])
        k = fluid.layers.data("k", shape=[2, 4, 4])
        v = fluid.layers.data("vv", shape=[2, 4, 4])
        with pytest.raises(ValueError, match="Tq <= Tk"):
            fluid.layers.fused_attention(q, k, v, causal=True)
        # the decode shape builds: Tq=4 suffix against Tk=8 keys
        q2 = fluid.layers.data("q2", shape=[2, 4, 4])
        k2 = fluid.layers.data("k2", shape=[2, 8, 4])
        v2 = fluid.layers.data("v2", shape=[2, 8, 4])
        out = fluid.layers.fused_attention(q2, k2, v2, causal=True)
        assert tuple(out.shape) == (-1, 2, 4, 4)


# ---------------------------------------------------------------------------
# the packed short-sequence kernel (ops/pallas/packed_attention.py): heads
# packed on the last axis, ``[B, T, H*D]``, the projections' own layout
# ---------------------------------------------------------------------------

from paddle_tpu.ops.pallas import packed_attention as pa  # noqa: E402


def _merge(x):
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _split(x, h):
    b, t, hd = x.shape
    return x.reshape(b, t, h, hd // h).transpose(0, 2, 1, 3)


def _packed(q, k, v, k_len, seed, causal, rate):
    h = q.shape[1]
    return _split(pa.packed_attention(
        _merge(q), _merge(k), _merge(v), k_len, seed, None, h, causal, rate,
        None, True), h)


def _packed_case(kind, dtype, seed=0):
    b, h, d = 4, 4, 32                     # H*D = 128: one lane tile
    tq, tk = (16, 24) if kind == "cross" else (16, 16)
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d), dtype)
               for t in (tq, tk, tk))
    # a klen == 0 row: fully masked, zeros out and zero gradients
    k_len = jnp.asarray([tk, tk - 5, 0, 3], jnp.int32) \
        if kind == "padded" else None
    return q, k, v, k_len, kind == "causal"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["self", "causal", "cross", "padded"])
def test_packed_kernel_matches_reference(kind, dtype):
    """Forward and all three gradients of the packed kernel (interpret
    mode) against ``reference_attention`` on the same operands."""
    q, k, v, k_len, causal = _packed_case(kind, jnp.dtype(dtype))
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: ax.reference_attention(q, k, v, k_len, None, causal),
        q, k, v)
    out, out_vjp = jax.vjp(
        lambda q, k, v: _packed(q, k, v, k_len, None, causal, 0.0), q, k, v)
    ct = jnp.asarray(np.random.RandomState(9).randn(*ref.shape), ref.dtype)
    # float32: rounding order only; bf16: the XLA body rounds dy to bf16
    # where the kernel keeps it float32, one bf16 ulp on O(1) gradients
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    np.testing.assert_allclose(f32(out), f32(ref), **tol)
    for g, g_ref in zip(out_vjp(ct), ref_vjp(ct)):
        np.testing.assert_allclose(f32(g), f32(g_ref), **tol)
    if kind == "padded":
        assert not f32(out)[2].any()
        assert all(not f32(g)[2].any() for g in out_vjp(ct))


@pytest.mark.parametrize("causal", [False, True])
def test_packed_dropout_mask_and_gradient_match_xla_body(causal):
    """The kernel draws the keep mask by the same counter hash on the same
    (b*H + h, query, key) indices: what is dropped is dropped in both,
    forward and backward, so outputs and gradients agree to rounding."""
    q, k, v, k_len, _ = _packed_case("padded", jnp.float32, seed=3)
    seed = jnp.asarray(20250925, jnp.uint32)
    rate = 0.4
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: ax.reference_attention(q, k, v, k_len, seed, causal,
                                               rate), q, k, v)
    out, out_vjp = jax.vjp(
        lambda q, k, v: _packed(q, k, v, k_len, seed, causal, rate), q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    ct = _rand(ref.shape, 5)
    for g, g_ref in zip(out_vjp(ct), ref_vjp(ct)):
        np.testing.assert_allclose(g, g_ref, rtol=2e-5, atol=2e-5)
    # and the mask did drop something: not the no-dropout result
    plain = _packed(q, k, v, k_len, None, causal, 0.0)
    assert np.abs(np.asarray(out - plain)).max() > 1e-2


def test_packed_shard_offsets_reproduce_the_global_mask():
    """A shard that is told its first global row and head draws the rows
    of the unsharded mask (what ``shard_map`` relies on under dp x tp)."""
    q, k, v, _, _ = _packed_case("self", jnp.float32, seed=4)
    seed = jnp.asarray(7, jnp.uint32)
    h = q.shape[1]
    whole = _packed(q, k, v, None, seed, False, 0.3)
    # rows 2..3, heads 2..3 of the [4, 4, ...] problem
    part = pa.packed_attention(
        _merge(q[2:, 2:]), _merge(k[2:, 2:]), _merge(v[2:, 2:]), None, seed,
        (2, 2, h), 2, False, 0.3, None, True)
    np.testing.assert_allclose(_split(part, 2), whole[2:, 2:],
                               rtol=1e-6, atol=1e-6)


def test_packed_supported_states_its_bound():
    """At H*D = 512 in bf16 the rule admits T = 64 and 128 with room and
    stops well before 2048; it wants whole sublane groups and lane tiles."""
    def ok(t, tk=None, h=8, d=64, dtype=jnp.bfloat16):
        return pa.supported((256, h, t, d), (256, h, tk or t, d), dtype)
    assert ok(64) and ok(128) and ok(256) and ok(64, 128)
    assert not ok(512) and not ok(1024) and not ok(2048) and not ok(64, 4096)
    assert not ok(60) and not ok(64, h=2, d=16)       # T % 8, H*D % 128
    assert pa._block_rows(256, 64, 64, 8, 64, jnp.bfloat16) == 8
    assert pa._block_rows(7, 64, 64, 8, 64, jnp.bfloat16) == 7
    assert pa._block_rows(22, 64, 64, 8, 64, jnp.bfloat16) == 2


def _cpu_takes_packed(monkeypatch, on=True):
    """Let a CPU trace take the packed kernel (interpreted), as a TPU
    trace does, until the test ends.  The trace cache is emptied: the
    patched tuple is in no cache key."""
    from paddle_tpu import compile_cache
    from paddle_tpu.ops import attention as att

    monkeypatch.setattr(att, "_PACKED_PLATFORMS",
                        ("tpu", "cpu") if on else ("tpu",))
    compile_cache.clear()


def _op_body(monkeypatch, q_shape, k_shape, causal=False, mesh=None,
             on_cpu=True, dtype=jnp.float32):
    """Trace the op's compute at these shapes; return the kernel bodies it
    recorded and its output."""
    from paddle_tpu import compile_cache
    from paddle_tpu.ops import attention as att
    from paddle_tpu.registry import ComputeContext

    if on_cpu is not None:
        _cpu_takes_packed(monkeypatch, on_cpu)
    q, k, v = _rand(q_shape, 0).astype(dtype), \
        _rand(k_shape, 1).astype(dtype), _rand(k_shape, 2).astype(dtype)
    ctx = ComputeContext(key=jax.random.key(0), platform="cpu", mesh=mesh)

    def fn(q, k, v):
        return att._fused_attention_compute(
            {"Q": [q], "K": [k], "V": [v]}, {"causal": causal}, ctx, 0)["Out"]

    before = dict(compile_cache.stats()["kernel_bodies"])
    out = jax.jit(fn)(q, k, v)
    after = compile_cache.stats()["kernel_bodies"]
    bodies = {key: n - before.get(key, 0) for key, n in after.items()
              if n != before.get(key, 0)}
    ref = ax.reference_attention(q, k, v, None, None, causal)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    return bodies


@pytest.mark.parametrize("case", ["in_bound", "out_of_bound", "decode_shape",
                                  "sp_mesh", "flag_off", "cpu_keeps_xla"])
def test_fused_attention_body_is_chosen_from_what_the_op_observes(
        case, monkeypatch, request):
    """The packed body's rule: platform, mesh, shapes — no flag turns it
    on, FLAGS_pallas_kernels=False turns it off.  (The test lets the CPU
    platform take the kernel, interpreted; the program never does.)"""
    from paddle_tpu.parallel.mesh import make_mesh

    small = (2, 2, 16, 64)
    if case == "in_bound":
        assert _op_body(monkeypatch, small, small) == \
            {"fused_attention:packed": 1}
    elif case == "out_of_bound":
        big = (1, 8, 512, 64)
        assert _op_body(monkeypatch, big, big) == {"fused_attention:xla": 1}
    elif case == "decode_shape":
        assert _op_body(monkeypatch, (2, 2, 8, 64), small, causal=True) == \
            {"fused_attention:xla": 1}
    elif case == "sp_mesh":
        mesh = make_mesh((2, 4), ("dp", "sp"))
        assert _op_body(monkeypatch, small, small, mesh=mesh) == \
            {"fused_attention:ring": 1}
    elif case == "flag_off":
        request.getfixturevalue("no_pallas")
        assert _op_body(monkeypatch, small, small) == \
            {"fused_attention:xla": 1}
    else:
        assert _op_body(monkeypatch, small, small, on_cpu=False) == \
            {"fused_attention:xla": 1}


def _tiny_nmt(dropout):
    from paddle_tpu.models import transformer as tfm

    t = 16
    main, start = fluid.Program(), fluid.Program()
    main.random_seed = start.random_seed = 3
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        words = [fluid.layers.data(n, shape=[1], dtype="int64", lod_level=1)
                 for n in ("src_word", "tgt_word", "lbl_word")]
        cost, _ = tfm.transformer(*words, t, t, 64, 64, n_layer=2, n_head=4,
                                  d_model=128, d_inner=64,
                                  dropout_rate=dropout)
        fluid.optimizer.SGD(0.1).minimize(cost)
        feeder = fluid.DataFeeder(feed_list=words, pad_to=t)
    rng = np.random.RandomState(0)
    rows = []
    for _ in range(8):
        seq = rng.randint(2, 64, (rng.randint(2, t + 1),)).astype("int64")
        rows.append((seq, np.concatenate([[1], seq[:-1]]).astype("int64"),
                     seq))
    return main, start, cost, feeder.feed(rows)


def _nmt_step(monkeypatch, packed, mesh=None, dropout=0.2):
    """(loss, the first q projection's gradient, the attention's kernel
    bodies) of one step of a 2-layer Transformer."""
    from paddle_tpu import compile_cache

    _cpu_takes_packed(monkeypatch, packed)
    main, start, cost, feed = _tiny_nmt(dropout)
    grad = "enc0_attn_q.w_0@GRAD"
    before = dict(compile_cache.stats()["kernel_bodies"])
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(start)
        if mesh is None:
            loss, g = fluid.Executor(fluid.CPUPlace()).run(
                main, feed=feed, fetch_list=[cost, grad])
        else:
            pe = fluid.ParallelExecutor(loss_name=cost.name,
                                        main_program=main, mesh=mesh)
            loss, g = pe.run(feed=feed, fetch_list=[cost, grad])
    after = compile_cache.stats()["kernel_bodies"]
    # the attention's bodies: the two tables' ``lookup_table_grad`` notes
    # its own (tests/test_embedding_grad.py)
    bodies = {key: n - before.get(key, 0) for key, n in after.items()
              if n != before.get(key, 0) and key.startswith("fused_attention")}
    return float(np.asarray(loss).ravel()[0]), np.asarray(g), bodies


def test_packed_body_trains_the_transformer_like_the_xla_body(monkeypatch):
    """Through the Executor, with dropout: every attention of the program
    takes the packed forward and the one-kernel gradient, and the step is
    the XLA body's step."""
    loss_x, g_x, bodies_x = _nmt_step(monkeypatch, packed=False)
    loss_p, g_p, bodies_p = _nmt_step(monkeypatch, packed=True)
    assert bodies_x == {"fused_attention:xla": 12}    # 6 fwd + 6 re-traced
    assert bodies_p == {"fused_attention:packed": 6,
                        "fused_attention_grad:packed": 6}
    np.testing.assert_allclose(loss_p, loss_x, rtol=1e-6)
    np.testing.assert_allclose(g_p, g_x, rtol=1e-4, atol=1e-7)


def test_packed_body_under_a_dp_mesh_matches_one_device(monkeypatch):
    """ParallelExecutor on the CPU's virtual devices, the kernel
    interpreted per shard: loss and a parameter's gradient equal the
    one-device XLA run's."""
    from paddle_tpu.parallel.mesh import make_mesh

    loss_1, g_1, _ = _nmt_step(monkeypatch, packed=False, dropout=0.0)
    loss_m, g_m, bodies = _nmt_step(
        monkeypatch, packed=True, mesh=make_mesh((4,), ("dp",)), dropout=0.0)
    assert bodies == {"fused_attention:packed": 6,
                      "fused_attention_grad:packed": 6}
    np.testing.assert_allclose(loss_m, loss_1, rtol=1e-6)
    np.testing.assert_allclose(g_m, g_1, rtol=1e-4, atol=1e-7)


def test_packed_body_under_dp_x_tp_draws_the_global_dropout_mask(
        monkeypatch):
    """Batch over dp, whole heads over tp, dropout on: the shards' hash
    offsets (first global row, first global head) make the step the XLA
    body's on the same mesh, whose mask uses global indices."""
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh((2, 2), ("dp", "tp"))
    loss_x, g_x, _ = _nmt_step(monkeypatch, packed=False, mesh=mesh)
    loss_p, g_p, bodies = _nmt_step(monkeypatch, packed=True, mesh=mesh)
    assert bodies == {"fused_attention:packed": 6,
                      "fused_attention_grad:packed": 6}
    np.testing.assert_allclose(loss_p, loss_x, rtol=1e-6)
    np.testing.assert_allclose(g_p, g_x, rtol=1e-4, atol=1e-7)


def test_packed_body_under_dp_gathers_nothing(monkeypatch):
    """The kernel is a custom call GSPMD cannot partition; wrapped in
    shard_map each chip runs it on its own rows: the partitioned module
    of the op, forward and gradient, holds no all-gather (nor any other
    collective) of Q, K or V."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.ops import attention as att
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.registry import ComputeContext

    _cpu_takes_packed(monkeypatch)
    mesh = make_mesh((4,), ("dp",))
    ctx = ComputeContext(key=jax.random.key(0), platform="cpu", mesh=mesh)
    q, k, v, ct = (_rand((8, 2, 16, 64), i) for i in range(4))
    klen = jnp.asarray([16, 9, 0, 16, 3, 16, 16, 1], jnp.int32)

    def step(q, k, v, klen, ct):
        ins = {"Q": [q], "K": [k], "V": [v], "KLen": [klen]}
        out = att._fused_attention_compute(ins, {"causal": True}, ctx,
                                           0)["Out"]
        grads = att._fused_attention_grad_compute(
            dict(ins, **{"GRAD::Out": [ct]}),
            {"causal": True, "__fwd_type__": "fused_attention"}, ctx, 1)
        return out, grads["GRAD::Q"][0], grads["GRAD::K"][0], \
            grads["GRAD::V"][0]

    rows = NamedSharding(mesh, P("dp"))
    compiled = jax.jit(step, in_shardings=(rows,) * 5).lower(
        q, k, v, klen, ct).compile()
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text \
        and "collective-permute" not in text and "all-to-all" not in text
    out, dq, dk, dv = compiled(q, k, v, klen, ct)
    ref, vjp = jax.vjp(
        lambda q, k, v: ax.reference_attention(q, k, v, klen, None, True),
        q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    for g, g_ref in zip((dq, dk, dv), vjp(ct)):
        np.testing.assert_allclose(g, g_ref, rtol=2e-5, atol=2e-5)


def test_packed_body_cancels_the_head_split_and_merge_copies(monkeypatch):
    """The model splits heads by reshape + transpose before the op and
    merges them after it; the packed body merges on entry and splits on
    exit, so each pair is a transpose of a transpose: the optimized module
    of a 2-layer model holds fewer transposes with the packed body than
    with the XLA body, and no 4-D [B, H, T, D] transpose at all."""
    import re

    from paddle_tpu import executor as ex

    def transposes(packed):
        _cpu_takes_packed(monkeypatch, packed)
        main, start, cost, feed = _tiny_nmt(0.0)
        names = sorted(feed)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(start)
            scope = fluid.global_scope()
            vals = [ex._coerce_feed(main.global_block(), n, feed[n])
                    for n in names]
            state, writeback = ex.analyze(main, names, scope, [cost.name])
            fn, _, _ = ex.trace_program(main, names, state, writeback,
                                        [cost.name], platform="cpu")
            text = jax.jit(fn).lower(
                vals, [scope.var(n) for n in state],
                jax.random.key(0)).compile().as_text()
        found = re.findall(r"= \S+?\[([\d,]*)\]\S* transpose\(", text)
        return [dims for dims in found if dims.count(",") == 3]

    assert len(transposes(packed=False)) > 0
    assert transposes(packed=True) == []
