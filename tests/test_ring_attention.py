"""Ring attention (context parallelism) tests on the 8-device virtual
mesh: numerical parity with full attention, causal masking, gradients,
and composition with a dp axis."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.parallel import ring_attention
from paddle_tpu.parallel.mesh import make_mesh


def _full_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        t = q.shape[2]
        mask = np.tril(np.ones((t, t), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    rng = np.random.RandomState(0)
    b, h, t, d = 2, 3, 32, 8
    q = rng.randn(b, h, t, d).astype("float32")
    k = rng.randn(b, h, t, d).astype("float32")
    v = rng.randn(b, h, t, d).astype("float32")
    mesh = make_mesh((8,), ("sp",))
    out = ring_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         mesh, axis="sp", causal=causal)
    want = _full_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)


def test_ring_attention_gradients_match_full():
    rng = np.random.RandomState(1)
    b, h, t, d = 1, 2, 16, 4
    q = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    mesh = make_mesh((8,), ("sp",))

    def ring_loss(q_, k_, v_):
        return jnp.sum(ring_attention(q_, k_, v_, mesh, axis="sp",
                                      causal=True) ** 2)

    def full_loss(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) / np.sqrt(d)
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v_) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-4)


def test_ring_attention_with_dp_axis():
    """sp composes with dp: batch sharded over dp, time over sp."""
    rng = np.random.RandomState(2)
    b, h, t, d = 4, 2, 8, 4
    q = rng.randn(b, h, t, d).astype("float32")
    k = rng.randn(b, h, t, d).astype("float32")
    v = rng.randn(b, h, t, d).astype("float32")
    mesh = make_mesh((2, 4), ("dp", "sp"))
    out = ring_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         mesh, axis="sp", batch_axis="dp")
    assert len(out.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(out),
                               _full_attention(q, k, v), atol=2e-5)


def test_ring_attention_bf16_accumulates_in_fp32():
    rng = np.random.RandomState(5)
    b, h, t, d = 1, 2, 32, 8
    q = rng.randn(b, h, t, d).astype("float32")
    k = rng.randn(b, h, t, d).astype("float32")
    v = rng.randn(b, h, t, d).astype("float32")
    mesh = make_mesh((8,), ("sp",))
    out = ring_attention(jnp.asarray(q, jnp.bfloat16),
                         jnp.asarray(k, jnp.bfloat16),
                         jnp.asarray(v, jnp.bfloat16), mesh)
    assert out.dtype == jnp.bfloat16
    want = _full_attention(q, k, v)
    # bf16 inputs, fp32 accumulation: error bounded by input precision
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), want, atol=0.05)


def test_ring_attention_rejects_unknown_axis():
    mesh = make_mesh((8,), ("dp",))
    with pytest.raises(ValueError, match="no axis"):
        ring_attention(jnp.zeros((1, 1, 8, 4)), jnp.zeros((1, 1, 8, 4)),
                       jnp.zeros((1, 1, 8, 4)), mesh, axis="sp")
    sp = make_mesh((8,), ("sp",))
    with pytest.raises(ValueError, match="must differ"):
        ring_attention(jnp.zeros((1, 1, 8, 4)), jnp.zeros((1, 1, 8, 4)),
                       jnp.zeros((1, 1, 8, 4)), sp, axis="sp",
                       batch_axis="sp")


def test_ring_attention_with_tp_sharded_heads():
    """sp composes with tp: heads sharded over tp inside the ring
    (ops/attention.py passes head_axis_name), batch over dp."""
    from paddle_tpu.ops.attention import _ring_attention

    rng = np.random.RandomState(7)
    b, h, t, d = 2, 4, 4, 4
    q = rng.randn(b, h, t, d).astype("float32")
    k = rng.randn(b, h, t, d).astype("float32")
    v = rng.randn(b, h, t, d).astype("float32")
    mesh = make_mesh((2, 2, 2), ("dp", "tp", "sp"))
    out = _ring_attention(mesh, jnp.asarray(q), jnp.asarray(k),
                          jnp.asarray(v), None, None, False, 0.0, None)
    assert len(out.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(out),
                               _full_attention(q, k, v), atol=2e-5)


def test_ring_attention_tp_heads_dropout_mask_parity():
    """The dropout hash must use GLOBAL head indices: a tp-sharded ring
    run reproduces the single-chip mask bit-for-bit."""
    from paddle_tpu.ops.attention import _ring_attention
    from paddle_tpu.ops.attention_xla import reference_attention

    rng = np.random.RandomState(8)
    b, h, t, d = 2, 4, 4, 4
    q = rng.randn(b, h, t, d).astype("float32")
    k = rng.randn(b, h, t, d).astype("float32")
    v = rng.randn(b, h, t, d).astype("float32")
    seed = jnp.asarray(12345, jnp.uint32)
    mesh = make_mesh((2, 2, 2), ("dp", "tp", "sp"))
    out = _ring_attention(mesh, jnp.asarray(q), jnp.asarray(k),
                          jnp.asarray(v), None, seed, False, 0.3, None)
    want = reference_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), None, seed, False, 0.3,
                               None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)
