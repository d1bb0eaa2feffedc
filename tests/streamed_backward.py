"""The streamed attention's two backward bodies on the same operands, for
the decoder cells' tests: the fused kernel (one ``pallas_call``: dQ, dK and
dV from one ``s``, ``p``, ``g`` and ``ds`` a block pair) against the dQ and
dK/dV kernels and against ``jax.vjp`` of the XLA body, all interpreted."""

import numpy as np

import jax

from paddle_tpu import compile_cache
from paddle_tpu.ops import attention_xla as fa
from paddle_tpu.ops.pallas import streamed_attention as sa


def _traces():
    return compile_cache.stats()["kernel_traces"].get(
        "streamed_attention", {"sites": 0, "traces": 0})["sites"]


def check_fused_backward(monkeypatch, q, k, v, ct, packed=None, causal=True,
                         scale=None, window=None, heads=None, same_bits=True):
    """dQ, dK, dV of the fused kernel at ``heads`` (K/V heads, query heads
    of each) a grid step — the rule's own where None — equal the dQ kernel's
    dQ to the bit, the dK/dV kernel's dK and dV to the bit where
    ``same_bits`` (a key block's additions arrive in the same order: always
    but for a group's heads split over steps otherwise than the two kernels
    split them) and to float32 round-off elsewhere, and ``jax.vjp`` of the
    XLA body within the kernels' standing tolerance.  The fused body is one
    call site, the two kernels two.  Returns the fused body's three."""
    out, lse = sa.forward(q, k, v, packed, causal, scale, True, window)
    rule = sa._fused_heads_per_step

    def backward(answer, body, sites):
        monkeypatch.setattr(sa, "_fused_heads_per_step", answer)
        took, a_step = sa.grad_step(q, k, v)
        assert took == body and (heads is None or body == "streamed"
                                 or a_step == heads)
        before = _traces()
        got = sa.backward(q, k, v, packed, out, lse, ct, causal, scale, True,
                          window)
        assert _traces() - before == sites
        return got
    fused = backward(rule if heads is None else lambda *a: heads,
                     "streamed_fused", 1)
    two = backward(lambda *a: None, "streamed", 2)
    monkeypatch.setattr(sa, "_fused_heads_per_step", rule)
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(two[0]))
    for a, b in zip(fused[1:], two[1:]):
        if same_bits:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    _, vjp = jax.vjp(lambda q, k, v: fa.reference_attention(
        q, k, v, None, None, causal, 0.0, scale, packed, False, window),
        q, k, v)
    for a, b in zip(fused, vjp(ct)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    return fused
