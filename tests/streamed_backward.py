"""The streamed attention's two backward bodies on the same operands, for
the decoder cells' tests: the fused kernel (one ``pallas_call``: dQ, dK and
dV from one ``s``, ``p``, ``g`` and ``ds`` a block pair) against the dQ and
dK/dV kernels and against ``jax.vjp`` of the XLA body, all interpreted; and a
latent block's attention, and a grouped-head block's, each as two programs:
composed of Fluid ops around the 4-D op and as the ONE op over the
projections' layout."""

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


# ---- a latent block's attention, composed of Fluid ops and as ONE op -------------

def latent_attention_programs(n, t, nope, rope, dv, theta, scale):
    """Two programs over the same feeds — ``q`` [B, t, n * (nope + rope)],
    ``kv`` [B, t, n * (nope + dv)], ``kr`` [B, t, rope] as a latent block's
    three projections write them, ``ct`` the result's cotangent — each with
    its backward: the block's attention as a model composed it before the op
    took the projections' layout (reshape / split / rotate / expand / concat
    / transpose around the 4-D ``fused_attention``), and the op over that
    layout.  Returns ``[(main, fetches)] * 2``, the fetches ``Out``, ``LSE``,
    dQ, dKV, dKShared and — the composition's, None the op's — the joined
    keys' gradient [B, n, t, nope + rope]."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    def composed(q, kv, kr):
        def rotate(v):
            if theta is None:
                return v
            return layers.rotary_embedding(v, theta=theta, interleaved=True)

        def to_bhtd(x):
            return layers.transpose(x, perm=[0, 2, 1, 3])
        q = layers.reshape(q, shape=[0, 0, n, nope + rope])
        if theta is not None:
            q_nope, q_rope = layers.split(q, [nope, rope], dim=-1)
            q = layers.concat([q_nope, rotate(q_rope)], axis=3)
        k_nope, v = layers.split(layers.reshape(
            kv, shape=[0, 0, n, nope + dv]), [nope, dv], dim=-1)
        kr = layers.expand(rotate(layers.reshape(kr, shape=[0, 0, 1, rope])),
                           [1, 1, n, 1])
        k = to_bhtd(layers.concat([k_nope, kr], axis=3))
        ctx = layers.fused_attention(to_bhtd(q), k, to_bhtd(v), causal=True,
                                     scale=scale)
        return layers.reshape(to_bhtd(ctx), shape=[0, 0, n * dv]), k

    def in_place(q, kv, kr):
        return layers.fused_attention(
            q, kv, causal=True, scale=scale, n_head=n, v_dim=dv, k_shared=kr,
            rope_theta=theta), None

    programs = []
    for build in (composed, in_place):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()), \
                fluid.unique_name.guard():
            feeds = [layers.data(name, shape=[t, width], dtype="float32")
                     for name, width in (("q", n * (nope + rope)),
                                         ("kv", n * (nope + dv)),
                                         ("kr", rope), ("ct", n * dv))]
            for x in feeds[:3]:
                x.stop_gradient = False
            out, joined = build(*feeds[:3])
            fluid.append_backward(layers.reduce_sum(
                layers.elementwise_mul(out, feeds[3])))
        op = next(o for o in main.global_block().ops
                  if o.type == "fused_attention")
        programs.append((main, [
            out, op.outputs["LSE"][0], "q@GRAD", "kv@GRAD", "kr@GRAD"]
            + ([] if joined is None else [joined.name + "@GRAD"])))
    return programs


# ---- grouped heads, composed of Fluid ops and as ONE op ---------------------------

def grouped_attention_programs(n, hk, d, t, window=None, law=None,
                               selected=False, head_norm=False, dv=None):
    """Two programs over the same feeds — ``q`` [B, t, n * d], ``k`` [B, t,
    hk * d], ``v`` [B, t, hk * dv] as a block's three projections write
    them, ``ct`` the result's cotangent and, ``selected``, the packed key
    mask ``sel`` — each with its backward: the block's attention as the
    decoders composed it before the op took the projections' layout (view
    as heads, a per-head RMSNorm where ``head_norm``, ``rotary_embedding``
    by ``law`` — that layer's keywords —, transposes around the 4-D
    ``fused_attention``), and the op over that layout, the rotation inside
    it and the per-head norm an ``rms_norm(group=d)`` before it.  Returns ``[(main, fetches)] * 2``, the fetches ``Out``, ``LSE``,
    dQ, dK, dV."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.ops import sparse_select as ss
    from paddle_tpu.param_attr import ParamAttr

    dv = d if dv is None else dv
    law = law or {}

    def heads(x, m, width=d):
        return layers.reshape(x, shape=[0, 0, m, width])

    def normed(x, name):
        return layers.rms_norm(x, 1e-6, ParamAttr(name=name)) \
            if head_norm else x

    def composed(q, k, v, sel):
        def rotate(x):
            return layers.rotary_embedding(x, **law) if law else x

        def to_bhtd(x):
            return layers.transpose(x, perm=[0, 2, 1, 3])
        q = rotate(normed(heads(q, n), "q_g"))
        k = rotate(normed(heads(k, hk), "k_g"))
        ctx = layers.fused_attention(
            to_bhtd(q), to_bhtd(k), to_bhtd(heads(v, hk, dv)), causal=True,
            scale=d ** -0.5, window=window, selected=sel)
        return layers.reshape(to_bhtd(ctx), shape=[0, 0, n * dv])

    def one(q, k, v, sel):
        if head_norm:
            q, k = (layers.rms_norm(x, 1e-6, ParamAttr(name=name), group=d)
                    for x, name in ((q, "q_g"), (k, "k_g")))
        return layers.fused_attention(
            q, k, v, causal=True, scale=d ** -0.5, window=window,
            selected=sel, n_head=n,
            **{"rope_" + key: value for key, value in law.items()})

    programs = []
    for build in (composed, one):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            feeds = [layers.data(name, shape=[t, width], dtype="float32")
                     for name, width in (("q", n * d), ("k", hk * d),
                                         ("v", hk * dv), ("ct", n * dv))]
            for x in feeds[:3]:
                x.stop_gradient = False
            sel = layers.data("sel", shape=[t, ss.packed_width(t)],
                              dtype="int32") if selected else None
            out = build(*feeds[:3], sel)
            fluid.append_backward(layers.reduce_sum(
                layers.elementwise_mul(out, feeds[3])))
        op = next(o for o in main.global_block().ops
                  if o.type == "fused_attention")
        # plain heads short enough for the 4-D op's other bodies keep no
        # log-sum-exp there: the output stands in and nobody compares it
        programs.append((main, [out, op.outputs.get("LSE", [out])[0],
                                "q@GRAD", "k@GRAD", "v@GRAD"], startup))
    return programs
