"""What the window / full attention mixture-of-experts decoder (ISSUE 49)
added, on the CPU at small sizes: ``rotary_embedding``'s frequency law and
scale — against a float64 table forward and backward, the ramp's ends as
computed, the degenerate ramp, and without the two attributes bit for bit
what it was — a window layer over grouped 128-wide heads against the
explicit mask (T off the window, a window over the whole row) in both
bodies, the decoder whose attention half is chosen by layer, the expert
layer's eight shares, the planted faults, and the tiny model's training
against the plain reference."""

import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu.models import sparse_moe_decoder as smd
from paddle_tpu.ops import activation as act
from paddle_tpu.ops import attention as att

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness                                 # noqa: E402
from benchmark.generators import train_window_steps as gen   # noqa: E402

CELL = "mellum2_12b_a2_5b.train_repo_8k"
# rope_parameters.full_attention of the published config.json
YARN = {"factor": 16, "original_length": 8192, "beta_fast": 32,
        "beta_slow": 1}
FACTOR = 1.2772588722239782


def _cfg(**over):
    bench = harness.load_benchmark(ROOT)
    _, cfg, traffic = harness.resolve_cell(bench, CELL, tiny=True)
    return dict(cfg, **over), traffic


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / (jnp.linalg.norm(jnp.asarray(b)) + 1e-30))


# ---- the rotation ------------------------------------------------------------------

def _table64(t, dim, theta, scaling, scale):
    """(cos, sin) [t, dim / 2] in float64, YaRN as ``transformers``'
    ``_compute_yarn_parameters`` computes it, and the ramp's ends."""
    w = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low = high = None
    if scaling is not None:
        def turns(r):
            return dim * math.log(scaling["original_length"]
                                  / (r * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(turns(scaling["beta_fast"])), 0)
        high = min(math.ceil(turns(scaling["beta_slow"])), dim - 1)
        top = high + 0.001 if low == high else high
        ramp = np.clip((np.arange(dim // 2) - low) / (top - low), 0, 1)
        w = (1 - ramp) * w + ramp * w / scaling["factor"]
    angle = np.arange(t, dtype=np.float64)[:, None] * w[None, :]
    return scale * np.cos(angle), scale * np.sin(angle), (low, high)


def _rotated64(x, cos, sin, interleaved, back=False):
    """``x`` [B, T, H, D] rotated in float64 — or, ``back``, by the negative
    angle (the gradient of the rotation) — at the same scale."""
    x = np.asarray(x, np.float64)
    if back:
        sin = -sin
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        out = np.empty_like(x)
        out[..., 0::2], out[..., 1::2] = a * c - b * s, b * c + a * s
        return out
    h = x.shape[-1] // 2
    a, b = x[..., :h], x[..., h:]
    return np.concatenate([a * c - b * s, b * c + a * s], -1)


def _rotary_program(shape, **kw):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=list(shape[1:]), dtype="float32")
        ct = fluid.layers.data("ct", shape=list(shape[1:]), dtype="float32")
        x.stop_gradient = False
        out = fluid.layers.rotary_embedding(x, **kw)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, ct))
        grad, = fluid.backward.calc_gradient(loss, [x])
    return main, [out, grad]


ROTARY_CASES = [
    # Mellum's full layers at their head width; a short original length
    # (the ramp's ends 0 and 2 of 8); both ends clamped to 0: low == high
    (128, 500000.0, YARN, FACTOR, False, (18, 35)),
    (16, 10000.0, dict(YARN, original_length=32), FACTOR, False, (0, 2)),
    (16, 10000.0, dict(YARN, original_length=4), 1.0, False, (0, 0)),
    (32, 10000.0, dict(YARN, factor=4, original_length=64), 0.5, True,
     (0, 5)),
    # the scale alone, and neither: the plain law
    (16, 10000.0, None, FACTOR, False, (None, None)),
    (16, 10000.0, None, 1.0, True, (None, None)),
]


@pytest.mark.parametrize("dim,theta,scaling,scale,interleaved,ends",
                         ROTARY_CASES)
def test_rotary_against_a_float64_table(dim, theta, scaling, scale,
                                        interleaved, ends):
    """Forward and gradient of the op through the executor against a
    float64 table: the gradient is the counter-rotation at the same
    frequencies times the same scale."""
    t, shape = 40, (2, 40, 3, dim)
    cos, sin, got_ends = _table64(t, dim, theta, scaling, scale)
    assert got_ends == ends
    if scaling is not None:
        w = act.scaled_frequencies(dim, theta, scaling)
        assert w.dtype == np.float64 and w.shape == (dim // 2,)
        np.testing.assert_allclose(
            np.cos(np.arange(t)[:, None] * w[None, :]) * scale, cos,
            rtol=0, atol=1e-12)
    rng = np.random.RandomState(3)
    x, ct = (rng.randn(*shape).astype("float32") for _ in range(2))
    main, fetch = _rotary_program(shape, theta=theta, interleaved=interleaved,
                                  freq_scaling=scaling, scale=scale)
    op, = [o for o in main.global_block().ops
           if o.type == "rotary_embedding"]
    assert ("freq_scaling" in op.attrs) == (scaling is not None)
    assert ("scale" in op.attrs) == (scale != 1.0)
    out, grad = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x, "ct": ct}, fetch_list=fetch)
    assert _rel(out, _rotated64(x, cos, sin, interleaved)) < 2e-6
    assert _rel(grad, _rotated64(ct, cos, sin, interleaved, back=True)) < 2e-6
    if scaling is not None and ends != (0, 0):
        # another function than the plain law at the same scale
        plain = _table64(t, dim, theta, None, scale)
        assert _rel(out, _rotated64(x, *plain[:2], interleaved)) > 1e-2


def test_mellums_ramp_ends_and_frequencies():
    """18.08 and 34.98 truncate to 18 and 35: the first 19 frequencies are
    the plain law's, those from the 36th on a sixteenth of it."""
    w = act.scaled_frequencies(128, 500000.0, YARN)
    w0 = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(w[:19], w0[:19], rtol=1e-15)
    np.testing.assert_allclose(w[35:], w0[35:] / 16, rtol=1e-15)
    assert ((w[19:35] < w0[19:35]) & (w[19:35] > w0[19:35] / 16)).all()
    np.testing.assert_allclose(w[19], w0[19] * (1 - 1 / 17 * 15 / 16),
                               rtol=1e-12)


@pytest.mark.parametrize("interleaved", [False, True])
def test_rotary_without_the_new_attributes_is_what_it_was(interleaved):
    """An op without a frequency scaling and without a scale carries neither
    attribute (its program's text and fingerprint do not move) and computes,
    bit for bit, what the op computed before it could take them."""
    def was(x, theta):
        d = x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
            * inv[None, :]
        angle = jnp.concatenate([angle, angle], -1)
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d,)
        xf = x.astype(jnp.float32)
        if interleaved:
            cos, sin = (jnp.repeat(t[:, :d // 2], 2, -1) for t in (cos, sin))
            pair = xf.reshape(xf.shape[:-1] + (d // 2, 2))
            half = jnp.stack([-pair[..., 1], pair[..., 0]], -1).reshape(
                xf.shape)
        else:
            half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
        return (xf * cos.reshape(shape)
                + half * sin.reshape(shape)).astype(x.dtype)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 33, 3, 16), jnp.bfloat16)
    attrs = {"theta": 500000.0}
    if interleaved:
        attrs["interleaved"] = True
    got = jax.jit(lambda x: act._rotary_compute({"X": [x]}, attrs, None,
                                                0)["Out"])(x)
    want = jax.jit(lambda x: was(x, 500000.0))(x)
    assert got.dtype == jnp.bfloat16
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()
    main, _ = _rotary_program((2, 8, 2, 16), theta=500000.0,
                              interleaved=interleaved)
    op, = [o for o in main.global_block().ops
           if o.type == "rotary_embedding"]
    assert {k: v for k, v in op.attrs.items() if k != "op_namescope"} == attrs
    assert str(jax.make_jaxpr(lambda x: act._rotary_compute(
        {"X": [x]}, attrs, None, 0)["Out"])(x)) == str(jax.make_jaxpr(
            lambda x: was(x, 500000.0))(x))


# ---- a window over grouped 128-wide heads ---------------------------------------------

H, HK, D = 8, 1, 128


def _qkv(t, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=s), jnp.float32) for s in (
        (1, H, t, D), (1, HK, t, D), (1, HK, t, D), (1, H, t, D))]


def _dense(q, k, v, window):
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, H // HK, 1),
                   precision="highest") * D ** -0.5
    i = jnp.arange(t)
    keep = i[None, :] <= i[:, None]
    if window is not None:
        keep = keep & (i[None, :] > i[:, None] - window)
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v, H // HK, 1),
                      precision="highest")


def _window_program(t, window):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, do = (fluid.layers.data(n, shape=[H, t, D], dtype="float32")
                 for n in ("q", "do"))
        k, v = (fluid.layers.data(n, shape=[HK, t, D], dtype="float32")
                for n in ("k", "v"))
        for x in (q, k, v):
            x.stop_gradient = False
        out = fluid.layers.fused_attention(q, k, v, causal=True,
                                           scale=D ** -0.5, window=window)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, do))
        grads = fluid.backward.calc_gradient(loss, [q, k, v])
    return main, [out] + list(grads)


# T off the window (a last, partial window), a window over the whole row
# and none, on eight query heads a 128-wide key/value head
@pytest.mark.parametrize("streamed,t,window", [
    (False, 200, 48), (False, 200, 200), (False, 200, 1024),
    (True, 384, 200), (True, 256, 1024), (True, 256, None)])
def test_a_window_layer_against_the_explicit_mask(monkeypatch, streamed, t,
                                                  window):
    """``layers.fused_attention(window=)`` over eight query heads a K/V head
    of 128 through the executor, forward and the three gradients, against a
    dense masked softmax: the XLA body on the CPU and the streamed kernels
    (interpreted) with the fused backward's whole group a step."""
    if streamed:
        monkeypatch.setattr(att, "_STREAMED_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    main, fetch = _window_program(t, window)
    op, = [o for o in main.global_block().ops if o.type == "fused_attention"]
    assert op.attrs.get("window") == window and op.outputs.get("LSE")
    q, k, v, do = _qkv(t, seed=6)
    before = dict(compile_cache.stats()["kernel_bodies"])
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={n: np.asarray(x) for n, x in zip("q k v do".split(),
                                                     (q, k, v, do))},
        fetch_list=fetch)
    stats = compile_cache.stats()["kernel_bodies"]
    notes = ("fused_attention:streamed", "streamed_step:1x8",
             "fused_attention_grad:streamed_fused",
             "streamed_grad_step:1x8") if streamed else (
        "fused_attention:xla",)
    for note in notes:
        assert stats.get(note, 0) > before.get(note, 0), note
    ref, vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, window), q, k, v)
    for g, w in zip(got, (ref,) + vjp(do)):
        assert _rel(g, w) < 5e-6
    if window is not None and window < t:
        full = _dense(q, k, v, None)
        assert _rel(got[0], full) > 1e-2
    if streamed:
        compile_cache.clear()


# ---- the layer's shares ------------------------------------------------------------

def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """The eight shares of the tiny layer's 8 experts (one held each; no
    expert is shared, nothing is computed alike on every chip), through the
    reference's own layer: the parts the shares give add up to what the
    layer holding all 8 gives, and every token-expert pair is counted
    once."""
    cfg, _ = _cfg()
    ref = harness.load_reference(cfg["reference"])
    whole = dict(cfg, num_experts_held=8, first_local_expert=0)
    spec = {n: s for n, s in ref.param_spec(whole).items()
            if n.startswith("l1.moe.")}
    p = {n: jnp.asarray(v) for n, v in gen.seeded_weights(spec, {}, 3).items()}
    x = jnp.asarray(np.random.RandomState(4).randn(
        64, cfg["hidden_size"]).astype("float32"))
    want, n_all = ref.experts(p, "l1.", x, whole, (8, 0), ref.f32_matmul)
    assert int(n_all) == 64 * cfg["num_experts_per_tok"]
    parts, pairs = zip(*(ref.experts(p, "l1.", x, whole, (1, i),
                                     ref.f32_matmul) for i in range(8)))
    assert sum(int(n) for n in pairs) == int(n_all)
    assert all(int(n) > 0 for n in pairs)
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)
    # the program's share is the reference's share (the tiny cell holds
    # experts 2 and 3 of 8): test_tiny_model_trains_... compares them whole
    assert ref.share_of(cfg) == (2, 2)


# ---- the model ---------------------------------------------------------------------

def _train(precision, seed=5):
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
    """The loss over three steps, the first gradient leaf by leaf, three
    Adam steps, the routed and the dropless counts and both kinds'
    attention outputs: in float32 the program and the reference are the same
    mathematics."""
    cfg, model, gaps, prog, want = _train("float32")
    assert gaps["loss_rel_gap"] < 3e-6
    assert gaps["grad_rel_error_rms"] < 2e-5
    assert gaps["grad_norm_gap"] < 2e-5 and gaps["update_norm_gap"] < 2e-4
    assert gaps["routed_pairs_gap"] == 0
    assert max(gaps["context_gaps"]) < 5e-6 and len(gaps["context_gaps"]) == 2
    assert all(s["pairs_routed"] == s["pairs_computed"] > 0
               for s in prog["stats"])
    # 80 tokens under a window of 24: (3 * 1644 + 3240) of 4 * 3240 pairs
    assert all(s["window_pair_share"] == pytest.approx(8172 / 12960)
               for s in prog["stats"])
    # every leaf has a gradient and an Adam state
    ref = harness.load_reference(cfg["reference"])
    assert set(want["grad_norms"]) == set(ref.param_spec(cfg))
    assert all(v > 0 for v in want["grad_norms"].values())
    assert model.scope.find_var("l3.attn.k_moment1_0") is not None
    model.close()


def test_tiny_model_trains_like_the_plain_reference_in_bf16():
    """Under bf16 AMP, inside the tiny limits of ``correct``."""
    cfg, model, gaps, prog, _ = _train("bf16_amp")
    limits = cfg["limits"]
    for name, value in gaps.items():
        if name != "context_gaps":
            assert value <= limits[name], (name, value)
    assert gaps["grad_rel_error_rms"] > 1e-4              # bf16 did round
    assert all(s["pairs_routed"] == s["pairs_computed"] for s in
               prog["stats"])
    model.close()


def test_program_chooses_its_attention_half_by_layer():
    cfg, traffic = _cfg()
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    ops = model.main.global_block().ops
    attention = [op for op in ops if op.type == "fused_attention"]
    assert [op.attr("window") for op in attention] == [24, 24, 24, None]
    assert all(op.outputs.get("LSE") for op in attention)       # grouped heads
    assert [op.attr("window") for op in ops
            if op.type == "fused_attention_grad"] == [None, 24, 24, 24]
    # the rotation of q and k rides on each layer's op (ISSUE 50): the plain
    # law on the window layers, YaRN's blend and the attention factor on
    # the full one; the op takes the three projections as they lie
    rope = cfg["rope_parameters"]["full_attention"]
    want = {"factor": 16.0, "beta_fast": 32.0, "beta_slow": 1.0,
            "original_length": float(rope["original_max_position_embeddings"])}
    for op in attention[:3]:
        assert "rope_freq_scaling" not in op.attrs \
            and "rope_scale" not in op.attrs
    assert attention[3].attr("rope_freq_scaling") == want
    assert attention[3].attr("rope_scale") == rope["attention_factor"]
    assert all(op.attr("rope_theta") == rope["rope_theta"]
               and op.attr("n_head") == cfg["num_attention_heads"]
               and not op.attr("rope_interleaved") for op in attention)
    block = model.main.global_block()
    for op in attention:
        assert [len(block.var(op.input(s)[0]).shape) for s in "QKV"] \
            == [3, 3, 3]
    types = [op.type for op in ops]
    assert not {"rotary_embedding", "transpose"} & set(types)
    assert types.count("moe_expert_ffn") == 4 and "select_keys" not in types
    assert types.count("rms_norm") == 9             # no per-head norm
    params = {p.name for p in model.main.global_block().all_parameters()}
    assert "l0.attn.q" in params and not {"l0.attn.q_g", "l0.idx.q"} & params
    assert model.main.step_stats[1] == smd.WINDOW_STEP_STATS
    assert smd.WINDOW_STEP_STATS[:3] == smd.STEP_STATS[:3]
    assert len(gen.STATS) == len(smd.WINDOW_STEP_STATS)
    assert smd.attention_pair_share(model.main) == pytest.approx(8172 / 12960)
    # the trace's names of the window layers' ops and of their gradients
    assert len(model.window_scopes) == 6
    assert sum(s.startswith("fluid[fused_attention]")
               for s in model.window_scopes) == 3
    with pytest.raises(ValueError, match="'window' or 'full'"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            tok = fluid.layers.data("tok", shape=[8, 1], dtype="int64")
            smd.window_moe_decoder_lm(
                tok, tok, 16, 32, ("window", "kda"), 2, 1, 16, 4,
                {"window": (1e4, None, 1.0), "full": (1e4, None, 1.0)},
                (2, 4, 0), 8, 2)
    model.close()


def test_the_pair_share_at_the_cells_shape():
    """8192 tokens under three windows of 1024 and one full layer:
    57,153,024 of 134,234,112 causal pairs, read off the program's ops."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        q = fluid.layers.data("q", shape=[8, 8192, 16], dtype="float32")
        k = fluid.layers.data("k", shape=[1, 8192, 16], dtype="float32")
        for window in (1024, 1024, 1024, None):
            fluid.layers.fused_attention(q, k, k, causal=True, window=window)
    assert smd.attention_pair_share(main) == 57153024 / 134234112
    assert round(smd.attention_pair_share(main), 4) == 0.4258


@pytest.mark.parametrize("fault", sorted(gen.FAULTS))
def test_a_planted_fault_changes_the_reference(fault):
    """Each fault the limits of ``correct`` stand against is another
    function: the reference's loss, first gradient or update moves (the
    benchmark's own test holds each to the cell's limits)."""
    cfg, traffic = _cfg()
    ref = harness.load_reference(cfg["reference"])
    batch = {n: jnp.asarray(v, jnp.int32) for n, v in gen.make_batches(
        dict(traffic, pool=1), cfg["vocab_size"], 7)[0].items()}
    p = {n: jnp.asarray(v) for n, v in gen.seeded_weights(
        ref.param_spec(cfg), cfg, 7).items()}
    sound = ref.loss_and_grad(p, batch, cfg, cfg["reference_block_rows"])
    faulty_cfg = gen.FAULTS[fault](cfg)
    faulty = ref.loss_and_grad(p, batch, faulty_cfg,
                               cfg["reference_block_rows"])
    if fault == "state_unchanged":
        moved, _ = ref.adam_step(p, sound[3], ref.adam_init(p), cfg)
        same, _ = ref.adam_step(p, faulty[3], ref.adam_init(p), faulty_cfg)
        assert _rel(moved["l0.attn.q"], p["l0.attn.q"]) > 0
        assert _rel(same["l0.attn.q"], p["l0.attn.q"]) == 0
        return
    assert abs(float(faulty[0]) - float(sound[0])) > 1e-6
    assert _rel(faulty[3]["l0.attn.q"], sound[3]["l0.attn.q"]) > 1e-3
    # which attention output moves says where the fault sits
    window, full = (_rel(faulty[1][i], sound[1][i]) for i in (0, 1))
    if fault in ("full_plain_rotation", "factor_on_query_alone",
                 "ramp_ends_swapped"):
        # layer 0's input is the embedding: the window layer's ctx is sound
        assert window == 0 and full > 1e-3
    elif fault != "weights_not_renormalised":
        assert window > 1e-3
