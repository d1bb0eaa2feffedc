"""What the delta-attention / latent-attention mixture-of-experts decoder
(ISSUE 46) added, on the CPU at small sizes: the ``gated_delta_rule`` op —
its chunked body against the token-by-token recurrence forward and backward,
against itself at two chunk sizes, its final state and the states its groups
start on, what it does around the rule (the norms, the scale, the gate's
activation, the gated head-wise norm) — the latent mixer without a
query rank and without rotation, the expert layer's shares with the shared
expert counted once, the planted faults, and the tiny model's training
against the plain reference."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import sparse_moe_decoder as smd
from paddle_tpu.ops import gated_delta_rule as gdr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness                                 # noqa: E402
from benchmark.generators import train_delta_steps as gen     # noqa: E402

CELL = "kimi_linear_48b_a3b.train_doc_4k"


def _cfg(**over):
    bench = harness.load_benchmark(ROOT)
    _, cfg, traffic = harness.resolve_cell(bench, CELL, tiny=True)
    return dict(cfg, **over), traffic


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / (jnp.linalg.norm(jnp.asarray(b)) + 1e-30))


# ---- the rule --------------------------------------------------------------------

def _recurrence(q, k, v, g, beta):
    """``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``, ``o_t =
    S_t^T q_t``, a step at a time: (o, S_T, the state BEFORE each step [T,
    B, H, Dk, Dv])."""
    b, t, h, dk = q.shape

    def step(s, x):
        qt, kt, vt, gt, bt = x
        before, s = s, jnp.exp(gt)[..., None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, s,
                                             precision="highest"))
        s = s + kt[..., None] * u[:, :, None, :]
        return s, (jnp.einsum("bhk,bhkv->bhv", qt, s, precision="highest"),
                   before)
    s, (o, before) = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s, before


def _operands(t, decay, beta=None, seed=1, b=2, h=2, dk=16, dv=24):
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k = (jax.random.normal(ks[i], (b, t, h, dk)) for i in (0, 1))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -decay * jax.random.uniform(ks[3], (b, t, h, dk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))) \
        if beta is None else jnp.full((b, t, h), beta, jnp.float32)
    return q, k, v, g, beta


# T off the chunk; alpha near 1 (decay 1e-4) and near 0 (a step's log-decay
# down to -30: a chunk's passes e^-88 many times over); beta 0 and 1
RULE_CASES = [(100, 0.1, None, 64), (37, 30.0, None, 32),
              (130, 1e-4, 1.0, 64), (64, 2.0, 0.0, 16),
              (200, 8.0, None, 64), (49, 0.5, None, 16)]


@pytest.mark.parametrize("t,decay,beta,chunk", RULE_CASES)
def test_chunked_body_against_the_recurrence(t, decay, beta, chunk):
    """Outputs, final state and gradients of the chunked form against the
    token-by-token recurrence, float32: ``jax.grad`` of the bare body (five
    operands), and the op's own backward, group after group from the kept
    starts, with the gated head-wise norm it carries (seven)."""
    ops = _operands(t, decay, beta)
    ks = jax.random.split(jax.random.key(9), 3)
    ct = jax.random.normal(ks[0], ops[2].shape)
    gate = jax.random.normal(ks[1], ops[2].shape)
    gain = 1.0 + 0.3 * jax.random.normal(ks[2], ops[2].shape[-1:])
    eps = 1e-5

    @jax.jit
    def both(gate, gain, *ops):
        out, state, starts = gdr.rule_xla(*ops, chunk)
        want, want_state, _ = _recurrence(*ops)
        want_grads = jax.grad(lambda *a: jnp.sum(_recurrence(*a)[0] * ct),
                              argnums=(0, 1, 2, 3, 4))(*ops)
        by_vjp = jax.grad(lambda *a: jnp.sum(gdr.rule_xla(*a, chunk)[0] * ct),
                          argnums=(0, 1, 2, 3, 4))(*ops)
        want_own = jax.grad(lambda ops, gate, gain: jnp.sum(gdr._finish(
            _recurrence(*ops)[0], gate, gain, eps) * ct),
            argnums=(0, 1, 2))(ops, gate, gain)
        *own, dgain = gdr.rule_grad_xla(
            tuple(gdr._by_chunks(x, chunk) for x in ops),
            gdr._by_chunks(gate, chunk), gain, eps,
            jnp.moveaxis(starts, 1, 0), gdr._by_chunks(ct, chunk))
        own = [gdr._from_chunks(d if d.ndim == 5 else d[..., None],
                                t).reshape(x.shape)
               for d, x in zip(own, ops + (gate,))] + [dgain]
        return (out, state, want, want_state, by_vjp, want_grads, own,
                list(want_own[0]) + list(want_own[1:]))
    with jax.default_matmul_precision("highest"):
        out, state, want, want_state, by_vjp, want_grads, own, want_own = \
            both(gate, gain, *ops)
    assert bool(jnp.isfinite(out).all())
    assert _rel(out, want) < 1e-5 and _rel(state, want_state) < 1e-5
    names = "q k v g beta gate gain".split()
    for name, a, c in list(zip(names, by_vjp, want_grads)) + list(
            zip(names, own, want_own)):
        if float(jnp.linalg.norm(c)) == 0.0:
            # beta 0: nothing reaches q, k, v, g (nor, with o = 0, the gain)
            assert float(jnp.abs(a).max()) == 0.0, name
            continue
        assert _rel(a, c) < 3e-5, name


def test_chunked_body_against_itself_at_two_chunk_sizes():
    with jax.default_matmul_precision("highest"):
        ops = _operands(150, 0.7)
        a = jax.jit(lambda *o: gdr.rule_xla(*o, 32))(*ops)
        b = jax.jit(lambda *o: gdr.rule_xla(*o, 64))(*ops)
    assert _rel(a[0], b[0]) < 1e-5 and _rel(a[1], b[1]) < 1e-5
    # five chunks and three: one group each, the state it starts on is zero
    assert a[2].shape[1] == b[2].shape[1] == 1 and not np.asarray(a[2]).any()


def test_final_state_and_the_states_the_groups_start_on(monkeypatch):
    """``Starts`` holds the state every GROUP of chunks starts on — the
    recurrence's state before that step — and ``State`` the last."""
    monkeypatch.setattr(gdr, "GROUP", 2)
    with jax.default_matmul_precision("highest"):
        ops = _operands(16 * 6, 0.3)
        _, state, starts = jax.jit(lambda *o: gdr.rule_xla(*o, 16))(*ops)
        _, want_state, before = jax.jit(_recurrence)(*ops)
    assert starts.shape == (2, 3, 2, 16, 24)
    assert not np.asarray(starts[:, 0]).any()
    for i, step in enumerate((32, 64)):
        assert _rel(starts[:, i + 1], before[step]) < 1e-5
    assert _rel(state, want_state) < 1e-5
    # seven chunks: no divisor up to 2 but 1, a chunk a group
    assert gdr._group(7) == 1 and gdr._group(64) == 2 and gdr._group(6) == 2


def test_inverse_of_a_unit_lower_matrix_and_its_gradient():
    a = jnp.tril(jax.random.normal(jax.random.key(0), (64, 64)), -1) * 0.1
    with jax.default_matmul_precision("highest"):
        inv = gdr._unit_lower_inverse(a)
        want = jnp.linalg.inv(jnp.eye(64) + a)
        ct = jax.random.normal(jax.random.key(1), a.shape)
        got = jax.grad(lambda a: jnp.sum(gdr._unit_lower_inverse(a) * ct))(a)
        ref = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(
            jnp.eye(64) + a) * ct))(a)
    assert _rel(inv, want) < 1e-4
    assert _rel(jnp.tril(got, -1), jnp.tril(ref, -1)) < 1e-3
    # all keys alike, beta 1, no decay: the all-ones matrix, whose inverse a
    # series would reach through terms of size C(63, 31)
    ones = jnp.tril(jnp.ones((64, 64)), -1)
    np.testing.assert_allclose(
        gdr._unit_lower_inverse(ones), jnp.linalg.inv(jnp.eye(64) + ones),
        atol=1e-5)


def _rule_program(shapes, precision, chunk, scale, a_log_shape=None):
    """A program of one ``layers.gated_delta_rule`` over fed operands
    (``shapes``: q, k, v, g, beta, gate and the result's cotangent ct) with
    its backward: ``(main, startup, fetches)``."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds = {n: fluid.layers.data(n, shape=list(s[1:]), dtype="float32")
                 for n, s in shapes.items()}
        for f in feeds.values():
            f.stop_gradient = False
        h, dk = shapes["q"][2:]
        a_log, dt_bias = (fluid.layers.create_parameter(
            list(shape), "float32", attr=fluid.ParamAttr(name=n))
            for n, shape in (("a_log", a_log_shape or (h,)),
                             ("dt_bias", (h, dk))))
        out, state = fluid.layers.gated_delta_rule(
            feeds["q"], feeds["k"], feeds["v"], feeds["g"], feeds["beta"],
            a_log, dt_bias, feeds["gate"], scale, chunk=chunk, epsilon=1e-5,
            out_norm_attr=fluid.ParamAttr(name="gain"))
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(
            out, feeds["ct"]))
        opt = fluid.optimizer.SGD(learning_rate=0.0)
        if precision == "bf16_amp":
            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)
    grads = [main.global_block().var(n + "@GRAD") for n in shapes
             if n != "ct"]
    return main, startup, [out, state] + grads


@pytest.mark.parametrize("precision", ["float32", "bf16_amp"])
def test_op_does_what_a_mixer_does_around_the_rule(precision):
    """The op through the executor, with its backward: raw ``q``, ``k`` (it
    L2-normalises and scales), the gate's pre-activation with ``ALog`` and
    ``DtBias``, and the gated head-wise norm of the result — against the
    recurrence over operands prepared by hand.  Under AMP the op stays
    float32 inside (the same numbers)."""
    t, h, dk, dv = 70, 2, 16, 16
    ks = jax.random.split(jax.random.key(3), 8)
    raw = {"q": jax.random.normal(ks[0], (2, t, h, dk)),
           "k": jax.random.normal(ks[1], (2, t, h, dk)),
           "v": jax.random.normal(ks[2], (2, t, h, dv)),
           "g": jax.random.normal(ks[3], (2, t, h, dk)),
           "beta": jax.nn.sigmoid(jax.random.normal(ks[4], (2, t, h))),
           "gate": jax.random.normal(ks[5], (2, t, h, dv)),
           "ct": jax.random.normal(ks[6], (2, t, h, dv))}
    a_log = jnp.log(jnp.asarray([2.0, 9.0]))
    dt_bias = jax.random.normal(ks[7], (h, dk)) - 3.0
    gain = jnp.linspace(0.5, 1.5, dv)

    def by_hand(q, k, v, g, beta, gate, a_log, dt_bias, gain):
        def norm(x):
            return x * jax.lax.rsqrt(jnp.maximum(
                jnp.sum(x * x, -1, keepdims=True), 1e-12))
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(g + dt_bias)
        o, state, _ = _recurrence(norm(q) * dk ** -0.5, norm(k), v, g, beta)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        return o * gain * jax.nn.sigmoid(gate), state
    args = [raw[n] for n in ("q", "k", "v", "g", "beta", "gate")] + [
        a_log, dt_bias, gain]
    with jax.default_matmul_precision("highest"):
        (want, want_state), pull = jax.vjp(by_hand, *args)
        want_grads = pull((raw["ct"], jnp.zeros_like(want_state)))
    main, startup, fetch = _rule_program(
        {n: v.shape for n, v in raw.items()}, precision, 32, dk ** -0.5)
    fetch += [main.global_block().var(n + "@GRAD")
              for n in ("a_log", "dt_bias", "gain")]
    before = dict(compile_cache.stats()["kernel_bodies"])
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for n, val in (("a_log", a_log), ("dt_bias", dt_bias),
                       ("gain", gain)):
            scope.set_var(n, jnp.array(val))
        got = exe.run(main, feed={n: np.asarray(v) for n, v in raw.items()},
                      fetch_list=fetch)
    bodies = compile_cache.stats()["kernel_bodies"]
    for body in ("gated_delta_rule:xla", "gated_delta_rule_grad:xla"):
        assert bodies.get(body, 0) - before.get(body, 0) == 1
    assert got[0].dtype == np.float32
    assert _rel(got[0], want) < 2e-5 and _rel(got[1], want_state) < 2e-5
    for name, a, b in zip("q k v g beta gate a_log dt_bias gain".split(),
                          got[2:], want_grads):
        assert _rel(a, b) < 1e-4, name


def test_the_ops_shapes_and_what_it_refuses():
    t, h, dk, dv = 40, 2, 16, 8
    shapes = {"q": (1, t, h, dk), "k": (1, t, h, dk), "v": (1, t, h, dv),
              "g": (1, t, h, dk), "beta": (1, t, h), "gate": (1, t, h, dv),
              "ct": (1, t, h, dv)}
    main, _, fetch = _rule_program(shapes, "float32", 16, 1.0)
    op = next(o for o in main.global_block().ops
              if o.type == "gated_delta_rule")
    assert sorted(op.inputs) == sorted(gdr._SLOTS)
    assert tuple(fetch[0].shape)[1:] == (t, h, dv)
    assert tuple(fetch[1].shape)[1:] == (h, dk, dv)
    starts = main.global_block().var(op.outputs["Starts"][0])
    assert tuple(starts.shape)[1:] == (1, h, dk, dv)    # 3 chunks: one group
    with pytest.raises(ValueError, match="power of two"):
        _rule_program(shapes, "float32", 48, 1.0)
    with pytest.raises(ValueError, match="ALog is"):
        _rule_program(shapes, "float32", 16, 1.0, a_log_shape=(h, dk))
    with pytest.raises(ValueError, match="Beta"):
        _rule_program(dict(shapes, beta=(1, t, h, 1)), "float32", 16, 1.0)
    # the gradient op is the op's own, from the forward's Starts: no generic
    # pass back through the whole sequence stands in for it
    x = jnp.ones((1, t, h, dk))
    with pytest.raises(ValueError, match="Starts"):
        gdr._grad_compute({"Q": [x]}, {"chunk": 16}, None, 0)


def test_the_rule_is_float32_by_its_own_text_and_casts_its_own_operands():
    lists = mixed_precision.AutoMixedPrecisionLists()
    assert "gated_delta_rule" in lists.SELF_CAST
    # on no other list — it decides nothing where SELF_CAST stands — and it
    # renames no other AMP program: the lists' text is the parent's
    assert lists.colour("gated_delta_rule") is None
    assert "gated_delta_rule" not in repr(mixed_precision.AMPPolicy())
    x = jnp.ones((2, 3), jnp.bfloat16)
    for op in ("gated_delta_rule", "gated_delta_rule_grad"):
        got = mixed_precision.AMPPolicy().cast_inputs(op, {"Q": [x]})
        assert got["Q"][0].dtype == jnp.bfloat16
    # ... and widens them inside
    ops = gdr._prelude(*([x.reshape(1, 2, 1, 3)] * 4), jnp.ones((1, 2, 1)),
                       jnp.zeros((1,)), jnp.zeros((1, 3)),
                       x.reshape(1, 2, 1, 3), x[0], 1.0)
    assert all(o.dtype == jnp.float32 for o in ops)


# ---- the latent mixer without a query rank and without rotation ----------------

def test_latent_mixer_without_rank_and_rotation_against_the_reference():
    cfg, _ = _cfg()
    ref = harness.load_reference(cfg["reference"])
    d, t = cfg["hidden_size"], 48
    sizes = smd.LatentSizes(
        cfg["num_attention_heads"], None, cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[t, d], dtype="float32")
        x.stop_gradient = False
        y = smd._latent_attention(x, "l2.", sizes, None, cfg["rms_norm_eps"])
        fluid.backward.append_backward(fluid.layers.reduce_sum(
            fluid.layers.square(y)))
    types = [op.type for op in main.global_block().ops]
    # the three products go to the op as they are, and it rotates nothing
    assert not {"rotary_embedding", "transpose", "expand", "concat",
                "reshape"} & set(types)
    op = next(o for o in main.global_block().ops
              if o.type == "fused_attention")
    assert "rope_theta" not in op.attrs and op.inputs["KShared"]
    assert op.inputs["Q"] != op.inputs["K"] and not op.inputs.get("V")
    params = {p.name for p in main.global_block().all_parameters()}
    assert "l2.attn.q" in params and not {"l2.attn.q_a", "l2.attn.q_b",
                                          "l2.attn.q_a_g"} & params
    spec = {n: s for n, s in ref.param_spec(cfg).items()
            if n.startswith("l2.attn.") or n == "l2.ln1.g"}
    w = gen.seeded_weights(spec, {}, 4)
    xs = np.random.RandomState(0).randn(1, t, d).astype("float32")

    def want(p, x, cfg=cfg):
        h = ref.rms_norm(x, p["l2.ln1.g"], cfg["rms_norm_eps"])
        return jnp.sum(jnp.square(x + ref.latent_attention(
            p, "l2.", h, cfg, 16, ref.f32_matmul)))
    p = {n: jnp.asarray(v) for n, v in w.items()}
    want_grads = jax.grad(want)(p, jnp.asarray(xs[0]))
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for n, v in w.items():
            scope.set_var(n, jnp.array(v))
        names = sorted(w)
        got = exe.run(main, feed={"x": xs},
                      fetch_list=[n + "@GRAD" for n in names])
    for n, g in zip(names, got):
        assert _rel(g, want_grads[n]) < 1e-4, n
    # the rotated mixer is another function: the planted fault differs
    rotated = jax.grad(want)(p, jnp.asarray(xs[0]),
                             dict(cfg, fault="latent_keys_rotated"))
    assert _rel(rotated["l2.attn.q"], want_grads["l2.attn.q"]) > 1e-2


# ---- the layer's shares ------------------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """The four shares of the tiny layer's 8 experts (two held each), the
    shared expert in ONE of them, through the reference's own layer: the
    parts the shares give add up to what the layer holding all 8 gives, and
    every token-expert pair is counted once."""
    cfg, _ = _cfg()
    ref = harness.load_reference(cfg["reference"])
    whole = dict(cfg, num_experts_held=8, first_local_expert=0)
    spec = {n: s for n, s in ref.param_spec(whole).items()
            if n.startswith("l1.moe.")}
    p = {n: jnp.asarray(v) for n, v in gen.seeded_weights(spec, {}, 3).items()}
    p["l1.moe.bias"] = jnp.asarray(
        np.random.RandomState(9).randn(8).astype("float32") * 0.3)
    x = jnp.asarray(np.random.RandomState(4).randn(
        64, cfg["hidden_size"]).astype("float32"))
    moe_cfg = ref._moe_cfg(whole)
    want, n_all = ref.experts(p, "l1.", x, moe_cfg, (8, 0), ref.f32_matmul)
    assert int(n_all) == 64 * cfg["num_experts_per_token"]
    parts, pairs = zip(*(ref.experts(p, "l1.", x, moe_cfg, (2, 2 * i),
                                     ref.f32_matmul, shared=i == 0)
                         for i in range(4)))
    assert sum(int(n) for n in pairs) == int(n_all)
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
    Adam steps, the routed and the dropless counts and the first delta
    layer's final state: in float32 the program — the chunked rule — and
    the reference — the recurrence — are the same mathematics."""
    cfg, model, gaps, prog, want = _train("float32")
    assert gaps["loss_rel_gap"] < 3e-6
    assert gaps["grad_rel_error_rms"] < 2e-5
    assert gaps["grad_norm_gap"] < 2e-5 and gaps["update_norm_gap"] < 2e-4
    assert gaps["routed_pairs_gap"] == 0 and gaps["delta_state_gap"] < 1e-5
    assert all(s["pairs_routed"] == s["pairs_computed"] > 0
               for s in prog["stats"])
    # the counters: the rule's state, decay and step size of layer 1
    for got, ref_row in zip(prog["stats"], want["ref_stats"]):
        np.testing.assert_allclose(
            [got[n] for n in ("delta_state_rms", "decay_mean", "beta_mean")],
            ref_row[1:], rtol=2e-3)
        assert 0 < got["decay_mean"] < 1 and 0 < got["beta_mean"] < 1
    # every trainable leaf has a gradient; the routers' biases have neither
    # gradient nor Adam state, and did not move
    ref = harness.load_reference(cfg["reference"])
    spec = ref.param_spec(cfg)
    assert set(want["grad_norms"]) == {n for n in spec if not ref.frozen(n)}
    assert all(v > 0 for v in want["grad_norms"].values())
    for leaf in ("l1.moe.bias", "l2.moe.bias"):
        assert leaf in spec and leaf not in want["grad_norms"]
        assert model.scope.find_var(leaf + "_moment1_0") is None
        assert not np.asarray(model.scope.find_var(leaf)).any()
    assert model.scope.find_var("l0.kda.A_log_moment1_0") is not None
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


def test_program_holds_a_mixer_a_layer_and_declares_its_counters():
    cfg, traffic = _cfg()
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    types = [op.type for op in model.main.global_block().ops]
    assert types.count("gated_delta_rule") == 2         # KDA, KDA, latent
    assert types.count("gated_delta_rule_grad") == 2
    assert types.count("fused_attention") == 1
    assert types.count("causal_conv1d") == 6 and "rotary_embedding" not in types
    latent = next(op for op in model.main.global_block().ops
                  if op.type == "fused_attention")
    assert latent.attrs["n_head"] == cfg["num_attention_heads"]
    assert "rope_theta" not in latent.attrs and latent.inputs["KShared"]
    assert "transpose" not in types and "expand" not in types
    assert types.count("moe_expert_ffn") == 2           # one dense layer
    assert model.main.step_stats[1] == smd.LINEAR_STEP_STATS
    assert smd.LINEAR_STEP_STATS[:3] == smd.STEP_STATS[:3]
    assert len(gen.STATS) == len(smd.LINEAR_STEP_STATS)
    with pytest.raises(ValueError, match="'kda' or 'mla'"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            tok = fluid.layers.data("tok", shape=[8, 1], dtype="int64")
            smd.linear_latent_decoder_lm(
                tok, tok, 16, 32, ("kda", "swa"), 1,
                smd.DeltaSizes(2, 8, 4, 8, 16),
                smd.LatentSizes(2, None, 8, 8, 4, 8), 16, (2, 4, 0), 8, 2, 8)
    model.close()


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
    sound = ref.loss_and_grad(p, batch, cfg, 16)
    bad_cfg = gen.FAULTS[fault](cfg)
    bad = ref.loss_and_grad(p, batch, bad_cfg, 16)
    if fault == "state_unchanged":
        assert bad_cfg["learning_rate"] == 0.0
        new, _ = ref.adam_step(p, bad[3], ref.adam_init(p), bad_cfg)
        assert all(bool((new[n] == p[n]).all()) for n in p)
        return
    assert abs(float(bad[0]) - float(sound[0])) > 1e-5 * float(sound[0])
    moved = max(_rel(bad[3][n], sound[3][n]) for n in sound[3])
    assert moved > 0.05
