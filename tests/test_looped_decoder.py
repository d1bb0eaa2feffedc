"""What the looped dense decoder (ISSUE 33) added, on the CPU at small
sizes: a variable's several gradients through ``backward._GradAccumulator``
(a looped model's every weight has one a pass), the exit-gated loss op, the sandwich-norm
block run several times over the same weights against the plain reference,
and the names a trace splits the passes by."""

import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import registry
from paddle_tpu.models import looped_decoder as ld
from paddle_tpu.ops import attention as att
from paddle_tpu.ops import loss as loss_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness                                 # noqa: E402
from benchmark.generators import train_loop_steps as gen      # noqa: E402

CELL = "ouro_2_6b.train_loop_4k"
JOYAI = "joyai_llm_flash.train_mtp_8k"


def _cfg(cell=CELL, **over):
    bench = harness.load_benchmark(ROOT)
    _, cfg, traffic = harness.resolve_cell(bench, cell, tiny=True)
    return dict(cfg, **over), traffic


def _sums(block, fwd_name=None):
    """The gradient-accumulation ``sum`` ops (of one variable)."""
    return [op for op in block.ops if op.type == "sum" and (
        fwd_name is None or op.outputs["Out"] == [fwd_name + "@GRAD"])]


# ---- a variable's several gradients ---------------------------------------------

def _multi_use_program(uses):
    """``loss = sum_i reduce_sum(w * a_i)``: the parameter ``w`` is read by
    ``uses`` ops, and use i's contribution to its gradient is ``a_i``
    exactly."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        w = fluid.layers.create_parameter([4, 3], "float32", name="w")
        feeds = [fluid.layers.data("a%d" % i, shape=[4, 3], dtype="float32",
                                   append_batch_size=False)
                 for i in range(uses)]
        parts = [fluid.layers.reduce_sum(fluid.layers.elementwise_mul(w, a))
                 for a in feeds]
        loss = parts[0]
        for p in parts[1:]:
            loss = fluid.layers.elementwise_add(loss, p)
        (_, grad), = fluid.backward.append_backward(loss)
    return main, startup, grad


@pytest.mark.parametrize("uses", [2, 3, 5])
def test_a_variable_used_several_times_gets_its_uses_sum(uses):
    """A variable used 2, 3 and 5 times: ONE ``sum`` op gathers the
    contributions, in the order they arrived (last use first), and adds
    them left to right — bit for bit, in float32."""
    main, startup, grad = _multi_use_program(uses)
    op, = _sums(main.global_block(), "w")
    assert op.inputs["X"] == ["w@GRAD"] + [
        "w@GRAD@RENAME@%d" % n for n in range(1, uses)]
    rng = np.random.RandomState(uses)
    # spread over magnitudes, so that another order of additions rounds
    # otherwise
    a = [(rng.randn(4, 3) * 10.0 ** rng.randint(-3, 4, (4, 3))).astype(
        "float32") for _ in range(uses)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    got, = exe.run(main, feed={"a%d" % i: a[i] for i in range(uses)},
                   fetch_list=[grad])
    want = a[-1]
    for x in a[-2::-1]:
        want = want + x                      # ((a_n + a_n-1) + ..) + a_1
    assert np.array_equal(got, want)
    np.testing.assert_allclose(got, np.sum(a, 0, dtype=np.float64),
                               rtol=1e-5, atol=1e-6 * np.abs(a).max())


def test_one_op_writing_two_contributions_has_both_summed():
    """``x * x`` hands its input two contributions at once, a third comes
    from another reader: one ``sum`` of three."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[3], dtype="float32")
        x.stop_gradient = False
        y = fluid.layers.elementwise_mul(x, x)
        z = fluid.layers.elementwise_add(y, fluid.layers.scale(x, scale=3.0))
        loss = fluid.layers.reduce_sum(z)
        gx, = fluid.backward.calc_gradient(loss, [x])
    op, = _sums(main.global_block(), "x")
    assert len(op.inputs["X"]) == 3
    xv = np.array([[1.0, -2.0, 0.5]], "float32")
    got, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": xv}, fetch_list=[gx])
    np.testing.assert_allclose(got, 2 * xv + 3.0, rtol=1e-6)


def test_sparse_row_lists_stay_sparse_through_the_sum():
    """Three uses of one sparse table: the sum of row lists is a row list
    (the optimizer keeps its sparse kernel), and trains as the dense table
    does."""
    from paddle_tpu.core import VarType

    def build(sparse):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            ids = [fluid.layers.data("i%d" % k, shape=[1], dtype="int64")
                   for k in range(3)]
            embs = [fluid.layers.embedding(
                i, size=[50, 8], is_sparse=sparse,
                param_attr=fluid.ParamAttr(name="table")) for i in ids]
            loss = fluid.layers.mean(fluid.layers.square(
                fluid.layers.sums(embs)))
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup
    feed = {"i%d" % k: np.array([[1 + k], [7], [3 * k]], "int64")
            for k in range(3)}
    tables = []
    for sparse in (True, False):
        main, startup = build(sparse)
        block = main.global_block()
        op, = _sums(block, "table")
        assert len(op.inputs["X"]) == 3
        assert (block.vars["table@GRAD"].type == VarType.SELECTED_ROWS) \
            == sparse
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(main, feed=feed)
            tables.append(np.array(scope.find_var("table"), copy=True))
    np.testing.assert_allclose(tables[0], tables[1], rtol=1e-6, atol=1e-7)


def test_shared_tables_of_the_latent_decoder_keep_their_program_text():
    """JoyAI's embedding and head have two uses each: one two-operand
    ``sum`` apiece, as before this configuration came."""
    cfg, traffic = _cfg(JOYAI)
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    block = model.main.global_block()
    for name in ("tok_emb", "out_w"):
        op, = _sums(block, name)
        assert op.inputs["X"] == [name + "@GRAD", name + "@GRAD@RENAME@1"]
    model.close()


# ---- the exit-gated loss -------------------------------------------------------

def _np_exit_loss(hs, ces, w, b, beta):
    """Written out a token at a time, in float64."""
    n, passes = ces[0].size, len(ces)
    total, mass = 0.0, np.zeros(passes)
    for i in range(n):
        lam = [1.0 / (1.0 + np.exp(-(h.reshape(n, -1)[i] @ w[:, 0] + b[0])))
               for h in hs]
        p, rest = [], 1.0
        for t in range(passes - 1):
            p.append(lam[t] * rest)
            rest *= 1.0 - lam[t]
        p.append(rest)
        total += sum(p[t] * ces[t].reshape(-1)[i] for t in range(passes)) \
            + beta * sum(q * np.log(q) for q in p)
        mass += p
    return total / n, mass / n


def _exit_inputs(passes, seed=0):
    rng = np.random.RandomState(seed)
    hs = [rng.randn(2, 5, 8).astype("float32") for _ in range(passes - 1)]
    ces = [rng.rand(2, 5, 1).astype("float32") * 5 for _ in range(passes)]
    return hs, ces, rng.randn(8, 1).astype("float32") * 0.5, \
        np.array([0.2], "float32")


def _exit_op(hs, ces, w, b, beta):
    return loss_ops._exit_gate_compute(
        {"H": [jnp.asarray(h) for h in hs], "CE": [jnp.asarray(c) for c in
                                                   ces],
         "W": [jnp.asarray(w)], "B": [jnp.asarray(b)]}, {"beta": beta},
        None, 0)


@pytest.mark.parametrize("passes", [2, 4])
def test_exit_gate_loss_against_the_written_out_expectation(passes):
    hs, ces, w, b = _exit_inputs(passes, passes)
    out = _exit_op(hs, ces, w, b, 0.05)
    want, mass = _np_exit_loss(hs, ces, w, b, 0.05)
    np.testing.assert_allclose(out["Loss"], [want], rtol=1e-5)
    stats = np.asarray(out["Stats"])
    assert stats.shape == (2 * passes,)
    np.testing.assert_allclose(stats[:passes], [c.mean() for c in ces],
                               rtol=1e-6)
    np.testing.assert_allclose(stats[passes:], mass, rtol=1e-5)
    # the exit distribution sums to 1, token by token and so in the mean
    np.testing.assert_allclose(stats[passes:].sum(), 1.0, rtol=1e-6)
    # the entropy term rewards a spread distribution
    assert float(_exit_op(hs, ces, w, b, 0.5)["Loss"][0]) < float(
        out["Loss"][0])


def test_exit_gate_loss_gradients_against_numeric_differences():
    hs, ces, w, b = _exit_inputs(3, 11)

    def f(hs, ces, w, b):
        return _exit_op(hs, ces, w, b, 0.05)["Loss"][0]
    grads = jax.grad(f, argnums=(0, 1, 2, 3))(
        [jnp.asarray(h) for h in hs], [jnp.asarray(c) for c in ces],
        jnp.asarray(w), jnp.asarray(b))
    eps = 1e-2
    for arg, (x, g) in enumerate(zip((hs[1], ces[2], w, b),
                                     (grads[0][1], grads[1][2], grads[2],
                                      grads[3]))):
        d = np.random.RandomState(arg).randn(*x.shape).astype("float32")

        def at(step):
            moved = [list(hs), list(ces), w, b]
            if arg == 0:
                moved[0][1] = x + step * d
            elif arg == 1:
                moved[1][2] = x + step * d
            else:
                moved[arg] = x + step * d
            return _np_exit_loss(*moved, 0.05)[0]
        num = (at(eps) - at(-eps)) / (2 * eps)
        np.testing.assert_allclose(float(jnp.sum(g * d)), num, rtol=2e-3,
                                   atol=1e-6)


def test_a_saturated_gate_gives_no_nan():
    hs, ces, w, b = _exit_inputs(3, 2)
    out = _exit_op([h * 1e3 for h in hs], ces, w, b, 0.05)
    assert np.isfinite(out["Loss"]).all() and np.isfinite(out["Stats"]).all()
    g = jax.grad(lambda w: _exit_op([h * 1e3 for h in hs], ces, w, b,
                                    0.05)["Loss"][0])(jnp.asarray(w))
    assert np.isfinite(g).all()


def test_one_pass_is_the_plain_mean_cross_entropy():
    _, ces, w, b = _exit_inputs(1, 4)
    out = _exit_op([], ces, w, b, 0.05)
    np.testing.assert_allclose(out["Loss"], [ces[0].mean()], rtol=1e-6)
    np.testing.assert_allclose(out["Stats"], [ces[0].mean(), 1.0], rtol=1e-6)


def test_layer_hands_the_op_all_passes_states_but_the_last():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        hs = [fluid.layers.data("h%d" % t, shape=[5, 8], dtype="float32")
              for t in range(3)]
        ces = [fluid.layers.data("c%d" % t, shape=[5, 1], dtype="float32")
               for t in range(3)]
        loss, stats = fluid.layers.exit_gate_loss(
            hs, ces, 0.05, param_attr=fluid.ParamAttr(name="gw"),
            bias_attr=fluid.ParamAttr(name="gb"))
    op = next(o for o in main.global_block().ops
              if o.type == "exit_gate_loss")
    assert op.inputs["H"] == ["h0", "h1"] and len(op.inputs["CE"]) == 3
    assert op.inputs["W"] == ["gw"] and op.inputs["B"] == ["gb"]
    assert tuple(loss.shape) == (1,) and tuple(stats.shape) == (6,)
    assert stats.stop_gradient
    assert tuple(main.global_block().vars["gw"].shape) == (8, 1)
    with pytest.raises(ValueError, match="passes before the last"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            fluid.default_main_program().global_block().append_op(
                type="exit_gate_loss",
                inputs={"H": ["h0"], "CE": ["c0", "c1", "c2"], "W": ["gw"],
                        "B": ["gb"]},
                outputs={"Loss": ["l"], "Stats": ["s"]}, attrs={})


# ---- the model -------------------------------------------------------------------

def _train(precision, seed=5, **over):
    cfg, traffic = _cfg(precision=precision, **over)
    ref = harness.load_reference(cfg["reference"])
    batches = gen.make_batches(traffic, cfg["vocab_size"], seed)
    w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
    want = gen.reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    names = list(model.main.step_stats[1])
    model.set_weights(w0)
    feeds = [model.make_feed(b) for b in batches]
    prog = gen.program_readings(model, feeds, w0, cfg["adam_beta1"], 3,
                                want["first_grad"], names)
    return cfg, model, gen.gaps(prog, want), prog, want


@pytest.mark.parametrize("layers", [1, 2])
@pytest.mark.parametrize("passes", [1, 3, 4])
def test_tiny_model_trains_like_the_plain_reference_in_float32(passes,
                                                               layers):
    """The loss, every pass's loss and exit mass over three steps, the
    first gradient leaf by leaf — every weight's is its passes' sum
    — and three Adam steps: in float32 the program and the reference are
    the same mathematics."""
    cfg, model, gaps, prog, want = _train(
        "float32", total_ut_steps=passes, num_hidden_layers=layers)
    assert gaps["loss_rel_gap"] < 3e-6 and gaps["loss_rel_gap.passes"] < 3e-6
    assert gaps["exit_mass_gap"] < 3e-6
    assert gaps["grad_rel_error_rms"] < 2e-5
    assert gaps["grad_norm_gap"] < 2e-5 and gaps["update_norm_gap"] < 1e-4
    ref = harness.load_reference(cfg["reference"])
    assert set(want["grad_norms"]) == set(ref.param_spec(cfg))
    assert len(prog["pass_losses"][0]) == len(prog["exit_masses"][0]) \
        == passes
    for masses in prog["exit_masses"]:
        assert sum(masses) == pytest.approx(1.0, rel=1e-5)
    if passes == 1:
        # no gate enters the loss: plain cross entropy, the gate at rest
        assert prog["losses"] == pytest.approx(
            [p[0] for p in prog["pass_losses"]], rel=1e-6)
        assert want["grad_norms"]["gate.w"] == 0.0
        assert want["update_norms"]["gate.w"] == 0.0
    else:
        assert all(v > 0 for v in want["grad_norms"].values())
        # neither uniform nor one-hot
        assert all(0.02 < m < 0.9 for m in prog["exit_masses"][0])
    model.close()


def test_tiny_model_trains_like_the_plain_reference_in_bf16():
    """Under bf16 AMP, inside the tiny limits of ``correct``."""
    cfg, model, gaps, _, _ = _train("bf16_amp")
    for name, value in gaps.items():
        # the passes' losses are held to the total's limit
        assert value <= cfg["limits"][name.split(".")[0]], (name, value)
    assert gaps["grad_rel_error_rms"] > 1e-4              # bf16 did round
    model.close()


@pytest.mark.parametrize("passes", [3, 4])
def test_a_weights_gradient_is_the_sum_of_its_passes_gradients(passes):
    """In the reference, one pass at a time (the other passes on
    ``stop_gradient`` of the weights): the per-pass gradients add up to the
    whole gradient, which is the program's ``sum``."""
    cfg, model, _, prog, want = _train("float32", total_ut_steps=passes)
    ref = harness.load_reference(cfg["reference"])
    _, traffic = _cfg()
    batch = gen.make_batches(traffic, cfg["vocab_size"], 5)[0]
    w0 = {n: jnp.asarray(v) for n, v in gen.seeded_weights(
        ref.param_spec(cfg), cfg, 5).items()}
    rows, t = batch["tok"].shape

    def loss(p, use):
        return sum(ref.doc_sums(p, jnp.asarray(batch["tok"][r], jnp.int32),
                                jnp.asarray(batch["lbl"][r], jnp.int32), cfg,
                                cfg["reference_block_rows"], use=use)[0]
                   for r in range(rows)) / (rows * t)
    with jax.default_matmul_precision("highest"):
        parts = [jax.grad(loss)(w0, (k,)) for k in range(1, passes + 1)]
    whole = want["first_grad"]
    for leaf in ("l0.attn.q", "l1.mlp.down", "l0.ln2.g", "ln_f.g", "out_w",
                 "gate.w", "tok_emb"):
        total = sum(np.asarray(g[leaf], np.float64) for g in parts)
        scale = np.abs(whole[leaf]).max()
        np.testing.assert_allclose(total, whole[leaf], rtol=1e-4,
                                   atol=1e-5 * scale)
        used = [float(jnp.abs(g[leaf]).max()) > 0 for g in parts]
        # the embedding is pass 1's alone; the last pass's gate enters no
        # loss; every other weight is used by every pass
        assert used == {"tok_emb": [True] + [False] * (passes - 1),
                        "gate.w": [True] * (passes - 1) + [False]}.get(
                            leaf, [True] * passes), leaf
    # and the program's first gradient is that sum (Adam's first moment)
    assert gen.rel_error_rms(prog["grad_errors"], want["grad_norms"]) < 2e-5
    model.close()


def _tiny_program(passes=3, layers=2):
    cfg, traffic = _cfg(total_ut_steps=passes, num_hidden_layers=layers)
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    return cfg, model


def test_every_weights_gradient_is_one_sum_over_its_passes():
    cfg, model = _tiny_program(passes=4)
    block = model.main.global_block()
    ref = harness.load_reference(cfg["reference"])
    for leaf in ref.param_spec(cfg):
        uses = {"tok_emb": 1, "gate.w": 1, "gate.b": 1}.get(leaf, 4)
        sums = _sums(block, leaf)
        assert [len(op.inputs["X"]) for op in sums] == [4] * (uses > 1), leaf
    # the gate's weight is ONE input of one op, whatever the passes
    assert [op.type for op in block.ops].count("exit_gate_loss") == 1
    model.close()


def test_program_runs_one_stack_over_the_same_weights():
    cfg, model = _tiny_program(passes=3, layers=2)
    block = model.main.global_block()
    types = [op.type for op in block.ops]
    apps = 3 * 2
    assert types.count("fused_attention") == apps
    assert types.count("fused_attention_grad") == apps
    assert types.count("swiglu") == apps
    # the op takes the projections as they lie and rotates q and k itself
    assert not {"rotary_embedding", "transpose", "reshape"} & set(types)
    assert types.count("rms_norm") == 4 * apps + 3        # + a pass's final
    assert types.count("mul") == 7 * apps + 3             # + a pass's head
    assert types.count("softmax_with_cross_entropy") == 3
    assert types.count("lookup_table") == 1
    assert types.count("adam") == len(harness.load_reference(
        cfg["reference"]).param_spec(cfg))
    for op in block.ops:
        if op.type == "fused_attention":
            assert op.attrs["causal"] and op.attrs["scale"] == \
                cfg["head_dim"] ** -0.5
            assert op.attrs["rope_theta"] == 1e6 and not op.attrs.get(
                "rope_interleaved")
            assert op.attrs["n_head"] == cfg["num_attention_heads"]
            assert [len(block.var(op.input(s)[0]).shape)
                    for s in "QKV"] == [3, 3, 3]
    # the same parameter under every pass: l0.attn.q is read by 3 products
    readers = [op for op in block.ops if op.type == "mul"
               and op.inputs["Y"] == ["l0.attn.q"]]
    assert len(readers) == 3
    model.close()


def test_every_op_of_a_pass_and_block_runs_under_a_scope_naming_both():
    """A trace splits by pass and block: the ``fluid[..]`` scope of every
    forward and gradient op of an application names ``p<t>.l<i>.``, a
    pass's final norm, head and loss ``p<t>.``; the sums of the weights'
    gradients carry the weight's name."""
    _, model = _tiny_program(passes=3, layers=2)
    block = model.main.global_block()
    app = re.compile(r"^fluid\[\w+\]p([1-3])\.l([01])\.")
    per_pass = re.compile(r"^fluid\[\w+\]p([1-3])\.")
    seen = set()
    for op in block.ops:
        scope = registry.fluid_scope_name(op)
        names = [n for n in list(op.input_arg_names)
                 + list(op.output_arg_names) if n]
        made_here = [n for n in op.output_arg_names
                     if n and re.match(r"p\d\.", n)]
        if op.type in ("adam", "scale", "fill_constant") or not made_here:
            continue
        if op.type in ("assign", "assign_grad"):
            continue            # the boundary's renaming: no device work
        assert per_pass.match(scope), (op.type, scope)
        m = app.match(scope)
        if m:
            seen.add((int(m.group(1)), int(m.group(2)), op.type))
        elif any(re.match(r"p\d\.l\d\.", n) for n in made_here):
            raise AssertionError((op.type, scope, names))
    for t in (1, 2, 3):
        for i in (0, 1):
            for kind in ("fused_attention", "fused_attention_grad", "mul",
                         "mul_grad", "rms_norm", "rms_norm_grad", "swiglu",
                         "swiglu_grad"):
                assert (t, i, kind) in seen, (t, i, kind)
    model.close()


def test_step_counters_carry_the_passes_losses_and_exit_masses(tmp_path):
    """Fetched to the host with the loss, the [2P] counters land in that
    step's StepStats record under ``step_stat_names``."""
    from paddle_tpu import monitor

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tok, lbl = (fluid.layers.data(n, shape=[16, 1], dtype="int64")
                    for n in ("tok", "lbl"))
        loss, stats = ld.looped_decoder_lm(tok, lbl, 64, 1, 4, 32, 4, 8, 48)
    names = ld.step_stat_names(4)
    assert len(names) == 8
    assert names[0] == "pass1_loss" and names[-1] == "pass4_exit_mass"
    assert main.step_stats == (stats.name, names)
    assert tuple(stats.shape) == (8,) and stats.stop_gradient
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    doc = np.random.RandomState(0).randint(0, 64, (2, 17, 1)).astype("int64")
    feed = {"tok": doc[:, :16], "lbl": doc[:, 1:]}
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
    assert [steps[0][n] for n in names] == st.tolist()
    assert sum(steps[0][n] for n in names[4:]) == pytest.approx(1.0,
                                                                rel=1e-5)
    assert not set(names) & set(steps[1])


def test_plain_128_wide_heads_at_4096_are_marked_for_the_streamed_kernels():
    """The cell's geometry: 16 plain heads, keys and values 128 wide, T =
    4096 — too long for the resident-K/V kernel, so the layer marks the op
    ``keep_lse`` (streamed on a TPU, the XLA body here)."""
    shape = (1, 16, 4096, 128)
    assert att.streams_plain_heads(shape, shape, shape, False, 0.0)
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q, k, v = (fluid.layers.data(n, shape=[16, 4096, 128],
                                     dtype="float32") for n in "qkv")
        fluid.layers.fused_attention(q, k, v, causal=True, scale=128 ** -0.5)
    op = next(o for o in main.global_block().ops
              if o.type == "fused_attention")
    assert op.attrs["keep_lse"] and op.outputs["LSE"]
    from paddle_tpu.ops.pallas import streamed_attention as sa
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert sa.step_heads(x, x, x) == (8, 1)
    # the backward's own heads a step: four, each with its whole float32 dK
    # and dV [4096, 128] resident beside the step's blocks
    assert sa.grad_step(x, x, x) == ("streamed_fused", (4, 1))


@pytest.mark.parametrize("t,a_step", [(640, 1), (640, 2), (640, 4),
                                      (1024, 4), (384, 2)])
def test_fused_backward_of_plain_128_wide_heads_against_the_two_kernels(
        t, a_step, monkeypatch):
    """The cell's heads — plain, keys and values 128 wide, ``causal`` — with
    more than two blocks a row (five of 128, two of 512, three of 128):
    pairs above the diagonal skipped, pairs it crosses, pairs below it; the
    fused backward's dQ, dK and dV are the two kernels' to the bit at any
    heads a step."""
    from streamed_backward import check_fused_backward

    rng = np.random.default_rng(t + a_step)
    q, k, v, ct = (jnp.asarray(rng.normal(size=(1, 4, t, 128)), jnp.float32)
                   for _ in range(4))
    check_fused_backward(monkeypatch, q, k, v, ct, None, True, 128 ** -0.5,
                         heads=(a_step, 1))
