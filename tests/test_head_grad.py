"""A vocabulary head's backward as one kernel (``ops/pallas/head_grad.py``,
interpreted here) against the op-by-op chain — ``softmax_with_cross_entropy_grad``
-> ``elementwise_add_grad`` -> ``mul_grad`` — for dX, dW and db; the chain
rule through ``Executor`` and ``ParallelExecutor`` with the body forced each
way; what keeps a chain op by op; the counters that say which body it took."""

import hashlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache, flags
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import transformer as tfm
from paddle_tpu.ops import loss as loss_ops
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import head_grad as hg
from paddle_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 128 x 128: a few hundred rows and columns are several row
    and vocabulary blocks (and the CPU's interpreter gets a grid of more than
    one step, which keeps XLA from folding the operands' transposes into
    bf16 products its CPU runtime does not have)."""
    monkeypatch.setattr(hg, "_MAX_ROWS", 128)
    monkeypatch.setattr(hg, "_MAX_COLS", 128)
    pallas.traced.cache_clear()
    yield
    pallas.traced.cache_clear()


@pytest.fixture
def on_the_cpu(monkeypatch, small_tiles):
    """The chain rule lets the (interpreted) kernel in on the CPU."""
    monkeypatch.setattr(loss_ops, "_HEAD_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    yield
    compile_cache.clear()


def _bodies_since(before):
    return {k: n - before.get(k, 0)
            for k, n in compile_cache.stats()["kernel_bodies"].items()
            if k.startswith("mul_grad") and n - before.get(k, 0)}


# ---- the kernel against the chain ---------------------------------------------

def _chain(x, w, b, label, ct, eps, dtype):
    """dX, dW, db as the three ops make them: the product stored in the
    operands' dtype, the bias added in float32, the loss op's own vjp, the
    rows' ``Loss@GRAD`` as the cotangent."""
    def loss(x, w, b):
        z = jnp.matmul(x.astype(dtype), w.astype(dtype)).astype(dtype)
        logits = z.astype(jnp.float32) + b
        out = loss_ops._swce_compute(
            {"Logits": [logits], "Label": [label[:, None]]},
            {"label_smooth_eps": eps}, None, 0)
        return jnp.sum(out["Loss"][:, 0] * ct)
    return jax.grad(loss, (0, 1, 2))(x, w, b)


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "all"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("eps", [0.0, 0.1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernel_is_the_chain(small_tiles, dtype, eps, bias, masked):
    n, d, v = 256, 128, 384
    rng = np.random.default_rng(int(eps * 10) + 2 * bias + 4 * masked)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, v)) * d ** -0.5, jnp.float32)
    b = jnp.asarray(rng.standard_normal(v) * 0.3 * bias, jnp.float32)
    label = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    ct = np.full(n, 1.0 / n, "float32")
    if masked:
        ct[rng.random(n) < 0.3] = 0.0       # padded positions: Loss@GRAD 0
    ct = jnp.asarray(ct)
    assert hg.supported(n, d, v, dtype) and hg.blocks(n, d, v) == (128, 128)
    xd, wd = x.astype(dtype), w.astype(dtype)
    z = jnp.matmul(xd, wd).astype(dtype)
    lse = jax.scipy.special.logsumexp(z.astype(jnp.float32) + b, axis=-1)
    dx, dw, db = hg.head_grad(xd, wd, z, b, lse, label, ct, eps,
                              interpret=True)       # b: zeros without a bias
    assert dx.dtype == dw.dtype == dtype and db.dtype == jnp.float32
    assert dx.shape == (n, d) and dw.shape == (d, v) and db.shape == (v,)
    rx, rw, rb = _chain(x, w, b, label, ct, eps, dtype)
    # float32: the same sums in another order; bf16: the products' operand
    # and result roundings besides (2^-8 relative an element)
    tol = 2e-6 if dtype == jnp.float32 else 1.2e-2
    for got, want in ((dx, rx), (dw, rw), (db, rb)):
        got, want = np.asarray(got, "float32"), np.asarray(want, "float32")
        assert np.any(want)
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
    if masked:
        assert not np.any(np.asarray(dx, "float32")[np.asarray(ct) == 0])


def test_the_forwards_masked_sum_is_the_gather():
    """The smoothing branch picks the label's logit by a masked row sum:
    the bits of ``take_along_axis`` on the log-softmax."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((6, 5, 33)) * 3, jnp.float32)
    label = jnp.asarray(rng.integers(0, 33, (6, 5, 1)), jnp.int32)
    eps = 0.1
    got = loss_ops._swce_compute({"Logits": [logits], "Label": [label]},
                                 {"label_smooth_eps": eps}, None, 0)["Loss"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logits - lse, label.astype(jnp.int32), -1)
    want = (1 - eps) * -picked + eps * (lse - jnp.mean(logits, -1,
                                                       keepdims=True))
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---- the rule's shapes ------------------------------------------------------------

# N / D / V of the head's chain in the one-chip cells, and whether its
# logits come from a ``mul`` (the chain) at all
CELL_HEADS = {
    "transformer_base.train_nmt": (16384, 512, 32000, True),
    "ouro_2_6b.train_loop_4k": (4096, 2048, 6144, False),       # too wide
    "keye_vl2_30b_a3b.train_longdoc_8k": (8192, 2048, 18992, False),
    "joyai_llm_flash.train_mtp_8k": (8192, 2048, 16160, False),
    "phi4_mini_flash.train_reason_4k": (4096, 2560, 25008, False),
}


@pytest.mark.parametrize("cell", sorted(CELL_HEADS))
def test_the_kernel_takes_the_cells_shapes_it_wins_at(cell):
    n, d, v, takes = CELL_HEADS[cell]
    assert hg.supported(n, d, v, jnp.bfloat16) == takes
    # a quarter of the rows: a shard of the four-chip cell
    assert hg.supported(n // 4, d, v, jnp.bfloat16) == takes


@pytest.mark.parametrize("n,d,v,dtype,takes", [
    (16384, 512, 32000, jnp.bfloat16, True),
    (16384, 512, 32000, jnp.float32, True),
    (16384, 512, 32000, jnp.float16, False),
    (16384, 512, 32100, jnp.bfloat16, False),     # V off the lane tiles
    (16400, 512, 32000, jnp.bfloat16, False),     # N off the row blocks
    (16384, 500, 32000, jnp.bfloat16, False),     # D off the lane tiles
    (8192, 1024, 32000, jnp.bfloat16, True),      # the widest it wins at
    (16384, 1024, 32000, jnp.bfloat16, False),    # dX beyond VMEM
    (16384, 1152, 32000, jnp.bfloat16, False),    # wider than it wins at
    (65536, 1024, 32000, jnp.bfloat16, False),    # dX beyond VMEM
    (128, 128, 128, jnp.bfloat16, True),
])
def test_the_kernel_reads_shapes_and_dtypes(n, d, v, dtype, takes):
    assert hg.supported(n, d, v, dtype) == takes


# ---- the chain through a Fluid program -------------------------------------------

def _head_program(n, d, v, bias=True, eps=0.1, amp=True, soft_label=False,
                  ignore_index=-100, read_softmax=False, fetch_grad=False):
    """x [n, d] -> fc -> softmax_with_cross_entropy -> mean, Adam."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[d], dtype="float32")
        if soft_label:
            label = fluid.layers.data("label", shape=[v], dtype="float32")
        else:
            label = fluid.layers.data("label", shape=[1], dtype="int64")
        hidden = fluid.layers.fc(x, size=d, act="tanh", name="hidden")
        logits = fluid.layers.fc(hidden, size=v, name="head",
                                 bias_attr=None if bias else False)
        out = fluid.layers.softmax_with_cross_entropy(
            logits, label, soft_label=soft_label, ignore_index=ignore_index,
            label_smooth_eps=0.0 if soft_label else eps,
            return_softmax=read_softmax)
        loss = fluid.layers.mean(out[0] if read_softmax else out)
        if read_softmax:
            fluid.layers.reduce_max(out[1])     # a reader off the loss's path
        opt = fluid.optimizer.Adam(learning_rate=1e-2)
        if amp:
            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss


def _feed(n, d, v, rng, soft_label=False):
    label = rng.integers(0, v, (n, 1)).astype("int64")
    if soft_label:
        label = np.eye(v, dtype="float32")[label[:, 0]]
    return {"x": rng.standard_normal((n, d)).astype("float32"),
            "label": label}


def _head_ops(main):
    ops = main.global_block().ops
    i = next(i for i, o in enumerate(ops)
             if o.type == "softmax_with_cross_entropy_grad")
    return ops, i


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
def test_the_chain_is_found_in_a_heads_backward(bias):
    main, _, _ = _head_program(256, 128, 256, bias=bias)
    ops, i = _head_ops(main)
    add, mul = loss_ops.head_chain(ops, i)
    assert (add is not None) == bias and mul.type == "mul_grad"
    assert [o.type for o in ops[i:i + 2 + bias]] == (
        ["softmax_with_cross_entropy_grad"]
        + ["elementwise_add_grad"] * bias + ["mul_grad"])
    assert mul.inputs["Y"] == ["head.w_0"]


@pytest.mark.parametrize("why,kwargs", [
    ("soft labels", dict(soft_label=True)),
    ("an ignore_index", dict(ignore_index=7)),
])
def test_a_loss_the_kernel_does_not_make_keeps_the_chain_op_by_op(
        on_the_cpu, why, kwargs):
    n, d, v = 256, 128, 256
    main, startup, loss = _head_program(n, d, v, **kwargs)
    ops, i = _head_ops(main)
    assert loss_ops.head_chain(ops, i) is None, why
    before = dict(compile_cache.stats()["kernel_bodies"])
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out, = exe.run(main, feed=_feed(n, d, v, np.random.default_rng(0),
                                        kwargs.get("soft_label", False)),
                       fetch_list=[loss])
    assert np.isfinite(out).all()
    assert _bodies_since(before) == {}


@pytest.mark.parametrize("why", ["softmax_read", "gradient_fetched",
                                 "off_the_tiles", "float32_program",
                                 "flag_off"])
def test_a_chain_the_rule_cannot_take_falls_back_and_says_so(
        on_the_cpu, monkeypatch, why):
    """The chain is there, the kernel is not its body: the ``Softmax``
    output read by another op, a gradient in between fetched, a vocabulary
    off the lane tiles, float32 products (the kernel was measured on bf16),
    ``FLAGS_pallas_kernels`` off — ``mul_grad:head_by_op``, and the
    step is the op-by-op step."""
    n, d, v = 256, 128, (200 if why == "off_the_tiles" else 256)
    main, startup, loss = _head_program(
        n, d, v, read_softmax=why == "softmax_read",
        amp=why != "float32_program")
    ops, i = _head_ops(main)
    assert loss_ops.head_chain(ops, i) is not None
    if why == "flag_off":
        monkeypatch.setitem(flags._FLAGS, "pallas_kernels", False)
    fetch = [loss] + (["head.tmp_1@GRAD"] if why == "gradient_fetched"
                      else [])
    before = dict(compile_cache.stats()["kernel_bodies"])
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = exe.run(main, feed=_feed(n, d, v, np.random.default_rng(0)),
                      fetch_list=fetch)
    assert all(np.isfinite(o).all() for o in out)
    assert _bodies_since(before) == {"mul_grad:head_by_op": 1}


def _train(main, startup, loss, feeds, watch, executor=None):
    """Losses of the steps, and the watched variables after them."""
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        exe = executor(main, loss) if executor else \
            fluid.Executor(fluid.CPUPlace())
        run = exe.run if executor else \
            (lambda **kw: exe.run(main, **kw))
        losses = [np.asarray(run(feed=f, fetch_list=[loss])[0]).copy()
                  for f in feeds]
        scope = fluid.global_scope()
        return losses, {n: np.array(scope.find_var(n), "float32", copy=True)
                        for n in watch}


WATCHED = ["head.w_0", "head.b_0", "hidden.w_0"]


@pytest.mark.parametrize("eps", [0.0, 0.1])
def test_an_executor_step_equals_the_op_by_op_step(on_the_cpu, monkeypatch,
                                                   eps):
    n, d, v = 256, 128, 256
    rng = np.random.default_rng(5)
    feeds = [_feed(n, d, v, rng) for _ in range(3)]
    got = {}
    for body in ("head_by_op", "head_fused"):
        monkeypatch.setattr(loss_ops, "_HEAD_PLATFORMS",
                            ("tpu", "cpu") if body == "head_fused" else ())
        compile_cache.clear()
        main, startup, loss = _head_program(n, d, v, eps=eps)
        before = dict(compile_cache.stats()["kernel_bodies"])
        got[body] = _train(main, startup, loss, feeds, WATCHED)
        assert _bodies_since(before) == {"mul_grad:" + body: 1}
    (la, wa), (lb, wb) = got["head_by_op"], got["head_fused"]
    # the first loss is the forward's alone: the same bits
    assert np.array_equal(la[0], lb[0])
    np.testing.assert_allclose(np.ravel(lb), np.ravel(la), rtol=2e-3)
    for name in WATCHED:
        # Adam's first steps move every weight by ~lr whatever its
        # gradient's size: a sign flipped on a gradient at rounding's level
        # shows as 2 lr, so compare the whole matrix's movement
        moved = np.abs(wa[name]).max()
        assert np.linalg.norm(wa[name] - wb[name]) \
            <= 0.02 * np.linalg.norm(wa[name]), name
        assert moved > 0


def test_the_kernel_runs_under_mul_grads_fluid_scope(on_the_cpu):
    """The lowered step names the one custom call's operations
    ``fluid[mul_grad]<X@GRAD>``: ``device_ms_per_step.matmul`` keeps the
    products, and the trace holds the program's own op names."""
    from paddle_tpu import executor as ex

    n, d, v = 256, 128, 256
    main, startup, loss = _head_program(n, d, v)
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        scope = fluid.global_scope()
        names = ["label", "x"]
        state, writeback = ex.analyze(main, names, scope, [loss.name])
        fn, _, _ = ex.trace_program(main, names, state, writeback,
                                    [loss.name], platform="cpu")
        feed = _feed(n, d, v, np.random.default_rng(0))
        text = jax.jit(fn).lower(
            [jnp.asarray(feed[k]) for k in names],
            [scope.find_var(k) for k in state],
            jax.random.key(0)).as_text(debug_info=True)
    ops, i = _head_ops(main)
    mul = loss_ops.head_chain(ops, i)[1]
    scope_name = "fluid[mul_grad]" + mul.outputs["GRAD::X"][0].replace(
        "@", ".")
    assert scope_name in text
    for covered in ("fluid[softmax_with_cross_entropy_grad]",
                    "fluid[elementwise_add_grad]head"):
        assert covered not in text


def test_a_dp_step_equals_one_device(on_the_cpu):
    """``ParallelExecutor`` on a four-device ``dp`` mesh: the kernel a shard,
    dW and db summed over the mesh — the step one device makes."""
    n, d, v = 512, 128, 256
    rng = np.random.default_rng(9)
    feeds = [_feed(n, d, v, rng) for _ in range(2)]
    mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])

    def pe(main, loss):
        return fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                      mesh=mesh)
    got = {}
    for name, executor in (("one", None), ("dp", pe)):
        compile_cache.clear()
        main, startup, loss = _head_program(n, d, v)
        before = dict(compile_cache.stats()["kernel_bodies"])
        got[name] = _train(main, startup, loss, feeds, WATCHED, executor)
        assert _bodies_since(before) == {"mul_grad:head_fused": 1}
    (la, wa), (lb, wb) = got["one"], got["dp"]
    np.testing.assert_allclose(np.ravel(lb), np.ravel(la), rtol=2e-3)
    for name in WATCHED:
        assert np.linalg.norm(wa[name] - wb[name]) \
            <= 0.02 * np.linalg.norm(wa[name]), name


def test_a_mesh_that_splits_the_weight_keeps_the_chain_op_by_op():
    """Whole weights on every device are what the per-shard kernel needs:
    a populated ``tp`` axis, or a weight placed in parts, says no."""
    mesh = make_mesh((2, 2), ("dp", "tp"), devices=jax.devices()[:4])
    ctx = types.SimpleNamespace(mesh=mesh, state_specs={})
    assert loss_ops._head_shards(ctx, ["w", "b"], 64) is None
    dp = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    ctx = types.SimpleNamespace(mesh=dp, state_specs={})
    assert loss_ops._head_shards(ctx, ["w", "b"], 64) == ("dp",)
    assert loss_ops._head_shards(ctx, ["w", "b"], 66) is None
    from jax.sharding import PartitionSpec as P
    ctx.state_specs = {"w": P(None, "dp")}
    assert loss_ops._head_shards(ctx, ["w", "b"], 64) is None
    assert loss_ops._head_shards(
        types.SimpleNamespace(mesh=None), ["w"], 64) == ()


# ---- the Transformer's program is the parent's ------------------------------------

def test_the_transformers_program_is_the_parents():
    """``transformer_base``'s Fluid program as the benchmark builds it: the
    fingerprint in its compiled module's name, and the op list, are what
    they were before the chain rule (it reads the program, never edits
    it); its head's backward is ops 378-380."""
    from benchmark.models import transformer_nmt

    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "transformer_base.json")))
    device = types.SimpleNamespace(platform="cpu", id=0)
    reset = transformer_nmt.TrainModel.reset
    transformer_nmt.TrainModel.reset = lambda self: None
    try:
        model = transformer_nmt.TrainModel(cfg, 64, [device], None)
    finally:
        transformer_nmt.TrainModel.reset = reset
    assert compile_cache.program_label(model.main) == "b86a7882"
    ops = model.main.global_block().ops
    assert len(ops) == 1324
    digest = hashlib.sha1(" ".join(o.type for o in ops).encode()).hexdigest()
    assert digest[:12] == "7fd54f3b02be"
    i = next(i for i, o in enumerate(ops)
             if o.type == "softmax_with_cross_entropy_grad")
    add, mul = loss_ops.head_chain(ops, i)
    assert i == 378 and [o.type for o in ops[i:i + 3]] == [
        "softmax_with_cross_entropy_grad", "elementwise_add_grad",
        "mul_grad"]
    assert add.outputs["GRAD::Y"] == ["dec_logits.b_0@GRAD"]
    assert mul.outputs["GRAD::Y"] == ["dec_logits.w_0@GRAD"]
