"""The dense products' required work, counted where they are lowered.

An op definition's ``work`` rule (``registry.register_op(..., work=)``) is
asked by ``registry.compute_op`` — and, for a gradient made from the forward
definition, by ``_generic_grad_compute`` — with the traced inputs the body
gets, and the answer lands in the compile record open then
(``compile_cache.note_op_work`` -> ``record["op_work"]``).  Inside a
``mul_grad`` / ``matmul_grad`` the operations that make dX run under a plain
``dx`` scope and those that make dW under ``dw``.  Counts only: no time is
measured here."""

import collections
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import compile_cache, executor, registry
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import transformer as tfm
from paddle_tpu.ops import loss as loss_ops
from paddle_tpu.ops.pallas import head_grad

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark_suite", "data"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import record_products_trace as tiny                  # noqa: E402
from benchmark.trace import scopes                    # noqa: E402


def _work_of(program, startup, feed, fetch):
    """The ``op_work`` of the step's compile record (one run on the CPU)."""
    compile_cache.clear()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(program, feed=feed, fetch_list=fetch)
    rec = compile_cache.compile_log()[-1]
    assert rec["name"] == compile_cache.step_name("exe", program)
    return rec


def _transformer(batch, seq, vocab, width, inner, heads, layers):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        src, tgt, lbl = (fluid.layers.data(n, shape=[1], dtype="int64",
                                           lod_level=1)
                         for n in ("src_word", "tgt_word", "lbl_word"))
        loss, _ = tfm.transformer(
            src, tgt, lbl, seq, seq, vocab, vocab, n_layer=layers,
            n_head=heads, d_model=width, d_inner=inner, dropout_rate=0.0,
            label_smooth_eps=0.1)
        mixed_precision.decorate(
            fluid.optimizer.Adam(learning_rate=1e-3)).minimize(loss)
    rng = np.random.default_rng(0)
    lens = np.full((batch,), seq, "int32")
    feed = {}
    for n in ("src_word", "tgt_word", "lbl_word"):
        feed[n] = rng.integers(1, vocab, (batch, seq, 1)).astype("int64")
        feed[n + "@LEN"] = lens
    return main, startup, feed, loss


def test_a_two_layer_transformer_steps_work_is_the_hand_count():
    b, t, v, d, di, layers = 4, 16, 256, 64, 128, 2
    main, startup, feed, loss = _transformer(b, t, v, d, di, 4, layers)
    rec = _work_of(main, startup, feed, [loss])
    n = b * t
    # by hand: q, k, v, o of every attention (one an encoder layer, two a
    # decoder layer), two products a feed-forward, the head; attention's own
    # products are fused_attention's (no rule here)
    square = 4 * layers + 8 * layers
    want = collections.Counter()
    for count, (m, k, nn) in ((square, (n, d, d)), (2 * layers, (n, d, di)),
                              (2 * layers, (n, di, d)), (1, (n, d, v))):
        # bf16 under AMP: two bytes an element, each operand and the
        # result once; every input here gets a gradient (no hole)
        nbytes = 2 * (m * k + k * nn + m * nn)
        flops = 2 * m * k * nn
        want[("mul", "fwd", flops, nbytes, (m, k, nn))] += count
        want[("mul_grad", "dx", flops, nbytes, (m, nn, k))] += count
        want[("mul_grad", "dw", flops, nbytes, (k, m, nn))] += count
    got = collections.Counter(row[1:] for row in rec["op_work"])
    assert got == want
    assert rec["batch_shards"] == 1
    # a part is noted once, under its op's own scope name
    keys = [(row[0], row[2]) for row in rec["op_work"]]
    assert len(set(keys)) == len(keys) == 3 * (square + 4 * layers + 1)
    assert all(scopes.fluid_scope(row[0])[0] == row[1]
               for row in rec["op_work"])


def test_a_hole_leaves_its_part_out_and_amp_sets_the_bytes():
    main, startup, loss = tiny.build(fluid)
    rec = _work_of(main, startup, tiny.feed(np, 0), [loss])
    by_scope = collections.defaultdict(dict)
    for scope, op_type, part, flops, nbytes, shape in rec["op_work"]:
        by_scope[scope][part] = (op_type, flops, nbytes, shape)
    n, side, width = tiny.ROWS * tiny.SEQ, tiny.SIDE, tiny.WIDTH
    # the fed feature's projection: float32 data, cast to bf16 by AMP
    # before the body (and the rule) sees it; X gets no gradient
    nbytes = 2 * (n * side + side * width + n * width)
    assert by_scope["fluid[mul]fc_0.tmp_0"] == {
        "fwd": ("mul", 2 * n * side * width, nbytes, (n, side, width))}
    assert by_scope["fluid[mul_grad]side.w.GRAD"] == {
        "dw": ("mul_grad", 2 * n * side * width, nbytes, (side, n, width))}
    # the tied head: a matmul against the [V, D] table, transposed
    v = tiny.VOCAB
    assert by_scope["fluid[matmul]matmul_0.tmp_0"]["fwd"][3] == (n, width, v)
    tied = by_scope["fluid[matmul_grad]layer_norm_1.tmp_2.GRAD"]
    assert tied["dx"][3] == (n, v, width) and tied["dw"][3] == (width, n, v)
    assert sorted(p for parts in by_scope.values() for p in parts).count(
        "dx") == 6


@pytest.mark.parametrize("x,y,attrs,grad,want", [
    # a weight on the right: batch dimensions multiplied into M
    ((2, 8, 16), (16, 32), {}, (), [("fwd", (16, 16, 32))]),
    ((2, 8, 16), (16, 32), {}, ("X", "Y"),
     [("dx", (16, 32, 16)), ("dw", (16, 16, 32))]),
    ((2, 8, 16), (32, 16), {"transpose_Y": True}, ("Y",),
     [("dw", (16, 16, 32))]),
    # two activations: both gradients are dX
    ((2, 3, 8, 16), (2, 3, 16, 8), {}, ("X", "Y"),
     [("dx", (48, 8, 16)), ("dx", (16, 48, 8))]),
    ((2, 16, 8), (2, 16, 4), {"transpose_X": True}, (),
     [("fwd", (16, 16, 4))]),
    ((16,), (16,), {}, (), [("fwd", (1, 16, 1))]),
])
def test_matmuls_rule_counts_the_flattened_product(x, y, attrs, grad, want):
    ins = {"X": [jnp.zeros(x, jnp.bfloat16)], "Y": [jnp.zeros(y, jnp.float32)]}
    got = registry.get_op_def("matmul").work(ins, attrs, grad)
    m, k, n = (want[0][1] if not grad else
               (want[0][1][0], want[0][1][2], want[0][1][1])
               if grad[0] == "X" else
               (want[0][1][1], want[0][1][0], want[0][1][2]))
    flops, nbytes = 2 * m * k * n, 2 * m * k + 4 * k * n + 2 * m * n
    assert got == [(part, flops, nbytes, shape) for part, shape in want]


def test_dequant_matmul_counts_its_int8_weight_in_bytes():
    ins = {"X": [jnp.zeros((4, 8, 64), jnp.bfloat16)],
           "QWeight": [jnp.zeros((64, 32), jnp.int8)]}
    assert registry.get_op_def("dequant_matmul").work(
        ins, {"x_num_col_dims": 2}, ()) == [
            ("fwd", 2 * 32 * 64 * 32, 2 * 32 * 64 + 64 * 32 + 2 * 32 * 32,
             (32, 64, 32))]
    assert registry.get_op_def("elementwise_add").work is None


def _head(n=256, d=128, v=256):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[n, d], append_batch_size=False)
        label = fluid.layers.data("label", shape=[n, 1], dtype="int64",
                                  append_batch_size=False)
        logits = fluid.layers.fc(fluid.layers.fc(x, size=d, act="tanh"),
                                 size=v, name="head")
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        mixed_precision.decorate(
            fluid.optimizer.SGD(learning_rate=0.0)).minimize(loss)
    rng = np.random.default_rng(0)
    feed = {"x": rng.standard_normal((n, d)).astype("float32"),
            "label": rng.integers(0, v, (n, 1))}
    return main, startup, feed, loss


def test_a_chain_lowered_head_notes_the_parts_of_the_op_by_op_spelling(
        monkeypatch):
    from paddle_tpu.ops import pallas

    monkeypatch.setattr(head_grad, "_MAX_ROWS", 128)
    monkeypatch.setattr(head_grad, "_MAX_COLS", 128)
    noted = {}
    for body, platforms in (("head_fused", ("tpu", "cpu")),
                            ("head_by_op", ())):
        monkeypatch.setattr(loss_ops, "_HEAD_PLATFORMS", platforms)
        pallas.traced.cache_clear()
        before = compile_cache.stats()["kernel_bodies"].get(
            "mul_grad:" + body, 0)
        main, startup, feed, loss = _head()
        noted[body] = _work_of(main, startup, feed, [loss])["op_work"]
        assert compile_cache.stats()["kernel_bodies"][
            "mul_grad:" + body] == before + 1
    assert noted["head_fused"] == noted["head_by_op"]
    head = [row for row in noted["head_fused"] if row[5][1:] == (128, 256)
            or row[5] in ((256, 256, 128), (128, 256, 256))]
    assert [(row[1], row[2]) for row in head] == [
        ("mul", "fwd"), ("mul_grad", "dx"), ("mul_grad", "dw")]
    compile_cache.clear()


def _lower(program, feed, fetch_names):
    """The step lowered by hand, with NO compile record open."""
    block = program.global_block()

    class Has:
        def has_var(self, name):
            v = block._find_var_recursive(name)
            return v is not None and v.persistable
    names = sorted(feed)
    state, writeback = executor.analyze(program, names, Has(), fetch_names)
    fn, state_in, _ = executor.trace_program(
        program, names, state, writeback, fetch_names, platform="cpu")

    def spec(name):
        v = block._find_var_recursive(name)
        return jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype))
    return jax.jit(fn).lower(
        [jax.ShapeDtypeStruct(feed[n].shape, feed[n].dtype) for n in names],
        [spec(n) for n in state_in], jax.random.key(0))


def test_with_no_record_open_nothing_is_noted_and_nothing_raises():
    compile_cache.note_op_work("fluid[mul]x", "mul", "fwd", 8, 8, (1, 2, 2))
    main, _, feed, loss = _head()
    feed["label"] = feed["label"].astype("int32")
    before = compile_cache.compile_log()
    assert _lower(main, feed, [loss.name]) is not None
    assert compile_cache.compile_log() == before


def test_a_mul_grads_lowered_text_carries_dx_and_dw_under_the_ops_scope():
    main, _, feed, loss = _head()
    feed["label"] = feed["label"].astype("int32")
    text = _lower(main, feed, [loss.name]).as_text(debug_info=True)
    names = set()
    for line in text.split("\n"):
        if "dot_general" in line and "fluid[mul_grad]" in line:
            names.update(n for n in line.split('"')
                         if "fluid[mul_grad]" in n and "dot_general" in n)
    dx = {n for n in names if scopes.under(n, "dx")}
    dw = {n for n in names if scopes.under(n, "dw")}
    assert dx and dw and dx | dw == names and not dx & dw
    assert any(n.endswith("fluid[mul_grad]fc_0.tmp_2.GRAD/dx/"
                          "transpose(jvp())/dot_general") for n in dx)
    # the plain part scope is not a Fluid scope: the innermost Fluid name
    # of both products is the op's own, as every existing reader takes it
    for n in names:
        assert scopes.fluid_scope(n)[0] == "mul_grad"
    outputs = {scopes.fluid_scope(n)[1] for n in dx}
    assert outputs == {scopes.fluid_scope(n)[1] for n in dw
                       if scopes.fluid_scope(n)[1] in outputs}


def test_a_record_that_finds_the_step_traced_carries_the_entrys_list():
    main, startup, feed, loss = _head(n=128)
    compile_cache.clear()
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        first = fluid.Executor(fluid.CPUPlace())
        first.run(main, feed=feed, fetch_list=[loss])
        miss = compile_cache.compile_log()[-1]
        with compile_cache.count_compiles() as cc:
            fluid.Executor(fluid.CPUPlace()).run(main, feed=feed,
                                                 fetch_list=[loss])
        hit = compile_cache.compile_log()[-1]
    assert (miss["trace_cache"], hit["trace_cache"]) == ("miss", "hit")
    assert cc()["lowerings"] == 0
    assert miss["op_work"] and hit["op_work"] == miss["op_work"]
    compile_cache.clear()


def test_under_a_mesh_the_note_is_global_and_the_record_says_the_split():
    from paddle_tpu.parallel import make_mesh

    main, startup, feed, loss = _head(n=256)
    mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    compile_cache.clear()
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                    mesh=mesh,
                                    build_strategy=fluid.BuildStrategy())
        with mesh:
            pe.run(feed=feed, fetch_list=[loss])
    rec = compile_cache.compile_log()[-1]
    assert rec["executor"] == "parallel_executor"
    assert rec["batch_shards"] == 4
    assert {row[5][0] for row in rec["op_work"] if row[2] == "fwd"} == {256}
    compile_cache.clear()
