"""An op definition's ``stored`` rule (``register_op(.., stored=)``): the
output slots it answers ``registry.compute_op`` puts into the step's
environment behind ``lax.optimization_barrier``, the FORWARD op's values
only, so the compiled step keeps them as arrays of their own and no reader's
fusion carries the op's body.  ``swiglu`` keeps its ``Out`` where it is at
least ``_STORED_WIDTH`` wide: the down projection's forward and its weight
gradient read one ``[T, F]`` array.  Counts, text and bits only: what it buys
is a chip run's to say (PERF.md 6.29)."""

import os
import re
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import compile_cache, executor, registry
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.ops import activation
from paddle_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import harness                          # noqa: E402

_BARRIER = re.compile(r"stablehlo\.optimization_barrier.*loc\((#loc\d+)\)\s*$",
                      re.M)
_FLUID = re.compile(r"fluid\[(\w+)\]")


def _stored_sites(body="stored"):
    return compile_cache.stats()["kernel_bodies"].get("swiglu:" + body, 0)


@pytest.fixture
def every_width(monkeypatch):
    """The tiny programs' feed-forwards are 16-96 wide: the rule's width at
    0, as the cells' 5120-10240 wide sites meet it."""
    monkeypatch.setattr(activation, "_STORED_WIDTH", 0)


def _lowered(program, feed, fetch_names):
    """The step lowered by hand for the CPU (no executor, no record)."""
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
        return jax.ShapeDtypeStruct(
            tuple(v.shape), np.dtype(executor.materialize_dtype(v.dtype)))
    return jax.jit(fn).lower(
        [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in
         (executor._coerce_feed(block, n, feed[n]) for n in names)],
        [spec(n) for n in state_in], jax.random.key(0)).as_text(
            debug_info=True)


def _barrier_scopes(text):
    """The innermost Fluid op type each ``optimization_barrier`` of a
    lowered step runs under (None: under no Fluid scope, a
    ``jax.checkpoint``'s own)."""
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    return [(_FLUID.findall(locs.get(ref, "")) or [None])[-1]
            for ref in _BARRIER.findall(text)]


# ---- the decoder programs: one site a ``swiglu`` op ------------------------------

@pytest.mark.parametrize("cell", [
    "phi4_mini_flash.train_reason_4k",        # hybrid_decoder
    "ouro_2_6b.train_loop_4k",                # looped_decoder
    "joyai_llm_flash.train_mtp_8k",           # latent: dense + shared expert
    "kimi_linear_48b_a3b.train_doc_4k",       # linear: the same, delta layers
])
@pytest.mark.parametrize("wide", [True, False], ids=["wide", "narrow"])
def test_every_swiglu_of_a_decoder_step_is_stored_once_and_no_gradient_is(
        cell, wide, monkeypatch):
    """The tiny program of a cell, its step lowered with the rule's width
    under its sites' and at the default, far over them: ``swiglu:stored``
    (``:inline``) counts the program's ``swiglu`` ops, the text holds one
    barrier a stored site under the op's own scope and none under
    ``swiglu_grad`` (a barrier there would tear ``swiglu_grad`` out of the dX
    product's epilogue)."""
    if wide:
        monkeypatch.setattr(activation, "_STORED_WIDTH", 0)
    bench = harness.load_benchmark(ROOT)
    _, cfg, traffic = harness.resolve_cell(bench, cell, tiny=True)
    model = harness.load_module("models", cfg["builder"], ROOT).build_train(
        cfg, traffic, jax.devices()[:1])
    try:
        block = model.main.global_block()
        types = [op.type for op in block.ops]
        sites = types.count("swiglu")
        assert sites > 0 and types.count("swiglu_grad") == sites
        feed = {n: np.zeros((traffic["rows"], traffic["seq"], 1), "int64")
                for n in ("tok", "lbl", "lbl2")
                if block._find_var_recursive(n) is not None}
        before = _stored_sites(), _stored_sites("inline")
        text = _lowered(model.main, feed, [v.name for v in model._fetch])
        assert (_stored_sites() - before[0],
                _stored_sites("inline") - before[1]) == (
                    (sites, 0) if wide else (0, sites))
        under = _barrier_scopes(text)
        assert under.count("swiglu") == (sites if wide else 0)
        # (other barriers are op bodies' own: the delta rule's gradient, a
        # ``jax.checkpoint`` inside the scan's)
        assert "swiglu_grad" not in under
    finally:
        model.close()


# ---- a swiglu -> mul chain: the same bits ----------------------------------------

def _gated_ffn(amp, width=32, inner=48, rows=8):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[inner], dtype="float32")
        y = fluid.layers.data("y", shape=[inner], dtype="float32")
        x.stop_gradient = y.stop_gradient = False
        a = fluid.layers.swiglu(x, y)
        out = fluid.layers.fc(a, width, bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.square(out))
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        if amp:
            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)
    r = np.random.RandomState(5)
    feed = {"x": r.randn(rows, inner).astype("float32"),
            "y": r.randn(rows, inner).astype("float32")}
    w = main.global_block().all_parameters()[0].name
    return main, startup, feed, [a.name, "x@GRAD", "y@GRAD", w + "@GRAD",
                                 loss.name]


def _run(main, startup, feed, fetch):
    compile_cache.clear()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return [np.array(v, copy=True) for v in
                exe.run(main, feed=feed, fetch_list=fetch)]


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
def test_a_stored_swiglu_and_its_gradients_are_the_unstored_bits(
        amp, every_width, monkeypatch):
    """``Out``, ``GRAD::X``, ``GRAD::Y``, the down projection's dW and the
    loss of a ``swiglu`` -> ``mul`` chain: bit for bit what the same program
    computes with no rule on the definition."""
    main, startup, feed, fetch = _gated_ffn(amp)
    before = _stored_sites()
    stored = _run(main, startup, feed, fetch)
    assert _stored_sites() == before + 1
    monkeypatch.setattr(registry.get_op_def("swiglu"), "stored", None)
    plain = _run(main, startup, feed, fetch)
    assert _stored_sites() == before + 1
    compile_cache.clear()
    for name, got, want in zip(fetch, stored, plain):
        assert got.dtype == want.dtype, name
        np.testing.assert_array_equal(got, want, err_msg=name)
    assert np.abs(stored[1]).max() > 0 and np.abs(stored[2]).max() > 0


# ---- a definition that declares nothing ------------------------------------------

@pytest.mark.parametrize("width, slots", [
    (768, ()), (1024, ()),                  # the shared experts'
    (2048, ("Out",)), (5120, ("Out",)), (5632, ("Out",)), (7168, ("Out",)),
    (9216, ("Out",)), (10240, ("Out",)),    # every cell's dense feed-forward
])
def test_the_rule_keeps_an_output_by_its_width_alone(width, slots):
    x = jax.ShapeDtypeStruct((1, 8, width), "bfloat16")
    rule = registry.get_op_def("swiglu").stored
    assert rule({"X": [x], "Y": [x]}, {}) == slots


def test_only_swiglu_declares_a_stored_slot_and_other_programs_meet_no_barrier(
        monkeypatch):
    """The branch is inert for a definition that declares nothing: no other
    registered op has a ``stored`` slot, a program without ``swiglu`` never
    reaches ``registry._store`` and its lowered step holds no barrier (its
    compiled text is the parent's: PERF.md 6.29)."""
    assert [t for t, d in registry.OPS.items() if d.stored is not None] == [
        "swiglu"]

    def refuse(op_type, slots, outs):
        raise AssertionError("%s reached _store" % op_type)
    monkeypatch.setattr(registry, "_store", refuse)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        img = fluid.layers.data("img", shape=[24], dtype="float32")
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        hidden = fluid.layers.fc(fluid.layers.layer_norm(img), 16, act="relu")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(hidden, 4), label))
        mixed_precision.decorate(
            fluid.optimizer.Adam(learning_rate=1e-3)).minimize(loss)
    feed = {"img": np.zeros((4, 24), "float32"),
            "label": np.zeros((4, 1), "int64")}
    before = _stored_sites()
    text = _lowered(main, feed, [loss.name])
    assert "optimization_barrier" not in text
    assert _stored_sites() == before


# ---- under a mesh ----------------------------------------------------------------

def test_a_stored_site_under_a_four_device_mesh_trains_like_one_device(
        every_width):
    """One ``swiglu`` between a column-parallel pair and a row-parallel
    down projection on a (dp 1, fsdp 2, tp 2) CPU mesh: the barrier keeps
    its operand's layout (``spec_layout`` lists the op among those that
    do), the site is counted, and three steps read the one-device losses."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", shape=[16], dtype="float32")
        gate = fluid.layers.fc(x, 32, bias_attr=False)
        up = fluid.layers.fc(x, 32, bias_attr=False)
        out = fluid.layers.fc(fluid.layers.swiglu(gate, up), 16,
                              bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.square(out - x))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    r = np.random.RandomState(3)
    batches = [{"x": r.randn(8, 16).astype("float32")}] * 3

    def losses(run):
        return [float(np.asarray(run(b)[0]).ravel()[0]) for b in batches]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        single = losses(lambda b: exe.run(main, feed=b, fetch_list=[loss]))
    strategy = fluid.BuildStrategy()
    strategy.sharding_rules = True
    before = _stored_sites()
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main,
            mesh=make_mesh((1, 2, 2), ("dp", "fsdp", "tp")),
            build_strategy=strategy)
        meshed = losses(lambda b: pe.run(feed=b, fetch_list=[loss]))
    assert _stored_sites() == before + 1
    np.testing.assert_allclose(meshed, single, rtol=1e-5, atol=1e-6)
    assert meshed[-1] < meshed[0]
