"""The step under Fluid names (ISSUE 24): a ``jax.named_scope`` per
lowered Fluid op (``registry.fluid_scope_name``), a name per compiled
program (``compile_cache.name_step``), and the executors' spans as
``pt/<name>`` annotations in a jax profiler trace (``profiler.RecordEvent``).
CPU only: names and counts, never a time."""

import contextlib
import glob
import os
import re

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import compile_cache, profiler
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.models import transformer as tfm
from paddle_tpu.parallel import make_mesh
from paddle_tpu.registry import fluid_scope_name

# the one regex a reader of a trace needs (benchmark/trace/scopes.py has
# the same): the LAST match in a name stack is the innermost scope
FLUID = re.compile(r"fluid\[([^\]/]+)\]([^/:]*)")
KINDS = ("exe", "pe")
ROWS, SEQ, VOCAB = 8, 8, 67


class TinyTransformer:
    """The transformer at toy sizes with bf16 AMP and Adam + noam, behind
    one ``step(feed)`` for either executor (``pe``: dp=4 over four of the
    virtual CPU devices, as tests/test_parallel_executor.py does)."""

    def __init__(self, kind):
        self.kind = kind
        self.main, startup = fluid.Program(), fluid.Program()
        self.main.random_seed = startup.random_seed = 7
        with fluid.program_guard(self.main, startup), \
                fluid.unique_name.guard():
            src, tgt, lbl = (fluid.layers.data(
                n, shape=[1], dtype="int64", lod_level=1)
                for n in ("src_word", "tgt_word", "lbl_word"))
            self.loss, _ = tfm.transformer(
                src, tgt, lbl, SEQ, SEQ, VOCAB, VOCAB, n_layer=1, n_head=2,
                d_model=16, d_inner=32, dropout_rate=0.0,
                label_smooth_eps=0.1)
            mixed_precision.decorate(fluid.optimizer.Adam(
                learning_rate=fluid.layers.noam_decay(16, 10))).minimize(
                    self.loss)
        self.scope = fluid.Scope()
        with fluid.scope_guard(self.scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
        self.mesh = None
        if kind == "pe":
            self.mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
            self.exe = fluid.ParallelExecutor(
                loss_name=self.loss.name, main_program=self.main,
                mesh=self.mesh, build_strategy=fluid.BuildStrategy(),
                scope=self.scope)
        else:
            self.exe = fluid.Executor(fluid.CPUPlace())
        rng = np.random.default_rng(0)
        ids = rng.integers(2, VOCAB, (3, ROWS, SEQ, 1), dtype=np.int64)
        lens = np.full((ROWS,), SEQ, "int32")
        self.feed = {"src_word": ids[0], "tgt_word": ids[1],
                     "lbl_word": ids[2], "src_word@LEN": lens,
                     "tgt_word@LEN": lens, "lbl_word@LEN": lens}

    def step(self):
        with self.mesh or contextlib.nullcontext():
            if self.kind == "pe":
                return self.exe.run(feed=self.feed, fetch_list=[self.loss],
                                    return_numpy=False)[0]
            with fluid.scope_guard(self.scope):
                return self.exe.run(self.main, feed=self.feed,
                                    fetch_list=[self.loss],
                                    return_numpy=False)[0]

    def lowered_text(self):
        """``as_text(debug_info=True)`` of the step as the executor
        compiled it."""
        self.step()
        (compiled,) = self.exe._cache.values()

        def shapes(vals):
            return [jax.ShapeDtypeStruct(np.shape(v), v.dtype) for v in vals]
        feeds = shapes([self.feed[n] for n in compiled.feed_names])
        state = shapes([self.scope.find_var(n) for n in compiled.state_in])
        with self.mesh or contextlib.nullcontext():
            return compiled.fn.lower(
                feeds, state, jax.random.key(0)).as_text(debug_info=True)


def main_op_names(text):
    """[(the op's text, its name location)] for every operation in the
    body of the public ``@main`` (parameters and the function itself have
    locations of other kinds)."""
    defs = dict(re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, re.M))
    start = text.index("func.func public @main")
    body = text[start:text.index("\n  } loc(", start)]
    out = []
    for line in body.splitlines()[1:]:
        m = re.search(r'loc\((#loc\d+)\)\s*$', line)
        if m and not line.strip().startswith("return "):
            name = re.match(r'"([^"]*)"', defs[m.group(1)])
            out.append((line.strip(), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_lowered_step_is_named_and_every_op_is_under_a_fluid_scope(kind):
    model = TinyTransformer(kind)
    text = model.lowered_text()
    label = compile_cache.program_label(model.main)
    assert label == compile_cache.program_fingerprint(model.main)[:8]
    module = "jit_pt_%s_%s" % (kind, label)
    assert re.search(r"module @(\S+)", text).group(1) == module
    ops = main_op_names(text)
    assert len(ops) > 500
    prefix = "jit(pt_%s_%s)/" % (kind, label)
    bare = [(op[:50], name) for op, name in ops
            if not name.startswith(prefix) or not FLUID.search(name)]
    assert not bare, bare[:5]
    # the innermost scope is a Fluid op of this program, by type and by
    # first output variable
    program_ops = {FLUID.match(fluid_scope_name(op)).groups()
                   for op in model.main.global_block().ops}
    assert ("mul_grad", "layer_norm_4.tmp_2.GRAD") in program_ops
    seen = {FLUID.findall(name)[-1] for _, name in ops}
    assert seen <= program_ops
    # every type that computes something is there (reshape and friends
    # may lower to nothing)
    types = {t for t, _ in seen}
    assert {"mul", "mul_grad", "fused_attention", "fused_attention_grad",
            "layer_norm", "layer_norm_grad", "softmax_with_cross_entropy",
            "softmax_with_cross_entropy_grad", "lookup_table",
            "lookup_table_grad", "adam", "scale", "elementwise_add",
            "relu"} <= types
    # the 2 x n beta-power scales are told from the embedding scale by
    # their output's name
    outs = {o for t, o in seen if t == "scale"}
    assert any(o.endswith("_beta1_pow_acc_0") for o in outs)
    assert "scale_0.tmp_0" in outs
    # the AMP casts, made before the kernel, are under the op they feed
    casts = [name for op, name in ops if "stablehlo.convert" in op
             and FLUID.findall(name)[-1][0] == "mul"]
    assert casts


def test_region_op_nests_scopes_and_the_innermost_owns_the_operation():
    x = fluid.layers.data("x", shape=[3], dtype="float32",
                          append_batch_size=False)
    acc = fluid.layers.fill_constant(shape=[3], dtype="float32", value=0.0)
    i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
    n = fluid.layers.fill_constant(shape=[1], dtype="int64", value=4)
    cond = fluid.layers.less_than(i, n)
    w = fluid.layers.While(cond)
    with w.block():
        fluid.layers.assign(fluid.layers.elementwise_add(acc, x), output=acc)
        fluid.layers.increment(i, value=1)
        fluid.layers.less_than(i, n, cond=cond)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.arange(3, dtype="float32")
    (got,) = exe.run(feed={"x": xv}, fetch_list=[acc])
    np.testing.assert_allclose(got, 4 * xv)
    (compiled,) = exe._cache.values()
    state = [fluid.global_scope().find_var(n) for n in compiled.state_in]
    text = compiled.fn.lower([xv], state, jax.random.key(0)).as_text(
        debug_info=True)
    nested = [FLUID.findall(name) for name in
              re.findall(r'loc\("([^"]*)"', text)
              if len(FLUID.findall(name)) > 1]
    assert nested
    assert all(stack[0][0] == "while" for stack in nested)
    assert {stack[-1][0] for stack in nested} >= {"elementwise_add",
                                                  "increment"}


def test_scope_name_is_type_and_first_output_in_safe_characters():
    block = fluid.Program().global_block()

    def op(outputs):
        return fluid.framework.Operator(block, "scale", {"X": ["a"]},
                                        outputs, {})
    assert fluid_scope_name(op({"Out": ["fc_0.tmp_0@GRAD@RENAME@1"]})) == \
        "fluid[scale]fc_0.tmp_0.GRAD.RENAME.1"
    assert fluid_scope_name(op({"Out": ["", "tower/fc:b"]})) == \
        "fluid[scale]tower.fc.b"
    assert fluid_scope_name(op({"Out": ["@LR_DECAY_COUNTER@begin=1"]})) == \
        "fluid[scale].LR_DECAY_COUNTER.begin.1"
    assert fluid_scope_name(op({})) == "fluid[scale]"
    assert FLUID.findall("jit(pt_exe_ab)/fluid[while]i/while/body/"
                         "fluid[scale]tower.fc.b/mul")[-1] == \
        ("scale", "tower.fc.b")


def test_a_label_is_not_structure():
    """Two programs that differ only in label share a fingerprint and a
    trace-cache entry; the label never reaches a cache key."""
    x = fluid.layers.data("x", shape=[4])
    y = fluid.layers.fc(x, 3)
    main = fluid.default_main_program()
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    twin = main.clone()
    twin._label = "decode_tick"
    fp = compile_cache.program_fingerprint(main)
    assert compile_cache.program_fingerprint(twin) == fp
    assert compile_cache.program_label(main) == fp[:8]
    assert compile_cache.program_label(twin) == "decode_tick"
    assert main.clone()._label is None and twin.clone()._label == \
        "decode_tick"
    feed = {"x": np.ones((2, 4), "float32")}
    before = compile_cache.stats()
    with compile_cache.count_compiles() as cc:
        a, = fluid.Executor(fluid.CPUPlace()).run(main, feed=feed,
                                                  fetch_list=[y])
        first = cc()["lowerings"]
        b, = fluid.Executor(fluid.CPUPlace()).run(twin, feed=feed,
                                                  fetch_list=[y.name])
    assert first == 1 and cc()["lowerings"] == 1
    assert compile_cache.stats()["trace_hits"] == before["trace_hits"] + 1
    np.testing.assert_array_equal(a, b)


def test_fingerprint_of_an_amp_program_is_the_same_in_the_next_process():
    """The default label is taken from the fingerprint, and the module's
    name is in jax's persistent-cache key: a fingerprint that hashed the
    AMP policy's address would miss the cache in every new process."""
    assert "0x" not in repr(mixed_precision.AMPPolicy())
    lists = mixed_precision.AutoMixedPrecisionLists(
        custom_black_list=["mul"])
    assert repr(mixed_precision.AMPPolicy(lists)) != \
        repr(mixed_precision.AMPPolicy())
    fps = []
    for _ in range(2):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            loss = fluid.layers.mean(fluid.layers.fc(
                fluid.layers.data("x", shape=[4]), 3))
            mixed_precision.decorate(
                fluid.optimizer.SGD(0.1)).minimize(loss)
        fps.append(compile_cache.program_fingerprint(main))
    assert fps[0] == fps[1]


def test_decoder_programs_carry_their_kind_as_label():
    from paddle_tpu.serving import decoder

    spec = decoder.build_decoder_lm(
        vocab_size=31, max_len=16, slots=2, n_layer=1, n_head=2,
        d_model=8, d_inner=16, prefix="lm", spec_k=2)
    labels = {kind: compile_cache.program_label(
        getattr(spec, kind + "_program"))
        for kind in ("score", "prefill", "decode", "verify")}
    assert labels == {"score": "lm_score", "prefill": "lm_prefill",
                      "decode": "lm_decode", "verify": "lm_verify"}

    def fn():
        pass
    assert compile_cache.name_step(
        fn, "exe", spec.decode_program).__name__ == "pt_exe_lm_decode"


def host_lines(trace_dir):
    """{line name: [event names]} of the ``/host:CPU`` plane."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    return {"%s#%d" % (line.name, k): [ev.name for ev in line.events]
            for k, line in enumerate(host.lines)}


@pytest.mark.parametrize("kind", KINDS)
def test_executor_spans_land_on_the_calling_threads_line(kind, tmp_path):
    """A jax profiler trace of two steps, with neither a fluid.profiler
    session nor the monitor on, holds the executor's spans as ``pt/``
    annotations beside the caller's own."""
    model = TinyTransformer(kind)
    np.asarray(model.step())
    assert not profiler.is_profiling()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bm/train_step"):
                loss = model.step()
        np.asarray(loss)
    finally:
        jax.profiler.stop_trace()
    lines = host_lines(str(tmp_path))
    (mine,) = [ev for ev in lines.values() if "bm/train_step" in ev]
    name = "executor" if kind == "exe" else "parallel_executor"
    for span in ("step", "h2d_transfer", "run", "dispatch"):
        assert mine.count("pt/%s/%s" % (name, span)) == 2, (span, mine)
    assert mine.count("bm/train_step") == 2
    # warm steps: nothing was compiled, so no compile span
    assert "pt/%s/compile" % name not in mine


def test_one_span_three_sinks_and_the_prefix_is_the_annotations(tmp_path):
    """Inside a ``fluid.profiler`` session with a ``trace_dir`` the same
    span is in the chrome export under its bare name (what
    tools/trace_summary.py reads) and in the ``.xplane.pb`` as ``pt/``."""
    chrome = str(tmp_path / "chrome.json")
    with profiler.profiler(profile_path=chrome,
                           trace_dir=str(tmp_path / "xplane")):
        with profiler.RecordEvent("executor/fetch_sync"):
            pass
        profiler.mark_event("compile_cache/hit")
    import json

    with open(chrome) as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]
                 if e.get("ph") == "X"]
    assert names == ["executor/fetch_sync", "compile_cache/hit"]
    table = profiler.summarize_events(
        [{"name": n, "ph": "X", "dur": 1.0} for n in names])
    assert "executor/fetch_sync" in table and "pt/" not in table
    seen = [n for ev in host_lines(str(tmp_path / "xplane")).values()
            for n in ev]
    assert "pt/executor/fetch_sync" in seen
    assert "pt/compile_cache/hit" in seen
    assert "executor/fetch_sync" not in seen


def test_warm_persistent_cache_reads_its_scopes_and_keys_on_the_module_name(
        tmp_path):
    """Scope names are metadata, which jax leaves out of its persistent
    cache key; the module name is in it.  So a warm cache gives a tree its
    own scopes back, a scope-less tree's ``jit_fn`` is never served to a
    ``jit_pt_exe_<label>`` (nor one label's to another), and the one case
    that does read stale names is a respelt scope under an unchanged module
    name, which is why ``fluid_scope_name`` must not be respelt lightly."""
    import jax.numpy as jnp

    def step(name, scope):
        def fn(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x) * 2
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn).lower(jnp.ones((8, 8))).compile()

    def names(compiled):
        return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))

    prev = compile_cache._persistent_base[0]
    try:
        compile_cache.enable_persistent_cache(str(tmp_path / "cache"))
        with compile_cache.count_compiles() as cc:
            # from one line: the call site is metadata too
            bare, at, dot, again, stale = [names(step(*a)) for a in (
                ("fn", "x"), ("pt_exe_cafe", "fluid[mul]x@GRAD"),
                ("pt_exe_beef", "fluid[mul]x.GRAD"),
                ("pt_exe_beef", "fluid[mul]x.GRAD"),
                ("pt_exe_beef", "fluid[mul]y.GRAD"))]
        assert "jit(fn)/x/tanh" in bare
        # why fluid_scope_name writes "." for "@": XLA reads a location
        # "<name>@<function>" and keeps the name, so the "@GRAD" spelling
        # loses the suffix AND the rest of the name stack
        assert "jit(pt_exe_cafe)/fluid[mul]x" in at
        assert not any("GRAD" in n or "tanh" in n for n in at)
        assert "jit(pt_exe_beef)/fluid[mul]x.GRAD/tanh" in dot
        assert again == dot
        # the hazard, pinned: same module name, same operations, another
        # scope -> the first spelling comes back from the warm cache
        assert stale == dot
        # one entry per module name; the last two compiles were hits
        entries = [f for _, _, files in os.walk(str(tmp_path / "cache"))
                   for f in files if f.endswith("-cache")]
        assert sorted(f.rsplit("-", 2)[0] for f in entries
                      if f.startswith(("jit_fn-", "jit_pt_exe_"))) == [
            "jit_fn", "jit_pt_exe_beef", "jit_pt_exe_cafe"]
        assert cc()["persistent_cache_hits"] >= 2
    finally:
        compile_cache.enable_persistent_cache(prev or "")
