"""Predictor API tests (paddle_inference_api.h parity): save -> load via
NativeConfig/AnalysisConfig, Run with PaddleTensor and dict inputs,
clone-per-thread, sequence inputs with lod lengths."""

import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu.inference import (AnalysisConfig, NativeConfig,
                                  PaddleTensor, create_paddle_predictor)


@pytest.fixture
def saved_model(tmp_path, fresh_programs):
    fluid.default_startup_program().random_seed = 7
    x = fluid.layers.data("x", shape=[6])
    h = fluid.layers.fc(x, size=8, act="relu")
    h = fluid.layers.dropout(h, dropout_prob=0.5)
    pred = fluid.layers.fc(h, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(fluid.default_startup_program())
        fluid.io.save_inference_model(str(tmp_path / "model"), ["x"],
                                      [pred], exe)
    return str(tmp_path / "model")


def test_native_predictor_runs(saved_model):
    pred = create_paddle_predictor(NativeConfig(model_dir=saved_model))
    assert pred.feed_names == ["x"]
    xv = np.random.RandomState(0).rand(4, 6).astype("float32")
    (out,) = pred.run([PaddleTensor(name="x", data=xv)])
    assert out.shape == (4, 3)
    np.testing.assert_allclose(np.asarray(out.data).sum(1),
                               np.ones(4), rtol=1e-5)
    # dict input form
    (out2,) = pred.run({"x": xv})
    np.testing.assert_array_equal(out.data, out2.data)


def test_analysis_predictor_deterministic_dropout(saved_model):
    """Saved inference models are inference-mode (for_test at save
    time): dropout is disabled, so repeated runs agree exactly.
    AnalysisConfig is API parity — same behavior as NativeConfig."""
    pred = create_paddle_predictor(AnalysisConfig(model_dir=saved_model))
    xv = np.random.RandomState(1).rand(2, 6).astype("float32")
    a = pred.run({"x": xv})[0].data
    b = pred.run({"x": xv})[0].data
    np.testing.assert_array_equal(a, b)


def test_predictor_clone_shares_weights_and_is_threadsafe(saved_model):
    base = create_paddle_predictor(AnalysisConfig(model_dir=saved_model))
    xv = np.random.RandomState(2).rand(3, 6).astype("float32")
    want = base.run({"x": xv})[0].data
    results = {}

    def worker(i):
        p = base.clone()
        results[i] = p.run({"x": xv})[0].data

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(4):
        np.testing.assert_array_equal(results[i], want)


def test_predictor_input_validation(saved_model):
    pred = create_paddle_predictor(NativeConfig(model_dir=saved_model))
    with pytest.raises(ValueError, match="not a feed target"):
        pred.run({"bogus": np.zeros((1, 6), "float32")})
    with pytest.raises(ValueError, match="missing inputs"):
        pred.run([])


def test_predictor_sequence_input_with_lod(tmp_path, fresh_programs):
    fluid.default_startup_program().random_seed = 3
    ids = fluid.layers.data("ids", shape=[1], dtype="int64", lod_level=1)
    emb = fluid.layers.embedding(ids, size=[20, 4])
    pooled = fluid.layers.sequence_pool(emb, "sum")
    out = fluid.layers.fc(pooled, size=2, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(fluid.default_startup_program())
        fluid.io.save_inference_model(
            str(tmp_path / "m2"), ["ids", "ids@LEN"], [out], exe)
    pred = create_paddle_predictor(
        NativeConfig(model_dir=str(tmp_path / "m2")))
    idv = np.random.RandomState(4).randint(0, 20, (2, 5, 1)).astype(
        "int64")
    (o,) = pred.run([PaddleTensor(name="ids", data=idv, lod=[5, 3])])
    assert o.shape == (2, 2)


def test_inference_transpiler_folds_bn_into_conv():
    """BN folding: the optimized program has NO batch_norm ops and
    produces the same outputs as the un-optimized inference program."""
    import numpy as np

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 9
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[3, 8, 8])
        c1 = fluid.layers.conv2d(img, 8, 3, padding=1, bias_attr=False)
        b1 = fluid.layers.batch_norm(c1, act="relu")
        c2 = fluid.layers.conv2d(b1, 4, 1, bias_attr=False)
        b2 = fluid.layers.batch_norm(c2, act=None)
        out = fluid.layers.reduce_mean(b2, dim=[2, 3])

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # make running stats non-trivial so the fold is a real test
        for op in main.global_block().ops:
            if op.type == "batch_norm":
                rng = np.random.RandomState(1)
                scope.set_var(op.inputs["Mean"][0],
                              rng.rand(*np.asarray(
                                  scope.var(op.inputs["Mean"][0])).shape
                                       ).astype("float32"))
                scope.set_var(op.inputs["Variance"][0],
                              (rng.rand(*np.asarray(scope.var(
                                  op.inputs["Variance"][0])).shape)
                               + 0.5).astype("float32"))
        infer = main.clone(for_test=True)
        rng = np.random.RandomState(0)
        xv = rng.rand(2, 3, 8, 8).astype("float32")
        (ref,) = exe.run(infer, feed={"img": xv}, fetch_list=[out.name])

        t = fluid.InferenceTranspiler()
        opt = t.transpile(infer, fluid.CPUPlace(), scope)
        types = [op.type for op in opt.global_block().ops]
        assert "batch_norm" not in types, types
        # the input program is untouched (use the return value)
        assert any(op.type == "batch_norm"
                   for op in infer.global_block().ops)
        (got,) = exe.run(opt, feed={"img": xv}, fetch_list=[out.name])
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
        # a TRAIN program transpiles too (is_test flip happens inside)
        opt2 = t.transpile(main, fluid.CPUPlace(), scope)
        assert not any(op.type == "batch_norm"
                       for op in opt2.global_block().ops)


def test_clone_concurrency_separate_caches_shared_weights(saved_model):
    """Clone() hardening: each clone owns its executor cache (no lock
    contention on compiled entries), all clones share the ONE immutable
    weight scope, and concurrent Runs are bit-identical to the base."""
    base = create_paddle_predictor(AnalysisConfig(model_dir=saved_model))
    xv = np.random.RandomState(5).rand(4, 6).astype("float32")
    want = base.run({"x": xv})[0].data
    clones = [base.clone() for _ in range(2)]
    for c in clones:
        # separate executors and compiled-program caches...
        assert c._exe is not base._exe
        assert c._exe._cache is not base._exe._cache
        # ...over the same shared weight scope and program
        assert c._scope is base._scope
        assert c._program is base._program
    results = {}

    def worker(i, p):
        results[i] = p.run({"x": xv})[0].data

    threads = [threading.Thread(target=worker, args=(i, c))
               for i, c in enumerate(clones)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(len(clones)):
        np.testing.assert_array_equal(results[i], want)
        # each clone compiled through its own cache
        assert len(clones[i]._exe._cache) == 1


def test_second_run_same_signature_zero_new_lowerings(saved_model):
    """Warm-path regression gate: a second Run with the same input
    signature is a pure dispatch — zero new jit/pmap lowerings."""
    pred = create_paddle_predictor(NativeConfig(model_dir=saved_model))
    xv = np.random.RandomState(6).rand(3, 6).astype("float32")
    pred.run({"x": xv})                      # cold: trace + compile
    with compile_cache.count_compiles() as n:
        out2 = pred.run({"x": xv})
        out3 = pred.run({"x": xv})
    assert n()["jax_lowerings"] == 0, n()
    np.testing.assert_array_equal(out2[0].data, out3[0].data)


def test_predictor_serving_delegation_matches_direct(saved_model):
    """enable_serving: Run splits the batch through the shared
    continuous-batching engine and reassembles — outputs identical to
    the direct dispatch, clones share ONE engine."""
    direct = create_paddle_predictor(AnalysisConfig(model_dir=saved_model))
    xv = np.random.RandomState(7).rand(5, 6).astype("float32")
    want = direct.run({"x": xv})[0].data

    cfg = AnalysisConfig(model_dir=saved_model).enable_serving(
        slots=4, timeout_s=60.0)
    pred = create_paddle_predictor(cfg)
    try:
        got = pred.run({"x": xv})[0].data
        np.testing.assert_array_equal(got, want)
        clone = pred.clone()
        got2 = clone.run({"x": xv})[0].data
        np.testing.assert_array_equal(got2, want)
        assert clone.serving_engine() is pred.serving_engine()
        summ = pred.serving_engine().metrics.summary()
        # each 5-row Run splits into ceil(5/4) slot-capacity requests
        assert summ["counts"]["completed"] == 4
    finally:
        pred.serving_engine().close()
