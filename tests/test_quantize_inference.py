"""Quantized execution (ISSUE 14): the ``quantize_inference`` program
pass, the ``dequant_matmul`` op's two XLA bodies, the accuracy-gated
``tune_quantization`` decision procedure, and the serving wiring.

CPU-testable by design: gate logic and pass semantics run on the XLA
int8 body."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import autotune, compile_cache
from paddle_tpu.transpiler import quantize_inference
from paddle_tpu.transpiler.quantize_pass import QUANT_SUFFIX, SCALE_SUFFIX


def _fc_program(seed=7, d_in=64, d_h=128, d_out=16):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[d_in])
        h = fluid.layers.fc(x, size=d_h, act="relu")
        pred = fluid.layers.fc(h, size=d_out, act="softmax")
    return main, startup, pred


def _init(startup, scope):
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return exe


# ---------------------------------------------------------------------------
# pass semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["weight_only", "dynamic"])
def test_pass_rewrites_weights_and_matches_fp(mode):
    main, startup, pred = _fc_program()
    scope = fluid.Scope()
    exe = _init(startup, scope)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 64).astype("float32")}
    with fluid.scope_guard(scope):
        (ref,) = exe.run(main, feed=feed, fetch_list=[pred])
        q = quantize_inference(main, scope=scope, mode=mode)
        types = [op.type for op in q.global_block().ops]
        assert types.count("dequant_matmul") == 2, types
        assert "mul" not in types
        # the original program is untouched
        assert "dequant_matmul" not in [
            op.type for op in main.global_block().ops]
        # int8 weights + per-output-channel f32 scales in the scope
        w8 = np.asarray(scope.var("fc_0.w_0" + QUANT_SUFFIX))
        sw = np.asarray(scope.var("fc_0.w_0" + SCALE_SUFFIX))
        assert w8.dtype == np.int8 and w8.shape == (64, 128)
        assert sw.dtype == np.float32 and sw.shape == (128,)
        # per-channel grid: each column's dequant error is bounded by
        # ITS OWN scale, not the global max
        w = np.asarray(scope.var("fc_0.w_0"))
        np.testing.assert_allclose(w8 * sw, w, atol=float(sw.max()))
        (out,) = exe.run(q, feed=feed, fetch_list=[pred.name],
                         scope=scope)
        delta = autotune.eval_delta([ref], [out])
        assert delta < 0.02, delta
        # distinct fingerprint: the goodput/program-profile stack
        # attributes the quantized program separately for free
        assert compile_cache.program_fingerprint(q) != \
            compile_cache.program_fingerprint(main)


def test_pass_skips_unquantizable_ops():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = fluid.layers.data("a", shape=[4, 8])
        b = fluid.layers.data("b", shape=[8, 4])
        # non-persistable Y: not a weight, must not be rewritten
        out = fluid.layers.matmul(a, b)
        fluid.layers.mean(out)
    scope = fluid.Scope()
    _init(startup, scope)
    q = quantize_inference(main, scope=scope)
    assert [op.type for op in q.global_block().ops] == \
        [op.type for op in main.global_block().ops]


def test_dequant_matmul_numerics():
    from paddle_tpu.ops.quantize import xla_dequant_matmul

    rng = np.random.RandomState(1)
    x = rng.randn(6, 96).astype(np.float32)
    w = (rng.randn(96, 160) * 0.05).astype(np.float32)
    sw = (np.abs(w).max(axis=0) / 127.0).astype(np.float32)
    qw = np.clip(np.round(w / sw), -127, 127).astype(np.int8)
    import jax.numpy as jnp

    wo = np.asarray(xla_dequant_matmul(jnp.asarray(x), jnp.asarray(qw),
                                       jnp.asarray(sw)))
    np.testing.assert_allclose(wo, x @ (qw.astype(np.float32) * sw),
                               rtol=1e-5, atol=1e-5)
    dyn = np.asarray(xla_dequant_matmul(jnp.asarray(x), jnp.asarray(qw),
                                        jnp.asarray(sw), mode="dynamic"))
    sx = np.maximum(np.abs(x).max(axis=1, keepdims=True), 1e-12) / 127.0
    qx = np.clip(np.round(x / sx), -127, 127).astype(np.int64)
    ref = (qx @ qw.astype(np.int64)).astype(np.float64) * sx * sw
    np.testing.assert_allclose(dyn, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# per-channel fake-quant (QAT grid parity satellite)
# ---------------------------------------------------------------------------

def test_fake_quantize_abs_max_per_channel():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[-1, 6],
                              append_batch_size=False)
        block = main.global_block()
        out = block.create_var(name="q", dtype="float32")
        scale = block.create_var(name="qs", dtype="float32")
        block.append_op(
            type="fake_quantize_abs_max", inputs={"X": [x]},
            outputs={"Out": [out], "OutScale": [scale]},
            attrs={"bit_length": 8, "quant_axis": 0})
    assert block.var("qs").shape == (-1,) or block.var("qs").shape[0] in \
        (-1, 6)   # -1 rows: channel count resolves at run time
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    xv = np.array([[0.5, -1.0, 2.0, 0.1, -0.2, 4.0],
                   [0.25, 0.5, -1.0, 0.05, 0.1, -2.0]], "float32")
    with fluid.scope_guard(sc):
        exe.run(startup)
        q, s = exe.run(main, feed={"x": xv}, fetch_list=["q", "qs"])
    # per-row (axis 0) grids: each row's scale is its own abs max
    np.testing.assert_allclose(np.asarray(s),
                               np.abs(xv).max(axis=1), rtol=1e-6)
    ref = np.round(xv / np.asarray(s)[:, None] * 127) \
        * np.asarray(s)[:, None] / 127
    np.testing.assert_allclose(np.asarray(q), ref, rtol=1e-5, atol=1e-6)


def test_qat_per_channel_weight_grid_matches_pass():
    """QuantizeTranspiler(weight_quant_axis='auto') trains against the
    SAME per-output-channel grid quantize_inference deploys."""
    from paddle_tpu.contrib.quantize import QuantizeTranspiler

    qt = QuantizeTranspiler(weight_quant_axis="auto")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        pred = fluid.layers.fc(x, size=8, act="softmax")
        n = qt.training_transpile(main, startup)
        assert n >= 2
    fq = [op for op in main.global_block().ops
          if op.type == "fake_quantize_abs_max"
          and op.inputs["X"][0] == "fc_0.w_0"]
    assert fq and fq[0].attrs.get("quant_axis") == 1
    scale_var = main.global_block().var(fq[0].outputs["OutScale"][0])
    assert scale_var.shape == (8,)     # one grid per output channel
    scope = fluid.Scope()
    exe = _init(startup, scope)
    with fluid.scope_guard(scope):
        (p,) = exe.run(main, feed={"x": np.random.RandomState(0)
                                   .rand(4, 16).astype("float32")},
                       fetch_list=[pred])
        assert np.isfinite(np.asarray(p)).all()
        # convert_to_int8 honors the per-channel axis
        conv = qt.convert_to_int8(main, scope=scope)
        q8 = np.asarray(scope.var("fc_0.w_0.int8"))
        s8 = np.asarray(scope.var("fc_0.w_0.int8_scale"))
        assert q8.dtype == np.int8 and s8.shape == (8,)
        w = np.asarray(scope.var("fc_0.w_0"))
        np.testing.assert_allclose(q8 * (s8 / 127.0), w,
                                   atol=float(s8.max()) / 100)
        assert "fc_0.w_0" in conv


def test_pass_consumes_qat_out_scale_as_calibration():
    """A frozen QAT program deploys on the TRAINED running envelope —
    the pass consumes it instead of re-measuring, and the weight-side
    fake-quant op disappears from the rewritten program."""
    from paddle_tpu.contrib.quantize import QuantizeTranspiler

    qt = QuantizeTranspiler(weight_quantize_type="range_abs_max",
                            activation_quantize_type="range_abs_max")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 6
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        pred = fluid.layers.fc(x, size=4, act="softmax")
        qt.training_transpile(main, startup)
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    scope = fluid.Scope()
    exe = _init(startup, scope)
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        for _ in range(3):
            exe.run(main, feed={
                "x": rng.rand(8, 16).astype("float32"),
                "label": rng.randint(0, 4, (8, 1)).astype("int64")},
                fetch_list=[loss])
        trained = float(np.asarray(scope.var("fc_0.w_0.scale"))[0])
        assert trained > 0
        # the inference subgraph (freeze + prune, what
        # save_inference_model ships) is what the pass quantizes
        frozen = qt.freeze_program(main, fluid.CPUPlace(), scope=scope) \
            .prune_feed_fetch(["x"], [pred.name])
        q = quantize_inference(frozen, scope=scope, mode="weight_only")
        info = q._quantize_info
        assert info["weights"]["fc_0.w_0"]["calibration"] == \
            "qat_out_scale"
        # deployed grid == trained envelope / 127 (broadcast)
        sw = np.asarray(scope.var("fc_0.w_0" + SCALE_SUFFIX))
        np.testing.assert_allclose(sw, trained / 127.0, rtol=1e-6)
        # the weight-side fake-quant is consumed; activation-side stays
        fq_inputs = [op.inputs["X"][0]
                     for op in q.global_block().ops
                     if op.type.startswith("fake_quantize")]
        assert "fc_0.w_0" not in fq_inputs
        feed = {"x": rng.rand(4, 16).astype("float32"),
                "label": np.zeros((4, 1), "int64")}
        (ref,) = exe.run(frozen, feed=feed, fetch_list=[pred.name],
                         scope=scope)
        (out,) = exe.run(q, feed=feed, fetch_list=[pred.name],
                         scope=scope)
        assert autotune.eval_delta([ref], [out]) < 0.05


def test_dynamic_mode_consumes_qat_activation_scale():
    from paddle_tpu.contrib.quantize import QuantizeTranspiler

    qt = QuantizeTranspiler(activation_quantize_type="range_abs_max")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 6
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        pred = fluid.layers.fc(x, size=4, act="softmax")
        qt.training_transpile(main, startup)
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    scope = fluid.Scope()
    exe = _init(startup, scope)
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(main, feed={
            "x": rng.rand(8, 16).astype("float32"),
            "label": rng.randint(0, 4, (8, 1)).astype("int64")},
            fetch_list=[loss])
        frozen = qt.freeze_program(main, fluid.CPUPlace(), scope=scope) \
            .prune_feed_fetch(["x"], [pred.name])
        q = quantize_inference(frozen, scope=scope, mode="dynamic")
        dq = [op for op in q.global_block().ops
              if op.type == "dequant_matmul"]
        assert dq and dq[0].inputs.get("XScale") == ["x.scale"]
        (out,) = exe.run(q, feed={"x": rng.rand(4, 16).astype(
            "float32")}, fetch_list=[pred.name], scope=scope)
        assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# save/load round trip + warm-path lowerings
# ---------------------------------------------------------------------------

def test_save_load_round_trip_cold_and_zero_warm_lowerings(tmp_path):
    main, startup, pred = _fc_program()
    scope = fluid.Scope()
    exe = _init(startup, scope)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 64).astype("float32")}
    d = str(tmp_path / "model")
    with fluid.scope_guard(scope):
        (ref,) = exe.run(main, feed=feed, fetch_list=[pred])
        q = quantize_inference(main, scope=scope, mode="weight_only")
        fluid.io.save_inference_model(
            d, ["x"], [q.global_block().var(pred.name)], exe,
            main_program=q)
    # the artifact ships int8 persistables and DROPS the fp masters
    import json

    mm = json.load(open(os.path.join(d, "__model__")))
    names = [v["name"] for b in mm["program"]["blocks"]
             for v in b["vars"]]
    assert any(n.endswith(QUANT_SUFFIX) for n in names)
    assert "fc_0.w_0" not in names
    # cold load runs quantized with no re-calibration
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        prog2, feeds, fetches = fluid.io.load_inference_model(d, exe)
        assert "dequant_matmul" in [op.type
                                    for op in prog2.global_block().ops]
        (out,) = exe.run(prog2, feed=feed, fetch_list=fetches)
        assert autotune.eval_delta([ref], [out]) < 0.02
        # warm serving path: a second dispatch of the same signature
        # performs ZERO lowerings
        with compile_cache.count_compiles() as n:
            (out2,) = exe.run(prog2, feed=feed, fetch_list=fetches)
        assert n()["jax_lowerings"] == 0, n()
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ---------------------------------------------------------------------------
# the accuracy gate
# ---------------------------------------------------------------------------

def test_tune_quantization_picks_mode_and_records_evidence():
    main, startup, pred = _fc_program()
    scope = fluid.Scope()
    _init(startup, scope)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(16, 64).astype("float32")}
    cfg = autotune.TunedConfig(meta={"model": "test"})
    with fluid.scope_guard(scope):
        d = autotune.tune_quantization(
            main, scope, feed, [pred], fluid.CPUPlace(),
            probe_steps=2, min_speedup=0.0, config=cfg)
    assert d["chosen"] in ("weight_only", "dynamic")
    assert d["accuracy_delta"] <= d["accuracy_budget"]
    assert {c["mode"] for c in d["candidates"]} == \
        {"weight_only", "dynamic"}
    for c in d["candidates"]:
        assert "accuracy_delta" in c and "step_s" in c
    # evidence landed in the TunedConfig artifact
    got = cfg.get("quantization")
    assert got is not None and got["chosen"] == d["chosen"]
    assert got["evidence"] == "measured_ab_window+eval_delta"


def test_tune_quantization_rejects_corrupted_scales_keeps_fp():
    """Acceptance drill: a deliberately accuracy-broken quantization
    (injected scale corruption) is rejected and full precision kept,
    with the rejection recorded as TunedConfig evidence."""
    main, startup, pred = _fc_program()
    scope = fluid.Scope()
    _init(startup, scope)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(16, 64).astype("float32")}
    cfg = autotune.TunedConfig(meta={"model": "test"})
    with fluid.scope_guard(scope):
        qbad = quantize_inference(main, scope=scope, mode="weight_only")
        sname = "fc_0.w_0" + SCALE_SUFFIX
        scope.set_var(sname, np.asarray(scope.var(sname)) * 100.0)
        d = autotune.tune_quantization(
            main, scope, feed, [pred], fluid.CPUPlace(),
            probe_steps=2, min_speedup=0.0,
            candidates=[("weight_only", qbad)], config=cfg)
    assert d["chosen"] is None          # full precision kept
    (cand,) = d["candidates"]
    assert cand["status"] == "rejected_accuracy"
    assert cand["accuracy_delta"] > d["accuracy_budget"]
    got = cfg.get("quantization")
    assert got["chosen"] is None
    assert got["candidates"][0]["status"] == "rejected_accuracy"


def test_tune_quantization_pinned_mode_wins():
    main, startup, pred = _fc_program()
    scope = fluid.Scope()
    _init(startup, scope)
    feed = {"x": np.random.RandomState(0).rand(4, 64).astype("float32")}
    from paddle_tpu import flags as _flags

    was_pinned = _flags.pinned("quantize_mode")
    fluid.set_flags({"FLAGS_quantize_mode": "off"})   # pins
    try:
        cfg = autotune.TunedConfig(meta={})
        with fluid.scope_guard(scope):
            d = autotune.tune_quantization(
                main, scope, feed, [pred], fluid.CPUPlace(), config=cfg)
        assert d["chosen"] is None and d["evidence"] == "pinned"
        assert cfg.get("quantization")["source"] == "pinned"
    finally:
        _flags.set_flags({"quantize_mode": ""}, pin=False)
        _flags._restore_pins({"quantize_mode": was_pinned})


def test_decide_quantization_pure_policy():
    cands = [
        {"mode": "weight_only", "accuracy_delta": 0.001, "step_s": 0.5},
        {"mode": "dynamic", "accuracy_delta": 0.5, "step_s": 0.2},
        {"mode": "broken", "rejected": "error: boom"},
    ]
    d = autotune.decide_quantization(1.0, cands, budget=0.02,
                                     min_speedup=1.0, batch=10)
    assert d["chosen"] == "weight_only"
    by_mode = {c["mode"]: c for c in d["candidates"]}
    assert by_mode["dynamic"]["status"] == "rejected_accuracy"
    assert by_mode["weight_only"]["status"] == "ok"
    assert "status" not in by_mode["broken"]
    assert d["chosen_tok_s"] == 20.0 and d["fp_tok_s"] == 10.0
    # a candidate under budget but SLOWER than fp is rejected too
    d2 = autotune.decide_quantization(
        1.0, [{"mode": "weight_only", "accuracy_delta": 0.001,
               "step_s": 1.5}], budget=0.02)
    assert d2["chosen"] is None
    assert d2["candidates"][0]["status"] == "rejected_slower"


# ---------------------------------------------------------------------------
# serving wiring
# ---------------------------------------------------------------------------

def test_inference_engine_quantized_matches_fp(tmp_path):
    from paddle_tpu.serving import InferenceEngine

    main, startup, pred = _fc_program(d_in=32, d_h=64, d_out=8)
    scope = fluid.Scope()
    exe = _init(startup, scope)
    d = str(tmp_path / "model")
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(
            d, ["x"], [pred], exe, main_program=main)
        feed = {"x": rng.rand(4, 32).astype("float32")}
        (ref,) = exe.run(main, feed=feed, fetch_list=[pred])
    eng = InferenceEngine(model_dir=d, slots=4, timeout_s=60.0,
                          quantize="weight_only")
    try:
        assert eng.quantize_mode == "weight_only"
        assert "dequant_matmul" in [
            op.type for op in eng._program.global_block().ops]
        outs = np.stack([np.asarray(eng.run({"x": feed["x"][i]})[0])
                         for i in range(4)])
        assert autotune.eval_delta([np.asarray(ref)], [outs]) < 0.02
    finally:
        eng.close()


def test_inference_engine_consumes_tuned_quantization_ruling(tmp_path):
    from paddle_tpu.serving import InferenceEngine

    main, startup, pred = _fc_program(d_in=32, d_h=64, d_out=8)
    scope = fluid.Scope()
    exe = _init(startup, scope)
    d = str(tmp_path / "model")
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(
            d, ["x"], [pred], exe, main_program=main)
    cfg = autotune.TunedConfig(decisions=[
        {"knob": "quantization", "chosen": "weight_only"}])
    eng = InferenceEngine(model_dir=d, slots=4, timeout_s=60.0,
                          tuned_config=cfg)
    try:
        assert eng.quantize_mode == "weight_only"
    finally:
        eng.close()
    # a gate that KEPT full precision must not quantize
    cfg2 = autotune.TunedConfig(decisions=[
        {"knob": "quantization", "chosen": None}])
    eng2 = InferenceEngine(model_dir=d, slots=4, timeout_s=60.0,
                           tuned_config=cfg2)
    try:
        assert eng2.quantize_mode is None
    finally:
        eng2.close()


@pytest.mark.slow
def test_generation_engine_quantized_decode():
    """Slow-marked for the tier-1 wall budget (the serving decode
    parity precedent); the DecoderSpec.quantize rewrite itself is
    cheap and the InferenceEngine wiring stays tier-1."""
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.serving.decoder import build_decoder_lm

    spec = build_decoder_lm(vocab_size=32, max_len=32, slots=4,
                            n_layer=1, n_head=2, d_model=16, d_inner=32,
                            seed=11, prefix="qlm")
    eng = GenerationEngine(spec, place=fluid.CPUPlace(),
                           max_new_tokens=4, record_logits=True,
                           quantize="weight_only", start=True)
    try:
        assert eng.quantize_mode == "weight_only"
        types = [op.type
                 for op in eng.spec.decode_program.global_block().ops]
        assert "dequant_matmul" in types
        r = eng.generate([3, 5, 7], timeout=120)
        assert len(r["tokens"]) == 4
        assert all(np.isfinite(row).all() for row in r["logits"])
        # int8 decode working set: the quantized weights really are
        # 1/4 the bytes of the f32 masters
        info = eng.spec.decode_program._quantize_info
        assert info["weights"]
        for w in info["weights"].values():
            assert w["bytes_int8"] * 4 == w["bytes_fp"]
    finally:
        eng.close()


def test_predictor_enable_quantization(tmp_path):
    from paddle_tpu.inference import (AnalysisConfig,
                                      create_paddle_predictor)

    main, startup, pred = _fc_program(d_in=32, d_h=64, d_out=8)
    scope = fluid.Scope()
    exe = _init(startup, scope)
    d = str(tmp_path / "model")
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(
            d, ["x"], [pred], exe, main_program=main)
    base = create_paddle_predictor(
        AnalysisConfig(model_dir=d, use_gpu=False))
    quant = create_paddle_predictor(
        AnalysisConfig(model_dir=d,
                       use_gpu=False).enable_quantization())
    xv = rng.rand(2, 32).astype("float32")
    (ref,) = base.run({"x": xv})
    (out,) = quant.run({"x": xv})
    assert autotune.eval_delta([ref.data], [out.data]) < 0.02
    # clones share the quantized program
    clone = quant.clone()
    (outc,) = clone.run({"x": xv})
    np.testing.assert_array_equal(out.data, outc.data)


# ---------------------------------------------------------------------------
# the bench rung acceptance: quantized beats bf16 at accuracy parity
# ---------------------------------------------------------------------------

def test_bench_quantized_rung_beats_bf16_under_budget():
    """ISSUE 14 acceptance: the quantized forward rung's tok/s beats
    the bf16 rung's with the accuracy delta under the configured
    budget — the gate predicate itself is the assertion."""
    import argparse
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    args = argparse.Namespace(model="quantized", device="cpu",
                              batch_size=0, iterations=3,
                              skip_batch_num=2)
    old_windows = bench.N_WINDOWS
    bench.N_WINDOWS = 2   # tier-1 wall-clock: 2 interleaved A/B windows
    try:
        r = bench.bench_quantized(args)
    finally:
        bench.N_WINDOWS = old_windows
    assert r["unit"] == "tokens/sec" and r["value"] > 0
    # the acceptance predicate: faster than bf16 AND delta under budget
    assert r["value"] > r["bf16_tok_s"], (r["value"], r["bf16_tok_s"])
    assert r["accuracy_delta"] <= r["accuracy_budget"], r
    assert r["gate_pass"] is True
    # evidence: the TunedConfig trail is embedded, weight bytes shrank
    knobs = [d["knob"] for d in r["autotune"]["decisions"]]
    assert "quantization" in knobs
    assert r["weight_bytes_int8"] * 4 == r["weight_bytes_fp"]
    assert r["min_step_s"] < r["bf16_min_step_s"]
