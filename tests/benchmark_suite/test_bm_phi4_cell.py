"""The cell ``phi4_mini_flash.train_reason_4k`` on the CPU: ``--check`` at its
tiny sizes agrees with the plain reference, the control in fp8 and every
planted fault do not, the configuration's file keeps to the catalog's sizes,
the three new readers read what a traced run hands them (and nothing from a
run without their op types), and the flops module counts what the issue's
arithmetic counts."""

import json
import math
import os
import shutil

import pytest

from bm_util import CELLS, ROOT, check_cell

from benchmark import harness
from benchmark.generators import train_hybrid_steps as gen
from benchmark.metrics import _scopes
from benchmark.trace import scopes

BENCH = harness.load_benchmark(ROOT)
OLDER = ("keye_vl2_30b_a3b.train_longdoc_8k", "joyai_llm_flash.train_mtp_8k",
         "ouro_2_6b.train_loop_4k")
CELL = "phi4_mini_flash.train_reason_4k"
NEW_METRICS = ("device_ms_per_step.ssm", "selective_scan_roofline",
               "hybrid_attention_roofline")
SETUP_METRICS = ("setup_build_s", "setup_program_trace_s", "setup_lowering_s",
                 "setup_executable_s", "setup_executables_compiled")
REDUCED = ["num_hidden_layers", "vocab_size"]
# the catalog's `config` of Phi-4-mini-flash-reasoning, less the reduced keys
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False}
LIMITS = {"grad_rel_error_rms", "loss_rel_gap", "scan_state_gap",
          "grad_norm_gap", "grad_norm_gap_rms", "update_norm_gap",
          "update_norm_gap_rms", "tied_table_grad_error"}
TINY_TRACE = os.path.join(ROOT, "tests", "benchmark_suite", "data",
                          "tiny_train_trace.xplane.pb")


def test_benchmark_json_holds_the_six_cells_and_five_configurations():
    """What ``test_bm_setup_metrics.py``'s pin meant, of the entries there
    are now: the five cells, four configurations and 31 per-layer metrics
    that were there first and unchanged but for this cell's name appended to
    their lists; then one configuration, one cell and three per-layer
    metrics more, each appended last; one cell of six on four chips."""
    cells = [w["name"] for w in BENCH["workloads"]]
    assert tuple(cells) == CELLS + OLDER + (CELL,)
    assert [c["name"] for c in BENCH["configs"]] == [
        "transformer_base", "keye_vl2_30b_a3b", "joyai_llm_flash",
        "ouro_2_6b", "phi4_mini_flash"]
    assert [w["chips"] for w in BENCH["workloads"]] == [1, 4, 1, 1, 1, 1]
    cell = BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4_mini_flash", "train_reason_4k", 1)
    assert len(cell["why"]) <= 200
    assert BENCH["run_seconds"] == 51
    assert [(m["name"], m["bound"], m["workloads"][-1])
            for m in BENCH["end_to_end"][:1]] == [
        ("train_tokens_per_s", 0.01, CELL)]
    assert [(m["name"], m["bound"]) for m in BENCH["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("setup_s", 0.1)]
    assert "workloads" not in BENCH["end_to_end"][1]        # every cell's
    older, last = BENCH["per_layer"][:-3], BENCH["per_layer"][-3:]
    assert len(older) == 31
    assert tuple(m["name"] for m in older[-5:]) == SETUP_METRICS
    for m in older[-5:]:
        assert (m["moves"], m["workloads"]) == ("setup_s", cells)
    assert tuple(m["name"] for m in older[-8:-5]) == (
        "plain_attention_roofline", "device_ms_per_step.exit_gate",
        "device_ms_per_step.rms_norm")
    for m in older[-8:-5]:                      # PR 33's: its cell's alone
        assert m["workloads"] == [OLDER[2]]
    assert tuple(m["name"] for m in last) == NEW_METRICS
    for m, (unit, better) in zip(last, (("ms", "lower"), ("%", "higher"),
                                        ("%", "higher"))):
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "device_trace", "layer": "op kernels",
                     "moves": "train_tokens_per_s", "workloads": [CELL]}
    # what the cell reports: the eighteen every cell reports, the two
    # groups that hold layer_norm and the default types, and its own three
    reports = {m["name"] for m in BENCH["per_layer"]
               if CELL in m["workloads"]}
    every = {m["name"] for m in older
             if set(cells[:-1]) <= set(m["workloads"])}
    assert len(every) == 18
    assert reports == every | {"device_ms_per_step.norm",
                               "device_ms_per_step.elementwise"} \
        | set(NEW_METRICS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL               # appended last
            assert m["workloads"].count(CELL) == 1
    # the looped cell still reports what it reported: the pin's last line
    assert len([m for m in older[:-5] if OLDER[1] in m["workloads"]]) == 17


def test_configuration_keeps_every_published_size():
    """What ``test_bm_contract.py::test_configuration_entry_and_file``
    holds a configuration to, with ``num_hidden_layers`` read as the depth
    it is."""
    conf = next(c for c in BENCH["configs"] if c["name"] == "phi4_mini_flash")
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert len(conf["why"]) <= 200
    assert conf["source"] == ("https://huggingface.co/microsoft/"
                              "Phi-4-mini-flash-reasoning/blob/main/"
                              "config.json")
    data = json.load(open(os.path.join(ROOT, conf["file"])))
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    for key, value in PUBLISHED.items():
        assert data[key] == value and type(data[key]) is type(value), key
    assert conf["reduced"] == data["reduced"] == REDUCED
    assert set(data["reduced_why"]) == set(REDUCED)
    assert (data["num_hidden_layers"], data["vocab_size"]) == (5, 25008)
    assert data["published"] == {"num_hidden_layers": 32,
                                 "vocab_size": 200064}
    assert data["vocab_size"] * 8 == data["published"]["vocab_size"]
    # five contiguous published layers, every kind of layer the model has
    assert data["layer_kinds"] == ["window", "mamba", "full", "gmu", "cross"]
    assert len(data["layer_kinds"]) == data["num_hidden_layers"]
    assert data["first_layer"] == 15
    assert (data["mamba_expand"], data["mamba_d_state"], data["mamba_d_conv"],
            data["mamba_dt_rank"]) == (2, 16, 4, math.ceil(2560 / 16))
    assert set(data["limits"]) == set(data["tiny"]["limits"]) == LIMITS
    assert set(data["limits_why"]) == LIMITS | {"readings"}
    assert all(0 < v < 1 for v in data["limits"].values())
    assert set(data["assumed"]) == {
        "pattern", "layer", "mamba", "memory", "differential_attention",
        "head", "optimizer", "weights", "job"}
    assert data["control_precision"] == "fp8"
    assert data["precision"] == "bf16_amp" \
        and "float32" in data["precision_stated"]
    for key in ("source_part", "deployment", "precision_stated"):
        assert data[key]
    for kind, key in (("models", "builder"), ("reference", "reference"),
                      ("flops", "flops")):
        harness.load_module(kind, data[key], ROOT)
    # no width is cut at the real sizes; the tiny sizes are the CPU's and
    # keep the shape: two query heads a key/value head, the heads in pairs
    tiny = data["tiny"]
    assert tiny["num_attention_heads"] == 2 * tiny["num_key_value_heads"]
    assert tiny["num_key_value_heads"] % 2 == 0
    for key in tiny:
        assert key in data, key


def test_traffic_draws_documents_one_token_longer():
    _, cfg, traffic = harness.resolve_cell(BENCH, CELL)
    assert (traffic["generator"], traffic["rows"], traffic["seq"],
            traffic["pool"], traffic["fetch_every"],
            traffic["profile_steps"]) == ("train_hybrid_steps", 1, 4096, 16,
                                          20, 10)
    assert traffic["seq"] == 8 * cfg["sliding_window"]
    small = dict(traffic, rows=2, seq=16, pool=3)
    a = gen.make_batches(small, 97, 2 ** 31 + 17)
    b = gen.make_batches(small, 97, 2 ** 31 + 17)
    assert len(a) == 3 and set(a[0]) == {"tok", "lbl"}
    for x, y in zip(a, b):
        for n in x:
            assert (x[n] == y[n]).all() and x[n].shape == (2, 16)
        assert (x["lbl"][:, :-1] == x["tok"][:, 1:]).all()
        assert 0 <= x["lbl"].min() and x["lbl"].max() < 97
    assert (a[0]["tok"] != gen.make_batches(small, 97, 5)[0]["tok"]).any()


def test_seeded_weights_follow_the_assumed_initialisations():
    import numpy as np

    _, cfg, _ = harness.resolve_cell(BENCH, CELL, tiny=True)
    ref = harness.load_reference(cfg["reference"], ROOT)
    spec = ref.param_spec(cfg)
    w = gen.seeded_weights(spec, cfg, 2 ** 31 + 5)
    again = gen.seeded_weights(spec, cfg, 2 ** 31 + 5)
    assert all((w[n] == again[n]).all() for n in w)
    assert (w["l16.ssm.in"] != gen.seeded_weights(spec, cfg, 6)[
        "l16.ssm.in"]).any()
    n = cfg["mamba_d_state"]
    np.testing.assert_allclose(np.exp(w["l16.ssm.A_log"][3]),
                               np.arange(1, n + 1), rtol=1e-6)
    assert (w["l16.ssm.D"] == 1).all() and (w["l16.ssm.conv.b"] == 0).all()
    step = np.log1p(np.exp(w["l16.ssm.dt.b"]))          # softplus
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert np.std(w["l15.attn.lq1"]) == pytest.approx(0.1, rel=0.5)
    assert np.std(w["tok_emb"]) == pytest.approx(cfg["initializer_range"],
                                                 rel=0.05)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_check_agrees_with_the_plain_reference(seed):
    result = check_cell(CELL, seed)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result) == set(harness.RESULT_KEYS)
    assert result["device"]["platform"] == "cpu"
    # a CPU run yields counts, never a time, a rate or a share
    assert result["metrics"] == {
        "compiles_in_window.train": {"value": 0, "unit": "count"}}


def _reference_side(seed):
    _, cfg, traffic = harness.resolve_cell(BENCH, CELL, tiny=True)
    ref = harness.load_reference(cfg["reference"])
    batches = gen.make_batches(dict(traffic, pool=3), cfg["vocab_size"], seed)
    w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
    want = gen.reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
    return ref, cfg, batches, w0, want


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_fp8_is_not_correct(seed):
    """The reference with its products in fp8 (e4m3), put in the program's
    place, fails the cell's numbers under the tiny limits: through the
    comparison that decides ``correct``."""
    ref, cfg, batches, w0, want = _reference_side(seed)
    ctl = gen.control_readings(ref, cfg, batches, w0, want,
                               cfg["control_precision"])
    assert {"grad_rel_error_rms", "scan_state_gap", "tied_table_grad_error",
            "grad_norm_gap_rms", "update_norm_gap_rms"} <= set(
        gen.checks_failed(ctl, want, cfg["limits"]))


# what each planted fault must fail at the least (CPU, tiny sizes)
FAULT_FAILS = {
    "window_off_by_block": {"grad_rel_error_rms", "loss_rel_gap.step1",
                            "grad_norm_gap_rms", "scan_state_gap"},
    "lambda_dropped": {"grad_rel_error_rms", "loss_rel_gap.step1",
                       "grad_norm_gap", "grad_norm_gap_rms"},
    "memory_after_gate": {"grad_rel_error_rms", "grad_norm_gap_rms"},
    "cross_own_keys": {"grad_rel_error_rms", "loss_rel_gap.step1",
                       "tied_table_grad_error", "grad_norm_gap_rms"},
    "conv_tap_ahead": {"grad_rel_error_rms", "scan_state_gap",
                       "grad_norm_gap_rms"},
    "head_untied": {"tied_table_grad_error"},
    "state_unchanged": {"update_norm_gap", "update_norm_gap_rms"},
}


@pytest.mark.parametrize("fault", sorted(FAULT_FAILS))
def test_a_planted_fault_is_not_correct(fault):
    """Each fault the limits are said to stand against, planted in the
    float32 reference and that run put in the program's place, fails the
    comparison that decides ``correct`` — by the numbers meant for it."""
    assert set(FAULT_FAILS) == set(gen.FAULTS)
    ref, cfg, batches, w0, want = _reference_side(2 ** 31 + 11)
    got = gen.fault_readings(ref, cfg, batches, w0, want, fault)
    failed = set(gen.checks_failed(got, want, cfg["limits"]))
    assert FAULT_FAILS[fault] <= failed
    if fault == "state_unchanged":              # the first gradient is sound
        assert not {"grad_rel_error_rms", "grad_norm_gap",
                    "tied_table_grad_error"} & failed
        assert gen.gaps(got, want)["update_norm_gap"] == pytest.approx(1.0)
    # the sound reference, in the program's place, fails nothing
    if fault == "head_untied":
        same = gen.in_program_place(gen.reference_readings(
            ref, cfg, batches, w0, ref.f32_matmul, 3), want)
        assert gen.checks_failed(same, want, cfg["limits"]) == []


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "hybrid_decoder.py")).read()
    body = src.split('"""', 2)[2]
    assert "paddle_tpu" not in body
    imports = [line for line in body.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == [
        "import math", "import jax", "import jax.numpy as jnp",
        "from benchmark.reference.latent_moe_decoder import ("
        "      # noqa: F401",
        "from benchmark.reference.sparse_moe_decoder import ("
        "      # noqa: F401"]
    # every product goes through mm, whose float32 form is at `highest`
    assert "jnp.matmul" not in body and "jnp.dot" not in body \
        and "einsum" not in body and " @ " not in body
    # the recurrence is a scan over time, no kernel
    assert "lax.scan" in body and "pallas" not in body


def _facts(by_type, steps=10):
    facts = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 2.0},
             "traced_steps": steps, "selective_scan_floor_s": 0.001,
             "hybrid_attention_floor_s": 0.004}
    _scopes._READ.clear()
    return facts, {"steps": steps, "host": None, "device": {
        "by_type": {t: {"s": s, "count": steps, "flops": 0, "bytes": 0,
                        "group": "elementwise"} for t, s in by_type.items()}}}


def test_new_readers_read_their_types_time(monkeypatch):
    facts, got = _facts({
        "fused_attention": 0.04, "fused_attention_grad": 0.12,
        "selective_scan": 0.015, "selective_scan_grad": 0.025,
        "causal_conv1d": 0.002, "causal_conv1d_grad": 0.008, "mul": 9.0})
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    ssm, scan, attention = (harness.load_reader(m, ROOT).read
                            for m in NEW_METRICS)
    assert ssm(facts) == pytest.approx(5.0)         # 50 ms over 10 steps
    assert scan(facts) == pytest.approx(25.0)       # 1 ms of 4 ms a step
    assert attention(facts) == pytest.approx(25.0)  # 4 ms of 16 ms a step


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing_where_there_is_nothing_to_read(
        monkeypatch, metric):
    """The parent's traced run of an old cell, with this PR's benchmark
    files laid over it: no such fact or op type was made; the reader
    returns nothing and does not raise.  Nor on empty facts, nor on a run
    that was not traced."""
    read = harness.load_reader(metric, ROOT).read
    # an older cell: attention ran, no state-space type, and none of this
    # cell's floors among the facts
    facts, got = _facts({"mul": 9.0, "fused_attention": 1.0})
    del facts["selective_scan_floor_s"], facts["hybrid_attention_floor_s"]
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    assert read(facts) is None
    # this cell's facts over a trace in which none of the types ran
    facts, got = _facts({"mul": 9.0})
    assert read(facts) is None
    monkeypatch.setattr(_scopes, "reading", lambda f: None)
    assert read({}) is None
    assert read({"selective_scan_floor_s": 0.001,
                 "hybrid_attention_floor_s": 0.004}) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_read_a_recorded_trace(tmp_path, monkeypatch, metric):
    """A trace recorded from another cell's program (the Transformer's tiny
    step, no state-space op and no ``fused_attention`` scope with this
    cell's floors), put where a traced run of this cell leaves its own: the
    readers go through the real reduction and report nothing, as on the
    parent; ``device_ms_per_step.norm``, which the cell also reports, reads
    its layer norms' time from the same file."""
    d = os.path.join(str(tmp_path), ".benchmark_out", "trace", CELL,
                     "plugins", "profile", "2026_01_01_00_00_00")
    os.makedirs(d)
    shutil.copy(TINY_TRACE, os.path.join(d, "vm.xplane.pb"))
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    _scopes._READ.clear()
    facts = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 1.0},
             "traced_steps": 3, "selective_scan_floor_s": 0.001}
    try:
        got = _scopes.reading(dict(facts))
        assert got["steps"] == 3 and "layer_norm" in got["device"]["by_type"]
        assert "selective_scan" not in got["device"]["by_type"]
        assert harness.load_reader(metric, ROOT).read(dict(facts)) is None
        assert harness.load_reader("device_ms_per_step.norm",
                                   ROOT).read(dict(facts)) > 0
    finally:
        _scopes._READ.clear()


def test_flops_count_the_issues_arithmetic():
    _, cfg, _ = harness.resolve_cell(BENCH, CELL)
    flops = harness.load_module("flops", cfg["flops"], ROOT)
    assert flops.causal_pairs(4096) == 8390656
    assert flops.window_pairs(4096, 512) == 1966336
    assert flops.window_pairs(4096, 4096) == flops.causal_pairs(4096)
    assert flops.window_pairs(4096, 1) == 4096
    assert flops.attention_pairs(cfg, 4096) == 2 * 8390656 + 1966336
    # the mixers, as ISSUE 38 counts them (M parameters)
    for kind, m in (("mamba", 41.1), ("window", 19.7), ("full", 19.7),
                    ("cross", 13.1), ("gmu", 26.2)):
        assert flops.mixer_matrix_params(cfg, kind) / 1e6 == pytest.approx(
            m, abs=0.06), kind
    assert flops.trainable_params(cfg) == pytest.approx(577.0e6, rel=5e-4)
    ref = harness.load_reference(cfg["reference"])
    assert flops.trainable_params(cfg) == sum(
        math.prod(shape) for shape, _ in ref.param_spec(cfg).values())
    # the tied table is one parameter, and one product (the head)
    assert flops.matrix_params(cfg) == sum(
        math.prod(shape) for name, (shape, _) in ref.param_spec(cfg).items()
        if len(shape) == 2 and "conv" not in name and "A_log" not in name)
    # QK over 64 and PV over 128 a query head, forward and twice backward
    assert flops.attention_flops(cfg, 1, 4096) == 3 * flops.attention_pairs(
        cfg, 4096) * 40 * (2 * 64 + 2 * 128)
    total = flops.required_flops(cfg, 1, 4096)
    assert total == pytest.approx(15.05e12, rel=2e-3)
    assert total == 6 * 4096 * flops.matrix_params(cfg) \
        + flops.attention_flops(cfg, 1, 4096) + flops.scan_flops(cfg, 1, 4096)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    floor, bound = flops.step_floor_seconds(cfg, 1, 4096, peaks)
    assert bound == "compute" and floor == pytest.approx(0.0764, rel=1e-3)
    # the scan's floor is its bytes (0.67 GB: c, delta, dy, y, dc, d-delta
    # and c, delta again for the backward, [4096, 5120] float32 each), the
    # attention's its operations
    scan_bytes = flops.scan_least_bytes(cfg, 1, 4096)
    assert scan_bytes == pytest.approx(8 * 4096 * 5120 * 4, rel=4e-3)
    assert flops.kernel_floor_seconds(
        flops.scan_flops(cfg, 1, 4096), scan_bytes, peaks) \
        == pytest.approx(scan_bytes / 819e9)
    assert flops.kernel_floor_seconds(
        flops.attention_flops(cfg, 1, 4096),
        flops.attention_least_bytes(cfg, 1, 4096), peaks) == pytest.approx(
        flops.attention_flops(cfg, 1, 4096) / 197e12)


def test_the_gaps_of_norms_leave_the_lambda_vectors_to_the_difference():
    """A lambda vector's gradient is one scalar times a fixed vector: its
    NORM's gap has no bound in bf16, so the two gaps of norms go over the
    other leaves, and ``grad_rel_error_rms`` — the norm of the difference —
    holds the lambda vectors as it holds every leaf."""
    names = ["l15.attn.lq1", "l15.attn.lk2", "l15.attn.sub.g", "l15.mlp.w1",
             "l16.ssm.dt.b"]
    assert sorted(gen.resolved(dict.fromkeys(names, 1.0))) == names[2:]
    want = {"grad_norms": dict.fromkeys(names, 1.0),
            "update_norms": dict.fromkeys(names, 1.0), "losses": [1.0]}
    got = {"grad_norms": dict(want["grad_norms"], **{names[0]: 3.0}),
           "update_norms": dict(want["update_norms"], **{names[1]: 0.0}),
           "grad_errors": dict.fromkeys(names, 0.0), "losses": [1.0]}
    gaps, worst = gen.norm_gaps(got, want)
    assert set(gaps.values()) == {0.0}
    got["update_norms"][names[3]] = 0.5             # a resolved leaf is seen
    gaps, worst = gen.norm_gaps(got, want)
    assert gaps["update_norm_gap"] == pytest.approx(0.5)
    assert worst["update_norm_gap"] == names[3]
    got["grad_errors"][names[0]] = 1.0              # and a lambda vector's
    checks = harness.Checks(lambda line: None)      # difference too
    limits = dict.fromkeys(LIMITS, 1e9)
    gen.compare(got, want, limits, checks)
    rows = {r[0]: r[1] for r in checks.rows}
    assert rows["grad_rel_error_rms"] == pytest.approx((1 / len(names)) ** 0.5)
    assert rows["update_norm_gap"] == pytest.approx(0.5)
    assert rows["grad_norm_gap"] == 0.0
