"""The cell ``mellum2_12b_a2_5b.train_repo_8k`` on the CPU: ``--check`` at its
tiny sizes agrees with the plain reference, the control in fp8 and every
planted fault do not, the configuration's file keeps to the catalog's sizes,
the reference imports nothing of the program, the two new readers read what a
traced run hands them (and nothing from a run without their facts), the
flops module counts what the issue's arithmetic counts, the new entries
needed no edit of a file that was there, and ``BENCHMARK.json`` holds eight
cells and seven configurations."""

import json
import math
import os
import shutil

import pytest

from bm_util import CELLS, ROOT, check_cell

from benchmark import harness
from benchmark.generators import train_window_steps as gen
from benchmark.metrics import _scopes
from benchmark.trace import scopes

BENCH = harness.load_benchmark(ROOT)
OLDER = ("keye_vl2_30b_a3b.train_longdoc_8k", "joyai_llm_flash.train_mtp_8k",
         "ouro_2_6b.train_loop_4k", "phi4_mini_flash.train_reason_4k",
         "kimi_linear_48b_a3b.train_doc_4k")
CELL = "mellum2_12b_a2_5b.train_repo_8k"
NEW_METRICS = ("mixed_attention_roofline", "window_attention_ms_per_step")
REDUCED = ["num_hidden_layers", "num_experts_held", "vocab_size"]
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog's `config` of Mellum2-12B-A2.5B-Instruct, less the reduced keys
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "use_sliding_window": True}
LIMITS = {"grad_rel_error_rms", "loss_rel_gap", "grad_norm_gap",
          "grad_norm_gap_rms", "update_norm_gap", "update_norm_gap_rms",
          "routed_pairs_gap", "mixer_context_gap", "dropped_token_pairs"}
TINY_TRACE = os.path.join(ROOT, "tests", "benchmark_suite", "data",
                          "tiny_train_trace.xplane.pb")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_benchmark_json_holds_the_eight_cells_and_seven_configurations():
    """What ``test_bm_kimi_cell.py``'s pin meant, of the entries there are
    now: the seven cells, six configurations and 36 per-layer metrics that
    were there first and unchanged but for this cell's name appended to
    their lists; then one configuration, one cell and two per-layer metrics
    more, each appended last; one cell of eight on four chips."""
    cells = [w["name"] for w in BENCH["workloads"]]
    assert tuple(cells) == CELLS + OLDER + (CELL,)
    assert [c["name"] for c in BENCH["configs"]] == [
        "transformer_base", "keye_vl2_30b_a3b", "joyai_llm_flash",
        "ouro_2_6b", "phi4_mini_flash", "kimi_linear_48b_a3b",
        "mellum2_12b_a2_5b"]
    assert [w["chips"] for w in BENCH["workloads"]] == [1, 4] + [1] * 6
    cell = BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2_12b_a2_5b", "train_repo_8k", 1)
    assert len(cell["why"]) <= 200
    assert BENCH["run_seconds"] == 51
    assert [(m["name"], m["bound"]) for m in BENCH["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("setup_s", 0.1)]
    assert BENCH["end_to_end"][0]["workloads"] == cells
    assert "workloads" not in BENCH["end_to_end"][1]        # every cell's
    older, last = BENCH["per_layer"][:-2], BENCH["per_layer"][-2:]
    assert len(older) == 36
    assert tuple(m["name"] for m in older[-2:]) == (
        "device_ms_per_step.delta_rule", "delta_rule_roofline")
    for m in older[-2:]:                        # PR 46's: its cell's alone
        assert m["workloads"] == [OLDER[4]]
    assert tuple(m["name"] for m in last) == NEW_METRICS
    for m, (unit, better) in zip(last, (("%", "higher"), ("ms", "lower"))):
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "device_trace", "layer": "op kernels",
                     "moves": "train_tokens_per_s", "workloads": [CELL]}
    # what the cell reports: the eighteen every cell reports, the experts'
    # three, the group that holds the default types (the rotations, the
    # RMSNorms), the RMSNorms' own reader (nine ops a step and their
    # gradients) and its own two
    reports = {m["name"] for m in BENCH["per_layer"]
               if CELL in m["workloads"]}
    every = {m["name"] for m in older
             if set(cells[:-1]) <= set(m["workloads"])}
    assert len(every) == 18
    assert reports == every | {
        "device_ms_per_step.moe", "expert_matmul_roofline",
        "expert_load_max_over_mean.train",
        "device_ms_per_step.elementwise",
        "device_ms_per_step.rms_norm"} | set(NEW_METRICS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL               # appended last
            assert m["workloads"].count(CELL) == 1
    # the delta-attention cell still reports what it reported
    assert len([m for m in older if OLDER[4] in m["workloads"]]) == 26


def test_configuration_keeps_every_published_size():
    """What ``test_bm_contract.py::test_configuration_entry_and_file``
    holds a configuration to, with ``num_hidden_layers`` read as the depth
    it is."""
    conf = next(c for c in BENCH["configs"]
                if c["name"] == "mellum2_12b_a2_5b")
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert len(conf["why"]) <= 200 and conf["source"] == SOURCE
    data = json.load(open(os.path.join(ROOT, conf["file"])))
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    for key, value in PUBLISHED.items():
        assert data[key] == value and type(data[key]) is type(value), key
    assert conf["reduced"] == data["reduced"] == REDUCED
    assert set(data["reduced_why"]) == set(REDUCED)
    assert (data["num_hidden_layers"], data["num_experts_held"],
            data["vocab_size"]) == (4, 8, 12288)
    assert data["published"] == {"num_hidden_layers": 28,
                                 "num_experts_held": 64,
                                 "vocab_size": 98304}
    assert data["vocab_size"] * 8 == data["published"]["vocab_size"]
    assert data["num_experts_held"] * 8 == data["num_experts"]
    assert data["first_local_expert"] == 0 and data["expert_tile"] == 640
    # the four leading published layers: one whole period
    ref = harness.load_reference(data["reference"], ROOT)
    assert ref.mixers(data) == ["window", "window", "window", "full"]
    assert ref.context_layers(data) == [0, 3]
    flops = harness.load_module("flops", data["flops"], ROOT)
    assert flops.mixers(data) == ref.mixers(data)     # the builder reads it
    assert set(data["limits"]) == set(data["tiny"]["limits"]) == LIMITS
    assert set(data["limits_why"]) == LIMITS | {"readings"}
    assert all(0 < v < 1 for k, v in data["limits"].items()
               if k != "dropped_token_pairs")
    assert data["limits"]["dropped_token_pairs"] == 0
    assert set(data["assumed"]) == {
        "qk_norm", "rotation", "yarn", "blocks", "router", "experts",
        "window_keys", "mtp_head", "job", "optimizer", "weights",
        "expert_tile"}
    assert data["control_precision"] == "fp8"
    assert data["precision"] == "bf16_amp" \
        and "float32" in data["precision_stated"]
    for key in ("source_part", "deployment", "precision_stated"):
        assert data[key]
    for kind, key in (("models", "builder"), ("reference", "reference"),
                      ("flops", "flops")):
        harness.load_module(kind, data[key], ROOT)
    # no width is cut at the real sizes; the tiny sizes are the CPU's: the
    # same period, 2 of 8 experts held from the third, a window of 24 keys
    tiny = dict(data, **data["tiny"])
    assert ref.mixers(tiny) == ref.mixers(data)
    assert (tiny["num_experts"], tiny["num_experts_held"],
            tiny["first_local_expert"], tiny["sliding_window"]) == (8, 2, 2,
                                                                    24)
    for key in data["tiny"]:
        assert key in data, key


def test_the_rotations_the_builder_hands_the_program():
    """``rope_parameters`` as the program takes it: the plain law with no
    scaling and unit scale, YaRN's four numbers and its attention factor."""
    _, cfg, _ = harness.resolve_cell(BENCH, CELL)
    rope_of = harness.load_module("models", cfg["builder"], ROOT).rope_of
    ropes = cfg["rope_parameters"]
    assert rope_of(ropes["sliding_attention"]) == (500000.0, None, 1.0)
    assert rope_of(ropes["full_attention"]) == (
        500000.0, {"factor": 16, "original_length": 8192, "beta_fast": 32,
                   "beta_slow": 1}, 1.2772588722239782)
    with pytest.raises(ValueError, match="rope_type"):
        rope_of({"rope_type": "llama3", "rope_theta": 1e4})
    # the reference reads the same numbers, by its own arithmetic
    ref = harness.load_reference(cfg["reference"], ROOT)
    w, a = ref.frequencies(cfg, "full")
    w0, one = ref.frequencies(cfg, "window")
    assert (a, one) == (1.2772588722239782, 1.0) and w.shape == (64,)
    assert (w[:19] == w0[:19]).all() and (w[35:] == w0[35:] / 16).all()


def test_traffic_draws_documents_one_token_longer():
    _, cfg, traffic = harness.resolve_cell(BENCH, CELL)
    assert (traffic["generator"], traffic["rows"], traffic["seq"],
            traffic["pool"], traffic["fetch_every"],
            traffic["profile_steps"]) == ("train_window_steps", 1, 8192, 16,
                                          20, 10)
    # eight windows long; 1024 tokens a held expert a step under uniform
    # routing: two tiles of 640 rows
    assert traffic["seq"] == 8 * cfg["sliding_window"]
    assert cfg["expert_tile"] < traffic["seq"] * cfg["num_experts_per_tok"] \
        // cfg["num_experts"] == 1024 < 2 * cfg["expert_tile"]
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


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_check_agrees_with_the_plain_reference(seed):
    result = check_cell(CELL, seed)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result) == set(harness.RESULT_KEYS)
    assert result["device"]["platform"] == "cpu"
    # a CPU run yields counts, never a time, a rate or a share
    assert result["metrics"] == {
        "compiles_in_window.train": {"value": 0, "unit": "count"}}


_REFERENCE_SIDE = {}


def _reference_side(seed):
    if seed not in _REFERENCE_SIDE:
        _, cfg, traffic = harness.resolve_cell(BENCH, CELL, tiny=True)
        ref = harness.load_reference(cfg["reference"])
        batches = gen.make_batches(dict(traffic, pool=3), cfg["vocab_size"],
                                   seed)
        w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
        want = gen.reference_readings(ref, cfg, batches, w0, ref.f32_matmul,
                                      3)
        _REFERENCE_SIDE[seed] = (ref, cfg, batches, w0, want)
    return _REFERENCE_SIDE[seed]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_fp8_is_not_correct(seed):
    """The reference with its products in fp8 (e4m3), put in the program's
    place, fails the cell's numbers under the tiny limits: through the
    comparison that decides ``correct``."""
    ref, cfg, batches, w0, want = _reference_side(seed)
    ctl = gen.control_readings(ref, cfg, batches, w0, want,
                               cfg["control_precision"])
    assert {"grad_rel_error_rms", "mixer_context_gap", "grad_norm_gap_rms",
            "update_norm_gap_rms"} <= set(
        gen.checks_failed(ctl, want, cfg["limits"]))


# what each planted fault must fail at the least (CPU, tiny sizes)
FAULT_FAILS = {
    "window_one_key_wide": {"grad_rel_error_rms", "mixer_context_gap"},
    "window_ignored_in_one_layer": {"grad_rel_error_rms",
                                    "mixer_context_gap"},
    "full_plain_rotation": {"grad_rel_error_rms", "mixer_context_gap"},
    "factor_on_query_alone": {"grad_norm_gap_rms", "mixer_context_gap"},
    "ramp_ends_swapped": {"grad_rel_error_rms", "mixer_context_gap"},
    "kv_head_by_remainder": {"grad_rel_error_rms", "mixer_context_gap",
                             "grad_norm_gap_rms"},
    "weights_not_renormalised": {"grad_rel_error_rms", "grad_norm_gap",
                                 "grad_norm_gap_rms"},
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
    gaps = gen.gaps(got, want)
    if fault == "state_unchanged":              # the first gradient is sound
        assert not {"grad_rel_error_rms", "grad_norm_gap",
                    "routed_pairs_gap"} & failed
        assert gaps["update_norm_gap"] == pytest.approx(1.0)
    if fault in ("full_plain_rotation", "factor_on_query_alone",
                 "ramp_ends_swapped"):
        # the full layer's own: the first window layer's ctx moves only
        # with the weights, after the first update
        assert gaps["context_gaps"][0] < 1e-3 < gaps["context_gaps"][1]
    # the sound reference, in the program's place, fails nothing
    if fault == "kv_head_by_remainder":
        same = gen.in_program_place(gen.reference_readings(
            ref, cfg, batches, w0, ref.f32_matmul, 3), want)
        assert gen.checks_failed(same, want, cfg["limits"]) == []


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "window_moe_decoder.py")).read()
    body = src.split('"""', 2)[2]
    assert "paddle_tpu" not in body
    imports = [line for line in body.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == [
        "import math", "import numpy as np", "import jax",
        "import jax.numpy as jnp",
        "from benchmark.reference.latent_moe_decoder import ("
        "      # noqa: F401",
        "from benchmark.reference.sparse_moe_decoder import ("
        "      # noqa: F401"]
    # every product goes through mm, whose float32 form is at `highest`
    assert "jnp.matmul" not in body and "jnp.dot" not in body \
        and "einsum" not in body and " @ " not in body
    # attention is an explicit mask and a softmax over whole rows: no kernel
    assert "jax.nn.softmax(jnp.where(counts[None], s, NEG), -1)" in body
    assert "pallas" not in body and "keys > rows - window" in body


def _facts(by_type, steps=10):
    facts = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 2.0},
             "traced_steps": steps, "mixed_attention_floor_s": 0.004,
             "window_attention_scopes": [
                 "fluid[fused_attention]fused_attention_0.tmp_0",
                 "fluid[fused_attention_grad]transpose_0.tmp_0.GRAD"]}
    _scopes._READ.clear()
    return facts, {"steps": steps, "host": None, "device": {
        "by_type": {t: {"s": s, "count": steps, "flops": 0, "bytes": 0,
                        "group": "attention"} for t, s in by_type.items()}}}


def _trace_of(ops):
    """``scopes.load``'s result for one chip's operations ``(Fluid scope or
    None, start ns, duration ns)``."""
    return {"devices": {0: {"ops": [
        ("fusion.%d" % i, start, dur,
         "jit(pt_exe_x)/%s/dot_general:" % scope if scope else "jit(x)/add:",
         "", 0, 0) for i, (scope, start, dur) in enumerate(ops)],
        "modules": []}}, "host": {}}


def test_new_readers_read_their_time(monkeypatch):
    facts, got = _facts({"fused_attention": 0.1, "fused_attention_grad": 0.3,
                         "mul": 9.0})
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    share, window = (harness.load_reader(m, ROOT).read for m in NEW_METRICS)
    assert share(facts) == pytest.approx(10.0)      # 4 ms of 40 ms a step
    # two window scopes (one a gradient op's, whose `while` spans a nested
    # operation under the same scope), a full layer's op and one under no
    # scope: 30 + 50 ms over ten steps
    monkeypatch.setattr(scopes, "find_newest", lambda: "x")
    monkeypatch.setattr(scopes, "load", lambda path: _trace_of([
        ("fluid[fused_attention]fused_attention_0.tmp_0", 0, 30e6),
        ("fluid[fused_attention_grad]transpose_0.tmp_0.GRAD", 40e6, 50e6),
        ("fluid[fused_attention_grad]transpose_0.tmp_0.GRAD", 45e6, 20e6),
        ("fluid[fused_attention]fused_attention_3.tmp_0", 100e6, 70e6),
        (None, 200e6, 5e6)]))
    assert window(facts) == pytest.approx(8.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing_where_there_is_nothing_to_read(
        monkeypatch, metric):
    """The parent's traced run of an old cell, with this PR's benchmark
    files laid over it: no such fact was made; the reader returns nothing
    and does not raise.  Nor on empty facts, nor on a run that was not
    traced, nor where none of the scopes ran."""
    read = harness.load_reader(metric, ROOT).read
    monkeypatch.setattr(scopes, "find_newest", lambda: "x")
    monkeypatch.setattr(scopes, "load", lambda path: _trace_of([
        ("fluid[fused_attention]fused_attention_9.tmp_0", 0, 30e6)]))
    # an older cell: attention ran, the generator made neither fact
    facts, got = _facts({"mul": 9.0, "fused_attention": 1.0})
    del facts["mixed_attention_floor_s"], facts["window_attention_scopes"]
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    assert read(facts) is None
    # this cell's facts over a trace in which none of its ops ran
    facts, got = _facts({"mul": 9.0})
    assert read(facts) is None
    monkeypatch.setattr(_scopes, "reading", lambda f: None)
    assert read({}) is None
    assert read({"mixed_attention_floor_s": 0.004,
                 "window_attention_scopes": ["fluid[fused_attention]x"]}) \
        is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_read_a_recorded_trace(tmp_path, monkeypatch, metric):
    """A trace recorded from another cell's program (the Transformer's tiny
    step: packed attention under other names, no window), put where a traced
    run of this cell leaves its own: the readers go through the real
    reduction; the window layers' reader finds none of its scopes and
    reports nothing, as on the parent, and finds the recorded attention ops
    when handed THEIR scopes; ``device_ms_per_step.matmul``, which the cell
    also reports, reads its products' time from the same file."""
    d = os.path.join(str(tmp_path), ".benchmark_out", "trace", CELL,
                     "plugins", "profile", "2026_01_01_00_00_00")
    os.makedirs(d)
    shutil.copy(TINY_TRACE, os.path.join(d, "vm.xplane.pb"))
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    _scopes._READ.clear()
    facts = dict(_facts({})[0], traced_steps=3, window_attention_scopes=[
        "fluid[fused_attention]l9.no_such_output.tmp_0"])
    try:
        got = _scopes.reading(dict(facts))
        assert got["steps"] == 3
        read = harness.load_reader(metric, ROOT).read
        if metric == "window_attention_ms_per_step":
            assert read(dict(facts)) is None
            ops = scopes.load(scopes.find_newest())["devices"][0]["ops"]
            recorded = sorted({"fluid[%s]%s" % scopes.fluid_scope(op[3])
                               for op in ops if "fluid[fused_attention"
                               in op[3]})
            assert recorded
            whole = got["device"]["by_type"]
            assert read(dict(facts, window_attention_scopes=recorded)) \
                == pytest.approx(sum(
                    whole[t]["s"] for t in whole
                    if t.startswith("fused_attention")) / 3 * 1e3)
        else:
            assert read(dict(facts)) > 0
        assert harness.load_reader("device_ms_per_step.matmul",
                                   ROOT).read(dict(facts)) > 0
    finally:
        _scopes._READ.clear()


def test_flops_count_the_issues_arithmetic():
    _, cfg, _ = harness.resolve_cell(BENCH, CELL)
    flops = harness.load_module("flops", cfg["flops"], ROOT)
    # ISSUE 49's bytes, reckoned before asking: 70.93 M a layer, 340.3 M
    assert flops.layer_matrix_params(cfg) == 21233664 + 147456
    assert flops.trainable_params(cfg) == 4 * (
        21233664 + 147456 + 4608 + 8 * 6193152) + 2 * 12288 * 2304 + 2304
    assert flops.trainable_params(cfg) == pytest.approx(340.3e6, rel=3e-4)
    ref = harness.load_reference(cfg["reference"])
    assert flops.trainable_params(cfg) == sum(
        math.prod(shape) for shape, _ in ref.param_spec(cfg).values())
    # the pairs that count, from the length and the window
    assert flops.window_pairs(8192, 1024) == 7864832
    assert flops.causal_pairs(8192) == 33558528
    assert flops.attention_pairs(cfg, 8192) == 57153024
    assert flops.attention_pairs(cfg, 8192) / (4 * 33558528) \
        == pytest.approx(0.4258, abs=5e-5)
    # a pair as keye's flops module counts one
    assert flops.attention_flops(cfg, 57153024) == 3 * 2 * 2 * 57153024 \
        * 32 * 128
    assert flops.attention_flops(cfg, 57153024) == pytest.approx(2.81e12,
                                                                 rel=2e-3)
    pairs = flops.expected_expert_pairs(cfg, 1, 8192)
    assert pairs == 4 * 8192 * 8 * 8 // 64 == 32768
    assert flops.expert_flops(cfg, pairs) == pytest.approx(1.2176e12,
                                                           rel=1e-3)
    total = flops.required_flops(cfg, 1, 8192, pairs)
    products = 6 * 8192 * (4 * flops.layer_matrix_params(cfg)
                           + 2304 * 12288)
    assert products == pytest.approx(4.2e12 + 1.39e12, rel=2e-3)
    assert total == products + flops.attention_flops(cfg, 57153024) \
        + flops.expert_flops(cfg, pairs)
    assert total == pytest.approx(9.63e12, rel=2e-3)
    floor, bound = flops.step_floor_seconds(cfg, 1, 8192, pairs, PEAKS)
    assert bound == "compute" and floor == pytest.approx(0.0489, rel=3e-3)
    # the attention's floor: each layer's own bound, summed — the full
    # layer's operations, and a window layer's too (its bytes are 0.27 ms)
    one = flops.attention_least_bytes(cfg, 1, 8192)
    assert one == 2 * (2 * 8192 * 4096 * 2 + 2 * 8192 * 512 * 2)
    want = (3 * flops.attention_flops(cfg, 7864832)
            + flops.attention_flops(cfg, 33558528)) / 197e12
    assert flops.mixed_attention_floor_seconds(cfg, 1, 8192, PEAKS) \
        == pytest.approx(want)
    assert want == pytest.approx(0.01426, rel=2e-3)
    floors = gen._floors(flops, cfg, 1, 8192, pairs, PEAKS)
    assert set(floors) == {"mixed_attention_floor_s",
                           "expert_matmul_floor_s"}
    assert floors["mixed_attention_floor_s"] == pytest.approx(want)
    # a window over the whole row counts every causal pair
    assert flops.window_pairs(512, 1024) == flops.causal_pairs(512)


def test_the_new_entries_needed_no_edit_of_a_file_that_was_there():
    """The harness finds the cell's files by the names in its entries: the
    configuration's three modules, the mix, the generator and the two
    readers are files of their own, and ``harness.py`` names none of
    them."""
    _, cfg, traffic = harness.resolve_cell(BENCH, CELL)
    new = {
        "configs/mellum2_12b_a2_5b.json", "traffic/train_repo_8k.json",
        "models/%s.py" % cfg["builder"], "reference/%s.py" % cfg["reference"],
        "flops/%s.py" % cfg["flops"],
        "generators/%s.py" % traffic["generator"],
        "metrics/mixed_attention_roofline.py",
        "metrics/window_attention_ms_per_step.py"}
    for rel in new:
        assert os.path.exists(os.path.join(ROOT, "benchmark", rel)), rel
    assert (cfg["builder"], cfg["reference"], cfg["flops"],
            traffic["generator"]) == (
        "window_moe_decoder", "window_moe_decoder", "mellum2_12b_a2_5b",
        "train_window_steps")
    # no other configuration or mix names the new modules, and the files
    # that were there name neither the cell nor its modules
    for c in BENCH["configs"][:-1]:
        data = json.load(open(os.path.join(ROOT, c["file"])))
        assert data["builder"] != cfg["builder"] \
            and data["reference"] != cfg["reference"]
    for name in ("harness.py", "run.py", "tools.py", "weights.py",
                 "metrics/_scopes.py", "metrics/_types.py",
                 "trace/scopes.py", "trace/reduce.py"):
        text = open(os.path.join(ROOT, "benchmark", name)).read()
        assert "mellum" not in text and "window_moe" not in text \
            and "train_window_steps" not in text, name
    for m in NEW_METRICS:
        assert harness.load_reader(m, ROOT).__file__.endswith(m + ".py")
