"""The cell ``kimi_linear_48b_a3b.train_doc_4k`` on the CPU: ``--check`` at its
tiny sizes agrees with the plain reference, the control in fp8 and every
planted fault do not, the configuration's file keeps to the catalog's sizes,
the reference imports nothing of the program, the two new readers read what a
traced run hands them (and nothing from a run without their op types), the
flops module counts what the issue's arithmetic counts, and ``BENCHMARK.json``
holds seven cells and six configurations."""

import json
import math
import os
import shutil

import pytest

from bm_util import CELLS, ROOT, check_cell

from benchmark import harness
from benchmark.generators import train_delta_steps as gen
from benchmark.metrics import _scopes
from benchmark.trace import scopes

BENCH = harness.load_benchmark(ROOT)
OLDER = ("keye_vl2_30b_a3b.train_longdoc_8k", "joyai_llm_flash.train_mtp_8k",
         "ouro_2_6b.train_loop_4k", "phi4_mini_flash.train_reason_4k")
CELL = "kimi_linear_48b_a3b.train_doc_4k"
NEW_METRICS = ("device_ms_per_step.delta_rule", "delta_rule_roofline")
REDUCED = ["num_hidden_layers", "num_experts_held", "vocab_size"]
# the catalog's `config` of Kimi-Linear-48B-A3B-Instruct, less the reduced keys
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128}
LIMITS = {"grad_rel_error_rms", "loss_rel_gap", "grad_norm_gap",
          "grad_norm_gap_rms", "update_norm_gap", "update_norm_gap_rms",
          "routed_pairs_gap", "delta_state_gap", "dropped_token_pairs"}
TINY_TRACE = os.path.join(ROOT, "tests", "benchmark_suite", "data",
                          "tiny_train_trace.xplane.pb")


def test_benchmark_json_holds_the_seven_cells_and_six_configurations():
    """What ``test_bm_phi4_cell.py``'s pin meant, of the entries there are
    now: the six cells, five configurations and 34 per-layer metrics that
    were there first and unchanged but for this cell's name appended to
    their lists; then one configuration, one cell and two per-layer metrics
    more, each appended last; one cell of seven on four chips."""
    cells = [w["name"] for w in BENCH["workloads"]]
    assert tuple(cells) == CELLS + OLDER + (CELL,)
    assert [c["name"] for c in BENCH["configs"]] == [
        "transformer_base", "keye_vl2_30b_a3b", "joyai_llm_flash",
        "ouro_2_6b", "phi4_mini_flash", "kimi_linear_48b_a3b"]
    assert [w["chips"] for w in BENCH["workloads"]] == [1, 4, 1, 1, 1, 1, 1]
    cell = BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi_linear_48b_a3b", "train_doc_4k", 1)
    assert len(cell["why"]) <= 200
    assert BENCH["run_seconds"] == 51
    assert [(m["name"], m["bound"]) for m in BENCH["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("setup_s", 0.1)]
    assert BENCH["end_to_end"][0]["workloads"] == cells
    assert "workloads" not in BENCH["end_to_end"][1]        # every cell's
    older, last = BENCH["per_layer"][:-2], BENCH["per_layer"][-2:]
    assert len(older) == 34
    assert tuple(m["name"] for m in older[-3:]) == (
        "device_ms_per_step.ssm", "selective_scan_roofline",
        "hybrid_attention_roofline")
    for m in older[-3:]:                        # PR 38's: its cell's alone
        assert m["workloads"] == [OLDER[3]]
    assert tuple(m["name"] for m in last) == NEW_METRICS
    for m, (unit, better) in zip(last, (("ms", "lower"), ("%", "higher"))):
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "device_trace", "layer": "op kernels",
                     "moves": "train_tokens_per_s", "workloads": [CELL]}
    # what the cell reports: the eighteen every cell reports, the experts'
    # three, the latent attention's share, the group that holds the default
    # types, the RMSNorms XLA leaves operations of their own, and its own two
    reports = {m["name"] for m in BENCH["per_layer"]
               if CELL in m["workloads"]}
    every = {m["name"] for m in older
             if set(cells[:-1]) <= set(m["workloads"])}
    assert len(every) == 18
    assert reports == every | {
        "device_ms_per_step.moe", "expert_matmul_roofline",
        "expert_load_max_over_mean.train", "latent_attention_roofline",
        "device_ms_per_step.elementwise",
        "device_ms_per_step.rms_norm"} | set(NEW_METRICS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL               # appended last
            assert m["workloads"].count(CELL) == 1
    # the hybrid cell still reports what it reported
    assert len([m for m in older if OLDER[3] in m["workloads"]]) == 23


def test_configuration_keeps_every_published_size():
    """What ``test_bm_contract.py::test_configuration_entry_and_file``
    holds a configuration to, with ``num_hidden_layers`` read as the depth
    it is."""
    conf = next(c for c in BENCH["configs"]
                if c["name"] == "kimi_linear_48b_a3b")
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert len(conf["why"]) <= 200
    assert conf["source"] == ("https://huggingface.co/moonshotai/"
                              "Kimi-Linear-48B-A3B-Instruct/blob/main/"
                              "config.json")
    data = json.load(open(os.path.join(ROOT, conf["file"])))
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    for key, value in PUBLISHED.items():
        assert data[key] == value and type(data[key]) is type(value), key
    assert conf["reduced"] == data["reduced"] == REDUCED
    assert set(data["reduced_why"]) == set(REDUCED)
    assert (data["num_hidden_layers"], data["num_experts_held"],
            data["vocab_size"]) == (5, 8, 20480)
    assert data["published"] == {"num_hidden_layers": 27,
                                 "num_experts_held": 256,
                                 "vocab_size": 163840}
    assert data["vocab_size"] * 8 == data["published"]["vocab_size"]
    assert data["first_local_expert"] == 0 and data["expert_tile"] == 256
    # the five leading published layers: three delta layers to a latent one
    ref = harness.load_reference(data["reference"], ROOT)
    assert ref.mixers(data) == ["kda", "kda", "kda", "mla", "kda"]
    flops = harness.load_module("flops", data["flops"], ROOT)
    assert flops.mixers(data) == ref.mixers(data)     # the builder reads it
    assert data["delta_rule_chunk"] == 64
    assert set(data["limits"]) == set(data["tiny"]["limits"]) == LIMITS
    assert set(data["limits_why"]) == LIMITS | {"readings"}
    assert all(0 < v < 1 for k, v in data["limits"].items()
               if k != "dropped_token_pairs")
    assert data["limits"]["dropped_token_pairs"] == 0
    assert set(data["assumed"]) == {
        "delta_attention", "delta_rule_chunk", "latent_attention", "router",
        "bias", "job", "optimizer", "weights", "expert_tile"}
    assert data["control_precision"] == "fp8"
    assert data["precision"] == "bf16_amp" \
        and "float32" in data["precision_stated"]
    for key in ("source_part", "deployment", "precision_stated"):
        assert data[key]
    for kind, key in (("models", "builder"), ("reference", "reference"),
                      ("flops", "flops")):
        harness.load_module(kind, data[key], ROOT)
    # no width is cut at the real sizes; the tiny sizes are the CPU's: three
    # layers (KDA + dense, KDA + experts, latent + experts), 2 of 8 experts
    # held from the third
    tiny = dict(data, **data["tiny"])
    assert ref.mixers(tiny) == ["kda", "kda", "mla"]
    assert (tiny["num_experts"], tiny["num_experts_held"],
            tiny["first_local_expert"]) == (8, 2, 2)
    for key in data["tiny"]:
        assert key in data, key


def test_traffic_draws_documents_one_token_longer():
    _, cfg, traffic = harness.resolve_cell(BENCH, CELL)
    assert (traffic["generator"], traffic["rows"], traffic["seq"],
            traffic["pool"], traffic["fetch_every"],
            traffic["profile_steps"]) == ("train_delta_steps", 1, 4096, 16,
                                          20, 10)
    assert traffic["seq"] % cfg["delta_rule_chunk"] == 0
    # 128 tokens a held expert a step under uniform routing, ~170 the
    # fullest the seeded routers fill: one tile each
    assert traffic["seq"] * cfg["num_experts_per_token"] \
        // cfg["num_experts"] == cfg["expert_tile"] // 2
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
    assert (w["l0.kda.q"] != gen.seeded_weights(spec, cfg, 6)[
        "l0.kda.q"]).any()
    rate = np.exp(w["l0.kda.A_log"])
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    step = np.log1p(np.exp(w["l0.kda.dt_bias"]))          # softplus
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    taps = w["l0.kda.k_conv"]
    assert taps.shape[0] == 4 and np.abs(taps).max() <= 0.5
    assert (w["l0.kda.o_g"] == 1).all() and not w["l1.moe.bias"].any()
    # embedding rows N(0, 16): the row scale of the configuration's file
    assert np.std(w["tok_emb"]) == pytest.approx(4.0, rel=0.1)


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
    assert {"grad_rel_error_rms", "delta_state_gap", "grad_norm_gap_rms",
            "update_norm_gap_rms"} <= set(
        gen.checks_failed(ctl, want, cfg["limits"]))


# what each planted fault must fail at the least (CPU, tiny sizes)
FAULT_FAILS = {
    "decay_one_a_head": {"grad_rel_error_rms", "delta_state_gap",
                         "grad_norm_gap_rms"},
    "delta_term_dropped": {"grad_rel_error_rms", "delta_state_gap",
                           "grad_norm_gap_rms"},
    "beta_one": {"grad_rel_error_rms", "delta_state_gap", "grad_norm_gap",
                 "update_norm_gap"},
    "keys_unnormalised": {"grad_rel_error_rms", "delta_state_gap",
                          "grad_norm_gap_rms"},
    "latent_keys_rotated": {"grad_rel_error_rms", "grad_norm_gap_rms"},
    "conv_tap_ahead": {"grad_rel_error_rms", "delta_state_gap",
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
    if fault == "state_unchanged":              # the first gradient is sound
        assert not {"grad_rel_error_rms", "grad_norm_gap",
                    "routed_pairs_gap"} & failed
        assert gen.gaps(got, want)["update_norm_gap"] == pytest.approx(1.0)
    if fault == "latent_keys_rotated":          # the delta layers are sound
        assert "delta_state_gap" not in failed
    # the sound reference, in the program's place, fails nothing
    if fault == "beta_one":
        same = gen.in_program_place(gen.reference_readings(
            ref, cfg, batches, w0, ref.f32_matmul, 3), want)
        assert gen.checks_failed(same, want, cfg["limits"]) == []


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "linear_latent_decoder.py")).read()
    body = src.split('"""', 2)[2]
    assert "paddle_tpu" not in body
    imports = [line for line in body.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == [
        "import jax", "import jax.numpy as jnp",
        "from benchmark.reference.latent_moe_decoder import ("
        "      # noqa: F401",
        "from benchmark.reference.sparse_moe_decoder import ("
        "      # noqa: F401"]
    # every product goes through mm, whose float32 form is at `highest`
    assert "jnp.matmul" not in body and "jnp.dot" not in body \
        and "einsum" not in body and " @ " not in body
    # the rule is a scan over the steps: no chunk algebra, no kernel
    assert "lax.scan(step" in body and "pallas" not in body
    assert "cumsum" not in body and "solve" not in body and "inv" not in body


def _facts(by_type, steps=10):
    facts = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 2.0},
             "traced_steps": steps, "delta_rule_floor_s": 0.002}
    _scopes._READ.clear()
    return facts, {"steps": steps, "host": None, "device": {
        "by_type": {t: {"s": s, "count": steps, "flops": 0, "bytes": 0,
                        "group": "elementwise"} for t, s in by_type.items()}}}


def test_new_readers_read_their_types_time(monkeypatch):
    facts, got = _facts({
        "gated_delta_rule": 0.1, "gated_delta_rule_grad": 0.3,
        "causal_conv1d": 0.002, "causal_conv1d_grad": 0.098, "mul": 9.0})
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    rule, share = (harness.load_reader(m, ROOT).read for m in NEW_METRICS)
    assert rule(facts) == pytest.approx(50.0)       # 500 ms over 10 steps
    assert share(facts) == pytest.approx(5.0)       # 2 ms of 40 ms a step


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing_where_there_is_nothing_to_read(
        monkeypatch, metric):
    """The parent's traced run of an old cell, with this PR's benchmark
    files laid over it: no such fact or op type was made; the reader
    returns nothing and does not raise.  Nor on empty facts, nor on a run
    that was not traced."""
    read = harness.load_reader(metric, ROOT).read
    # an older cell: attention ran, no delta rule and no convolution
    facts, got = _facts({"mul": 9.0, "fused_attention": 1.0})
    del facts["delta_rule_floor_s"]
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    assert read(facts) is None
    # this cell's facts over a trace in which none of the types ran
    facts, got = _facts({"mul": 9.0})
    assert read(facts) is None
    monkeypatch.setattr(_scopes, "reading", lambda f: None)
    assert read({}) is None
    assert read({"delta_rule_floor_s": 0.002}) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_read_a_recorded_trace(tmp_path, monkeypatch, metric):
    """A trace recorded from another cell's program (the Transformer's tiny
    step: no delta rule, no convolution), put where a traced run of this
    cell leaves its own: the readers go through the real reduction and
    report nothing, as on the parent; ``device_ms_per_step.matmul``, which
    the cell also reports, reads its products' time from the same file."""
    d = os.path.join(str(tmp_path), ".benchmark_out", "trace", CELL,
                     "plugins", "profile", "2026_01_01_00_00_00")
    os.makedirs(d)
    shutil.copy(TINY_TRACE, os.path.join(d, "vm.xplane.pb"))
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    _scopes._READ.clear()
    facts = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 1.0},
             "traced_steps": 3, "delta_rule_floor_s": 0.002}
    try:
        got = _scopes.reading(dict(facts))
        assert got["steps"] == 3
        assert "gated_delta_rule" not in got["device"]["by_type"]
        assert harness.load_reader(metric, ROOT).read(dict(facts)) is None
        assert harness.load_reader("device_ms_per_step.matmul",
                                   ROOT).read(dict(facts)) > 0
    finally:
        _scopes._READ.clear()


def test_flops_count_the_issues_arithmetic():
    _, cfg, _ = harness.resolve_cell(BENCH, CELL)
    flops = harness.load_module("flops", cfg["flops"], ROOT)
    assert flops.mixers(cfg).count("kda") == 4
    assert flops.blocks(cfg) == (1, 4)
    # the mixers, as ISSUE 46 counts them (M parameters)
    assert (flops.delta_matrix_params(cfg) + flops.delta_vector_params(cfg)
            ) / 1e6 == pytest.approx(39.5, abs=0.06)
    assert flops.latent_matrix_params(cfg) / 1e6 == pytest.approx(29.1,
                                                                  abs=0.06)
    assert flops.trainable_params(cfg) == pytest.approx(602.4e6, rel=2e-4)
    ref = harness.load_reference(cfg["reference"])
    spec = ref.param_spec(cfg)
    assert flops.trainable_params(cfg) + flops.frozen_params(cfg) == sum(
        math.prod(shape) for shape, _ in spec.values())
    assert flops.frozen_params(cfg) == 4 * 256
    # 335.8 M multiply-adds a token under uniform routing: 8.25 TFLOP
    pairs = flops.expected_expert_pairs(cfg, 1, 4096)
    assert pairs == 4 * 4096 * 8 * 8 // 256 == 4096
    # (the issue's 335.8 M counts each delta mixer's 39.5 M whole, its
    # 53,000 taps, decays and gains with its matrices)
    per_token = flops.per_token_params(cfg) + pairs * 3 * 2304 * 1024 / 4096
    assert per_token / 1e6 == pytest.approx(335.8, abs=0.25)
    products = 6 * 4096 * flops.per_token_params(cfg) \
        + flops.expert_flops(cfg, pairs)
    assert products == pytest.approx(8.25e12, rel=1e-3)
    # all causal pairs of ONE latent block, keys 192 / values 128
    assert flops.causal_pairs(4096) == 8390656
    assert flops.attention_flops(cfg, 1, 4096) == 3 * 2 * 8390656 * 32 * 320
    # the rule's chunk products at C = 64: ~0.3 TFLOP over the four layers
    assert flops.rule_flops(cfg, 1, 4096) == 3 * 2 * 64 * 32 * (
        64 * 64 * 5 * 128 + 3 * 64 * 128 * 128)
    assert 4 * flops.rule_flops(cfg, 1, 4096) == pytest.approx(0.283e12,
                                                               rel=5e-3)
    total = flops.required_flops(cfg, 1, 4096, pairs)
    assert total == products + flops.attention_flops(cfg, 1, 4096) \
        + 4 * flops.rule_flops(cfg, 1, 4096)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    floor, bound = flops.step_floor_seconds(cfg, 1, 4096, pairs, peaks)
    assert bound == "compute" and floor == pytest.approx(0.0459, rel=5e-3)
    # the rule's floor is its bytes, each array once in the dtype the op is
    # handed it (0.47 GB a layer: q, k, v, the two gates' pre-activations and
    # their five gradients [4096, 4096] bf16, out and its gradient float32,
    # beta's two), the attention's its operations
    rule_bytes = flops.rule_least_bytes(cfg, 1, 4096)
    assert rule_bytes == 4096 * 4096 * (10 * 2 + 2 * 4) + 2 * 4096 * 32 * 4
    assert flops.rule_least_bytes(cfg, 1, 4096, itemsize=4) \
        == (12 * 4096 * 4096 + 2 * 4096 * 32) * 4
    assert flops.kernel_floor_seconds(
        flops.rule_flops(cfg, 1, 4096), rule_bytes, peaks) \
        == pytest.approx(rule_bytes / 819e9)
    assert flops.kernel_floor_seconds(
        flops.attention_flops(cfg, 1, 4096),
        flops.attention_least_bytes(cfg, 1, 4096), peaks) == pytest.approx(
        flops.attention_flops(cfg, 1, 4096) / 197e12)
    floors = gen._floors(flops, cfg, 1, 4096, pairs, peaks)
    assert floors["delta_rule_floor_s"] == pytest.approx(
        4 * rule_bytes / 819e9)
    assert set(floors) == {"delta_rule_floor_s", "latent_attention_floor_s",
                           "expert_matmul_floor_s"}
