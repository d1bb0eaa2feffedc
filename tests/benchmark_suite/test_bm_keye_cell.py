"""The cell ``keye_vl2_30b_a3b.train_longdoc_8k`` on the CPU: ``--check``
at its tiny sizes agrees with the plain reference, the control in fp8 does
not, the configuration's file keeps to the catalog's sizes, and the new
readers read what a traced run hands them (and nothing from a run without
the new ops)."""

import json
import os

import jax.numpy as jnp
import pytest

from bm_util import CELLS, ROOT, check_cell

from benchmark import harness
from benchmark.generators import train_lm_steps as gen

BENCH = harness.load_benchmark(ROOT)
CELL = "keye_vl2_30b_a3b.train_longdoc_8k"
NEW_METRICS = ("device_ms_per_step.sparse_select", "device_ms_per_step.moe",
               "sparse_attention_roofline", "expert_matmul_roofline",
               "expert_load_max_over_mean.train")
# the language model's published settings (the catalog's `config` of
# Keye-VL-2.0-30B-A3B); the three reduced keys are checked apart
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False}


def test_benchmark_json_holds_the_training_cells():
    """test_bm_contract's pin, of the cells there are now: the two of
    ``transformer_base`` first and unchanged, then this one."""
    assert tuple(w["name"] for w in BENCH["workloads"]) == CELLS + (CELL,)
    assert [c["name"] for c in BENCH["configs"]] == [
        "transformer_base", "keye_vl2_30b_a3b"]
    cells = set(CELLS) | {CELL}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    new = [m for m in BENCH["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] for m in new)
    assert BENCH["per_layer"][-len(new):] == new           # appended last
    for name in ("device_ms_per_step.norm", "device_ms_per_step.elementwise",
                 "collective_ms_per_step"):
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in m["workloads"]


def test_configuration_keeps_every_published_size():
    conf = next(c for c in BENCH["configs"] if c["name"] == "keye_vl2_30b_a3b")
    data = json.load(open(os.path.join(ROOT, conf["file"])))
    for key, value in PUBLISHED.items():
        assert data[key] == value, key
    assert conf["reduced"] == data["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (data["num_hidden_layers"], data["num_local_experts"],
            data["vocab_size"]) == (4, 16, 18992)
    assert data["published"] == {"num_hidden_layers": 48,
                                 "num_local_experts": 128,
                                 "vocab_size": 151936}
    assert data["vocab_size"] * 8 == data["published"]["vocab_size"]
    assert set(data["limits"]) == set(data["tiny"]["limits"]) == {
        "grad_rel_error_rms", "loss_rel_gap", "grad_norm_gap",
        "grad_norm_gap_rms", "update_norm_gap", "update_norm_gap_rms",
        "selected_overlap_min", "dropped_token_pairs"}
    assert data["limits"]["dropped_token_pairs"] == 0
    for key in ("deployment", "assumed", "precision_stated",
                "control_precision", "limits_why"):
        assert data[key]


def test_check_agrees_with_the_plain_reference():
    result = check_cell(CELL)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result) == set(harness.RESULT_KEYS)
    assert result["device"]["platform"] == "cpu"
    # a CPU run yields counts, never a time, a rate or a share
    assert result["metrics"] == {
        "compiles_in_window.train": {"value": 0, "unit": "count"}}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_fp8_is_not_correct(seed):
    """The reference with its products in fp8 (e4m3), put in the program's
    place, fails at least one of the cell's numbers under the tiny
    limits."""
    import jax

    from benchmark import weights

    _, cfg, traffic = harness.resolve_cell(BENCH, CELL, tiny=True)
    ref = harness.load_reference(cfg["reference"])
    batches = gen.make_batches(dict(traffic, pool=3), cfg["vocab_size"], seed)
    w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
    want = gen.reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
    ctl = gen.reference_readings(
        ref, cfg, batches, w0, ref.lowp_matmul(cfg["control_precision"]), 3)
    ctl["grad_errors"] = gen.grad_error_norms(
        {n: jnp.asarray(v) for n, v in ctl["first_grad"].items()}, 1.0,
        want["first_grad"])
    ctl["stats"] = [{"pairs_routed": 0.0, "pairs_computed": 0.0}]
    lines = []
    checks = harness.Checks(lines.append)
    gen.compare(ctl, want, cfg["limits"], checks)
    gen.compare_selection(ctl, want, cfg["limits"], checks)
    assert not checks.ok()
    assert "grad_rel_error_rms" in {r[0] for r in checks.rows if not r[3]}


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "sparse_moe_decoder.py")).read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]
    assert "Precision.HIGHEST" in src


def _facts(by_type, steps=10):
    """Facts as a traced run of the new cell hands them, with the table by
    Fluid type put where ``_scopes.reading`` caches it."""
    from benchmark.metrics import _scopes

    facts = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 2.0},
             "traced_steps": steps, "sparse_attention_floor_s": 0.01,
             "expert_matmul_floor_s": 0.002,
             "expert_load_max_over_mean": 1.25}
    _scopes._READ.clear()
    return facts, {"steps": steps, "host": None, "device": {
        "by_type": {t: {"s": s, "count": steps, "flops": 0, "bytes": 0,
                        "group": "elementwise"} for t, s in by_type.items()}}}


def test_new_readers_sum_their_types_and_their_gradients(monkeypatch):
    from benchmark.metrics import _scopes

    facts, got = _facts({
        "indexer_score": 0.2, "select_topk_keys": 0.3, "moe_router": 0.01,
        "moe_router_grad": 0.02, "moe_dispatch": 0.03, "moe_expert_ffn": 0.1,
        "moe_expert_ffn_grad": 0.3, "fused_attention": 0.5,
        "fused_attention_grad": 1.5, "mul": 9.0})
    monkeypatch.setattr(_scopes, "reading", lambda f: got)

    def read(name):
        return harness.load_reader(name, ROOT).read(facts)
    assert read("device_ms_per_step.sparse_select") == pytest.approx(50.0)
    assert read("device_ms_per_step.moe") == pytest.approx(46.0)
    assert read("sparse_attention_roofline") == pytest.approx(5.0)
    assert read("expert_matmul_roofline") == pytest.approx(5.0)
    assert read("expert_load_max_over_mean.train") == 1.25


def test_new_readers_find_nothing_in_a_program_without_the_new_ops(
        monkeypatch):
    """The parent's traced run of an old cell, with this PR's benchmark
    files laid over it: no such op type ran, no such fact was made; the
    readers return nothing and do not raise."""
    from benchmark.metrics import _scopes

    facts, got = _facts({"mul": 9.0, "fused_attention": 0.5})
    for key in ("sparse_attention_floor_s", "expert_matmul_floor_s",
                "expert_load_max_over_mean"):
        del facts[key]
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    for name in NEW_METRICS:
        assert harness.load_reader(name, ROOT).read(facts) is None
    monkeypatch.setattr(_scopes, "reading", lambda f: None)
    for name in NEW_METRICS:
        assert harness.load_reader(name, ROOT).read({}) is None


def test_flops_count_selected_pairs_and_computed_pairs_only():
    _, cfg, _ = harness.resolve_cell(BENCH, CELL)
    flops = harness.load_module("flops", cfg["flops"], ROOT)
    pairs = flops.selected_pairs(8192, 2048)
    assert pairs == 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert round(pairs / (8192 * 8193 / 2), 2) == 0.44
    # 0.24 TFLOP a layer forward (QK and PV over the selected pairs)
    assert flops.attention_flops(cfg, pairs) == 3 * 2 * 2 * pairs * 32 * 128
    assert flops.expert_flops(cfg, 1) == 3 * 2 * 3 * 2048 * 768
    assert flops.expected_expert_pairs(cfg, 1, 8192) == 4 * 8192
    assert flops.trainable_params(cfg) == pytest.approx(456.4e6, rel=2e-3)
    total = flops.required_flops(cfg, 1, 8192, 4 * 8192)
    # forward 3.56 TFLOP (ISSUE 26's sum) of which the frozen indexer's
    # 0.41 has no backward
    assert total == pytest.approx(3 * 3.15e12 + 0.42e12, rel=0.02)
