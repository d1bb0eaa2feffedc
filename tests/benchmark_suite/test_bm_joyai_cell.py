"""The cell ``joyai_llm_flash.train_mtp_8k`` on the CPU: ``--check`` at its
tiny sizes agrees with the plain reference, the control in fp8 does not,
the configuration's file keeps to the catalog's sizes, the new reader reads
what a traced run hands it (and nothing from a run without the new facts),
and the flops module counts what the issue's arithmetic counts."""

import json
import os

import pytest

from bm_util import CELLS, ROOT, check_cell

from benchmark import harness
from benchmark.generators import train_mtp_steps as gen

BENCH = harness.load_benchmark(ROOT)
KEYE = "keye_vl2_30b_a3b.train_longdoc_8k"
CELL = "joyai_llm_flash.train_mtp_8k"
NEW_METRIC = "latent_attention_roofline"
REDUCED = ["num_hidden_layers", "n_routed_experts_held", "vocab_size"]
# the catalog's `config` of JoyAI-LLM-Flash, less the reduced keys
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128}
LIMITS = {"grad_rel_error_rms", "loss_rel_gap", "grad_norm_gap",
          "grad_norm_gap_rms", "update_norm_gap", "update_norm_gap_rms",
          "routed_pairs_gap", "dropped_token_pairs"}


def test_benchmark_json_holds_the_four_training_cells():
    """The contract test's pin, of the cells there are now: the three that
    were there first and unchanged, then this one; one configuration, one
    cell and one per-layer metric more, each appended last."""
    assert tuple(w["name"] for w in BENCH["workloads"]) == CELLS + (KEYE,
                                                                    CELL)
    assert [c["name"] for c in BENCH["configs"]] == [
        "transformer_base", "keye_vl2_30b_a3b", "joyai_llm_flash"]
    cell = BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai_llm_flash", "train_mtp_8k", 1)
    cells = set(CELLS) | {KEYE, CELL}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    new = BENCH["per_layer"][-1]
    assert new == {"name": NEW_METRIC, "unit": "%", "better": "higher",
                   "source": "device_trace", "layer": "op kernels",
                   "moves": "train_tokens_per_s", "workloads": [CELL]}
    reports = {m["name"] for m in BENCH["per_layer"]
               if CELL in m["workloads"]}
    assert reports == {
        "dispatch_ms.train", "compiles_in_window.train",
        "train_step_roofline", "device_idle_share.train",
        "peak_hbm_gb.train", "device_ms_per_step.matmul",
        "device_ms_per_step.attention", "device_ms_per_step.loss",
        "device_ms_per_step.embedding", "device_ms_per_step.optimizer",
        "device_ms_per_step.moe", "device_unscoped_share",
        "host_self_ms_per_step.train", "host_wait_ms_per_step.train",
        "expert_matmul_roofline", "expert_load_max_over_mean.train",
        NEW_METRIC}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL               # appended last
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "train_tokens_per_s")
    assert tokens["workloads"][-1] == CELL and tokens["bound"] == 0.01


def test_configuration_keeps_every_published_size():
    conf = next(c for c in BENCH["configs"] if c["name"] == "joyai_llm_flash")
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert len(conf["why"]) <= 200
    data = json.load(open(os.path.join(ROOT, conf["file"])))
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    for key, value in PUBLISHED.items():
        assert data[key] == value, key
    assert conf["reduced"] == data["reduced"] == REDUCED
    assert set(data["reduced_why"]) == set(REDUCED)
    assert (data["num_hidden_layers"], data["n_routed_experts_held"],
            data["vocab_size"]) == (5, 8, 16160)
    assert data["published"] == {"num_hidden_layers": 40,
                                 "n_routed_experts_held": 256,
                                 "vocab_size": 129280}
    assert data["vocab_size"] * 8 == data["published"]["vocab_size"]
    assert data["n_routed_experts_held"] * 32 == data["n_routed_experts"]
    assert set(data["limits"]) == set(data["tiny"]["limits"]) == LIMITS
    assert data["limits"]["dropped_token_pairs"] == 0
    for key in ("source_part", "deployment", "assumed", "precision_stated",
                "control_precision", "limits_why"):
        assert data[key]
    for kind, key in (("models", "builder"), ("reference", "reference"),
                      ("flops", "flops")):
        harness.load_module(kind, data[key], ROOT)
    # no width is cut, at the real sizes (the tiny sizes are the CPU's)
    for key in data["tiny"]:
        if key.endswith(("_dim", "_rank", "_size")) and key != "vocab_size":
            assert key in PUBLISHED and data[key] == PUBLISHED[key]


def test_traffic_draws_documents_two_tokens_longer():
    _, cfg, traffic = harness.resolve_cell(BENCH, CELL)
    assert (traffic["generator"], traffic["rows"], traffic["seq"],
            traffic["pool"], traffic["fetch_every"],
            traffic["profile_steps"]) == ("train_mtp_steps", 1, 8192, 16, 20,
                                          10)
    small = dict(traffic, rows=2, seq=16, pool=3)
    a = gen.make_batches(small, 97, 2 ** 31 + 17)
    b = gen.make_batches(small, 97, 2 ** 31 + 17)
    assert len(a) == 3 and set(a[0]) == {"tok", "lbl", "lbl2"}
    for x, y in zip(a, b):
        for n in x:
            assert (x[n] == y[n]).all() and x[n].shape == (2, 16)
        # the next token and the next but one of the SAME document: no
        # wrapped label at the row's end
        assert (x["lbl"][:, :-1] == x["tok"][:, 1:]).all()
        assert (x["lbl2"][:, :-1] == x["lbl"][:, 1:]).all()
        assert 0 <= x["lbl2"].min() and x["lbl2"].max() < 97
    assert (a[0]["tok"] != gen.make_batches(small, 97, 5)[0]["tok"]).any()


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
    _, cfg, traffic = harness.resolve_cell(BENCH, CELL, tiny=True)
    ref = harness.load_reference(cfg["reference"])
    batches = gen.make_batches(dict(traffic, pool=3), cfg["vocab_size"], seed)
    w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
    want = gen.reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
    ctl = gen.control_readings(ref, cfg, batches, w0, want,
                               cfg["control_precision"])
    checks = harness.Checks(lambda line: None)
    gen.compare(ctl, want, cfg["limits"], checks)
    gen.compare_module(ctl, want, cfg["limits"], checks)
    assert not checks.ok()
    failed = {r[0] for r in checks.rows if not r[3]}
    assert {"grad_rel_error_rms", "update_norm_gap_rms"} <= failed


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "latent_moe_decoder.py")).read()
    body = src.split('"""', 2)[2]
    assert "paddle_tpu" not in body
    imports = [line for line in body.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import math", "import jax", "import jax.numpy as jnp",
                       "from benchmark.reference.sparse_moe_decoder import ("
                       "      # noqa: F401"]
    # every product goes through mm, whose float32 form is at `highest`
    assert "jnp.matmul" not in body and "jnp.dot" not in body \
        and "einsum" not in body and " @ " not in body


def _facts(by_type, steps=10):
    from benchmark.metrics import _scopes

    facts = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 2.0},
             "traced_steps": steps, "latent_attention_floor_s": 0.06}
    _scopes._READ.clear()
    return facts, {"steps": steps, "host": None, "device": {
        "by_type": {t: {"s": s, "count": steps, "flops": 0, "bytes": 0,
                        "group": "attention"} for t, s in by_type.items()}}}


def test_new_reader_takes_the_floor_over_the_attention_ops_time(monkeypatch):
    from benchmark.metrics import _scopes

    facts, got = _facts({"fused_attention": 0.5, "fused_attention_grad": 1.5,
                         "mul": 9.0})
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    read = harness.load_reader(NEW_METRIC, ROOT).read
    assert read(facts) == pytest.approx(30.0)     # 60 ms of 200 ms a step


def test_new_reader_finds_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """The parent's traced run of an old cell, with this PR's benchmark
    files laid over it: no such fact was made; the reader returns nothing
    and does not raise.  Nor on empty facts, nor on a run that was not
    traced."""
    from benchmark.metrics import _scopes

    read = harness.load_reader(NEW_METRIC, ROOT).read
    facts, got = _facts({"mul": 9.0, "fused_attention": 0.5})
    del facts["latent_attention_floor_s"]
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    assert read(facts) is None
    facts, got = _facts({"mul": 9.0})                # no attention op ran
    assert read(facts) is None
    monkeypatch.setattr(_scopes, "reading", lambda f: None)
    assert read({}) is None
    assert read({"latent_attention_floor_s": 0.06}) is None


def test_flops_count_all_causal_pairs_at_192_over_128():
    _, cfg, _ = harness.resolve_cell(BENCH, CELL)
    flops = harness.load_module("flops", cfg["flops"], ROOT)
    pairs = 8192 * 8193 // 2
    assert flops.causal_pairs(8192) == pairs
    assert flops.blocks(cfg) == (1, 5)
    # QK over 192 and PV over 128, forward and twice that backward
    assert flops.attention_flops(cfg, 1, 8192) == 3 * 2 * pairs * 32 * 320
    assert 6 * flops.attention_flops(cfg, 1, 8192) == pytest.approx(
        12.37e12, rel=1e-3)
    assert flops.attention_params(cfg) == pytest.approx(26.35e6, rel=1e-3)
    assert flops.expert_flops(cfg, 1) == 3 * 2 * 3 * 2048 * 768
    assert flops.expected_expert_pairs(cfg, 1, 8192) == 5 * 8 * 256
    assert flops.trainable_params(cfg) == pytest.approx(491.7e6, rel=1e-3)
    assert flops.frozen_params(cfg) == 5 * 256
    ref = harness.load_reference(cfg["reference"])
    import math
    assert flops.trainable_params(cfg) == sum(
        math.prod(shape) for name, (shape, _) in ref.param_spec(cfg).items()
        if not ref.frozen(name))
    total = flops.required_flops(cfg, 1, 8192, 5 * 256 * 8)
    assert total == pytest.approx(27.55e12, rel=2e-3)
    # the attention kernels are compute-bound by far: the floor is FLOPs
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.kernel_floor_seconds(
        flops.attention_flops(cfg, 1, 8192),
        flops.attention_least_bytes(cfg, 1, 8192), peaks) == pytest.approx(
        flops.attention_flops(cfg, 1, 8192) / 197e12)
