"""The cell ``ouro_2_6b.train_loop_4k`` on the CPU: ``--check`` at its tiny
sizes agrees with the plain reference, the control in fp8 does not, the
configuration's file keeps to the catalog's sizes, the new readers read
what a traced run hands them (and nothing from a run without the new
facts), and the flops module counts what the issue's arithmetic counts."""

import json
import math
import os

import pytest

from bm_util import CELLS, ROOT, check_cell

from benchmark import harness
from benchmark.generators import train_loop_steps as gen

BENCH = harness.load_benchmark(ROOT)
OLDER = ("keye_vl2_30b_a3b.train_longdoc_8k", "joyai_llm_flash.train_mtp_8k")
CELL = "ouro_2_6b.train_loop_4k"
NEW_METRICS = ("plain_attention_roofline", "device_ms_per_step.exit_gate",
               "device_ms_per_step.rms_norm")
REDUCED = ["num_hidden_layers", "vocab_size"]
# the catalog's `config` of Ouro-2.6B, less the reduced keys
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False}
LIMITS = {"grad_rel_error_rms", "loss_rel_gap", "exit_mass_gap",
          "grad_norm_gap", "grad_norm_gap_rms", "update_norm_gap",
          "update_norm_gap_rms"}


def test_benchmark_json_holds_the_five_training_cells():
    """What ``test_bm_joyai_cell.py``'s pin meant, of the cells there are
    now: the four that were there first and unchanged, then this one; one
    configuration, one cell and three per-layer metrics more, each appended
    last; at most one cell of five on four chips."""
    assert tuple(w["name"] for w in BENCH["workloads"]) == CELLS + OLDER + (
        CELL,)
    assert [c["name"] for c in BENCH["configs"]] == [
        "transformer_base", "keye_vl2_30b_a3b", "joyai_llm_flash",
        "ouro_2_6b"]
    cell = BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro_2_6b", "train_loop_4k", 1)
    assert len(cell["why"]) <= 200
    assert [w["chips"] for w in BENCH["workloads"]].count(4) == 1
    cells = set(CELLS) | set(OLDER) | {CELL}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    assert tuple(m["name"] for m in BENCH["per_layer"][-3:]) == NEW_METRICS
    for m, (unit, better) in zip(BENCH["per_layer"][-3:], (
            ("%", "higher"), ("ms", "lower"), ("ms", "lower"))):
        assert m == {"name": m["name"], "unit": unit, "better": better,
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
        "device_ms_per_step.elementwise", "device_unscoped_share",
        "host_self_ms_per_step.train",
        "host_wait_ms_per_step.train"} | set(NEW_METRICS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL               # appended last
    # JoyAI's cell is still where it was: last but for this one
    joyai = [m for m in BENCH["per_layer"] if OLDER[1] in m["workloads"]]
    assert len(joyai) == 17
    for m in joyai:
        rest = [w for w in m["workloads"] if w != CELL]
        assert rest[-1] == OLDER[1]
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "train_tokens_per_s")
    assert tokens["workloads"][-1] == CELL and tokens["bound"] == 0.01
    assert BENCH["run_seconds"] == 51


def test_configuration_keeps_every_published_size():
    """What ``test_bm_contract.py::test_configuration_entry_and_file``
    holds a configuration to, with ``num_hidden_layers`` read as the depth
    it is."""
    conf = next(c for c in BENCH["configs"] if c["name"] == "ouro_2_6b")
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert len(conf["why"]) <= 200
    data = json.load(open(os.path.join(ROOT, conf["file"])))
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    for key, value in PUBLISHED.items():
        assert data[key] == value, key
    assert conf["reduced"] == data["reduced"] == REDUCED
    assert set(data["reduced_why"]) == set(REDUCED)
    assert (data["num_hidden_layers"], data["vocab_size"]) == (4, 6144)
    assert data["published"] == {"num_hidden_layers": 48,
                                 "vocab_size": 49152}
    assert data["vocab_size"] * 8 == data["published"]["vocab_size"]
    assert data["num_hidden_layers"] * 12 \
        == data["published"]["num_hidden_layers"]
    assert set(data["limits"]) == set(data["tiny"]["limits"]) == LIMITS
    assert set(data["limits_why"]) == LIMITS | {"readings"}
    assert set(data["assumed"]) == {"block", "loop", "gate", "loss",
                                    "optimizer", "weights", "job"}
    assert data["exit_beta"] == 0.05 and data["control_precision"] == "fp8"
    for key in ("source_part", "deployment", "precision_stated"):
        assert data[key]
    for kind, key in (("models", "builder"), ("reference", "reference"),
                      ("flops", "flops")):
        harness.load_module(kind, data[key], ROOT)
    # no width, head count or pass count is cut, at the real sizes (the
    # tiny sizes are the CPU's)
    tiny = data["tiny"]
    assert (tiny["hidden_size"], tiny["num_hidden_layers"],
            tiny["total_ut_steps"], tiny["num_attention_heads"],
            tiny["head_dim"], tiny["vocab_size"]) == (32, 2, 3, 4, 8, 97)
    for key in tiny:
        if key not in ("vocab_size", "num_hidden_layers", "limits",
                       "limits_why", "init_scale", "reference_block_rows"):
            assert data[key] == PUBLISHED[key], key
    # embedding rows N(0, 16): 4 sqrt(hidden) on weights.py's draw
    assert data["init_scale"]["tok_emb"] == pytest.approx(4 * 2048 ** 0.5)
    assert tiny["init_scale"]["tok_emb"] == pytest.approx(4 * 32 ** 0.5)


def test_traffic_draws_documents_one_token_longer():
    _, cfg, traffic = harness.resolve_cell(BENCH, CELL)
    assert (traffic["generator"], traffic["rows"], traffic["seq"],
            traffic["pool"], traffic["fetch_every"],
            traffic["profile_steps"]) == ("train_loop_steps", 1, 4096, 16,
                                          20, 10)
    small = dict(traffic, rows=2, seq=16, pool=3)
    a = gen.make_batches(small, 97, 2 ** 31 + 17)
    b = gen.make_batches(small, 97, 2 ** 31 + 17)
    assert len(a) == 3 and set(a[0]) == {"tok", "lbl"}
    for x, y in zip(a, b):
        for n in x:
            assert (x[n] == y[n]).all() and x[n].shape == (2, 16)
        # the next token of the SAME document: no wrapped label
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
    assert {"grad_rel_error_rms", "grad_norm_gap_rms", "update_norm_gap_rms",
            "exit_mass_gap"} <= set(gen.checks_failed(ctl, want,
                                                      cfg["limits"]))


# what each planted fault must fail at the least (CPU, tiny sizes)
FAULT_FAILS = {
    "weight_use_missing": {"grad_rel_error_rms", "grad_norm_gap",
                           "grad_norm_gap_rms"},
    "pass_left_out": {"exit_mass_gap", "loss_rel_gap.step1",
                      "grad_rel_error_rms", "grad_norm_gap_rms"},
    "entropy_term_dropped": {"loss_rel_gap.step1", "loss_rel_gap.step2",
                             "loss_rel_gap.step3"},
    "gate_weight_unset": {"exit_mass_gap", "grad_rel_error_rms"},
    "rotary_base_default": {"loss_rel_gap.passes", "exit_mass_gap",
                            "grad_rel_error_rms"},
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
    if fault == "state_unchanged":              # and nothing else moved
        assert failed == FAULT_FAILS[fault]
        assert gen.gaps(got, want)["update_norm_gap"] == pytest.approx(1.0)
    # the sound reference, in the program's place, fails nothing
    if fault == "weight_use_missing":
        same = gen.in_program_place(gen.reference_readings(
            ref, cfg, batches, w0, ref.f32_matmul, 3), want)
        assert gen.checks_failed(same, want, cfg["limits"]) == []


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "looped_decoder.py")).read()
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


def test_the_exit_distribution_of_the_reference_sums_to_one():
    import jax
    import jax.numpy as jnp

    ref = harness.load_reference("looped_decoder")
    lam = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (4, 64)) * 3)
    p = ref.exit_distribution(lam)
    assert p.shape == (4, 64) and bool(jnp.all(p > 0))
    assert float(jnp.max(jnp.abs(jnp.sum(p, 0) - 1.0))) < 1e-6
    assert bool(jnp.all(p[0] == lam[0]))
    assert bool(jnp.allclose(p[3], (1 - lam[0]) * (1 - lam[1])
                             * (1 - lam[2])))           # lam_4 enters nothing
    assert bool(jnp.all(ref.exit_distribution(lam[:1]) == 1.0))


def _facts(by_type, steps=10):
    from benchmark.metrics import _scopes

    facts = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 2.0},
             "traced_steps": steps, "plain_attention_floor_s": 0.06}
    _scopes._READ.clear()
    return facts, {"steps": steps, "host": None, "device": {
        "by_type": {t: {"s": s, "count": steps, "flops": 0, "bytes": 0,
                        "group": "attention"} for t, s in by_type.items()}}}


def test_new_readers_read_their_types_time(monkeypatch):
    from benchmark.metrics import _scopes

    facts, got = _facts({"fused_attention": 0.5, "fused_attention_grad": 1.5,
                         "exit_gate_loss": 0.01, "exit_gate_loss_grad": 0.03,
                         "rms_norm": 0.02, "rms_norm_grad": 0.03, "mul": 9.0})
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    roofline, gate, norms = (harness.load_reader(m, ROOT).read
                             for m in NEW_METRICS)
    assert roofline(facts) == pytest.approx(30.0)  # 60 ms of 200 ms a step
    assert gate(facts) == pytest.approx(4.0)       # 40 ms over 10 steps
    assert norms(facts) == pytest.approx(5.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing_where_there_is_nothing_to_read(
        monkeypatch, metric):
    """The parent's traced run of an old cell, with this PR's benchmark
    files laid over it: no such fact or op type was made; the reader
    returns nothing and does not raise.  Nor on empty facts, nor on a run
    that was not traced."""
    from benchmark.metrics import _scopes

    read = harness.load_reader(metric, ROOT).read
    facts, got = _facts({"mul": 9.0})                # none of the types ran
    del facts["plain_attention_floor_s"]
    monkeypatch.setattr(_scopes, "reading", lambda f: got)
    assert read(facts) is None
    monkeypatch.setattr(_scopes, "reading", lambda f: None)
    assert read({}) is None
    assert read({"plain_attention_floor_s": 0.06}) is None


def test_flops_count_sixteen_applications_and_four_heads():
    _, cfg, _ = harness.resolve_cell(BENCH, CELL)
    flops = harness.load_module("flops", cfg["flops"], ROOT)
    pairs = 4096 * 4097 // 2
    assert flops.causal_pairs(4096) == pairs
    assert (flops.passes(cfg), flops.applications(cfg)) == (4, 16)
    assert flops.block_params(cfg) == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert flops.block_params(cfg) == pytest.approx(51.4e6, rel=1e-3)
    # QK and PV over 128, forward and twice that backward
    assert flops.attention_flops(cfg, 1, 4096) == 3 * 2 * pairs * 16 * 256
    assert 16 * flops.attention_flops(cfg, 1, 4096) == pytest.approx(
        3.3e12, rel=1e-2)
    assert flops.trainable_params(cfg) == pytest.approx(230.7e6, rel=1e-3)
    ref = harness.load_reference(cfg["reference"])
    assert flops.trainable_params(cfg) == sum(
        math.prod(shape) for shape, _ in ref.param_spec(cfg).values())
    # 20.2 TFLOP of block products, 3.3 of attention, 1.2 of four heads
    total = flops.required_flops(cfg, 1, 4096)
    assert total == pytest.approx(24.74e12, rel=2e-3)
    assert total == pytest.approx(
        16 * 6 * 4096 * flops.block_params(cfg)
        + 16 * flops.attention_flops(cfg, 1, 4096)
        + 4 * 6 * 4096 * 2048 * 6144 + 3 * 6 * 4096 * 2048)
    assert 4 * flops.pass_flops(cfg, 1, 4096) > total   # the last gate
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    floor, bound = flops.step_floor_seconds(cfg, 1, 4096, peaks)
    assert bound == "compute" and floor == pytest.approx(0.1256, rel=1e-3)
    assert flops.least_bytes(cfg) / 819e9 < 0.01
    # the attention kernels are compute-bound by far: the floor is FLOPs
    assert flops.kernel_floor_seconds(
        flops.attention_flops(cfg, 1, 4096),
        flops.attention_least_bytes(cfg, 1, 4096), peaks) == pytest.approx(
        flops.attention_flops(cfg, 1, 4096) / 197e12)
