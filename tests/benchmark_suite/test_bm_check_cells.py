"""``--check`` at tiny sizes on the CPU: what the timed path produces
agrees with the plain reference, and the result line has the contract's
keys.  The controls — the plain reference computed in the nearest precision
below the one the configuration states — must come out as not correct."""

import jax
import jax.numpy as jnp
import pytest

from bm_util import CELLS, ROOT, SERVE_CELL, check_cell, with_serve_cell

from benchmark import harness, weights
from benchmark.generators import serve_open_loop as serve
from benchmark.generators import train_steps as train

BENCH = with_serve_cell(harness.load_benchmark(ROOT))


@pytest.mark.parametrize("workload", CELLS + (SERVE_CELL,))
def test_check_agrees_with_the_plain_reference(workload, serve_root):
    result = check_cell(workload,
                        root=serve_root if workload == SERVE_CELL else ROOT)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result) == set(harness.RESULT_KEYS)
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    # a CPU run yields counts, never a time, a rate or a share
    units = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
    assert result["metrics"]
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and units[name] == "count"
        assert m["value"] == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_in_fp8_is_not_correct(seed):
    """The reference with its products in fp8 (e4m3), put in the
    program's place, fails at least one of the cell's numbers under the
    tiny limits."""
    _, cfg, traffic = harness.resolve_cell(BENCH, CELLS[0], tiny=True)
    cfg = dict(cfg, max_len=traffic["seq"])
    ref = harness.load_reference(cfg["reference"])
    batches = train.make_batches(dict(traffic, pool=3), cfg["vocab_size"],
                                 seed)
    w = weights.make_weights(ref.encdec_param_spec(cfg), seed)
    want = train.reference_readings(ref, cfg, batches, w, ref.f32_matmul, 3)
    ctl = train.reference_readings(
        ref, cfg, batches, w, ref.lowp_matmul(cfg["control_precision"]), 3)
    ctl["grad_errors"] = train.grad_error_norms(
        {n: jnp.asarray(v) for n, v in ctl["first_grad"].items()}, 1.0,
        want["first_grad"])
    lines = []
    checks = harness.Checks(lines.append)
    train.compare(ctl, want, cfg["limits"], checks)
    assert not checks.ok()
    failed = {r[0] for r in checks.rows if not r[3]}
    assert "grad_rel_error_rms" in failed
    assert all("limit" in line for line in lines)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_in_lower_precision_is_not_correct(seed):
    """At each position of the same prompts and tokens, the token the
    lower precision puts first lies below the reference's best: over a
    few hundred positions the widest such gap passes the tiny limit, while
    the reference's own first choice has no gap at all."""
    _, cfg, _ = harness.resolve_cell(BENCH, SERVE_CELL, tiny=True)
    ref = harness.load_reference(cfg["reference"])
    w = weights.make_weights(ref.declm_param_spec(cfg), seed)
    worst = {"own": 0.0, "control": 0.0}
    for i in range(6):
        seq = jax.random.randint(weights.seed_key(seed, 9 + i), (60,), 2,
                                 cfg["vocab_size"]).tolist()
        for name, mm in (("own", ref.f32_matmul),
                         ("control", ref.lowp_matmul("fp8"))):
            gap, n = serve.served_logit_gap(ref, cfg, w, seq[:5], seq[5:],
                                            mm)
            assert n == 55 and gap >= 0
            worst[name] = max(worst[name], gap)
    assert worst["own"] == 0.0
    assert worst["control"] > cfg["limits"]["served_logit_gap"]
