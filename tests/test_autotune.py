"""Profile-guided auto-configuration (ISSUE 9): the decision functions
as pure functions of synthetic measurements, the TunedConfig artifact,
pin semantics, probe-accounting exclusion, and the CPU-drivable tuner
loops (the batch ladder's rejection mechanism is the compiled module's
own peak-HBM estimate against a fake ``FLAGS_autotune_hbm_bytes``
ceiling — never an OOM — which is exactly what makes these tests
hardware-free)."""

import json

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import autotune, compile_cache, flags, monitor
from paddle_tpu.monitor import program_profile


@pytest.fixture(autouse=True)
def _clean_autotune_state():
    prev_pins = {n: flags.pinned(n)
                 for n in ("autotune_hbm_bytes", "autotune_dir")}
    # a test file run earlier on this worker may have left one PINNED
    # (``set_flags`` pins by default): these tests start from none, and
    # the pins found are put back after
    flags._restore_pins(dict.fromkeys(prev_pins, False))
    yield
    fluid.set_flags({"FLAGS_autotune_hbm_bytes": 0,
                     "FLAGS_autotune_dir": ""}, pin=False)
    flags._restore_pins(prev_pins)
    program_profile.reset()
    if monitor.enabled():
        monitor.disable()
        monitor.registry().reset()


# ---------------------------------------------------------------------------
# batch-size ladder (pure)
# ---------------------------------------------------------------------------

def test_batch_ladder_geometric():
    assert autotune.batch_ladder(32, 256) == [32, 64, 128, 256]
    assert autotune.batch_ladder(48, 100) == [48, 96]


def test_ladder_stops_at_projected_hbm_ceiling():
    """Once two rungs' probed peaks fit a line, an over-ceiling
    projection stops the ladder WITHOUT spending that rung's compile."""
    probed, measured = [], []

    def probe(b):
        probed.append(b)
        return 1000 * b          # affine in batch

    def measure(b):
        measured.append(b)
        return 0.001 * b ** 0.9  # s/example improves monotonically

    d = autotune.run_batch_ladder([16, 32, 64, 128], hbm_limit=70000,
                                  probe_fn=probe, measure_fn=measure,
                                  headroom=0.9)
    # 16k/32k probed fine; 64's projection (64k > 63k ceiling) stops it
    assert probed == [16, 32]
    assert measured == [16, 32]
    assert d["chosen"] == 32
    last = d["candidates"][-1]
    assert last["status"] == "rejected_projected_hbm"
    assert last["batch"] == 64
    assert last["projected_peak_hbm_bytes"] == pytest.approx(64000, rel=.01)
    # the projection rejection spent neither a compile nor a window
    assert "step_s" not in last


def test_ladder_rejects_probed_peak_before_any_dispatch():
    """A rung whose PROBED estimate exceeds the ceiling never gets a
    measurement window — rejection is the estimate, not an OOM."""
    measured = []
    # a nonlinear peak curve defeats the projection, forcing the probe
    peaks = {16: 10_000, 32: 80_000}

    d = autotune.run_batch_ladder(
        [16, 32], hbm_limit=70_000, probe_fn=lambda b: peaks[b],
        measure_fn=lambda b: measured.append(b) or 0.0001 * b,
        headroom=1.0)
    assert measured == [16]
    assert d["candidates"][-1]["status"] == "rejected_hbm"
    assert d["chosen"] == 16


def test_ladder_throughput_regression_stop():
    """The PERF.md b512-not-b1024 shape: seconds-per-example improves,
    plateaus, then regresses — the ladder stops at the regression and
    picks the best measured rung."""
    spe = {16: 10.0, 32: 6.0, 64: 4.0, 128: 4.1, 256: 6.0, 512: 9.9}
    d = autotune.run_batch_ladder(
        sorted(spe), hbm_limit=None, probe_fn=lambda b: None,
        measure_fn=lambda b: spe[b] * b, regress_tol=0.05)
    assert d["chosen"] == 64
    statuses = [c["status"] for c in d["candidates"]]
    # 128 is within tolerance of 64 (measured, kept); 256 regresses
    assert statuses == ["ok", "ok", "ok", "ok", "regressed"]
    assert d["candidates"][-1]["batch"] == 256


def test_ladder_no_limit_measures_every_rung():
    d = autotune.run_batch_ladder(
        [8, 16], hbm_limit=None, probe_fn=lambda b: 100 * b,
        measure_fn=lambda b: 0.001 * b)
    assert [c["status"] for c in d["candidates"]] == ["ok", "ok"]
    # equal seconds-per-example: the tie keeps the SMALLER batch (same
    # throughput, less memory headroom consumed)
    assert d["chosen"] == 8
    assert d["hbm_limit_bytes"] is None


# ---------------------------------------------------------------------------
# bucket bounds (pure)
# ---------------------------------------------------------------------------

def _wmt16_like_lengths():
    """The bench's realistic skewed mix: lognormal lengths clipped to
    [4, 64] (bench_transformer_realdist's distribution)."""
    rng = np.random.RandomState(7)
    return np.clip(rng.lognormal(3.2, 0.55, size=4000), 4,
                   64).astype(int).tolist()


def test_token_fill_and_4_not_6_outcome():
    """The PERF.md r4 ruling reproduced: six finer-but-ragged bounds
    have HIGHER fill than the four MXU-friendly ones, yet the chooser —
    hardware-friendly multiples first — returns the four."""
    lengths = _wmt16_like_lengths()
    friendly = [16, 32, 48, 64]
    ragged6 = [12, 20, 28, 36, 48, 64]
    assert autotune.token_fill(lengths, ragged6) > \
        autotune.token_fill(lengths, friendly)
    d = autotune.choose_bucket_bounds(lengths, k=6, multiple=16)
    assert d["chosen"] == friendly
    assert d["fill"] == pytest.approx(
        autotune.token_fill(lengths, friendly), abs=1e-3)
    # and the 4 bounds beat pad-to-max decisively (the 1.94x shape)
    assert d["fill"] > 1.5 * d["pad_to_max_fill"]


def test_choose_bucket_bounds_k_subsets():
    # mass only near 16 and 64: two bounds suffice, the chooser finds
    # the right pair out of the candidate multiples
    lengths = {14: 100, 16: 100, 60: 10, 64: 10}
    d = autotune.choose_bucket_bounds(lengths, k=2, multiple=16)
    assert d["chosen"] == [16, 64]
    # top bound always covers the max length, rounded up to a multiple
    d = autotune.choose_bucket_bounds({5: 3, 33: 1}, k=1, multiple=16)
    assert d["chosen"] == [48]


# ---------------------------------------------------------------------------
# checkpoint interval (pure)
# ---------------------------------------------------------------------------

def test_checkpoint_interval_monotone_in_save_cost():
    """The formula is monotone non-decreasing in every measured cost —
    the ISSUE's stated unit property."""
    prev = 0
    for save_s in (0.01, 0.1, 0.5, 2.0, 5.0):
        d = autotune.decide_checkpoint_interval(
            step_s=0.1, snapshot_s=0.01, save_s=save_s, budget=0.035)
        assert d["chosen"] >= prev
        prev = d["chosen"]
    prev = 0
    for snap_s in (0.001, 0.01, 0.05, 0.2):
        d = autotune.decide_checkpoint_interval(
            step_s=0.1, snapshot_s=snap_s, save_s=0.0, budget=0.035)
        assert d["chosen"] >= prev
        assert d["overhead_frac"] <= 0.035 + 1e-9
        prev = d["chosen"]


def test_checkpoint_interval_drain_and_sync_modes():
    # async: the on-step cost is the snapshot only, but the write must
    # drain inside the interval
    d = autotune.decide_checkpoint_interval(
        step_s=0.1, snapshot_s=0.001, save_s=2.0, budget=0.035)
    assert d["chosen"] == 20 and d["drain_bound_steps"] == 20
    # sync: the whole write lands on the step path
    d_sync = autotune.decide_checkpoint_interval(
        step_s=0.1, snapshot_s=0.001, save_s=2.0, budget=0.035,
        async_save=False)
    assert d_sync["chosen"] > 500
    assert d_sync["overhead_frac"] <= 0.035 + 1e-9
    with pytest.raises(ValueError):
        autotune.decide_checkpoint_interval(0.0, 0.01, 0.01)


# ---------------------------------------------------------------------------
# TunedConfig artifact + pinning
# ---------------------------------------------------------------------------

def test_tuned_config_round_trip(tmp_path):
    cfg = autotune.TunedConfig(meta={"model": "t"})
    cfg.add({"knob": "batch_size", "chosen": 512,
             "candidates": [{"batch": 512, "status": "ok"}]},
            fingerprint="abcdef012345")
    cfg.add(autotune.decide_checkpoint_interval(0.02, 0.002, 0.01))
    path = cfg.save(str(tmp_path / "tuned.json"))
    loaded = autotune.TunedConfig.load(path)
    assert loaded.value("batch_size") == 512
    assert loaded.value("checkpoint_interval") == cfg.value(
        "checkpoint_interval")
    assert loaded.meta["model"] == "t"
    assert loaded.get("batch_size")["fingerprint"] == "abcdef012345"
    # latest-wins on duplicate knobs
    loaded.add({"knob": "batch_size", "chosen": 256})
    assert loaded.value("batch_size") == 256
    # the raw artifact is plain JSON (the report tool's contract)
    doc = json.loads(open(path).read())
    assert doc["meta"]["version"] == autotune.TunedConfig.VERSION


_OLD_RULINGS = {
    # as ``tune_attention_kernel`` / ``tune_quant_kernel`` wrote them
    # until PR 45
    "attention_kernel": {
        "knob": "attention_kernel", "shape": "T4096:K4096:d64:bfloat16",
        "pallas": True, "xla_step_s": 0.012, "pallas_step_s": 0.004,
        "speedup": 3.0, "min_speedup": 1.03,
        "evidence": "measured_ab_window", "fingerprint": "abcdef012345",
        "source": "measured"},
    "quant_kernel": {
        "knob": "quant_kernel", "shape": "M16:K2048:N2048:float32:dynamic",
        "pallas": True, "evidence": "decision_table", "cached": True,
        "fingerprint": "abcdef012345", "source": "cached"},
}


@pytest.mark.parametrize("knob", sorted(_OLD_RULINGS))
def test_an_old_artifacts_kernel_ruling_is_ignored(knob, tmp_path):
    """An artifact an older run wrote is input from outside: its
    ``attention_kernel`` / ``quant_kernel`` ruling loads, reads ``ignored``
    — never an error — and changes nothing a step is keyed on or a rule
    reads; the decisions beside it apply as before."""
    path = str(tmp_path / "tuned.json")
    with open(path, "w") as f:
        json.dump({"meta": {"version": 1, "run_id": "r-old"},
                   "decisions": [
                       {"knob": "batch_size", "chosen": 512},
                       _OLD_RULINGS[knob],
                       {"knob": "checkpoint_interval", "chosen": 40}]}, f)
    cfg = autotune.TunedConfig.load(path)
    keyed, every = compile_cache.trace_flag_values(), dict(flags._FLAGS)
    assert cfg.apply() == [("batch_size", "advisory"), (knob, "ignored"),
                           ("checkpoint_interval", "advisory")]
    assert compile_cache.trace_flag_values() == keyed
    assert flags._FLAGS == every
    assert cfg.value("batch_size") == 512
    assert cfg.value("checkpoint_interval") == 40
    assert cfg.value(knob) is None


# ---------------------------------------------------------------------------
# probe accounting (satellite bugfix)
# ---------------------------------------------------------------------------

def test_probe_accounting_excluded_from_report():
    program_profile.reset()
    with program_profile.probe_accounting():
        assert program_profile.probe_active()
        program_profile.note_step("probefp", 5.0, 32)
    program_profile.note_step("steadyfp", 1.0, 32)
    rows = {(r["fingerprint"], bool(r.get("probe"))): r
            for r in program_profile.report_rows() if r["steps"]}
    assert rows[("probefp", True)]["wall_share"] == 0.0
    assert rows[("probefp", True)]["mfu"] is None
    # the steady row owns 100% of the (non-probe) wall clock even
    # though the probe burned 5x its time
    assert rows[("steadyfp", False)]["wall_share"] == 1.0
    table = program_profile.render_table(
        program_profile.report_rows())
    assert "probe:" in table


def test_probe_work_never_blends_into_steady_row():
    """A tuner probing the SAME fingerprint the run then trains: probe
    wall clock lands in its own flagged row — the steady row's share
    and step count exclude it entirely."""
    program_profile.reset()
    with program_profile.probe_accounting():
        for _ in range(5):
            program_profile.note_step("fp", 2.0, 8)      # 10s of probes
    program_profile.note_step("fp", 1.0, 8)              # 1s steady
    rows = [r for r in program_profile.report_rows() if r["steps"]]
    assert len(rows) == 2
    steady = next(r for r in rows if not r.get("probe"))
    probe = next(r for r in rows if r.get("probe"))
    assert steady["fingerprint"] == probe["fingerprint"] == "fp"
    assert steady["steps"] == 1 and steady["wall_s"] == 1.0
    assert steady["wall_share"] == 1.0
    assert probe["steps"] == 5 and probe["wall_s"] == 10.0
    assert probe["wall_share"] == 0.0 and probe["mfu"] is None


# ---------------------------------------------------------------------------
# CPU-driven tuner loops
# ---------------------------------------------------------------------------

def _toy_mlp():
    fluid.default_main_program().random_seed = 7
    fluid.default_startup_program().random_seed = 7
    img = fluid.layers.data("img", shape=[784])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(img, size=64, act="relu")
    pred = fluid.layers.fc(h, size=10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(0)

    def make_feed(b):
        return {"img": rng.rand(b, 784).astype("float32"),
                "label": rng.randint(0, 10, (b, 1)).astype("int64")}
    return loss, make_feed


def test_tune_batch_size_fake_hbm_limit_and_zero_extra_compiles():
    """The CPU-drivable ladder: a fake FLAGS_autotune_hbm_bytes ceiling
    rejects by ESTIMATE (the documented mechanism), probe compiles are
    exactly the declared ladder (one per probed rung, trace-cache
    counted), and re-measuring the chosen rung afterwards performs zero
    further lowerings (the window dispatches the seeded executable)."""
    from paddle_tpu.executor import Executor
    from paddle_tpu.scope import Scope, scope_guard

    loss, make_feed = _toy_mlp()
    fluid.set_flags({"FLAGS_autotune_hbm_bytes": 2_000_000}, pin=False)
    # warm the one-time machinery OUTSIDE the count (startup lowering,
    # jax.random key jits, device_put paths) — and the start rung's own
    # profile, which the tuner then serves from the registry for free
    warm_scope = Scope()
    with scope_guard(warm_scope):
        exe = Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program(), scope=warm_scope)
        exe.cost_analysis(fluid.default_main_program(), make_feed(16),
                          [loss], scope=warm_scope)
        autotune.measure_step_window(
            exe, fluid.default_main_program(), make_feed(16), [loss],
            steps=1, scope=warm_scope)
    cfg = autotune.TunedConfig()
    # regress_tol effectively off: step timing on a loaded CI box is
    # noisy enough to fire the (pure-function-tested) regression stop
    # before the ladder reaches the ceiling — this test pins the MEMORY
    # path, so the ladder must climb until the estimate rejects
    with compile_cache.count_compiles() as n:
        d = autotune.tune_batch_size(
            fluid.default_main_program(),
            fluid.default_startup_program(), make_feed, loss,
            fluid.CPUPlace(), start=16, max_batch=4096, probe_steps=2,
            regress_tol=1e9, config=cfg)
    probed = [c for c in d["candidates"] if "peak_hbm_bytes" in c]
    rejected = [c for c in d["candidates"]
                if str(c["status"]).startswith("rejected")]
    # the fake 2 MB ceiling stopped the ladder before max_batch
    assert rejected, d["candidates"]
    assert d["chosen"] is not None
    assert d["hbm_limit_bytes"] == 2_000_000
    # every rejection happened via the estimate, never a dispatch
    for c in rejected:
        assert "step_s" not in c
    # zero compiles beyond the declared probe ladder: one lowering per
    # NEW probed rung (the cost_analysis explicit compile, whose
    # executable the measured window then dispatches); the pre-warmed
    # b16 rung and the startup program re-lower nothing
    assert n()["jax_lowerings"] == len(probed) - 1, (n(), d)
    # warm re-measure of the chosen batch in a fresh scope/executor:
    # the trace cache + seeded AOT slot serve it, zero new lowerings
    from paddle_tpu.executor import Executor
    from paddle_tpu.scope import Scope, scope_guard

    scope = Scope()
    with scope_guard(scope):
        exe = Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program(), scope=scope)
        with compile_cache.count_compiles() as n2:
            autotune.measure_step_window(
                exe, fluid.default_main_program(),
                make_feed(d["chosen"]), [loss], steps=2, scope=scope)
    assert n2()["jax_lowerings"] == 0, n2()
    # the decision landed in the config with provenance
    assert cfg.value("batch_size") == d["chosen"]
    assert cfg.get("batch_size")["fingerprint"]


def test_tune_batch_size_twice_warm_registry_same_peaks():
    """Second tune in one process: probes are served from the warm
    profile registry, and each rung must get ITS OWN signature's peak —
    not the newest-captured profile (which would be the first run's
    largest rung, instantly mis-rejecting the ladder's base)."""
    loss, make_feed = _toy_mlp()
    fluid.set_flags({"FLAGS_autotune_hbm_bytes": 2_000_000}, pin=False)
    kw = dict(start=16, max_batch=4096, probe_steps=1, regress_tol=1e9)
    d1 = autotune.tune_batch_size(
        fluid.default_main_program(), fluid.default_startup_program(),
        make_feed, loss, fluid.CPUPlace(), **kw)
    d2 = autotune.tune_batch_size(
        fluid.default_main_program(), fluid.default_startup_program(),
        make_feed, loss, fluid.CPUPlace(), **kw)
    peaks1 = {c["batch"]: c.get("peak_hbm_bytes")
              for c in d1["candidates"]}
    peaks2 = {c["batch"]: c.get("peak_hbm_bytes")
              for c in d2["candidates"]}
    assert peaks2 == peaks1
    assert d2["chosen"] is not None
    assert [c["status"] for c in d2["candidates"]] \
        == [c["status"] for c in d1["candidates"]]


def test_trainer_consumes_tuned_config(tmp_path):
    """Trainer(autotune=path): the tuned checkpoint interval re-gates
    the manager — unless the user pinned step_interval explicitly."""
    from paddle_tpu.contrib import CheckpointConfig, Trainer
    from paddle_tpu.reader import checkpointable

    cfg = autotune.TunedConfig()
    cfg.add(autotune.decide_checkpoint_interval(
        step_s=0.02, snapshot_s=0.002, save_s=0.01, async_save=False))
    path = cfg.save(str(tmp_path / "tuned.json"))
    expect = cfg.value("checkpoint_interval")
    assert expect and expect != 10       # would mask the default

    def train_func():
        fluid.default_main_program().random_seed = 7
        fluid.default_startup_program().random_seed = 7
        x = fluid.layers.data("x", shape=[8])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        pred = fluid.layers.fc(x, size=4, act="softmax")
        return fluid.layers.mean(fluid.layers.cross_entropy(pred, label))

    def samples():
        rng = np.random.RandomState(0)
        for _ in range(16):
            yield (rng.rand(8).astype("float32"),
                   rng.randint(0, 4, (1,)).astype("int64"))

    losses = []

    def handler(ev):
        if hasattr(ev, "metrics"):
            losses.append(float(np.ravel(ev.metrics[0])[0]))

    # unpinned CheckpointConfig: the tuned cadence applies
    tr = Trainer(train_func=train_func, place=fluid.CPUPlace(),
                 optimizer_func=lambda: fluid.optimizer.Adam(1e-3),
                 checkpoint_config=CheckpointConfig(
                     checkpoint_dir=str(tmp_path / "ck1"),
                     async_save=False),
                 autotune=path)
    assert tr.checkpoint_cfg.step_interval == expect
    assert tr._ckpt_mgr.save_interval_steps == expect
    tr.train(num_epochs=1, event_handler=handler,
             reader=checkpointable(fluid.batch(samples, batch_size=8)),
             feed_order=["x", "label"])
    assert losses and np.isfinite(losses[-1])

    # pinned step_interval: the user's cadence survives
    tr2 = Trainer(train_func=train_func, place=fluid.CPUPlace(),
                  optimizer_func=lambda: fluid.optimizer.Adam(1e-3),
                  checkpoint_config=CheckpointConfig(
                      checkpoint_dir=str(tmp_path / "ck2"),
                      step_interval=5, async_save=False),
                  autotune=path)
    assert tr2.checkpoint_cfg.step_interval == 5
    assert tr2._ckpt_mgr.save_interval_steps == 5


def test_manager_measured_costs_and_tune(tmp_path):
    """The checkpoint manager's own cost samples feed the interval
    tuner (measured evidence, not a guess)."""
    from paddle_tpu.parallel.checkpoint import (
        TrainStateCheckpointManager)

    x = fluid.layers.data("x", shape=[4])
    loss = fluid.layers.mean(fluid.layers.fc(x, size=2))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    mgr = TrainStateCheckpointManager(str(tmp_path), async_save=False)
    assert mgr.measured_costs() == {}
    mgr.save(1, program=fluid.default_main_program(),
             executors={"train": exe})
    costs = mgr.measured_costs()
    assert costs["n"] == 1
    assert costs["snapshot_s"] > 0 and costs["save_s"] > 0
    d = autotune.tune_checkpoint_interval(step_s=0.05, manager=mgr,
                                          async_save=False)
    assert d["chosen"] >= 1 and d["measured_saves"] == 1
    mgr.set_interval(7)
    assert mgr.save_interval_steps == 7
    with pytest.raises(ValueError):
        autotune.tune_checkpoint_interval(manager=mgr)   # no step time


@pytest.mark.slow
def test_acceptance_tuner_matches_best_grid_point():
    """Acceptance: the tuner's chosen batch has measured
    step-time/example within tolerance of the best exhaustive grid
    point (the tuner finds what a full sweep finds, cheaper)."""
    loss, make_feed = _toy_mlp()
    main = fluid.default_main_program()
    startup = fluid.default_startup_program()
    place = fluid.CPUPlace()
    grid = [32, 64, 128, 256, 512]
    d = autotune.tune_batch_size(main, startup, make_feed, loss, place,
                                 ladder=list(grid), probe_steps=6,
                                 warmup_steps=2)
    assert d["chosen"] is not None
    # exhaustive grid sweep with the same measurement machinery
    from paddle_tpu.executor import Executor
    from paddle_tpu.scope import Scope, scope_guard

    sweep = {}
    scope = Scope()
    with scope_guard(scope), program_profile.probe_accounting():
        exe = Executor(place)
        exe.run(startup, scope=scope)
        for b in grid:
            feed = make_feed(b)
            exe.cost_analysis(main, feed, [loss], scope=scope)
            sweep[b] = autotune.measure_step_window(
                exe, main, feed, [loss], steps=6, warmup=2,
                scope=scope) / b
    best = min(sweep.values())
    # generous tolerance: CPU step timing under concurrent test load is
    # noisy; the claim is "the tuner lands in the right neighborhood",
    # not microbenchmark equality
    assert sweep[d["chosen"]] <= best * 1.6, (d["chosen"], sweep)


# ---------------------------------------------------------------------------
# pipeline schedule + microbatch tuning (ISSUE 12)
# ---------------------------------------------------------------------------

def test_decide_pipeline_fast_then_low_bubble():
    """Fastest wins outright; near-ties (within tol) settle by the
    schedule table's bubble fraction, then the memory bound."""
    cands = [
        {"schedule": "gpipe", "microbatches": 4, "step_s": 0.100,
         "bubble_fraction": 0.20, "in_flight": 7},
        {"schedule": "interleaved", "microbatches": 4, "step_s": 0.102,
         "bubble_fraction": 0.10, "in_flight": 11},
        {"schedule": "1f1b", "microbatches": 16, "step_s": 0.200,
         "bubble_fraction": 0.15, "in_flight": 7},
    ]
    d = autotune.decide_pipeline(cands, tol=0.05)
    assert d["chosen"] == {"schedule": "interleaved", "microbatches": 4}
    assert d["evidence"] == "measured_step_window"
    assert len(d["candidates"]) == 3
    # a decisive speed gap beats a nicer schedule table
    cands[0]["step_s"] = 0.05
    d2 = autotune.decide_pipeline(cands, tol=0.05)
    assert d2["chosen"]["schedule"] == "gpipe"
    # rejected/unmeasured candidates never win; all-rejected raises
    with pytest.raises(ValueError, match="no measured candidate"):
        autotune.decide_pipeline(
            [{"schedule": "gpipe", "microbatches": 2,
              "rejected": "peak_hbm"}])


def _pipelined_fc_program(stages=2, microbatches=2, size=8):
    x = fluid.layers.data("x", shape=[size])
    pipe = fluid.layers.Pipeline(microbatches=microbatches)
    for i in range(stages):
        with pipe.stage():
            c = pipe.carry(x if i == 0 else None)
            c = fluid.layers.fc(c, size=size, act="tanh")
            pipe.emit(c)
    out = pipe()
    loss = fluid.layers.mean(fluid.layers.square(out))
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return loss


def test_tune_pipeline_pinned_schedule_skips_probes():
    """An explicit BuildStrategy.pipeline_schedule is the user's pin:
    recorded as such, zero candidates measured."""
    from paddle_tpu.parallel import make_mesh

    loss = _pipelined_fc_program()
    mesh = make_mesh((1, 2), ("dp", "pp"))
    bs = fluid.BuildStrategy()
    bs.pipeline_schedule = "1f1b"
    bs.pipeline_microbatches = 4
    cfg = autotune.TunedConfig()
    d = autotune.tune_pipeline(
        fluid.default_main_program(), fluid.default_startup_program(),
        {"x": np.zeros((8, 8), "float32")}, loss, mesh,
        build_strategy=bs, config=cfg)
    assert d["evidence"] == "pinned"
    assert d["chosen"] == {"schedule": "1f1b", "microbatches": 4}
    assert d["candidates"] == []
    assert cfg.get("pipeline")["source"] == "pinned"


def test_tune_pipeline_requires_pipelined_program():
    from paddle_tpu.parallel import make_mesh

    x = fluid.layers.data("x", shape=[4])
    loss = fluid.layers.mean(fluid.layers.fc(x, size=4))
    with pytest.raises(ValueError, match="no pipeline_region"):
        autotune.tune_pipeline(
            fluid.default_main_program(),
            fluid.default_startup_program(),
            {"x": np.zeros((4, 4), "float32")}, loss,
            make_mesh((1, 2), ("dp", "pp")))


def test_tune_pipeline_measures_and_records():
    """The measured path: one compile per candidate, decision +
    per-candidate evidence (step_s, bubble fraction, memory bound) in
    the TunedConfig artifact; probe steps ride the probe accounting."""
    from paddle_tpu.parallel import make_mesh

    loss = _pipelined_fc_program(stages=2, microbatches=2)
    mesh = make_mesh((1, 2), ("dp", "pp"))
    cfg = autotune.TunedConfig()
    rng = np.random.RandomState(0)
    d = autotune.tune_pipeline(
        fluid.default_main_program(), fluid.default_startup_program(),
        {"x": rng.rand(8, 8).astype("float32")}, loss, mesh,
        microbatch_candidates=[2, 4], probe_steps=1, warmup_steps=1,
        config=cfg)
    assert d["chosen"]["schedule"] in ("gpipe", "1f1b")
    assert d["chosen"]["microbatches"] in (2, 4)
    measured = [c for c in d["candidates"] if c.get("step_s")]
    assert len(measured) == 4        # 2 schedules x 2 microbatch counts
    for c in measured:
        assert 0.0 < c["bubble_fraction"] < 1.0
        assert c["in_flight"] >= 1
    rec = cfg.get("pipeline")
    assert rec["chosen"] == d["chosen"]
    assert rec["evidence"] == "measured_step_window"
    assert rec["mesh_pp"] == 2


def test_tune_pipeline_hbm_gate_rejects_all(monkeypatch):
    """A fake 1-byte ceiling (FLAGS_autotune_hbm_bytes) rejects every
    candidate from the compiled peak estimate before any measured
    window — the CPU-testable rejection path."""
    from paddle_tpu.parallel import make_mesh

    loss = _pipelined_fc_program(stages=2, microbatches=2)
    mesh = make_mesh((1, 2), ("dp", "pp"))
    fluid.set_flags({"FLAGS_autotune_hbm_bytes": 1,
                     "FLAGS_preflight_oom": "warn"})
    try:
        with pytest.raises(ValueError, match="no measured candidate"):
            autotune.tune_pipeline(
                fluid.default_main_program(),
                fluid.default_startup_program(),
                {"x": np.zeros((8, 8), "float32")}, loss, mesh,
                microbatch_candidates=[2], schedules=["gpipe"],
                probe_steps=1)
    finally:
        fluid.set_flags({"FLAGS_autotune_hbm_bytes": 0,
                         "FLAGS_preflight_oom": "auto"})
