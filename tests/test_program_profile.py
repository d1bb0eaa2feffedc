"""Program-level cost & memory attribution tests (ISSUE 5): profile
capture at the cold dispatch with ZERO extra lowerings, the HBM
preflight, registry-served cost_analysis, per-program step accounting
and /metrics family, run-id correlation across JSONL / chrome traces /
exposition, the program_report CLI, and the watchdog's suspect-program
line."""

import json
import os
import subprocess
import sys
import time
import urllib.request
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache, monitor, profiler
from paddle_tpu.monitor import program_profile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def clean_profile_state():
    """Every test starts and ends with default preflight flags, a
    disabled monitor, and an empty profile registry."""
    fluid.set_flags({"FLAGS_preflight_oom": "auto",
                     "FLAGS_preflight_hbm_bytes": 0})
    program_profile.reset()
    yield
    monitor.disable()
    monitor.registry().reset()
    monitor.step_stats().reset()
    program_profile.reset()
    fluid.set_flags({"FLAGS_preflight_oom": "auto",
                     "FLAGS_preflight_hbm_bytes": 0})


def _build_mlp(seed=0):
    fluid.default_main_program().random_seed = seed
    x = fluid.layers.data("x", shape=[4])
    h = fluid.layers.fc(x, size=8, act="relu")
    loss = fluid.layers.mean(fluid.layers.fc(h, size=3))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _run_steps(loss, steps=3, batch=8):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    x = np.random.RandomState(0).rand(batch, 4).astype("float32")
    for _ in range(steps):
        exe.run(feed={"x": x}, fetch_list=[loss])
    return exe


# ---------------------------------------------------------------------------
# capture + accounting + report
# ---------------------------------------------------------------------------

def test_cold_dispatch_captures_cost_and_memory_profile():
    monitor.enable()
    loss = _build_mlp()
    _run_steps(loss, steps=3)
    fp = compile_cache.program_fingerprint(fluid.default_main_program())
    prof = program_profile.get(fp)
    assert prof is not None and prof.kind == "executor"
    # the compiler's own accounting, not a heuristic
    assert prof.flops > 0
    assert prof.bytes_accessed > 0
    assert prof.argument_bytes > 0          # params + feed cross the step
    assert prof.peak_hbm_bytes > 0
    assert set(prof.breakdown()) == {
        "argument_bytes", "output_bytes", "temp_bytes",
        "generated_code_bytes", "alias_bytes", "peak_hbm_bytes"}
    # step accounting joined the profile
    acct = program_profile.accounting()[fp]
    assert acct["steps"] == 3
    assert acct["examples"] == 24
    assert acct["wall_s"] > 0
    # per-program /metrics family
    fp12 = fp[:12]
    reg = monitor.registry()
    assert reg.get("program/%s/steps_total" % fp12).value == 3
    assert reg.get("program/%s/step_seconds" % fp12).count == 3
    assert reg.get("program/%s/examples_total" % fp12).value == 24


def test_two_program_run_report_acceptance():
    """Acceptance: MLP + transformer in one monitored run -> report
    rows with distinct fingerprints, compiler-accounted flops/bytes/
    peak-HBM per program, correct step counts, wall-clock shares."""
    from paddle_tpu.models import transformer as tfm

    monitor.enable()
    mlp_loss = _build_mlp()
    exe = _run_steps(mlp_loss, steps=4)
    mlp_fp = compile_cache.program_fingerprint(fluid.default_main_program())

    with fluid.program_guard(fluid.Program(), fluid.Program()):
        src = fluid.layers.data("src_word", shape=[1], dtype="int64",
                                lod_level=1)
        tgt = fluid.layers.data("tgt_word", shape=[1], dtype="int64",
                                lod_level=1)
        label = fluid.layers.data("lbl_word", shape=[1], dtype="int64",
                                  lod_level=1)
        cost, _ = tfm.transformer(
            src, tgt, label, 8, 8, 12, 12, n_layer=1, n_head=2,
            d_model=16, d_inner=32, dropout_rate=0.0)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        tfm_prog = fluid.default_main_program()
        tfm_fp = compile_cache.program_fingerprint(tfm_prog)

        feeder = fluid.DataFeeder(feed_list=[src, tgt, label], pad_to=8)
        rng = np.random.RandomState(0)
        rows = [[rng.randint(1, 12, (8,)), rng.randint(1, 12, (8,)),
                 rng.randint(1, 12, (8,))] for _ in range(2)]
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(fluid.default_startup_program())
        for _ in range(2):
            exe2.run(feed=feeder.feed(rows), fetch_list=[cost])

    assert mlp_fp != tfm_fp
    report = program_profile.report_rows(peak_tflops=100.0)
    by_fp = {r["fingerprint"]: r for r in report}
    assert mlp_fp in by_fp and tfm_fp in by_fp
    assert by_fp[mlp_fp]["steps"] == 4
    assert by_fp[tfm_fp]["steps"] == 2
    for fp in (mlp_fp, tfm_fp):
        assert by_fp[fp]["flops_per_step"] > 0
        assert by_fp[fp]["bytes_per_step"] > 0
        assert by_fp[fp]["peak_hbm_bytes"] > 0
        assert by_fp[fp]["mfu"] is not None and by_fp[fp]["mfu"] >= 0
    # the transformer step does vastly more arithmetic than the MLP
    assert by_fp[tfm_fp]["flops_per_step"] > by_fp[mlp_fp]["flops_per_step"]
    shares = sum(r["wall_share"] for r in report)
    assert shares == pytest.approx(1.0, abs=0.01)
    # the rendered table carries one line per program
    table = program_profile.render_table(report)
    assert mlp_fp[:12] in table and tfm_fp[:12] in table


def test_profile_capture_costs_zero_extra_lowerings():
    """The acceptance gate: lowering AND backend-compile counts (jax's
    own counters plus the trace cache's) are IDENTICAL between a
    profile-off and a profile-on run of the same fresh program — the
    capture is the one compile, not an extra one."""
    def arm():
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            loss = _build_mlp()
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                _run_steps(loss, steps=3)

    arm()                                   # warmup: jnp helper modules

    # default flags, monitor off: capture is dormant (auto mode)
    assert not program_profile.capture_enabled()
    compile_cache.clear()
    compile_cache.reset_stats()
    with compile_cache.count_compiles() as off_n:
        arm()

    monitor.enable()
    assert program_profile.capture_enabled()
    compile_cache.clear()
    compile_cache.reset_stats()
    with compile_cache.count_compiles() as on_n:
        arm()

    on, off = on_n(), off_n()
    assert on["jax_lowerings"] == off["jax_lowerings"], \
        "profile capture added jax lowerings"
    assert on["jax_backend_compiles"] == off["jax_backend_compiles"], \
        "profile capture added backend compiles"
    assert on["lowerings"] == off["lowerings"], \
        "profile capture added trace-cache lowerings"
    assert program_profile.profiles(), "profile-on arm captured nothing"


def test_monitor_off_captures_nothing_by_default():
    """Default flags (preflight auto) + monitor off: the executors run
    their unmodified jit path — no profiles, no accounting, no AOT
    executables."""
    assert not monitor.enabled()
    assert not program_profile.capture_enabled()
    loss = _build_mlp()
    exe = _run_steps(loss, steps=2)
    assert program_profile.profiles() == []
    assert program_profile.accounting() == {}
    assert all(not c.aot for c in exe._cache.values())
    # explicit "off" dominates even with the monitor on
    fluid.set_flags({"FLAGS_preflight_oom": "off"})
    monitor.enable()
    assert program_profile.capture_enabled()   # profiles still wanted
    fluid.set_flags({"FLAGS_monitor": False})


# ---------------------------------------------------------------------------
# HBM preflight
# ---------------------------------------------------------------------------

def test_preflight_warns_with_buffer_class_breakdown():
    # "warn" forces capture+preflight even on this unmonitored run
    fluid.set_flags({"FLAGS_preflight_oom": "warn",
                     "FLAGS_preflight_hbm_bytes": 16})   # mocked capacity
    loss = _build_mlp()
    with warnings.catch_warnings(record=True) as ws:
        warnings.simplefilter("always")
        _run_steps(loss, steps=2)
    msgs = [str(w.message) for w in ws
            if "HBM preflight" in str(w.message)]
    assert msgs, "no preflight warning at 16-byte capacity"
    m = msgs[0]
    for cls in ("arguments", "outputs", "temps", "generated code",
                "aliased"):
        assert cls in m, "breakdown missing %r: %s" % (cls, m)
    assert "exceeds capacity" in m


def test_preflight_strict_raises_before_first_dispatch():
    fluid.set_flags({"FLAGS_preflight_oom": "strict",
                     "FLAGS_preflight_hbm_bytes": 16})
    _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(program_profile.PreflightOOMError,
                       match="exceeds capacity"):
        exe.run(fluid.default_startup_program())
    # the raise happened BEFORE the dispatch: the startup program never
    # wrote its parameters back, and a retry still preflights (the
    # signature was never marked seen)
    with pytest.raises(program_profile.PreflightOOMError):
        exe.run(fluid.default_startup_program())
    # widening the mocked capacity unblocks the same executor
    fluid.set_flags({"FLAGS_preflight_hbm_bytes": 1 << 30})
    exe.run(fluid.default_startup_program())


def test_preflight_normal_run_unaffected():
    """A normal monitored run: capture happens (auto mode), but CPU
    devices report no capacity and no override is set — no warning,
    steps run normally."""
    monitor.enable()
    loss = _build_mlp()
    with warnings.catch_warnings(record=True) as ws:
        warnings.simplefilter("always")
        _run_steps(loss, steps=2)
    assert program_profile.profiles()          # capture did run
    assert not [w for w in ws if "HBM preflight" in str(w.message)]


# ---------------------------------------------------------------------------
# cost_analysis served from the registry
# ---------------------------------------------------------------------------

def test_cost_analysis_free_on_warm_program():
    monitor.enable()
    loss = _build_mlp()
    exe = _run_steps(loss, steps=2)
    feed = {"x": np.zeros((8, 4), "float32")}
    with compile_cache.count_compiles() as n:
        ca = exe.cost_analysis(feed=feed, fetch_list=[loss])
    assert n()["jax_lowerings"] == 0, "warm cost_analysis paid a lowering"
    assert ca["flops"] > 0 and ca["bytes accessed"] > 0
    # compile_if_missing=False on a never-analyzed signature -> None
    cold = {"x": np.zeros((16, 4), "float32")}     # unseen batch size
    assert exe.cost_analysis(feed=cold, fetch_list=[loss],
                             compile_if_missing=False) is None


def test_cost_analysis_distinguishes_fetch_sets():
    """The profile registry keys on the fetch set too: asking for a
    smaller fetch set must not serve the full train-step module's
    numbers (different fetch lists lower to different XLA modules)."""
    monitor.enable()
    x = fluid.layers.data("x", shape=[4])
    h = fluid.layers.fc(x, size=8, act="relu")
    loss = fluid.layers.mean(fluid.layers.fc(h, size=3))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.zeros((8, 4), "float32")}
    exe.run(feed=feed, fetch_list=[loss])      # captures the train module
    train_ca = exe.cost_analysis(feed=feed, fetch_list=[loss])
    # fwd-only fetch of the hidden layer: not served from the train
    # profile (registry miss on the fetch set), and cheaper than the
    # fwd+bwd+update module
    fwd_ca = exe.cost_analysis(feed=feed, fetch_list=[h])
    assert fwd_ca["flops"] < train_ca["flops"]
    # and the fwd-only analysis is now itself registry-served
    assert exe.cost_analysis(feed=feed, fetch_list=[h],
                             compile_if_missing=False) is not None


def test_cost_analysis_fallback_seeds_registry():
    """A never-run program pays one explicit compile, after which the
    registry serves repeats for free."""
    fluid.set_flags({"FLAGS_preflight_oom": "off"})    # no auto-capture
    loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.zeros((4, 4), "float32")}
    ca = exe.cost_analysis(feed=feed, fetch_list=[loss])
    assert ca["flops"] > 0
    with compile_cache.count_compiles() as n:
        ca2 = exe.cost_analysis(feed=feed, fetch_list=[loss])
    assert n()["jax_lowerings"] == 0 and ca2["flops"] == ca["flops"]


# ---------------------------------------------------------------------------
# correlation ids: JSONL <-> chrome trace <-> /metrics
# ---------------------------------------------------------------------------

def test_run_id_and_fingerprint_correlate_all_sinks(tmp_path):
    monitor.enable(log_dir=str(tmp_path))
    loss = _build_mlp()
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    _run_steps(loss, steps=2)
    profiler.stop_profiler(profile_path=None)
    trace_path = str(tmp_path / "trace.json")
    profiler.export_chrome_tracing(trace_path)

    fp = compile_cache.program_fingerprint(fluid.default_main_program())
    rid = monitor.run_id()

    # JSONL: step records carry run_id + fingerprint; profile event too
    files = [f for f in os.listdir(str(tmp_path)) if f.endswith(".jsonl")]
    records = [json.loads(ln)
               for ln in open(os.path.join(str(tmp_path), files[0]))]
    steps = [r for r in records if r.get("event") == "step_stats"
             and r.get("fingerprint") == fp]
    assert len(steps) == 2
    assert all(r["run_id"] == rid for r in steps)
    profs = [r for r in records if r.get("event") == "program_profile"
             and r.get("fingerprint") == fp]
    assert profs and profs[0]["run_id"] == rid
    assert profs[0]["flops"] > 0

    # chrome trace: top-level metadata + process metadata + span args
    trace = json.load(open(trace_path))
    assert trace["metadata"]["run_id"] == rid
    procs = [e for e in trace["traceEvents"]
             if e.get("name") == "process_name"]
    assert procs and procs[0]["args"]["run_id"] == rid
    tagged = [e for e in trace["traceEvents"]
              if e.get("args", {}).get("fingerprint") == fp[:12]]
    assert tagged, "no span tagged with the program fingerprint"
    assert all(e["args"]["run_id"] == rid for e in tagged)
    assert {e["name"] for e in tagged} <= {"executor/compile",
                                           "executor/dispatch"}

    # /metrics: run_id comment + the per-program family
    text = monitor.expose_text()
    assert text.startswith("# run_id %s\n" % rid)
    assert ("program_%s_steps_total" % fp[:12]) in text


# ---------------------------------------------------------------------------
# program_report CLI
# ---------------------------------------------------------------------------

def test_program_report_cli_from_jsonl(tmp_path):
    monitor.enable(log_dir=str(tmp_path))
    loss = _build_mlp()
    _run_steps(loss, steps=3)
    fp = compile_cache.program_fingerprint(fluid.default_main_program())
    # live-registry view, read before disable() resets the accounting
    live = {r["fingerprint"]: r for r in program_profile.report_rows()}
    monitor.disable()

    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "program_report.py"),
         str(tmp_path), "--json", "--run_id", monitor.run_id()],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120, check=True).stdout
    # stable --json schema: {programs: [...], devices: {...}} (devices
    # empty on backends with no memory stats)
    payload = json.loads(out)
    rows = {r["fingerprint"]: r for r in payload["programs"]}
    assert isinstance(payload["devices"], dict)
    assert rows[fp]["steps"] == 3
    assert rows[fp]["flops_per_step"] > 0
    assert rows[fp]["peak_hbm_bytes"] > 0
    assert 0 < rows[fp]["wall_share"] <= 1.0
    # the offline JSONL replay agrees with the live registry's table
    from tools.program_report import load_records, rows_from_records
    replay = rows_from_records(load_records(str(tmp_path)),
                               run_id=monitor.run_id())
    row = [ln for ln in program_profile.render_table(replay).splitlines()
           if ln.startswith(fp[:12])]
    assert row and row[0].split()[2] == "3"     # steps column
    assert live[fp]["steps"] == rows[fp]["steps"]


# ---------------------------------------------------------------------------
# watchdog names the suspect program
# ---------------------------------------------------------------------------

def test_watchdog_stall_diag_names_last_program(tmp_path):
    monitor.enable(log_dir=str(tmp_path))
    loss = _build_mlp()
    _run_steps(loss, steps=2)
    fp = compile_cache.program_fingerprint(fluid.default_main_program())
    # arm the short stall window only after the (slow, cold-compiling)
    # steps, so the first firing reports the completed run's state
    fluid.set_flags({"FLAGS_monitor_stall_seconds": 0.2})
    deadline = time.monotonic() + 2.0
    stalls = monitor.registry().counter("monitor/watchdog_stalls")
    while stalls.value == 0 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert stalls.value >= 1
    files = [f for f in os.listdir(str(tmp_path)) if f.endswith(".jsonl")]
    records = [json.loads(ln)
               for ln in open(os.path.join(str(tmp_path), files[0]))]
    dumps = [r for r in records if r.get("event") == "watchdog_stall"]
    assert dumps
    suspect = dumps[0].get("last_program")
    assert suspect is not None
    assert suspect["fingerprint"] == fp[:12]
    assert suspect["steps"] == 2
    assert suspect["flops"] > 0
    assert suspect["peak_hbm_bytes"] > 0


# ---------------------------------------------------------------------------
# ParallelExecutor: capture + per-device gauges
# ---------------------------------------------------------------------------

def test_parallel_executor_capture_and_device_gauges():
    import jax

    monitor.enable()
    fluid.default_main_program().random_seed = 3
    img = fluid.layers.data("img", shape=[16])
    h = fluid.layers.fc(img, size=8, act="relu")
    loss = fluid.layers.mean(h)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name)
    x = np.random.RandomState(0).rand(16, 16).astype("float32")
    for _ in range(2):
        pe.run(feed={"img": x}, fetch_list=[loss.name])

    fp = compile_cache.program_fingerprint(fluid.default_main_program())
    prof = program_profile.get(fp, kind="parallel_executor")
    assert prof is not None
    assert prof.flops > 0
    acct = program_profile.accounting()[fp]
    assert acct["steps"] == 2 and acct["kind"] == "parallel_executor"
    # one steps_total counter per local mesh device
    reg = monitor.registry()
    dev_counters = [n for n in reg.names()
                    if n.startswith("device/") and n.endswith("steps_total")]
    assert len(dev_counters) == len(jax.local_devices())
    assert all(reg.get(n).value == 2 for n in dev_counters)
