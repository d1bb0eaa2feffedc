"""The compile record (ISSUE 36): ``compile_cache.compile_log()`` holds one
plain dict per lowering — who, why, whether the trace cache had it, what
each phase of the cold call cost — written on the cold path only, with
jax's own durations attributed by the calling thread's open record and
everything else in ``outside_compiles()``."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.monitoring
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import compile_cache, monitor, profiler, registry
from paddle_tpu.monitor import program_profile
from paddle_tpu.parallel.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIELDS = {"name", "executor", "fingerprint", "ops", "cause", "trace_cache",
          "start_us", "build_s", "build", "analyze_s", "program_trace_s",
          "jax_trace_s", "kernel_trace_s", "kernel_traces", "lowering_s",
          "executable_s", "executable", "first_call_s", "unaccounted_s",
          "op_work", "batch_shards"}
SECONDS = ("build_s", "analyze_s", "program_trace_s", "jax_trace_s",
           "kernel_trace_s", "lowering_s", "executable_s", "first_call_s")


@pytest.fixture(autouse=True)
def _fresh_log():
    compile_cache.reset_stats()
    yield
    monitor.disable()


def _build(width=16, amp=False):
    """(main, startup, loss) of a small regression; ``width`` keeps one
    test's fingerprints apart from another's."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        h = fluid.layers.fc(x, size=width, act="relu")
        loss = fluid.layers.mean(fluid.layers.fc(h, size=1))
        opt = fluid.optimizer.SGD(learning_rate=1e-2)
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss


def _started(startup):
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    return scope


def _x(rows):
    return {"x": np.full((rows, 8), 0.5, "float32")}


def test_a_cold_run_closes_one_record_with_every_field_set():
    main, startup, loss = _build(17)
    scope = _started(startup)
    (start_rec,) = compile_cache.compile_log()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    log = compile_cache.compile_log()
    assert len(log) == 2 and log[0] == start_rec
    rec = log[1]
    assert set(rec) == FIELDS and None not in rec.values()
    assert rec["name"] == "pt_exe_" + compile_cache.program_label(main)
    assert rec["executor"] == "executor"
    assert rec["fingerprint"] == compile_cache.program_fingerprint(main)[:12]
    assert rec["ops"] == len(main.global_block().ops)
    assert (rec["cause"], rec["trace_cache"]) == ("first", "miss")
    # no persistent cache under the tests: compiled, and kept nowhere
    assert rec["executable"] == "uncached"
    assert all(rec[f] >= 0 for f in SECONDS)
    assert rec["jax_trace_s"] > 0 and rec["lowering_s"] > 0 \
        and rec["executable_s"] > 0
    parts = rec["jax_trace_s"] + rec["lowering_s"] + rec["executable_s"]
    assert parts <= rec["first_call_s"]
    assert rec["unaccounted_s"] == pytest.approx(rec["first_call_s"] - parts)
    assert rec["kernel_trace_s"] <= rec["jax_trace_s"]
    assert rec["start_us"] > start_rec["start_us"]
    json.dumps(log)                     # plain dicts
    # a copy: the caller's edits stay the caller's
    log[1]["cause"] = "edited"
    assert compile_cache.compile_log()[1]["cause"] == "first"


@pytest.mark.parametrize("case", ["feed_signature", "program_changed",
                                  "other_key", "other_fetch_list"])
def test_cause_says_why_a_step_was_lowered_again(case):
    main, startup, loss = _build(18)
    scope = _started(startup)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    before = len(compile_cache.compile_log())
    want_cache = "miss"
    if case == "feed_signature":
        # one jitted entry serves every shape: the trace cache has it,
        # and jax traces and compiles the new shape all the same
        exe.run(main, feed=_x(6), fetch_list=[loss], scope=scope)
        want, want_cache = "feed_signature", "hit"
    elif case == "program_changed":
        with fluid.program_guard(main, startup):
            fluid.layers.scale(loss, scale=2.0)
        exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
        want = "program_changed"
    elif case == "other_key":
        # a second executor over the same program: nothing is lowered,
        # and this process has run the signature, so no cold call follows
        fluid.Executor(fluid.CPUPlace()).run(
            main, feed=_x(4), fetch_list=[loss], scope=scope)
        want, want_cache = "other_key", "hit"
    else:
        exe.run(main, feed=_x(4), fetch_list=[], scope=scope)
        want = "other_key"
    (rec,) = compile_cache.compile_log()[before:]
    assert (rec["cause"], rec["trace_cache"]) == (want, want_cache)
    if case == "other_key":
        assert rec["first_call_s"] == 0 and rec["executable"] == "none"
    else:
        assert rec["first_call_s"] > 0 and rec["jax_trace_s"] > 0


def test_a_warm_step_appends_nothing():
    main, startup, loss = _build(19)
    scope = _started(startup)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    before = compile_cache.compile_log()
    outside = compile_cache.outside_compiles()
    for _ in range(5):
        exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    assert compile_cache.compile_log() == before
    assert compile_cache.outside_compiles() == outside


def test_a_plain_jit_between_two_runs_lands_outside():
    main, startup, loss = _build(20)
    scope = _started(startup)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    log = compile_cache.compile_log()
    before = compile_cache.outside_compiles()

    def plain_reference(x):
        return jnp.tanh(x) * 3.0 + 1.0
    jax.jit(plain_reference)(jnp.ones((5, 3)))
    after = compile_cache.outside_compiles()
    for phase in ("jax_trace", "mlir_lowering", "executable_uncached"):
        was = before.get(phase, {"events": 0, "seconds": 0.0})
        assert after[phase]["events"] > was["events"]
        assert after[phase]["seconds"] > was["seconds"]
    exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    assert compile_cache.compile_log() == log


def test_a_jit_nested_in_an_ops_compute_is_not_counted_twice(monkeypatch):
    """A ``jax.jit`` inside the step's trace fires its own trace event
    inside the step's: the record holds the step's event alone, and the
    nested one reaches neither the record's sum nor ``outside``."""
    relu = registry.get_op_def("relu")
    real = relu.compute

    def slow_inner(x):
        time.sleep(0.05)                # trace time, seen by both events
        return jnp.maximum(x, 0)

    def compute(ins, *rest):
        return real({k: [jax.jit(slow_inner)(v) for v in vs]
                     for k, vs in ins.items()}, *rest)
    monkeypatch.setattr(relu, "compute", compute)
    raw = []

    def listener(event, duration, fun_name=None, **_):
        if event.endswith("jaxpr_trace_duration"):
            raw.append((fun_name, duration))
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        main, startup, loss = _build(21)
        scope = _started(startup)
        before = compile_cache.outside_compiles().get(
            "jax_trace", {"seconds": 0.0})["seconds"]
        fluid.Executor(fluid.CPUPlace()).run(
            main, feed=_x(4), fetch_list=[loss], scope=scope)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    rec = compile_cache.compile_log()[-1]
    own = [d for n, d in raw if n == rec["name"]]
    nested = [d for n, d in raw if n == "slow_inner"]
    assert len(own) == 1 and nested and sum(nested) >= 0.05
    assert rec["jax_trace_s"] == own[0] >= sum(nested)
    assert rec["jax_trace_s"] + rec["lowering_s"] + rec["executable_s"] \
        <= rec["first_call_s"]
    grew = compile_cache.outside_compiles()["jax_trace"]["seconds"] - before
    assert grew < 0.05


def test_parallel_executor_closes_a_pt_pe_record():
    main, startup, loss = _build(22)
    scope = _started(startup)
    mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    exe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                 mesh=mesh, scope=scope)
    assert exe.device_count == 4
    exe.run([loss], feed=_x(8))
    rec = compile_cache.compile_log()[-1]
    assert rec["name"] == "pt_pe_" + compile_cache.program_label(main)
    assert rec["executor"] == "parallel_executor"
    assert (rec["cause"], rec["trace_cache"]) == ("first", "miss")
    assert rec["lowering_s"] > 0 and rec["executable_s"] > 0
    assert rec["unaccounted_s"] >= 0
    n = len(compile_cache.compile_log())
    exe.run([loss], feed=_x(8))
    assert len(compile_cache.compile_log()) == n


def test_count_compiles_deltas_are_what_they_were():
    main, startup, loss = _build(23)
    scope = _started(startup)
    exe = fluid.Executor(fluid.CPUPlace())
    with compile_cache.count_compiles() as cold:
        exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    d = cold()
    assert set(d) == {"lowerings", "jax_lowerings", "jax_lowering_seconds",
                      "jax_backend_compiles", "jax_backend_compile_seconds",
                      "persistent_cache_hits", "persistent_cache_misses"}
    assert d["lowerings"] == 1
    # the step, and the eager key programs of the executor's first step
    assert d["jax_lowerings"] >= 1 and d["jax_backend_compiles"] >= 1
    rec = compile_cache.compile_log()[-1]
    assert d["jax_lowering_seconds"] >= rec["lowering_s"] > 0
    assert d["jax_backend_compile_seconds"] >= rec["executable_s"] > 0
    with compile_cache.count_compiles() as warm:
        for _ in range(3):
            exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    assert {k: v for k, v in warm().items() if v} == {}


def test_the_listeners_start_with_the_first_record_not_count_compiles():
    """A fresh process that never enters ``count_compiles()`` still fills
    its records and its ``outside`` bucket."""
    code = (
        "import json, numpy as np, jax, jax.numpy as jnp\n"
        "import paddle_tpu as fluid\n"
        "from paddle_tpu import compile_cache\n"
        "x = fluid.layers.data('x', shape=[4])\n"
        "loss = fluid.layers.mean(fluid.layers.fc(x, size=3))\n"
        "fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)\n"
        "exe = fluid.Executor(fluid.CPUPlace())\n"
        "exe.run(fluid.default_startup_program())\n"
        "exe.run(feed={'x': np.ones((2, 4), 'float32')}, fetch_list=[loss])\n"
        "jax.jit(lambda v: v * 2)(jnp.ones(3))\n"
        "print(json.dumps({'log': compile_cache.compile_log(),\n"
        "                  'outside': compile_cache.outside_compiles()}))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), check=True,
        stdout=subprocess.PIPE).stdout
    got = json.loads(out.strip().splitlines()[-1])
    assert [r["cause"] for r in got["log"]] == ["first", "first"]
    assert all(r["lowering_s"] > 0 and r["executable_s"] > 0
               and r["executable"] == "uncached" for r in got["log"])
    assert got["log"][1]["build"].keys() == {"append_backward", "minimize"}
    assert got["outside"]["executable_uncached"]["events"] >= 1


def test_a_persistent_cache_tells_compiled_from_read(tmp_path):
    """Two processes on one cache directory: the first compiles and
    writes, the second reads — in the records and, for what ran before
    any record (listening starts when the cache is turned on), outside."""
    code = (
        "import json, numpy as np, jax, jax.numpy as jnp\n"
        "import paddle_tpu as fluid\n"
        "from paddle_tpu import compile_cache\n"
        "compile_cache.enable_persistent_cache(chip_entry=True)\n"
        "jax.jit(lambda v: jnp.tanh(v) @ v.T)(jnp.ones((7, 5)))\n"
        "before = compile_cache.outside_compiles()\n"
        "x = fluid.layers.data('x', shape=[4])\n"
        "loss = fluid.layers.mean(fluid.layers.fc(x, size=3))\n"
        "fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)\n"
        "exe = fluid.Executor(fluid.CPUPlace())\n"
        "exe.run(fluid.default_startup_program())\n"
        "exe.run(feed={'x': np.ones((2, 4), 'float32')}, fetch_list=[loss])\n"
        "print(json.dumps({'log': compile_cache.compile_log(),\n"
        "                  'before': before}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, text=True, timeout=300,
            env=env, check=True, stdout=subprocess.PIPE).stdout
        runs.append(json.loads(out.strip().splitlines()[-1]))
    cold, warm = runs
    assert [r["executable"] for r in cold["log"]] == ["compiled"] * 2
    assert [r["executable"] for r in warm["log"]] == ["read"] * 2
    assert cold["before"]["executable_compiled"]["events"] >= 1
    assert "executable_compiled" not in warm["before"]
    assert warm["before"]["executable_read"]["events"] >= 1
    for a, b in zip(cold["log"], warm["log"]):
        assert (a["name"], a["cause"]) == (b["name"], b["cause"])


@pytest.mark.parametrize("amp", [False, True], ids=["fp32", "amp"])
def test_build_passes_cost_the_program_they_rewrote_once(amp):
    main, startup, loss = _build(24, amp=amp)
    passes = {"append_backward", "minimize"} | (
        {"mixed_precision"} if amp else set())
    assert set(main._build_s) == passes
    assert all(v >= 0 for v in main._build_s.values())
    # a copy starts at nothing: an evaluation clone never repeats them
    clone = main.clone(for_test=True)
    assert clone._build_s == {} and set(main._build_s) == passes
    built = sum(main._build_s.values())
    scope = _started(startup)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
    exe.run(main, feed=_x(6), fetch_list=[loss], scope=scope)
    first, again = compile_cache.compile_log()[-2:]
    assert set(first["build"]) == passes
    assert first["build_s"] == pytest.approx(built) and built > 0
    assert (again["build_s"], again["build"]) == (0.0, {})
    assert main._build_s == {}


def test_build_pass_spans_nest_and_count_their_own_seconds(monkeypatch):
    """``outer``'s own seconds are its whole less ``inner``'s, on a clock
    the test turns itself: a loaded host (six xdist workers) stretches a
    ``sleep`` past any bound on the wall clock."""
    import types

    now = [0]
    monkeypatch.setattr(profiler, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: now[0]))

    def passes(ms):
        now[0] += ms * 1_000_000

    main = fluid.Program()
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    try:
        with profiler.build_pass(main, "outer"):
            passes(20)
            with profiler.build_pass(main, "inner"):
                passes(30)
            passes(5)
        events = {e["name"]: e for e in profiler._events}
        names = [e["name"] for e in profiler._events]
    finally:
        profiler.stop_profiler(profile_path=None)
        profiler.reset_profiler()
    # the inner span closes first and lies inside the outer one
    assert names == ["build/inner", "build/outer"]
    inner, outer = events["build/inner"], events["build/outer"]
    assert (inner["dur"], outer["dur"]) == (30e3, 55e3)        # microseconds
    assert outer["ts"] <= inner["ts"] \
        and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # own seconds: the whole less what a nested pass counted
    assert main._build_s == {"inner": pytest.approx(0.030),
                             "outer": pytest.approx(0.025)}
    # a second pass of the same name adds to it
    with profiler.build_pass(main, "inner"):
        passes(10)
    assert main._build_s["inner"] == pytest.approx(0.040)


def test_a_closed_record_is_spans_under_a_profiler_session():
    main, startup, loss = _build(25)
    scope = _started(startup)
    exe = fluid.Executor(fluid.CPUPlace())
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    try:
        exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
        exe.run(main, feed=_x(4), fetch_list=[loss], scope=scope)
        events = list(profiler._events)
    finally:
        profiler.stop_profiler(profile_path=None)
        profiler.reset_profiler()
    rec = compile_cache.compile_log()[-1]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    cold = max(by_name["executor/compile"], key=lambda e: e["dur"])
    for span, field in (("executor/jax_trace", "jax_trace_s"),
                        ("executor/mlir_lowering", "lowering_s"),
                        ("executor/executable", "executable_s")):
        (e,) = by_name[span]
        assert e["dur"] == pytest.approx(rec[field] * 1e6)
        assert e["args"] == {"module": rec["name"], "cause": "first"}
        # back-dated into the cold call's span (jax's clock is the wall's:
        # a millisecond of slack)
        assert e["ts"] >= cold["ts"] - 1e3
        assert e["ts"] + e["dur"] <= cold["ts"] + cold["dur"] + 1e3
    assert len(by_name["executor/dispatch"]) == 1


def test_kernel_traces_give_their_seconds_to_the_open_record():
    main, startup, loss = _build(26)
    compile_cache.open_record("executor", "exe", main, "first")
    compile_cache.note_kernel_trace("streamed_attention", "sites")
    compile_cache.note_kernel_trace("streamed_attention", "traces", 0.25)
    compile_cache.note_kernel_trace("grouped_experts", "traces", 0.5)
    rec = compile_cache.close_record(None)
    assert (rec["kernel_traces"], rec["kernel_trace_s"]) == (2, 0.75)
    assert compile_cache.stats()["kernel_traces"] == {
        "streamed_attention": {"sites": 1, "traces": 1},
        "grouped_experts": {"sites": 0, "traces": 1}}
    # with no record open the seconds are nobody's
    compile_cache.note_kernel_trace("grouped_experts", "traces", 0.125)
    assert compile_cache.outside_compiles()["kernel_trace"] == {
        "events": 1, "seconds": 0.125}
    assert compile_cache.close_record(None) is None


@pytest.mark.parametrize("reset", [False, True])
def test_a_record_left_open_is_closed_by_the_threads_next(reset):
    """``cost_analysis`` closes its own; a lowering that raised leaves its
    record open, and the next one on the thread closes it with no call —
    unless ``reset_stats`` came between: it forgets the open record with
    the closed ones (a later test's log does not start with this one's)."""
    main, startup, loss = _build(27)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(RuntimeError, match="startup program"):
        exe.run(main, feed=_x(4), fetch_list=[loss], scope=fluid.Scope())
    assert compile_cache.compile_log() == []
    if reset:
        compile_cache.reset_stats()
        _started(startup)
        (only,) = compile_cache.compile_log()
        assert only["first_call_s"] > 0
        return
    scope = _started(startup)
    failed, start_rec = compile_cache.compile_log()
    assert failed["fingerprint"] == \
        compile_cache.program_fingerprint(main)[:12]
    assert (failed["first_call_s"], failed["executable"]) == (0.0, "none")
    assert start_rec["first_call_s"] > 0
    cost = exe.cost_analysis(main, feed=_x(4), fetch_list=[loss],
                             scope=scope)
    assert cost
    rec = compile_cache.compile_log()[-1]
    assert rec["name"].startswith("pt_exe_") and rec["executable_s"] > 0
    assert rec["first_call_s"] >= rec["lowering_s"] + rec["executable_s"]


def test_the_monitor_log_and_the_program_report_carry_the_record(tmp_path):
    monitor.enable(log_dir=str(tmp_path))
    program_profile.reset()
    main, startup, loss = _build(28)
    scope = _started(startup)
    exe = fluid.Executor(fluid.CPUPlace())
    for rows in (4, 4, 6, 10):
        exe.run(main, feed=_x(rows), fetch_list=[loss], scope=scope)
    fp = compile_cache.program_fingerprint(main)
    (row,) = [r for r in program_profile.report_rows()
              if r["fingerprint"] == fp]
    assert row["lowerings"] == 3
    assert row["cause"] == "first,feed_signature*2"
    mine = [r for r in compile_cache.compile_log()
            if r["fingerprint"] == fp[:12]]
    for col, fields in (("build_s", ("build_s",)),
                        ("trace_s", ("analyze_s", "program_trace_s",
                                     "jax_trace_s")),
                        ("lowering_s", ("lowering_s",)),
                        ("executable_s", ("executable_s",))):
        assert row[col] == pytest.approx(
            sum(r[f] for r in mine for f in fields), abs=1e-6)
    table = program_profile.render_table([row])
    assert "first,feed_signature*2" in table and "lower(s)" in table
    monitor.disable()

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import program_report
    finally:
        sys.path.pop(0)
    records = program_report.load_records(str(tmp_path))
    assert sum(r.get("event") == "compile_record" for r in records) >= 4
    (offline,) = [r for r in program_report.rows_from_records(records)
                  if r["fingerprint"] == fp]
    for col in ("lowerings", "cause", "build_s", "trace_s", "lowering_s",
                "executable_s", "op_work"):
        assert offline[col] == row[col]
    # the products' work, counted at lowering: two fc layers, each forward
    # and dW, the second's dX too (the newest record's: 10 rows)
    assert offline["op_work"] == {
        "mul:fwd": [2 * 10 * 8 * 28 + 2 * 10 * 28, 4 * (
            10 * 8 + 8 * 28 + 10 * 28 + 10 * 28 + 28 + 10)],
        "mul_grad:dw": [2 * 10 * 8 * 28 + 2 * 10 * 28, 4 * (
            10 * 8 + 8 * 28 + 10 * 28 + 10 * 28 + 28 + 10)],
        "mul_grad:dx": [2 * 10 * 28, 4 * (10 * 28 + 28 + 10)]}
    assert "mul_grad:dx 5.6e-10/1.272e-06" in program_profile.render_table(
        [offline])
