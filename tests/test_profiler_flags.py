"""Profiler wiring + numeric-debugging flag tests (SURVEY §5: tracing,
race/numeric debugging).  The reference wraps every op run in RecordEvent
(operator.cc:153) and exports chrome traces (tools/timeline.py); here the
executor step/compile and trainer step are the spanned units, and
FLAGS_check_nan_inf raises on non-finite step outputs (operator.cc:717)."""

import json

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler


def _build_mlp():
    x = fluid.layers.data("x", shape=[4])
    y = fluid.layers.fc(x, size=3, act="relu")
    loss = fluid.layers.mean(y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def test_executor_spans_appear_in_chrome_trace(tmp_path, fresh_programs):
    loss = _build_mlp()
    path = str(tmp_path / "trace.json")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    x = np.random.rand(8, 4).astype("float32")
    with profiler.profiler("All", profile_path=path):
        for _ in range(3):
            exe.run(feed={"x": x}, fetch_list=[loss])
    trace = json.load(open(path))
    names = [e["name"] for e in trace["traceEvents"]]
    assert "executor/compile" in names
    assert names.count("executor/run") == 3
    for e in trace["traceEvents"]:
        # spans are X-phase with real durations; the only other phase
        # is the M-phase process/thread-name metadata
        assert e["ph"] in ("X", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0


def test_record_event_outside_profiler_is_dropped(fresh_programs):
    profiler.reset_profiler()
    with profiler.RecordEvent("unprofiled"):
        pass
    with profiler._events_lock:
        assert not profiler._events


def test_span_straddling_stop_profiler_is_kept(fresh_programs):
    """__enter__ latches the enabled state: a span started under the
    session is recorded even if stop_profiler lands before __exit__
    (previously __exit__ decided post-hoc and dropped it)."""
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    ev = profiler.RecordEvent("straddle")
    ev.__enter__()
    profiler.stop_profiler(profile_path=None)
    ev.__exit__(None, None, None)
    with profiler._events_lock:
        names = [e["name"] for e in profiler._events]
    assert "straddle" in names
    # and the inverse: started while disabled, exited under a session
    ev2 = profiler.RecordEvent("pre_session")
    ev2.__enter__()
    profiler.start_profiler("CPU")
    ev2.__exit__(None, None, None)
    profiler.stop_profiler(profile_path=None)
    with profiler._events_lock:
        names = [e["name"] for e in profiler._events]
    assert "pre_session" not in names


def _fabricate_events():
    """Deterministic event set: 'a' called 3x (total 3ms, max 1.5ms),
    'b' called once (total 10ms)."""
    profiler.reset_profiler()
    with profiler._events_lock:
        for dur in (500.0, 1000.0, 1500.0):
            profiler._events.append({"name": "a", "ts": 0.0, "dur": dur,
                                     "ph": "X", "pid": 1, "tid": 1})
        profiler._events.append({"name": "b", "ts": 0.0, "dur": 10000.0,
                                 "ph": "X", "pid": 1, "tid": 1})


def test_print_summary_sorted_key_variants(fresh_programs, capsys):
    _fabricate_events()
    first_row = {}
    for key in (None, "total", "calls", "ave", "max"):
        profiler._print_summary(key)
        out = capsys.readouterr().out.splitlines()
        assert out[0].startswith("Event")
        first_row[key] = out[1].split()[0]
    # total/avg/max rank the long single span first; calls ranks 'a'
    assert first_row[None] == "b"
    assert first_row["total"] == "b"
    assert first_row["ave"] == "b"
    assert first_row["max"] == "b"
    assert first_row["calls"] == "a"
    # summarize_events is the same formatter the offline CLI prints
    with profiler._events_lock:
        events = list(profiler._events)
    profiler._print_summary("total")
    assert capsys.readouterr().out.strip() == \
        profiler.summarize_events(events, "total")
    profiler.reset_profiler()


def test_mark_event_counting(fresh_programs, capsys):
    profiler.reset_profiler()
    profiler.mark_event("cache/hit")          # outside a session: dropped
    profiler.start_profiler("CPU")
    for _ in range(3):
        profiler.mark_event("cache/hit")
    profiler.mark_event("cache/miss")
    profiler.stop_profiler(profile_path=None)
    out = capsys.readouterr().out
    row = [ln for ln in out.splitlines() if ln.startswith("cache/hit")]
    assert row and row[0].split()[2] == "3"   # calls column counts marks
    with profiler._events_lock:
        marks = [e for e in profiler._events if e["name"] == "cache/hit"]
    assert len(marks) == 3 and all(e["dur"] == 0.0 for e in marks)
    profiler.reset_profiler()


def test_chrome_trace_thread_metadata(tmp_path, fresh_programs):
    """export_chrome_tracing labels worker threads with M-phase
    process_name/thread_name metadata instead of raw tids."""
    import threading

    profiler.reset_profiler()
    profiler.start_profiler("CPU")

    def worker():
        with profiler.RecordEvent("worker_span"):
            pass

    t = threading.Thread(target=worker, name="prefetch-producer-0")
    t.start()
    t.join()
    with profiler.RecordEvent("main_span"):
        pass
    path = str(tmp_path / "trace.json")
    profiler.stop_profiler(profile_path=path)
    trace = json.load(open(path))["traceEvents"]
    meta = [e for e in trace if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "paddle_tpu" for e in meta)
    tnames = {e["args"]["name"] for e in meta
              if e["name"] == "thread_name"}
    assert "prefetch-producer-0" in tnames
    # every span's tid has a thread_name metadata entry
    span_tids = {e["tid"] for e in trace if e["ph"] == "X"}
    meta_tids = {e["tid"] for e in meta if e["name"] == "thread_name"}
    assert span_tids <= meta_tids
    profiler.reset_profiler()


def test_check_nan_inf_catches_injected_nan(fresh_programs):
    x = fluid.layers.data("x", shape=[2])
    out = fluid.layers.log(x)          # log(-1) -> nan
    exe = fluid.Executor(fluid.CPUPlace())
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        exe.run(feed={"x": np.ones((2, 2), "float32")}, fetch_list=[out])
        with pytest.raises(RuntimeError, match="contains nan"):
            exe.run(feed={"x": -np.ones((2, 2), "float32")},
                    fetch_list=[out])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})
    # flag off: silently returns the nan (reference default behavior)
    (v,) = exe.run(feed={"x": -np.ones((2, 2), "float32")},
                   fetch_list=[out])
    assert np.isnan(v).all()


def test_check_nan_inf_names_state_var(fresh_programs):
    x = fluid.layers.data("x", shape=[2])
    h = fluid.layers.fc(x, size=2, act=None)
    loss = fluid.layers.mean(fluid.layers.log(h))
    fluid.optimizer.SGD(learning_rate=1e30).minimize(loss)  # diverges
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(RuntimeError, match="check_nan_inf"):
            for _ in range(5):
                exe.run(feed={"x": np.random.rand(4, 2).astype("float32")},
                        fetch_list=[loss])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


def test_flags_api_roundtrip_and_unknown():
    fluid.set_flags({"FLAGS_benchmark": True})
    assert fluid.get_flags("FLAGS_benchmark")["FLAGS_benchmark"] is True
    fluid.set_flags({"benchmark": False})   # bare spelling accepted
    assert fluid.get_flags(["benchmark"])["benchmark"] is False
    with pytest.raises(KeyError):
        fluid.set_flags({"FLAGS_no_such_flag": 1})
    with pytest.raises(KeyError):
        fluid.get_flags("nope")


def test_trace_summary_cli_offline(tmp_path, fresh_programs):
    """tools/trace_summary.py summarizes an exported chrome trace
    offline, printing the same per-name table stop_profiler prints."""
    import os
    import subprocess
    import sys

    loss = _build_mlp()
    path = str(tmp_path / "trace.json")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    x = np.random.rand(8, 4).astype("float32")
    with profiler.profiler("CPU", profile_path=path):
        for _ in range(2):
            exe.run(feed={"x": x}, fetch_list=[loss])
            profiler.mark_event("reader/epoch_end")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "trace_summary.py"),
         path, "--sorted_key", "calls"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120, check=True).stdout
    lines = out.splitlines()
    # the export's correlation id leads, then the live-format table
    assert lines[0].startswith("run_id ")
    assert lines[1].startswith("Event")
    assert any(ln.startswith("executor/run") for ln in lines)
    # row format matches the live summary: name total calls avg max
    row = [ln for ln in lines if ln.startswith("executor/run")][0]
    assert row.split()[2] == "2"
    # marks are tallied as counter totals, not zero-ms span rows
    assert any(ln.startswith("mark/reader/epoch_end") for ln in lines)
    assert not any(ln.startswith("reader/") for ln in lines)


def test_trace_summary_cli_top_and_metadata_only(tmp_path):
    """--top caps the table; a trace whose threads carry only M-phase
    metadata events (or events missing dur) must not crash."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(root, "tools", "trace_summary.py")

    many = {"traceEvents": [
        {"name": "span%d" % i, "ph": "X", "ts": 0.0, "dur": 10.0 + i,
         "pid": 1, "tid": 1} for i in range(10)]}
    p1 = str(tmp_path / "many.json")
    json.dump(many, open(p1, "w"))
    out = subprocess.run(
        [sys.executable, tool, p1, "--top", "3"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120, check=True).stdout
    rows = [ln for ln in out.splitlines() if ln.startswith("span")]
    assert len(rows) == 3

    meta_only = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1,
         "args": {"name": "paddle_tpu"}},
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 7,
         "args": {"name": "prefetch-producer"}},
        {"ph": "X", "ts": 0.0, "pid": 1, "tid": 7},   # nameless stray
    ]}
    p2 = str(tmp_path / "meta.json")
    json.dump(meta_only, open(p2, "w"))
    res = subprocess.run(
        [sys.executable, tool, p2],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120)
    assert res.returncode == 0, res.stderr
    assert "metadata-only" in res.stdout


def test_trainer_step_spans(tmp_path, fresh_programs):
    from paddle_tpu.contrib import Trainer

    def train_func():
        x = fluid.layers.data("x", shape=[4])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        pred = fluid.layers.fc(x, size=2, act="softmax")
        return fluid.layers.mean(fluid.layers.cross_entropy(pred, label))

    def optimizer_func():
        return fluid.optimizer.SGD(learning_rate=0.01)

    def reader():
        rng = np.random.RandomState(0)
        for _ in range(4):
            yield rng.rand(4).astype("float32"), np.array([1], "int64")

    trainer = Trainer(train_func=train_func, place=fluid.CPUPlace(),
                      optimizer_func=optimizer_func)
    path = str(tmp_path / "t.json")
    with profiler.profiler(profile_path=path):
        trainer.train(num_epochs=1, event_handler=lambda e: None,
                      reader=fluid.batch(reader, batch_size=2),
                      feed_order=["x", "label"])
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert names.count("trainer/step") == 2
    assert "executor/run" in names
