"""The five per-layer metrics that move ``setup_s`` (ISSUE 36): each reads
the program's compile records, and only for a traced run on the chip."""

import json

import numpy as np
import pytest

from bm_util import CELLS, ROOT, check_cell

import paddle_tpu as fluid
from benchmark import harness
from benchmark.metrics import _setup
from paddle_tpu import compile_cache

with open(ROOT + "/BENCHMARK.json") as f:
    BENCH = json.load(f)
NAMES = ("setup_build_s", "setup_program_trace_s", "setup_lowering_s",
         "setup_executable_s", "setup_executables_compiled")
TRACED = {"kind": "train", "trace": {"busy_s": 1.0, "window_s": 1.0},
          "traced_steps": 5}


def _lower_a_tiny_step():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=5, act="tanh"))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed={"x": np.ones((3, 6), "float32")},
            fetch_list=[loss], scope=scope)


def test_benchmark_json_holds_the_five_cells_and_five_metrics_more():
    """What ``test_bm_ouro_cell.py``'s pin meant, of the metrics there are
    now: the five cells and four configurations unchanged, the 26 per-layer
    metrics that were there first and unchanged (PR 33's three their last),
    then the five that move ``setup_s``, each in every cell."""
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == [
        "transformer_base.train_nmt", "transformer_base.train_nmt_dp4",
        "keye_vl2_30b_a3b.train_longdoc_8k", "joyai_llm_flash.train_mtp_8k",
        "ouro_2_6b.train_loop_4k"]
    assert [c["name"] for c in BENCH["configs"]] == [
        "transformer_base", "keye_vl2_30b_a3b", "joyai_llm_flash",
        "ouro_2_6b"]
    assert [w["chips"] for w in BENCH["workloads"]] == [1, 4, 1, 1, 1]
    assert BENCH["run_seconds"] == 51
    assert [(m["name"], m["bound"]) for m in BENCH["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("setup_s", 0.1)]
    older, last = BENCH["per_layer"][:-5], BENCH["per_layer"][-5:]
    assert len(older) == 26
    assert [m["moves"] for m in older] == ["train_tokens_per_s"] * 26
    assert tuple(m["name"] for m in older[-3:]) == (
        "plain_attention_roofline", "device_ms_per_step.exit_gate",
        "device_ms_per_step.rms_norm")
    for m in older[-3:]:
        assert (m["source"], m["layer"], m["workloads"]) == (
            "device_trace", "op kernels", [cells[-1]])
    assert tuple(m["name"] for m in last) == NAMES
    for m, (unit, source, layer) in zip(last, (
            ("s", "program_span", "step lowering"),) * 3 + (
            ("s", "program_span", "compile caches"),
            ("count", "program_counter", "compile caches"))):
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": "setup_s",
                     "workloads": cells}
    # what each cell reported it still reports, and these five besides
    assert {m["name"] for m in BENCH["per_layer"]
            if cells[-1] in m["workloads"]} - set(NAMES) == {
        "dispatch_ms.train", "compiles_in_window.train",
        "train_step_roofline", "device_idle_share.train",
        "peak_hbm_gb.train", "device_ms_per_step.matmul",
        "device_ms_per_step.attention", "device_ms_per_step.loss",
        "device_ms_per_step.embedding", "device_ms_per_step.optimizer",
        "device_ms_per_step.elementwise", "device_unscoped_share",
        "host_self_ms_per_step.train", "host_wait_ms_per_step.train",
        "plain_attention_roofline", "device_ms_per_step.exit_gate",
        "device_ms_per_step.rms_norm"}
    assert len([m for m in older if cells[3] in m["workloads"]]) == 17


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_reads_the_records_of_a_traced_run_only(name, capsys):
    compile_cache.reset_stats()
    read = harness.load_reader(name, ROOT).read
    # nothing lowered yet: nothing to read, traced or not
    assert read(dict(TRACED)) is None
    _lower_a_tiny_step()
    for facts in ({}, {"kind": "train"},
                  # what a CPU --check hands the readers
                  {"kind": "train", "compiles_in_window": 0,
                   "tokens_per_step": 12, "padded_flops_per_step": 1e6}):
        assert read(dict(facts)) is None
    value = read(dict(TRACED))
    log = compile_cache.compile_log()
    assert len(log) == 2
    if name == "setup_executables_compiled":
        # the tests run with no persistent cache: compiled, kept nowhere
        assert value == 0 and isinstance(value, int)
        assert {r["executable"] for r in log} == {"uncached"}
    else:
        assert isinstance(value, float) and value > 0
        field = {"setup_build_s": "build_s",
                 "setup_lowering_s": "lowering_s",
                 "setup_executable_s": "executable_s"}.get(name)
        if field:
            assert value == sum(r[field] for r in log)
        else:
            assert value == sum(r["analyze_s"] + r["program_trace_s"]
                                + r["jax_trace_s"] for r in log)
    total = sum(harness.load_reader(n, ROOT).read(dict(TRACED))
                for n in NAMES[:4])
    assert total < sum(r["build_s"] + r["analyze_s"] + r["program_trace_s"]
                       + r["first_call_s"] for r in log)


def test_the_first_reader_logs_the_records_and_the_outside_bucket(
        monkeypatch, capsys):
    compile_cache.reset_stats()
    _lower_a_tiny_step()
    monkeypatch.setattr(_setup, "_LOGGED", [])
    for name in NAMES:
        harness.load_reader(name, ROOT).read(dict(TRACED))
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[benchmark setup] ")]
    rows = [ln for ln in lines if " cause " in ln]
    assert len(rows) == 2 and all("pt_exe_" in ln and "unaccounted" in ln
                                  for ln in rows)
    assert sum("build: append_backward" in ln for ln in lines) == 1
    assert sum(ln.startswith("[benchmark setup] outside") for ln in lines) \
        == 1


def test_a_check_run_reports_none_of_them():
    result = check_cell(CELLS[0])
    assert result["correct"] is True
    assert not set(NAMES) & set(result["metrics"])


def test_a_program_with_no_compile_log_reads_as_nothing(monkeypatch):
    """Over the parent commit's program the readers find nothing and do
    not raise: the line leaves the metrics out."""
    _lower_a_tiny_step()
    monkeypatch.delattr(compile_cache, "compile_log")
    for name in NAMES:
        assert harness.load_reader(name, ROOT).read(dict(TRACED)) is None
