"""BENCHMARK.json against the contract, every data file loads, every name
resolves; the result line's keys; the timed path refuses to run without a
TPU."""

import json
import os
import re
import subprocess
import sys

import pytest

from bm_util import CELLS, ROOT, SERVE_CELL, with_serve_cell

from benchmark import harness

BENCH = harness.load_benchmark(ROOT)
# with the serve cell's entries, which a later PR adds: their files are
# under benchmark/ already and are held to the same rules
FULL = with_serve_cell(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LAYERS = {"load generator", "step lowering", "compile caches",
          "serving scheduler", "serving engine", "KV cache", "op kernels",
          "mesh runtime", "device"}
METRICS = FULL["end_to_end"] + FULL["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark", "tests/benchmark_suite"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_benchmark_json_holds_the_training_cells_only():
    assert tuple(w["name"] for w in BENCH["workloads"]) == CELLS
    assert [c["name"] for c in BENCH["configs"]] == ["transformer_base"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("conf", FULL["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and len(conf["why"]) <= 200
    assert conf["file"].startswith("benchmark/configs/")
    data = json.load(open(os.path.join(ROOT, conf["file"])))
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    # no width is ever reduced
    for key in conf["reduced"]:
        assert not re.search(r"(_dim|_rank|d_model|d_inner|hidden|head)",
                             key)
    for kind, key in (("models", "builder"), ("reference", "reference"),
                      ("flops", "flops")):
        harness.load_module(kind, data[key], ROOT)
    assert all(v is not None for v in data["limits"].values())
    assert data["control_precision"] in ("fp8", "int8", "bf16")
    assert any(w["config"] == conf["name"] for w in FULL["workloads"])


@pytest.mark.parametrize("cell", FULL["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_traffic_file(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == "%s.%s" % (cell["config"], cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    for tiny in (False, True):
        _, cfg, traffic = harness.resolve_cell(FULL, cell["name"],
                                               tiny=tiny, root=ROOT)
        gen = harness.load_module("generators", traffic["generator"], ROOT)
        assert callable(gen.run)
    reported = [m for m in FULL["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert {"setup_s"} < {m["name"] for m in reported}
    assert any(cell["name"] in m["workloads"] for m in FULL["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_resolves(metric):
    per_layer = metric in FULL["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) <= allowed and allowed - set(metric) <= {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in FULL["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if per_layer:
        assert metric["layer"] in LAYERS
        moved = {m["name"]: m for m in FULL["end_to_end"]}[metric["moves"]]
        # each listed cell reports the end-to-end metric this one moves
        assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
        assert callable(harness.load_reader(metric["name"], ROOT).read)
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1


def test_names_are_unique_and_every_file_under_metrics_is_read():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in FULL[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    files = {f for f in os.listdir(os.path.join(ROOT, "benchmark", "metrics"))
             if f.endswith(".py") and not f.startswith("_")}
    assert files == {os.path.basename(
        harness.load_reader(m["name"], ROOT).__file__)
        for m in FULL["per_layer"]}
    # a quantity split over two end-to-end metrics has one reader
    assert harness.load_reader("peak_hbm_gb.train", ROOT).__file__ == \
        harness.load_reader("peak_hbm_gb.serve", ROOT).__file__


@pytest.mark.parametrize("facts", [{}, {"kind": "train"}, {"kind": "serve"}])
def test_a_reader_that_finds_nothing_returns_nothing(facts):
    for m in FULL["per_layer"]:
        assert harness.load_reader(m["name"], ROOT).read(
            dict(facts)) is None


def test_metrics_for_follows_the_workloads_key():
    cell = SERVE_CELL
    e2e = {m["name"] for m in harness.metrics_for(FULL, "end_to_end", cell,
                                                  set())}
    assert e2e == {"request_latency_p50_ms", "request_latency_p95_ms",
                   "serve_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in harness.metrics_for(FULL, "per_layer",
                                                    cell, e2e)}
    assert "decode_tick_ms" in layer and "dispatch_ms.train" not in layer


def test_the_timed_path_refuses_to_run_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        harness.require_chips(1)
    assert e.value.code == 2


def test_the_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
