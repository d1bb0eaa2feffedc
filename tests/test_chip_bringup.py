"""Bring-up contracts that hold on the CPU: nothing that should run on the
chip can quietly run somewhere else.

``chip_smoke.py`` itself only runs on a TPU; what this file pins is the
behaviour around it — places that refuse to fall back, the one cache-
directory resolver, the peaks table that yields no MFU for an unknown
device, the smoke's refusal to start without a chip, and the bench
ladder's parent staying off the JAX backend."""

import os
import subprocess
import sys
import types

import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu import executor as executor_mod
from paddle_tpu.monitor import program_profile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable] + (
        [code_or_script] if code_or_script.endswith(".py")
        else ["-c", code_or_script])
    return subprocess.run(cmd, cwd=REPO, env=env, text=True, timeout=120,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def test_tpu_place_never_resolves_to_another_device(monkeypatch):
    # CPU-only backend: an error, not the host CPU
    with pytest.raises(RuntimeError, match="no accelerator"):
        fluid.TPUPlace(0).jax_device()
    # the library default still picks the CPU where there is no chip
    assert isinstance(executor_mod.default_place(), fluid.CPUPlace)
    # one chip present: id 1 is an error, not chip 0 again
    chip = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(executor_mod.jax, "local_devices", lambda: [chip])
    assert fluid.TPUPlace(0).jax_device() is chip
    with pytest.raises(RuntimeError, match="out of range"):
        fluid.TPUPlace(1).jax_device()
    assert isinstance(executor_mod.default_place(), fluid.TPUPlace)


def test_cache_resolver_precedence(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    resolve = compile_cache.persistent_cache_dir
    assert resolve() is None                          # bare import: off
    assert resolve("/flag/dir") == "/flag/dir"
    fixed = resolve(chip_entry=True)
    assert fixed == compile_cache.CHECKOUT_CACHE_DIR
    assert os.path.dirname(fixed) == REPO             # inside the checkout
    assert resolve("/flag/dir", chip_entry=True) == "/flag/dir"
    # the environment wins over the flag, the CLI and the fixed path
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/env/dir")
    assert resolve() == "/env/dir"
    assert resolve("/flag/dir", chip_entry=True) == "/env/dir"


def test_bare_import_leaves_the_cache_off():
    r = _run("import jax, paddle_tpu\n"
             "from paddle_tpu import compile_cache\n"
             "print(jax.config.jax_compilation_cache_dir,"
             " compile_cache.stats()['persistent_dir'])",
             drop=("JAX_COMPILATION_CACHE_DIR", "FLAGS_compile_cache_dir"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["None", "None"]


def test_unknown_device_kind_yields_no_mfu():
    assert program_profile.bf16_peak_tflops("cpu") is None
    assert program_profile.DEVICE_PEAKS["TPU v5 lite"]["hbm_gbps"] == 819.0
    acct = {"fp": {"steps": 10, "wall_s": 1.0, "examples": 10,
                   "kind": "executor"}}

    def mfu(kind, **kw):
        prof = program_profile.ProgramProfile(
            "fp", (), "executor", flops=1e12, device_kind=kind)
        (row,) = program_profile.report_rows(
            profiles_by_fp={"fp": prof}, acct_by_fp=acct, **kw)
        return row["mfu"]
    assert mfu("cpu") is None                      # not a default peak
    assert mfu(None) is None
    assert mfu("TPU v5 lite") == pytest.approx(10 / 197.0, rel=1e-3)
    assert mfu("cpu", peak_tflops=100.0) == pytest.approx(0.1)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = _run(os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode == 2, (r.returncode, r.stderr[-2000:])
    assert "no TPU" in r.stderr
    assert r.stdout == ""                          # no result line


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver parses the last stdout line: exactly ``ok`` and
    ``device`` = {platform, kind (text), count (a whole number)}; the
    per-phase report goes to stderr, not into this object."""
    r = _run("import types, chip_smoke\n"
             "d = types.SimpleNamespace(platform='tpu',"
             " device_kind='TPU v5 lite')\n"
             "print(chip_smoke.result_line([d]))\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout == ('{"ok": true, "device": {"platform": "tpu", '
                        '"kind": "TPU v5 lite", "count": 1}}\n')


def test_bench_ladder_parent_stays_off_the_jax_backend():
    """A chip belongs to one process: the ladder's parent resolves its
    device (auto = the chip, never the CPU) and walks the rung list
    without initialising a backend, so every rung child can have it."""
    r = _run("import sys\n"
             "sys.argv = ['bench.py', '--smoke', '--budget-seconds', '0']\n"
             "import bench\n"
             "assert bench._resolve_device('auto') == 'tpu'\n"
             "assert bench._resolve_device('cpu') == 'cpu'\n"
             "bench.main()\n"
             "from jax._src import xla_bridge\n"
             "assert not xla_bridge.backends_are_initialized()\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert '"omitted": ["mlp", "mlp_with_reader"]' in r.stdout
