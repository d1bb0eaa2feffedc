"""Test configuration: force the CPU backend with 8 virtual devices so
multi-chip sharding tests run without TPU hardware (SURVEY.md §4 TPU
translation of the reference's multi-device test strategy)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The suite relies on a COLD compile cache (warm multi-device CPU
# executables were found nondeterministic, PERF.md): a cache directory
# inherited from the environment must not reach jax here.  Tests of the
# cache resolver set the variable themselves.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_programs():
    """Isolate each test: fresh default programs, scope, and name counter
    (the reference achieves this with new Program() per test; we reset the
    singletons)."""
    import paddle_tpu as fluid
    from paddle_tpu import flags, framework, unique_name
    from paddle_tpu.scope import Scope

    # the benchmark's builder sets FLAGS_fast_prng for the process (a
    # benchmark run owns it); an xdist worker goes on to other files,
    # where a leaked rbg PRNG changes every seeded initialisation
    prng, prng_pinned = flags.flag("fast_prng"), flags.pinned("fast_prng")
    old_main = framework.switch_main_program(fluid.Program())
    old_startup = framework.switch_startup_program(fluid.Program())
    old_gen = unique_name.switch()
    scope = Scope()
    with fluid.scope_guard(scope):
        yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    unique_name.switch(old_gen)
    flags.set_flags({"FLAGS_fast_prng": prng}, pin=False)
    flags._restore_pins({"fast_prng": prng_pinned})
    # the per-test unique_name reset makes structurally identical
    # programs from DIFFERENT tests fingerprint-collide in the
    # process-global trace cache; drop it so a monkeypatched op in one
    # test can never serve a stale trace to the next
    from paddle_tpu import compile_cache

    compile_cache.clear()


@pytest.fixture
def no_pallas():
    """``FLAGS_pallas_kernels=False`` — the operator's "no Pallas" — until
    the test ends (an xdist worker goes on to other files)."""
    from paddle_tpu import flags

    flags.set_flags({"FLAGS_pallas_kernels": False})
    yield
    flags.set_flags({"FLAGS_pallas_kernels": True})


# Tests of tests/benchmark_suite/ that state what was true of the benchmark
# when they were written and is not of one with a further configuration; the
# benchmark's own files change in a benchmark PR only, so until one
# restates them they are expected to fail here, and the newest cell's test
# (tests/benchmark_suite/test_bm_mellum_cell.py) asserts what they meant of
# the benchmark there is now.
_RESTATED = {
    "test_bm_contract.py::test_benchmark_json_holds_the_training_cells_only":
        "pins BENCHMARK.json to PR 23's two cells and one configuration; "
        "ISSUE 26 adds keye_vl2_30b_a3b.train_longdoc_8k",
    "test_bm_contract.py::test_configuration_entry_and_file"
    "[keye_vl2_30b_a3b]":
        "reads 'hidden' in the reduced key num_hidden_layers (a depth, and "
        "the published config.json's own key) as a width",
    "test_bm_contract.py::test_configuration_entry_and_file"
    "[joyai_llm_flash]":
        "the same reading of num_hidden_layers; "
        "test_bm_joyai_cell.py::test_configuration_keeps_every_published_"
        "size holds the file to the rest of that test",
    "test_bm_keye_cell.py::test_benchmark_json_holds_the_training_cells":
        "pins BENCHMARK.json to PR 26's three cells and two configurations; "
        "ISSUE 30 adds joyai_llm_flash.train_mtp_8k",
    "test_bm_joyai_cell.py::test_benchmark_json_holds_the_four_training_"
    "cells":
        "pins BENCHMARK.json to PR 30's four cells and three configurations "
        "and wants JoyAI's cell last in every list; ISSUE 33 appends "
        "ouro_2_6b.train_loop_4k after it",
    "test_bm_contract.py::test_configuration_entry_and_file[ouro_2_6b]":
        "the same reading of num_hidden_layers as a width; "
        "test_bm_ouro_cell.py::test_configuration_keeps_every_published_"
        "size holds the file to the rest of that test",
    "test_bm_ouro_cell.py::test_benchmark_json_holds_the_five_training_"
    "cells":
        "wants PR 33's three metrics last in per_layer and pins what its "
        "cell reports; ISSUE 36 appends the five that move setup_s, in "
        "every cell (test_bm_setup_metrics.py::test_benchmark_json_holds_"
        "the_five_cells_and_five_metrics_more says what the pin meant)",
    "test_bm_setup_metrics.py::test_benchmark_json_holds_the_five_cells_"
    "and_five_metrics_more":
        "pins BENCHMARK.json to PR 36's five cells, four configurations "
        "and wants the five set-up metrics last in per_layer; ISSUE 38 "
        "appends phi4_mini_flash.train_reason_4k and its three metrics "
        "(test_bm_phi4_cell.py::test_benchmark_json_holds_the_six_cells_"
        "and_five_configurations says what the pin meant)",
    "test_bm_contract.py::test_configuration_entry_and_file"
    "[phi4_mini_flash]":
        "the same reading of num_hidden_layers as a width; "
        "test_bm_phi4_cell.py::test_configuration_keeps_every_published_"
        "size holds the file to the rest of that test",
    "test_bm_phi4_cell.py::test_benchmark_json_holds_the_six_cells_and_"
    "five_configurations":
        "pins BENCHMARK.json to PR 38's six cells, five configurations and "
        "wants its three metrics last in per_layer; ISSUE 46 appends "
        "kimi_linear_48b_a3b.train_doc_4k and its two metrics "
        "(test_bm_kimi_cell.py::test_benchmark_json_holds_the_seven_cells_"
        "and_six_configurations says what the pin meant)",
    "test_bm_contract.py::test_configuration_entry_and_file"
    "[kimi_linear_48b_a3b]":
        "the same reading of num_hidden_layers as a width; "
        "test_bm_kimi_cell.py::test_configuration_keeps_every_published_"
        "size holds the file to the rest of that test",
    "test_bm_kimi_cell.py::test_benchmark_json_holds_the_seven_cells_and_"
    "six_configurations":
        "pins BENCHMARK.json to PR 46's seven cells, six configurations and "
        "wants its two metrics last in per_layer; ISSUE 49 appends "
        "mellum2_12b_a2_5b.train_repo_8k and its two metrics "
        "(test_bm_mellum_cell.py::test_benchmark_json_holds_the_eight_cells_"
        "and_seven_configurations says what the pin meant)",
    "test_bm_contract.py::test_configuration_entry_and_file"
    "[mellum2_12b_a2_5b]":
        "the same reading of num_hidden_layers as a width; "
        "test_bm_mellum_cell.py::test_configuration_keeps_every_published_"
        "size holds the file to the rest of that test",
    "test_bm_mellum_cell.py::test_benchmark_json_holds_the_eight_cells_and_"
    "seven_configurations":
        "wants PR 49's two metrics last in per_layer and pins what its cell "
        "reports; ISSUE 51 appends the dense products' four, in every cell "
        "(test_bm_products.py::test_the_benchmark_lists_the_four_metrics_"
        "last_for_every_cell says what the pin meant)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, why in _RESTATED.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=why, strict=False))
