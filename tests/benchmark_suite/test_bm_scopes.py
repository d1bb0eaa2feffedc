"""benchmark/trace/scopes.py and its readers: Fluid names and executor
spans out of a recorded ``.xplane.pb``, read as a raw ``XSpace``.  Two
traces recorded on one TPU v5e chip: PR 23's probe (one ``jax.named_scope``,
no Fluid scope) and PR 24's three traced steps of the ``tiny``
transformer_base configuration.  Arithmetic on recorded numbers: no time is
measured here."""

import json
import os
import shutil

import pytest

from bm_util import ROOT

from benchmark import harness
from benchmark.metrics import _scopes
from benchmark.trace import reduce, scopes

DATA = os.path.join(ROOT, "tests", "benchmark_suite", "data")
PROBE = os.path.join(DATA, "probe_trace.xplane.pb")
TINY = os.path.join(DATA, "tiny_train_trace.xplane.pb")
NEW_METRICS = (
    ["device_ms_per_step." + g for g in (
        "matmul", "attention", "norm", "loss", "embedding", "optimizer",
        "elementwise")]
    + ["device_unscoped_share", "host_self_ms_per_step.train",
       "host_wait_ms_per_step.train"])
TRACED = {"trace": {"busy_s": 1.0, "window_s": 1.0}, "traced_steps": 3}


@pytest.fixture(scope="module")
def rules():
    return scopes.load_groups()


@pytest.fixture(scope="module")
def probe():
    """PR 23's probe: six runs of one jitted program, eight chained 4096^2
    bf16 matmuls inside ``jax.named_scope("bm_matmul_chain")``."""
    return scopes.load(PROBE)


@pytest.fixture(scope="module")
def tiny():
    return scopes.load(TINY)


@pytest.fixture
def traced_root(tmp_path, monkeypatch):
    """Puts a recorded trace where a traced run of a cell leaves its own
    (``harness.trace_dir``'s layout under a root of the test's)."""
    def put(path, cell="transformer_base.train_nmt"):
        d = os.path.join(str(tmp_path), ".benchmark_out", "trace", cell,
                         "plugins", "profile", "2026_01_01_00_00_00")
        os.makedirs(d, exist_ok=True)
        shutil.copy(path, os.path.join(d, "vm.xplane.pb"))
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    _scopes._READ.clear()
    yield put
    _scopes._READ.clear()


# ---------------------------------------------------------------------------
# the probe: event metadata, one plain named scope, no Fluid scope
# ---------------------------------------------------------------------------

def test_probe_metadata_puts_the_matmul_chain_under_its_scope(probe):
    ops = probe["devices"][0]["ops"]
    assert len(ops) == 84
    inside = [op for op in ops if scopes.under(op[3], "bm_matmul_chain")]
    # 8 matmuls a run, 6 runs: seven convolution_tanh_fusion and the
    # eighth, which XLA fused with the convert and the reduction after it
    # and gave the dot's tf_op — a fusion carries ONE name
    names = [op[0].split(".")[0] for op in inside]
    assert names.count("convolution_tanh_fusion") == 42
    assert names.count("convert_reduce_fusion") == 6
    assert len(inside) == 48
    assert {op[3] for op in inside} == {
        "jit(step)/bm_matmul_chain/dot_general:"}
    assert {op[4] for op in inside} == {"convolution fusion"}
    # hand-summed from the events' duration_ps
    assert sum(op[2] for op in inside) / 1e9 == pytest.approx(
        0.03396924461, rel=1e-9)
    # 2 x 4096^3 a matmul, as XLA counts it
    assert {op[5] for op in inside if op[0].startswith("convolution")} == {
        137472507904}
    assert all(op[6] == 100663296 for op in inside)
    # the copies around the chain are under no scope at all
    outside = [op for op in ops if not scopes.under(op[3],
                                                    "bm_matmul_chain")]
    assert len(outside) == 36
    assert {op[0].split(".")[0] for op in outside} == {
        "copy-start", "copy-done"}
    assert {op[3] for op in outside} == {""}
    assert not scopes.under("jit(step)/bm_matmul_chain_2/dot:",
                            "bm_matmul_chain")


def test_probe_agrees_with_the_profile_data_reader(probe):
    """The raw XSpace and ``reduce.load`` (jax.profiler.ProfileData) see
    the same events at the same times."""
    old = reduce.load(PROBE)["devices"][0]
    assert [op[0] for op in probe["devices"][0]["ops"]] == \
        [name for name, _, _ in old["ops"]]
    for mine, (_, start, dur) in zip(probe["devices"][0]["ops"],
                                     old["ops"]):
        assert mine[1] == pytest.approx(start, abs=1.0)
        assert mine[2] == pytest.approx(dur, abs=1.0)
    assert [m[0] for m in probe["devices"][0]["modules"]] == \
        [name for name, _, _ in old["modules"]]
    # the host's spans, by thread: the probe's own bm/ ones
    (events,) = probe["host"].values()
    assert [n for n, _, _ in events].count("bm/step") == 6


def test_a_trace_with_no_fluid_scope_reads_all_unscoped(probe, rules):
    table = scopes.device_table(probe["devices"][0]["ops"], rules)
    assert table["groups"] == dict.fromkeys(rules["groups"], 0.0)
    assert table["collective_s"] == 0.0
    assert table["unscoped_s"] == table["busy_s"]
    busy = reduce.total(reduce.busy_intervals(
        reduce.load(PROBE)["devices"][0])) / 1e9
    assert table["busy_s"] == pytest.approx(busy, rel=1e-5)
    assert table["by_type"] == {} and table["top"] == []
    # by HLO op without its number: a thousand copy-done.N are one row
    assert table["unscoped_top"][0] == ("convolution_tanh_fusion",
                                        pytest.approx(0.029720391094))
    assert set(table["by_category"]) == {
        "convolution fusion", "copy-done", "copy-start"}
    # no pt/ span: a program without the annotations is not one that
    # never waits
    assert scopes.host_steps(probe["host"]) is None
    assert scopes.host_medians(None) is None


def test_readers_on_a_scopeless_trace(traced_root):
    """What the parent commit's program gives: unscoped 100, every group
    0, no host number, and nothing raised."""
    traced_root(PROBE)
    read = {m: harness.load_reader(m).read(TRACED) for m in NEW_METRICS}
    assert read["device_unscoped_share"] == pytest.approx(100.0)
    assert [read[m] for m in NEW_METRICS[:7]] == [0.0] * 7
    assert read["host_self_ms_per_step.train"] is None
    assert read["host_wait_ms_per_step.train"] is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_reader_returns_none_without_a_traced_run(metric, traced_root):
    reader = harness.load_reader(metric)
    assert reader.read({}) is None
    assert reader.read({"kind": "train", "trace": None,
                        "traced_steps": 0}) is None
    # traced, but no profile where a traced run leaves it
    assert reader.read(TRACED) is None
    # a file that is no XSpace: logged, not raised
    bad = os.path.join(scopes.ROOT, "bad.pb")
    with open(bad, "wb") as f:
        f.write(b"\xff\xff\xff\xffnot a trace")
    traced_root(bad)
    assert reader.read(TRACED) is None


# ---------------------------------------------------------------------------
# names -> groups
# ---------------------------------------------------------------------------

def test_innermost_fluid_scope():
    assert scopes.fluid_scope(
        "jit(pt_exe_1a2b3c4d)/fluid[mul]enc0_ffn_fc1.tmp_0/dot_general:") \
        == ("mul", "enc0_ffn_fc1.tmp_0")
    assert scopes.fluid_scope(
        "jit(pt_pe_x)/fluid[while]i/while/body/fluid[scale]a.b.GRAD/mul:") \
        == ("scale", "a.b.GRAD")
    assert scopes.fluid_scope(
        "jit(pt_exe_x)/fluid[mul_grad]x.GRAD/transpose(jvp())/dot:") == \
        ("mul_grad", "x.GRAD")
    # an operation that is the scope's own: the name ends the tf_op
    assert scopes.fluid_scope(
        "jit(pt_exe_x)/fluid[mul_grad]layer_norm_9.tmp_2.GRAD:") == \
        ("mul_grad", "layer_norm_9.tmp_2.GRAD")
    assert scopes.fluid_scope("jit(step)/bm_matmul_chain/dot:") is None
    assert scopes.fluid_scope("") is None and scopes.fluid_scope(None) is None


@pytest.mark.parametrize("op_type,output,group,named", [
    ("mul", "enc0_ffn_fc1.tmp_0", "matmul", True),
    ("mul_grad", "layer_norm_8.tmp_2.GRAD", "matmul", True),
    ("fused_attention_grad", "transpose_20.tmp_0.GRAD", "attention", True),
    ("layer_norm", "layer_norm_0.tmp_2", "norm", True),
    ("softmax_with_cross_entropy_grad", "dec_logits.tmp_1.GRAD", "loss",
     True),
    ("reduce_sum", "reduce_sum_0.tmp_0", "loss", True),
    ("lookup_table_grad", "src_word_emb.GRAD", "embedding", True),
    ("adam", "dec_logits.w_0", "optimizer", True),
    # the embedding's scale against the 2 x n beta-power scales
    ("scale", "scale_0.tmp_0", "elementwise", True),
    ("scale", "dec_logits.w_0_beta1_pow_acc_0", "optimizer", True),
    ("scale_grad", "embedding_0.tmp_0.GRAD", "elementwise", True),
    # the learning-rate schedule and its counter
    ("increment", ".LR_DECAY_COUNTER.begin.1", "optimizer", True),
    ("rsqrt", "noam_decay_0.tmp_0", "optimizer", True),
    ("elementwise_min", "noam_decay_0.tmp_2", "optimizer", True),
    ("elementwise_add", "enc0_ffn_fc1.tmp_1", "elementwise", True),
    ("relu_grad", "enc0_ffn_fc1.tmp_1.GRAD", "elementwise", True),
    # an output pattern only reclaims the types it lists
    ("mul", "weight_decay_0.tmp_0", "matmul", True),
    # a type the file does not name: the default group, and said so
    ("roi_pool", "roi_pool_0.tmp_0", "elementwise", False),
])
def test_group_of(rules, op_type, output, group, named):
    assert scopes.group_of(op_type, output, rules) == (group, named)


def test_groups_file_is_sound(rules):
    assert rules["default"] in rules["groups"]
    assert len(rules["groups"]) == 7
    named = set(rules["by_type"].values()) | {
        r["group"] for r in rules["by_output"]}
    assert named == set(rules["groups"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        metrics = {m["name"] for m in json.load(f)["per_layer"]}
    assert {"device_ms_per_step." + g for g in rules["groups"]} <= metrics


# ---------------------------------------------------------------------------
# arithmetic on plain tuples
# ---------------------------------------------------------------------------

def op(name, start, dur, tf_op="", flops=0, nbytes=0):
    return (name, float(start), float(dur), tf_op, "", flops, nbytes)


def test_self_times_take_nested_events_out_of_their_parent():
    ops = [op("while.1", 0, 100), op("fusion.1", 10, 20),
           op("fusion.2", 40, 30), op("fusion.3", 120, 5)]
    assert scopes.self_times(ops) == [50.0, 20.0, 30.0, 5.0]
    # the order given is kept, whatever the order in time
    assert scopes.self_times(ops[::-1]) == [5.0, 30.0, 20.0, 50.0]
    assert sum(scopes.self_times(ops)) == reduce.total(
        reduce.union([(s, s + d) for _, s, d, *_ in ops]))


def test_device_table_by_hand(rules):
    scope = "jit(pt_exe_ab)/fluid[%s]%s/x:"
    ops = [
        op("fusion.1", 0, 4e6, scope % ("mul", "fc.tmp_0"), 8, 2),
        op("fusion.2", 5e6, 1e6, scope % ("mul_grad", "x.GRAD"), 16, 4),
        op("fusion.3", 7e6, 2e6, scope % ("scale", "w_beta1_pow_acc_0")),
        op("fusion.4", 10e6, 1e6, scope % ("scale", "scale_0.tmp_0")),
        op("fusion.5", 12e6, 3e6, scope % ("roi_pool", "r.tmp_0")),
        op("all-reduce.7", 16e6, 5e6, scope % ("mul_grad", "x.GRAD")),
        op("copy.1", 22e6, 2e6),
        op("fusion.1", 30e6, 4e6, scope % ("mul", "fc.tmp_0"), 8, 2),
    ]
    t = scopes.device_table(ops, rules)
    assert t["groups"] == {
        "matmul": pytest.approx(9e-3), "attention": 0.0, "norm": 0.0,
        "loss": 0.0, "embedding": 0.0, "optimizer": pytest.approx(2e-3),
        "elementwise": pytest.approx(4e-3)}
    assert t["unscoped_s"] == pytest.approx(2e-3)
    # a collective is in no group, whatever scope it was traced under
    assert t["collective_s"] == pytest.approx(5e-3)
    assert t["busy_s"] == pytest.approx(22e-3)
    assert t["by_type"]["mul"] == {"group": "matmul", "count": 2,
                                   "s": pytest.approx(8e-3), "flops": 16,
                                   "bytes": 4}
    assert t["by_type"]["scale"]["s"] == pytest.approx(3e-3)
    assert t["top"][0] == ("mul/fc.tmp_0 fusion.1", pytest.approx(8e-3))
    assert t["unscoped_top"] == [("copy", pytest.approx(2e-3))]
    assert t["by_category"] == {"": pytest.approx(17e-3)}
    # never dropped: counted under the default group, and named
    assert t["unnamed_types"] == ["roi_pool"]


def test_host_steps_by_hand():
    host = {
        "python#1": [
            ("bm/train_step", 0.0, 100e6),
            ("pt/executor/step", 1e6, 98e6),
            ("pt/executor/h2d_transfer", 2e6, 10e6),
            ("pt/executor/fetch_sync", 30e6, 60e6),
            ("bm/train_step", 100e6, 20e6),
            ("pt/executor/step", 101e6, 18e6),
            ("bm/train_step", 120e6, 90e6),
            # two syncs in one step; one straddles the span's end
            ("pt/parallel_executor/fetch_sync", 130e6, 10e6),
            ("pt/parallel_executor/fetch_sync", 200e6, 30e6),
            ("bm/fetch_loss", 230e6, 5e6)],
        # another thread's sync is not this thread's wait
        "worker#2": [("pt/executor/fetch_sync", 0.0, 300e6)],
    }
    steps = scopes.host_steps(host)
    assert steps == [(pytest.approx(0.100), pytest.approx(0.060)),
                     (pytest.approx(0.020), 0.0),
                     (pytest.approx(0.090), pytest.approx(0.020))]
    self_s, wait_s = scopes.host_medians(steps)
    assert self_s == pytest.approx(0.040) and wait_s == pytest.approx(0.020)
    # no step span at all: nothing to read
    assert scopes.host_medians(scopes.host_steps(
        {"python#1": [("pt/executor/step", 0.0, 1e6)]})) is None


# ---------------------------------------------------------------------------
# three traced steps of the tiny configuration, recorded on the chip
# ---------------------------------------------------------------------------

@pytest.fixture
def tiny_program():
    """The program the fixture was recorded from: the ``tiny`` sizes of
    transformer_base.train_nmt through the benchmark's own builder (built
    here on the CPU, never run; tests/conftest.py puts back the
    ``FLAGS_fast_prng`` the builder sets)."""
    import jax

    bench = harness.load_benchmark()
    _, cfg, traffic = harness.resolve_cell(
        bench, "transformer_base.train_nmt", tiny=True)
    cfg = dict(cfg, max_len=traffic["seq"])
    return harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1]).main


def test_tiny_trace_groups_unscoped_and_collectives_add_up_to_busy(
        tiny, rules):
    (chip,) = tiny["devices"]
    table = scopes.device_table(tiny["devices"][chip]["ops"], rules)
    busy = reduce.total(reduce.busy_intervals(
        reduce.load(TINY)["devices"][chip])) / 1e9
    parts = (sum(table["groups"].values()) + table["unscoped_s"]
             + table["collective_s"])
    assert parts == pytest.approx(table["busy_s"])
    assert parts == pytest.approx(busy, rel=0.01)
    assert all(s > 0 for s in table["groups"].values())
    assert table["collective_s"] == 0.0            # one chip
    assert table["unnamed_types"] == []
    # at toy sizes the copies XLA's memory-space assignment adds (no op
    # metadata, so no scope can own them) and the PRNG key's small
    # programs weigh more than at the real size
    assert 0 < table["unscoped_s"] / table["busy_s"] < 0.25
    assert table["unscoped_top"][0][0] == "copy-done"
    for name, _ in table["top"]:
        fluid, _, hlo = name.partition(" ")
        assert "/" in fluid and hlo and not fluid.endswith(":")


def test_tiny_trace_names_are_the_programs(tiny, rules, tiny_program):
    from paddle_tpu import compile_cache

    program = tiny_program
    (chip,) = tiny["devices"]
    table = scopes.device_table(tiny["devices"][chip]["ops"], rules)
    types = {op.type for op in program.global_block().ops}
    # nothing invented: every scope read back is an op of the program,
    # by type and by first output variable
    assert set(table["by_type"]) <= types
    from paddle_tpu.registry import fluid_scope_name

    named = {scopes.fluid_scope(fluid_scope_name(op))
             for op in program.global_block().ops}
    assert ("mul_grad", "layer_norm_9.tmp_2.GRAD") in named
    seen = {scopes.fluid_scope(op[3])
            for op in tiny["devices"][chip]["ops"]} - {None}
    assert seen <= named
    # every type that keeps an operation of its own after XLA's fusion
    # (relu, elementwise_add, scale, ... end up inside their neighbours'
    # fusions and are counted there: a fusion carries one name)
    assert {"mul", "mul_grad", "fused_attention", "fused_attention_grad",
            "layer_norm", "layer_norm_grad", "softmax_with_cross_entropy",
            "softmax_with_cross_entropy_grad", "lookup_table",
            "lookup_table_grad", "adam", "transpose", "reshape_grad",
            "reduce_sum", "elementwise_add_grad"} <= set(table["by_type"])
    # the compiled step is named by kind and label, and the label — the
    # program's fingerprint — came out on the chip as it does here
    modules = {name.split("(")[0] for name, _, _ in
               tiny["devices"][chip]["modules"]}
    step = "jit_pt_exe_" + compile_cache.program_label(program)
    assert step in modules
    assert [n.split("(")[0] for n, _, _ in
            tiny["devices"][chip]["modules"]].count(step) == 3
    # what run() dispatches around the step (the PRNG key) is there too,
    # under jax's own names, and lands in unscoped time
    assert "jit__threefry_fold_in" in modules


def test_tiny_trace_host_numbers_add_up_to_the_step_spans(tiny):
    (events,) = tiny["host"].values()
    names = [n for n, _, _ in events]
    assert names.count("bm/train_step") == 3
    for span in ("step", "h2d_transfer", "run", "dispatch"):
        assert names.count("pt/executor/" + span) == 3
    steps = scopes.host_steps(tiny["host"])
    assert len(steps) == 3
    spans = sorted(d / 1e9 for n, _, d in events if n == "bm/train_step")
    assert sorted(s for s, _ in steps) == pytest.approx(spans)
    self_s, wait_s = scopes.host_medians(steps)
    assert 0 <= wait_s < self_s
    assert self_s + wait_s == pytest.approx(spans[1], rel=0.02)
    # the executor's own span is the caller's, less the call
    inner = sorted(d / 1e9 for n, _, d in events
                   if n == "pt/executor/step")
    assert all(0 <= a - b < 2e-4 for a, b in zip(spans, inner))


def test_readers_on_the_tiny_trace(traced_root, tiny, rules):
    traced_root(TINY)
    read = {m: harness.load_reader(m).read(TRACED) for m in NEW_METRICS}
    assert all(v is not None for v in read.values())
    (chip,) = tiny["devices"]
    table = scopes.device_table(tiny["devices"][chip]["ops"], rules)
    for group in rules["groups"]:
        assert read["device_ms_per_step." + group] == pytest.approx(
            table["groups"][group] / 3 * 1e3)
    busy_ms = table["busy_s"] / 3 * 1e3
    assert (sum(read[m] for m in NEW_METRICS[:7])
            + read["device_unscoped_share"] / 100 * busy_ms) == \
        pytest.approx(busy_ms)
    self_s, wait_s = scopes.host_medians(scopes.host_steps(tiny["host"]))
    assert read["host_self_ms_per_step.train"] == pytest.approx(self_s * 1e3)
    assert read["host_wait_ms_per_step.train"] == pytest.approx(wait_s * 1e3)
    # the newest profile wins, whichever cell left it
    traced_root(PROBE, cell="transformer_base.train_nmt_dp4")
    os.utime(os.path.join(
        scopes.ROOT, ".benchmark_out", "trace", "transformer_base.train_nmt",
        "plugins", "profile", "2026_01_01_00_00_00", "vm.xplane.pb"),
        (1, 1))
    _scopes._READ.clear()
    assert harness.load_reader("device_unscoped_share").read(TRACED) == \
        pytest.approx(100.0)
