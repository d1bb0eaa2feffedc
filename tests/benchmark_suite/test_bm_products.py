"""benchmark/trace/hlo.py and benchmark/metrics/_products.py: the compiled
step as a recorded trace holds it, and the dense products' noted work joined
with the operations that hold them.  Two traces recorded on one TPU v5e
chip: PR 23's probe (eight chained matmuls; it has an ``Hlo Proto``) and
PR 51's three traced steps of a tiny two-layer program with Adam
(``data/record_products_trace.py``, which also wrote the step's ``op_work``
beside it).  Arithmetic on recorded numbers: no time is measured here."""

import json
import os
import shutil
import sys

import pytest

from bm_util import ROOT

from benchmark import harness
from benchmark.metrics import _products, _scopes
from benchmark.trace import hlo, scopes

DATA = os.path.join(ROOT, "tests", "benchmark_suite", "data")
PROBE = os.path.join(DATA, "probe_trace.xplane.pb")
TINY = os.path.join(DATA, "tiny_train_trace.xplane.pb")
PRODUCTS = os.path.join(DATA, "products_trace.xplane.pb")
METRICS = ["dense_product_roofline", "device_ms_per_step.matmul_fwd",
           "device_ms_per_step.matmul_dx", "device_ms_per_step.matmul_dw"]
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "products_trace.op_work.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def loaded():
    return hlo.load(PRODUCTS)


@pytest.fixture(scope="module")
def joined(recorded, loaded):
    work, shards = _products.noted([recorded])
    found = _products.attribute(loaded["devices"][0]["ops"],
                                hlo.Programs(loaded), work,
                                scopes.load_groups())
    return work, shards, found


@pytest.fixture
def traced_root(tmp_path, monkeypatch):
    """Puts a recorded trace where a traced run of a cell leaves its own."""
    def put(path, cell="transformer_base.train_nmt"):
        d = os.path.join(str(tmp_path), ".benchmark_out", "trace", cell,
                         "plugins", "profile", "2026_01_01_00_00_00")
        os.makedirs(d, exist_ok=True)
        shutil.copy(path, os.path.join(d, "vm.xplane.pb"))
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    _products._READ.clear()
    _scopes._READ.clear()
    yield put
    _products._READ.clear()
    _scopes._READ.clear()


# ---------------------------------------------------------------------------
# hlo.py over the probe
# ---------------------------------------------------------------------------

def test_the_probes_module_prints_with_its_fused_computations():
    got = hlo.load(PROBE)
    (pid,) = got["programs"]
    assert got["programs"][pid] == "jit_step(%s)" % pid
    assert got["peaks"]["device_type_string"] == "TPU v5 Lite"
    text = hlo.module_text(got["protos"][pid])
    assert text.startswith("HloModule jit_step, is_scheduled=true")
    comps = hlo.parse_module(text)
    assert "fused_computation.7" in comps and "region_0.1" in comps
    # every operation names the one program, in scopes.load's order
    ops = got["devices"][0]["ops"]
    assert {op[8] for op in ops} == {pid}
    assert [op[:7] for op in ops] == scopes.load(PROBE)["devices"][0]["ops"]
    assert hlo.module_text(b"") is None and hlo.module_text(b"\x12\x00") \
        is None


def test_a_fusions_inner_product_carries_its_own_name():
    got = hlo.load(PROBE)
    programs = hlo.Programs(got)
    op = next(o for o in got["devices"][0]["ops"]
              if o[0] == "convolution_tanh_fusion.6")
    ins = hlo.parse_instruction(op[7])
    assert (ins.opcode, ins.calls) == ("fusion", ("fused_computation.7",))
    products, names = hlo.contents(programs.computations(op[8]), ins)
    assert [(p.opcode, p.op_name) for p in products] == [
        ("convolution", "jit(step)/bm_matmul_chain/dot_general")]
    assert set(names) == {"jit(step)/bm_matmul_chain/dot_general",
                          "jit(step)/bm_matmul_chain/tanh"}
    # the last of the chain: XLA fused the reduction after it in
    last = next(o for o in got["devices"][0]["ops"]
                if o[0].startswith("convert_reduce_fusion"))
    products, names = hlo.contents(programs.computations(last[8]),
                                   hlo.parse_instruction(last[7]))
    assert len(products) == 1 and "jit(step)/reduce_sum" in names
    # both operands came through a prefetch, and the result stays there
    result, opnds = hlo.operands(op[7])
    assert "S(1)" in result and len(opnds) == 2
    assert all("S(1)" in s and s.startswith("bf16[4096,4096]")
               for s in opnds)
    assert programs.computations("no such program") is None


def test_a_tuple_result_and_a_bare_instruction_parse():
    ins = hlo.parse_instruction(
        "  ROOT %fusion.3 = (f32[]{:T(128)}, bf16[8,128]{1,0:T(8,128)(2,1)}) "
        "fusion(f32[8,128]{1,0} %p.1, bf16[8,128]{1,0:T(8,128)(2,1)S(1)} "
        "%copy-done), kind=kLoop, calls=%fused_computation.3, "
        "metadata={op_name=\"jit(f)/fluid[mul_grad]x.GRAD/dw/dot_general\"}")
    assert ins.name == "fusion.3" and ins.opcode == "fusion"
    assert ins.shape.startswith("(f32[]") and ins.shape.endswith(")")
    assert ins.op_name == "jit(f)/fluid[mul_grad]x.GRAD/dw/dot_general"
    assert _products.part_of(ins.op_name) == "dw"
    assert _products.part_of("jit(f)/fluid[mul]x/dot_general") == "fwd"
    assert _products.part_of("jit(f)/fluid[mul_grad]dx.GRAD/x") == "fwd"
    # a product that stands alone is its own contents
    alone = hlo.parse_instruction(
        "%convolution.5 = bf16[8,8]{1,0} convolution(bf16[8,4]{1,0} %a, "
        "bf16[4,8]{1,0} %b), dim_labels=bf_io->bf")
    assert hlo.contents({}, alone) == ([alone], [])
    assert hlo.parse_instruction("not an instruction") is None


# ---------------------------------------------------------------------------
# the recorded step: every noted part against the operations
# ---------------------------------------------------------------------------

def test_the_recorded_works_parts_are_todays(recorded):
    """The program the fixture was recorded from notes the same parts
    today (rebuilt on the CPU: flops, bytes and shapes are the rule's, the
    head kernel's choice the platform's)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import compile_cache
    sys.path.insert(0, DATA)
    try:
        import record_products_trace as tiny
    finally:
        sys.path.pop(0)
    main, startup, loss = tiny.build(fluid)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=tiny.feed(np, 0), fetch_list=[loss])
    rec = compile_cache.compile_log()[-1]
    assert rec["name"] == recorded["name"]
    assert sorted(map(tuple, (r[:5] + [tuple(r[5])]
                              for r in recorded["op_work"]))) == sorted(
        rec["op_work"])
    assert recorded["kernel_bodies"].get("mul_grad:head_fused") == 1


def test_every_noted_part_is_in_an_operation_once_or_split_by_flops(joined):
    work, shards, found = joined
    assert shards == 1 and len(work) == 20
    assert set(found["parts"]) == set(work)
    ops = found["operations"]
    for name, d in ops.items():
        assert d["keys"] and d["s"] > 0
    # an operation's time goes to its parts by their flops, all of it
    total = sum(acc["s"] for acc in found["parts"].values())
    assert total == pytest.approx(sum(d["s"] for d in ops.values()),
                                  rel=1e-9)
    shared = [d for d in ops.values() if len(d["keys"]) > 1]
    for d in shared:
        flops = [work[k][1] for k in d["keys"]]
        got = [found["parts"][k]["s"] for k in d["keys"]]
        if all(len(found["parts"][k]["ops"]) == 1 for k in d["keys"]):
            assert got[0] / got[1] == pytest.approx(flops[0] / flops[1])
    # the head's backward is one custom call holding dX and dW
    head = [d for d in ops.values()
            if d["root"] and d["root"][0] == "mul_grad"
            and {k[1] for k in d["keys"]} == {"dx", "dw"}]
    assert len(head) == 1 and head[0]["types"] == set()


def test_what_xla_fused_into_a_product_is_named(joined):
    work, _, found = joined
    # XLA fused Adam's update into the products that make the weights'
    # gradients; in this tiny step it left every such fusion rooted in the
    # product's own scope, so the group metrics count them as the parts do
    fused = [k for k, acc in found["parts"].items() if "adam" in acc["types"]]
    assert fused and {k[1] for k in fused} == {"dw"}
    assert found["foreign"] == {}
    assert all(not acc["roots"] or set(acc["roots"]) <= {"mul_grad"}
               for acc in found["parts"].values())
    # the group also holds operations with no product in them (the bias
    # sums, the casts): that is the difference to device_ms_per_step.matmul
    assert set(found["no_product"]) <= {"mul", "mul_grad", "matmul",
                                        "matmul_grad"}
    assert sum(found["no_product"].values()) > 0


class _OneProgram:
    """``hlo.Programs`` over a module given as text."""

    def __init__(self, text):
        self._comps = hlo.parse_module(text)

    def computations(self, program_id):
        return self._comps


def test_a_product_under_a_foreign_root_is_named_as_such():
    """A dW fused with the weight's Adam and rooted THERE: the group metrics
    count it under ``optimizer``; the parts take it by the inner product."""
    module = """HloModule jit_pt_exe_x, is_scheduled=true

%fused_computation.1 (p0: bf16[64,32], p1: bf16[64,16], p2: f32[32,16]) -> f32[32,16] {
  %p0 = bf16[64,32]{1,0} parameter(0)
  %p1 = bf16[64,16]{1,0} parameter(1)
  %convolution.1 = f32[32,16]{1,0} convolution(%p0, %p1), dim_labels=fb_io->bf, metadata={op_name="jit(pt_exe_x)/fluid[mul_grad]w.GRAD/dw/transpose(jvp())/dot_general"}
  %p2 = f32[32,16]{1,0} parameter(2)
  ROOT %subtract.1 = f32[32,16]{1,0} subtract(%p2, %convolution.1), metadata={op_name="jit(pt_exe_x)/fluid[adam]w/sub"}
}

%fused_computation.2 (p0: bf16[64,16], p1: bf16[32,16]) -> bf16[64,32] {
  %p0 = bf16[64,16]{1,0} parameter(0)
  %p1 = bf16[32,16]{1,0} parameter(1)
  ROOT %convolution.2 = bf16[64,32]{1,0} convolution(%p0, %p1), dim_labels=bf_oi->bf, metadata={op_name="jit(pt_exe_x)/fluid[mul_grad]w.GRAD/dx/transpose(jvp())/dot_general"}
}

ENTRY %main (a: bf16[64,32]) -> f32[32,16] {
  %a = bf16[64,32]{1,0} parameter(0)
}
"""
    flops = 2 * 64 * 32 * 16
    work = {("fluid[mul_grad]w.GRAD", "dw"): ("mul_grad", flops, 7168,
                                              (32, 64, 16)),
            ("fluid[mul_grad]w.GRAD", "dx"): ("mul_grad", flops, 7168,
                                              (64, 16, 32)),
            ("fluid[mul]out", "fwd"): ("mul", flops, 7168, (64, 32, 16))}

    def op(name, start, dur, tf_op, text):
        return (name, start, dur, tf_op, "", 0, 0, text, "1")
    ops = [
        op("subtract_fusion.1", 0.0, 3000.0, "jit(pt_exe_x)/fluid[adam]w/sub:",
           "%subtract_fusion.1 = f32[32,16]{1,0} fusion(bf16[64,32]{1,0:S(1)} "
           "%a, bf16[64,16]{1,0} %g, f32[32,16]{1,0} %w), kind=kOutput, "
           "calls=%fused_computation.1"),
        op("fusion.2", 3000.0, 1000.0,
           "jit(pt_exe_x)/fluid[mul_grad]w.GRAD/dx/transpose(jvp())/"
           "dot_general:",
           "%fusion.2 = bf16[64,32]{1,0} fusion(bf16[64,16]{1,0} %g, "
           "bf16[32,16]{1,0} %w), kind=kOutput, calls=%fused_computation.2"),
        op("copy.3", 4000.0, 500.0, "jit(pt_exe_x)/fluid[mul_grad]w.GRAD/"
           "dx/transpose:", "%copy.3 = bf16[64,32]{0,1} copy(bf16[64,32]{1,0} "
           "%fusion.2)"),
        op("all-reduce.1", 4500.0, 900.0, "", "%all-reduce.1 = f32[] "
           "all-reduce(f32[] %x)")]
    found = _products.attribute(ops, _OneProgram(module), work,
                                scopes.load_groups())
    dw = found["parts"][("fluid[mul_grad]w.GRAD", "dw")]
    assert dw["s"] == pytest.approx(3e-6) and dw["roots"] == {
        "adam": pytest.approx(3e-6)}
    assert dw["types"] == {"adam": 1} and dw["fast"] == {"bf16[64,32]": 1}
    assert found["foreign"] == {"adam": pytest.approx(3e-6)}
    assert found["no_product"] == {"mul_grad": pytest.approx(0.5e-6)}
    out = _products.summarize(found, work, 1, 1, V5E)
    assert out["part_s"] == {"fwd": 0.0, "dx": pytest.approx(1e-6),
                             "dw": pytest.approx(3e-6)}
    # a noted part no operation holds stays out of the floor, and is named
    assert out["unfound"] == [("fluid[mul]out", "fwd")]
    assert out["floor_s"] == pytest.approx(2 * 7168 / 819e9)
    row = out["rows"][((32, 64, 16), "dw")]
    assert row["roots"] == {"adam": pytest.approx(3e-6)}


def test_the_four_numbers_and_the_difference_to_the_group(joined, loaded):
    work, shards, found = joined
    out = _products.summarize(found, work, shards, 3, V5E)
    assert out["unfound"] == []
    assert all(out["part_s"][p] > 0 for p in _products.PARTS)
    assert out["time_s"] == pytest.approx(sum(out["part_s"].values()))
    floor = sum(max(w[1] / 197e12, w[2] / 819e9) for w in work.values())
    assert out["floor_s"] == pytest.approx(floor)
    assert 0 < out["floor_s"] / out["time_s"] < 1
    # rows by (shape, part): the parts' count and flops add up
    assert sum(r["n"] for r in out["rows"].values()) == len(work)
    assert sum(r["flops"] for r in out["rows"].values()) == sum(
        w[1] for w in work.values())
    # parts' sum = the matmul group + products under another root - the
    # group's operations that hold none, exactly
    table = scopes.device_table(
        [op[:7] for op in loaded["devices"][0]["ops"]], scopes.load_groups())
    assert out["time_s"] == pytest.approx(
        table["groups"]["matmul"] / 3 + sum(out["foreign"].values())
        - sum(out["no_product"].values()), rel=1e-9)
    # without the device's peaks there is no floor, the times stay
    bare = _products.summarize(found, work, shards, 3, None)
    assert bare["floor_s"] is None and bare["part_s"] == out["part_s"]
    # under a mesh a chip's share of the flops is the noted over the split
    quarter = _products.summarize(found, work, 4, 3, V5E)
    assert quarter["floor_s"] == pytest.approx(out["floor_s"] / 4)


def test_the_readers_over_the_recorded_run(recorded, traced_root,
                                           monkeypatch, capsys):
    from paddle_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "compile_log", lambda: [recorded])
    traced_root(PRODUCTS)
    facts = {"trace": {"busy_s": 1.0, "window_s": 1.0}, "traced_steps": 3}
    got = {m: harness.load_reader(m).read(facts) for m in METRICS}
    assert all(isinstance(v, float) and v > 0 for v in got.values())
    assert got["dense_product_roofline"] < 100
    assert sum(got[m] for m in METRICS[1:]) == pytest.approx(
        _products.reading(facts)["time_s"] * 1e3)
    out = capsys.readouterr().out
    lines = [ln for ln in out.split("\n")
             if ln.startswith("[benchmark products]")]
    # the table is logged once, by the first reader to ask
    assert sum("by product shape" in ln for ln in lines) == 1
    assert any("256 x 128 x 512" in ln and " fwd " in ln for ln in lines)
    assert any("fused in: adam" in ln for ln in lines)
    assert any("Hlo Proto" in ln and "read in" in ln for ln in lines)


@pytest.mark.parametrize("case", ["empty_facts", "untraced", "no_op_work",
                                  "no_proto", "no_trace_file"])
def test_with_nothing_to_read_every_reader_returns_none(
        case, recorded, traced_root, monkeypatch):
    from paddle_tpu import compile_cache

    log = [dict(recorded, op_work=[])] if case == "no_op_work" else [recorded]
    monkeypatch.setattr(compile_cache, "compile_log", lambda: log)
    if case != "no_trace_file":
        # PR 24's trace has the Fluid scopes and no Hlo Proto
        traced_root(TINY if case == "no_proto" else PRODUCTS)
    facts = {"trace": {"busy_s": 1.0, "window_s": 1.0}, "traced_steps": 3}
    if case == "empty_facts":
        facts = {}
    elif case == "untraced":
        facts = {"trace": None, "traced_steps": 0}
    for m in METRICS:
        assert harness.load_reader(m).read(facts) is None


def test_a_program_that_keeps_no_op_work_reads_none(traced_root,
                                                    monkeypatch):
    """The parent of PR 51: records without the field."""
    from paddle_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "compile_log",
                        lambda: [{"name": "pt_exe_x", "ops": 3}])
    traced_root(PRODUCTS)
    facts = {"trace": {"busy_s": 1.0, "window_s": 1.0}, "traced_steps": 3}
    assert _products.noted(compile_cache.compile_log()) is None
    assert all(harness.load_reader(m).read(facts) is None for m in METRICS)


def test_the_benchmark_lists_the_four_metrics_last_for_every_cell():
    """What ``test_bm_mellum_cell.py``'s pin meant, of the entries there
    are now: the eight cells, seven configurations and 38 per-layer metrics
    that were there first, unchanged; then the four, each appended last and
    reported in every cell."""
    bench = harness.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells == [
        "transformer_base.train_nmt", "transformer_base.train_nmt_dp4",
        "keye_vl2_30b_a3b.train_longdoc_8k", "joyai_llm_flash.train_mtp_8k",
        "ouro_2_6b.train_loop_4k", "phi4_mini_flash.train_reason_4k",
        "kimi_linear_48b_a3b.train_doc_4k",
        "mellum2_12b_a2_5b.train_repo_8k"]
    assert [c["name"] for c in bench["configs"]] == [
        "transformer_base", "keye_vl2_30b_a3b", "joyai_llm_flash",
        "ouro_2_6b", "phi4_mini_flash", "kimi_linear_48b_a3b",
        "mellum2_12b_a2_5b"]
    assert [w["chips"] for w in bench["workloads"]] == [1, 4] + [1] * 6
    assert bench["run_seconds"] == 51
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("setup_s", 0.1)]
    older, last = bench["per_layer"][:-4], bench["per_layer"][-4:]
    assert len(older) == 38
    assert [m["name"] for m in older[-2:]] == [
        "mixed_attention_roofline", "window_attention_ms_per_step"]
    for m in older[-2:]:                        # PR 49's: its cell's alone
        assert m["workloads"] == [cells[-1]]
    assert not any(m["name"] in METRICS for m in older)
    assert [m["name"] for m in last] == METRICS
    for m, (unit, better) in zip(last, [("%", "higher")] + [
            ("ms", "lower")] * 3):
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "device_trace", "layer": "op kernels",
                     "moves": "train_tokens_per_s", "workloads": cells}
    # what the newest cell reports: what it reported, and the four
    reports = {m["name"] for m in bench["per_layer"]
               if cells[-1] in m["workloads"]}
    assert set(METRICS) <= reports and len(reports) == 25 + 4
