"""The benchmark's own arithmetic against hand-computed values: the trace
reduction (on a small trace recorded on a TPU v5e and on hand-made
intervals), required FLOPs and least bytes, percentiles and due-time
latencies, the seeded generators."""

import math
import os

import numpy as np
import pytest

from bm_util import ROOT

from benchmark import peaks, stats
from benchmark.flops import decoder_base as dec_flops
from benchmark.flops import transformer_base as tb_flops
from benchmark.generators import serve_open_loop as serve
from benchmark.generators import train_steps as train
from benchmark.trace import reduce

TRACE = os.path.join(ROOT, "tests", "benchmark_suite", "data",
                     "probe_trace.xplane.pb")
BASE = dict(d_model=512, d_inner=2048, n_head=8, n_layer=6, vocab_size=32000)


@pytest.fixture(scope="module")
def recorded():
    """Six runs of one jitted program (8 chained 4096^2 bf16 matmuls,
    5.708 ms each on the device) with a 50 ms host sleep after the third;
    recorded on one TPU v5e chip (my chip run, PR 23)."""
    return reduce.load(TRACE)


def test_recorded_trace_busy_and_idle(recorded):
    s = reduce.summarize(recorded)
    # 6 modules x 5.7083 ms; the ops' union is the modules' time less the
    # few microseconds between ops
    assert s["devices"] == 1
    assert s["busy_s"] == pytest.approx(6 * 5.7083e-3, rel=2e-3)
    # first module starts at 47.494 ms, the last ends at 133.725 ms
    assert s["window_s"] == pytest.approx(0.0862307, rel=1e-4)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.6028, abs=2e-3)


def test_recorded_trace_time_by_operation_and_module(recorded):
    s = reduce.summarize(recorded)
    (name, (count, seconds)), = s["modules"].items()
    assert name.startswith("jit_step(") and count == 6
    assert seconds == pytest.approx(6 * 5.7083e-3, rel=1e-3)
    ops = dict(s["device_ops"])
    # eight fusions of ~0.706-0.717 ms run six times each
    assert ops["convolution_tanh_fusion.7"] == pytest.approx(6 * 0.7175e-3,
                                                             rel=1e-2)
    assert len(s["device_ops"]) <= 10
    assert all(not n.startswith("%") for n in ops)


def test_recorded_trace_gap_goes_to_the_benchmarks_span(recorded):
    gaps = dict(reduce.summarize(recorded)["idle_gaps"])
    # the one long gap is the annotated host sleep (50 ms + the fetch)
    assert gaps["bm/host_sleep"] == pytest.approx(0.052, abs=2e-3)
    assert max(gaps, key=gaps.get) == "bm/host_sleep"


def test_interval_arithmetic_by_hand():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert reduce.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]
                           ) == [(0, 2), (3, 8), (22, 29)]
    assert reduce.gaps([(2, 4), (6, 9)], 0, 10) == [(0, 2), (4, 6), (9, 10)]
    assert reduce.total([(0, 3), (5, 8)]) == 6


def test_exposed_collective_time_by_hand():
    """An all-reduce of 10 us (async start..done 100..110) of which 6 us
    are covered by fusions; a synchronous all-gather of 3 us alone."""
    dev = {"modules": [("jit_fn(1)", 0.0, 200.0)],
           "ops": [("fusion.1", 90.0, 14.0),           # 90..104
                   ("all-reduce-start.1", 100.0, 0.5),
                   ("fusion.2", 106.0, 2.0),           # 106..108
                   ("all-reduce-done.1", 109.5, 0.5),
                   ("all-gather.3", 150.0, 3.0)],
           "async": [("all-reduce-start.1", 100.0, 10.0)]}
    coll, exposed = reduce.collective_times(dev)
    assert coll * 1e9 == pytest.approx(13.0)
    # 100..110 less fusion cover 100..104 and 106..108 = 4 us, plus 3 us
    assert exposed * 1e9 == pytest.approx(7.0)
    assert reduce.op_name("%all-reduce-start.1 = (f32[8]{0}) all-reduce-"
                          "start(%p)") == "all-reduce-start.1"


def test_gap_attribution_prefers_own_span_then_most_specific_frame():
    host = [("$main.py:1 main", 0.0, 1000.0), ("$engine.py:9 _prefill",
                                               100.0, 60.0),
            ("bm/train_step", 300.0, 5.0)]
    out = dict(reduce.attribute_gaps([(110.0, 150.0), (301.0, 303.0),
                                      (2000.0, 2010.0)], host))
    assert out["$engine.py:9 _prefill"] == pytest.approx(40e-9)
    assert out["bm/train_step"] == pytest.approx(2e-9)
    assert out["(no host span)"] == pytest.approx(10e-9)


# -- FLOPs and bytes --------------------------------------------------------

def test_transformer_base_padded_flops_by_hand():
    # per position pair, MACs: encoder 6 x (4 x 512^2 + 2 x 512 x 2048 +
    # 2 x 64 x 512) = 19,267,584; decoder 6 x (6 x 512^2 + 2 x 512^2 [cross
    # k, v] + 2 x 512 x 2048 + 4 x 64 x 512) = 25,952,256; logits 512 x 32000
    macs = 19267584 + 25952256 + 16384000
    assert tb_flops.padded_flops(BASE, 256, 64) == 6 * 16384 * macs
    assert tb_flops.padded_flops(BASE, 256, 64) == pytest.approx(6.06e12,
                                                                 rel=3e-3)


def test_transformer_base_required_flops_and_params_by_hand():
    # one row, 3 source and 2 target tokens, one layer, tiny widths
    cfg = dict(d_model=4, d_inner=8, vocab_size=10, n_layer=1)
    enc = 3 * (4 * 16 + 2 * 32) + 2 * 9 * 4
    dec = 2 * (6 * 16 + 2 * 32) + 3 * 2 * 16 + 2 * 3 * 4 + 2 * 2 * 3 * 4
    assert tb_flops.required_flops(cfg, [3], [2]) == 6 * (enc + dec + 2 * 40)
    assert tb_flops.param_count(BASE) == 93285632
    assert tb_flops.least_bytes(BASE) == 93285632 * 24
    lens = np.full(256, 64)
    assert tb_flops.required_flops(BASE, lens, lens) < \
        tb_flops.padded_flops(BASE, 256, 64)


def test_decoder_base_tick_bytes_by_hand():
    cfg = dict(BASE, max_len=1024)
    layer = 4 * 512 * 512 + 2 * 512 * 2048 + 2048 + 512 + 4 * 512
    assert dec_flops.weight_bytes(cfg) == (6 * layer + 512 * 32000
                                           + 32000) * 4
    assert dec_flops.kv_bytes_per_token(cfg) == 24576
    assert dec_flops.tick_least_bytes(cfg, 1000) == \
        dec_flops.weight_bytes(cfg) + 24576000
    floor, bound = dec_flops.tick_floor_seconds(
        cfg, 32, 32 * 200, peaks.PEAKS["TPU v5 lite"])
    assert bound == "memory"
    assert floor == pytest.approx((141272064 + 6400 * 24576) / 819e9,
                                  rel=1e-6)


@pytest.mark.parametrize("step_s", [0.031, 0.06, 0.16])
def test_no_share_over_100_percent_at_any_possible_step_time(step_s):
    """The floor is a lower bound: a step cannot be faster than required
    FLOPs over the peak, so the share stays under 100% for every step
    time the chip can reach (0.031 s = the padded count at the peak)."""
    p = peaks.PEAKS["TPU v5 lite"]
    lens = np.full(256, 64)
    floor, bound = tb_flops.step_floor_seconds(BASE, lens, lens, p)
    assert bound == "compute"
    assert floor <= tb_flops.padded_flops(BASE, 256, 64) / p["bf16_flops"]
    assert 100.0 * floor / step_s <= 100.0


def test_peaks_table_and_unknown_device():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")


# -- percentiles and due times -----------------------------------------------

def test_percentile_and_spread_by_hand():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10.0 and stats.percentile(xs, 100) == 50
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.samples_beyond(400, 95) == 20
    assert stats.samples_beyond(199, 95) == 9
    # statistics.quantiles(n=4) of 1..6: q1 1.75, q3 5.25, median 3.5
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)
    assert math.isinf(stats.percentile([1.0, math.inf], 95))


def test_latency_is_measured_from_when_the_request_was_due():
    # due at 1.0 but sent late at 1.4, done at 2.0: the user waited 1.0
    assert stats.latencies_from_due([1.0, 2.0], [2.0, None]) == [
        1.0, math.inf]
    # a run reports a missing request at the end of its drain instead
    assert stats.latencies_from_due([1.0, 2.0], [2.0, None], 9.0) == [
        1.0, 7.0]


# -- generators ---------------------------------------------------------------

MIX = {"prompt": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                  "lo": 16, "hi": 512},
       "output": {"dist": "lognormal", "median": 48, "sigma": 0.7, "lo": 8,
                  "hi": 256},
       "shared_prefix_tokens": 64, "shared_prefix_share": 0.7}


def _key(reqs):
    return [(round(r["due"], 9), tuple(r["prompt"]), r["max_new"])
            for r in reqs]


def test_open_loop_generator_same_seed_same_requests_other_seed_same_work():
    a = serve.make_requests(MIX, 32000, 11, 200, 40.0, 8.0)
    b = serve.make_requests(MIX, 32000, 11, 200, 40.0, 8.0)
    c = serve.make_requests(MIX, 32000, 2**31 + 12, 200, 40.0, 8.0)
    assert _key(a) == _key(b) and _key(a) != _key(c)
    # the schedule is the mix's: another seed sends other token ids at the
    # same times, with the same lengths
    shape = [(r["due"], len(r["prompt"]), r["max_new"], r["shared"])
             for r in a]
    assert shape == [(r["due"], len(r["prompt"]), r["max_new"], r["shared"])
                     for r in c]
    other = serve.make_requests(dict(MIX, schedule_seed=5), 32000, 11, 200,
                                40.0, 8.0)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"]
                                                     for r in other)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in other]
    ga = np.diff([8.0] + [r["due"] for r in a])
    go = np.diff([8.0] + [r["due"] for r in other])
    assert np.allclose(np.sort(ga), np.sort(go))
    assert all(8.0 < r["due"] < 48.0 for r in a)
    assert sum(r["shared"] for r in a) == 140
    pre = [tuple(r["prompt"][:64]) for r in a if r["shared"]]
    assert len(set(pre)) == 1
    assert pre[0] != tuple([r for r in c if r["shared"]][0]["prompt"][:64])
    assert all(16 <= len(r["prompt"]) <= 512 and 8 <= r["max_new"] <= 256
               for r in a)


def test_length_quantiles_follow_the_stated_distribution():
    lens = serve.quantile_lengths(MIX["output"], 1000)
    assert np.median(lens) == pytest.approx(48, abs=1)
    assert np.mean(lens) == pytest.approx(61, abs=2)
    assert np.percentile(lens, 95) == pytest.approx(152, abs=5)
    gaps = serve.quantile_gaps(500, 50.0)
    assert sum(gaps) == pytest.approx(50.0 * 500 / 501)
    # exponential: the standard deviation is about the mean
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.08)


def test_train_batches_same_seed_same_batches_and_the_same_work():
    t = {"rows": 16, "seq": 12, "min_len": 6, "pool": 3}
    a, b = train.make_batches(t, 50, 5), train.make_batches(t, 50, 5)
    c = train.make_batches(t, 50, 2**31 + 6)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["src"], c[0]["src"])
    # every batch of every seed holds the same multiset of lengths
    counts = {train.real_tokens(x) for x in a + c}
    assert len(counts) == 1
    assert sorted(a[0]["src_len"]) == sorted(c[2]["tgt_len"])
    assert a[0]["src_len"].min() == 6 and a[0]["src_len"].max() == 12
    assert np.array_equal(a[0]["lbl"], np.roll(a[0]["tgt"], -1, 1))
    # rows all differ
    assert len({tuple(r) for r in a[0]["src"]}) == 16


def test_worst_leaf_gap_measures_small_leaves_against_the_median_leaf():
    want = {"a": 1.0, "b": 0.1, "c": 1e-9}
    got = {"a": 1.01, "b": 0.1, "c": 2e-9}
    gap, leaf = train.worst_leaf_gap(got, want)
    assert leaf == "a" and gap == pytest.approx(0.01)
    gap, leaf = train.worst_leaf_gap({"a": 1.0, "b": 0.1, "c": 0.05}, want)
    assert leaf == "c" and gap == pytest.approx(0.5)
    assert math.isinf(train.worst_leaf_gap(
        {"a": math.nan, "b": 0.1, "c": 0.0}, want)[0])
