"""tools/bench_history.py: cross-run bench regression tracking over
driver wrappers (BENCH_r*.json) and fresh bench.py artifacts —
legacy-methodology gating, noise-band verdicts, the +20% synthetic
perturbation gate, and bare-artifact (schema v2) ingestion."""

import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(ROOT, "tools", "bench_history.py")

sys.path.insert(0, os.path.join(ROOT, "tools"))

import bench_history  # noqa: E402


def _wrapper(n, parsed, rc=0):
    return {"n": n, "cmd": "python bench.py", "rc": rc, "tail": "",
            "parsed": parsed}


def _rung(metric, value, step_s=None, mfu=None, goodput=None,
          informational=False, **extra):
    out = dict({"metric": metric, "value": value, "unit": "items/sec",
                "vs_baseline": 1.0}, **extra)
    if step_s is not None:
        out["min_step_s"] = step_s
        out["n_windows"] = 3
    if mfu is not None:
        out["mfu"] = mfu
    if goodput is not None:
        out["goodput"] = {"goodput_ratio": goodput,
                          "buckets": {}, "wall_seconds": 1.0}
    if informational:
        out["informational"] = True
    return out


def _write(tmp_path, name, payload):
    p = tmp_path / name
    p.write_text(json.dumps(payload))
    return str(p)


def _history(tmp_path):
    """The four wrapper shapes a driver history holds, as files: two
    legacy runs (rungs without ``min_step_s``/``n_windows``), one
    comparable run with extra rungs, and an rc=124 timeout with no
    parsed line."""
    legacy2 = _rung("resnet50_images_per_sec_bf16", 7903.64)
    legacy2["extra_metrics"] = [
        _rung("transformer_base_tokens_per_sec_bf16", 137013.68)]
    ok = _rung("resnet50_images_per_sec_bf16", 2334.75, step_s=0.054824,
               est_mfu=0.1458)
    ok["extra_metrics"] = [
        _rung("transformer_base_tokens_per_sec_bf16", 133219.73,
              step_s=0.061492, est_mfu=0.2455)]
    wrappers = [_wrapper(1, _rung("resnet50_images_per_sec", 7966.2)),
                _wrapper(2, legacy2), _wrapper(3, ok),
                _wrapper(4, None, rc=124)]
    return [_write(tmp_path, "BENCH_r%02d.json" % w["n"], w)
            for w in wrappers], ok


def test_committed_artifact_evolution_passes(tmp_path):
    """A r01->r04 history: r01/r02 predate the fetch-sync methodology
    (legacy, never baselines), r04 is an rc=124 timeout with no parsed
    line (incomplete), r03 is the first comparable run — the evolution
    PASSes."""
    paths, _ = _history(tmp_path)
    runs = [bench_history.load_artifact(p, i) for i, p in
            enumerate(paths)]
    by = {r["run"]: r for r in runs}
    assert by["r01"]["status"] == "legacy_methodology"
    assert by["r02"]["status"] == "legacy_methodology"
    assert by["r03"]["status"] == "ok"
    assert by["r04"]["status"] == "incomplete" and by["r04"]["rc"] == 124
    report = bench_history.compare(runs)
    assert report["overall"] == "PASS"
    assert report["latest"] == "r03"


def test_synthetic_perturbation_regresses(tmp_path):
    """A +20% step-time copy of r03 (value scaled down accordingly)
    must come back REGRESSED against the history — the CI gate's
    self-check."""
    paths, r03 = _history(tmp_path)
    bad = copy.deepcopy(r03)
    bad["min_step_s"] = round(r03["min_step_s"] * 1.2, 6)
    bad["value"] = round(r03["value"] / 1.2, 2)
    p = _write(tmp_path, "BENCH_r05.json", _wrapper(5, bad))
    out = subprocess.run(
        [sys.executable, TOOL] + paths + [p, "--json"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert out.returncode == 1, out.stderr
    report = json.loads(out.stdout)
    assert report["overall"] == "REGRESSED"
    latest = [r for r in report["runs"] if r["run"] == "r05"][0]
    fields = {(c["metric"], c["field"]): c["verdict"]
              for c in latest["comparisons"]}
    assert fields[("resnet50_images_per_sec_bf16",
                   "min_step_s")] == "REGRESSED"
    assert fields[("resnet50_images_per_sec_bf16",
                   "value")] == "REGRESSED"


def test_noise_band_tolerates_small_deltas(tmp_path):
    """Deltas inside the noise band PASS in either direction."""
    a = _wrapper(1, _rung("m", 100.0, step_s=0.100, mfu=0.2,
                          goodput=0.9))
    b = _wrapper(2, _rung("m", 97.0, step_s=0.103, mfu=0.195,
                          goodput=0.87))
    runs = [bench_history.load_artifact(
        _write(tmp_path, "a%d.json" % w["n"], w), i)
        for i, w in enumerate((a, b))]
    report = bench_history.compare(runs, noise=0.05)
    assert report["overall"] == "PASS"
    # ...and a goodput collapse beyond the band is a regression even
    # when throughput holds
    c = _wrapper(3, _rung("m", 100.0, step_s=0.100, mfu=0.2,
                          goodput=0.70))
    runs.append(bench_history.load_artifact(
        _write(tmp_path, "a3.json", c), 2))
    report = bench_history.compare(runs, noise=0.05)
    assert report["overall"] == "REGRESSED"
    regs = report["runs"][-1]["regressions"]
    assert [r["field"] for r in regs] == ["goodput"]


def test_baseline_is_best_prior_not_last(tmp_path):
    """Comparisons run against the BEST prior value, so a slow run
    does not lower the bar for the one after it."""
    ws = [_wrapper(1, _rung("m", 100.0, step_s=0.100)),
          _wrapper(2, _rung("m", 80.0, step_s=0.125)),   # slow run
          _wrapper(3, _rung("m", 90.0, step_s=0.111))]   # still slow
    runs = [bench_history.load_artifact(
        _write(tmp_path, "w%d.json" % w["n"], w), i)
        for i, w in enumerate(ws)]
    report = bench_history.compare(runs, noise=0.05)
    assert report["runs"][1]["verdict"] == "REGRESSED"
    assert report["runs"][2]["verdict"] == "REGRESSED"   # vs r1's best


def test_informational_and_error_rungs_do_not_gate(tmp_path):
    parsed = dict(_rung("scored", 100.0, step_s=0.1),
                  extra_metrics=[
                      _rung("era_rung", 50.0, step_s=0.2,
                            informational=True),
                      dict(_rung("broken_error", 0.0), unit="error",
                           error="boom")])
    a = _wrapper(1, parsed)
    worse = copy.deepcopy(parsed)
    worse["extra_metrics"][0]["min_step_s"] = 0.4   # era rung 2x slower
    b = _wrapper(2, worse)
    runs = [bench_history.load_artifact(
        _write(tmp_path, "i%d.json" % w["n"], w), i)
        for i, w in enumerate((a, b))]
    report = bench_history.compare(runs, noise=0.05)
    # the informational regression is VISIBLE but does not gate
    comps = report["runs"][1]["comparisons"]
    assert any(c["metric"] == "era_rung"
               and c["verdict"] == "REGRESSED" for c in comps)
    assert report["overall"] == "PASS"
    # error rungs are never judged
    assert not any(c["metric"] == "broken_error" for c in comps)


def test_trace_stage_fields_index_without_gating(tmp_path):
    """ISSUE 17: p99_queue_wait_ms / p99_decode_ms are indexed and
    judged against history, but NEVER gate — even inside a gating
    (non-informational) rung, a 10x stage regression stays
    informational while a real p99_ms regression still gates."""
    assert "p99_queue_wait_ms" in bench_history.INFORMATIONAL_FIELDS
    assert "p99_decode_ms" in bench_history.INFORMATIONAL_FIELDS
    base = _rung("serving_requests_per_sec", 100.0, step_s=0.1,
                 p99_ms=20.0, p99_queue_wait_ms=5.0, p99_decode_ms=2.0)
    worse = dict(base, p99_queue_wait_ms=50.0, p99_decode_ms=20.0)
    runs = [bench_history.load_artifact(
        _write(tmp_path, "t%d.json" % i, _wrapper(i + 1, r)), i)
        for i, r in enumerate((base, worse))]
    report = bench_history.compare(runs, noise=0.05)
    comps = report["runs"][1]["comparisons"]
    # both stage fields are indexed, judged REGRESSED, and marked
    # informational despite riding a gating rung
    for f in ("p99_queue_wait_ms", "p99_decode_ms"):
        c = next(c for c in comps if c["field"] == f)
        assert c["verdict"] == "REGRESSED" and c["informational"], c
    assert report["runs"][1]["verdict"] == "PASS"
    assert report["overall"] == "PASS"
    # control: the same delta on p99_ms itself DOES gate
    gated = dict(base, p99_ms=200.0)
    runs = [bench_history.load_artifact(
        _write(tmp_path, "g%d.json" % i, _wrapper(i + 1, r)), i)
        for i, r in enumerate((base, gated))]
    assert bench_history.compare(
        runs, noise=0.05)["runs"][1]["verdict"] == "REGRESSED"


def test_fleet_fields_index_without_gating(tmp_path):
    """ISSUE 18: aggregate_rps / reroute_latency_ms (the serving-fleet
    scaling and failover-latency pair) are indexed and judged against
    history but NEVER gate — multi-process drill numbers move with
    host load."""
    assert "aggregate_rps" in bench_history.INFORMATIONAL_FIELDS
    assert "reroute_latency_ms" in bench_history.INFORMATIONAL_FIELDS
    base = _rung("serving_fleet", 390.0, step_s=0.1,
                 aggregate_rps=390.0, reroute_latency_ms=270.0)
    worse = dict(base, aggregate_rps=100.0, reroute_latency_ms=2000.0)
    runs = [bench_history.load_artifact(
        _write(tmp_path, "f%d.json" % i, _wrapper(i + 1, r)), i)
        for i, r in enumerate((base, worse))]
    report = bench_history.compare(runs, noise=0.05)
    comps = report["runs"][1]["comparisons"]
    for f in ("aggregate_rps", "reroute_latency_ms"):
        c = next(c for c in comps if c["field"] == f)
        assert c["verdict"] == "REGRESSED" and c["informational"], c
    assert report["overall"] == "PASS"


def test_fleet_telemetry_fields_index_without_gating(tmp_path):
    """ISSUE 19: digest_build_us / straggler_detect_windows (the fleet
    telemetry rung's digest-cost and detection-latency pair) are
    indexed and judged against history but NEVER gate — microsecond
    timings swing with CI host load."""
    assert "digest_build_us" in bench_history.INFORMATIONAL_FIELDS
    assert "straggler_detect_windows" in bench_history.INFORMATIONAL_FIELDS
    base = _rung("fleet_telemetry", 40.0, step_s=0.1,
                 digest_build_us=40.0, straggler_detect_windows=1)
    worse = dict(base, digest_build_us=300.0, straggler_detect_windows=8)
    runs = [bench_history.load_artifact(
        _write(tmp_path, "t%d.json" % i, _wrapper(i + 1, r)), i)
        for i, r in enumerate((base, worse))]
    report = bench_history.compare(runs, noise=0.05)
    comps = report["runs"][1]["comparisons"]
    for f in ("digest_build_us", "straggler_detect_windows"):
        c = next(c for c in comps if c["field"] == f)
        assert c["verdict"] == "REGRESSED" and c["informational"], c
    assert report["overall"] == "PASS"


def test_bare_schema_v2_artifact_ingests_with_goodput(tmp_path):
    """A fresh bench.py artifact (bare JSON line, schema_version 2,
    run_id, embedded goodput) ingests as a comparable run keyed after
    the wrapper history."""
    bare = dict(_rung("m", 100.0, step_s=0.1, goodput=0.93),
                schema_version=2, run_id="abcd1234-0001",
                ladder_complete=True)
    run = bench_history.load_artifact(
        _write(tmp_path, "fresh.json", bare), 7)
    assert run["status"] == "ok"
    assert run["schema_version"] == 2
    assert run["run_id"] == "abcd1234-0001"
    assert run["rungs"][0]["goodput"] == pytest.approx(0.93)
    # a ladder --out file is the reprinted LAST line of a JSONL stream
    stream = "\n".join(["not json", json.dumps(bare)])
    p = tmp_path / "stream.json"
    p.write_text(stream)
    run2 = bench_history.load_artifact(str(p), 8)
    assert run2["status"] == "ok"


def test_index_written_atomically(tmp_path):
    a = _write(tmp_path, "x1.json",
               _wrapper(1, _rung("m", 100.0, step_s=0.1)))
    idx = str(tmp_path / "history.json")
    rc = bench_history.main([a, "--index", idx, "--json"])
    assert rc == 0
    with open(idx) as f:
        saved = json.load(f)
    assert saved["overall"] == "PASS"
    assert saved["runs"][0]["run"] == "r01"


def test_serving_rung_slo_fields_indexed_but_non_gating(tmp_path):
    """The serving rung's {throughput_rps, p99_ms} SLO pair is indexed
    and judged, but the rung is informational — a serving regression
    never flips the overall verdict (non-gating at first)."""
    def serving(rps, p99):
        return _rung("serving_requests_per_sec", rps,
                     informational=True, throughput_rps=rps,
                     p99_ms=p99, min_step_s=0.01, n_windows=1)

    r1 = {"metric": "resnet", "value": 100.0, "unit": "img/s",
          "vs_baseline": 1.0, "min_step_s": 0.5, "n_windows": 3,
          "extra_metrics": [serving(3000.0, 25.0)]}
    # next run: scored rung steady, serving MUCH worse
    r2 = copy.deepcopy(r1)
    r2["extra_metrics"] = [serving(1000.0, 400.0)]
    paths = [_write(tmp_path, "a.json", _wrapper(1, r1)),
             _write(tmp_path, "b.json", _wrapper(2, r2))]
    report = bench_history.compare(
        [bench_history.load_artifact(p, i)
         for i, p in enumerate(paths)])
    runs = {r["run"]: r for r in report["runs"]}
    rec = [g for g in runs["r02"]["rungs"]
           if g["metric"] == "serving_requests_per_sec"][0]
    assert rec["throughput_rps"] == 1000.0 and rec["p99_ms"] == 400.0
    judged = {c["field"]: c for c in runs["r02"]["comparisons"]
              if c["metric"] == "serving_requests_per_sec"}
    assert judged["throughput_rps"]["verdict"] == "REGRESSED"
    assert judged["p99_ms"]["verdict"] == "REGRESSED"
    assert judged["throughput_rps"]["informational"]
    # ...but the run (and the report) still PASS
    assert runs["r02"]["verdict"] == "PASS"
    assert report["overall"] == "PASS"


def test_longctx_ring_rung_indexes_informational(tmp_path):
    """ISSUE 12: the T>=32k ring-attention rung indexes (value +
    min_step_s + goodput tracked against prior history) but never
    gates — a collapsed tokens/sec flags the comparison as
    informational while the run verdict stays PASS."""
    ring = {"metric": "longctx_ring_tokens_per_sec", "value": 5000.0,
            "unit": "tokens/sec", "vs_baseline": 0.0, "seq_len": 32768,
            "sp": 8, "min_step_s": 6.5, "n_windows": 2,
            "informational": True, "virtual_mesh": True,
            "goodput": {"goodput_ratio": 0.4,
                        "buckets": {"compute": 2.0}}}
    base = _wrapper(1, {"metric": "resnet50_images_per_sec_bf16",
                        "value": 100.0, "unit": "images/sec",
                        "vs_baseline": 1.0, "min_step_s": 0.5,
                        "n_windows": 3, "schema_version": 2,
                        "extra_metrics": [ring]})
    worse_ring = copy.deepcopy(ring)
    worse_ring["value"] = 1000.0          # 5x throughput collapse
    worse_ring["goodput"]["goodput_ratio"] = 0.05
    nxt = _wrapper(2, {"metric": "resnet50_images_per_sec_bf16",
                       "value": 100.0, "unit": "images/sec",
                       "vs_baseline": 1.0, "min_step_s": 0.5,
                       "n_windows": 3, "schema_version": 2,
                       "extra_metrics": [worse_ring]})
    p1 = tmp_path / "BENCH_r01.json"
    p2 = tmp_path / "BENCH_r02.json"
    p1.write_text(json.dumps(base))
    p2.write_text(json.dumps(nxt))
    report = bench_history.compare(
        [bench_history.load_artifact(str(p1), 0),
         bench_history.load_artifact(str(p2), 1)])
    last = report["runs"][-1]
    ring_cmp = [c for c in last["comparisons"]
                if c["metric"] == "longctx_ring_tokens_per_sec"]
    assert ring_cmp, "longctx rung not indexed"
    assert any(c["field"] == "value" and c["verdict"] == "REGRESSED"
               for c in ring_cmp)
    assert all(c["informational"] for c in ring_cmp)
    assert last["verdict"] == "PASS"      # informational: never gates
    assert report["overall"] == "PASS"


def test_ckpt_sharded_rung_save_wall_indexed_but_non_gating(tmp_path):
    """ISSUE 13: the per-host sharded checkpoint rung's save wall-clock
    is indexed and judged against prior history (lower is better), but
    the rung is informational (disk-bound) — a slower save never flips
    the overall verdict."""
    def ckpt(wall):
        return _rung("ckpt_sharded_per_host_save", wall,
                     informational=True, save_wall_s=wall,
                     state_bytes=50_000_000,
                     per_host={"4": {"wall_s": wall}})

    r1 = {"metric": "resnet", "value": 100.0, "unit": "img/s",
          "vs_baseline": 1.0, "min_step_s": 0.5, "n_windows": 3,
          "extra_metrics": [ckpt(0.09)]}
    r2 = copy.deepcopy(r1)
    r2["extra_metrics"] = [ckpt(0.50)]       # 5x slower per-host save
    paths = [_write(tmp_path, "a.json", _wrapper(1, r1)),
             _write(tmp_path, "b.json", _wrapper(2, r2))]
    report = bench_history.compare(
        [bench_history.load_artifact(p, i)
         for i, p in enumerate(paths)])
    runs = {r["run"]: r for r in report["runs"]}
    rec = [g for g in runs["r02"]["rungs"]
           if g["metric"] == "ckpt_sharded_per_host_save"][0]
    assert rec["save_wall_s"] == 0.50
    judged = {c["field"]: c for c in runs["r02"]["comparisons"]
              if c["metric"] == "ckpt_sharded_per_host_save"}
    assert judged["save_wall_s"]["verdict"] == "REGRESSED"
    assert judged["save_wall_s"]["informational"]
    assert runs["r02"]["verdict"] == "PASS"
    assert report["overall"] == "PASS"


def test_quantized_rung_accuracy_delta_indexed_but_non_gating(tmp_path):
    """ISSUE 14: the quantized rung's {tok_s, accuracy_delta} index and
    judge against prior history (value higher-better, delta
    lower-better), but the rung is informational while it accumulates
    history — a worse delta never flips the overall verdict."""
    def quant(tok_s, delta):
        return _rung("quantized_tok_per_sec", tok_s, step_s=1.0 / tok_s,
                     informational=True, accuracy_delta=delta,
                     bf16_tok_s=tok_s / 1.5, gate_pass=True)

    r1 = {"metric": "resnet", "value": 100.0, "unit": "img/s",
          "vs_baseline": 1.0, "min_step_s": 0.5, "n_windows": 3,
          "extra_metrics": [quant(420.0, 0.009)]}
    r2 = copy.deepcopy(r1)
    r2["extra_metrics"] = [quant(400.0, 0.019)]   # worse delta + tok/s
    paths = [_write(tmp_path, "qa.json", _wrapper(1, r1)),
             _write(tmp_path, "qb.json", _wrapper(2, r2))]
    report = bench_history.compare(
        [bench_history.load_artifact(p, i)
         for i, p in enumerate(paths)])
    runs = {r["run"]: r for r in report["runs"]}
    rec = [g for g in runs["r02"]["rungs"]
           if g["metric"] == "quantized_tok_per_sec"][0]
    assert rec["accuracy_delta"] == 0.019
    judged = {c["field"]: c for c in runs["r02"]["comparisons"]
              if c["metric"] == "quantized_tok_per_sec"}
    assert judged["accuracy_delta"]["verdict"] == "REGRESSED"
    assert judged["accuracy_delta"]["informational"]
    assert judged["value"]["current"] == 400.0
    assert runs["r02"]["verdict"] == "PASS"
    assert report["overall"] == "PASS"


def test_rec_sparse_rung_fields_indexed_but_non_gating(tmp_path):
    """ISSUE 15: the rec_sparse rung's vocab-scaling fields
    (sparse_step_s / dense_step_s / incr_ckpt_bytes) are indexed and
    judged against prior history (all lower is better), but the rung is
    informational — a regression in any of them never flips the overall
    verdict (the ckpt_sharded precedent)."""
    def rec(sp, dn, incr):
        return _rung("rec_sparse_vocab_scaling", dn / sp,
                     informational=True, sparse_step_s=sp,
                     dense_step_s=dn, incr_ckpt_bytes=incr,
                     per_vocab={"1000000": {"sparse_step_s": sp}})

    r1 = {"metric": "resnet", "value": 100.0, "unit": "img/s",
          "vs_baseline": 1.0, "min_step_s": 0.5, "n_windows": 3,
          "extra_metrics": [rec(0.006, 0.09, 230_000)]}
    r2 = copy.deepcopy(r1)
    # sparse step 10x slower, incremental bytes 50x fatter: the exact
    # regressions the index must surface
    r2["extra_metrics"] = [rec(0.060, 0.09, 12_000_000)]
    paths = [_write(tmp_path, "a.json", _wrapper(1, r1)),
             _write(tmp_path, "b.json", _wrapper(2, r2))]
    report = bench_history.compare(
        [bench_history.load_artifact(p, i)
         for i, p in enumerate(paths)])
    runs = {r["run"]: r for r in report["runs"]}
    rec2 = [g for g in runs["r02"]["rungs"]
            if g["metric"] == "rec_sparse_vocab_scaling"][0]
    assert rec2["sparse_step_s"] == 0.060
    assert rec2["incr_ckpt_bytes"] == 12_000_000
    judged = {c["field"]: c for c in runs["r02"]["comparisons"]
              if c["metric"] == "rec_sparse_vocab_scaling"}
    assert judged["sparse_step_s"]["verdict"] == "REGRESSED"
    assert judged["incr_ckpt_bytes"]["verdict"] == "REGRESSED"
    assert judged["dense_step_s"]["verdict"] == "PASS"
    assert all(judged[f]["informational"]
               for f in ("sparse_step_s", "dense_step_s",
                         "incr_ckpt_bytes"))
    assert runs["r02"]["verdict"] == "PASS"   # informational: no gate
    assert report["overall"] == "PASS"


def test_decode_paged_rung_fields_indexed_but_non_gating(tmp_path):
    """ISSUE 16: the decode_paged rung's triple (sessions_at_fixed_hbm /
    spec_tok_s / prefix_hit_rate — all higher is better) is indexed and
    judged against prior history, but the rung is informational while
    it accumulates history — a collapse in any of them surfaces in the
    comparisons without flipping the overall verdict."""
    def paged(sess, spec_ts, hit):
        return _rung("decode_sessions_at_fixed_hbm", sess,
                     informational=True, sessions_at_fixed_hbm=sess,
                     spec_tok_s=spec_ts, prefix_hit_rate=hit,
                     spec_outputs_match=True)

    r1 = {"metric": "resnet", "value": 100.0, "unit": "img/s",
          "vs_baseline": 1.0, "min_step_s": 0.5, "n_windows": 3,
          "extra_metrics": [paged(10.2, 37.0, 0.75)]}
    r2 = copy.deepcopy(r1)
    # HBM ratio halved, spec tok/s collapsed, prefix cache cold: the
    # exact decode-path regressions the index must surface
    r2["extra_metrics"] = [paged(4.8, 12.0, 0.10)]
    paths = [_write(tmp_path, "a.json", _wrapper(1, r1)),
             _write(tmp_path, "b.json", _wrapper(2, r2))]
    report = bench_history.compare(
        [bench_history.load_artifact(p, i)
         for i, p in enumerate(paths)])
    runs = {r["run"]: r for r in report["runs"]}
    rec = [g for g in runs["r02"]["rungs"]
           if g["metric"] == "decode_sessions_at_fixed_hbm"][0]
    assert rec["sessions_at_fixed_hbm"] == 4.8
    assert rec["spec_tok_s"] == 12.0
    assert rec["prefix_hit_rate"] == 0.10
    judged = {c["field"]: c for c in runs["r02"]["comparisons"]
              if c["metric"] == "decode_sessions_at_fixed_hbm"}
    assert judged["sessions_at_fixed_hbm"]["verdict"] == "REGRESSED"
    assert judged["spec_tok_s"]["verdict"] == "REGRESSED"
    assert judged["prefix_hit_rate"]["verdict"] == "REGRESSED"
    assert all(judged[f]["informational"]
               for f in ("sessions_at_fixed_hbm", "spec_tok_s",
                         "prefix_hit_rate"))
    assert runs["r02"]["verdict"] == "PASS"   # informational: no gate
    assert report["overall"] == "PASS"
