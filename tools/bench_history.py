"""Cross-run bench regression tracking over BENCH_r*.json artifacts.

Driver wrappers (``{n, cmd, rc, tail, parsed}``) and freshly produced
bench.py artifacts (the bare primary JSON line, ``--out`` files) are
ingested into a history index; every rung's step time / throughput /
MFU / goodput ratio is compared against the **best prior comparable
run** with a noise band, and a PASS/REGRESSED table comes out
(``--json`` for CI).

Comparability gating: bench.py ends a timed window by fetching the loss
(since r3); windows recorded before that were ended another way and
read 2-4.5x high.  Runs whose rungs carry no ``min_step_s``/
``n_windows`` fields predate that methodology and are indexed as
``legacy_methodology``: listed, never used as baselines, never judged.
Runs whose wrapper has ``parsed: null`` (a driver timeout that killed
the artifact) are ``incomplete``.

Per-rung fields compared, each with the same relative noise band
(default 5%):

* ``min_step_s``   — lower is better (the primary estimator)
* ``value``        — higher is better (throughput)
* ``mfu``          — higher is better (falls back to ``est_mfu``)
* ``goodput``      — higher is better (``goodput.goodput_ratio``,
  artifacts from schema_version 2 on)

Error rungs (``unit == "error"``) and rungs marked ``informational``
are listed but excluded from the overall verdict — the scored rungs
are the regression gate, exactly as bench.py's ladder defines them.

Usage:
    python tools/bench_history.py BENCH_r0*.json
    python tools/bench_history.py BENCH_r0*.json new_run.json --json
    python tools/bench_history.py ... --noise 0.08 --index history.json
"""

import argparse
import glob
import json
import os
import sys

# (field, better, pretty) — the comparison schema per rung.
# throughput_rps / p99_ms are the serving rung's SLO pair (schema v2+);
# that rung is informational, so they index and judge without gating.
# save_wall_s is the ckpt_sharded rung's per-host checkpoint save wall
# clock (also informational: disk-bound, not chip-bound).
# accuracy_delta is the quantized rung's eval delta vs full precision
# (informational like the rung: indexed and judged, never gating).
# sparse_step_s / dense_step_s / incr_ckpt_bytes are the rec_sparse
# rung's vocab-scaling evidence at vocab=1e6 (sparse warm step, the
# dense A/B step, and the incremental-checkpoint delta bytes — all
# lower is better; informational like the rung).
# sessions_at_fixed_hbm / spec_tok_s / prefix_hit_rate are the
# decode_paged rung's ISSUE-16 triple (HBM-per-session ratio,
# speculative token rate, prefix-cache hit rate — all higher is
# better; informational like the rung, indexed so regressions in the
# decode path surface across rounds without gating).
# p99_queue_wait_ms / p99_decode_ms are the ISSUE-17 request-trace
# stage p99s (serving admission wait; per-tick decode share on the
# paged arm) — informational, never gating: they attribute a p99_ms
# move to a stage, they don't independently gate a run.
# aggregate_rps / reroute_latency_ms are the ISSUE-18 serving-fleet
# pair (4-replica routed aggregate req/s against mock-backend
# capacity; p99 first-route-to-accepted-completion failover latency)
# — informational: both ride multi-process drills whose absolute
# numbers move with host load, so they index trends, never gate.
# fields that are informational PER-FIELD, even inside a gating rung:
# judged against history and printed, but never counted into a run's
# ``regressions`` — stage attribution explains a p99_ms move, it must
# not double-gate it
INFORMATIONAL_FIELDS = frozenset({"p99_queue_wait_ms",
                                  "p99_decode_ms",
                                  "aggregate_rps",
                                  "reroute_latency_ms",
                                  "digest_build_us",
                                  "straggler_detect_windows",
                                  "health_overhead_pct_c1",
                                  "health_overhead_pct_c10",
                                  "provenance_replay_ms"})

FIELDS = (("min_step_s", "lower", "step_s"),
          ("value", "higher", "value"),
          ("mfu", "higher", "mfu"),
          ("goodput", "higher", "goodput"),
          ("throughput_rps", "higher", "rps"),
          ("p99_ms", "lower", "p99"),
          ("save_wall_s", "lower", "save_s"),
          ("accuracy_delta", "lower", "acc_d"),
          ("sparse_step_s", "lower", "sp_step"),
          ("dense_step_s", "lower", "dn_step"),
          ("incr_ckpt_bytes", "lower", "incr_b"),
          ("sessions_at_fixed_hbm", "higher", "sess_x"),
          ("spec_tok_s", "higher", "spec_ts"),
          ("prefix_hit_rate", "higher", "pfx_hit"),
          ("p99_queue_wait_ms", "lower", "p99_qw"),
          ("p99_decode_ms", "lower", "p99_dec"),
          ("aggregate_rps", "higher", "agg_rps"),
          ("reroute_latency_ms", "lower", "rerte"),
          ("digest_build_us", "lower", "dig_us"),
          ("straggler_detect_windows", "lower", "strag_w"),
          # ISSUE-20 model-health probe: FLAGS_health step overhead at
          # publication cadence 1 / 10 and the one-shot NaN-provenance
          # replay latency — informational (CPU wall clock), indexed so
          # probe-cost regressions surface across rounds
          ("health_overhead_pct_c1", "lower", "hlth_c1"),
          ("health_overhead_pct_c10", "lower", "hlth_c10"),
          ("provenance_replay_ms", "lower", "prov_ms"))


def _rung_record(r):
    """Normalize one rung dict (primary or extra_metrics entry)."""
    if not isinstance(r, dict) or not r.get("metric"):
        return None
    out = {"metric": r["metric"], "unit": r.get("unit"),
           "value": r.get("value"),
           "vs_baseline": r.get("vs_baseline"),
           "informational": bool(r.get("informational"))
           or r.get("unit") == "error" or "error" in r,
           "error": r.get("error")}
    if r.get("min_step_s") is not None:
        out["min_step_s"] = r["min_step_s"]
        out["n_windows"] = r.get("n_windows")
    mfu = r.get("mfu", r.get("exact_mfu", r.get("est_mfu")))
    if mfu is not None:
        out["mfu"] = mfu
    for f in ("throughput_rps", "p99_ms", "save_wall_s",
              "accuracy_delta", "sparse_step_s", "dense_step_s",
              "incr_ckpt_bytes", "sessions_at_fixed_hbm",
              "spec_tok_s", "prefix_hit_rate",
              "p99_queue_wait_ms", "p99_decode_ms",
              "aggregate_rps", "reroute_latency_ms",
              "digest_build_us", "straggler_detect_windows",
              "health_overhead_pct_c1", "health_overhead_pct_c10",
              "provenance_replay_ms"):
        if r.get(f) is not None:
            out[f] = r[f]
    gp = r.get("goodput")
    if isinstance(gp, dict) and gp.get("goodput_ratio") is not None:
        out["goodput"] = gp["goodput_ratio"]
    return out


def normalize_run(payload, key, order):
    """One artifact -> a normalized history entry.  ``payload`` is the
    bench.py primary dict (already unwrapped); ``key`` a stable run
    name; ``order`` the comparison ordering index."""
    rungs = []
    for r in [payload] + list(payload.get("extra_metrics") or []):
        rec = _rung_record(r)
        if rec is not None:
            rungs.append(rec)
    comparable = any("min_step_s" in r for r in rungs)
    return {"run": key, "order": order,
            "run_id": payload.get("run_id"),
            "schema_version": payload.get("schema_version", 1),
            "ladder_complete": payload.get("ladder_complete"),
            "status": "ok" if comparable else "legacy_methodology",
            "rungs": rungs}


def load_artifact(path, order):
    """Load one artifact file: a driver wrapper ({n, rc, parsed}), a
    bare bench.py JSON line/dict, or a JSONL whose LAST parseable line
    is the artifact (the ladder reprints the primary after every
    rung)."""
    with open(path) as f:
        text = f.read().strip()
    try:
        data = json.loads(text)
    except ValueError:
        data = None
        for ln in reversed(text.splitlines()):
            try:
                data = json.loads(ln)
                break
            except ValueError:
                continue
        if data is None:
            return {"run": _run_key(path, None), "order": order,
                    "status": "unparseable", "rungs": []}
    if isinstance(data, dict) and "parsed" in data and "rc" in data:
        # driver wrapper (the committed BENCH_r*.json shape)
        key = _run_key(path, data.get("n"))
        if not isinstance(data.get("parsed"), dict):
            return {"run": key, "order": order, "status": "incomplete",
                    "rc": data.get("rc"), "rungs": []}
        out = normalize_run(data["parsed"], key, order)
        out["rc"] = data.get("rc")
        return out
    if isinstance(data, dict):
        return normalize_run(data, _run_key(path, None), order)
    return {"run": _run_key(path, None), "order": order,
            "status": "unparseable", "rungs": []}


def _run_key(path, n):
    if n is not None:
        return "r%02d" % int(n)
    return os.path.splitext(os.path.basename(path))[0]


def _judge(field, better, cur, best, noise):
    """PASS/REGRESSED verdict for one field against the prior best."""
    if cur is None or best is None:
        return None
    if better == "lower":
        regressed = cur > best * (1.0 + noise)
        delta = (cur - best) / best if best else 0.0
    else:
        regressed = cur < best * (1.0 - noise)
        delta = (cur - best) / best if best else 0.0
    return {"field": field, "current": cur, "best_prior": best,
            "delta": round(delta, 4),
            "verdict": "REGRESSED" if regressed else "PASS"}


def compare(runs, noise=0.05):
    """Judge every comparable run against the best prior comparable
    values per (metric, field).  Mutates each run dict with a
    ``comparisons`` list; returns the overall report."""
    runs = sorted(runs, key=lambda r: r["order"])
    # best-so-far per (metric, field), built run by run so each run is
    # judged only against STRICTLY PRIOR history
    best = {}
    latest_judged = None
    for run in runs:
        comparisons = []
        if run["status"] == "ok":
            for rung in run["rungs"]:
                if rung.get("error"):
                    continue   # failed rung: nothing meaningful to judge
                for field, better, _ in FIELDS:
                    cur = rung.get(field)
                    if cur is None:
                        continue
                    v = _judge(field, better,
                               cur, best.get((rung["metric"], field)),
                               noise)
                    if v is not None:
                        v.update(metric=rung["metric"],
                                 informational=rung["informational"]
                                 or field in INFORMATIONAL_FIELDS)
                        comparisons.append(v)
            run["comparisons"] = comparisons
            run["regressions"] = [
                c for c in comparisons
                if c["verdict"] == "REGRESSED" and not c["informational"]]
            run["verdict"] = "REGRESSED" if run["regressions"] else "PASS"
            latest_judged = run
            # fold this run into the baselines AFTER judging it
            # (informational rungs too: they are judged-not-gating, so
            # they need baselines; error rungs carry no numbers)
            for rung in run["rungs"]:
                if rung.get("error"):
                    continue
                for field, better, _ in FIELDS:
                    cur = rung.get(field)
                    if cur is None:
                        continue
                    k = (rung["metric"], field)
                    if k not in best:
                        best[k] = cur
                    elif better == "lower":
                        best[k] = min(best[k], cur)
                    else:
                        best[k] = max(best[k], cur)
    overall = latest_judged["verdict"] if latest_judged is not None \
        else "NO_COMPARABLE_RUNS"
    return {"noise_band": noise, "runs": runs,
            "latest": latest_judged["run"] if latest_judged else None,
            "overall": overall}


def render(report):
    lines = []
    for run in report["runs"]:
        if run["status"] != "ok":
            lines.append("%-12s %s%s" % (
                run["run"], run["status"],
                " (rc=%s)" % run.get("rc")
                if run.get("rc") not in (None, 0) else ""))
            continue
        lines.append("%-12s %s  (%d rungs, schema v%s)"
                     % (run["run"], run.get("verdict", "-"),
                        len(run["rungs"]), run.get("schema_version")))
        for c in run.get("comparisons", []):
            lines.append(
                "  %-44s %-10s %12.6g vs best %12.6g  %+6.1f%%  %s%s"
                % (c["metric"], c["field"], c["current"],
                   c["best_prior"], 100 * c["delta"], c["verdict"],
                   " (informational)" if c["informational"] else ""))
    lines.append("overall (latest comparable run%s): %s"
                 % (" %s" % report["latest"] if report["latest"] else "",
                    report["overall"]))
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="cross-run bench regression tracking over bench "
                    "artifacts (driver wrappers or bare bench.py JSON)")
    p.add_argument("artifacts", nargs="+",
                   help="artifact files in run order (globs ok); driver "
                        "wrappers order by their 'n', the rest by "
                        "position")
    p.add_argument("--noise", type=float, default=0.05,
                   help="relative noise band before a delta counts as a "
                        "regression (default 0.05)")
    p.add_argument("--json", action="store_true",
                   help="emit the full report as JSON (CI mode); exit "
                        "code stays 0/1/2 either way")
    p.add_argument("--index", default=None,
                   help="also write the normalized history index to "
                        "this JSON file")
    args = p.parse_args(argv)

    paths = []
    for a in args.artifacts:
        hits = sorted(glob.glob(a))
        paths.extend(hits if hits else [a])
    runs = []
    for i, path in enumerate(paths):
        try:
            runs.append(load_artifact(path, i))
        except OSError as e:
            print("cannot read %s: %s" % (path, e), file=sys.stderr)
            return 2
    # wrapper runs carry their own ordinal: honor it over file order
    for r in runs:
        if r["run"].startswith("r") and r["run"][1:].isdigit():
            r["order"] = (0, int(r["run"][1:]))
        else:
            r["order"] = (1, r["order"])
    report = compare(runs, noise=args.noise)
    if args.index:
        tmp = args.index + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2)
        os.replace(tmp, args.index)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render(report))
    return 1 if report["overall"] == "REGRESSED" else 0


if __name__ == "__main__":
    sys.exit(main())
