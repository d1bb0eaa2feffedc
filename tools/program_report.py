"""Per-program cost/memory/step report — which compiled program spends
the time and the HBM.

Renders the table the program-profile registry maintains in-process
(fingerprint, executor kind, steps, wall clock + share, flops/step,
bytes/step, estimated peak HBM, ground-truth MFU from the compiler's
own flop accounting, and from the compile records what set-up paid to
lower it — build, trace, lowering, executable seconds — and why: a retrace
storm reads ``first,feed_signature*40`` — and what its dense products must
do a step, by op type and part: the ``op_work`` its ``work`` rules counted
at lowering, no benchmark run needed) from a monitor JSONL log — the offline twin of
calling ``paddle_tpu.monitor.program_profile.report_rows()`` /
``render_table()`` on a live registry.

Usage:
    python tools/program_report.py /path/to/monitor_logs        # dir
    python tools/program_report.py monitor-1234.jsonl           # one file
    python tools/program_report.py logs/ --peak_tflops 197 --json

The log must come from a run with the monitor on
(``FLAGS_monitor_log_dir=...``): ``program_profile`` events carry each
compiled program's cost/memory analysis, ``compile_record`` events each
lowering's cause and phases, ``step_stats`` events carry the
per-step fingerprint tags this report joins on, and ``device_stats``
events (mesh runs) feed the per-device peak-HBM block — min/max across
the mesh devices, the one-table readout of the fsdp 1/N claim.
"""

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_records(path):
    """All JSONL records under ``path`` (a file, or a directory whose
    ``*.jsonl`` files — including rotated ``.jsonl.N`` generations — are
    read).  Unparseable lines are skipped (a crashed writer can leave a
    torn tail)."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "*.jsonl"))
                       + glob.glob(os.path.join(path, "*.jsonl.*")))
    else:
        files = [path]
    records = []
    for f in files:
        with open(f) as fh:
            for ln in fh:
                try:
                    records.append(json.loads(ln))
                except ValueError:
                    continue
    return records


def rows_from_records(records, peak_tflops=None, run_id=None):
    """Replay JSONL records into program-report rows: profiles from
    ``program_profile`` events (latest per fingerprint wins), step
    accounting from fingerprint-tagged ``step_stats`` events.
    ``run_id`` filters to one run's records (a shared log dir holds
    many)."""
    from paddle_tpu.monitor.program_profile import (ProgramProfile,
                                                    report_rows)

    profiles, acct, probe_acct, lowered = {}, {}, {}, []
    partitions = {}     # fingerprint -> set of distinct partition ids
    for r in records:
        if not isinstance(r, dict):
            continue
        if run_id and r.get("run_id") not in (None, run_id):
            continue
        ev = r.get("event")
        if ev == "program_profile" and r.get("fingerprint"):
            partitions.setdefault(r["fingerprint"], set()).add(
                r.get("partition"))
            profiles[r["fingerprint"]] = ProgramProfile(
                r["fingerprint"], (), r.get("kind", "executor"),
                flops=r.get("flops", 0.0) or 0.0,
                bytes_accessed=r.get("bytes_accessed", 0.0) or 0.0,
                argument_bytes=r.get("argument_bytes", 0),
                output_bytes=r.get("output_bytes", 0),
                temp_bytes=r.get("temp_bytes", 0),
                generated_code_bytes=r.get("generated_code_bytes", 0),
                alias_bytes=r.get("alias_bytes", 0),
                peak_hbm_bytes=r.get("peak_hbm_bytes", 0),
                device=r.get("device"),
                device_kind=r.get("device_kind"))
        elif ev == "compile_record" and r.get("fingerprint"):
            lowered.append(r)
        elif ev == "step_stats" and r.get("fingerprint"):
            # tuner-probe steps (tagged by probe_accounting at record
            # time) accumulate separately, mirroring note_step: probe
            # wall clock never blends into a steady row, even for the
            # same fingerprint
            bucket = probe_acct if r.get("probe") else acct
            a = bucket.setdefault(r["fingerprint"],
                                  {"steps": 0, "wall_s": 0.0,
                                   "examples": 0,
                                   "kind": r.get("executor", "")})
            a["steps"] += 1
            a["wall_s"] += r.get("step_seconds", 0.0) or 0.0
            a["examples"] += r.get("examples", 0) or 0
    rows = report_rows(peak_tflops=peak_tflops, profiles_by_fp=profiles,
                       acct_by_fp=acct, probe_acct_by_fp=probe_acct,
                       compile_records=lowered)
    # one program compiled under SEVERAL mesh/sharding layouts (the
    # replicated-vs-fsdp A/B) shares a fingerprint: step accounting
    # covers all layouts while the profile columns are the latest
    # layout's — flag the multiplicity so the row isn't read as one
    # homogeneous program
    for row in rows:
        n = len(partitions.get(row["fingerprint"], ()))
        if n > 1:
            row["partitions"] = n
            row["fp12"] = row["fp12"][:11] + "*"   # visible in the table
    return rows


def devices_from_records(records, run_id=None):
    """Per-device memory summary from ``device_stats`` events (the JSONL
    twin of the ``device/<id>/bytes_in_use`` gauges ParallelExecutor
    publishes each sampled mesh step): ``{device: {bytes_in_use_peak,
    bytes_limit}}``.  The min/max across the mesh makes the fsdp 1/N
    per-device HBM claim readable from one table."""
    out = {}
    for r in records:
        if not isinstance(r, dict) or r.get("event") != "device_stats":
            continue
        if run_id and r.get("run_id") not in (None, run_id):
            continue
        for dev, ms in (r.get("devices") or {}).items():
            cur = out.setdefault(dev, {"bytes_in_use_peak": 0,
                                       "bytes_limit": None})
            peak = ms.get("bytes_in_use_peak") or ms.get("bytes_in_use")
            if peak and peak > cur["bytes_in_use_peak"]:
                cur["bytes_in_use_peak"] = int(peak)
            if ms.get("bytes_limit"):
                cur["bytes_limit"] = int(ms["bytes_limit"])
    return out


def render_device_table(devices):
    """Fixed-width per-device peak-HBM block + the min/max summary."""
    from paddle_tpu.monitor.program_profile import _fmt_mib

    lines = ["", "%-12s %12s %12s" % ("device", "peakHBM", "limit"),
             "-" * 38]
    for dev in sorted(devices):
        d = devices[dev]
        lines.append("%-12s %12s %12s" % (
            dev, _fmt_mib(d["bytes_in_use_peak"]),
            _fmt_mib(d["bytes_limit"]) if d["bytes_limit"] else "-"))
    peaks = [d["bytes_in_use_peak"] for d in devices.values()]
    lines.append("per-device peak HBM across %d devices: min %s / max %s"
                 % (len(peaks), _fmt_mib(min(peaks)), _fmt_mib(max(peaks))))
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="per-program cost/memory/step report from a monitor "
                    "JSONL log; the last column is the dense products' "
                    "required TFLOP and least GB a step by op type and "
                    "part (fwd / dx / dw), as counted at lowering")
    p.add_argument("log", help="monitor JSONL file, or a "
                               "FLAGS_monitor_log_dir directory")
    p.add_argument("--peak_tflops", type=float, default=None,
                   help="chip peak TFLOP/s for the MFU column "
                        "(default: the DEVICE_PEAKS row of each "
                        "program's recorded device_kind; an unlisted "
                        "device gets no MFU)")
    p.add_argument("--run_id", default=None,
                   help="only records of this run correlation id")
    p.add_argument("--top", type=int, default=0,
                   help="show only the top N programs by wall clock")
    p.add_argument("--json", action="store_true",
                   help="emit the rows as JSON instead of a table")
    args = p.parse_args(argv)

    from paddle_tpu.monitor.program_profile import render_table

    records = load_records(args.log)
    rows = rows_from_records(records, peak_tflops=args.peak_tflops,
                             run_id=args.run_id)
    devices = devices_from_records(records, run_id=args.run_id)
    if args.top:
        rows = rows[:args.top]
    if not rows:
        print("no program_profile / fingerprint-tagged step_stats "
              "records in %s (monitor on? FLAGS_monitor_log_dir set?)"
              % args.log)
        return 1
    if args.json:
        # one stable schema: devices is {} on runs whose backend
        # reports no memory stats (single-device/CPU)
        print(json.dumps({"programs": rows, "devices": devices},
                         indent=2))
    else:
        print(render_table(rows))
        if devices:
            print(render_device_table(devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
