"""Render a TunedConfig artifact as a human-readable decision table.

The auto-tuner (``paddle_tpu.autotune``) records every decision with
its evidence — probe measurements, rejected candidates, the preflight
estimates vs measured windows that drove each choice.  This CLI turns
that JSON artifact into the table an operator reads before trusting
(or pinning over) a tuned configuration.

Usage:
    python tools/autotune_report.py /path/to/tuned.json
    python tools/autotune_report.py tuned.json --json       # passthrough
    python tools/autotune_report.py tuned.json --verbose    # + candidates
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _fmt_value(d):
    """The chosen value column: each knob renders its own shape."""
    v = d.get("chosen")
    if isinstance(v, list):
        return "{%s}" % ",".join(str(x) for x in v)
    return str(v)


def _fmt_evidence(d):
    """One-line evidence summary per knob."""
    knob = d.get("knob")
    if knob == "batch_size":
        cands = d.get("candidates", [])
        ok = sum(1 for c in cands if c.get("status") == "ok")
        rej = [c for c in cands if str(c.get("status", "")).startswith(
            "rejected")]
        parts = ["%d measured" % ok]
        if rej:
            parts.append("%d rejected by HBM estimate" % len(rej))
        reg = [c for c in cands if c.get("status") == "regressed"]
        if reg:
            parts.append("stopped at b%d (s/example regressed)"
                         % reg[0]["batch"])
        if d.get("hbm_limit_bytes"):
            parts.append("ceiling %.1f MiB"
                         % (d["hbm_limit_bytes"] / 1048576.0))
        return ", ".join(parts)
    if knob == "bucket_bounds":
        return "fill %.1f%% vs pad-to-max %.1f%% (%d multiples-of-%d " \
            "considered)" % (100 * d.get("fill", 0.0),
                             100 * d.get("pad_to_max_fill", 0.0),
                             d.get("candidates_considered", 0),
                             d.get("multiple", 0))
    if knob == "checkpoint_interval":
        return ("step %.4fs, snapshot %.4fs, save %.4fs -> overhead "
                "%.2f%% of %.2f%% budget%s" % (
                    d.get("step_s", 0.0), d.get("snapshot_s", 0.0),
                    d.get("save_s", 0.0),
                    100 * d.get("overhead_frac", 0.0),
                    100 * d.get("budget", 0.0),
                    ", drain-bound" if d.get("drain_bound_steps", 0)
                    and d.get("chosen") == d.get("drain_bound_steps")
                    else ""))
    return d.get("evidence", "")


def _rejected(d):
    """Rejected/regressed candidate summaries for the verbose view."""
    out = []
    for c in d.get("candidates", []) or []:
        status = c.get("status", "")
        if status == "ok":
            continue
        line = "b%s: %s" % (c.get("batch"), status)
        if c.get("peak_hbm_bytes"):
            line += " (est peak %.1f MiB)" % (c["peak_hbm_bytes"]
                                              / 1048576.0)
        if c.get("projected_peak_hbm_bytes"):
            line += " (projected peak %.1f MiB, no compile spent)" % (
                c["projected_peak_hbm_bytes"] / 1048576.0)
        if c.get("s_per_example") is not None:
            line += " (%.3g s/example)" % c["s_per_example"]
        out.append(line)
    return out


def render(doc, verbose=False):
    meta = doc.get("meta", {})
    decisions = doc.get("decisions", [])
    lines = []
    head = "TunedConfig"
    if meta.get("model"):
        head += " [%s]" % meta["model"]
    if meta.get("run_id"):
        head += "  run_id=%s" % meta["run_id"]
    lines.append(head)
    hdr = "%-20s %-24s %-8s %s" % ("knob", "chosen", "source",
                                   "evidence")
    lines += [hdr, "-" * max(len(hdr), 72)]
    for d in decisions:
        lines.append("%-20s %-24s %-8s %s" % (
            d.get("knob", "?"), _fmt_value(d)[:24],
            (d.get("source", "") or "")[:8], _fmt_evidence(d)))
        if verbose:
            for r in _rejected(d):
                lines.append("    rejected %s" % r)
            if d.get("fingerprint"):
                lines.append("    program %s" % d["fingerprint"])
    if not decisions:
        lines.append("(no decisions recorded)")
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="decision table from a TunedConfig JSON artifact "
                    "(paddle_tpu.autotune)")
    p.add_argument("artifact", help="TunedConfig JSON file (written by "
                                    "TunedConfig.save / bench.py "
                                    "--autotune)")
    p.add_argument("--json", action="store_true",
                   help="emit the raw artifact JSON (validated)")
    p.add_argument("--verbose", action="store_true",
                   help="also list every rejected candidate with the "
                        "evidence that rejected it")
    args = p.parse_args(argv)

    with open(args.artifact) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "decisions" not in doc:
        print("not a TunedConfig artifact: %s" % args.artifact,
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
    else:
        print(render(doc, verbose=args.verbose))
    return 0


if __name__ == "__main__":
    sys.exit(main())
