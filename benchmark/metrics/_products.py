"""Shared by the readers of the dense products' required work against their
device time, by part: forward, the input's gradient (dX), the weight's (dW).

Two sources, joined here.  The program's compile records
(``paddle_tpu.compile_cache.compile_log()``) carry ``op_work``: one tuple
``(scope name, op type, part, flops, least bytes, (M, K, N))`` per part of a
``mul`` / ``matmul`` / ``dequant_matmul`` and of their gradients, counted
by the op's own ``work`` rule where the step was lowered.  The traced run's
profile carries the compiled step itself (``benchmark/trace/hlo.py``).

**A device operation belongs to the product part(s) whose ``dot`` /
``convolution`` sits inside it**, by the inner instruction's own
``fluid[..]`` scope and the plain ``dx`` / ``dw`` scope under it — NOT by
the root's ``tf_op``, as the group metrics attribute (``_scopes.py``): XLA
fuses a weight's Adam, a tied ``sum`` or a norm into the product that feeds
it and roots the fusion there.  A custom call (a Pallas kernel standing for
products) has no inner ``dot``: it belongs to the noted parts of its own
scope.  An operation holding several noted parts is split among them by
their flops.  So the floor and the time are of the same operations, and a
share over 100% can only mean a wrong ``work`` rule.

A reader gets ``facts`` and nothing else: handed facts of no traced run, a
trace without an ``Hlo Proto`` (a CPU's) or a program that keeps no
``op_work``, every reader returns None.  The first reader to ask also logs
the table, on lines of their own before the result line."""

import collections
import os
import re
import time

from benchmark import peaks as peaks_table
from benchmark.trace import hlo, scopes
from benchmark.trace import reduce as trace_reduce

PARTS = ("fwd", "dx", "dw")
GROUP = "matmul"
_READ = {}
_LAYOUT = re.compile(r"\{[^}]*\}")


def _log(msg):
    print("[benchmark products] " + msg, flush=True)


# ---------------------------------------------------------------------------
# the two sources
# ---------------------------------------------------------------------------

def noted(records):
    """({(scope name, part): (op type, flops, least bytes, shape)}, over
    how many devices the batch axis is split) from compile records, the
    first note of a part kept (a body traced twice notes twice); None
    where no record holds an ``op_work``."""
    work, shards = {}, 1
    for rec in records or ():
        rows = rec.get("op_work") or ()
        if rows:
            shards = int(rec.get("batch_shards", 1) or 1)
        for row in rows:
            scope, op_type, part, flops, nbytes = row[:5]
            shape = tuple(row[5]) if len(row) > 5 and row[5] else None
            work.setdefault((scope, part), (op_type, int(flops), int(nbytes),
                                            shape))
    return (work, shards) if work else None


def records(facts):
    if not facts.get("trace") or not facts.get("traced_steps"):
        return None
    try:
        from paddle_tpu import compile_cache
        return compile_cache.compile_log()
    except (ImportError, AttributeError):
        return None


def device_peaks(stated):
    """benchmark/peaks.py's row for the device the trace names
    (``device_type_string``, letter case aside), or None."""
    kind = str(stated.get("device_type_string") or "").lower()
    for name, row in peaks_table.PEAKS.items():
        if name.lower() == kind:
            return row
    return None


# ---------------------------------------------------------------------------
# the join: arithmetic on plain tuples
# ---------------------------------------------------------------------------

def _short(shape):
    return _LAYOUT.sub("", shape)


def part_of(op_name):
    if scopes.under(op_name, "dx"):
        return "dx"
    return "dw" if scopes.under(op_name, "dw") else "fwd"


def describe(op, comps, work):
    """What one device operation holds: {"keys": the noted (scope, part)s
    inside it, "types": the Fluid types of its inner instructions, "root":
    (type, output) of its ``tf_op`` or None, "fast": which operands (the
    first four by shape) and whether the result (``out``) lie in the fast
    memory}."""
    tf_op, text = op[3], op[7]
    root = scopes.fluid_scope(tf_op)
    ins = hlo.parse_instruction(text)
    keys, types = [], set()
    if ins is not None and ins.opcode == "custom-call":
        if root is not None:
            scope = "fluid[%s]%s" % root
            keys = [(scope, p) for p in PARTS if (scope, p) in work]
    elif ins is not None:
        products, names = hlo.contents(comps, ins)
        own = tf_op.rsplit(":", 1)[0]
        for p in products:
            name = p.op_name or own
            inner = scopes.fluid_scope(name)
            key = inner and ("fluid[%s]%s" % inner, part_of(name))
            if key in work and key not in keys:
                keys.append(key)
        types = {t[0] for t in map(scopes.fluid_scope, names) if t}
    result, opnds = hlo.operands(text)
    fast = [_short(s) for s in opnds if "S(1)" in s]
    if len(fast) > 4:
        fast[4:] = ["+%d more" % (len(fast) - 4)]
    if "S(1)" in result:
        fast.append("out")
    return {"keys": keys, "types": types, "root": root,
            "fast": " ".join(fast) or "-"}


def attribute(ops, programs, work, rules):
    """One chip's operations against the noted parts.  Seconds over the
    whole trace.  {"parts": {(scope, part): {s, xla_flops, xla_bytes,
    types Counter, fast Counter, roots {type: s}, ops set}}, "foreign":
    {root type outside the group: s of products counted there},
    "no_product": {root type of the group: s of its operations that hold no
    noted product}, "operations": {op name: {s, keys, types, fast, root}}}."""
    parts, foreign, bare, operations, known = {}, {}, {}, {}, {}
    for op, own in zip(ops, scopes.self_times(ops)):
        if trace_reduce.COLLECTIVE.match(op[0]):
            continue
        own /= 1e9
        which = (op[8], op[0])
        if which not in known:
            comps = programs.computations(op[8])
            known[which] = None if comps is None else describe(
                op, comps, work)
        d = known[which]
        if d is None:
            continue
        root = d["root"]
        group = root and scopes.group_of(root[0], root[1], rules)[0]
        if not d["keys"]:
            if group == GROUP:
                bare[root[0]] = bare.get(root[0], 0.0) + own
            continue
        if group != GROUP:
            kind = root[0] if root else "(unscoped)"
            foreign[kind] = foreign.get(kind, 0.0) + own
        row = operations.setdefault(op[0], dict(d, s=0.0))
        row["s"] += own
        total = float(sum(work[k][1] for k in d["keys"])) or 1.0
        for key in d["keys"]:
            share = work[key][1] / total if len(d["keys"]) > 1 else 1.0
            acc = parts.setdefault(key, {
                "s": 0.0, "xla_flops": 0.0, "xla_bytes": 0.0,
                "types": collections.Counter(),
                "fast": collections.Counter(), "roots": {}, "ops": set()})
            acc["s"] += own * share
            acc["xla_flops"] += op[5] * share
            acc["xla_bytes"] += op[6] * share
            if op[0] not in acc["ops"]:
                acc["ops"].add(op[0])
                acc["fast"][d["fast"]] += 1
                acc["types"].update(d["types"] - {work[key][0]})
            if root is None or root[0] != work[key][0]:
                kind = root[0] if root else "(unscoped)"
                acc["roots"][kind] = acc["roots"].get(kind, 0.0) + own * share
    return {"parts": parts, "foreign": foreign, "no_product": bare,
            "operations": operations}


def floor_seconds(flops, nbytes, shards, peak):
    """The least time one chip's share of a part could take: the larger of
    its flops over the bf16 peak and its least bytes over the HBM peak (the
    bytes divided like the flops: the weights' are not split, so this is a
    lower bound and the share of the roofline is never read too high)."""
    return max(flops / shards / peak["bf16_flops"],
               nbytes / shards / peak["hbm_bytes_per_s"])


def summarize(found, work, shards, steps, peak):
    """The four metrics' numbers and the table's rows from ``attribute``'s
    result: seconds a step by part, the found parts' floor, rows by
    (shape, part)."""
    part_s = dict.fromkeys(PARTS, 0.0)
    rows, floor_s = {}, 0.0
    for key, acc in found["parts"].items():
        op_type, flops, nbytes, shape = work[key]
        part_s[key[1]] += acc["s"] / steps
        if peak:
            floor_s += floor_seconds(flops, nbytes, shards, peak)
        row = rows.setdefault((shape, key[1]), {
            "n": 0, "s": 0.0, "flops": 0, "bytes": 0, "xla_flops": 0.0,
            "xla_bytes": 0.0, "types": collections.Counter(),
            "fast": collections.Counter(), "roots": {}, "op_types": set()})
        row["n"] += 1
        row["s"] += acc["s"] / steps
        row["flops"] += flops / shards
        row["bytes"] += nbytes / shards
        row["xla_flops"] += acc["xla_flops"] / steps
        row["xla_bytes"] += acc["xla_bytes"] / steps
        row["types"].update(acc["types"])
        row["fast"].update(acc["fast"])
        row["op_types"].add(op_type)
        for kind, s in acc["roots"].items():
            row["roots"][kind] = row["roots"].get(kind, 0.0) + s / steps
    time_s = sum(part_s.values())
    return {"steps": steps, "part_s": part_s, "time_s": time_s,
            "floor_s": floor_s if peak else None, "rows": rows,
            "unfound": sorted(k for k in work if k not in found["parts"]),
            "foreign": {k: s / steps for k, s in found["foreign"].items()},
            "no_product": {k: s / steps
                           for k, s in found["no_product"].items()}}


# ---------------------------------------------------------------------------
# one traced run
# ---------------------------------------------------------------------------

def reading(facts):
    """``summarize``'s result for the traced run, or None when there is
    nothing to read."""
    got = noted(records(facts))
    if got is None:
        return None
    path = scopes.find_newest()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _READ:
        _READ.clear()
        try:
            _READ[key] = _read(path, facts["traced_steps"], *got)
        except Exception as e:     # noqa: BLE001 — a reader never ends a run
            _log("cannot read %s: %s: %s" % (path, type(e).__name__, e))
            _READ[key] = None
    return _READ[key]


def _read(path, steps, work, shards):
    t0 = time.perf_counter()
    loaded = hlo.load(path)
    programs = hlo.Programs(loaded)
    chips = sorted(i for i, d in loaded["devices"].items() if d["ops"])
    if not programs or not chips:
        _log("%s holds no Hlo Proto (or no device operation): nothing to "
             "join the %d noted parts with" % (os.path.basename(path),
                                               len(work)))
        return None
    t1 = time.perf_counter()
    found = attribute(loaded["devices"][chips[0]]["ops"], programs, work,
                      scopes.load_groups())
    if not found["parts"]:
        _log("none of the %d noted parts is in the trace's operations"
             % len(work))
        return None
    peak = device_peaks(loaded["peaks"])
    out = summarize(found, work, shards, steps, peak)
    t2 = time.perf_counter()
    _log("%s: %d noted parts (batch axis over %d), %d traced steps, first "
         "chip %d; read in %.2f s (the file %.2f s; %d programs' Hlo Proto "
         "to %.1f MB of text, parsed and joined %.2f s)"
         % (os.path.relpath(path, scopes.ROOT), len(work), shards, steps,
            chips[0], t2 - t0, t1 - t0, len(programs.names),
            programs.text_bytes / 1e6, t2 - t1))
    _report(out, found, work, shards, peak)
    return out


def _rate(amount, seconds, unit):
    return amount / seconds / unit if seconds else 0.0


def _counts(counter, most=4):
    return ", ".join("%s x%d" % kv for kv in counter.most_common(most)) \
        or "-"


def _ms(by_kind):
    return ", ".join("%s %.3f" % (k, s * 1e3) for k, s in sorted(
        by_kind.items(), key=lambda kv: -kv[1])) or "-"


def _report(out, found, work, shards, peak):
    steps = out["steps"]
    _log("device ms a step by part: " + ", ".join(
        "%s %.3f" % (p, out["part_s"][p] * 1e3) for p in PARTS)
        + "; sum %.3f" % (out["time_s"] * 1e3)
        + ("; floor %.3f ms = %.2f%% (dense_product_roofline)" % (
            out["floor_s"] * 1e3, 100.0 * out["floor_s"] / out["time_s"])
           if peak else "; no peaks for this device: no floor"))
    _log("against device_ms_per_step.%s: products counted under a root of "
         "another group [+ms]: %s; operations of the group that hold no "
         "noted product [-ms]: %s" % (GROUP, _ms(out["foreign"]),
                                      _ms(out["no_product"])))
    if out["unfound"]:
        _log("noted but in no operation (%d; not in the floor): %s" % (
            len(out["unfound"]), ", ".join(
                "%s/%s" % k for k in out["unfound"][:8])))
    _log("by product shape (M x K x N of the part's own product; required "
         "TFLOP/s and GB/s over its device time, XLA's flops and "
         "bytes_accessed for the same operations beside them):")
    for (shape, part), r in sorted(out["rows"].items(),
                                   key=lambda kv: -kv[1]["s"]):
        _log("  %-22s %-3s %-12s %3d ops/step %8.3f ms %7.2f TFLOP/s "
             "%7.1f GB/s | XLA %7.2f TFLOP/s %7.1f GB/s | fused in: %s | "
             "in fast memory: %s | counted under: %s" % (
                 " x ".join(map(str, shape)) if shape else "?", part,
                 "+".join(sorted(r["op_types"])), r["n"], r["s"] * 1e3,
                 _rate(r["flops"], r["s"], 1e12),
                 _rate(r["bytes"], r["s"], 1e9),
                 _rate(r["xla_flops"], r["s"], 1e12),
                 _rate(r["xla_bytes"], r["s"], 1e9), _counts(r["types"], 6),
                 _counts(r["fast"], 2), _ms(r["roots"])))
    _log("longest operations that hold a product:")
    rows = sorted(found["operations"].items(), key=lambda kv: -kv[1]["s"])
    for name, d in rows[:16]:
        held = ["%s %s" % (" x ".join(map(str, work[k][3] or ("?",))), k[1])
                for k in d["keys"]]
        flops = sum(work[k][1] for k in d["keys"]) / shards
        own = {work[k][0] for k in d["keys"]}
        _log("  %-40s %8.3f ms %7.2f TFLOP/s | %s | root %s | fused in: %s "
             "| in fast memory: %s" % (
                 name, d["s"] / steps * 1e3,
                 _rate(flops, d["s"] / steps, 1e12), "; ".join(held),
                 "%s/%s" % d["root"] if d["root"] else "(unscoped)",
                 ", ".join(sorted(d["types"] - own)) or "-", d["fast"]))


def part_ms_per_step(facts, part):
    got = reading(facts)
    return None if got is None else got["part_s"][part] * 1e3


def roofline(facts):
    got = reading(facts)
    if got is None or got["floor_s"] is None or not got["time_s"]:
        return None
    return 100.0 * got["floor_s"] / got["time_s"]
