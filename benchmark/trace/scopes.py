"""Fluid names from a profiler trace: which Fluid op each device operation
came from, and which of the executors' spans the host was in.

``reduce.py`` reads a trace through ``jax.profiler.ProfileData``, which
yields an event's own stats only.  What ties a device operation to the
program sits one level up, in the **event metadata** of the device plane's
``XLA Ops`` line (looked at by hand on a v5e trace, PR 24): per HLO op the
stats ``tf_op`` (the jax name stack the op was traced under, e.g.
``jit(pt_exe_1a2b3c4d)/fluid[mul]enc0_ffn_fc1.tmp_0/dot_general:``),
``hlo_category``, ``flops``, ``bytes_accessed``, ``source``,
``program_id``.  So this module reads the ``.xplane.pb`` as a raw
``XSpace`` with a descriptor built here from ``google.protobuf`` (the six
message types of tsl's ``xplane.proto``; no TensorFlow, no xprof).

The naming scheme is the program's (``paddle_tpu/registry.py``,
``fluid_scope_name``): every lowered Fluid op runs under
``jax.named_scope("fluid[<op type>]<first output variable>")`` (of the
variable's name only letters, digits, ``_``, ``.`` and ``-`` are kept, ``.``
stands for anything else: ``x@GRAD`` reads ``x.GRAD``); region ops nest, and
the innermost scope owns the operation.  A fusion has the
``tf_op`` of its root instruction, so a fusion belongs to the Fluid op of
its root — as xprof attributes it too.  The executors' host spans are
``jax.profiler.TraceAnnotation("pt/<span>")`` (``paddle_tpu/profiler.py``),
beside the benchmark's own ``bm/`` spans on the calling thread's line.

The second half is arithmetic on plain tuples and knows nothing of the
profiler.
"""

import functools
import glob
import json
import os
import re
import statistics

from benchmark.trace import reduce as trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the one regex that matches the program's marker; the LAST match in a
# name stack is the innermost scope (a ``tf_op`` is ``<name stack>:<type>``,
# so an operation that IS the scope's own ends in ``]<output>:``)
FLUID = re.compile(r"fluid\[([^\]/]+)\]([^/:]*)")
_NUMBER = re.compile(r"\.\d+$")
HOST_PREFIXES = ("pt/", "bm/")
STEP_SPAN = "bm/train_step"


# ---------------------------------------------------------------------------
# the raw XSpace
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _xspace_class():
    """The ``XSpace`` message class, from a descriptor built in code
    (field numbers as in tsl/profiler/protobuf/xplane.proto)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_trace_xplane.proto", package="bm_xplane",
        syntax="proto3")

    def message(name, fields, parent=None, oneof=None):
        """``oneof``: (name, the field names in it) — xplane.proto keeps a
        stat's value and an event's offset in one."""
        m = (parent.nested_type if parent is not None
             else fd.message_type).add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof[0])
        for fname, number, ftype, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if type_name:
                f.type_name = ".bm_xplane." + type_name
            if oneof and fname in oneof[1]:
                f.oneof_index = 0
        return m

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, dbl, st, by, msg = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE,
                                  F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE)
    message("XStat", [
        ("metadata_id", 1, i64, one, None),
        ("double_value", 2, dbl, one, None),
        ("uint64_value", 3, u64, one, None),
        ("int64_value", 4, i64, one, None),
        ("str_value", 5, st, one, None), ("bytes_value", 6, by, one, None),
        ("ref_value", 7, u64, one, None)],
        oneof=("value", ("double_value", "uint64_value", "int64_value",
                         "str_value", "bytes_value", "ref_value")))
    message("XEvent", [
        ("metadata_id", 1, i64, one, None), ("offset_ps", 2, i64, one, None),
        ("duration_ps", 3, i64, one, None), ("stats", 4, msg, many, "XStat"),
        ("num_occurrences", 5, i64, one, None)],
        oneof=("data", ("offset_ps", "num_occurrences")))
    message("XLine", [
        ("id", 1, i64, one, None), ("name", 2, st, one, None),
        ("timestamp_ns", 3, i64, one, None),
        ("events", 4, msg, many, "XEvent"),
        ("duration_ps", 9, i64, one, None),
        ("display_id", 10, i64, one, None),
        ("display_name", 11, st, one, None)])
    message("XEventMetadata", [
        ("id", 1, i64, one, None), ("name", 2, st, one, None),
        ("metadata", 3, by, one, None), ("display_name", 4, st, one, None),
        ("stats", 5, msg, many, "XStat"), ("child_id", 6, i64, many, None)])
    message("XStatMetadata", [
        ("id", 1, i64, one, None), ("name", 2, st, one, None),
        ("description", 3, st, one, None)])
    plane = message("XPlane", [
        ("id", 1, i64, one, None), ("name", 2, st, one, None),
        ("lines", 3, msg, many, "XLine"),
        ("event_metadata", 4, msg, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, msg, many, "XPlane.StatMetadataEntry"),
        ("stats", 6, msg, many, "XStat")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(entry, [("key", 1, i64, one, None),
                            ("value", 2, msg, one, value)], parent=plane)
        e.options.map_entry = True
    message("XSpace", [
        ("planes", 1, msg, many, "XPlane"), ("errors", 2, st, many, None),
        ("warnings", 3, st, many, None), ("hostnames", 4, st, many, None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bm_xplane.XSpace"))


def _stat_value(stat, stat_names):
    """A stat's value; a ``ref_value`` points at the stat metadata whose
    name IS the string."""
    which = stat.WhichOneof("value")
    if which == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, which) if which else None


def find_newest():
    """The newest profile under ``harness.trace_dir``'s fixed layout,
    ``.benchmark_out/trace/<cell>/plugins/profile/<time>/*.xplane.pb``
    (a traced run empties its cell's directory first), or None."""
    found = glob.glob(os.path.join(ROOT, ".benchmark_out", "trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path):
    """{"devices": {ordinal: {"ops": [(name, start_ns, dur_ns, tf_op,
    hlo_category, flops, bytes_accessed), ...], "modules": [(name,
    start_ns, dur_ns), ...]}}, "host": {thread line: [(name, start_ns,
    dur_ns), ...]}} — device operations with what their event metadata
    says of them, and the host's ``pt/`` and ``bm/`` spans by thread."""
    from google.protobuf.message import DecodeError

    space = _xspace_class()()
    with open(path, "rb") as f:
        try:
            space.ParseFromString(f.read())
        except DecodeError as e:
            raise ValueError("%s is no XSpace: %s" % (path, e))
    out = {"devices": {}, "host": {}}
    for plane in space.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            out["devices"][int(m.group(1))] = _device_plane(plane)
        elif plane.name == "/host:CPU":
            # a traced run's Python tracer leaves millions of events
            # here: pick the few spans by metadata id before touching them
            spans = {k: md.name for k, md in plane.event_metadata.items()
                     if md.name.startswith(HOST_PREFIXES)}
            for line in plane.lines:
                events = [
                    (spans[ev.metadata_id],
                     line.timestamp_ns + ev.offset_ps / 1e3,
                     ev.duration_ps / 1e3)
                    for ev in line.events if ev.metadata_id in spans]
                if events:
                    out["host"]["%s#%d" % (line.name, line.id)] = events
    return out


def _device_plane(plane):
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    meta = {}

    def describe(metadata_id):
        if metadata_id not in meta:
            md = plane.event_metadata[metadata_id]
            stats = {stat_names.get(s.metadata_id): _stat_value(s, stat_names)
                     for s in md.stats}
            meta[metadata_id] = (
                md.name, str(stats.get("tf_op") or ""),
                str(stats.get("hlo_category") or ""),
                int(stats.get("flops") or 0),
                int(stats.get("bytes_accessed") or 0))
        return meta[metadata_id]

    dev = {"ops": [], "modules": []}
    for line in plane.lines:
        if line.name == "XLA Ops":
            for ev in line.events:
                name, tf_op, cat, flops, nbytes = describe(ev.metadata_id)
                dev["ops"].append((
                    trace_reduce.op_name(name),
                    line.timestamp_ns + ev.offset_ps / 1e3,
                    ev.duration_ps / 1e3, tf_op, cat, flops, nbytes))
        elif line.name == "XLA Modules":
            for ev in line.events:
                dev["modules"].append((
                    plane.event_metadata[ev.metadata_id].name,
                    line.timestamp_ns + ev.offset_ps / 1e3,
                    ev.duration_ps / 1e3))
    return dev


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

def fluid_scope(tf_op):
    """(Fluid op type, first output variable) of the innermost Fluid scope
    in a name stack, or None when it is under none."""
    found = FLUID.findall(tf_op or "")
    return found[-1] if found else None


def under(tf_op, scope):
    """Whether ``scope`` is one of the ``/``-separated entries of the name
    stack (any ``jax.named_scope``, not only a Fluid one)."""
    return scope in (tf_op or "").rstrip(":").split("/")


def load_groups():
    with open(os.path.join(HERE, "fluid_groups.json")) as f:
        rules = json.load(f)
    for rule in rules["by_output"]:
        rule["_re"] = re.compile(rule["pattern"])
    return rules


def group_of(op_type, output, rules):
    """(group, whether the file names the type) by fluid_groups.json's
    ``how``."""
    base = op_type[:-5] if op_type.endswith("_grad") else op_type
    for rule in rules["by_output"]:
        if rule["_re"].search(output) and (
                "types" not in rule or base in rule["types"]):
            return rule["group"], True
    by_type = rules["by_type"]
    if op_type in by_type:
        return by_type[op_type], True
    if base in by_type:
        return by_type[base], True
    return rules["default"], False


# ---------------------------------------------------------------------------
# arithmetic on plain tuples
# ---------------------------------------------------------------------------

def self_times(ops):
    """Per event of one device line, its duration less that of the events
    nested inside it (a ``while`` or ``call`` spans its body's events), in
    the order given — so the self times add up to the line's busy time."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [op[2] for op in ops]
    stack = []                              # (index, end)
    for i in order:
        start, dur = ops[i][1], ops[i][2]
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack and start + dur <= stack[-1][1]:
            own[stack[-1][0]] -= dur
        stack.append((i, start + dur))
    return [max(t, 0.0) for t in own]


def device_table(ops, rules):
    """One chip's operations by Fluid name.  Seconds throughout.

    ``groups`` (every group of the file, 0.0 where nothing ran),
    ``unscoped_s`` (compute operations under no Fluid scope),
    ``collective_s`` (collective operations on the ops line, in no group)
    and their sum ``busy_s``; ``by_type`` {Fluid type: {group, s, count,
    flops, bytes}}; ``top`` the twenty largest [("<type>/<output> <hlo
    op>", s)]; ``unscoped_top`` [(hlo op without its number, s)];
    ``by_category`` {XLA's hlo_category: s} over the compute operations;
    ``unnamed_types``
    the Fluid types the file does not name (they went to its default
    group)."""
    groups = dict.fromkeys(rules["groups"], 0.0)
    by_type, by_op, by_category, unscoped, unnamed = {}, {}, {}, {}, set()
    unscoped_s = collective_s = 0.0
    for op, own in zip(ops, self_times(ops)):
        name, _, _, tf_op, category, flops, nbytes = op
        own /= 1e9
        if trace_reduce.COLLECTIVE.match(name):
            collective_s += own
            continue
        by_category[category] = by_category.get(category, 0.0) + own
        scope = fluid_scope(tf_op)
        if scope is None:
            unscoped_s += own
            family = _NUMBER.sub("", name)
            unscoped[family] = unscoped.get(family, 0.0) + own
            continue
        group, named = group_of(scope[0], scope[1], rules)
        if not named:
            unnamed.add(scope[0])
        groups[group] += own
        row = by_type.setdefault(scope[0], {
            "group": group, "s": 0.0, "count": 0, "flops": 0, "bytes": 0})
        # (a type whose ops fall in two groups, as scale does, keeps the
        # group of the first one seen; the group sums above are exact)
        row["s"] += own
        row["count"] += 1
        row["flops"] += flops
        row["bytes"] += nbytes
        key = "%s/%s %s" % (scope[0], scope[1], name)
        by_op[key] = by_op.get(key, 0.0) + own

    def largest(acc):
        return sorted(acc.items(), key=lambda kv: -kv[1])[:20]
    return {"groups": groups, "unscoped_s": unscoped_s,
            "collective_s": collective_s,
            "busy_s": sum(groups.values()) + unscoped_s + collective_s,
            "by_type": by_type, "top": largest(by_op),
            "unscoped_top": largest(unscoped),
            "by_category": dict(largest(by_category)),
            "unnamed_types": sorted(unnamed)}


def host_steps(host):
    """[(span_s, wait_s)] per ``bm/train_step`` on the thread that has
    them: the span's length, and the part of it spent inside
    ``pt/*/fetch_sync`` spans on the same thread (the host blocked on the
    dispatch window).
    None when the trace holds no ``pt/`` span at all: a program without
    the annotations cannot be told from one that never waits."""
    if not any(n.startswith("pt/") for ev in host.values() for n, _, _ in ev):
        return None
    out = []
    for events in host.values():
        waits = [(s, s + d) for n, s, d in events
                 if n.startswith("pt/") and n.endswith("/fetch_sync")]
        for n, s, d in events:
            if n != STEP_SPAN:
                continue
            inside = trace_reduce.union(
                [(max(a, s), min(b, s + d)) for a, b in waits])
            out.append((d / 1e9, trace_reduce.total(inside) / 1e9))
    return out


def host_medians(steps):
    """(median self seconds, median wait seconds) over ``host_steps``."""
    if not steps:
        return None
    return (statistics.median(span - wait for span, wait in steps),
            statistics.median(wait for _, wait in steps))
