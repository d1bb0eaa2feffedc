"""Reduction from a profiler trace (``.xplane.pb``) to device metrics.

Two halves.  :func:`load` turns ``jax.profiler.ProfileData`` into plain
lists of ``(name, start_ns, dur_ns)`` — what a TPU v5e trace looks like
(looked at by hand, PR 23): one plane ``/device:TPU:<i>`` per chip with the
lines ``XLA Modules`` (one event per executed program, ``jit_<fn>(<hash>)``),
``XLA Ops`` (one event per HLO op, named by its full HLO text ``%name =
...``) and ``Async XLA Ops`` (start..done spans of asynchronous copies and
collectives); the plane ``/host:CPU`` has one line per host thread, and
``jax.profiler.TraceAnnotation`` spans land on the annotating thread's line
under their own name, next to the Python tracer's ``$file:line fn`` frames.
Device and host timestamps share one base to about a millisecond.  The
second half is interval arithmetic on those lists and knows nothing of the
profiler, so it is tested against hand-computed values.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)")
_OP_NAME = re.compile(r"^%?([^\s=]+)")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def op_name(hlo_text):
    """``%fusion.7 = bf16[...] fusion(...)`` -> ``fusion.7``."""
    m = _OP_NAME.match(hlo_text.strip())
    return m.group(1) if m else hlo_text


def load(path):
    """{"devices": {ordinal: {"modules": [...], "ops": [...], "async":
    [...]}}, "host": [(name, start_ns, dur_ns), ...]} from an xplane file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops",
                       "Async XLA Ops": "async"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = ev.name if key == "modules" else op_name(ev.name)
                    dev[key].append((name, float(ev.start_ns),
                                     float(ev.duration_ns)))
            out["devices"][int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    out["host"].append((ev.name, float(ev.start_ns),
                                        float(ev.duration_ns)))
    return out


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals):
    """Merge [(start, end), ...] into disjoint sorted intervals."""
    merged = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] given disjoint sorted ``busy``."""
    return subtract([(lo, hi)], busy)


def spans(events):
    return [(s, s + d) for _, s, d in events]


def device_window(dev):
    """[first module start, last module end] on one device."""
    ev = dev["modules"] or dev["ops"]
    if not ev:
        return None
    return min(s for _, s, _ in ev), max(s + d for _, s, d in ev)


def busy_intervals(dev):
    """Union of the intervals in which an operation ran on the device."""
    return union(spans(dev["ops"] or dev["modules"]))


def by_operation(dev, top=10):
    """[(op name, seconds)] of the ops that took most device time."""
    acc = {}
    for name, _, d in dev["ops"]:
        acc[name] = acc.get(name, 0.0) + d
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [(n, d / 1e9) for n, d in rows]


def by_module(dev):
    """{module name: (count, busy seconds)} per executed program."""
    acc = {}
    for name, _, d in dev["modules"]:
        c, t = acc.get(name, (0, 0.0))
        acc[name] = (c + 1, t + d / 1e9)
    return acc


def collective_times(dev):
    """(seconds of collective operations, seconds of them during which no
    compute op ran on that device).  Collectives are the async start..done
    spans plus synchronous collective ops; compute is every other op."""
    coll = [(s, s + d) for n, s, d in dev["async"] if COLLECTIVE.match(n)]
    coll += [(s, s + d) for n, s, d in dev["ops"]
             if COLLECTIVE.match(n) and not n.split(".")[0].endswith("-start")]
    compute = union([(s, s + d) for n, s, d in dev["ops"]
                     if not COLLECTIVE.match(n)])
    coll = union(coll)
    return total(coll) / 1e9, total(subtract(coll, compute)) / 1e9


def attribute_gaps(idle, host_events, top=10, prefix="bm/"):
    """[(what the host was doing, seconds)] for the longest idle gaps.
    A gap goes to the benchmark's own span (``prefix``) that overlaps it
    most; failing that to the shortest host event covering at least half
    of it (the most specific frame); failing that to "(no host span)"."""
    acc = {}
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:200]:
        best, best_key = "(no host span)", None
        for name, hs, hd in host_events:
            ov = min(e, hs + hd) - max(s, hs)
            if ov <= 0:
                continue
            own = name.startswith(prefix)
            if not own and ov < 0.5 * (e - s):
                continue
            key = (own, ov if own else -hd)
            if best_key is None or key > best_key:
                best, best_key = name, key
        acc[best] = acc.get(best, 0.0) + (e - s)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [(n, d / 1e9) for n, d in rows]


def summarize(trace, n_devices=None):
    """The numbers every traced run reports: busy seconds averaged over
    the devices used, the traced window, the breakdown, per-device
    collective time."""
    devs = sorted(trace["devices"].items())
    devs = [(i, d) for i, d in devs if d["ops"] or d["modules"]]
    if n_devices is not None:
        devs = devs[:n_devices]
    if not devs:
        raise ValueError("no device operation in the trace")
    los, his = zip(*(device_window(d) for _, d in devs))
    lo, hi = min(los), max(his)
    busy = [busy_intervals(d) for _, d in devs]
    busy_s = sum(total(b) for b in busy) / len(busy) / 1e9
    first = devs[0][1]
    coll = [collective_times(d) for _, d in devs]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "devices": len(devs),
        "device_ops": by_operation(first),
        "modules": by_module(first),
        "idle_gaps": attribute_gaps(gaps(busy[0], lo, hi), trace["host"]),
        "collective_s": sum(c for c, _ in coll) / len(coll),
        "exposed_collective_s": sum(x for _, x in coll) / len(coll),
    }
