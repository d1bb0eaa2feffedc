"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
refuses to time anything without the chip, runs the cell's generator and
its per-layer readers, and prints the one result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own (see benchmark/README.md);
nothing here names a cell."""

import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class Refused(SystemExit):
    """The run cannot be made here: message on stderr, exit code 2, no
    result line."""

    def __init__(self, msg):
        print("benchmark: " + msg, file=sys.stderr, flush=True)
        super().__init__(2)


# ---------------------------------------------------------------------------
# files by name
# ---------------------------------------------------------------------------

def load_benchmark(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name, root=ROOT):
    """``benchmark/<kind>/<name>.py`` by file name (metric names may hold
    dots, so this does not go through the import system's dotted names)."""
    if not NAME.match(name):
        raise ValueError("bad %s name %r" % (kind, name))
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError("no %s file %s" % (kind, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark.%s.%s" % (kind, name.replace(".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name, root=ROOT):
    return load_module("reference", name, root)


def load_reader(metric, root=ROOT):
    """A per-layer metric's reader: ``benchmark/metrics/<metric>.py``, or,
    for a quantity split over cells that report different end-to-end
    metrics (``peak_hbm_gb.train``, ``peak_hbm_gb.serve``), the one file of
    the name before the last dot."""
    try:
        return load_module("metrics", metric, root)
    except FileNotFoundError:
        if "." not in metric:
            raise
        return load_module("metrics", metric.rsplit(".", 1)[0], root)


def with_tiny(data, tiny):
    """``data`` with its ``tiny`` group laid over it when ``tiny`` (the
    CPU ``--check`` sizes), without it otherwise."""
    out = {k: v for k, v in data.items() if k != "tiny"}
    if tiny:
        for k, v in data.get("tiny", {}).items():
            out[k] = v
    return out


def resolve_cell(bench, workload, tiny=False, root=ROOT):
    """(cell entry, configuration, traffic mix) for a workload name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused("no workload %r in BENCHMARK.json (have %s)"
                      % (workload, sorted(cells)))
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = with_tiny(_json(os.path.join(root, conf["file"])), tiny)
    traffic = with_tiny(_json(os.path.join(
        root, "benchmark", "traffic", cell["traffic"] + ".json")), tiny)
    return cell, cfg, traffic


def metrics_for(bench, group, workload, reported):
    """The metrics of ``group`` this cell reports: those that list it
    under ``workloads``, or list nothing and move something it reports."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_chips(chips):
    """The cell's devices, or no run at all: a TPU in the peaks table,
    at least ``chips`` of them.  Never falls back to the CPU."""
    import jax

    from benchmark import peaks

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused("JAX found no backend: %s" % e)
    if devs[0].platform != "tpu":
        raise Refused("no accelerator: jax.devices() = %s. The timed path "
                      "never runs on the CPU; use --check for the CPU "
                      "correctness pass." % (devs,))
    if len(devs) < chips:
        raise Refused("the cell needs %d chips, JAX sees %d"
                      % (chips, len(devs)))
    return devs, peaks.peaks_for(devs[0].device_kind)


def device_block(devices, chips):
    d = devices[0]
    return {"platform": str(d.platform), "kind": str(d.device_kind),
            "count": int(chips)}


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest chip.  The runtime's
    ``peak_bytes_in_use`` counts arrays only; what a compiled program needs
    for its temporaries is under ``bytes_reserved`` and stays reserved
    after the program ran (my chip run, PR 23: a program with an 8.59 GB
    temporary read 0.68 GB in use and 8.59 GB reserved).  So the peak is
    the larger of the arrays' peak and what the chip holds now, arrays and
    reservation together — call this after the window, with the program's
    state still alive."""
    best = 0
    for d in devices:
        s = d.memory_stats() or {}
        held = int(s.get("bytes_in_use", 0)) + int(s.get("bytes_reserved", 0))
        best = max(best, held, int(s.get("peak_bytes_in_use", 0)))
    return best


# ---------------------------------------------------------------------------
# the program's counters
# ---------------------------------------------------------------------------

def count_compiles():
    from paddle_tpu import compile_cache

    return compile_cache.count_compiles()


def n_compiles(delta):
    """Anything lowered or compiled: trace-cache lowerings, jax lowerings,
    backend compiles."""
    return int(delta["lowerings"] + delta["jax_lowerings"]
               + delta["jax_backend_compiles"])


def enable_cache():
    """The persistent compile cache where the program's one resolver puts
    it for a chip entry point: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed ``.jax_compile_cache/`` inside the checkout."""
    from paddle_tpu import compile_cache

    return compile_cache.enable_persistent_cache(chip_entry=True)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Checks:
    """Every number compared, printed beside its limit; a value over its
    limit (or not finite) makes the run not correct."""

    def __init__(self, log):
        self.log, self.rows = log, []

    def add(self, name, value, limit, note=""):
        if limit is None:
            raise Refused("no limit recorded for %s" % name)
        passed = math.isfinite(value) and value <= limit
        self.rows.append((name, value, limit, passed))
        self.log("check %-28s value %.6g limit %.6g %s%s"
                 % (name, value, limit, "ok" if passed else "NOT CORRECT",
                    (" (" + note + ")") if note else ""))

    def ok(self):
        return bool(self.rows) and all(r[3] for r in self.rows)


def trace_dir(ctx):
    """A fixed, gitignored directory inside the checkout for the traced
    run's profile; emptied first."""
    d = os.path.join(ctx.root, ".benchmark_out", "trace",
                     ctx.workload.replace("/", "_"))
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def log_line(device):
    tag = "[benchmark %s x%d %s]" % (device["kind"], device["count"],
                                     device["platform"])

    def log(msg):
        print(tag + " " + msg, flush=True)
    return log


def open_cell(workload, seed, seconds, trace, check=False, root=ROOT,
              t_start=None, devices=None):
    """What a generator gets (``ctx``) for one cell: its files resolved,
    its devices found — or the run refused.  ``check`` is the CPU pass at
    tiny sizes; ``devices`` is for the tests, which skip the look for a
    chip."""
    bench = load_benchmark(root)
    cell, cfg, traffic = resolve_cell(bench, workload, tiny=check, root=root)
    chips = int(cell["chips"])
    peaks = None
    if devices is None:
        if check:
            import jax
            devices = jax.devices()
            if len(devices) < chips:
                raise Refused("--check of a %d-chip cell needs %d devices "
                              "(XLA_FLAGS=--xla_force_host_platform_device_"
                              "count=%d)" % (chips, chips, chips))
        else:
            devices, peaks = require_chips(chips)
            enable_cache()
    device = device_block(devices, chips)
    log = log_line(device)
    log("cell %s seed %d seconds %s trace %d%s"
        % (workload, seed, seconds, trace, " CHECK (tiny sizes, no time "
           "is measured)" if check else ""))
    return types.SimpleNamespace(
        bench=bench, workload=workload, cell=cell, cfg=cfg, traffic=traffic,
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        check=bool(check), devices=devices, chips=chips, peaks=peaks,
        device=device, root=root, log=log,
        t_start=time.perf_counter() if t_start is None else t_start)


def run_cell(workload, seed, seconds, trace, check=False, root=ROOT,
             t_start=None, devices=None):
    """Run one cell once; returns the result dict (the last stdout line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    ctx = open_cell(workload, seed, seconds, trace, check, root, t_start,
                    devices)
    bench, traffic, device, log = ctx.bench, ctx.traffic, ctx.device, ctx.log
    gen = load_module("generators", traffic["generator"], root)
    out = gen.run(ctx)

    metrics = {}
    if not check:
        # the contract keeps the plain reference's time out of setup_s
        # (every run pays it all the same: it is logged here)
        wall_s = out["window_start"] - t_start
        setup_s = wall_s - out["reference_s"]
        out["end_to_end"]["setup_s"] = setup_s
        log("process start to window start %.3f s by the wall clock: "
            "setup_s %.3f s + the plain reference's %.3f s, which setup_s "
            "does not count" % (wall_s, setup_s, out["reference_s"]))
    reported = set(out["end_to_end"])
    if trace or check:
        facts = out["facts"]
        for m in metrics_for(bench, "per_layer", workload,
                             reported or {m["name"] for m in
                                          bench["end_to_end"]}):
            if check and m["unit"] != "count":
                continue        # a CPU run yields counts, never a time
            value = load_reader(m["name"], root).read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_for(bench, "end_to_end", workload, reported):
            if m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = int(out.get("memory_peak_bytes", 0))
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    summary = out["facts"].get("trace") if trace else None
    if summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary["device_ops"]],
            "idle_gaps": [[n, s] for n, s in summary["idle_gaps"]]}
    return result
