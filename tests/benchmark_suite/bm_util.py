"""Shared by the benchmark's CPU tests: the repo root, a quiet run of one
cell's ``--check`` pass (tiny sizes, counts only), and the serve cell that
BENCHMARK.json does not hold yet."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELLS = ("transformer_base.train_nmt", "transformer_base.train_nmt_dp4")
SERVE_CELL = "decoder_base.serve_steady"
GROUPS = ("configs", "workloads", "end_to_end", "per_layer")


def with_serve_cell(bench):
    """``bench`` with the serve cell's entries added, as a later PR would
    add them (data/serve_steady_entries.json): the serve generator, its
    builder, reference, mix and readers stay under ``benchmark/`` and stay
    tested, though no cell of BENCHMARK.json uses them (PERF.md, Open
    questions)."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "serve_steady_entries.json")) as f:
        more = json.load(f)
    return dict(bench, **{g: bench[g] + more[g] for g in GROUPS})


def root_with_serve_cell(dest):
    """A copy of the benchmark under ``dest`` whose BENCHMARK.json also
    holds the serve cell; returns ``dest``."""
    dest = str(dest)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = with_serve_cell(json.load(f))
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


def check_cell(workload, seed=3, root=ROOT):
    from benchmark import harness

    return harness.run_cell(workload, seed, 1.0, 0, check=True, root=root)
