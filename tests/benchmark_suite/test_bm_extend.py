"""A configuration, a cell, a traffic kind and a per-layer metric can each
be added as new files plus entries, with no edit to a file that is there:
done here in a temporary copy, then ``--check`` runs the new cell."""

import json
import os

from bm_util import SERVE_CELL, check_cell, root_with_serve_cell


def test_new_files_and_entries_make_a_new_cell(tmp_path):
    root = root_with_serve_cell(tmp_path)
    before = _listing(root)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    b = os.path.join(root, "benchmark")

    # a configuration: its file of sizes (the reference is beside it)
    conf = json.load(open(os.path.join(b, "configs", "decoder_base.json")))
    conf.update(name="decoder_two_layer", n_layer=2)
    conf["tiny"]["n_layer"] = 1
    _write(b, "configs/decoder_two_layer.json", json.dumps(conf))
    bench["configs"].append({
        "name": "decoder_two_layer", "source": conf["source"],
        "file": "benchmark/configs/decoder_two_layer.json",
        "reduced": ["n_layer"], "why": "test"})
    # a traffic kind: one generator module, and a mix that names it
    _write(b, "generators/serve_trickle.py",
           "from benchmark.generators import serve_open_loop\n\n\n"
           "def run(ctx):\n"
           "    out = serve_open_loop.run(ctx)\n"
           "    out['facts']['trickle'] = len(ctx.traffic['buckets'])\n"
           "    return out\n")
    mix = json.load(open(os.path.join(b, "traffic", "serve_steady.json")))
    mix.update(generator="serve_trickle")
    mix["tiny"]["rate_per_s"] = 12
    _write(b, "traffic/serve_trickle.json", json.dumps(mix))
    # a cell
    name = "decoder_two_layer.serve_trickle"
    bench["workloads"].append({"name": name, "config": "decoder_two_layer",
                               "traffic": "serve_trickle", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if SERVE_CELL in m.get("workloads", []):
            m["workloads"].append(name)
    # a per-layer metric: a small reader of its own
    _write(b, "metrics/prefill_buckets.py",
           "def read(facts):\n    return facts.get('trickle')\n")
    bench["per_layer"].append({
        "name": "prefill_buckets", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "serving engine",
        "moves": "request_latency_p95_ms", "workloads": [name]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    result = check_cell(name, root=root)
    assert result["correct"] is True and result["attempted"] == 12
    assert result["metrics"]["prefill_buckets"] == {"value": 3,
                                                    "unit": "count"}
    # nothing that was there was edited
    after = _listing(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 4


def _write(base, rel, text):
    with open(os.path.join(base, rel), "w") as f:
        f.write(text)


def _listing(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out
