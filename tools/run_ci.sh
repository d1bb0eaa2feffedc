#!/usr/bin/env bash
# CI driver (the reference's paddle/scripts/paddle_build.sh role):
# full test suite, API-signature gate, multi-device dryrun, and a bench
# smoke — everything the round driver checks, runnable locally.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== 1/20 test suite (tier-1 gate: -m 'not slow'; run the slow set =="
echo "==     explicitly with: python -m pytest tests/ -m slow)        =="
python -m pytest tests/ -q -m 'not slow'

echo "== 2/20 API signature gate =="
python tools/print_signatures.py > /tmp/api_live.txt
python tools/diff_api.py tools/api_signatures.txt /tmp/api_live.txt

echo "== 3/20 8-device virtual-mesh dryrun =="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== 4/20 bench smoke (CPU backend, tiny) =="
python bench.py --model mlp --device cpu --iterations 5 --skip_batch_num 1

echo "== 5/20 observability tooling smoke (program_report + trace_summary) =="
OBS_DIR=$(mktemp -d)
SMOKE_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR"' EXIT
JAX_PLATFORMS=cpu python - "$OBS_DIR" <<'PY'
import sys
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor, profiler

out = sys.argv[1]
monitor.enable(log_dir=out)
x = fluid.layers.data("x", shape=[8])
loss = fluid.layers.mean(fluid.layers.fc(x, size=4, act="relu"))
fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
with profiler.profiler("CPU", profile_path=None):
    for _ in range(3):
        exe.run(feed={"x": np.random.rand(4, 8).astype("float32")},
                fetch_list=[loss])
profiler.export_chrome_tracing(out + "/trace.json")
monitor.disable()
PY
python tools/program_report.py "$OBS_DIR" --top 5
python tools/trace_summary.py "$OBS_DIR/trace.json" --top 10 --sorted_key calls

echo "== 6/20 preemption smoke (SIGTERM a monitored run -> exact resume) =="
cat > "$SMOKE_DIR/smoke.py" <<'PY'
import os, signal, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())          # run_ci runs from the repo root
mode, ckpt = sys.argv[1], sys.argv[2]
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.contrib import Trainer, CheckpointConfig
from paddle_tpu.reader import checkpointable

monitor.enable(log_dir=os.path.join(os.path.dirname(ckpt), "monitor"))

def train_func():
    fluid.default_main_program().random_seed = 7
    fluid.default_startup_program().random_seed = 7
    x = fluid.layers.data("x", shape=[8])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, size=16, act="relu")
    pred = fluid.layers.fc(h, size=4, act="softmax")
    return fluid.layers.mean(fluid.layers.cross_entropy(pred, label))

def samples():
    rng = np.random.RandomState(0)
    for _ in range(24):
        x = rng.rand(8).astype("float32")
        yield x, np.array([int(np.argmax(x[:4]))], "int64")

cfg = CheckpointConfig(checkpoint_dir=ckpt, step_interval=1)
trainer = Trainer(train_func=train_func, place=fluid.CPUPlace(),
                  optimizer_func=lambda: fluid.optimizer.Adam(1e-2),
                  checkpoint_config=cfg)
if mode == "resume":
    print("RESUMED", cfg.load_serial, flush=True)
    assert cfg.load_serial == 3, cfg.load_serial
state = {"step": cfg.load_serial or 0}

def handler(event):
    if not hasattr(event, "metrics"):
        return
    state["step"] += 1
    print("STEP %d %r" % (state["step"],
                          float(np.ravel(event.metrics[0])[0])),
          flush=True)
    if mode == "run" and state["step"] == 3:
        os.kill(os.getpid(), signal.SIGTERM)   # preemption notice

trainer.train(num_epochs=1, event_handler=handler,
              reader=checkpointable(fluid.batch(samples, batch_size=4)),
              feed_order=["x", "label"])
PY
JAX_PLATFORMS=cpu python "$SMOKE_DIR/smoke.py" ref "$SMOKE_DIR/ref_ckpt" \
  > "$SMOKE_DIR/ref.out"
set +e
JAX_PLATFORMS=cpu python "$SMOKE_DIR/smoke.py" run "$SMOKE_DIR/ckpt" \
  > "$SMOKE_DIR/run.out"
rc=$?
set -e
test "$rc" -eq 143  # the flush ran, then SIGTERM's default proceeded
JAX_PLATFORMS=cpu python "$SMOKE_DIR/smoke.py" resume "$SMOKE_DIR/ckpt" \
  > "$SMOKE_DIR/resume.out"
grep -q "^RESUMED 3$" "$SMOKE_DIR/resume.out"
# resumed steps 4-6 must reproduce the uninterrupted run's losses exactly
diff <(grep "^STEP [456] " "$SMOKE_DIR/ref.out") \
     <(grep "^STEP [456] " "$SMOKE_DIR/resume.out")
grep -ql checkpoint_saved "$SMOKE_DIR"/monitor/*.jsonl

echo "== 7/20 fsdp mesh smoke (4 virtual devices, sharding_rules) =="
FSDP_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR"' EXIT
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python - "$FSDP_DIR" <<'PY'
import os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import jax
import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.models import transformer as tfm
from paddle_tpu.parallel import make_mesh

out = sys.argv[1]
monitor.enable(log_dir=out)
fluid.default_main_program().random_seed = 7
fluid.default_startup_program().random_seed = 7
src = fluid.layers.data("src_word", shape=[1], dtype="int64", lod_level=1)
tgt = fluid.layers.data("tgt_word", shape=[1], dtype="int64", lod_level=1)
lbl = fluid.layers.data("lbl_word", shape=[1], dtype="int64", lod_level=1)
loss, _ = tfm.transformer(src, tgt, lbl, 8, 8, 32, 32, n_layer=2,
                          n_head=2, d_model=16, d_inner=32,
                          dropout_rate=0.1)
fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

mesh = make_mesh((1, 4), ("dp", "fsdp"))
bs = fluid.BuildStrategy()
bs.sharding_rules = True
fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh,
                            build_strategy=bs)
rng = np.random.RandomState(0)
for step in range(4):
    ids = rng.randint(2, 32, (8, 8, 1)).astype("int64")
    lens = rng.randint(4, 9, (8,)).astype("int32")
    (lv,) = pe.run(feed={"src_word": ids, "src_word@LEN": lens,
                         "tgt_word": ids, "tgt_word@LEN": lens,
                         "lbl_word": ids, "lbl_word@LEN": lens},
                   fetch_list=[loss])
    lv = float(np.asarray(lv).ravel()[0])
    assert np.isfinite(lv), lv
    print("FSDP STEP %d loss %.6f" % (step, lv), flush=True)
from jax.sharding import PartitionSpec as P
emb = fluid.global_scope().var("src_word_emb")
assert isinstance(emb, jax.Array) and emb.sharding.spec == P("fsdp"), \
    emb.sharding
print("FSDP SHARDED src_word_emb", emb.sharding.spec, flush=True)
monitor.disable()
PY
# the profile registry captured the SHARDED per-device peak HBM
# (the kind column truncates to 10 chars: "parallel_e")
python tools/program_report.py "$FSDP_DIR" --top 3 | tee "$FSDP_DIR/report.txt"
grep -q "parallel_e" "$FSDP_DIR/report.txt"

echo "== 8/20 guardian smoke (NaN injected at step 5 -> rollback -> finite) =="
GUARD_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR"' EXIT
# the drill is installed purely from the environment (FLAGS_fault_spec)
# and the guardian purely from flags — no code changes to the script
JAX_PLATFORMS=cpu \
FLAGS_guardian=1 FLAGS_guardian_policy=rollback,abort \
FLAGS_fault_spec='nan_var:fc_0.w_0@5' \
  python - "$GUARD_DIR" <<'PY'
import os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.contrib import Trainer, CheckpointConfig
from paddle_tpu.reader import checkpointable

out = sys.argv[1]
monitor.enable(log_dir=os.path.join(out, "monitor"))

def train_func():
    fluid.default_main_program().random_seed = 7
    fluid.default_startup_program().random_seed = 7
    x = fluid.layers.data("x", shape=[8])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, size=16, act="relu")
    pred = fluid.layers.fc(h, size=4, act="softmax")
    return fluid.layers.mean(fluid.layers.cross_entropy(pred, label))

def samples():
    rng = np.random.RandomState(0)
    for _ in range(48):
        x = rng.rand(8).astype("float32")
        yield x, np.array([int(np.argmax(x[:4]))], "int64")

losses = []
def handler(ev):
    if hasattr(ev, "metrics"):
        losses.append(float(np.ravel(ev.metrics[0])[0]))
        print("STEP %d %.6f" % (len(losses), losses[-1]), flush=True)

trainer = Trainer(train_func=train_func, place=fluid.CPUPlace(),
                  optimizer_func=lambda: fluid.optimizer.Adam(1e-2),
                  checkpoint_config=CheckpointConfig(
                      checkpoint_dir=os.path.join(out, "ckpt"),
                      step_interval=2, async_save=False))
trainer.train(num_epochs=1, event_handler=handler,
              reader=checkpointable(fluid.batch(samples, batch_size=4)),
              feed_order=["x", "label"])
assert np.isfinite(losses[-1]), losses[-1]
print("GUARDIAN FINAL %.6f after %d observed steps" %
      (losses[-1], len(losses)), flush=True)
PY
# the decision trail landed in the JSONL, run_id-correlated
grep -ql fault_injected "$GUARD_DIR"/monitor/*.jsonl
grep -ql guardian_rollback "$GUARD_DIR"/monitor/*.jsonl

echo "== 9/20 autotune smoke (tune toy MLP -> artifact -> report -> Trainer) =="
TUNE_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR"' EXIT
JAX_PLATFORMS=cpu python - "$TUNE_DIR" <<'PY'
import os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import autotune, monitor

out = sys.argv[1]
monitor.enable(log_dir=os.path.join(out, "monitor"))
# a fake device-memory ceiling: the probe's rejection mechanism is the
# compiled module's own peak-HBM ESTIMATE vs this limit, never an OOM —
# which is exactly what makes the ladder drivable on the CPU backend
fluid.set_flags({"FLAGS_autotune_hbm_bytes": 3_000_000})
img = fluid.layers.data("img", shape=[784])
label = fluid.layers.data("label", shape=[1], dtype="int64")
h = fluid.layers.fc(img, size=64, act="relu")
pred = fluid.layers.fc(h, size=10, act="softmax")
loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
fluid.optimizer.Adam(1e-3).minimize(loss)
rng = np.random.RandomState(0)
def make_feed(b):
    return {"img": rng.rand(b, 784).astype("float32"),
            "label": rng.randint(0, 10, (b, 1)).astype("int64")}
cfg = autotune.TunedConfig(meta={"model": "mlp_smoke"})
d = autotune.tune_batch_size(
    fluid.default_main_program(), fluid.default_startup_program(),
    make_feed, loss, fluid.CPUPlace(), start=16, max_batch=1024,
    probe_steps=2, config=cfg)
assert d["chosen"], d
# a checkpoint-interval decision from synthetic-but-plausible measured
# costs rides in the same artifact (the Trainer consumes it below)
cfg.add(autotune.decide_checkpoint_interval(
    step_s=0.02, snapshot_s=0.002, save_s=0.01, async_save=False))
path = cfg.save(os.path.join(out, "tuned.json"))
print("TUNED batch=%s -> %s" % (d["chosen"], path), flush=True)
PY
test -s "$TUNE_DIR/tuned.json"
python tools/autotune_report.py "$TUNE_DIR/tuned.json" --verbose \
  | tee "$TUNE_DIR/report.txt"
grep -q "batch_size" "$TUNE_DIR/report.txt"
grep -q "checkpoint_interval" "$TUNE_DIR/report.txt"
# the decision trail landed in the JSONL
grep -ql autotune_decision "$TUNE_DIR"/monitor/*.jsonl
# a Trainer run CONSUMING the artifact completes with finite loss (the
# tuned checkpoint interval re-gates its manager; nothing is pinned)
JAX_PLATFORMS=cpu python - "$TUNE_DIR" <<'PY'
import os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import paddle_tpu as fluid
from paddle_tpu.contrib import Trainer, CheckpointConfig
from paddle_tpu.reader import checkpointable

out = sys.argv[1]

def train_func():
    fluid.default_main_program().random_seed = 7
    fluid.default_startup_program().random_seed = 7
    img = fluid.layers.data("img", shape=[784])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(img, size=64, act="relu")
    pred = fluid.layers.fc(h, size=10, act="softmax")
    return fluid.layers.mean(fluid.layers.cross_entropy(pred, label))

def samples():
    rng = np.random.RandomState(0)
    for _ in range(64):
        yield (rng.rand(784).astype("float32"),
               rng.randint(0, 10, (1,)).astype("int64"))

losses = []
def handler(ev):
    if hasattr(ev, "metrics"):
        losses.append(float(np.ravel(ev.metrics[0])[0]))

trainer = Trainer(train_func=train_func, place=fluid.CPUPlace(),
                  optimizer_func=lambda: fluid.optimizer.Adam(1e-3),
                  checkpoint_config=CheckpointConfig(
                      checkpoint_dir=os.path.join(out, "ckpt"),
                      async_save=False),
                  autotune=os.path.join(out, "tuned.json"))
# ceil((0.002+0.01) / (0.035 * 0.02)) = 18: the artifact's tuned
# cadence re-gated the manager (step_interval was NOT pinned)
assert trainer.checkpoint_cfg.step_interval == 18, \
    trainer.checkpoint_cfg.step_interval
trainer.train(num_epochs=1, event_handler=handler,
              reader=checkpointable(fluid.batch(samples, batch_size=16)),
              feed_order=["img", "label"])
assert losses and np.isfinite(losses[-1]), losses[-1:]
print("AUTOTUNE TRAINER FINAL %.6f over %d steps"
      % (losses[-1], len(losses)), flush=True)
PY

echo "== 10/20 goodput smoke + bench-history regression gate =="
GOOD_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR"' EXIT
# (a) a 3-step monitored MLP run -> the goodput ledger attributes its
# wall clock, the report renders it, and the ratio is in (0, 1]
JAX_PLATFORMS=cpu python - "$GOOD_DIR" <<'PY'
import os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor

out = sys.argv[1]
monitor.enable(log_dir=os.path.join(out, "monitor"))
x = fluid.layers.data("x", shape=[8])
loss = fluid.layers.mean(fluid.layers.fc(x, size=4, act="relu"))
fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
for _ in range(3):
    exe.run(feed={"x": np.random.rand(4, 8).astype("float32")},
            fetch_list=[loss])
s = monitor.goodput_stamp()
assert s["goodput_ratio"] is not None and 0 < s["goodput_ratio"] <= 1, s
print("GOODPUT ratio %.4f over %.3fs (%d steps)"
      % (s["goodput_ratio"], s["wall_seconds"], s["steps"]), flush=True)
PY
python tools/goodput_report.py "$GOOD_DIR/monitor" | tee "$GOOD_DIR/report.txt"
grep -q "goodput ratio" "$GOOD_DIR/report.txt"
grep -q "trace_compile" "$GOOD_DIR/report.txt"
# (b) cross-run regression gate: a four-wrapper history (two legacy
# runs, one comparable, one rc=124 with no parsed line) PASSes, and a
# synthetically perturbed (+20% step time) copy of the comparable run
# comes back REGRESSED
python - "$GOOD_DIR" <<'PY'
import copy, json, sys
out = sys.argv[1]
def wrapper(n, parsed, rc=0):
    json.dump({"n": n, "cmd": "python bench.py", "rc": rc, "tail": "",
               "parsed": parsed}, open("%s/BENCH_r%02d.json" % (out, n), "w"))
def rung(value, **kw):
    return dict({"metric": "resnet50_images_per_sec_bf16", "value": value,
                 "unit": "images/sec", "vs_baseline": 1.0}, **kw)
ok = rung(2334.75, min_step_s=0.054824, n_windows=3, est_mfu=0.1458)
wrapper(1, rung(7966.2)); wrapper(2, rung(7903.64)); wrapper(3, ok)
wrapper(4, None, rc=124)
bad = copy.deepcopy(ok)
bad["min_step_s"] = round(ok["min_step_s"] * 1.2, 6)
bad["value"] = round(ok["value"] / 1.2, 2)
json.dump({"n": 99, "cmd": "python bench.py", "rc": 0, "tail": "",
           "parsed": bad}, open(out + "/BENCH_r99_perturbed.json", "w"))
PY
python tools/bench_history.py "$GOOD_DIR"/BENCH_r0*.json --json \
  | python -c "import json,sys; r=json.load(sys.stdin); \
assert r['overall']=='PASS', r['overall']; print('bench_history: history PASS')"
set +e
python tools/bench_history.py "$GOOD_DIR"/BENCH_r0*.json "$GOOD_DIR/BENCH_r99_perturbed.json" \
  --json > "$GOOD_DIR/history.json"
rc=$?
set -e
test "$rc" -eq 1   # a regression exits 1 (the CI contract)
python - "$GOOD_DIR" <<'PY'
import json, sys
r = json.load(open(sys.argv[1] + "/history.json"))
assert r["overall"] == "REGRESSED", r["overall"]
bad = [x for x in r["runs"] if x["run"] == "r99"][0]
assert any(c["field"] == "min_step_s" and c["verdict"] == "REGRESSED"
           for c in bad["comparisons"]), bad
print("bench_history: +20% perturbation flagged REGRESSED")
PY

echo "== 11/20 serving smoke (engine over toy MLP, concurrent requests) =="
SERVE_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR"' EXIT
JAX_PLATFORMS=cpu python - "$SERVE_DIR" <<'PY'
import os, sys, threading
sys.path.insert(0, os.getcwd())
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.serving import InferenceEngine

out = sys.argv[1]
monitor.enable(log_dir=os.path.join(out, "monitor"))
fluid.default_startup_program().random_seed = 7
x = fluid.layers.data("x", shape=[32])
h = fluid.layers.fc(x, size=32, act="relu")
pred = fluid.layers.fc(h, size=4, act="softmax")
exe = fluid.Executor(fluid.CPUPlace())
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(os.path.join(out, "model"), ["x"],
                                  [pred], exe)
eng = InferenceEngine(model_dir=os.path.join(out, "model"), slots=8,
                      timeout_s=60.0)
xs = [np.random.RandomState(i).rand(32).astype("float32")
      for i in range(24)]
results = {}
def client(i):
    results[i] = eng.run({"x": xs[i]}, timeout=120)
threads = [threading.Thread(target=client, args=(i,))
           for i in range(len(xs))]
for t in threads:
    t.start()
for t in threads:
    t.join()
assert len(results) == len(xs)
assert all(np.isfinite(v[0]).all() for v in results.values())
s = eng.metrics.summary()
assert s["counts"]["completed"] == len(xs), s
# generous p99 bound: the smoke asserts the SLO pipeline, not the chip
assert s["p99_ms"] is not None and s["p99_ms"] < 10000, s
assert s["goodput_view"]["goodput_ratio"] is not None, s
print("SERVING p50 %.2fms p99 %.2fms over %d requests (%d batches)"
      % (s["p50_ms"], s["p99_ms"], s["counts"]["completed"],
         s["counts"]["batches"]), flush=True)
text = monitor.expose_text()
assert "serving_request_latency_seconds" in text, "missing histogram"
assert "serving_queue_depth" in text, "missing gauge"
eng.close()
monitor.disable()
PY
# per-request serving/* events landed in the JSONL, run_id-correlated
grep -ql serving_request "$SERVE_DIR"/monitor/*.jsonl

echo "== 12/20 pipeline schedules smoke (2 virtual devices: 1F1B/interleaved =="
echo "==       loss parity vs GPipe + measured pipeline_bubble drop)        =="
PIPE_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR"' EXIT
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  python - "$PIPE_DIR" <<'PY'
import os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.models import transformer as tfm
from paddle_tpu.parallel import make_mesh

out = sys.argv[1]
monitor.enable(log_dir=out)
mesh = make_mesh((1, 2), ("dp", "pp"))
rng = np.random.RandomState(3)
batches = []
for _ in range(3):
    ids = rng.randint(2, 32, (8, 8, 1)).astype("int64")
    lens = rng.randint(4, 9, (8,)).astype("int32")
    batches.append({"src_word": ids, "src_word@LEN": lens,
                    "tgt_word": ids, "tgt_word@LEN": lens,
                    "lbl_word": ids, "lbl_word@LEN": lens})
losses, fractions = {}, {}
# EQUAL (S=2, M=2): the same 4-layer model — gpipe/1f1b run it as 2 fat
# stages, interleaved as 4 thin stages (v=2 chunks per device)
for sched, lps in (("gpipe", 2), ("1f1b", 2), ("interleaved", 1)):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        fluid.default_main_program().random_seed = 13
        fluid.default_startup_program().random_seed = 13
        src = fluid.layers.data("src_word", shape=[1], dtype="int64",
                                lod_level=1)
        tgt = fluid.layers.data("tgt_word", shape=[1], dtype="int64",
                                lod_level=1)
        lbl = fluid.layers.data("lbl_word", shape=[1], dtype="int64",
                                lod_level=1)
        loss, _ = tfm.transformer(src, tgt, lbl, 8, 8, 32, 32,
                                  n_layer=4, n_head=2, d_model=16,
                                  d_inner=32, dropout_rate=0.0,
                                  pipeline_microbatches=2,
                                  pipeline_layers_per_stage=lps)
        fluid.optimizer.Adam(learning_rate=2e-3).minimize(loss)
        bs = fluid.BuildStrategy()
        bs.pipeline_schedule = sched
        with fluid.scope_guard(fluid.Scope()):
            fluid.Executor(fluid.CPUPlace()).run(
                fluid.default_startup_program())
            pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh,
                                        build_strategy=bs)
            pe.run(feed=batches[0], fetch_list=[loss])      # warm
            monitor.goodput_reset()
            losses[sched] = [
                float(np.asarray(pe.run(feed=b, fetch_list=[loss])[0])
                      .ravel()[0]) for b in batches]
        stamp = monitor.goodput_stamp()
        assert stamp["buckets"]["pipeline_bubble"] > 0, stamp
        warm = stamp["buckets"]["pipeline_bubble"] + \
            stamp["buckets"]["compute"]
        fractions[sched] = stamp["buckets"]["pipeline_bubble"] / warm
np.testing.assert_allclose(losses["gpipe"], losses["1f1b"],
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(losses["gpipe"], losses["interleaved"],
                           rtol=2e-4, atol=2e-4)
assert fractions["interleaved"] < fractions["gpipe"], fractions
print("PIPELINE schedules loss parity OK; measured bubble fractions: "
      "gpipe=%.3f 1f1b=%.3f interleaved=%.3f"
      % (fractions["gpipe"], fractions["1f1b"],
         fractions["interleaved"]), flush=True)
monitor.disable()
PY
# the pipeline_bubble bucket landed in the goodput JSONL stamps
grep -ql pipeline_bubble "$PIPE_DIR"/*.jsonl

echo "== 13/20 cluster elastic-resume drill (2 members, SIGKILL one mid-run) =="
CLUSTER_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR" "$CLUSTER_DIR"' EXIT
# the supervisor runs the whole acceptance drill: an uninterrupted
# small-mesh reference, a 2-member gloo world over one ClusterMaster
# with per-host sharded checkpoints, SIGKILL of member 1 at step 8, and
# the survivor's barrier-observed lease expiry -> reshape -> re-exec
# onto the smaller mesh -> resume from the last committed step.  It
# asserts the parity band, the manifest's ~1/N per-host bytes, and the
# resume provenance itself; the grep re-checks the headline landed.
python tests/cluster_runner.py supervise "$CLUSTER_DIR" \
  | tee "$CLUSTER_DIR/drill.out"
grep -q "CLUSTER_DRILL OK" "$CLUSTER_DIR/drill.out"
# the ckpt_sharded bench rung emits per-host save wall-clock evidence
# (1/N bytes per host, flat MB/s) that bench_history indexes
python bench.py --model ckpt_sharded --device cpu > "$CLUSTER_DIR/ckpt_bench.json"
python - "$CLUSTER_DIR" <<'PY'
import json, sys
r = json.loads(open(sys.argv[1] + "/ckpt_bench.json").read().strip().splitlines()[-1])
assert r["roundtrip_bit_identical"] is True, r
assert r["bytes_one_over_n"]["4"] < 0.3, r["bytes_one_over_n"]
assert r["save_wall_s"] is not None and r["informational"] is True
print("CKPT_SHARDED per-host wall %.3fs, bytes/N %s, MB/s spread %.2f"
      % (r["save_wall_s"], r["bytes_one_over_n"], r["mb_per_s_spread"]))
PY

echo "== 14/20 quantized inference smoke (pass -> gate -> save -> serving) =="
QUANT_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR" "$CLUSTER_DIR" "$QUANT_DIR"' EXIT
# end-to-end int8: accuracy-gated tune_quantization over a toy inference
# program -> TunedConfig evidence -> quantize_inference rewrite ->
# save_inference_model (int8 persistables, fp masters gone) -> a COLD
# serving-engine load of the quantized artifact answers requests with
# finite outputs and an eval delta under the budget
JAX_PLATFORMS=cpu python - "$QUANT_DIR" <<'PY'
import os, sys
sys.path.insert(0, os.getcwd())
import json
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import autotune, monitor
from paddle_tpu.serving import InferenceEngine
from paddle_tpu.transpiler import quantize_inference

out = sys.argv[1]
monitor.enable(log_dir=os.path.join(out, "monitor"))
fluid.default_main_program().random_seed = 11
fluid.default_startup_program().random_seed = 11
x = fluid.layers.data("x", shape=[64])
h = fluid.layers.fc(x, size=256, act="relu")
pred = fluid.layers.fc(h, size=16, act="softmax")
main = fluid.default_main_program()
scope = fluid.Scope()
rng = np.random.RandomState(0)
feed = {"x": rng.rand(8, 64).astype("float32")}
exe = fluid.Executor(fluid.CPUPlace())
with fluid.scope_guard(scope):
    exe.run(fluid.default_startup_program())
    (ref,) = exe.run(main, feed=feed, fetch_list=[pred])
    cfg = autotune.TunedConfig(meta={"model": "quant_smoke"})
    d = autotune.tune_quantization(main, scope, feed, [pred],
                                   fluid.CPUPlace(), probe_steps=2,
                                   min_speedup=0.0, config=cfg)
    assert d["chosen"] is not None, d   # a mode survived the gate
    cfg.save(os.path.join(out, "tuned.json"))
    qprog = quantize_inference(main, scope=scope, mode=d["chosen"])
    fluid.io.save_inference_model(
        os.path.join(out, "model"), ["x"],
        [qprog.global_block().var(pred.name)], exe, main_program=qprog)
# artifact holds int8 weights, not the fp masters
mm = json.load(open(os.path.join(out, "model", "__model__")))
names = [v["name"] for b in mm["program"]["blocks"] for v in b["vars"]]
assert any(n.endswith("@INT8") for n in names), names
assert "fc_0.w_0" not in names, "fp master weight still in artifact"
# cold load into the serving engine; finite outputs, delta under budget
eng = InferenceEngine(model_dir=os.path.join(out, "model"), slots=4,
                      timeout_s=60.0)
outs = [eng.run({"x": feed["x"][i]}) for i in range(8)]
eng.close()
q = np.stack([np.asarray(o[0]) for o in outs])
assert np.isfinite(q).all()
delta = autotune.eval_delta([np.asarray(ref)], [q])
budget = fluid.get_flags("quantize_accuracy_budget")[
    "quantize_accuracy_budget"]
assert delta <= budget, (delta, budget)
print("QUANTIZED mode=%s accuracy_delta=%.6f (budget %.3f), "
      "cold serving load OK" % (d["chosen"], delta, budget), flush=True)
PY
# the gate's decision trail landed in the JSONL
grep -ql '"knob": "quantization"' "$QUANT_DIR"/monitor/*.jsonl || \
  grep -ql quantization "$QUANT_DIR"/monitor/*.jsonl

echo "== 15/20 sparse-embedding smoke (ctr_dnn is_sparse + incremental =="
echo "==       checkpoints: SIGTERM flush -> base+delta resume bit-identical) =="
SPARSE_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR" "$CLUSTER_DIR" "$QUANT_DIR" "$SPARSE_DIR"' EXIT
cat > "$SPARSE_DIR/sparse_smoke.py" <<'PY'
import os, signal, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())
mode, ckpt = sys.argv[1], sys.argv[2]
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.contrib import Trainer, CheckpointConfig
from paddle_tpu.models.ctr_dnn import ctr_dnn
from paddle_tpu.reader import checkpointable

monitor.enable(log_dir=os.path.join(os.path.dirname(ckpt), "monitor"))
DNN_V, LR_V, T = 400, 50, 5

def train_func():
    fluid.default_main_program().random_seed = 7
    fluid.default_startup_program().random_seed = 7
    dnn = fluid.layers.data("dnn_ids", shape=[1], dtype="int64",
                            lod_level=1)
    lr = fluid.layers.data("lr_ids", shape=[1], dtype="int64",
                           lod_level=1)
    label = fluid.layers.data("click", shape=[1], dtype="int64")
    cost, _p, _a = ctr_dnn(dnn, lr, label, DNN_V, LR_V)
    return cost

def samples():
    rng = np.random.RandomState(0)
    for _ in range(24):
        yield (rng.randint(0, DNN_V, (T, 1)).astype("int64"),
               rng.randint(0, LR_V, (2, 1)).astype("int64"),
               np.array([int(rng.rand() < 0.5)], "int64"))

# incremental='auto': every is_sparse table + its Adam moments are
# delta-encoded against the step-1 full base
cfg = CheckpointConfig(checkpoint_dir=ckpt, step_interval=1,
                       incremental="auto")
trainer = Trainer(train_func=train_func, place=fluid.CPUPlace(),
                  optimizer_func=lambda: fluid.optimizer.Adam(1e-2),
                  checkpoint_config=cfg)
if mode == "resume":
    print("RESUMED", cfg.load_serial, flush=True)
    assert cfg.load_serial == 3, cfg.load_serial
state = {"step": cfg.load_serial or 0}

def handler(event):
    if not hasattr(event, "metrics"):
        return
    state["step"] += 1
    print("STEP %d %r" % (state["step"],
                          float(np.ravel(event.metrics[0])[0])),
          flush=True)
    if mode == "run" and state["step"] == 3:
        os.kill(os.getpid(), signal.SIGTERM)   # preemption notice

trainer.train(num_epochs=1, event_handler=handler,
              reader=checkpointable(fluid.batch(samples, batch_size=4)),
              feed_order=["dnn_ids", "lr_ids", "click"])
PY
JAX_PLATFORMS=cpu python "$SPARSE_DIR/sparse_smoke.py" ref "$SPARSE_DIR/ref_ckpt" \
  > "$SPARSE_DIR/ref.out"
set +e
JAX_PLATFORMS=cpu python "$SPARSE_DIR/sparse_smoke.py" run "$SPARSE_DIR/ckpt" \
  > "$SPARSE_DIR/run.out"
rc=$?
set -e
test "$rc" -eq 143  # checkpoint flushed, then SIGTERM's default proceeded
# the flushed artifacts are an incremental chain: step 1 full, 2-3 deltas
python - "$SPARSE_DIR/ckpt" <<'PY'
import json, os, sys
ck = sys.argv[1]
steps = sorted(d for d in os.listdir(ck) if d.startswith("step_"))
assert len(steps) >= 3, steps
kinds = []
for d in steps[:3]:
    m = json.load(open(os.path.join(ck, d, "MANIFEST.json")))
    kinds.append("delta" if m.get("incremental") else "full")
assert kinds == ["full", "delta", "delta"], kinds
print("INCREMENTAL CHAIN", kinds, flush=True)
PY
JAX_PLATFORMS=cpu python "$SPARSE_DIR/sparse_smoke.py" resume "$SPARSE_DIR/ckpt" \
  > "$SPARSE_DIR/resume.out"
grep -q "^RESUMED 3$" "$SPARSE_DIR/resume.out"
# base+delta restore: resumed steps 4-6 reproduce the uninterrupted
# run's losses bit-exactly (%r prints full precision)
diff <(grep "^STEP [456] " "$SPARSE_DIR/ref.out") \
     <(grep "^STEP [456] " "$SPARSE_DIR/resume.out")
# touched-row telemetry rode the per-step JSONL records
grep -ql sparse_touched_rows "$SPARSE_DIR"/monitor/*.jsonl

echo "== 16/20 paged-KV + speculative decode smoke (prefix reuse, =="
echo "==       spec==greedy parity, page-leak-free teardown)      =="
PAGED_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR" "$CLUSTER_DIR" "$QUANT_DIR" "$SPARSE_DIR" "$PAGED_DIR"' EXIT
JAX_PLATFORMS=cpu python - "$PAGED_DIR/monitor" <<'PY'
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())
import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.serving.decoder import build_decoder_lm, sync_draft_weights
from paddle_tpu.serving.engine import GenerationEngine

monitor.enable(log_dir=sys.argv[1])
V, L, S, PS, K = 31, 32, 2, 8, 3
dims = dict(n_layer=1, n_head=2, d_model=16, d_inner=32)
# one full shared page of system prompt + a unique tail token: the
# within-batch aliasing opportunity prefix reuse exists for
system = list(range(2, 2 + PS))
prompts = [system + [9 + i] for i in range(4)]

# baseline: plain greedy through the fixed-region engine
fixed = build_decoder_lm(V, L, S, prefix="cif", **dims)
eng = GenerationEngine(fixed, place=fluid.CPUPlace(),
                       max_new_tokens=6, timeout_s=300.0)
try:
    base = [r.result(600)["tokens"] for r in
            [eng.submit(p) for p in prompts]]
finally:
    eng.close()

# paged target + perfect self-draft (target weights copied onto the
# draft): the full propose/verify/rollback path, deterministically
spec = build_decoder_lm(V, L, S, paged=True, page_size=PS, spec_k=K,
                        prefix="cip", **dims)
draft = build_decoder_lm(V, L, S, prefix="cid", **dims)
eng = GenerationEngine(spec, place=fluid.CPUPlace(), max_new_tokens=6,
                       timeout_s=300.0, draft_spec=draft, start=False)
try:
    synced = sync_draft_weights(eng._scope, spec, draft)
    eng.start()
    outs = [r.result(600)["tokens"] for r in
            [eng.submit(p) for p in prompts]]
    snap = eng.metrics.paged_snapshot()
    leaks = eng._alloc.check_leaks()
finally:
    eng.close()
assert outs == base, (outs, base)            # speculation = greedy
assert snap["prefix_hits"] > 0, snap         # prefix pages aliased
assert snap["spec_accepted"] > 0, snap       # draft tokens survived
assert leaks == [], leaks                    # every page returned
print("PAGED+SPEC OK prefix_hits=%d accepted=%d/%d synced=%d"
      % (snap["prefix_hits"], snap["spec_accepted"],
         snap["spec_proposed"], synced), flush=True)
PY
# the paged/speculation counters rode the run_id-stamped JSONL
grep -ql prefix_hits "$PAGED_DIR"/monitor/*.jsonl

echo "== 17/20 traced serving smoke (request trace trees from JSONL) =="
TRACE_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR" "$CLUSTER_DIR" "$QUANT_DIR" "$SPARSE_DIR" "$PAGED_DIR" "$TRACE_DIR"' EXIT
JAX_PLATFORMS=cpu python - "$TRACE_DIR/monitor" <<'PY'
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())
import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.monitor import tracing
from paddle_tpu.serving.decoder import build_decoder_lm
from paddle_tpu.serving.engine import GenerationEngine

monitor.enable(log_dir=sys.argv[1])
tracing.enable()
V, L, S, PS = 31, 32, 2, 8
dims = dict(n_layer=1, n_head=2, d_model=16, d_inner=32)
spec = build_decoder_lm(V, L, S, paged=True, page_size=PS,
                        prefix="tci", **dims)
eng = GenerationEngine(spec, place=fluid.CPUPlace(), max_new_tokens=5,
                       timeout_s=300.0)
try:
    # open-loop: more requests than slots, so the trace trees cover
    # queueing, paged admission back-pressure, and slot recycling
    reqs = [eng.submit(list(range(2, 2 + PS)) + [9 + i])
            for i in range(8)]
    outs = [r.result(600) for r in reqs]
finally:
    eng.close()
assert len(outs) == 8 and all(o["tokens"] for o in outs)
# slot-recycling hygiene: every request kept its own trace identity
tids = {r.trace.trace_id for r in reqs}
assert len(tids) == 8, tids
print("TRACED SERVING OK requests=%d" % len(outs), flush=True)
PY
# cross-process assembly gate: >=99% of terminal requests must form
# complete trees (admission -> terminal, every parent link resolving),
# breakdown table printed from the same JSONL the run wrote
python tools/request_trace.py "$TRACE_DIR"/monitor --assert-complete 0.99

echo "== 18/20 serving-fleet failover smoke (2 replicas, SIGKILL one =="
echo "==      under load -> zero lost requests, re-routed completes) =="
FLEET_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR" "$CLUSTER_DIR" "$QUANT_DIR" "$SPARSE_DIR" "$PAGED_DIR" "$TRACE_DIR" "$FLEET_DIR"' EXIT
# the supervisor asserts the acceptance criteria itself: 24/24
# completed (zero loss), the victim quarantined, re-routed requests
# finishing on the survivor, pages drained there, parity with direct
# dispatch, and measured re-route latency in the FLEET_DRILL line
JAX_PLATFORMS=cpu python tests/fleet_runner.py supervise "$FLEET_DIR" 2 24
# fleet-assembled trace trees: client + master + both replicas wrote
# one shared JSONL dir; every terminal request must assemble complete
# ACROSS the SIGKILL (rpc-server spans open-anchor on entry)
python tools/request_trace.py "$FLEET_DIR"/monitor --assert-complete 0.99

echo "== 19/20 fleet telemetry drill (3 members, digests over heartbeat, =="
echo "==      delay_dispatch straggler -> alert fires + resolves) =="
TELEM_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR" "$CLUSTER_DIR" "$QUANT_DIR" "$SPARSE_DIR" "$PAGED_DIR" "$TRACE_DIR" "$FLEET_DIR" "$TELEM_DIR"' EXIT
# the supervisor asserts the acceptance evidence itself: all 3 members
# push digests over the real heartbeat RPC, the slowed member (m-0)
# flags as straggler and the alert fires with its member_id, merged
# fleet series appear on the master's /metrics, the alert resolves
# after the fault window disarms, and the master JSONL holds the
# firing -> resolved pair
JAX_PLATFORMS=cpu python tests/fleet_telemetry_runner.py supervise "$TELEM_DIR" 3
# the operator pane renders from the same master JSONL (replay path)
python tools/fleet_report.py "$TELEM_DIR"/master

echo "== 20/20 model-health + NaN-provenance drill (fault nan at a named =="
echo "==      param -> guardian quarantines -> provenance names the op)  =="
HEALTH_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR" "$SMOKE_DIR" "$FSDP_DIR" "$GUARD_DIR" "$TUNE_DIR" "$GOOD_DIR" "$SERVE_DIR" "$PIPE_DIR" "$CLUSTER_DIR" "$QUANT_DIR" "$SPARSE_DIR" "$PAGED_DIR" "$TRACE_DIR" "$FLEET_DIR" "$TELEM_DIR" "$HEALTH_DIR"' EXIT
# drill installed purely from the environment: FLAGS_health turns the
# in-graph probe on, FLAGS_fault_spec poisons fc_0.w_0 after step 5, so
# step 6's first consumer of that param (mul -> fc_0.tmp_0) goes
# non-finite — the provenance record must name exactly that op
JAX_PLATFORMS=cpu \
FLAGS_health=1 FLAGS_health_every=2 \
FLAGS_guardian=1 FLAGS_guardian_policy=skip,abort \
FLAGS_fault_spec='nan_var:fc_0.w_0@5' \
  python - "$HEALTH_DIR" <<'PY'
import glob, json, os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import guardian, monitor

out = sys.argv[1]
monitor.enable(log_dir=os.path.join(out, "monitor"))
fluid.default_main_program().random_seed = 7
fluid.default_startup_program().random_seed = 7
x = fluid.layers.data("x", shape=[8])
label = fluid.layers.data("label", shape=[1], dtype="int64")
h = fluid.layers.fc(x, size=16, act="relu")
pred = fluid.layers.fc(h, size=4, act="softmax")
loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
g = guardian.install(guardian.Guardian(
    quarantine_dir=os.path.join(out, "quarantine")))
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
rng = np.random.RandomState(0)
aborted = None
try:
    for step in range(10):
        exe.run(feed={"x": rng.rand(4, 8).astype("float32"),
                      "label": rng.randint(0, 4, (4, 1)).astype("int64")},
                fetch_list=[loss])
    g.flush()
except guardian.GuardianAbortError as e:
    aborted = str(e)
stats = g.stats()
guardian.uninstall()
assert stats["quarantined"] >= 1, stats
# the sidecar carries the op-level attribution of the poisoned param
sidecars = sorted(glob.glob(os.path.join(out, "quarantine", "*.json")))
assert sidecars, "no quarantine sidecar written"
prov = json.load(open(sidecars[0])).get("provenance")
assert prov and prov["found"], prov
assert prov["out_var"] == "fc_0.tmp_0", prov
assert "fc_0.w_0" in prov["in_vars"], prov
# an abort (skip budget) must carry the per-layer health snapshot
if aborted is not None:
    assert "health" in aborted, aborted
print("HEALTH DRILL OK: %s -> %r (op #%d, layer %s)"
      % (prov["op_type"], prov["out_var"], prov["op_index"],
         prov.get("layer")), flush=True)
monitor.disable()
PY
# the provenance event and the per-layer health records landed in the
# JSONL, and the offline report renders both
grep -ql guardian_nan_provenance "$HEALTH_DIR"/monitor/*.jsonl
grep -ql model_health "$HEALTH_DIR"/monitor/*.jsonl
python tools/health_report.py "$HEALTH_DIR/monitor" \
  | tee "$HEALTH_DIR/report.txt"
grep -q "grad_norm" "$HEALTH_DIR/report.txt"
grep -q "nan provenance" "$HEALTH_DIR/report.txt"

echo "CI OK"
