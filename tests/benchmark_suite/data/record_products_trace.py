"""Records ``products_trace.xplane.pb`` and ``products_trace.op_work.json``
(tests/benchmark_suite/test_bm_products.py) on one TPU v5e chip:

    chiprun -- python3 tests/benchmark_suite/data/record_products_trace.py

A tiny two-layer step with Adam under bf16 AMP, through ``fluid.Executor``:
an embedding plus a fed feature's projection (no dX: a hole), two
feed-forward layers with post-norms, a ``mul`` head with
its bias under ``softmax_with_cross_entropy`` (on a TPU the head's backward
is PR 44's one kernel, ``mul_grad:head_fused``) and a ``matmul`` head tied
to the embedding table (its dW arrives in a ``sum`` with the table's own
gradient).  Three steps are traced after two warm ones; the profile keeps
the first chip's plane and ``/host:metadata`` (the compiled step as ``Hlo
Proto``), the host's lines are dropped (the Python tracer's events are
megabytes), and the step's ``op_work`` is written beside it.  The outputs
land in ``chiprun_out/products_trace/``; copy the two files into this
directory.  ``build`` is what the test rebuilds on the CPU to hold today's
``op_work`` against the recorded one."""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ROWS, SEQ, VOCAB, WIDTH, SIDE, STEPS = 4, 64, 384, 128, 32, 3


def build(fluid):
    """(main, startup, loss) of the tiny program."""
    from paddle_tpu.contrib import mixed_precision

    layers = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tok = layers.data("tok", shape=[SEQ, 1], dtype="int64")
        lbl = layers.data("lbl", shape=[SEQ, 1], dtype="int64")
        h = layers.embedding(tok, size=[VOCAB, WIDTH],
                             param_attr=fluid.ParamAttr(name="table"))
        table = main.global_block().var("table")
        # a fed feature: its projection's gradient has no dX (a hole)
        side = layers.data("side", shape=[SEQ, SIDE], dtype="float32")
        h = layers.elementwise_add(h, layers.fc(
            side, WIDTH, num_flatten_dims=2, bias_attr=False,
            param_attr=fluid.ParamAttr(name="side.w")))
        for i in range(2):
            up = layers.fc(h, 4 * WIDTH, num_flatten_dims=2, act="relu",
                           param_attr=fluid.ParamAttr(name="l%d.up.w" % i),
                           bias_attr=fluid.ParamAttr(name="l%d.up.b" % i))
            down = layers.fc(up, WIDTH, num_flatten_dims=2,
                             param_attr=fluid.ParamAttr(name="l%d.down.w" % i),
                             bias_attr=fluid.ParamAttr(name="l%d.down.b" % i))
            h = layers.layer_norm(layers.elementwise_add(h, down),
                                  begin_norm_axis=2)
        logits = layers.fc(h, VOCAB, num_flatten_dims=2,
                           param_attr=fluid.ParamAttr(name="head.w"),
                           bias_attr=fluid.ParamAttr(name="head.b"))
        loss = layers.elementwise_add(
            layers.mean(layers.softmax_with_cross_entropy(logits, lbl)),
            layers.mean(layers.softmax_with_cross_entropy(
                layers.matmul(h, table, transpose_y=True), lbl)))
        mixed_precision.decorate(
            fluid.optimizer.Adam(learning_rate=1e-3)).minimize(loss)
    return main, startup, loss


def feed(np, step):
    rng = np.random.default_rng(step)
    tok = rng.integers(0, VOCAB, (ROWS, SEQ, 1), dtype=np.int64)
    return {"tok": tok, "lbl": np.roll(tok, -1, axis=1),
            "side": rng.standard_normal((ROWS, SEQ, SIDE), np.float32)}


def main():
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from benchmark.trace import scopes
    from paddle_tpu import compile_cache

    out = os.path.join(ROOT, "chiprun_out", "products_trace")
    os.makedirs(out, exist_ok=True)
    program, startup, loss = build(fluid)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    for step in range(2):
        np.asarray(exe.run(program, feed=feed(np, step),
                           fetch_list=[loss])[0])
    jax.profiler.start_trace(out)
    for step in range(2, 2 + STEPS):
        with jax.profiler.TraceAnnotation("bm/train_step"):
            last = exe.run(program, feed=feed(np, step), fetch_list=[loss],
                           return_numpy=False)
    np.asarray(last[0])
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    space = scopes._xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    keep = [p for p in space.planes
            if p.name in ("/device:TPU:0", "/host:metadata")]
    del space.planes[:]
    space.planes.extend(keep)
    with open(os.path.join(out, "products_trace.xplane.pb"), "wb") as f:
        f.write(space.SerializeToString())
    (rec,) = [r for r in compile_cache.compile_log() if r["op_work"]]
    with open(os.path.join(out, "products_trace.op_work.json"), "w") as f:
        json.dump({"name": rec["name"], "steps": STEPS,
                   "batch_shards": rec["batch_shards"],
                   "kernel_bodies": compile_cache.stats()["kernel_bodies"],
                   "op_work": rec["op_work"]}, f, indent=1)
    print("recorded", os.path.getsize(os.path.join(
        out, "products_trace.xplane.pb")), "bytes;", len(rec["op_work"]),
        "parts;", compile_cache.stats()["kernel_bodies"])


if __name__ == "__main__":
    main()
