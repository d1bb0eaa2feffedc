"""The one switch over the kernels' rules.

Every Pallas kernel is picked by its op's own rule (platform, mesh, shapes),
and every rule goes through ``ops.pallas.kernel_allowed``, which reads
``FLAGS_pallas_kernels`` (default True): set to False — the operator's "no
Pallas", against a kernel that miscompiles on a new runtime — a cached
program lowers again (the flag is in every step's cache key) and each rule
says no.  One case a rule; the CPU is let into the rule's platforms for the
test (interpreted), as each kernel's own tests do."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache, flags
from paddle_tpu.ops import attention, gated_delta_rule, loss as loss_ops, \
    manipulation, moe, pallas, sparse_select, state_space
from paddle_tpu.ops.pallas import head_grad


def _data(name, shape, dtype="float32"):
    return fluid.layers.data(name, shape=list(shape), dtype=dtype,
                             append_batch_size=False)


def _normal(rng, *shape):
    return rng.standard_normal(shape).astype("float32")


def _attention(q_shape, kv_heads, dv):
    def build(rng):
        b, h, t, d = q_shape
        shapes = {"q": q_shape, "k": (b, kv_heads, t, d),
                  "v": (b, kv_heads, t, dv)}
        out = fluid.layers.fused_attention(
            *(_data(n, s) for n, s in shapes.items()), causal=True)
        return {n: _normal(rng, *s) for n, s in shapes.items()}, [out]
    return build


def _select(rng):
    t, heads, dim = 128, 2, 8
    shapes = {"iq": (1, t, heads, dim), "ik": (1, t, dim), "iw": (1, t, heads)}
    words, share = fluid.layers.select_keys(
        *(_data(n, s) for n, s in shapes.items()), 16, scale=dim ** -0.5)
    return {n: _normal(rng, *s) for n, s in shapes.items()}, [words, share]


def _experts(rng):
    x = _data("x", (256, 128))
    out, counts, _ = fluid.layers.routed_experts(x, 4, 2, 128, tile=128)
    return {"x": _normal(rng, 256, 128)}, [out, counts]


def _embedding(rng):
    ids = _data("ids", (48, 1), "int64")
    rows = fluid.layers.embedding(
        ids, size=[64, 128], param_attr=fluid.ParamAttr(name="table"))
    loss = fluid.layers.mean(fluid.layers.square(rows))
    fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)
    return {"ids": rng.integers(0, 64, (48, 1))}, [loss, "table@GRAD"]


def _head(rng):
    from paddle_tpu.contrib import mixed_precision

    n, d, v = 256, 128, 256
    x, label = _data("x", (n, d)), _data("label", (n, 1), "int64")
    # a layer below the head: the chain has the head's dX to make
    logits = fluid.layers.fc(fluid.layers.fc(x, size=d, act="tanh"), size=v,
                             name="head")
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    mixed_precision.decorate(
        fluid.optimizer.SGD(learning_rate=0.0)).minimize(loss)
    return {"x": _normal(rng, n, d),
            "label": rng.integers(0, v, (n, 1))}, [loss, "head.w_0@GRAD"]


def _scan(rng):
    t, e, n = 24, 128, 16
    shapes = {"x": (1, t, e), "dl": (1, t, e), "a": (e, n), "b": (1, t, n),
              "c": (1, t, n), "d": (e,)}
    v = {name: _data(name, s) for name, s in shapes.items()}
    y, state = fluid.layers.selective_scan(
        v["x"], v["dl"], v["a"], v["b"], v["c"], v["d"], chunk=8)
    feed = {name: _normal(rng, *s) for name, s in shapes.items()}
    feed["a"] = -np.abs(feed["a"]) - 0.1
    return feed, [y, state]


def _delta(rng):
    t, h, d = 64, 2, 128
    shapes = {"q": (1, t, h, d), "k": (1, t, h, d), "v": (1, t, h, d),
              "g": (1, t, h, d), "beta": (1, t, h), "gate": (1, t, h, d)}
    v = {name: _data(name, s) for name, s in shapes.items()}
    a_log, dt_bias = (fluid.layers.create_parameter(
        list(shape), "float32", attr=fluid.ParamAttr(name=n))
        for n, shape in (("a_log", (h,)), ("dt_bias", (h, d))))
    out, state = fluid.layers.gated_delta_rule(
        v["q"], v["k"], v["v"], v["g"], v["beta"], a_log, dt_bias, v["gate"],
        d ** -0.5, chunk=32)
    feed = {name: _normal(rng, *s) for name, s in shapes.items()}
    feed["beta"] = 1 / (1 + np.exp(-feed["beta"]))
    return feed, [out, state]


# rule -> (the module and platform tuple its rule reads, program, the note
# with the kernel, the note without)
RULES = {
    "packed": (attention, "_PACKED_PLATFORMS",
               _attention((2, 2, 16, 64), 2, 64),
               "fused_attention:packed", "fused_attention:xla"),
    "streamed": (attention, "_STREAMED_PLATFORMS",
                 _attention((1, 2, 128, 64), 1, 128),
                 "fused_attention:streamed", "fused_attention:xla"),
    "select_topk": (sparse_select, "_KERNEL_PLATFORMS", _select,
                    "select_topk_keys:pallas", "select_topk_keys:xla"),
    "grouped": (moe, "_GROUPED_PLATFORMS", _experts,
                "moe_expert_ffn:grouped", "moe_expert_ffn:loop"),
    "segment": (manipulation, "_SEGMENT_PLATFORMS", _embedding,
                "lookup_table_grad:segment", "lookup_table_grad:xla"),
    "head_fused": (loss_ops, "_HEAD_PLATFORMS", _head,
                   "mul_grad:head_fused", "mul_grad:head_by_op"),
    "chunked": (state_space, "_KERNEL_PLATFORMS", _scan,
                "selective_scan:chunked", "selective_scan:xla"),
    "chunked_delta": (gated_delta_rule, "_KERNEL_PLATFORMS", _delta,
                      "gated_delta_rule:chunked", "gated_delta_rule:xla"),
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_flag_off_relowers_a_cached_program_without_the_kernel(
        rule, monkeypatch, request):
    module, platforms, build, with_kernel, without = RULES[rule]
    monkeypatch.setattr(module, platforms, ("tpu", "cpu"))
    # tiles of 128 x 128: the interpreter's grid has more than one step
    monkeypatch.setattr(head_grad, "_MAX_ROWS", 128)
    monkeypatch.setattr(head_grad, "_MAX_COLS", 128)
    pallas.traced.cache_clear()
    compile_cache.clear()
    assert flags.flag("pallas_kernels") is True            # the default
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feed, fetch = build(np.random.default_rng(0))

    def noted():
        bodies = compile_cache.stats()["kernel_bodies"]
        return bodies.get(with_kernel, 0), bodies.get(without, 0)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        before = noted()
        first = exe.run(main, feed=feed, fetch_list=fetch)
        taken = noted()
        assert taken[0] > before[0] and taken[1] == before[1]
        held = len(exe._cache)
        exe.run(main, feed=feed, fetch_list=fetch)
        assert len(exe._cache) == held and noted() == taken    # cached
        request.getfixturevalue("no_pallas")     # set_flags, until the end
        second = exe.run(main, feed=feed, fetch_list=fetch)
        off = noted()
        assert len(exe._cache) == held + 1                     # lowered again
        assert off[0] == taken[0] and off[1] > taken[1]
    # the same step by the other body (learning rate 0 where it trains)
    for a, b in zip(first, second):
        a, b = np.asarray(a, "float32"), np.asarray(b, "float32")
        assert np.abs(a - b).max() <= 2e-2 * max(np.abs(b).max(), 1e-6)
    pallas.traced.cache_clear()
    compile_cache.clear()
