"""Step-overlap pipeline: async dispatch, device prefetch, compile cache.

The contract under test (ISSUE 1 acceptance): the overlapped path —
``DevicePrefetcher`` staging feeds ahead + ``return_numpy=False`` with a
bounded dispatch window — must be *bit-identical* in loss trajectory to
the fully synchronous path, the prefetcher must drain cleanly on early
shutdown and surface producer exceptions after the good batches, and a
second executor over the same program+signature must perform zero new
lowerings (process-global trace cache).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu.reader import DevicePrefetcher


def _mlp_program(seed=7):
    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        img = fluid.layers.data("img", shape=[8])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(img, size=8, act="relu")
        pred = fluid.layers.fc(h, size=4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog.random_seed = seed
    sprog.random_seed = seed
    return prog, sprog, loss


def _feeds(n, batch=4):
    rng = np.random.RandomState(0)
    return [{"img": rng.rand(batch, 8).astype("float32"),
             "label": rng.randint(0, 4, (batch, 1)).astype("int64")}
            for _ in range(n)]


# ---------------------------------------------------------------------------
# loss-trajectory parity
# ---------------------------------------------------------------------------

def test_overlap_loss_parity_bit_identical():
    """Seeded program run synchronously vs through the full overlapped
    pipeline (prefetcher + async dispatch window) produces bit-identical
    per-step losses: overlap must never change numerics."""
    prog, sprog, loss = _mlp_program()
    feeds = _feeds(6)

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sprog)
        sync_losses = [
            exe.run(prog, feed=f, fetch_list=[loss])[0].item()
            for f in feeds
        ]

    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(sprog)
        handles = []
        with DevicePrefetcher(iter(feeds), place=fluid.CPUPlace(),
                              capacity=2) as pf:
            for f in pf:
                handles.append(exe2.run(prog, feed=f, fetch_list=[loss],
                                        return_numpy=False))
        exe2.sync()
        overlap_losses = [np.asarray(h[0]).item() for h in handles]

    assert sync_losses == overlap_losses


def test_async_dispatch_window_bounds_inflight():
    """The dispatch window never holds more than max_inflight steps and
    drain() empties it."""
    from paddle_tpu.executor import AsyncDispatchQueue

    q = AsyncDispatchQueue(max_inflight=3)
    for i in range(10):
        q.push([np.float32(i)])
        assert len(q) <= 3
    q.drain()
    assert len(q) == 0


def test_async_dispatch_window_skips_donated_buffers():
    """A window entry whose buffers were donated away by a later step
    (fetch-less steps push new_state; donate_argnums reuses it) must be
    skipped, not block_until_ready-ed into 'Array has been deleted'."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.executor import AsyncDispatchQueue

    q = AsyncDispatchQueue(max_inflight=4)
    a = jnp.arange(4.0)
    jax.block_until_ready(a)
    a.delete()                           # what donation does on TPU
    q.push([a])
    q.push([jnp.arange(2.0)])
    q.drain()                            # must not raise
    assert len(q) == 0
    # an all-donated oldest entry must still produce a real bound:
    # _sync_oldest falls through to the oldest live leaf of a younger
    # in-flight step rather than skipping the sync outright
    b, c = jnp.arange(3.0), jnp.arange(5.0)
    jax.block_until_ready([b, c])
    b.delete()
    q.push([b])
    q.push([c])
    assert q._live_leaves([b]) == []
    q._sync_oldest()                     # pops [b], blocks via [c]
    assert len(q) == 1
    q.drain()


def test_async_dispatch_empty_fetch_list():
    """return_numpy=False with an empty fetch_list still bounds and
    drains the window (handles are the donated new_state)."""
    prog, sprog, loss = _mlp_program(seed=19)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sprog)
        for f in _feeds(12):             # > FLAGS_max_inflight_steps
            exe.run(prog, feed=f, fetch_list=[], return_numpy=False)
        # the window holds tiny derived tokens, not the donated
        # new_state buffers themselves (which the next step deletes on
        # real accelerators) — so the bound survives donation
        assert exe._dispatch_queue._inflight[-1][0].size == 1
        exe.sync()
        assert len(exe._dispatch_queue) == 0


def test_executor_sync_retires_inflight():
    prog, sprog, loss = _mlp_program()
    feeds = _feeds(4)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sprog)
        for f in feeds:
            exe.run(prog, feed=f, fetch_list=[loss], return_numpy=False)
        assert len(exe._dispatch_queue) > 0
        exe.sync()
        assert len(exe._dispatch_queue) == 0


# ---------------------------------------------------------------------------
# prefetcher lifecycle
# ---------------------------------------------------------------------------

def test_prefetcher_exception_after_good_batches():
    """A producer exception surfaces at the consumer AFTER every
    already-produced batch — not as a silent end-of-data, not before the
    good batches."""
    def source():
        yield {"x": np.zeros(2, "float32")}
        yield {"x": np.ones(2, "float32")}
        raise RuntimeError("decode failed")

    pf = DevicePrefetcher(source, capacity=4)
    it = iter(pf)
    got = [next(it), next(it)]
    assert [g["x"][0] for g in got] == [0.0, 1.0]
    with pytest.raises(RuntimeError, match="decode failed"):
        next(it)


def test_prefetcher_close_midstream_joins_producer():
    """close() while the producer is blocked on a full queue stops and
    joins the thread (no daemon-thread leak, no hang)."""
    def source():
        for i in range(1000):
            yield {"x": np.full(2, i, "float32")}

    pf = DevicePrefetcher(source, capacity=1)
    it = iter(pf)
    first = next(it)
    assert first["x"][0] == 0.0
    time.sleep(0.05)           # let the producer block on the full queue
    pf.close()
    assert not pf._thread.is_alive()
    # close is idempotent
    pf.close()


def test_prefetcher_context_manager_abandoned_iteration():
    consumed = []
    with DevicePrefetcher(iter(_feeds(50)), capacity=2) as pf:
        for f in pf:
            consumed.append(f)
            if len(consumed) == 3:
                break
    assert len(consumed) == 3
    assert not pf._thread.is_alive()


def test_prefetcher_abandoned_iterator_stops_producer():
    """Dropping the iterator (the facades keep no other handle) stops
    the producer thread via GeneratorExit — no busy-polling leak."""
    pf = DevicePrefetcher(iter(_feeds(1000)), capacity=1)
    it = iter(pf)
    next(it)
    it.close()
    assert not pf._thread.is_alive()


def test_prefetcher_partial_shardings_dict_still_stages_rest():
    """Feeds missing from a partial shardings dict fall back to plain
    device placement instead of silently staying host arrays."""
    import jax
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(jax.devices("cpu")[0])
    feeds = [{"img": np.zeros((2, 4), "float32"),
              "label": np.zeros((2, 1), "int64")}]
    with DevicePrefetcher(iter(feeds), place=fluid.CPUPlace(),
                          shardings={"img": sh}) as pf:
        out = next(iter(pf))
    assert isinstance(out["img"], jax.Array)
    assert isinstance(out["label"], jax.Array)   # the unlisted feed


def test_prefetcher_reiterable_with_callable_source():
    """A callable source makes the prefetcher re-iterable (the PyReader
    multi-epoch contract): each epoch sees the full fresh stream."""
    def source():
        return iter(_feeds(4))

    with DevicePrefetcher(source, capacity=2) as pf:
        epochs = [len(list(pf)), len(list(pf))]
    assert epochs == [4, 4]
    assert not pf._thread.is_alive()


def test_prefetcher_fresh_iter_supersedes_live_stream():
    """iter() over a live stream (callable source) restarts from the
    top — the fresh epoch never shares the half-consumed stream, and a
    stale superseded iterator can neither steal its batches nor kill it
    when dropped/GC'd."""
    import gc

    def source():
        return iter(_feeds(5))

    pf = DevicePrefetcher(source, capacity=2)
    it1 = iter(pf)
    first = next(it1)
    epoch2 = [f for f in pf]            # fresh iter() mid-stream
    assert len(epoch2) == 5
    assert np.array_equal(epoch2[0]["img"], first["img"])  # from the top
    del it1
    gc.collect()                         # stale iterator GC: no effect
    assert len(list(pf)) == 5
    pf.close()


def test_prefetcher_enter_is_lazy_no_batch_loss():
    """__enter__ must not pre-start a producer the first iter() then
    restarts: a callable source over a shared underlying stream sees
    every batch exactly once."""
    stream = iter(_feeds(5))
    with DevicePrefetcher(lambda: stream, capacity=2) as pf:
        got = list(pf)
    assert len(got) == 5


def test_prefetcher_second_live_iter_over_plain_iterator_raises():
    """A second iter() while a plain-iterator epoch is live raises
    instead of silently competing for (and truncating) the stream."""
    pf = DevicePrefetcher(iter(_feeds(5)), capacity=2)
    it1 = iter(pf)
    next(it1)
    with pytest.raises(RuntimeError, match="active iterator"):
        iter(pf)
    pf.close()


def test_prefetcher_exhausted_iterator_raises():
    """Re-iterating over a consumed one-shot-iterator source raises
    instead of silently yielding an empty epoch."""
    pf = DevicePrefetcher(iter(_feeds(2)), capacity=2)
    assert len(list(pf)) == 2
    with pytest.raises(RuntimeError, match="exhausted"):
        iter(pf)


def test_prefetcher_reiterable_with_list_source():
    """A re-iterable container source (list of feed dicts) supports
    multi-epoch iteration like a reader creator."""
    pf = DevicePrefetcher(_feeds(3), capacity=2)
    assert [len(list(pf)) for _ in range(3)] == [3, 3, 3]
    pf.close()


def test_prefetcher_two_unadvanced_iters_do_not_share_epoch():
    """A second iter() before the first is ever advanced must supersede
    (callable source) or raise (one-shot iterator) — never silently
    hand out two consumers over one epoch's queue."""
    import gc

    pf = DevicePrefetcher(lambda: iter(_feeds(6)), capacity=2)
    it1 = iter(pf)
    it2 = iter(pf)                   # supersedes it1 pre-advance
    assert len(list(it2)) == 6       # full epoch, nothing stolen
    assert list(it1) == []           # superseded: cleanly empty
    pf.close()

    pf2 = DevicePrefetcher(iter(_feeds(3)), capacity=2)
    it1 = iter(pf2)
    with pytest.raises(RuntimeError, match="active iterator"):
        iter(pf2)
    del it1
    gc.collect()                     # a dropped unadvanced consumer...
    assert len(list(pf2)) == 3       # ...doesn't block recovery


def test_prefetcher_unadvanced_iterator_leaks_no_thread():
    """iter() alone must not spawn a producer: a created-but-never-
    advanced generator's finally never runs, so an eager thread would
    leak (busy-polling, pinning staged batches) for the process life."""
    import gc

    pf = DevicePrefetcher(iter(_feeds(50)), capacity=1)
    it = iter(pf)
    assert pf._thread is None        # producer starts on first next()
    del it
    gc.collect()
    assert pf._thread is None
    assert len(list(pf)) == 50       # still consumable afterwards


def test_prefetcher_threads_do_not_leak():
    before = threading.active_count()
    for _ in range(5):
        with DevicePrefetcher(iter(_feeds(10)), capacity=2) as pf:
            next(iter(pf))
    assert threading.active_count() <= before + 1


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_second_executor_zero_lowerings():
    """A fresh Executor over the same program+signature reuses the
    process-global trace cache: zero new lowerings on the second run."""
    prog, sprog, loss = _mlp_program()
    feeds = _feeds(2)

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sprog)
        exe.run(prog, feed=feeds[0], fetch_list=[loss])
    baseline = compile_cache.stats()

    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(sprog)
        exe2.run(prog, feed=feeds[1], fetch_list=[loss])
    after = compile_cache.stats()

    assert after["lowerings"] == baseline["lowerings"]
    assert after["trace_hits"] >= baseline["trace_hits"] + 2

    # structural mutation invalidates the fingerprint: appending an op
    # must NOT serve the stale trace
    fp_before = compile_cache.program_fingerprint(prog)
    with fluid.program_guard(prog, sprog):
        fluid.layers.scale(loss, scale=2.0)
    assert compile_cache.program_fingerprint(prog) != fp_before


def test_parallel_executor_return_numpy_false_async():
    """ParallelExecutor honors return_numpy=False: device arrays come
    back without a per-step sync, and the values match the numpy path."""
    import jax

    prog, sprog, loss = _mlp_program()
    feeds = _feeds(3)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sprog)
        pe = fluid.ParallelExecutor(use_cuda=False, main_program=prog,
                                    loss_name=loss.name)
        dev_losses = []
        for f in feeds:
            out = pe.run(feed=f, fetch_list=[loss], return_numpy=False)
            assert isinstance(out[0], jax.Array)
            dev_losses.append(out[0])
        pe.sync()
        np_vals = [np.asarray(d).item() for d in dev_losses]

    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sprog)
        pe2 = fluid.ParallelExecutor(use_cuda=False, main_program=prog,
                                     loss_name=loss.name)
        ref = [pe2.run(feed=f, fetch_list=[loss])[0].item() for f in feeds]

    assert np_vals == ref


def test_parallel_executor_check_nan_inf_keeps_device_arrays():
    """FLAGS_check_nan_inf adds a per-step sync but must not change the
    return_numpy=False type contract: fetches stay jax Arrays."""
    import jax

    prog, sprog, loss = _mlp_program(seed=17)
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(sprog)
            pe = fluid.ParallelExecutor(use_cuda=False, main_program=prog,
                                        loss_name=loss.name)
            out = pe.run(feed=_feeds(1)[0], fetch_list=[loss],
                         return_numpy=False)
            assert isinstance(out[0], jax.Array)
            pe.sync()
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


def test_persistent_cache_dir_populated(tmp_path):
    """FLAGS_compile_cache_dir points jax's on-disk executable cache at
    the directory; a compile writes at least one entry."""
    cache_dir = str(tmp_path / "xla_cache")
    fluid.set_flags({"FLAGS_compile_cache_dir": cache_dir})
    try:
        prog, sprog, loss = _mlp_program(seed=11)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(sprog)
            exe.run(prog, feed=_feeds(1)[0], fetch_list=[loss])
        entries = []
        for root, _, files in os.walk(cache_dir):
            entries.extend(files)
        assert entries, "persistent compilation cache wrote no entries"
    finally:
        fluid.set_flags({"FLAGS_compile_cache_dir": ""})


# ---------------------------------------------------------------------------
# profiler observability
# ---------------------------------------------------------------------------

def test_profiler_records_pipeline_spans():
    """h2d_transfer / dispatch / fetch_sync / compile spans are visible in
    the captured events, with the cold call's inside as jax reported it;
    whether the trace cache had the step is the compile record's."""
    from paddle_tpu import compile_cache, profiler

    prog, sprog, loss = _mlp_program(seed=13)
    feeds = _feeds(3)
    profiler.reset_profiler()
    profiler.start_profiler()
    try:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(sprog)
            exe.run(prog, feed=feeds[0], fetch_list=[loss])          # compile
            exe.run(prog, feed=feeds[1], fetch_list=[loss])          # dispatch
            exe.run(prog, feed=feeds[2], fetch_list=[loss],
                    return_numpy=False)
            exe.sync()                                               # window
        names = {e["name"] for e in profiler._events}
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    for expected in ("executor/h2d_transfer", "executor/compile",
                     "executor/dispatch", "executor/fetch_sync",
                     "executor/jax_trace", "executor/mlir_lowering",
                     "executor/executable"):
        assert expected in names, (expected, sorted(names))
    assert not {n for n in names if n.startswith("compile_cache/")}
    assert {r["trace_cache"] for r in compile_cache.compile_log()[-2:]} \
        <= {"hit", "miss"}          # the startup program's, then the step's


# ---------------------------------------------------------------------------
# bench ladder smoke (slow: excluded from the tier-1 gate)
# ---------------------------------------------------------------------------

BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


@pytest.mark.slow
def test_bench_smoke_ladder(tmp_path):
    """`bench.py --smoke` exercises the real ladder machinery (subprocess
    rungs, budget gate, partial-artifact emit) in ~30s: exit 0, valid
    JSON lines, final line ladder_complete, artifact file written."""
    out = str(tmp_path / "BENCH_smoke.json")
    cache_dir = str(tmp_path / "xla_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, BENCH, "--smoke", "--device", "cpu",
         "--budget-seconds", "240", "--out", out,
         "--compile_cache_dir", cache_dir],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=420, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    # rung subprocesses inherit the persistent cache dir via the env: a
    # second invocation starts warm (the VERDICT r4 wall-clock lever)
    cached = [f for _, _, fs in os.walk(cache_dir) for f in fs]
    assert cached, "ladder rungs wrote no persistent-cache entries"
    lines = [l for l in res.stdout.strip().splitlines() if l.startswith("{")]
    assert lines, res.stdout
    final = json.loads(lines[-1])
    assert final["ladder_complete"] is True
    assert final["metric"].startswith("mnist_mlp")
    assert final["value"] > 0
    # one per-rung reprint + the final line
    assert len(lines) >= 2
    with open(out) as f:
        assert json.load(f)["ladder_complete"] is True


@pytest.mark.slow
def test_bench_budget_skips_rungs_exit_zero(tmp_path):
    """An exhausted --budget-seconds records remaining rungs as omitted
    and still exits 0 with a valid artifact (the rc=124 fix)."""
    out = str(tmp_path / "BENCH_budget.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, BENCH, "--smoke", "--device", "cpu",
         "--budget-seconds", "1", "--out", out],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    final = json.loads(res.stdout.strip().splitlines()[-1])
    assert final["ladder_complete"] is True
    assert len(final.get("omitted", [])) == 2
