"""The one step path under both executors (ISSUE 28): ``executor.StepPath``
runs the step, ``Executor`` and ``ParallelExecutor`` only place it.  One
test, parametrised over the two placements: the order of a step's effects
with every plane on, and the drift between the two former copies that the
shared path repaired."""

import io
import os
import tokenize

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import executor, fault, guardian, monitor
from paddle_tpu.monitor import health, program_profile
from paddle_tpu.scope import Scope

KINDS = ("executor", "parallel_executor")
SRC = os.path.dirname(os.path.abspath(executor.__file__))


@pytest.fixture(autouse=True)
def _clean():
    yield
    fault.clear()
    fault.clear_injections()
    guardian.uninstall()
    fluid.set_flags({"FLAGS_health": False, "FLAGS_health_every": 10,
                     "FLAGS_guardian": False, "FLAGS_check_nan_inf": False})
    monitor.disable()
    monitor.registry().reset()
    monitor.step_stats().reset()


def _build():
    """A small classifier with an int64-declared feed and two per-step
    counters declared as ``Program.step_stats``."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=16, act="relu")
        pred = fluid.layers.fc(h, size=4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(learning_rate=1e-2).minimize(loss)
        stats = fluid.layers.concat(
            [fluid.layers.mean(x), fluid.layers.mean(h)], axis=0)
        stats.stop_gradient = True
    main.step_stats = (stats.name, ("x_mean", "h_mean"))
    return main, startup, loss, pred, stats


def _placed(kind, main, startup, loss):
    """``(the executor, run(feed, fetch_list, **kw))`` on a fresh scope."""
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    if kind == "executor":
        exe = fluid.Executor(fluid.CPUPlace())
        return exe, lambda feed, fetch, **kw: exe.run(
            main, feed=feed, fetch_list=fetch, scope=scope, **kw)
    exe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                 scope=scope)
    assert exe.device_count == 8
    return exe, lambda feed, fetch, **kw: exe.run(fetch, feed=feed, **kw)


def _feed(batch):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(batch, 8).astype("float32"),
            "label": rng.randint(0, 4, (batch, 1)).astype("int64")}


def _order_of_effects(kind, monkeypatch):
    """Every plane on (monitor, guardian + skip guard, health probe, fault
    drills, check_nan_inf): a step's effects come in the one order the
    guardian's, the drills' and exact resume's contracts were written
    against, under either placement."""
    log = []

    def note(what):
        if not log or log[-1] != what:      # per-variable repeats are one
            log.append(what)

    def spy(owner, attr, what):
        real = getattr(owner, attr)

        def wrapper(*a, **kw):
            note(what)
            return real(*a, **kw)
        monkeypatch.setattr(owner, attr, wrapper)

    class Span(executor.RecordEvent):
        def __enter__(self):
            note("span:" + self.name)
            return super().__enter__()

    fluid.set_flags({"FLAGS_guardian": True, "FLAGS_health": True,
                     "FLAGS_health_every": 1, "FLAGS_check_nan_inf": True})
    monitor.enable()
    g = guardian.Guardian()
    guardian.install(g)
    main, startup, loss, pred, _ = _build()
    exe, run = _placed(kind, main, startup, loss)
    counter_at = {}
    for point in ("executor/feed", "executor/dispatch", "executor/step_done"):
        def drill(step, _point=point, **ctx):
            counter_at[_point] = exe._run_counter
            note("drill:" + _point)
        fault.register(point, drill, fault.FaultSchedule(every=1))

    monkeypatch.setattr(executor, "RecordEvent", Span)
    spy(jax.random, "fold_in", "fold_in")
    spy(program_profile, "capture", "capture")
    spy(health, "note_step", "health.note_step")
    spy(Scope, "set_var", "writeback")
    spy(executor, "_check_finite", "nan_check")
    spy(monitor, "record_step", "record_step")
    spy(g, "note_step", "guardian.note_step")
    spy(exe._dispatch_queue, "push_step", "queue")
    trims = hasattr(exe, "_trim_fetches")
    if trims:
        spy(exe, "_trim_fetches", "trim")

    tail = ["health.note_step", "writeback", "drill:executor/step_done"] \
        + (["trim"] if trims else []) + ["nan_check"]
    head = ["span:%s/step" % kind, "drill:executor/feed"]
    put = ["span:%s/h2d_transfer" % kind, "fold_in",
           "drill:executor/dispatch", "span:%s/run" % kind]
    end = ["record_step", "guardian.note_step"]

    # 9 rows on 8 devices: a mesh pads the batch and trims the fetches
    feed = _feed(9)
    (lv, pv) = run(feed, [loss, pred])
    assert pv.shape[0] == 9 and np.isfinite(lv).all()
    cold = [e for e in log if e in set(
        head + put + tail + end + ["capture", "span:%s/compile" % kind,
                                   "span:%s/trace" % kind,
                                   "span:%s/fetch_sync" % kind])]
    assert cold == head + ["span:%s/compile" % kind, "span:%s/trace" % kind] \
        + put + ["span:%s/compile" % kind, "capture"] + tail \
        + ["span:%s/fetch_sync" % kind] + end
    # the key folds in the counter before the step bumps it, and the
    # dispatch drill already sees the bump
    assert counter_at == {"executor/feed": 0, "executor/dispatch": 1,
                          "executor/step_done": 1}
    assert exe.state_dict()["run_counter"] == 1

    del log[:]
    run(feed, [loss, pred])
    assert log == head + put + ["span:%s/dispatch" % kind] + tail \
        + ["span:%s/fetch_sync" % kind] + end

    del log[:]
    run(feed, [loss, pred], return_numpy=False)
    assert log == head + put + ["span:%s/dispatch" % kind] + tail \
        + ["queue"] + end
    exe.sync()


def _step_stats_reach_the_record(kind, monkeypatch):
    """``Program.step_stats`` fetched with the loss lands in the step
    record under either placement (PR 26 wired one copy only)."""
    records = []
    real = monitor.record_step
    monkeypatch.setattr(monitor, "record_step",
                        lambda *a, **kw: records.append(real(*a, **kw)))
    main, startup, loss, _, stats = _build()
    _, run = _placed(kind, main, startup, loss)
    monitor.enable()
    _, sv = run(_feed(8), [loss, stats])
    run(_feed(8), [loss])
    assert [r["executor"] for r in records] == [kind, kind]
    assert [records[0][n] for n in ("x_mean", "h_mean")] == sv.tolist()
    assert not {"x_mean", "h_mean"} & set(records[1])


def _feed_keeps_the_materialized_dtype(kind, monkeypatch):
    """An int64-declared feed is coerced to the dtype the device will
    hold (int32 with x64 off), not to the declared one, so the feed
    signature names what the executable was compiled for."""
    main, startup, loss, _, _ = _build()
    exe, run = _placed(kind, main, startup, loss)
    run(_feed(8), [loss])
    (compiled,) = exe._cache.values()
    (sig,) = compiled.seen_sigs
    want = str(np.dtype(fluid.core.materialize_dtype("int64")))
    assert want == "int32"
    assert {n: d for n, _, d in sig} == {"x": "float32", "label": want}
    assert (sig, jax.devices()[0].id) not in compiled.aot  # capture is off


def _code(path):
    """The file's code with comments and strings taken out."""
    with open(os.path.join(SRC, path)) as f:
        toks = tokenize.generate_tokens(io.StringIO(f.read()).readline)
        return " ".join(t.string for t in toks if t.type not in (
            tokenize.COMMENT, tokenize.STRING, tokenize.NL, tokenize.NEWLINE,
            tokenize.INDENT, tokenize.DEDENT)).replace(" ", "")


def _one_call_site(kind, monkeypatch):
    """Every hook of the step path is called from ``executor.py`` alone,
    once; the mesh's file imports the step path and never the reverse."""
    shared, mesh = _code("executor.py"), _code("parallel/parallel_executor.py")
    for call, n in (("wrap_step_guard(", 1), ("wrap_step_probe(", 1),
                    ("build_probe(", 1), ("program_profile.capture(", 1),
                    ("health.note_step(", 1), ("monitor.record_step(", 1),
                    ("warn_unobserved_skip_guard(", 1), ("fault.fire(", 3),
                    ("jax.random.fold_in(", 1), ("jax.jit(", 1),
                    ("trace_program(", 2)):     # its definition + the call
        assert (shared.count(call), mesh.count(call)) == (n, 0), call
    assert "Executor.__new__" not in shared + mesh
    assert "from..executorimportStepPath" in mesh
    assert "parallel_executor" not in shared and "from.parallel" not in shared


CASES = {"order_of_effects": _order_of_effects,
         "step_stats": _step_stats_reach_the_record,
         "feed_dtype": _feed_keeps_the_materialized_dtype,
         "one_call_site": _one_call_site}


@pytest.mark.parametrize(
    "case,kind",
    [(c, k) for c in CASES if c != "one_call_site" for k in KINDS]
    + [("one_call_site", None)],
    ids=lambda v: str(v))
def test_step_path(case, kind, monkeypatch):
    CASES[case](kind, monkeypatch)
