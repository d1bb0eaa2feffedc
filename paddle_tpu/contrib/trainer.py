"""High-level Trainer (reference ``python/paddle/fluid/contrib/trainer.py``:
Trainer:169 — build programs from train_func, optimizer_func; event-driven
train loop; CheckpointConfig:100 periodic save + auto-resume; cluster role
wiring via PADDLE_TRAINING_ROLE env).

TPU redesign notes: the executor is the whole-program jit Executor (or the
mesh ParallelExecutor with ``parallel=True``); the pserver training role
is subsumed by mesh sharding, so PADDLE_TRAINING_ROLE=PSERVER raises with
guidance instead of transpiling (SURVEY.md §2.4)."""

import os
import warnings

import numpy as np

from .. import flags as _flags
from .. import guardian as _guardian
from .. import io as fluid_io
from .. import monitor
from .. import unique_name
from ..data_feeder import DataFeeder
from ..executor import Executor, default_place
from ..framework import Program, default_main_program, \
    default_startup_program, program_guard
from ..optimizer import Optimizer
from ..parallel import ParallelExecutor
from ..profiler import RecordEvent
from ..scope import Scope, scope_guard

__all__ = [
    "Trainer", "CheckpointConfig",
    "BeginEpochEvent", "EndEpochEvent", "BeginStepEvent", "EndStepEvent",
]


class BeginEpochEvent:
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class EndEpochEvent:
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class BeginStepEvent:
    def __init__(self, epoch_id, step_id):
        self.epoch = epoch_id
        self.step = step_id
        self.fetch_metrics = True


class EndStepEvent:
    def __init__(self, epoch_id, step_id, metrics):
        self.epoch = epoch_id
        self.step = step_id
        self.metrics = metrics


class CheckpointConfig:
    """reference contrib/trainer.py:100 — grown into the exact-resume
    config: checkpoints are full ``TrainState`` artifacts (params +
    optimizer slots + LR/step counters + executor PRNG counters +
    reader position), written asynchronously under compute
    (``async_save``) and committed atomically with checksum manifests
    (``parallel.checkpoint.TrainStateCheckpointManager``).
    ``step_interval`` counts GLOBAL steps across epochs."""

    def __init__(self, checkpoint_dir=None, max_num_checkpoints=3,
                 epoch_interval=1, step_interval=None, async_save=True,
                 incremental=None, incremental_full_every=8):
        self.checkpoint_dir = checkpoint_dir or os.path.join(
            os.getcwd(), "checkpoints")
        self.max_num_checkpoints = max_num_checkpoints
        self.epoch_interval = max(int(epoch_interval), 1)
        # incremental table checkpoints (Check-N-Run): 'auto'/True delta-
        # encodes every is_sparse lookup table + its row-wise optimizer
        # slots; or pass an explicit var-name list.  See
        # TrainStateCheckpointManager(incremental=...).
        self.incremental = incremental
        self.incremental_full_every = int(incremental_full_every)
        # an EXPLICIT step_interval is a pin: the auto-tuner's
        # checkpoint-interval decision (Trainer(autotune=...)) never
        # overrides a cadence the user chose; None takes the historical
        # default of 10 and stays tunable
        self.step_interval_pinned = step_interval is not None
        self.step_interval = max(int(step_interval), 1) \
            if step_interval is not None else 10
        self.async_save = bool(async_save)
        self.epoch_id = 0
        self.step_id = 0
        # the restored global-step index after an auto-resume (kept
        # under the reference's name: scripts test it for truthiness)
        self.load_serial = None


class Trainer:
    """reference contrib/trainer.py:169.

    ``train_func`` builds the model and returns the loss Variable (or a
    list whose first element is the loss); ``optimizer_func`` returns an
    Optimizer.
    """

    def __init__(self, train_func, optimizer_func, param_path=None,
                 place=None, parallel=False, checkpoint_config=None,
                 mesh=None, guardian_config=None, autotune=None,
                 cluster_member=None):
        """``guardian_config``: the recovery policy — a ``Guardian``
        instance, or a kwargs dict for ``guardian.Guardian`` (policy
        ladder, window, budgets...).  Passing one turns the guardian on
        (``FLAGS_guardian``) for the duration of ``train()``; with the
        flag already set the Trainer wires a default Guardian in by
        itself, so a flag-enabled run is guarded with no code
        changes.

        ``autotune``: a ``paddle_tpu.autotune.TunedConfig`` (or a path
        to its JSON artifact).  Flag-backed decisions apply through
        ``TunedConfig.apply`` (pinned flags win); a tuned
        ``checkpoint_interval`` re-gates the checkpoint manager unless
        the user pinned ``CheckpointConfig(step_interval=...)``
        explicitly.

        ``cluster_member``: a ``paddle_tpu.cluster.ClusterMember`` — the
        host's session against a ClusterMaster.  With one, multi-host
        sharded checkpoint commits go through the master's saver
        election, and — when a guardian is enabled (``FLAGS_guardian``
        or ``guardian_config``) — verdicts are cluster-arbitrated
        (``ClusterGuardian``: one host's rollback wins cluster-wide).
        A plain ``Guardian`` INSTANCE as ``guardian_config`` conflicts
        with that promise and raises; pass a kwargs dict or a
        ``ClusterGuardian``."""
        self.__stop = False
        self.parallel = parallel
        self.place = default_place(place)
        self._mesh = mesh
        self._guardian_config = guardian_config
        self._cluster_member = cluster_member
        self._set_guardian_flag = False
        self._current_epoch = 0

        if checkpoint_config is not None and not isinstance(
                checkpoint_config, CheckpointConfig):
            raise TypeError(
                "checkpoint_config must be a CheckpointConfig instance")
        self.checkpoint_cfg = checkpoint_config

        self.scope = Scope()
        self.startup_program = Program()
        self.train_program = Program()

        # fresh name generator so parameter names (fc_0.w_0, ...) are
        # reproducible regardless of what this process built before —
        # Inferencer rebuilds the net under the same guard and must get
        # identical names to match the saved files
        with unique_name.guard(), \
                program_guard(self.train_program, self.startup_program):
            program_func_outs = train_func()
            self.train_func_outputs = (
                program_func_outs if isinstance(program_func_outs, list)
                else [program_func_outs])
            # test program: forward only, before optimizer ops
            self.test_program = self.train_program.clone(for_test=True)
            if not isinstance(optimizer_func, type(lambda: None)) and \
                    not callable(optimizer_func):
                raise TypeError("optimizer_func must be callable")
            optimizer = optimizer_func()
            if not isinstance(optimizer, Optimizer):
                raise TypeError(
                    "optimizer_func must return a paddle_tpu Optimizer")
            loss = self.train_func_outputs[0]
            optimizer.minimize(loss)
        self._loss_name = loss.name

        self._dist_transpile_if_necessary()

        with scope_guard(self.scope):
            exe = Executor(self.place)
            exe.run(self.startup_program)

        if param_path is not None:
            with scope_guard(self.scope):
                fluid_io.load_persistables(
                    Executor(self.place), param_path,
                    main_program=self.startup_program)

        self._autotune = None
        if autotune is not None:
            from .. import autotune as _at

            self._autotune = autotune if isinstance(
                autotune, _at.TunedConfig) else _at.TunedConfig.load(
                autotune)
            # pinned flags win inside apply()
            self._autotune.apply()
            interval = self._autotune.value("checkpoint_interval")
            if interval and self.checkpoint_cfg is not None:
                if self.checkpoint_cfg.step_interval_pinned:
                    monitor.log_event({
                        "event": "autotune_applied",
                        "knob": "checkpoint_interval",
                        "outcome": "pinned",
                        "pinned_interval":
                            self.checkpoint_cfg.step_interval})
                else:
                    self.checkpoint_cfg.step_interval = max(
                        1, int(interval))
                    monitor.log_event({
                        "event": "autotune_applied",
                        "knob": "checkpoint_interval",
                        "outcome": "applied",
                        "interval": self.checkpoint_cfg.step_interval})

        self._ckpt_mgr = None
        self._global_step = 0
        self._resume_epoch = 0
        self._pending_resume = None
        if self.checkpoint_cfg is not None:
            from ..parallel.checkpoint import TrainStateCheckpointManager

            cfg = self.checkpoint_cfg
            member = self._cluster_member
            self._ckpt_mgr = TrainStateCheckpointManager(
                cfg.checkpoint_dir,
                max_to_keep=cfg.max_num_checkpoints,
                save_interval_steps=cfg.step_interval,
                async_save=cfg.async_save,
                incremental=getattr(cfg, "incremental", None),
                incremental_full_every=getattr(
                    cfg, "incremental_full_every", 8),
                # cluster runs elect exactly one manifest committer per
                # step through the master (sharded-mode saves only)
                saver_elect=member.request_save
                if member is not None else None)
            with scope_guard(self.scope):
                restored = self._ckpt_mgr.restore(
                    scope=self.scope, program=self.train_program)
            if restored is not None:
                cfg.load_serial = restored
                self._global_step = restored
                # consumed (once) by train()'s _apply_resume_state
                self._pending_resume = self._ckpt_mgr.last_restored.host
                self._resume_epoch = int(
                    self._pending_resume.get("extra", {}).get("epoch", 0))
            else:
                # a dir holding only the PREVIOUS Trainer's serial-based
                # format must not be silently abandoned: resume its
                # persistables (params-only legacy semantics) and say so
                serial = fluid_io.get_latest_checkpoint_serial(
                    cfg.checkpoint_dir)
                if serial >= 0:
                    import warnings

                    warnings.warn(
                        "resuming a LEGACY (serial-based, params-only) "
                        "checkpoint from %s; future saves use the "
                        "TrainState format" % cfg.checkpoint_dir)
                    cfg.load_serial = serial
                    with scope_guard(self.scope):
                        fluid_io.load_checkpoint(
                            Executor(self.place), cfg.checkpoint_dir,
                            main_program=self.train_program)

    # ------------------------------------------------------------------
    def _dist_transpile_if_necessary(self):
        role = os.getenv("PADDLE_TRAINING_ROLE")
        if role is None or role == "TRAINER":
            return
        if role == "PSERVER":
            raise RuntimeError(
                "parameter-server roles do not exist on the TPU runtime: "
                "parameters live sharded on the mesh (use parallel=True "
                "with a Mesh spanning your hosts via jax.distributed)")
        raise ValueError("unknown PADDLE_TRAINING_ROLE %r" % role)

    def stop(self):
        self.__stop = True

    # ------------------------------------------------------------------
    def train(self, num_epochs, event_handler, reader=None, feed_order=None):
        with scope_guard(self.scope):
            if self.parallel:
                executor = ParallelExecutor(
                    loss_name=self._loss_name,
                    main_program=self.train_program, mesh=self._mesh)
                run = lambda feed, fetch: executor.run(
                    feed=feed, fetch_list=fetch)
            else:
                executor = Executor(self.place)
                run = lambda feed, fetch: executor.run(
                    self.train_program, feed=feed, fetch_list=fetch)
            feeder = self._feeder(feed_order)
            epoch_id = self._apply_resume_state(executor, reader)
            try:
                # inside the try: a raising Guardian construction
                # (invalid config) must also restore the flag below
                g = self._make_guardian()
                with self._signal_guard(), _guardian.installed(g):
                    # detect -> decide -> recover loop: a
                    # GuardianRollback raised by the guardian (from
                    # inside executor.run) restores the newest clean
                    # TrainState and re-enters the epoch loop from the
                    # restored position; the rollback budget turns a
                    # persistent fault into a typed GuardianAbortError
                    # instead of recovering forever
                    while True:
                        try:
                            self._run_epochs(epoch_id, num_epochs,
                                             event_handler, reader,
                                             feeder, run, executor)
                            break
                        except _guardian.GuardianRollback as rb:
                            epoch_id = self._rollback_recover(
                                rb, executor, reader)
                            if self.__stop or self.__preempted:
                                break
                    if self.__preempted and self._ckpt_mgr is not None \
                            and self._global_step > 0:
                        # > 0: a preemption before any step completed
                        # has nothing worth flushing — and a step-0
                        # artifact would restore as load_serial=0,
                        # falsy under the documented
                        # `if cfg.load_serial:` resume check
                        # preemption: the step finished, now force a
                        # synchronous TrainState flush, then let the
                        # signal's default behavior proceed (SURVEY §5
                        # checkpoint-on-signal; reference analog:
                        # listen_and_serv_op.cc signal handler)
                        self._flush_checkpoint(executor, reader,
                                               self._current_epoch)
            finally:
                if self._set_guardian_flag:
                    # restore the flag this train() set: a later plain
                    # executor (or the next Trainer's startup program)
                    # must not run guarded with nobody deciding
                    self._set_guardian_flag = False
                    _flags.set_flags({"guardian": False})
                if monitor.enabled():
                    # stamp the run's wall-clock attribution into the
                    # JSONL at the boundary every post-mortem starts
                    # from — in the finally, because the runs that NEED
                    # a post-mortem (guardian abort, preemption) are
                    # the ones that don't return cleanly
                    try:
                        monitor.goodput_stamp()
                        # final per-layer model-health state next to it
                        # (no-op while FLAGS_health never published)
                        monitor.health.stamp()
                    except Exception:  # noqa: BLE001 — telemetry must
                        pass           # not mask the real exit
            if self._ckpt_mgr is not None:
                # a trailing async write must land before the process
                # can exit believing the state is durable
                self._ckpt_mgr.wait_until_finished()

    def _run_epochs(self, epoch_id, num_epochs, event_handler, reader,
                    feeder, run, executor):
        g = _guardian.active()
        for epoch_id in range(epoch_id, num_epochs):
            self._current_epoch = epoch_id
            if self.__stop:
                break
            event_handler(BeginEpochEvent(epoch_id))
            for step_id, data in enumerate(reader()):
                if self.__stop:
                    break
                begin = BeginStepEvent(epoch_id, step_id)
                event_handler(begin)
                fetch = [v.name for v in self.train_func_outputs] \
                    if begin.fetch_metrics else []
                with RecordEvent("trainer/step"):
                    metrics = run(feeder.feed(data), fetch)
                    metrics = [np.asarray(m) for m in metrics]
                self._global_step += 1
                event_handler(EndStepEvent(epoch_id, step_id,
                                           metrics))
                with RecordEvent("trainer/checkpoint"):
                    self._maybe_save_checkpoint(executor, reader,
                                                epoch_id)
                if self.__preempted:
                    break
            if g is not None:
                # epoch boundary: force every deferred guardian
                # observation through the ladder while the recovery
                # loop can still catch its decision
                g.flush()
            event_handler(EndEpochEvent(epoch_id))
            if self.__preempted:
                break
        if g is not None:
            g.flush()

    def _make_guardian(self):
        """The default wiring: a caller-installed guardian stays in
        charge (returns None so the Trainer neither re-installs nor
        uninstalls it); otherwise FLAGS_guardian / guardian_config
        build one, quarantining next to the checkpoints unless
        configured elsewhere."""
        if self._guardian_config is not None \
                and not _flags.flag("guardian"):
            # explicit config implies intent: enable the flag so the
            # executors lower the in-graph skip guard too.  Deferred to
            # train() (not __init__) and restored when train() returns:
            # programs run while no guardian is installed (this
            # Trainer's startup, a later plain executor) must not be
            # silently guarded
            _flags.set_flags({"guardian": True})
            self._set_guardian_flag = True
        if _guardian.active() is not None:
            return None
        cfg = self._guardian_config
        if cfg is None and not _flags.flag("guardian"):
            return None
        if isinstance(cfg, _guardian.Guardian):
            from ..cluster import ClusterGuardian

            if self._cluster_member is not None \
                    and not isinstance(cfg, ClusterGuardian):
                # a plain Guardian instance would decide ALONE while
                # cluster_member promises arbitration — silently
                # bypassing it is exactly the per-process-divergence
                # hole the bridge exists to close; make the conflict a
                # configuration error instead
                raise ValueError(
                    "Trainer(cluster_member=...) with a plain Guardian "
                    "instance: verdicts would not be cluster-"
                    "arbitrated.  Pass guardian_config as a kwargs "
                    "dict (the Trainer builds a ClusterGuardian), or "
                    "construct cluster.ClusterGuardian(member, ...) "
                    "yourself")
            g = cfg
            # budgets/history are per-run: a reused instance must not
            # carry a spent rollback budget into this train() (the
            # kwargs path below builds a fresh Guardian each time)
            g.reset_run_state()
        elif self._cluster_member is not None:
            # cluster runs arbitrate verdicts through the master: one
            # host's rollback/abort becomes the cluster's
            from ..cluster import ClusterGuardian

            g = ClusterGuardian(self._cluster_member, **dict(cfg or {}))
        else:
            g = _guardian.Guardian(**dict(cfg or {}))
        if not g.quarantine_dir \
                and not _flags.flag("guardian_quarantine_dir") \
                and self.checkpoint_cfg is not None:
            g.quarantine_dir = os.path.join(
                self.checkpoint_cfg.checkpoint_dir, "quarantine")
        return g

    def _rollback_recover(self, rb, executor, reader):
        """One rung of the recovery ladder: charge the rollback budget,
        restore the newest clean TrainState (skipping corrupt or
        NaN-poisoned artifacts), re-apply executor PRNG counter and
        reader position, and fast-forward the reader past a poisoned
        batch window.  Returns the epoch to re-enter the loop at."""
        g = _guardian.active()
        if g is None:
            raise rb
        if self._ckpt_mgr is None:
            raise _guardian.GuardianAbortError(
                "guardian requested a rollback at step %d (%s) but the "
                "Trainer has no CheckpointConfig — nothing to roll back "
                "to" % (rb.step, rb.reason)) from rb
        g.begin_rollback(rb)          # budget; raises when exhausted
        executor.sync()               # retire in-flight async steps
        readers = self._ckpt_readers(reader)
        if reader is not None and not readers:
            warnings.warn(
                "guardian rollback cannot rewind this reader (no "
                "state_dict — wrap it with reader.checkpointable()): "
                "the replay re-enters the epoch from the reader's "
                "current position, so the recovered trajectory will "
                "NOT exactly reproduce the clean run")
        restored = g.rollback_restore(
            self._ckpt_mgr, rb, scope=self.scope,
            program=self.train_program, executors={"train": executor},
            readers=readers)
        self._global_step = restored
        if self.checkpoint_cfg is not None:
            self.checkpoint_cfg.load_serial = restored
        ff = g.post_restore(rb, restored)
        if ff:
            if hasattr(reader, "fast_forward"):
                reader.fast_forward(ff)
                monitor.log_event({"event": "guardian_fast_forward",
                                   "batches": ff,
                                   "restored_step": restored})
            else:
                warnings.warn(
                    "guardian rollback wants to skip %d poisoned "
                    "batches but the reader has no fast_forward() — "
                    "wrap it with reader.checkpointable(); the replay "
                    "may re-trip the sentinel" % ff)
        if reader is not None and hasattr(reader, "state_dict"):
            try:
                return int(reader.state_dict().get(
                    "epoch", self._current_epoch))
            except Exception:  # noqa: BLE001 — epoch is best-effort
                pass
        return self._current_epoch

    def _apply_resume_state(self, executor, reader):
        """After an auto-resume, re-apply the non-scope legs of the
        restored TrainState to the objects that now exist: the
        executor's PRNG fold-in counter and the reader's position.
        Consumed once — a second train() call must not rewind the
        executor to the restore point (it starts a fresh epoch range).
        Returns the resume epoch."""
        host, self._pending_resume = self._pending_resume, None
        start, self._resume_epoch = self._resume_epoch, 0
        if host is None:
            return start
        ex_state = host.get("executors", {}).get("train")
        if ex_state is not None:
            executor.load_state_dict(ex_state)
        rd_state = host.get("readers", {}).get("train")
        if rd_state is not None and hasattr(reader, "load_state_dict"):
            reader.load_state_dict(rd_state)
            # the reader's own epoch counter is the precise resume
            # epoch (it rolls over exactly at source exhaustion)
            return int(rd_state.get("epoch", start))
        return start

    def _signal_guard(self):
        """While training, SIGTERM/SIGINT request a graceful stop: the
        current step finishes, a checkpoint is flushed, and the signal
        is re-raised with its original handler."""
        import contextlib
        import signal as _signal

        self.__preempted = None

        @contextlib.contextmanager
        def _ctx():
            prev = {}

            def handler(signum, frame):
                self.__preempted = signum
                self.__stop = True

            try:
                for s in (_signal.SIGTERM, _signal.SIGINT):
                    prev[s] = _signal.signal(s, handler)
            except ValueError:      # not the main thread
                yield
                return
            try:
                yield
            finally:
                for s, h in prev.items():
                    _signal.signal(s, h)
                if self.__preempted is not None:
                    _signal.raise_signal(self.__preempted)

        return _ctx()

    def _ckpt_readers(self, reader):
        if reader is not None and hasattr(reader, "state_dict"):
            return {"train": reader}
        return None

    def _flush_checkpoint(self, executor, reader, epoch_id):
        self._ckpt_mgr.save_now(
            self._global_step, scope=self.scope,
            program=self.train_program, executors={"train": executor},
            readers=self._ckpt_readers(reader),
            extra={"epoch": epoch_id, "preempted": True})

    def test(self, reader, feed_order=None):
        """Average the train_func outputs over the test reader."""
        with scope_guard(self.scope):
            executor = Executor(self.place)
            feeder = self._feeder(feed_order, program=self.test_program)
            accumulated = None
            count = 0
            for data in reader():
                outs = executor.run(
                    self.test_program, feed=feeder.feed(data),
                    fetch_list=[v.name for v in self.train_func_outputs])
                outs = [float(np.asarray(o).mean()) for o in outs]
                accumulated = outs if accumulated is None else [
                    a + o for a, o in zip(accumulated, outs)]
                count += 1
            if count == 0:
                return accumulated
            return [a / count for a in accumulated]

    def save_params(self, param_path):
        with scope_guard(self.scope):
            fluid_io.save_persistables(
                Executor(self.place), param_path,
                main_program=self.train_program)

    def save_inference_model(self, param_path, feeded_var_names,
                             target_var_indexes):
        with scope_guard(self.scope):
            fluid_io.save_inference_model(
                param_path, feeded_var_names,
                [self.train_func_outputs[i] for i in target_var_indexes],
                Executor(self.place), main_program=self.train_program)

    # ------------------------------------------------------------------
    def _feeder(self, feed_order, program=None):
        program = program or self.train_program
        if feed_order is None:
            feed_order = [
                v.name for v in program.global_block().vars.values()
                if getattr(v, "is_data", False)
                and not v.name.endswith("@LEN")
            ]
        feed_list = [
            program.global_block().var(name) for name in feed_order
        ]
        return DataFeeder(feed_list=feed_list, place=self.place,
                          program=program)

    def _maybe_save_checkpoint(self, executor, reader, epoch_id):
        cfg = self.checkpoint_cfg
        if cfg is None or epoch_id % cfg.epoch_interval != 0:
            return
        # the manager gates on the GLOBAL step interval; the snapshot is
        # synchronous (device->host), the write overlaps later compute
        self._ckpt_mgr.save(
            self._global_step, scope=self.scope,
            program=self.train_program, executors={"train": executor},
            readers=self._ckpt_readers(reader),
            extra={"epoch": epoch_id})
