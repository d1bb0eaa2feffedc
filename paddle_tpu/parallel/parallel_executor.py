"""ParallelExecutor: pjit a Program over a device mesh.

Capability parity with the reference's single-process multi-GPU runtime
(``framework/parallel_executor.cc:58-247``: per-device scopes, NCCL
context map, SSA-graph replication with allreduce handles, threaded
dataflow executor) — re-designed TPU-first:

* The program is traced ONCE into a pure step function
  (executor.trace_program) and jit-compiled with
  ``in_shardings``/``out_shardings`` over a named Mesh.  XLA GSPMD
  partitions the computation and inserts ICI collectives — the psum of
  data-parallel gradients replaces ``all_reduce_op_handle.cc``; the
  reduce-scatter/all-gather pair of the kReduce strategy replaces
  ``reduce_op_handle.cc`` + ``broadcast_op_handle.cc``.
* Gradient averaging needs no explicit scale_loss_grad op: the batch is
  sharded over ``dp`` and mean-reduced losses psum partial means, which
  is exactly CoeffNumDevice semantics.
* Feeds: one global batch dict (sharded on dim 0 over ``dp``), or the
  reference's per-device list-of-dicts form (concatenated).
* State lives in the Scope as global jax Arrays; between steps sharded
  params stay resident on their devices (no host round-trip) — the analog
  of the reference's persistent per-device scopes.
* Multi-host ("NCCL2 mode", ``num_trainers``/``trainer_id``): initialize
  ``jax.distributed`` first; the same mesh then spans hosts and XLA
  routes collectives over ICI/DCN (replaces gen_nccl_id + flat NCCL
  world, parallel_executor.cc:94-103).
"""

import contextlib
import math
import time
import warnings

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import monitor, registry  # noqa: F401  (op registry must be loaded)
from ..executor import StepPath, _declares_batch
from ..framework import default_main_program
from ..scope import global_scope
from .mesh import make_mesh, AXIS_DP, AXIS_FSDP
from .spec_layout import SpecLayout
from .strategy import BuildStrategy, ExecutionStrategy

__all__ = ["ParallelExecutor"]

# sharding_rules=True resolves to this shared table (SpecLayout hashes
# by value, so a per-call instance would also cache correctly — one
# object just keeps the intent obvious)
_DEFAULT_SPEC_LAYOUT = SpecLayout()


class ParallelExecutor(StepPath):
    """The step path placed on a mesh: this class is the mesh's side of
    ``executor.StepPath``'s placement hooks and nothing of the step."""

    _name = "parallel_executor"
    _label = "pe"
    # state may span hosts (not fully addressable): fetches only
    _check_state = False
    _times_steps = False

    def __init__(self, use_cuda=True, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None, mesh=None):
        super().__init__()
        self._mesh = mesh if mesh is not None else make_mesh()
        if AXIS_DP not in self._mesh.axis_names and \
                AXIS_FSDP not in self._mesh.axis_names:
            raise ValueError(
                "mesh must have a data axis (%r or %r)"
                % (AXIS_DP, AXIS_FSDP))
        self._program = main_program
        self._scope = scope
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._loss_name = loss_name
        self._num_trainers = num_trainers
        self._trainer_id = trainer_id
        self._mesh_key = (tuple(self._mesh.axis_names),
                          tuple(self._mesh.devices.shape),
                          tuple(int(d.id) for d in self._mesh.devices.flat))
        self._auto_seed_val = None
        # observability: how many ragged batches were replication-padded
        # (the data_balance_op_handle capability — see _pad_uneven)
        self.uneven_batches_padded = 0
        if share_vars_from is not None:
            # parity with PE(share_vars_from=train_exe): same scope object
            self._scope = share_vars_from._actual_scope()

    # ------------------------------------------------------------------
    @property
    def device_count(self):
        return int(np.prod(self._mesh.devices.shape))

    def _actual_scope(self):
        return self._scope if self._scope is not None else global_scope()

    def _dp_size(self):
        """Total batch-sharding extent: dp x fsdp.  Both axes shard the
        batch (fsdp is a data-parallel axis for activations; it
        additionally ZeRO-shards params/optimizer state — spec_layout)."""
        return self._axis_size(AXIS_DP) * self._axis_size(AXIS_FSDP)

    def _data_axes(self):
        """The mesh axes the batch dim shards over, in (dp, fsdp) order.
        When both are size 1 (or absent), fall back to whichever data
        axis the mesh actually HAS — naming an absent axis in a spec is
        a jax error even at size 1."""
        axes = tuple(a for a in (AXIS_DP, AXIS_FSDP)
                     if self._axis_size(a) > 1)
        if axes:
            return axes
        return (AXIS_DP,) if AXIS_DP in self._mesh.axis_names \
            else (AXIS_FSDP,)

    def _zero_axis(self):
        """The axis ZeRO-style state sharding targets: ``fsdp`` when the
        mesh has a populated one (the kReduce strategy generalized off
        pure-dp), else ``dp`` (the original kReduce behavior) — always
        an axis the mesh actually has."""
        if self._axis_size(AXIS_FSDP) > 1 or \
                AXIS_DP not in self._mesh.axis_names:
            return AXIS_FSDP
        return AXIS_DP

    # ------------------------------------------------------------------
    def _axis_size(self, axis):
        if axis not in self._mesh.axis_names:
            return 1
        return self._mesh.devices.shape[self._mesh.axis_names.index(axis)]

    def _spec_fits(self, spec, shape, local_batch=False):
        """True iff every named axis in ``spec`` divides its dim of shape.
        With ``local_batch`` (multi-host feeds), dim 0 holds only this
        process's slice, so its divisor shrinks by the process count."""
        entries = tuple(spec)
        if len(entries) > len(shape):
            return False
        for i, (dim, entry) in enumerate(zip(shape, entries)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for ax in axes:
                if ax not in self._mesh.axis_names:
                    return False
                total *= self._axis_size(ax)
            if local_batch and i == 0:
                total = max(1, total // jax.process_count())
            if total > 1 and (dim <= 0 or dim % total != 0):
                return False
        return True

    def _sharding_layout(self):
        """The BuildStrategy's sharding_rules normalized to a SpecLayout
        (``True`` selects the shared default table — the user's strategy
        object is read, never mutated), or None."""
        rules = self._build_strategy.sharding_rules
        if rules is True:
            return _DEFAULT_SPEC_LAYOUT
        return rules

    def _state_spec(self, name, val, rule_specs):
        """Sharding spec for a persistable state array.  Precedence:
        the param_sharding_fn hook (when it returns a spec), then the
        resolved sharding_rules table, then the reduce-strategy
        fallback (ZeRO dim-0 over the fsdp/dp axis under kReduce,
        replicate under kAllReduce)."""
        custom = self._build_strategy.param_sharding_fn
        if custom is not None:
            spec = custom(name, tuple(getattr(val, "shape", ())))
            if spec is not None:
                if not self._spec_fits(spec, tuple(val.shape)):
                    raise ValueError(
                        "param_sharding_fn spec %r does not divide %r of "
                        "shape %s on mesh %s"
                        % (spec, name, tuple(val.shape),
                           dict(zip(self._mesh.axis_names,
                                    self._mesh.devices.shape))))
                return spec
        rule = rule_specs.get(name)
        if rule is not None and rule != P():
            return rule
        # a rules resolution that degraded all the way to "replicate"
        # (e.g. sharding_rules on a mesh with no populated fsdp/tp axis)
        # falls THROUGH to the reduce-strategy tier, so kReduce ZeRO
        # sharding on a pure-dp mesh survives enabling the table; use
        # param_sharding_fn to force-replicate a var against kReduce.
        strat = self._build_strategy.reduce_strategy
        if strat == BuildStrategy.ReduceStrategy.Reduce:
            # ZeRO-style: shard dim 0 over the zero axis when it divides
            # evenly.  Read shape only — np.asarray here would download
            # every param from device HBM at compile time.
            shape = tuple(getattr(val, "shape", ()))
            ax = self._zero_axis()
            if len(shape) >= 1 and shape[0] > 0 \
                    and shape[0] % self._axis_size(ax) == 0:
                return P(ax)
        return P()

    # -- the placement: a mesh ---------------------------------------------
    @property
    def donate_state(self):
        return self._build_strategy.donate_state

    def _first_device(self):
        return self._mesh.devices.flat[0]

    def _placement_key(self, dev):
        # mesh identity and the sharding policy knobs; policy fns go in
        # as objects (kept alive by the cache, so no id()-reuse aliasing
        # after GC)
        bs = self._build_strategy
        return ("pjit", self._mesh_key, bs.reduce_strategy,
                bs.param_sharding_fn, bs.feed_sharding_fn,
                self._sharding_layout(), bs.sequence_parallel, bs.remat,
                bs.donate_state, jax.process_count(),
                bs.pipeline_schedule, bs.pipeline_microbatches)

    def _batch_shards(self):
        return self._dp_size()

    def _trace_sigs(self, feed_names, feed_sig, state_names, scope):
        # shapes decide the shardings below, so they key the entry
        return feed_sig, tuple(
            (n, tuple(getattr(scope.var(n), "shape", ())),
             str(getattr(scope.var(n), "dtype", "")))
            for n in state_names)

    def _state_specs(self, program, state):
        """``{name: PartitionSpec}`` for the persistables ``state``
        (name -> value) under this executor's policy."""
        layout = self._sharding_layout()
        rule_specs = {}
        if layout is not None:
            rule_specs = layout.resolve(
                program, self._mesh,
                [(n, tuple(getattr(v, "shape", ())))
                 for n, v in state.items()])
        return {n: self._state_spec(n, v, rule_specs)
                for n, v in state.items()}

    def _trace_kwargs(self, program, state_names, scope, dev):
        bs = self._build_strategy
        # the state placement is resolved BEFORE tracing: sharded-op
        # lowerings (sparse embedding lookup/update over row-sharded
        # tables) read their operands' specs from the trace context, so
        # the placement is an input of the trace, not an afterthought
        return {"platform": dev.platform, "mesh": self._mesh,
                "sequence_parallel": bs.sequence_parallel,
                "pipeline_schedule": bs.pipeline_schedule,
                "pipeline_microbatches": bs.pipeline_microbatches,
                "state_specs": self._state_specs(
                    program, {n: scope.var(n) for n in state_names})}

    def _wrap_traced(self, fn):
        # INSIDE the guard, so the guard's select is not rematerialized
        return jax.checkpoint(fn) if self._build_strategy.remat else fn

    def _jit_kwargs(self, program, traced, feed_names, feed_vals, state_in,
                    state_out, n_fetches):
        mesh = self._mesh
        spec_by_name = traced["state_specs"]
        data_axes = self._data_axes()
        batch_spec = P(data_axes if len(data_axes) > 1 else data_axes[0])
        feed_shardings = []
        # multi-host: each process feeds its local slice, so the local
        # batch only needs to cover this process's share of the dp axis
        dp = max(1, self._dp_size() // jax.process_count())
        custom_feed = self._build_strategy.feed_sharding_fn
        for n, arr in zip(feed_names, feed_vals):
            spec = None
            if custom_feed is not None:
                spec = custom_feed(n, tuple(arr.shape))
            if spec is not None:
                if not self._spec_fits(spec, tuple(arr.shape),
                                       local_batch=jax.process_count() > 1):
                    raise ValueError(
                        "feed_sharding_fn spec %r does not divide feed %r "
                        "of shape %s" % (spec, n, tuple(arr.shape)))
                feed_shardings.append(NamedSharding(mesh, spec))
            elif arr.ndim >= 1 and arr.shape[0] % dp == 0 \
                    and arr.shape[0] > 0:
                feed_shardings.append(NamedSharding(mesh, batch_spec))
            else:
                raise ValueError(
                    "feed %r batch dim %s is not divisible by the "
                    "data-parallel mesh extent %d (dp x fsdp)"
                    % (n, arr.shape[:1], dp)
                )
        state_shardings = [
            NamedSharding(mesh, spec_by_name[n]) for n in state_in
        ]
        out_state_shardings = [
            NamedSharding(mesh, spec_by_name.get(n, P()))
            for n in state_out
        ]
        # multi-host: fetches are forced replicated so every process can
        # read them (np.asarray on a non-addressable array would throw)
        fetch_shardings = [NamedSharding(mesh, P())] * n_fetches \
            if jax.process_count() > 1 else None
        return ({"in_shardings": (feed_shardings, state_shardings, None),
                 "out_shardings": (fetch_shardings, out_state_shardings)},
                {"feed_shardings": feed_shardings,
                 "state_shardings": state_shardings,
                 "partition_key": self._mesh_key[:2] + (tuple(
                     (n, str(spec_by_name[n])) for n in state_in
                     if spec_by_name[n] != P()),),
                 "pipeline_stats": self._pipeline_stats(program)})

    _dispatch_scope = staticmethod(contextlib.nullcontext)

    def _pipeline_stats(self, program):
        """Per-tick stage-idle accounting for the program's
        pipeline_region ops under this executor's mesh + schedule — the
        numbers behind the goodput ledger's ``pipeline_bubble`` bucket.
        Mirrors the lowering's engagement test (ops/pipeline_region.py);
        None when no region runs pipelined on this mesh."""
        from .mesh import AXIS_PP
        from .pipeline import normalize_schedule, schedule_stats

        pp = self._axis_size(AXIS_PP)
        if pp <= 1:
            return None
        schedule = normalize_schedule(
            self._build_strategy.pipeline_schedule)
        override = self._build_strategy.pipeline_microbatches
        regions = []
        for op in program.global_block().ops:
            if op.type != "pipeline_region":
                continue
            s_count = int(op.attrs["stages"])
            if schedule == "interleaved":
                if s_count % pp or s_count <= 1:
                    continue
                v = s_count // pp
            else:
                if s_count != pp or s_count <= 1:
                    continue
                v = 1
            m = int(override or op.attrs.get("microbatches") or s_count)
            regions.append(schedule_stats(schedule, pp, m, v))
        if not regions:
            return None
        total = sum(r["total_units"] for r in regions)
        idle = sum(r["idle_units"] for r in regions)
        return {"schedule": schedule,
                "bubble_fraction": idle / total if total else 0.0,
                "regions": regions}

    @staticmethod
    def _global_state(val, sharding):
        """Lift a host-local state value (identical on every process, by
        deterministic seeded startup) into a global array on ``sharding``."""
        if isinstance(val, jax.Array) and len(val.sharding.device_set) > 1:
            return val          # already global (previous step's output)
        host = np.asarray(val)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    def _pad_uneven(self, feed_vals):
        """Ragged-batch handling (reference
        ``details/data_balance_op_handle.cc:1`` redistributes uneven
        epoch-end batches across devices): SPMD-jitted steps have static
        shapes, so the ragged global batch is replicated WHOLE,
        r = dp / gcd(B, dp) times, making dim 0 divisible.  Replication
        (unlike zero-pad-and-mask) is EXACT: means over the batch,
        per-sample gradients of a mean loss, and BN batch statistics are
        all invariant under whole-batch replication, so the training
        trajectory matches the single-device run bit-for-bit; per-sample
        fetches are trimmed back to the true batch.  Costs r x compute
        for the one ragged batch per epoch."""
        if not self._build_strategy.pad_uneven_batches:
            return feed_vals
        dp = max(1, self._dp_size() // jax.process_count())
        bs = {v.shape[0] for v in feed_vals if getattr(v, "ndim", 0) >= 1}
        if len(bs) != 1:
            return feed_vals
        b = bs.pop()
        if b <= 0 or b % dp == 0:
            return feed_vals
        r = dp // math.gcd(b, dp)
        if self.uneven_batches_padded == 0:
            warnings.warn(
                "ragged batch %d replicated x%d to fit the dp=%d mesh: "
                "exact for mean-normalized losses and BN stats; a "
                "sum-reduced objective would scale by the replication "
                "factor — set BuildStrategy.pad_uneven_batches=False to "
                "reject ragged batches instead" % (b, r, dp),
                stacklevel=5)
        self.uneven_batches_padded += 1
        return [np.concatenate([np.asarray(v)] * r, axis=0)
                for v in feed_vals]

    def _trim_fetches(self, block, fetch_names, fetches, fed, padded):
        """Trim per-sample fetches (e.g. predictions [B*r, ...]) back to
        the true batch; scalars/means are replication-invariant.  Only
        BATCH-dim vars trim (program shape[0] == -1): a parameter whose
        leading dim coincidentally equals the padded batch must come
        back whole."""
        padded_b, true_b = (
            next((v.shape[0] for v in vals if getattr(v, "ndim", 0) >= 1), 0)
            for vals in (padded, fed))
        return [
            f[:true_b] if getattr(f, "ndim", 0) >= 1
            and f.shape[0] == padded_b and _declares_batch(block, n) else f
            for n, f in zip(fetch_names, fetches)
        ]

    def _put(self, compiled, feed_vals, scope, dev):
        if jax.process_count() > 1:
            # NCCL2-mode parity: each trainer process feeds its LOCAL
            # shard of the global batch; the global array spans hosts
            # (parallel_executor.cc:102 flat world of trainer ranks)
            return ([v if isinstance(v, jax.Array)
                     and len(v.sharding.device_set) > 1
                     else jax.make_array_from_process_local_data(s, v)
                     for v, s in zip(feed_vals, compiled.feed_shardings)],
                    [self._global_state(scope.var(n), s)
                     for n, s in zip(compiled.state_in,
                                     compiled.state_shardings)])
        return ([jax.device_put(v, s)
                 for v, s in zip(feed_vals, compiled.feed_shardings)],
                [jax.device_put(scope.var(n), s)
                 for n, s in zip(compiled.state_in,
                                 compiled.state_shardings)])

    def _before_record(self, compiled, cold, mon_t0, fp):
        ps = compiled.pipeline_stats
        if ps is not None and not cold:
            # measured bubble attribution: the executed schedule's
            # per-tick stage-idle fraction (exact, from the lowering's
            # own schedule tables) carved out of this step's measured
            # wall clock.  Warm steps only — a cold step's wall is
            # compile, already attributed.  The whole step is treated as
            # pipelined time (the regions dominate deep models;
            # documented in README).
            monitor.observe_span(
                "pipeline/bubble",
                (time.perf_counter() - mon_t0) * ps["bubble_fraction"] * 1e6,
                args={"bucket": "pipeline_bubble",
                      "schedule": ps["schedule"],
                      "fraction": round(ps["bubble_fraction"], 4),
                      "run_id": monitor.run_id(),
                      "fingerprint": fp[:12] if fp else None})

    def _after_record(self):
        # per-device memory/step gauges for the whole local mesh (the
        # step record's sample covers only the first device)
        monitor.sample_device_gauges(
            [d for d in self._mesh.devices.flat
             if d.process_index == jax.process_index()])

    # ------------------------------------------------------------------
    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        feed = feed if feed is not None else feed_dict
        if isinstance(feed, (list, tuple)):
            # reference per-device feed list: concatenate along batch
            feed = {k: np.concatenate([np.asarray(d[k]) for d in feed],
                                      axis=0) for k in feed[0]}
        return self._run_step(
            self._program or default_main_program(), self._actual_scope(),
            feed, fetch_list, return_numpy)

    def state_shardings(self, program=None, scope=None):
        """``{name: NamedSharding}`` for every persistable var of
        ``program`` as this executor's policy would place it on its mesh
        — the ``shardings=`` argument for TrainState/orbax restores, so
        a checkpoint written on any topology lands directly sharded on
        this one instead of replicating through host memory first."""
        from .checkpoint import _persistable_state

        program = program if program is not None else (
            self._program or default_main_program())
        scope = scope if scope is not None else self._actual_scope()
        specs = self._state_specs(program, _persistable_state(scope, program))
        return {n: NamedSharding(self._mesh, spec)
                for n, spec in specs.items()}

    def state_dict(self):
        """Exact-resume host state (see ``Executor.state_dict``): the
        PRNG fold-in counter plus the once-per-executor auto seed for
        seedless programs (drawn at first run, broadcast across hosts —
        restoring it keeps the resumed random stream identical)."""
        st = super().state_dict()
        if self._auto_seed_val is not None:
            st["auto_seed"] = int(self._auto_seed_val)
        return st

    def load_state_dict(self, state):
        super().load_state_dict(state)
        if state.get("auto_seed") is not None:
            self._auto_seed_val = np.uint32(state["auto_seed"])

    def _auto_seed(self):
        """Seed for programs with no explicit random_seed.  Drawn once
        per executor and, on multi-host jobs, broadcast from process 0:
        SPMD requires every process to feed the *same* rng key or
        nominally-replicated state silently diverges across hosts."""
        if self._auto_seed_val is None:
            seed = np.random.randint(0, 2**31 - 1)
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                seed = int(multihost_utils.broadcast_one_to_all(
                    np.int64(seed)))
            self._auto_seed_val = np.uint32(seed)
        return self._auto_seed_val

    @staticmethod
    def _fetch_to_np(f):
        if isinstance(f, jax.Array) and not f.is_fully_addressable:
            # multi-host: fetches are compiled with replicated
            # out_shardings, so the local shard IS the global value
            return np.asarray(f.addressable_shards[0].data)
        return np.asarray(f)
