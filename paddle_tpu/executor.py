"""Executor: lowers a Program to one jit-compiled XLA computation and runs it.

Capability parity with the reference's single-device Executor
(``paddle/fluid/framework/executor.cc:295-428``: Prepare ops from a block,
interpret them in order on one place, GC dead tensors) — re-designed
TPU-first:

* Instead of an op-by-op interpreter, ``Executor.run`` *traces* every op's
  JAX compute function in program order into a single function
  ``f(feeds, state, key) -> (fetches, new_state)`` and ``jax.jit``-compiles
  it once per (program, feed-signature).  The whole step — forward, backward,
  optimizer update — is one HLO module: XLA fuses elementwise chains into
  the matmuls (HBM-bandwidth win) and schedules for the MXU.  This is the
  TPU answer to the reference's per-op kernel launches.
* "State" is the set of persistable variables (parameters, optimizer
  accumulators, LR, step counters) read from / written back to the Scope.
  Input state buffers are donated to the computation, so parameter updates
  are in-place at the XLA level — the analog of the reference's var reuse,
  without a garbage collector (temporaries die inside the fused module).
* Feed/fetch: no feed/fetch ops are injected (reference executor.py:290-334
  injects feed_op/fetch_op); feeds bind program input vars directly and
  fetches are read off the traced environment.
* PRNG: programs are deterministic given ``program.random_seed``; each run
  folds a step counter into the key so dropout masks differ per step while
  remaining reproducible (replaces the reference's per-op seed attrs).
"""

import collections
import contextlib
import time

import numpy as np

import jax

from . import compile_cache, fault, flags, guardian, monitor, registry
from .core import materialize_dtype
from .framework import Program, Variable, default_main_program
from .monitor import program_profile
from .profiler import RecordEvent, is_profiling
from .registry import ComputeContext
from .scope import Scope, global_scope

__all__ = ["Executor", "AsyncDispatchQueue", "CPUPlace", "TPUPlace",
           "default_place", "place_from_string"]


class Place:
    """Device abstraction (reference platform/place.h:25-51).  On TPU builds
    there are two interesting places: host CPU and TPU chips; XLA handles
    everything below this level."""

    def jax_device(self):
        raise NotImplementedError

    def __repr__(self):
        return self.__class__.__name__


class CPUPlace(Place):
    def jax_device(self):
        # local_devices: under multi-host (jax.distributed) the first
        # GLOBAL device may belong to another process.  No CPU backend
        # (JAX_PLATFORMS names only the chip) raises: a host place never
        # resolves to an accelerator
        return jax.local_devices(backend="cpu")[0]

    def __eq__(self, other):
        return isinstance(other, CPUPlace)

    def __hash__(self):
        return hash("CPUPlace")


class TPUPlace(Place):
    """The first-class TPU place named in the north star (BASELINE.json)."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def jax_device(self):
        """The chip this place names.  Never another device: no
        accelerator, or an id past the last local chip, is an error —
        a step that was asked to run on a chip must not report from the
        host CPU or from a different chip."""
        devs = [d for d in jax.local_devices() if d.platform != "cpu"]
        if not devs:
            raise RuntimeError(
                "%r: this process has no accelerator (jax.local_devices() "
                "= %s); use CPUPlace() to run on the host" %
                (self, jax.local_devices()))
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                "%r: device id out of range, this process has %d "
                "accelerator device(s)" % (self, len(devs)))
        return devs[self.device_id]

    def __eq__(self, other):
        return isinstance(other, TPUPlace) and other.device_id == self.device_id

    def __hash__(self):
        return hash(("TPUPlace", self.device_id))

    def __repr__(self):
        return "TPUPlace(%d)" % self.device_id


# CUDAPlace alias for scripts written against the reference API surface:
# on this framework "the accelerator" is the TPU.
CUDAPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    """Pinned-host staging place (reference platform/place.h:36
    CUDAPinnedPlace).  Page-locked memory is a CUDA-transfer concept;
    on this runtime host arrays already stage through the PJRT transfer
    path, so this is the host place under a parity name."""

    def __eq__(self, other):
        return isinstance(other, CUDAPinnedPlace)

    def __hash__(self):
        return hash("CUDAPinnedPlace")


def default_place(place=None):
    """The library default where a caller names no place: chip 0 when
    the process has an accelerator, else the host CPU.  Chip entry
    points (chip_smoke.py, bench.py) do not go through this — they pass
    an explicit ``TPUPlace``, which raises without a chip."""
    if place is not None:
        return place
    accel = any(d.platform != "cpu" for d in jax.local_devices())
    return TPUPlace(0) if accel else CPUPlace()


def place_from_string(s):
    s = s.lower()
    if s in ("cpu",):
        return CPUPlace()
    if s in ("tpu", "cuda", "gpu", "device"):
        return TPUPlace(0)
    raise ValueError("unknown place %r" % s)


def _coerce_feed(block, name, v):
    """Convert one feed value to the program var's MATERIALIZED dtype.

    Device arrays pass through without a host round-trip; under x64-off
    a device array fed back (PyReader staging) is already int32, and
    asking jax for int64 would warn-and-truncate."""
    if not isinstance(v, jax.Array):
        v = np.asarray(v)
    pv = block._find_var_recursive(name)
    if pv is not None and pv.dtype is not None:
        want = materialize_dtype(pv.dtype)
        if np.dtype(v.dtype) != np.dtype(want):
            v = v.astype(want)
    return v


def _sparse_feed_info(program):
    """(ids feed names tuple, total sparse-table bytes) for telemetry:
    the is_sparse lookup tables' directly-fed Ids vars + total table
    bytes.  The one-time program walk caches ON the program object
    keyed by its version (an id()-keyed module dict would go stale when
    a freed program's id is recycled); the per-step cost is a np.unique
    over the id feeds."""
    cached = getattr(program, "_sparse_feed_cache", None)
    if cached is not None and cached[0] == program._version:
        return cached[1]
    from .ops.selected_rows import sparse_lookup_tables

    tables = {w: int(np.prod(v.shape)) * np.dtype(
        materialize_dtype(v.dtype)).itemsize
        for w, v in sparse_lookup_tables(program).items()}
    feeds = []
    for blk in program.blocks:
        for op in blk.ops:
            if op.type != "lookup_table" or \
                    not op.attrs.get("is_sparse", False):
                continue
            for n in op.inputs.get("Ids", []):
                v = blk._find_var_recursive(n)
                if v is not None and getattr(v, "is_data", False) \
                        and n not in feeds:
                    feeds.append(n)
    hit = (tuple(feeds), sum(tables.values()))
    program._sparse_feed_cache = (program._version, hit)
    return hit


def _step_extras(program, feed_names, feed_vals, fetch_names, fetches):
    """The step record's producer-supplied fields.

    The sparse embedding path's: distinct rows touched this step (summed
    over id feeds) + static table bytes.  Host feeds only — counting a
    device-resident id feed would force a per-step sync on the async
    path.  Also bumps the ``sparse/touched_rows`` registry counter.

    And the values of a ``program.step_stats`` variable
    (``framework.Program.step_stats``: a builder's per-step counters, e.g.
    a routed-expert layer's pairs computed) WHEN the caller fetched it to
    the host with this step — a counter never costs a sync of its own."""
    extras = {}
    feeds, table_bytes = _sparse_feed_info(program)
    if feeds:
        touched = 0
        by_name = dict(zip(feed_names, feed_vals))
        for n in feeds:
            v = by_name.get(n)
            if isinstance(v, np.ndarray) and v.size:
                touched += int(np.unique(v).size)
        monitor.count("sparse/touched_rows", touched)
        extras = {"sparse_touched_rows": touched,
                  "sparse_table_bytes": int(table_bytes)}
    declared = getattr(program, "step_stats", None)
    if declared and declared[0] in fetch_names:
        val = fetches[fetch_names.index(declared[0])]
        if isinstance(val, np.ndarray):
            extras.update(zip(declared[1], val.reshape(-1).tolist()))
    return extras or None


def _declares_batch(block, name):
    """Whether the program var declares a batch dim (shape[0] == -1/None)."""
    pv = block._find_var_recursive(name)
    return (pv is not None and pv.shape is not None
            and len(pv.shape) >= 1 and pv.shape[0] in (-1, None))


def _batch_examples(block, feed_names, feed_vals):
    """Examples-per-step for StepStats: the leading dim of a feed whose
    program var declares a batch dim; fallback is the max leading dim
    over array feeds (an alphabetically-first scalar aux feed must not
    report examples=1)."""
    best = 0
    for n, v in zip(feed_names, feed_vals):
        if getattr(v, "ndim", 0) < 1:
            continue
        if _declares_batch(block, n):
            return int(v.shape[0])
        best = max(best, int(v.shape[0]))
    return best


def trace_program(program, feed_names, state_names, writeback, fetch_names,
                  platform=None, mesh=None, sequence_parallel=True,
                  pipeline_schedule=None, pipeline_microbatches=None,
                  state_specs=None):
    """Build the pure step function for ``program``'s global block:
    ``fn(feed_vals, state_vals, key) -> (fetches, new_state)``.

    This is the single lowering point shared by the single-device Executor,
    the mesh ParallelExecutor, and ``__graft_entry__`` — a Program becomes
    one traceable JAX function that pjit/jit compile to one HLO module.
    ``platform`` names the executing device platform ("cpu"/"tpu") so
    Pallas call sites pick mosaic vs interpret.  Returns
    ``(fn, state_in, state_out)``.
    """
    block = program.global_block()
    ops = list(block.ops)
    state_in = list(state_names)
    # every read state var is also returned so XLA donation never leaves
    # a dangling (invalidated) buffer in the scope
    state_out = list(dict.fromkeys(list(state_names) + list(writeback)))
    kept = frozenset(fetch_names) | frozenset(state_out)

    def fn(feed_vals, state_vals, key):
        env = {}
        env.update(zip(feed_names, feed_vals))
        env.update(zip(state_in, state_vals))
        ctx = ComputeContext(key=key, platform=platform, mesh=mesh)
        ctx.sequence_parallel = sequence_parallel
        ctx.pipeline_schedule = pipeline_schedule
        ctx.pipeline_microbatches = pipeline_microbatches
        if state_specs:
            # how the PE placed each persistable on the mesh: sharded
            # sparse-table lowerings consult this at trace time
            ctx.state_specs = dict(state_specs)
        ctx.program = program
        ctx.amp = getattr(program, '_amp_policy', None)
        registry.compute_ops(ops, env, ctx, kept)
        fetches = [env[n] for n in fetch_names]
        new_state = [env[n] for n in state_out]
        return fetches, new_state

    return fn, state_in, state_out


class AsyncDispatchQueue:
    """Bounded window of in-flight (dispatched, not-yet-synced) steps.

    jax dispatch is already asynchronous; what needs managing is the
    HOST's run-ahead: an unbounded `return_numpy=False` loop enqueues
    work (and keeps fetch buffers alive) faster than the device retires
    it.  Each dispatched step's fetch handles are ``push``ed; once more
    than ``max_inflight`` steps are outstanding the OLDEST is
    ``block_until_ready``-ed — the only sync on the fast path, at the
    window edge, never per step.  ``drain`` syncs everything (epoch
    boundaries, checkpointing, reading host values)."""

    def __init__(self, max_inflight=None, name="executor"):
        # None = re-read FLAGS_max_inflight_steps on every push, so
        # set_flags keeps working after the executor is constructed
        self._max_inflight = max_inflight
        self._name = name
        self._inflight = collections.deque()
        # watchdog diagnostics read the queue state through monitor's
        # weak tracking — a stalled window edge is then visible as
        # depth == max_inflight in the stall dump
        monitor.track(self)

    def monitor_state(self):
        return {"kind": "dispatch_queue", "name": self._name,
                "depth": len(self._inflight),
                "max_inflight": self.max_inflight}

    @property
    def max_inflight(self):
        lim = self._max_inflight
        if lim is None:
            lim = flags.flag("max_inflight_steps")
        return max(1, int(lim))

    def __len__(self):
        return len(self._inflight)

    def push(self, handles):
        """Register one dispatched step's output handles; blocks on the
        oldest step iff the window is over-full."""
        self._inflight.append(handles)
        while len(self._inflight) > self.max_inflight:
            self._sync_oldest()

    def push_step(self, fetches, new_state):
        """Register one async-dispatched step: its fetch handles when
        present, else a tiny sync token derived from the state.  A
        fetch-less step has nothing un-donated to wait on — the next
        step's dispatch donates every new_state buffer — so the token
        (a one-element gather, NOT ravel(): an eager reshape copies the
        whole array and forces a layout change on sharded state) is
        what keeps the window a real bound.  Multihost non-addressable
        state can't be sliced from one process; those fetch-less loops
        go unbounded rather than crash."""
        handles = fetches
        if not handles and new_state and \
                getattr(new_state[0], "is_fully_addressable", True):
            s0 = new_state[0]
            handles = [s0[(0,) * s0.ndim]]
        if handles:
            self.push(handles)

    @staticmethod
    def _live_leaves(handles):
        return [l for l in jax.tree_util.tree_leaves(handles)
                if not getattr(l, "is_deleted", lambda: False)()]

    def _sync_oldest(self):
        oldest = self._inflight.popleft()
        # liveness signal for the watchdog: a window-edge sync that
        # never returns (device wedge) leaves this heartbeat stale while
        # the blocked thread looks merely "busy"
        monitor.heartbeat(self._name + "/dispatch")
        with RecordEvent(self._name + "/fetch_sync"):
            live = self._live_leaves(oldest)
            if not live:
                # a fetch-less step's handles are its new_state, and the
                # NEXT step donates those buffers (donate_argnums), so
                # the popped entry may hold nothing waitable.  Blocking
                # on the oldest still-live leaf among the younger
                # in-flight steps retires this one too (same-device
                # program order) and keeps the window a real bound —
                # skipping outright would let the host run ahead
                # without limit.
                for entry in self._inflight:
                    live = self._live_leaves(entry)
                    if live:
                        break
            jax.block_until_ready(live)

    def drain(self):
        """Block until every in-flight step has retired."""
        while self._inflight:
            self._sync_oldest()


def analyze(program, feed_names, scope, fetch_names=()):
    """Split program vars into feeds / state-from-scope / temporaries.
    Returns ``(state_names, writeback)``."""
    block = program.global_block()
    produced = set(feed_names)
    state = []
    for op in block.ops:
        for n in op.input_arg_names:
            if n and n not in produced and n not in state:
                if scope.has_var(n):
                    state.append(n)
                else:
                    raise RuntimeError(
                        "input var %r of op %r is neither fed, produced by "
                        "an earlier op, nor present in the scope. Feed it "
                        "or run the startup program first." % (n, op.type)
                    )
        for n in op.output_arg_names:
            if n:
                produced.add(n)
    # fetch targets no op produces but the scope holds (evaluator
    # state reads, plain var inspection) load like any other state
    for n in fetch_names:
        if n and n not in produced and n not in state \
                and scope.has_var(n):
            state.append(n)
    # persistable outputs must be written back even if never read
    writeback = []
    for op in block.ops:
        for n in op.output_arg_names:
            v = block._find_var_recursive(n) if n else None
            if v is not None and v.persistable and n not in writeback:
                writeback.append(n)
    return state, writeback


class CompiledStep:
    """One lowered+jitted step: an entry of the process-global trace
    cache, shared by every executor whose placement keys alike."""

    def __init__(self, fn, feed_names, state_in, state_out, fetch_names,
                 guarded=False, probe=None, feed_shardings=None,
                 state_shardings=None, partition_key=None,
                 pipeline_stats=None):
        self.fn = fn
        self.feed_names = feed_names
        self.state_in = state_in      # read from scope before the step
        self.state_out = state_out    # written back to scope after
        self.fetch_names = fetch_names
        # lowered with the guardian's in-graph skip guard: the step
        # returns a trailing `ok` fetch (stripped before user fetches)
        # and suppresses its state update when a float fetch is
        # non-finite
        self.guarded = guarded
        # lowered with the model-health probe (FLAGS_health): a HealthProbe
        # whose (L, 4) per-layer stats array rides as one extra fetch
        # between the user fetches and the guard's ok; None = every
        # health call site in the step is skipped (disabled-is-free)
        self.probe = probe
        # feed signatures already dispatched through this entry.  jax.jit
        # retraces+recompiles per feed shape, and the entry is shared
        # process-globally (trace cache), so warmth is per-signature: an
        # unseen shape's first call pays trace + XLA compile (or a
        # persistent-cache deserialize) and is recorded as a "compile"
        # span, seen shapes as "dispatch"
        self.seen_sigs = set()
        # AOT-captured executables keyed (feed_sig, first device id):
        # while profile capture is on, the cold dispatch compiles through
        # program_profile.capture (so cost/memory analyses are readable)
        # and every later step of that signature dispatches through the
        # same executable — jax's AOT and jit call paths do not share a
        # backend-compile cache, so mixing them would compile twice
        self.aot = {}
        # a mesh entry's placement of feeds and state, and its
        # mesh/sharding identity for the program-profile registry: the
        # same program compiled replicated vs fsdp-sharded has ~N-times
        # different per-device memory analyses — separate profile slots
        self.feed_shardings = feed_shardings
        self.state_shardings = state_shardings
        self.partition_key = partition_key
        # schedule accounting for the program's pipeline regions on its
        # mesh (None = nothing runs pipelined)
        self.pipeline_stats = pipeline_stats
        # {feed signature: the ``op_work`` its trace noted} (the products'
        # required work, compile_cache.note_op_work): a later record that
        # finds this entry traced already carries the list, not an empty one
        self.op_work = {}


def _names(fetch_list):
    return [v.name if isinstance(v, Variable) else v
            for v in fetch_list or []]


def _feed_sig(feed_names, feed_vals):
    return tuple((n, tuple(v.shape), str(v.dtype))
                 for n, v in zip(feed_names, feed_vals))


@contextlib.contextmanager
def _cold_call(name, span_args, compiled, feed_sig):
    """The cold call's ``<name>/compile`` span, which also closes the
    program's compile record with the call's wall time and keeps what the
    trace noted of its products' work on the entry."""
    t0 = time.perf_counter_ns()
    try:
        with RecordEvent(name + "/compile", args=span_args):
            yield
    finally:
        rec = compile_cache.close_record(t0)
        if rec is not None and rec["op_work"]:
            compiled.op_work[feed_sig] = rec["op_work"]


class StepPath:
    """The one step path under both executors: resolve feeds and fetches,
    find or lower the step (trace -> guard -> probe -> jit), put feeds
    and state on the device(s), dispatch (capturing the executable at
    the cold step), strip the guard's and the probe's fetches, write the
    state back, check, sync or queue, record.

    A subclass is a *placement* — what really differs between one device
    and a mesh — and nothing here asks which one it is.  It sets
    ``_name`` (spans, monitor and guardian records), ``_label`` (the
    module reads ``jit_pt_<label>_<program>``) and ``donate_state``, and
    answers the placement hooks below; the bodies here place the step on
    the one device ``_first_device()`` names."""

    _name = None
    _label = None
    # whether check_nan_inf may read the new state on the host, and
    # whether FLAGS_benchmark times each step
    _check_state = True
    _times_steps = True

    def __init__(self):
        self._cache = {}
        self._run_counter = 0
        self._warned_unobserved_guard = False
        self._dispatch_queue = AsyncDispatchQueue(name=self._name)

    def sync(self):
        """Retire every in-flight async-dispatched step (the
        ``return_numpy=False`` fast path never syncs per step; call this
        at epoch/checkpoint boundaries to force completion)."""
        self._dispatch_queue.drain()

    def state_dict(self):
        """Host-side executor state an exact resume must carry: the PRNG
        fold-in counter (each ``run`` folds it into the program seed, so
        dropout masks etc. at step N depend on how many steps ran
        before).  Captured into ``TrainState`` checkpoints; exactness
        additionally requires a nonzero ``program.random_seed`` (a
        seedless program draws a fresh seed per process)."""
        return {"run_counter": int(self._run_counter)}

    def load_state_dict(self, state):
        self._run_counter = int(state["run_counter"])

    # -- the placement ---------------------------------------------------
    def _first_device(self):
        """The device whose platform the trace is told, whose id keys the
        AOT slot, and which step records and profiles name."""
        raise NotImplementedError

    def _placement_key(self, dev):
        """What this placement bakes into a lowering: joins the
        per-executor key and the trace key."""
        return ("jit", dev.platform, self.donate_state)

    def _trace_sigs(self, feed_names, feed_sig, state_names, scope):
        """The trace key's feed and state parts: names where one entry
        serves every shape (jit retraces), signatures where shapes
        decide the placement."""
        return feed_names, tuple(state_names)

    def _trace_kwargs(self, program, state_names, scope, dev):
        """What ``trace_program`` is told beyond the names."""
        return {"platform": dev.platform}

    def _wrap_traced(self, fn):
        """A wrap of the traced function inside guard and probe."""
        return fn

    def _jit_kwargs(self, program, traced, feed_names, feed_vals, state_in,
                    state_out, n_fetches):
        """``(jax.jit arguments beyond donation, CompiledStep's placement
        attributes)``; ``traced`` is what ``_trace_kwargs`` returned."""
        return {}, {}

    # the context the step is captured and called under
    _dispatch_scope = staticmethod(jax.default_device)

    def _pad_uneven(self, feed_vals):
        """The feeds the step runs on, given the feeds as fed; one that
        returns others also has ``_trim_fetches`` to undo it on the
        fetches."""
        return feed_vals

    def _put(self, compiled, feed_vals, scope, dev):
        """``(feeds, state)`` on the device(s)."""
        return ([jax.device_put(v, dev) for v in feed_vals],
                [jax.device_put(scope.var(n), dev)
                 for n in compiled.state_in])

    def _batch_shards(self):
        """Over how many devices the placement splits the batch axis (the
        compile record's ``batch_shards``)."""
        return 1

    def _auto_seed(self):
        """The seed of a program that declares none."""
        return np.random.randint(0, 2**31 - 1)

    _fetch_to_np = staticmethod(np.asarray)

    def _before_record(self, compiled, cold, mon_t0, fp):
        """Spans the step record's goodput attribution should see."""

    def _after_record(self):
        """Gauges beyond the first device's."""

    # -- lowering --------------------------------------------------------
    def _entry(self, program, scope, feed_names, feed_vals, fetch_names,
               dev):
        """``(feed signature, CompiledStep)`` of this step, lowering it
        when neither this executor nor the process has it."""
        feed_sig = _feed_sig(feed_names, feed_vals)
        # program._version bumps on structural mutation (op append/insert,
        # rename_var) so stale compiled functions are not reused; direct
        # attr edits on existing ops are NOT tracked — clone() instead.
        # the policy object itself goes in the key (kept alive by the
        # cache) — id() could alias a recycled address after GC
        key = (id(program), program._version, program.random_seed, feed_sig,
               tuple(fetch_names), id(scope),
               getattr(program, '_amp_policy', None),
               # trace-time flag choices are baked into the jaxpr
               compile_cache.trace_flag_values()) + self._placement_key(dev)
        compiled = self._cache.get(key)
        if compiled is None:
            # one compile record a lowering: opened here, closed when the
            # cold call has returned (_cold_call)
            compile_cache.open_record(self._name, self._label, program,
                                      self._cause(program, key),
                                      batch_shards=self._batch_shards())
            # the reference wraps op instantiation in RecordBlock
            # (executor.cc Prepare); here the analog is the trace+jit
            # (_lower consults the process-global trace cache first)
            with RecordEvent(self._name + "/compile"):
                compiled = self._cache[key] = self._lower(
                    program, scope, feed_names, feed_vals, feed_sig,
                    fetch_names, dev)
            if feed_sig in compiled.seen_sigs:
                # the process has run this entry at this signature (the
                # trace cache's hit): no cold call follows, nothing is
                # traced again
                compile_cache.close_record(
                    None, op_work=compiled.op_work.get(feed_sig))
        return feed_sig, compiled

    def _cause(self, program, key):
        """Why this executor lowers ``key``: the compile record's
        ``cause``.  A program object this executor holds only at other
        versions was changed (its new fingerprint would read ``first``
        too); a key that differs from a held one in its feed signature
        alone is a new shape."""
        held = [k for k in self._cache if k[0] == key[0]]
        if held and all(k[1] != key[1] for k in held):
            return "program_changed"
        if not compile_cache.lowered_before(program):
            return "first"
        if any(k[:3] + k[4:] == key[:3] + key[4:] for k in held):
            return "feed_signature"
        return "other_key"

    def _lower(self, program, scope, feed_names, feed_vals, feed_sig,
               fetch_names, dev):
        t = time.perf_counter_ns()
        state_names, writeback = analyze(
            program, feed_names, scope, fetch_names)
        t = compile_cache.note_phase("analyze_s", t)
        # process-global trace cache: a second executor over the same
        # program structure + signature (bench reruns, evaluator clones)
        # reuses the jitted step — zero new lowerings
        tkey = compile_cache.trace_key(
            program,
            *self._trace_sigs(feed_names, feed_sig, state_names, scope),
            fetch_names, *self._placement_key(dev),
            compile_cache.trace_flag_values())
        cached = compile_cache.lookup(tkey)
        if cached is not None:
            return cached
        traced = self._trace_kwargs(program, state_names, scope, dev)
        # FLAGS_health: per-layer grad/param/update stats ride the step as
        # one fused extra fetch.  The grad vars are added to the traced
        # fetch list (XLA sees them as outputs); enablement is part of
        # trace_flag_values so the probe-free trace is never served stale
        probe = monitor.health.build_probe(program, state_names) \
            if monitor.health.probe_enabled() else None
        guarded = guardian.skip_guard_enabled()
        with RecordEvent(self._name + "/trace"):
            traced_fetches = list(fetch_names) + \
                (list(probe.grad_names) if probe is not None else [])
            fn, state_in, state_out = trace_program(
                program, feed_names, state_names, writeback, traced_fetches,
                **traced)
            fn = self._wrap_traced(fn)
            if guarded:
                # in-graph sentinel + skip: non-finite float fetches
                # suppress the whole state update on-device (the
                # guardian's skip-step rung); baked into the trace key
                # via trace_flag_values.  n_watch excludes the probe's
                # grad fetches: an exploding-but-finite gradient is the
                # probe's business, and a non-finite one already poisons
                # a watched fetch downstream
                fn = guardian.wrap_step_guard(fn, state_in, state_out,
                                              n_watch=len(fetch_names))
            if probe is not None:
                fn = monitor.health.wrap_step_probe(
                    fn, probe, len(fetch_names), guarded, state_in,
                    state_out)
        # the guard's trailing ok fetch and the probe's stats array are
        # fetches to the placement too
        jit_kwargs, placed = self._jit_kwargs(
            program, traced, feed_names, feed_vals, state_in, state_out,
            len(fetch_names) + (probe is not None) + guarded)
        # jax.jit is lazy (tracing deferred to the first call): the real
        # jaxpr cost is the trace_program above
        jitted = jax.jit(
            compile_cache.name_step(fn, self._label, program),
            donate_argnums=(1,) if self.donate_state else (), **jit_kwargs)
        compile_cache.note_phase("program_trace_s", t)
        return compile_cache.store(tkey, CompiledStep(
            jitted, feed_names, state_in, state_out, fetch_names,
            guarded=guarded, probe=probe, **placed))

    # -- the step --------------------------------------------------------
    def _run_step(self, program, scope, feed, fetch_list, return_numpy):
        # the whole call is one span, so that a profiler trace with no
        # span of the caller's in it still has the step
        with RecordEvent(self._name + "/step"):
            return self._step(program, scope, feed, fetch_list,
                              return_numpy)

    def _step(self, program, scope, feed, fetch_list, return_numpy):
        name = self._name
        # a single module-global bool read when telemetry is off — the
        # whole StepStats assembly is behind it
        mon_t0 = time.perf_counter() if monitor.enabled() else None
        feed = dict(feed or {})
        fetch_names = _names(fetch_list)
        feed_names = sorted(feed.keys())
        # cast feeds to the var's materialized dtype when the program
        # declares one; jax arrays already on device pass through
        # untouched (the input-pipeline fast path: py_reader/double-buffer
        # feeds stay device-resident instead of re-crossing the host link
        # every step)
        block = program.global_block()
        feed_vals = [_coerce_feed(block, n, feed[n]) for n in feed_names]

        # this run's step index (the PRNG fold-in counter before this
        # step bumps it): fault schedules and guardian records key on it
        step_idx = self._run_counter
        if fault.active():
            # drills mutate feed_vals in place (poison_batch); shapes/
            # dtypes are preserved, so the signature below is unaffected
            fault.fire("executor/feed", step_idx,
                       feed_names=feed_names, feed_vals=feed_vals)

        # feed_vals stays the batch AS FED (post-drill, pre-pad): what
        # the guardian quarantines and the probe's replay stashes must
        # match what the reader yielded, not a mesh-padded copy
        step_feeds = self._pad_uneven(feed_vals)
        dev = self._first_device()
        feed_sig, compiled = self._entry(
            program, scope, feed_names, step_feeds, fetch_names, dev)

        with RecordEvent(name + "/h2d_transfer"):
            feed_dev, state_dev = self._put(compiled, step_feeds, scope, dev)
        seed = program.random_seed or 0
        rng = jax.random.key(
            np.uint32(seed) if seed else self._auto_seed(),
            impl="rbg" if flags.flag("fast_prng") else None,
        )
        rng = jax.random.fold_in(rng, self._run_counter)
        self._run_counter += 1

        t0 = time.perf_counter() \
            if self._times_steps and flags.flag("benchmark") else None
        # an unseen feed signature's first call pays jaxpr trace + XLA
        # compile (or a persistent-cache deserialize) — recorded as a
        # compile span so cache hits are observable as its disappearance
        cold = feed_sig not in compiled.seen_sigs
        # correlation tags: fingerprint is memoized per program version
        # (one attribute read when warm), computed only when some
        # observability layer is on — a dark process pays nothing here
        fp = compile_cache.program_fingerprint(program) \
            if (mon_t0 is not None or is_profiling()) else None
        # bucket hint: the goodput ledger (and offline trace_summary)
        # classify the cold step span as compile badput, the warm one as
        # the compute remainder — by the producer's own verdict, not by
        # name guessing
        span_args = {"run_id": monitor.run_id(), "fingerprint": fp[:12],
                     "step": step_idx,
                     "bucket": "trace_compile" if cold else "compute"} \
            if fp else None
        if fault.active():
            fault.fire("executor/dispatch", step_idx)
        with RecordEvent(name + "/run"):
            with (_cold_call(name, span_args, compiled, feed_sig) if cold else
                  RecordEvent(name + "/dispatch", args=span_args)):
                with self._dispatch_scope(dev):
                    fn = compiled.fn
                    slot = (feed_sig, getattr(dev, "id", 0))
                    if cold and program_profile.capture_enabled() \
                            and slot not in compiled.aot \
                            and not flags.flag("debug_nans"):
                        # the step is AOT-compiled here — profiled
                        # (cost/memory analysis) and HBM-preflighted
                        # BEFORE its first dispatch — and the same
                        # executable serves every later step of this
                        # signature: one compile total.  SPMD analyses
                        # are per-device, which is the granularity the
                        # preflight compares against.  debug_nans keeps
                        # the jit path (its nan re-run machinery lives
                        # there).
                        compiled.aot[slot] = program_profile.capture(
                            fp if fp is not None else
                            compile_cache.program_fingerprint(program),
                            feed_sig, compiled.fn,
                            (feed_dev, state_dev, rng),
                            device=dev, kind=name,
                            fetch_names=tuple(fetch_names),
                            partition=compiled.partition_key)
                    # debug_nans checked at dispatch too: a previously
                    # captured executable must not bypass the jit
                    # path's op-level nan re-run machinery
                    if compiled.aot and not flags.flag("debug_nans"):
                        fn = compiled.aot.get(slot, fn)
                    # an AOT executable that rejects its args raises
                    # here: re-dispatching through jit would hide a
                    # second compile of the step
                    fetches, new_state = fn(feed_dev, state_dev, rng)
        compiled.seen_sigs.add(feed_sig)

        ok_flag = None
        if compiled.guarded:
            # the in-graph sentinel's verdict rides as a trailing fetch;
            # user-visible fetches exclude it
            ok_flag = fetches[-1]
            fetches = fetches[:-1]
        if compiled.probe is not None:
            # per-layer health stats ride second-to-last (before ok);
            # note_step stashes the replay context every step and syncs
            # the stats to host only on the FLAGS_health_every cadence
            health_stats = fetches[-1]
            fetches = fetches[:-1]
            monitor.health.note_step(
                name, step_idx, compiled.probe, health_stats,
                program=program, scope=scope, rng=rng,
                feed_names=feed_names, feed_vals=feed_vals,
                platform=dev.platform)

        for n, v in zip(compiled.state_out, new_state):
            scope.set_var(n, v)

        if fault.active():
            fetches = list(fetches)
            fault.fire("executor/step_done", step_idx, scope=scope,
                       state_names=compiled.state_out,
                       fetch_names=compiled.fetch_names, fetches=fetches)
        if step_feeds is not feed_vals:
            fetches = self._trim_fetches(block, compiled.fetch_names,
                                         fetches, feed_vals, step_feeds)

        np_fetches = None
        if flags.flag("check_nan_inf"):
            ctx = lambda: "run_id=%s fp12=%s step=%d" % (  # noqa: E731
                monitor.run_id(),
                compile_cache.program_fingerprint(program)[:12], step_idx)
            # a side copy, so return_numpy=False still hands back device
            # arrays (the check implies a per-step sync, not a type
            # change)
            np_fetches = [self._fetch_to_np(f) for f in fetches]
            try:
                _check_finite(zip(compiled.fetch_names, np_fetches),
                              context=ctx)
                if self._check_state:
                    _check_finite(zip(compiled.state_out, new_state),
                                  context=ctx)
            except RuntimeError as e:
                raise _with_provenance(e, compiled.probe, step_idx) \
                    from None
        if t0 is not None:
            jax.block_until_ready(new_state if new_state else fetches)
            print("[benchmark] step %.3f ms"
                  % ((time.perf_counter() - t0) * 1e3))

        if return_numpy:
            with RecordEvent(name + "/fetch_sync"):
                fetches = np_fetches if np_fetches is not None else \
                    [self._fetch_to_np(f) for f in fetches]
        else:
            # async fast path: fetches stay (possibly sharded) device
            # arrays; bound the host's run-ahead on the dispatch window
            # (sync only at window edges, never per step)
            self._dispatch_queue.push_step(fetches, new_state)
        if mon_t0 is not None:
            self._before_record(compiled, cold, mon_t0, fp)
            monitor.record_step(
                name, time.perf_counter() - mon_t0,
                _batch_examples(block, feed_names, feed_vals),
                len(self._dispatch_queue), device=dev,
                warm=not cold, fingerprint=fp,
                extras=_step_extras(program, feed_names, feed_vals,
                                    compiled.fetch_names, fetches))
            self._after_record()
        # guardian hook LAST (after telemetry): a ladder decision raises
        # out of run() with this step's record already published.  One
        # module-global read when no guardian is installed.
        g = guardian.active()
        if g is not None:
            g.note_step(name, step_idx, ok=ok_flag,
                        fetch_names=compiled.fetch_names, fetches=fetches,
                        feed=(feed_names, feed_vals), sync=return_numpy)
        elif ok_flag is not None:
            guardian.warn_unobserved_skip_guard(self)
        return fetches


class Executor(StepPath):
    """Runs Programs on a Place (reference executor.py:256 / executor.cc:85):
    the step path placed on one device."""

    _name = "executor"
    _label = "exe"

    def __init__(self, place=None, donate_state=True):
        """``donate_state=False`` keeps input state buffers alive after
        the step (no XLA donation): required when several executors
        share one scope concurrently (inference predictor clones) —
        donation would delete the weight buffers under the other
        executors.  Training keeps the default in-place donation."""
        super().__init__()
        self.place = default_place(place)
        self.donate_state = donate_state

    def close(self):
        self.sync()
        self._cache.clear()

    def _first_device(self):
        return self.place.jax_device()

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        scope=None,
        return_numpy=True,
    ):
        """Execute ``program``: feed dict name->array, fetch list of
        Variables/names; persistable results are committed back to scope."""
        return self._run_step(
            program if program is not None else default_main_program(),
            scope if scope is not None else global_scope(),
            feed, fetch_list, return_numpy)

    def cost_analysis(self, program=None, feed=None, fetch_list=None,
                      scope=None, compile_if_missing=True):
        """XLA compiled-module cost analysis for the step this
        (program, feed signature, fetch set) lowers to: exact flops /
        bytes-accessed per step from the compiler's own accounting (the
        `est_mfu` heuristic's ground truth; bench.py --exact_mfu).

        Served from the program-profile registry when the program was
        already compiled (the cold dispatch captured the analysis at
        zero extra cost) — *free* for warm programs.  Never-run programs
        fall back to one explicit lower+compile (and seed the registry
        so the next call is free); ``compile_if_missing=False`` returns
        None instead of paying that compile."""
        if program is None:
            program = default_main_program()
        feed = dict(feed or {})
        scope = scope if scope is not None else global_scope()
        fetch_names = _names(fetch_list)
        feed_names = sorted(feed.keys())
        block = program.global_block()
        feed_vals = [_coerce_feed(block, n, feed[n]) for n in feed_names]
        feed_sig = _feed_sig(feed_names, feed_vals)
        fp = compile_cache.program_fingerprint(program)
        prof = program_profile.get(fp, feed_sig, kind="executor",
                                   fetch_names=tuple(fetch_names))
        if prof is not None and prof.cost:
            return dict(prof.cost)
        if not compile_if_missing:
            return None
        dev = self._first_device()
        _, compiled = self._entry(
            program, scope, feed_names, feed_vals, fetch_names, dev)
        state_vals = [np.asarray(scope.var(n)) for n in compiled.state_in]
        rng = jax.random.key(
            0, impl="rbg" if flags.flag("fast_prng") else None)
        # lower on the executor's device so the executable is the one a
        # run() of this signature would build; this compile is the
        # record's cold call
        t0 = time.perf_counter_ns()
        with self._dispatch_scope(dev):
            cexec = compiled.fn.lower(feed_vals, state_vals, rng).compile()
        compile_cache.close_record(t0)
        # seed the profile registry AND the entry's AOT-dispatch slot:
        # repeated cost_analysis calls are free, and a later run() of
        # the same signature dispatches through this executable instead
        # of paying a second backend compile (jax's AOT and jit call
        # paths share no compile cache)
        program_profile.store_compiled(fp, feed_sig, cexec,
                                       device=dev, kind="executor",
                                       fetch_names=tuple(fetch_names))
        compiled.aot[(feed_sig, getattr(dev, "id", 0))] = cexec
        return dict(cexec.cost_analysis())


def _check_finite(named_vals, context=None):
    """FLAGS_check_nan_inf parity (operator.cc:31,717): verify every
    floating output of the step; raise naming the FIRST bad variable and
    summarizing every other one found in the same scan (one host pass —
    the whole step already synced, so scanning to the end costs nothing
    and turns "loss is nan" into "loss, fc_0.w_0@GRAD, ... are nan").
    ``context`` (a callable, evaluated only on failure) adds the run_id
    / program fingerprint / step index so the raise correlates with the
    JSONL and trace records of the same step."""
    from .core import bfloat16

    bad_vars = []
    first_kind = None
    for name, v in named_vals:
        a = np.asarray(v)
        if a.dtype == bfloat16:
            a = a.astype(np.float32)  # np.isfinite lacks a bf16 loop
        if np.issubdtype(a.dtype, np.floating) and not np.isfinite(a).all():
            bad_vars.append(name)
            if first_kind is None:
                first_kind = "nan" if np.isnan(a).any() else "inf"
    if not bad_vars:
        return
    where = ""
    if context is not None:
        try:
            where = " [%s]" % (context() if callable(context)
                               else context)
        except Exception:  # noqa: BLE001 — the raise must land
            pass
    others = "" if len(bad_vars) == 1 else \
        " (+%d more non-finite: %s)" % (
            len(bad_vars) - 1, ", ".join(repr(n) for n in bad_vars[1:5]))
    raise RuntimeError(
        "check_nan_inf: variable %r contains %s after step%s%s "
        "(enable FLAGS_debug_nans to localize the producing op)"
        % (bad_vars[0], first_kind, others, where))


def _with_provenance(err, probe, step_idx):
    """Augment a check_nan_inf raise with op-level NaN provenance when
    the health probe is on: replay the stashed step off the hot path and
    name the first op whose output went non-finite.  The original error
    text is preserved; provenance failures never mask it."""
    if probe is None:
        return err
    from .monitor import health

    try:
        prov = health.nan_provenance(step_idx)
    except Exception:  # noqa: BLE001 — diagnostics must not mask the raise
        return err
    if not prov or not prov.get("found"):
        return err
    return RuntimeError(
        "%s; first non-finite op: %s -> %r (op #%d%s)"
        % (err, prov["op_type"], prov["out_var"], prov["op_index"],
           ", layer %s" % prov["layer"] if prov.get("layer") else ""))
