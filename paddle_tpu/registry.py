"""Operator registry: shape/dtype inference + JAX compute + gradient makers.

Capability parity with the reference's op registry stack
(``paddle/fluid/framework/op_registry.h``, ``op_info.cc``,
``grad_op_desc_maker.h``, and OperatorWithKernel dispatch
``operator.h:315``), re-designed TPU-first:

* An op's *kernel* is a pure JAX function ``compute(ins, attrs, ctx)`` where
  ``ins`` maps input slot -> list of jax arrays.  There is no per-device
  kernel dispatch (OpKernelType, operator.cc:672): XLA owns placement and
  fusion; a single traceable function covers CPU/TPU, and Pallas kernels
  slot in as alternative compute bodies for hot ops (see ``ops/pallas/``).
* Gradients: instead of 300 hand-written grad kernels, the default grad maker
  wires a generic ``<type>_grad`` op whose kernel re-runs the forward under
  ``jax.vjp`` and applies the output cotangents.  Because the whole program
  is one traced jaxpr, XLA CSE merges the recomputed forward with the
  original — the recompute is free in the compiled HLO.  Ops that must not
  be re-executed (stateful randomness like dropout) register custom grad
  makers that consume saved forward outputs (e.g. the dropout mask), exactly
  the cases where the reference saves intermediates too.
* Shape inference (``infer``) runs at append time; it must handle -1 batch
  dims.  This is the build-time half of the reference's InferShape.
"""

import re

import numpy as np

import jax
import jax.numpy as jnp

from .core import convert_dtype, dtype_is_floating
from .framework import grad_var_name

__all__ = [
    "OpDef",
    "register_op",
    "get_op_def",
    "infer_op",
    "compute_op",
    "compute_ops",
    "register_chain",
    "make_grad_ops",
    "OPS",
]

OPS = {}


class ComputeContext:
    """Per-trace context handed to kernels: PRNG key material and flags."""

    def __init__(self, key=None, is_test=False, platform=None, mesh=None):
        self._key = key
        self.is_test = is_test
        self.amp = None  # AMPPolicy (contrib.mixed_precision) or None
        # the executing device's platform ("cpu"/"tpu"), threaded from the
        # executor's Place so Pallas call sites pick mosaic vs interpret
        self.platform = platform
        # the ParallelExecutor's device mesh (None single-device): ops with
        # mesh-aware lowerings (fused_attention -> ring attention over sp)
        # consult it at trace time
        self.mesh = mesh
        # {state var name: PartitionSpec} as the ParallelExecutor placed
        # the persistable state on the mesh — ops with sharded lowerings
        # (sparse embedding lookup/update over row-sharded tables) read
        # their operands' placement from here.  Empty single-device.
        self.state_specs = {}
        # the Operator currently being traced (set by compute_op): gives
        # kernels access to their input/output VAR NAMES so they can
        # consult state_specs
        self.op = None

    def rng_key(self, op_index):
        if self._key is None:
            raise RuntimeError(
                "op requires randomness but the executor provided no PRNG key"
            )
        return jax.random.fold_in(self._key, op_index)


class OpDef:
    def __init__(
        self,
        type,
        inputs,
        outputs,
        infer,
        compute,
        grad=None,
        no_grad_inputs=(),
        stateful_random=False,
        doc="",
        work=None,
        stored=None,
    ):
        self.type = type
        self.input_slots = tuple(inputs)
        self.output_slots = tuple(outputs)
        self.infer = infer
        self.compute = compute
        # grad: None => not differentiable; "auto" => generic vjp;
        #       callable(op, block, no_grad_set) -> list of op-spec dicts
        self.grad = grad
        self.no_grad_inputs = frozenset(no_grad_inputs)
        self.stateful_random = stateful_random
        self.doc = doc
        # work(ins, attrs, grad) -> [(part, flops, least_bytes, (M, K, N))]:
        # what the op's dense products must do, from the traced inputs the
        # body is handed (after AMP's casts).  ``grad`` is () for the op
        # itself (one part, ``fwd``) and, asked by the generic gradient of
        # THIS definition, the input slots that get a gradient: one part a
        # slot, in their order, named for the scope its operations run under
        self.work = work
        # stored(ins, attrs) -> the output slots the compiled step keeps as
        # arrays of their own, from the traced inputs the body is handed: the
        # FORWARD op's values go into the environment behind
        # ``lax.optimization_barrier`` (``compute_op``), so XLA writes them
        # once and every later reader — a product's forward, its weight
        # gradient — reads the stored array instead of carrying the op's
        # body inside its own fusion, evaluated again for every output tile.
        # The generic gradient differentiates ``compute`` alone: no barrier
        # lands on a cotangent
        self.stored = stored


def register_op(
    type,
    inputs,
    outputs,
    infer,
    compute,
    grad="auto",
    no_grad_inputs=(),
    stateful_random=False,
    doc="",
    work=None,
    stored=None,
):
    if type in OPS:
        raise ValueError("op type %r already registered" % type)
    OPS[type] = OpDef(
        type, inputs, outputs, infer, compute, grad, no_grad_inputs,
        stateful_random, doc, work, stored,
    )
    return OPS[type]


def get_op_def(type):
    if type not in OPS:
        raise KeyError("op type %r is not registered" % type)
    return OPS[type]


def infer_op(op, block):
    """Run build-time shape/dtype inference for ``op`` in ``block``."""
    d = get_op_def(op.type)
    if d.infer is not None:
        d.infer(op, block)


_SCOPE_UNSAFE = re.compile(r"[^\w.\-]")


def fluid_scope_name(op):
    """``fluid[<op type>]<first output variable>``: the ``jax.named_scope``
    one lowered Fluid op runs under.  The ``fluid[`` marker is what readers
    of a trace match (``benchmark/trace/scopes.py``): no entry of jax's own
    name stack (``jit(..)``, ``jvp(..)``, ``transpose(..)``, ``checkpoint``,
    ``shard_map``) is spelled with a bracket.  Of the output's name only
    letters, digits, ``_``, ``.`` and ``-`` are kept, anything else becomes
    ``.`` (``fc_0.tmp_0@GRAD`` -> ``fc_0.tmp_0.GRAD``): ``/`` separates the
    stack's entries, ``:`` ends a name in the profiler's ``tf_op``, and XLA
    reads a location ``<name>@<function>`` and keeps the name only — an
    ``@`` cut the variable AND the rest of the stack (my chip run, PR 24).

    Scope names are metadata, which jax leaves out of its persistent-cache
    key: respell this function and a warm cache keeps serving the old
    names under an unchanged module name (seen on the chip, PR 24) until
    the directory is cleared.  A renamed variable or op is safe: it moves
    the fingerprint, and with it ``compile_cache.name_step``'s module
    name, which IS in the key."""
    out = next((n for names in op.outputs.values() for n in names if n), "")
    return "fluid[%s]%s" % (op.type, _SCOPE_UNSAFE.sub(".", out))


def note_work(op, parts):
    """Note what a ``work`` rule answered for ``op`` into the compile record
    open on this thread, under the op's own scope name
    (``compile_cache.note_op_work``; nothing without a record: eager
    programs, the reference).  Called where the scope is opened, while the
    step is traced for lowering, never per step."""
    from .compile_cache import note_op_work

    scope = fluid_scope_name(op)
    for part, flops, least_bytes, shape in parts:
        note_op_work(scope, op.type, part, flops, least_bytes, shape)


def _store(op_type, slots, outs):
    """``outs`` with ``slots`` — what the definition's ``stored`` rule
    answered — behind a barrier; ``kernel_bodies`` counts the sites
    (``<type>:stored``, or ``<type>:inline`` where the rule kept nothing)."""
    from .compile_cache import note_kernel_body

    note_kernel_body(op_type, "stored" if slots else "inline")
    outs = dict(outs)
    for slot in slots:
        outs[slot] = jax.lax.optimization_barrier(outs[slot])
    return outs


def compute_op(op, env, ctx, op_index=0):
    """Execute one op inside a trace: read inputs from env, write outputs."""
    d = get_op_def(op.type)
    # empty names are "holes" (e.g. pruned grad slots): pass/collect None.
    # Out:: slots of grad ops are lenient — an optional forward output
    # (e.g. sequence_pool MaxIndex under "last") may never have been
    # produced.  A GRAD:: name is only lenient when its forward output is
    # itself absent; a missing gradient for a produced output is a real
    # wiring bug and must stay a loud KeyError, not silent zeros.
    ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if not n:
                vals.append(None)
            elif slot.startswith("Out::"):
                vals.append(env.get(n))
            elif slot.startswith("GRAD::"):
                fwd = n[: -len("@GRAD")] if n.endswith("@GRAD") else n
                vals.append(env.get(n) if fwd not in env else env[n])
            else:
                vals.append(env[n])
        ins[slot] = vals
    # save/restore: region ops (pipeline_region, control flow) re-enter
    # compute_op for their body ops under the same ctx
    prev_op, ctx.op = ctx.op, op
    try:
        # everything this op traces — the AMP casts, the kernel, the
        # generic-gradient vjp — carries the op's Fluid name in the
        # profiler's trace; region bodies nest, the innermost scope owns
        with jax.named_scope(fluid_scope_name(op)):
            if ctx.amp is not None:
                ins = ctx.amp.cast_inputs(op.type, ins)
            if d.work is not None:
                note_work(op, d.work(ins, op.attrs, ()))
            outs = d.compute(ins, op.attrs, ctx, op_index)
            if d.stored is not None:
                outs = _store(op.type, d.stored(ins, op.attrs), outs)
    finally:
        ctx.op = prev_op
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        for name, val in zip(names, vals):
            if name:
                env[name] = val
    return env


# A chain rule lowers SEVERAL consecutive ops with one body: ``rule(ops, i,
# env, ctx, kept)`` is asked at every op of its first type, and returns how
# many ops from ``ops[i]`` on it lowered — their results that the rest of
# the program reads written to ``env`` — or 0 to leave them op by op.
# ``kept`` are the names the step fetches or writes back: a chain never
# swallows one.  The Fluid program is not edited; the rule reads it.
CHAIN_RULES = {}


def register_chain(first_type, rule):
    if first_type in CHAIN_RULES:
        raise ValueError("op type %r already starts a chain" % first_type)
    CHAIN_RULES[first_type] = rule


def compute_ops(ops, env, ctx, kept=()):
    """Lower a block's ``ops`` in order inside a trace: op by op
    (``compute_op``), but for the ops a chain rule takes together."""
    i = 0
    while i < len(ops):
        rule = CHAIN_RULES.get(ops[i].type)
        done = rule(ops, i, env, ctx, kept) if rule is not None else 0
        if not done:
            compute_op(ops[i], env, ctx, op_index=i)
            done = 1
        i += done
    return env


# --------------------------------------------------------------------------
# Generic gradient machinery
# --------------------------------------------------------------------------

GENERIC_GRAD_SUFFIX = "_grad"


def make_grad_ops(op, no_grad_set):
    """Return a list of grad-op specs for a forward op, or [] if none.

    A spec is a dict(type=..., inputs=..., outputs=..., attrs=...) with
    variable *names*.  Mirrors the reference's GradOpDescMaker protocol
    (grad_op_desc_maker.h) driven from backward.py.
    """
    d = get_op_def(op.type)
    if d.grad is None:
        return []
    if callable(d.grad):
        return d.grad(op, no_grad_set)
    if d.grad == "auto":
        return _auto_grad_maker(op, no_grad_set)
    raise ValueError("bad grad spec for op %r" % op.type)


def _auto_grad_maker(op, no_grad_set):
    """Default grad maker: one ``<type>_grad`` op taking all forward inputs,
    forward outputs, and output grads; producing input grads."""
    d = get_op_def(op.type)
    g_inputs = {}
    for slot, names in op.inputs.items():
        g_inputs[slot] = list(names)
    for slot, names in op.outputs.items():
        g_inputs["Out::" + slot] = list(names)
        g_inputs["GRAD::" + slot] = [grad_var_name(n) for n in names]
    g_outputs = {}
    any_grad = False
    for slot, names in op.inputs.items():
        if slot in d.no_grad_inputs:
            continue
        outs = []
        for n in names:
            if n in no_grad_set:
                outs.append("")  # hole: grad not needed
            else:
                outs.append(grad_var_name(n))
                any_grad = True
        g_outputs["GRAD::" + slot] = outs
    if not any_grad:
        return []
    attrs = dict(op.attrs)
    attrs["__fwd_type__"] = op.type
    return [
        dict(
            type=op.type + GENERIC_GRAD_SUFFIX,
            inputs=g_inputs,
            outputs=g_outputs,
            attrs=attrs,
        )
    ]


def _generic_grad_infer(gop, block):
    """Grad vars mirror the shape/dtype of their forward vars."""
    fwd_slots = [s for s in gop.inputs if not s.startswith(("Out::", "GRAD::"))]
    for slot in fwd_slots:
        out_slot = "GRAD::" + slot
        if out_slot not in gop.outputs:
            continue
        for fwd_name, g_name in zip(gop.inputs[slot], gop.outputs[out_slot]):
            if not g_name:
                continue
            fwd_var = block._find_var_recursive(fwd_name)
            if fwd_var is None:
                continue
            block.create_var(
                name=g_name,
                shape=fwd_var.shape,
                dtype=fwd_var.dtype,
                persistable=False,
            )


def _generic_grad_compute(ins, attrs, ctx, op_index):
    fwd_type = attrs["__fwd_type__"]
    fwd_def = get_op_def(fwd_type)
    fwd_attrs = {k: v for k, v in attrs.items()
                 if k not in ("__fwd_type__", "__fwd_op_index__")}
    # stateful-random forwards (nce sampling, dropout without its custom
    # grad) must re-draw the SAME randomness in the recompute: use the
    # forward op's trace index for the PRNG fold, not the grad op's
    op_index = attrs.get("__fwd_op_index__", op_index)

    primal_ins = {
        slot: vals
        for slot, vals in ins.items()
        if not slot.startswith(("Out::", "GRAD::"))
    }
    # differentiate only w.r.t. floating-point inputs
    diff_slots = []
    for slot, vals in primal_ins.items():
        if slot in fwd_def.no_grad_inputs:
            continue
        if all(dtype_is_floating(v.dtype) for v in vals) and vals:
            diff_slots.append(slot)

    def fwd_fn(diff_vals):
        full = dict(primal_ins)
        full.update(diff_vals)
        outs = fwd_def.compute(full, fwd_attrs, ctx, op_index)
        # canonicalize: slot -> list
        canon = {}
        for slot in fwd_def.output_slots:
            v = outs.get(slot)
            if v is None:
                continue
            canon[slot] = list(v) if isinstance(v, (list, tuple)) else [v]
        return canon

    # build cotangents: use provided GRAD:: slots, zeros elsewhere (an
    # integer output, e.g. a router's expert ids, takes jax's float0)
    def zero_ct(v):
        if dtype_is_floating(v.dtype):
            return jnp.zeros_like(v)
        return np.zeros(v.shape, jax.dtypes.float0)

    def pull_back(slots):
        outs, vjp_fn = jax.vjp(
            fwd_fn, {slot: primal_ins[slot] for slot in slots})
        cts = {}
        for slot, vals in outs.items():
            gslot = "GRAD::" + slot
            if gslot in ins and ins[gslot]:
                gvals = ins[gslot]
                # cotangents must match the recomputed forward's output
                # dtype: under the AMP policy a white-listed forward yields
                # bf16 while the incoming cotangent may be fp32 (or vice
                # versa)
                cts[slot] = [
                    g.astype(v.dtype)
                    if g is not None and dtype_is_floating(v.dtype)
                    else zero_ct(v)
                    for g, v in zip(gvals, vals)
                ]
            else:
                cts[slot] = [zero_ct(v) for v in vals]
        (grads,) = vjp_fn(cts)
        return {"GRAD::" + slot: grads[slot] for slot in slots}

    if fwd_def.work is None:
        return pull_back(diff_slots)
    # a definition that counts its products: each wanted gradient is pulled
    # back alone, under the part's own plain scope (``dx`` / ``dw``), so a
    # trace tells the two products of one ``fluid[mul_grad]..`` apart.  The
    # one-sided pull-backs' dead forwards and their second cast of the
    # cotangent fold away: the compiled step is the one-vjp spelling's
    # instruction for instruction (compiled for a described v5e, PR 51).
    held = getattr(ctx.op, "outputs", None)
    wanted = tuple(
        slot for slot in diff_slots
        if held is None or any(held.get("GRAD::" + slot, ())))
    parts = fwd_def.work(primal_ins, fwd_attrs, wanted)
    if ctx.op is not None:
        note_work(ctx.op, parts)
    result = {}
    for slot, part in zip(wanted, parts):
        with jax.named_scope(part[0]):
            result.update(pull_back([slot]))
    return result


class _GenericGradRegistrar:
    """Lazily register ``<type>_grad`` op defs the first time they appear."""

    @staticmethod
    def ensure(grad_type):
        if grad_type in OPS:
            return
        if not grad_type.endswith(GENERIC_GRAD_SUFFIX):
            raise KeyError(grad_type)
        fwd_type = grad_type[: -len(GENERIC_GRAD_SUFFIX)]
        if fwd_type not in OPS:
            raise KeyError(grad_type)
        OPS[grad_type] = OpDef(
            grad_type,
            inputs=(),
            outputs=(),
            infer=_generic_grad_infer,
            compute=_generic_grad_compute,
            grad=None,
            doc="auto-vjp gradient of %s" % fwd_type,
        )


_orig_get = get_op_def


def get_op_def(type):  # noqa: F811 — wraps to lazily add _grad defs
    if type not in OPS and type.endswith(GENERIC_GRAD_SUFFIX):
        try:
            _GenericGradRegistrar.ensure(type)
        except KeyError:
            pass
    if type not in OPS:
        raise KeyError("op type %r is not registered" % type)
    return OPS[type]


# --------------------------------------------------------------------------
# Shape-inference helpers shared by op definitions
# --------------------------------------------------------------------------

def set_output(op, block, slot, shape, dtype, lod_level=0):
    """Create/refresh the output var for slot (single-var slots)."""
    names = op.outputs.get(slot, [])
    for name in names:
        v = block._find_var_recursive(name)
        if v is None:
            v = block.create_var(name=name)
        v.shape = tuple(int(s) for s in shape) if shape is not None else None
        v.dtype = convert_dtype(dtype) if dtype is not None else None
        v.lod_level = lod_level


def in_var(op, block, slot, idx=0):
    names = op.inputs.get(slot, [])
    if not names:
        return None
    return block._find_var_recursive(names[idx])


def same_shape_infer(in_slot, out_slot):
    def infer(op, block):
        x = in_var(op, block, in_slot)
        set_output(op, block, out_slot, x.shape, x.dtype, x.lod_level)

    return infer


def broadcast_shapes(s1, s2):
    """Numpy-style broadcast of shapes with -1 (dynamic) dims propagated."""
    out = []
    for a, b in zip(reversed(s1), reversed(s2)):
        if a == -1 or b == -1:
            out.append(-1 if (a in (-1, 1) and b in (-1, 1)) else max(a, b))
        elif a == 1:
            out.append(b)
        elif b == 1 or a == b:
            out.append(a)
        else:
            raise ValueError("cannot broadcast %s with %s" % (s1, s2))
    longer = s1 if len(s1) > len(s2) else s2
    out.extend(reversed(longer[: abs(len(s1) - len(s2))]))
    return tuple(reversed(out))


def int_list(v, n):
    """Normalize a scalar-or-sequence attr (strides/paddings/ksize...) to a
    length-n list (shared by conv/pool ops and CNN layers)."""
    if isinstance(v, (list, tuple)):
        if len(v) != n:
            raise ValueError(
                "expected %d values, got %r" % (n, list(v))
            )
        return list(v)
    return [v] * n
