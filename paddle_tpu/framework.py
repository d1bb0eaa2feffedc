"""Program-graph IR: Program / Block / Operator / Variable / Parameter.

Capability parity with the reference's Python graph builder
(``python/paddle/fluid/framework.py`` — Variable:204, Operator:494, Block:920,
Program:1404, Parameter:1964) and the underlying ProgramDesc protobuf IR
(``paddle/fluid/framework/framework.proto:42-183``), re-designed TPU-first:

* There is no protobuf / C++ OpDesc mirror.  The Python objects ARE the IR;
  the executor lowers a Program directly to a jaxpr by tracing the registered
  JAX compute function of every op in order, then jit-compiles the whole
  program once (XLA fuses across op boundaries — the program is one HLO
  module, the TPU analog of whole-graph compilation named in the north star).
* Shape/dtype inference runs eagerly at ``append_op`` time through the op
  registry (the reference runs InferShape both at build time from Python and
  again inside OperatorWithKernel::RunImpl; with static shapes + XLA we only
  need the build-time pass).
* Blocks still exist — control-flow ops (while/cond, see
  ``layers/control_flow.py``) own sub-blocks which lower to ``lax.scan`` /
  ``lax.cond`` / ``lax.while_loop`` so everything stays inside one jit.
* Programs serialize to a plain JSON-able dict (``Program.to_dict`` /
  ``Program.from_dict``) which replaces ProgramDesc serialization for
  save/load_inference_model parity.
"""

import collections
import contextlib
import copy
import json

import numpy as np

from . import core, unique_name
from .core import VarType, convert_dtype

__all__ = [
    "Program",
    "Block",
    "Operator",
    "Variable",
    "Parameter",
    "default_startup_program",
    "default_main_program",
    "program_guard",
    "name_scope",
    "grad_var_name",
    "GRAD_VAR_SUFFIX",
]

GRAD_VAR_SUFFIX = "@GRAD"


def grad_var_name(var_name):
    """Name of the gradient variable of ``var_name`` (reference
    framework.py:grad_var_name / framework.cc GradVarName)."""
    return var_name + GRAD_VAR_SUFFIX


_name_scope_stack = []


@contextlib.contextmanager
def name_scope(prefix=None):
    """Name scoping for profiling/visualization (reference framework.py:80)."""
    _name_scope_stack.append(prefix or "")
    try:
        yield
    finally:
        _name_scope_stack.pop()


def _full_name_scope():
    return "/".join([s for s in _name_scope_stack if s])


class Variable:
    """A typed symbol in a Block (reference framework.py:204).

    Concrete storage lives in a ``Scope`` (name -> jax.Array); a Variable is
    only the compile-time description: shape (with -1 batch dims), dtype,
    persistable (parameters / optimizer state survive across executor runs),
    stop_gradient (backward pruning), lod_level (sequence nesting parity —
    packed representation, see ``paddle_tpu.sequence``).
    """

    def __init__(
        self,
        block,
        name=None,
        shape=None,
        dtype=None,
        type=VarType.DENSE_TENSOR,
        persistable=False,
        stop_gradient=False,
        is_data=False,
        lod_level=0,
        initializer=None,
        **kwargs,
    ):
        self.block = block
        if name is None:
            name = unique_name.generate("_generated_var")
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = convert_dtype(dtype) if dtype is not None else None
        self.type = type
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.lod_level = lod_level
        self.initializer = initializer
        # op that produced this var most recently (set by append_op)
        self.op = None
        # name of the companion [batch] int32 length var for padded
        # sequences (the LoD replacement; see ops/sequence.py)
        self._seq_len_name = None

    # ---- properties used throughout layers --------------------------------
    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def astype_desc(self):
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": str(self.dtype) if self.dtype is not None else None,
            "type": self.type,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "is_data": self.is_data,
            "lod_level": self.lod_level,
        }

    def __repr__(self):
        return "Variable(name=%s, shape=%s, dtype=%s%s)" % (
            self.name,
            self.shape,
            self.dtype,
            ", persistable" if self.persistable else "",
        )

    __str__ = __repr__


class Parameter(Variable):
    """A persistable, trainable Variable (reference framework.py:1964)."""

    def __init__(self, block, shape, dtype, **kwargs):
        if shape is None or dtype is None:
            raise ValueError("Parameter must have shape and dtype")
        for s in shape:
            if s <= 0:
                raise ValueError("each dim of Parameter must be > 0, got %s" % (shape,))
        kwargs.setdefault("persistable", True)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)
        self.trainable = kwargs.get("trainable", True)
        self.optimize_attr = kwargs.get("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.get("regularizer", None)
        self.gradient_clip_attr = kwargs.get("gradient_clip_attr", None)
        self.do_model_average = kwargs.get("do_model_average", None)

    def __repr__(self):
        return "Parameter(name=%s, shape=%s, dtype=%s)" % (
            self.name,
            self.shape,
            self.dtype,
        )

    __str__ = __repr__


class Operator:
    """One node of the program graph (reference framework.py:494 /
    framework.proto:42 OpDesc).

    inputs/outputs map *slot* names to lists of variable names; attrs is a
    plain dict of JSON-able values.  Appending an operator immediately runs
    the registered shape/dtype inference so downstream layers can size
    parameters — the build-time half of the reference's InferShape.
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs = {}
        self.outputs = {}
        self.attrs = dict(attrs) if attrs else {}
        ns = _full_name_scope()
        if ns:
            self.attrs.setdefault("op_namescope", ns)

        def _canon(mapping):
            out = collections.OrderedDict()
            if not mapping:
                return out
            for slot, vs in mapping.items():
                if vs is None:
                    out[slot] = []
                    continue
                if not isinstance(vs, (list, tuple)):
                    vs = [vs]
                out[slot] = [v.name if isinstance(v, Variable) else v for v in vs]
            return out

        self.inputs = _canon(inputs)
        self.outputs = _canon(outputs)

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for vs in self.inputs.values() for n in vs]

    @property
    def output_arg_names(self):
        return [n for vs in self.outputs.values() for n in vs]

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def has_attr(self, name):
        return name in self.attrs

    def to_dict(self):
        return {
            "type": self.type,
            "inputs": {k: list(v) for k, v in self.inputs.items()},
            "outputs": {k: list(v) for k, v in self.outputs.items()},
            "attrs": _jsonable_attrs(self.attrs),
        }

    def __repr__(self):
        return "{%s: (%s) -> (%s)}" % (
            self.type,
            ", ".join("%s=%s" % kv for kv in self.inputs.items()),
            ", ".join("%s=%s" % kv for kv in self.outputs.items()),
        )

    __str__ = __repr__


def _jsonable_attrs(attrs):
    out = {}
    for k, v in attrs.items():
        if isinstance(v, np.dtype):
            v = str(v)
        elif isinstance(v, np.ndarray):
            v = v.tolist()
        elif isinstance(v, (np.integer,)):
            v = int(v)
        elif isinstance(v, (np.floating,)):
            v = float(v)
        out[k] = v
    return out


class Block:
    """An ordered list of Operators plus a symbol table of Variables
    (reference framework.py:920 / framework.proto:170 BlockDesc)."""

    def __init__(self, program, idx, parent_idx=-1, forward_block_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.forward_block_idx = forward_block_idx
        self.vars = collections.OrderedDict()  # name -> Variable
        self.ops = []

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    # ---- variable management ---------------------------------------------
    def create_var(self, **kwargs):
        name = kwargs.get("name", None)
        if name is not None and name in self.vars:
            return self.vars[name]
        var = Variable(self, **kwargs)
        self.vars[var.name] = var
        return var

    def create_parameter(self, **kwargs):
        # parameters always live in the top-level (global) block, like the
        # reference (framework.py Block.create_parameter promotes to global)
        global_block = self.program.global_block()
        param = Parameter(global_block, **kwargs)
        global_block.vars[param.name] = param
        return param

    def has_var(self, name):
        return name in self.vars

    def has_var_recursive(self, name):
        return self._find_var_recursive(name) is not None

    def var(self, name):
        v = self.vars.get(name)
        if v is None:
            raise ValueError("var %r does not exist in block %d" % (name, self.idx))
        return v

    def _find_var_recursive(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def var_recursive(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("var %r not found in block %d or ancestors" % (name, self.idx))
        return v

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def rename_var(self, old_name, new_name):
        self.program._version += 1
        v = self.vars.pop(old_name)
        v.name = new_name
        self.vars[new_name] = v
        for op in self.ops:
            for slot, names in op.inputs.items():
                op.inputs[slot] = [new_name if n == old_name else n for n in names]
            for slot, names in op.outputs.items():
                op.outputs[slot] = [new_name if n == old_name else n for n in names]
        return v

    # ---- op management ----------------------------------------------------
    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type=type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.append(op)
        self._infer_and_mark(op)
        return op

    def _prepend_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type=type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.insert(0, op)
        self._infer_and_mark(op)
        return op

    def insert_op(self, index, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type=type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.insert(index, op)
        self._infer_and_mark(op)
        return op

    def _infer_and_mark(self, op):
        from .registry import infer_op  # local import to avoid cycle

        self.program._version += 1
        infer_op(op, self)
        # propagate the sequence-length companion (the padded-batch analog
        # of the reference's LoD propagation through ops): outputs inherit
        # the first input's length var unless they set their own
        seq_len = None
        for name in op.input_arg_names:
            v = self._find_var_recursive(name) if name else None
            if v is not None and getattr(v, "_seq_len_name", None):
                seq_len = v._seq_len_name
                break
        for name in op.output_arg_names:
            v = self._find_var_recursive(name)
            if v is not None:
                v.op = op
                if seq_len and not getattr(v, "_seq_len_name", None):
                    v._seq_len_name = seq_len

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "forward_block_idx": self.forward_block_idx,
            "vars": [v.astype_desc() | {"is_parameter": isinstance(v, Parameter)}
                     for v in self.vars.values()],
            "ops": [op.to_dict() for op in self.ops],
        }

    def __repr__(self):
        lines = ["Block(%d):" % self.idx]
        for v in self.vars.values():
            lines.append("  " + repr(v))
        for op in self.ops:
            lines.append("  " + repr(op))
        return "\n".join(lines)

    __str__ = __repr__


class _BuildSeconds(dict):
    """``Program._build_s``: pass name -> seconds the whole-program passes
    of build (``profiler.build_pass``) spent on THAT program object; a copy
    of the program starts at nothing."""

    def __deepcopy__(self, memo):
        return _BuildSeconds()


class Program:
    """A whole trainable/inferable computation (reference framework.py:1404 /
    framework.proto:183).  Holds nested blocks; block 0 is global."""

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._op_role_stack = []
        # fingerprint cache for executor compile caching
        self._version = 0
        # trace-time mixed-precision policy (contrib.mixed_precision)
        self._amp_policy = None
        # optional name of this program's compiled module in a profiler
        # trace (compile_cache.program_label); not structure, not hashed
        self._label = None
        # (variable name, field names): a builder's per-step counters, a
        # small float vector a caller may fetch WITH the loss; the
        # executor then writes them into that step's StepStats record
        # (executor._step_extras).  Not structure, not hashed.
        self.step_stats = None
        # what build's passes cost, until the first compile record takes it
        self._build_s = _BuildSeconds()

    # ---- block management --------------------------------------------------
    def global_block(self):
        return self.blocks[0]

    def block(self, idx):
        return self.blocks[idx]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None, forward_block_idx=-1):
        new_idx = len(self.blocks)
        parent = self.current_block_idx if parent_idx is None else parent_idx
        self.blocks.append(Block(self, new_idx, parent_idx=parent,
                                 forward_block_idx=forward_block_idx))
        self.current_block_idx = new_idx
        return self.current_block()

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    # ---- parameters --------------------------------------------------------
    def all_parameters(self):
        return self.global_block().all_parameters()

    def list_vars(self):
        for blk in self.blocks:
            yield from blk.vars.values()

    # ---- cloning / pruning -------------------------------------------------
    def clone(self, for_test=False):
        """Deep-copy the program.  ``for_test=True`` rewrites training-only
        behavior (dropout/batch_norm switch to inference mode) like the
        reference's ``Program.clone(for_test=True)`` + inference_optimize."""
        p = copy.deepcopy(self)
        if for_test:
            for blk in p.blocks:
                for op in blk.ops:
                    if "is_test" in _TEST_MODE_OPS.get(op.type, ()):
                        op.attrs["is_test"] = True
        return p

    def prune_feed_fetch(self, feed_names, fetch_names):
        """Keep only ops needed to compute ``fetch_names`` from
        ``feed_names`` (reference prune.cc / Program._prune).  Returns a new
        Program over the same global block contents."""
        p = copy.deepcopy(self)
        blk = p.global_block()
        needed = set(fetch_names)
        kept = []
        for op in reversed(blk.ops):
            if set(op.output_arg_names) & needed:
                kept.append(op)
                for n in op.input_arg_names:
                    needed.add(n)
        blk.ops = list(reversed(kept))
        used = set()
        for op in blk.ops:
            used.update(op.input_arg_names)
            used.update(op.output_arg_names)
        used.update(feed_names)
        used.update(fetch_names)
        blk.vars = collections.OrderedDict(
            (n, v) for n, v in blk.vars.items() if n in used
        )
        return p

    # ---- serialization -----------------------------------------------------
    def to_dict(self):
        return {
            "version": 1,
            "random_seed": self.random_seed,
            "blocks": [b.to_dict() for b in self.blocks],
        }

    def to_json(self):
        return json.dumps(self.to_dict())

    @staticmethod
    def from_dict(d):
        p = Program()
        p.random_seed = d.get("random_seed", 0)
        p.blocks = []
        for bd in d["blocks"]:
            blk = Block(p, bd["idx"], bd.get("parent_idx", -1),
                        bd.get("forward_block_idx", -1))
            for vd in bd["vars"]:
                cls = Parameter if vd.get("is_parameter") else Variable
                kwargs = dict(
                    name=vd["name"],
                    shape=vd["shape"],
                    dtype=vd["dtype"],
                    type=vd.get("type", VarType.DENSE_TENSOR),
                    persistable=vd.get("persistable", False),
                    stop_gradient=vd.get("stop_gradient", False),
                    is_data=vd.get("is_data", False),
                    lod_level=vd.get("lod_level", 0),
                )
                v = cls(blk, **kwargs) if cls is Variable else cls(
                    blk, kwargs.pop("shape"), kwargs.pop("dtype"), **kwargs)
                blk.vars[v.name] = v
            for od in bd["ops"]:
                op = Operator(blk, od["type"], od["inputs"], od["outputs"], od["attrs"])
                blk.ops.append(op)
            p.blocks.append(blk)
        p.current_block_idx = 0
        return p

    @staticmethod
    def from_json(s):
        return Program.from_dict(json.loads(s))

    def fingerprint(self):
        """Stable hash for executor compile caching."""
        return hash(self.to_json())

    def __repr__(self):
        return "\n".join(repr(b) for b in self.blocks)

    __str__ = __repr__


# ops whose attrs flip in clone(for_test=True)
_TEST_MODE_OPS = {
    "dropout": ("is_test",),
    "batch_norm": ("is_test",),
    # test mode consumes the TRAINED running scale instead of updating it
    "fake_quantize_range_abs_max": ("is_test",),
}


# --------------------------------------------------------------------------
# default program singletons (reference framework.py:2048-2160)
# --------------------------------------------------------------------------
_main_program_ = Program()
_startup_program_ = Program()


def default_startup_program():
    return _startup_program_


def default_main_program():
    return _main_program_


def switch_main_program(program):
    global _main_program_
    prev = _main_program_
    _main_program_ = program
    return prev


def switch_startup_program(program):
    global _startup_program_
    prev = _startup_program_
    _startup_program_ = program
    return prev


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    """Route subsequent layer calls into the given programs
    (reference framework.py:program_guard)."""
    prev_main = switch_main_program(main_program)
    prev_startup = None
    if startup_program is not None:
        prev_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_startup is not None:
            switch_startup_program(prev_startup)
