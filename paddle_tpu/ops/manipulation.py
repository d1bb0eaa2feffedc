"""Tensor manipulation ops: reshape, transpose, concat, split, slice, ...

Parity: reference ``reshape_op.cc``, ``transpose_op.cc``, ``concat_op.cc``,
``split_op.cc``, ``squeeze/unsqueeze``, ``flatten_op.cc``, ``slice_op.cc``,
``expand_op.cc``, ``stack/unstack``, ``gather_op.cc``, ``scatter_op.cc``,
``pad_op.cc``, ``reverse_op.cc``, ``one_hot_op.cc``, ``top_k_op.cc``,
``lookup_table_op.cc``, ``multiplex_op.cc``, ``label_smooth_op.cc`` —
all shape-static so XLA can lay out and fuse freely.
"""

import numpy as np

import jax
import jax.numpy as jnp

from ..core import convert_dtype, long_dtype, materialize_dtype
from ..registry import (register_op, set_output, in_var,
                        _generic_grad_infer)


# -- reshape ----------------------------------------------------------------

def _resolve_reshape(in_shape, spec):
    out = []
    for i, s in enumerate(spec):
        if s == 0:
            out.append(in_shape[i])
        else:
            out.append(s)
    if -1 in out:
        known = 1
        for s in out:
            if s != -1:
                known *= s
        total = 1
        for s in in_shape:
            total *= s
        out[out.index(-1)] = total // known
    return tuple(out)


def _reshape_infer(op, block):
    x = in_var(op, block, "X")
    spec = list(op.attrs["shape"])
    if -1 not in x.shape:
        out = _resolve_reshape(x.shape, spec)
    else:
        # dynamic dims present: resolve what we can — 0 copies the input
        # dim (possibly -1), -1 stays symbolic
        out = tuple(
            (x.shape[i] if i < len(x.shape) else -1) if s == 0 else s
            for i, s in enumerate(spec)
        )
    set_output(op, block, "Out", out, x.dtype)


def _reshape_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    return {"Out": x.reshape(_resolve_reshape(x.shape, list(attrs["shape"])))}


register_op("reshape", ["X"], ["Out"], infer=_reshape_infer,
            compute=_reshape_compute)


def _flatten_infer(op, block):
    x = in_var(op, block, "X")
    axis = op.attrs.get("axis", 1)
    lead = 1
    for s in x.shape[:axis]:
        lead *= s
    rest = 1
    for s in x.shape[axis:]:
        rest *= s
    set_output(op, block, "Out", (lead, rest), x.dtype)


register_op(
    "flatten", ["X"], ["Out"], infer=_flatten_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": ins["X"][0].reshape(
            int(np.prod(ins["X"][0].shape[: attrs.get("axis", 1)] or (1,))),
            -1,
        )
    },
)


def _squeeze_infer(op, block):
    x = in_var(op, block, "X")
    axes = op.attrs.get("axes", [])
    if axes:
        axes = [a % len(x.shape) for a in axes]
        out = tuple(s for i, s in enumerate(x.shape) if i not in axes or s != 1)
    else:
        out = tuple(s for s in x.shape if s != 1)
    set_output(op, block, "Out", out, x.dtype)


def _squeeze_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    axes = attrs.get("axes", [])
    if axes:
        axes = tuple(a % x.ndim for a in axes if x.shape[a % x.ndim] == 1)
        return {"Out": jnp.squeeze(x, axis=axes)}
    return {"Out": jnp.squeeze(x)}


register_op("squeeze", ["X"], ["Out"], infer=_squeeze_infer,
            compute=_squeeze_compute)


def _unsqueeze_infer(op, block):
    x = in_var(op, block, "X")
    out = list(x.shape)
    for a in sorted(op.attrs["axes"]):
        out.insert(a if a >= 0 else a + len(out) + 1, 1)
    set_output(op, block, "Out", out, x.dtype)


def _unsqueeze_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    for a in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, a if a >= 0 else a + x.ndim + 1)
    return {"Out": x}


register_op("unsqueeze", ["X"], ["Out"], infer=_unsqueeze_infer,
            compute=_unsqueeze_compute)


# -- transpose --------------------------------------------------------------

def _transpose_infer(op, block):
    x = in_var(op, block, "X")
    perm = op.attrs["axis"]
    set_output(op, block, "Out", tuple(x.shape[p] for p in perm), x.dtype)


register_op(
    "transpose", ["X"], ["Out"], infer=_transpose_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.transpose(ins["X"][0], attrs["axis"])
    },
)


# -- concat / split / stack -------------------------------------------------

def _concat_infer(op, block):
    xs = [block.var_recursive(n) for n in op.inputs["X"]]
    axis = op.attrs.get("axis", 0) % len(xs[0].shape)
    out = list(xs[0].shape)
    sizes = [v.shape[axis] for v in xs]
    # any unknown (-1) contributor makes the result unknown, not a
    # meaningless negative sum
    out[axis] = -1 if any(s < 0 for s in sizes) else sum(sizes)
    set_output(op, block, "Out", out, xs[0].dtype)


register_op(
    "concat", ["X"], ["Out"], infer=_concat_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.concatenate(ins["X"], axis=attrs.get("axis", 0))
    },
)


def _split_infer(op, block):
    x = in_var(op, block, "X")
    axis = op.attrs.get("axis", 0) % len(x.shape)
    sections = op.attrs.get("sections", [])
    num = op.attrs.get("num", 0)
    outs = op.outputs["Out"]
    if sections:
        sizes = sections
    else:
        n = num or len(outs)
        sizes = [x.shape[axis] // n] * n
    for name, size in zip(outs, sizes):
        shape = list(x.shape)
        shape[axis] = size
        v = block._find_var_recursive(name) or block.create_var(name=name)
        v.shape = tuple(shape)
        v.dtype = x.dtype


def _split_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    axis = attrs.get("axis", 0) % x.ndim
    sections = attrs.get("sections", [])
    if sections:
        idx = np.cumsum(sections)[:-1].tolist()
        return {"Out": jnp.split(x, idx, axis=axis)}
    n = attrs.get("num", 0) or attrs["__num_outputs__"]
    return {"Out": jnp.split(x, n, axis=axis)}


register_op("split", ["X"], ["Out"], infer=_split_infer,
            compute=_split_compute)


def _stack_infer(op, block):
    xs = [block.var_recursive(n) for n in op.inputs["X"]]
    axis = op.attrs.get("axis", 0)
    out = list(xs[0].shape)
    out.insert(axis if axis >= 0 else axis + len(out) + 1, len(xs))
    set_output(op, block, "Y", out, xs[0].dtype)


register_op(
    "stack", ["X"], ["Y"], infer=_stack_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "Y": jnp.stack(ins["X"], axis=attrs.get("axis", 0))
    },
)


# -- slice / expand / reverse / pad ----------------------------------------

def _slice_infer(op, block):
    x = in_var(op, block, "Input")
    shape = list(x.shape)
    for ax, st, en in zip(op.attrs["axes"], op.attrs["starts"],
                          op.attrs["ends"]):
        dim = shape[ax]
        st2 = max(st + dim, 0) if st < 0 else min(st, dim)
        en2 = max(en + dim, 0) if en < 0 else min(en, dim)
        shape[ax] = max(en2 - st2, 0)
    set_output(op, block, "Out", shape, x.dtype)


def _slice_compute(ins, attrs, ctx, op_index):
    x = ins["Input"][0]
    idx = [slice(None)] * x.ndim
    for ax, st, en in zip(attrs["axes"], attrs["starts"], attrs["ends"]):
        idx[ax] = slice(st, en)
    return {"Out": x[tuple(idx)]}


register_op("slice", ["Input"], ["Out"], infer=_slice_infer,
            compute=_slice_compute)


def _expand_infer(op, block):
    x = in_var(op, block, "X")
    times = op.attrs["expand_times"]
    set_output(op, block, "Out",
               tuple(s * t for s, t in zip(x.shape, times)), x.dtype)


register_op(
    "expand", ["X"], ["Out"], infer=_expand_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.tile(ins["X"][0], attrs["expand_times"])
    },
)

register_op(
    "reverse", ["X"], ["Out"],
    infer=lambda op, block: set_output(
        op, block, "Out", in_var(op, block, "X").shape,
        in_var(op, block, "X").dtype),
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.flip(ins["X"][0], axis=tuple(attrs["axis"]))
    },
)


def _pad_infer(op, block):
    x = in_var(op, block, "X")
    p = op.attrs["paddings"]
    out = [s + p[2 * i] + p[2 * i + 1] for i, s in enumerate(x.shape)]
    set_output(op, block, "Out", out, x.dtype)


def _pad_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    p = attrs["paddings"]
    pads = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))}


register_op("pad", ["X"], ["Out"], infer=_pad_infer, compute=_pad_compute)


# -- gather / scatter -------------------------------------------------------

def _gather_infer(op, block):
    x = in_var(op, block, "X")
    ids = in_var(op, block, "Index")
    set_output(op, block, "Out", (ids.shape[0],) + tuple(x.shape[1:]), x.dtype)


register_op(
    "gather", ["X", "Index"], ["Out"], infer=_gather_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.take(ins["X"][0], ins["Index"][0].reshape(-1), axis=0)
    },
    no_grad_inputs=("Index",),
)


def _scatter_compute(ins, attrs, ctx, op_index):
    x, ids, upd = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    ids = ids.reshape(-1)
    if attrs.get("overwrite", True):
        out = x.at[ids].set(upd)
    else:
        out = x.at[ids].add(upd)
    return {"Out": out}


register_op(
    "scatter", ["X", "Ids", "Updates"], ["Out"],
    infer=lambda op, block: set_output(
        op, block, "Out", in_var(op, block, "X").shape,
        in_var(op, block, "X").dtype),
    compute=_scatter_compute, no_grad_inputs=("Ids",),
)


# -- one_hot / label_smooth / multiplex ------------------------------------

def _one_hot_infer(op, block):
    x = in_var(op, block, "X")
    depth = op.attrs["depth"]
    shape = tuple(x.shape[:-1]) + (depth,) if x.shape[-1] == 1 else \
        tuple(x.shape) + (depth,)
    set_output(op, block, "Out", shape, np.float32)


def _one_hot_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    if x.shape and x.shape[-1] == 1:
        x = x.reshape(x.shape[:-1])
    return {"Out": jax.nn.one_hot(x, attrs["depth"], dtype=jnp.float32)}


register_op("one_hot", ["X"], ["Out"], infer=_one_hot_infer,
            compute=_one_hot_compute, grad=None)


def _label_smooth_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    if ins.get("PriorDist") and ins["PriorDist"][0] is not None:
        prior = ins["PriorDist"][0]
        return {"Out": (1 - eps) * x + eps * prior}
    return {"Out": (1 - eps) * x + eps / x.shape[-1]}


register_op(
    "label_smooth", ["X", "PriorDist"], ["Out"],
    infer=lambda op, block: set_output(
        op, block, "Out", in_var(op, block, "X").shape,
        in_var(op, block, "X").dtype),
    compute=_label_smooth_compute,
)


def _multiplex_compute(ins, attrs, ctx, op_index):
    ids = ins["Ids"][0].reshape(-1)
    stacked = jnp.stack(ins["X"], axis=0)  # [n, batch, ...]
    return {"Out": jnp.take_along_axis(
        stacked, ids[None, :, None].astype(jnp.int32), axis=0
    )[0] if stacked.ndim == 3 else stacked[ids, jnp.arange(ids.shape[0])]}


register_op(
    "multiplex", ["X", "Ids"], ["Out"],
    infer=lambda op, block: set_output(
        op, block, "Out", in_var(op, block, "X").shape,
        in_var(op, block, "X").dtype),
    compute=_multiplex_compute, no_grad_inputs=("Ids",),
)


# -- top_k ------------------------------------------------------------------

def _top_k_infer(op, block):
    x = in_var(op, block, "X")
    k = op.attrs["k"]
    out = tuple(x.shape[:-1]) + (k,)
    set_output(op, block, "Out", out, x.dtype)
    set_output(op, block, "Indices", out, np.int64)


def _top_k_compute(ins, attrs, ctx, op_index):
    vals, idx = jax.lax.top_k(ins["X"][0], attrs["k"])
    return {"Out": vals, "Indices": idx.astype(long_dtype())}


register_op("top_k", ["X"], ["Out", "Indices"], infer=_top_k_infer,
            compute=_top_k_compute, grad=None)


# -- argsort ----------------------------------------------------------------

def _argsort_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", x.shape, x.dtype)
    set_output(op, block, "Indices", x.shape, np.int64)


def _argsort_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    idx = jnp.argsort(x, axis=axis)
    return {"Out": jnp.sort(x, axis=axis), "Indices": idx.astype(long_dtype())}


register_op("argsort", ["X"], ["Out", "Indices"], infer=_argsort_infer,
            compute=_argsort_compute, grad=None)


# -- lookup_table (embedding; lookup_table_op.cc) ---------------------------

def _lookup_table_infer(op, block):
    w = in_var(op, block, "W")
    ids = in_var(op, block, "Ids")
    shape = tuple(ids.shape[:-1]) + (w.shape[1],) if ids.shape[-1] == 1 \
        else tuple(ids.shape) + (w.shape[1],)
    set_output(op, block, "Out", shape, w.dtype)


def _lookup_table_compute(ins, attrs, ctx, op_index):
    w, ids = ins["W"][0], ins["Ids"][0]
    squeeze = ids.shape and ids.shape[-1] == 1
    flat = ids.reshape(-1)
    out = None
    if attrs.get("is_sparse", False) and ctx.mesh is not None \
            and ctx.state_specs and ctx.op is not None:
        # row-sharded table on the mesh: gather only local rows + psum
        # the [N, D] activations over the table axis — never an
        # all-gathered [vocab, D] table (parallel/embedding.py).  Gated
        # to is_sparse tables: their backward is the custom
        # SelectedRows grad op, so no AD flows through this lowering.
        from ..parallel.embedding import sharded_sparse_lookup

        out = sharded_sparse_lookup(ctx, w, flat,
                                    ctx.op.inputs["W"][0])
    if out is None:
        out = jnp.take(w, flat, axis=0)
    pad = attrs.get("padding_idx", -1)
    if pad is not None and pad != -1:
        mask = (flat != pad)[:, None]
        out = out * mask.astype(out.dtype)
    shape = (ids.shape[:-1] if squeeze else ids.shape) + (w.shape[1],)
    return {"Out": out.reshape(shape)}


def _lookup_table_grad(op, no_grad_set):
    # sparse path (is_sparse attr) emits a SelectedRows gradient
    from .selected_rows import lookup_table_grad_maker
    return lookup_table_grad_maker(op, no_grad_set)


register_op(
    "lookup_table", ["W", "Ids"], ["Out"], infer=_lookup_table_infer,
    compute=_lookup_table_compute, grad=_lookup_table_grad,
    no_grad_inputs=("Ids",),
)


# Where the segment kernel may be the dense gradient's body (tests add
# "cpu": interpreted).
_SEGMENT_PLATFORMS = ("tpu",)


def segment_body(ctx, n, v, d, w_dtype, g_dtype):
    """Whether ``lookup_table_grad`` — ``n`` rows into a ``[v, d]`` table —
    lowers to ``ops/pallas/embedding_grad.py``: a TPU, one device, and a
    shape the kernel takes."""
    from .pallas import embedding_grad as eg, kernel_allowed

    return kernel_allowed(ctx, _SEGMENT_PLATFORMS) \
        and getattr(ctx, "mesh", None) is None \
        and eg.supported(n, v, d, w_dtype, g_dtype)


def _lookup_table_grad_compute(ins, attrs, ctx, op_index):
    """The dense gradient (``is_sparse`` tables emit
    ``lookup_table_sparse_grad`` instead): the sorted-segment kernel where
    ``segment_body`` says so, elsewhere ``jax.vjp`` of the forward's
    ``jnp.take`` — one scattered add."""
    from ..compile_cache import note_kernel_body
    from ..registry import _generic_grad_compute

    w, ids = ins["W"][0], ins["Ids"][0]
    gout = (ins.get("GRAD::Out") or [None])[0]
    n, (v, d) = ids.size, w.shape
    segment = gout is not None and segment_body(ctx, n, v, d, w.dtype,
                                                gout.dtype)
    note_kernel_body("lookup_table_grad", "segment" if segment else "xla")
    if not segment:
        return _generic_grad_compute(ins, attrs, ctx, op_index)
    from .pallas import embedding_grad as eg, interpret_mode

    grad = eg.embedding_grad(ids.reshape(-1), gout.reshape(n, d), v,
                             attrs.get("padding_idx", -1),
                             interpret_mode(ctx))
    return {"GRAD::W": [grad.astype(w.dtype)]}


# the gradient op the default grad maker emits for a dense table
register_op("lookup_table_grad", (), (), infer=_generic_grad_infer,
            compute=_lookup_table_grad_compute, grad=None,
            doc="dense gradient of lookup_table")


# -- crop (reference crop_op.cc) --------------------------------------------

def _crop_infer(op, block):
    x = in_var(op, block, "X")
    shape = op.attrs.get("shape") or None
    if not shape:
        y = in_var(op, block, "Y")
        shape = y.shape
    set_output(op, block, "Out", tuple(shape), x.dtype)


def _crop_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    shape = attrs.get("shape") or None
    if not shape:
        shape = ins["Y"][0].shape
    offsets_in = ins.get("Offsets")
    if offsets_in and offsets_in[0] is not None:
        if attrs.get("offsets"):
            raise ValueError(
                "crop: runtime input Offsets and attr offsets are mutually "
                "exclusive (crop_op.cc contract)")
        offs = [offsets_in[0][i] for i in range(x.ndim)]
        static_offs = None
    else:
        offs = list(attrs.get("offsets") or [0] * x.ndim)
        static_offs = offs
    if any(s == -1 for s in shape):
        # -1 = "rest of the dim from the offset" (batch-dim convention);
        # needs static offsets since XLA slice sizes are compile-time
        if static_offs is None:
            raise ValueError(
                "crop: shape dims of -1 require attr offsets, not the "
                "runtime Offsets input (slice sizes are static under XLA)")
        shape = [x.shape[i] - static_offs[i] if s == -1 else s
                 for i, s in enumerate(shape)]
    out = jax.lax.dynamic_slice(x, offs, tuple(shape))
    return {"Out": out}


register_op("crop", ["X", "Y", "Offsets"], ["Out"],
            infer=_crop_infer, compute=_crop_compute,
            no_grad_inputs=("Y", "Offsets"))


# -- pad2d (reference pad2d_op.cc: constant / reflect / edge modes) ---------

def _pad2d_infer(op, block):
    x = in_var(op, block, "X")
    p = op.attrs["paddings"]  # [top, bottom, left, right]
    fmt = op.attrs.get("data_format", "NCHW")
    n, a, b, c = x.shape
    if fmt == "NCHW":
        out = (n, a, b + p[0] + p[1], c + p[2] + p[3])
    else:  # NHWC
        out = (n, a + p[0] + p[1], b + p[2] + p[3], c)
    set_output(op, block, "Out", out, x.dtype)


def _pad2d_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    p = attrs["paddings"]
    fmt = attrs.get("data_format", "NCHW")
    mode = attrs.get("mode", "constant")
    hw = [(p[0], p[1]), (p[2], p[3])]
    pads = [(0, 0), (0, 0)] + hw if fmt == "NCHW" else \
        [(0, 0)] + hw + [(0, 0)]
    if mode == "constant":
        out = jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))
    elif mode == "reflect":
        out = jnp.pad(x, pads, mode="reflect")
    elif mode == "edge":
        out = jnp.pad(x, pads, mode="edge")
    else:
        raise ValueError("pad2d: unknown mode %r" % mode)
    return {"Out": out}


register_op("pad2d", ["X"], ["Out"], infer=_pad2d_infer,
            compute=_pad2d_compute)


# -- pad_constant_like (reference pad_constant_like_op.cc) ------------------

def _pad_const_like_infer(op, block):
    x = in_var(op, block, "X")
    y = in_var(op, block, "Y")
    set_output(op, block, "Out", x.shape, y.dtype)


def _pad_const_like_compute(ins, attrs, ctx, op_index):
    x, y = ins["X"][0], ins["Y"][0]
    pads = [(0, sx - sy) for sx, sy in zip(x.shape, y.shape)]
    return {"Out": jnp.pad(y, pads,
                           constant_values=attrs.get("pad_value", 0.0))}


register_op("pad_constant_like", ["X", "Y"], ["Out"],
            infer=_pad_const_like_infer, compute=_pad_const_like_compute,
            no_grad_inputs=("X",))


# -- unstack (reference unstack_op.h) ---------------------------------------

def _unstack_infer(op, block):
    x = in_var(op, block, "X")
    axis = op.attrs.get("axis", 0)
    if axis < 0:
        axis += len(x.shape)
    out_shape = tuple(x.shape[:axis]) + tuple(x.shape[axis + 1:])
    for name in op.outputs.get("Y", []):
        v = block._find_var_recursive(name) or block.create_var(name=name)
        v.shape = out_shape
        v.dtype = x.dtype


def _unstack_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    if axis < 0:
        axis += x.ndim
    n = x.shape[axis]
    parts = jnp.split(x, n, axis=axis)
    return {"Y": [jnp.squeeze(p, axis=axis) for p in parts]}


register_op("unstack", ["X"], ["Y"], infer=_unstack_infer,
            compute=_unstack_compute)


# -- is_empty (reference is_empty_op.cc) ------------------------------------

register_op(
    "is_empty", ["X"], ["Out"],
    infer=lambda op, block: set_output(op, block, "Out", (1,), "bool"),
    compute=lambda ins, attrs, ctx, op_index: {
        # shape is static under XLA: the answer is a trace-time constant
        "Out": jnp.full((1,), ins["X"][0].size == 0, jnp.bool_)
    },
    grad=None,
)


# -- fill (reference fill_op.cc: row-major float values + dtype attr) -------

def _fill_infer(op, block):
    set_output(op, block, "Out", op.attrs["shape"],
               op.attrs.get("dtype", "float32"))


def _fill_compute(ins, attrs, ctx, op_index):
    dtype = materialize_dtype(attrs.get("dtype", "float32"))
    vals = np.asarray(attrs["value"], dtype=np.float64).reshape(
        tuple(attrs["shape"]))
    return {"Out": jnp.asarray(vals.astype(dtype))}


register_op("fill", [], ["Out"], infer=_fill_infer, compute=_fill_compute,
            grad=None)


# -- scale_sub_region (v1 legacy ScaleSubRegionLayer): scale a per-sample
# [c0..c1, h0..h1, w0..w1] block of an NCHW tensor by ``value`` ----------

def _scale_sub_region_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", x.shape, x.dtype)


def _scale_sub_region_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]                       # [B, C, H, W]
    idx = ins["Indices"][0]               # [B, 6] 1-based inclusive
    value = attrs.get("value", 1.0)
    b, c, h, w = x.shape
    ci = jnp.arange(c).reshape(1, c, 1, 1)
    hi = jnp.arange(h).reshape(1, 1, h, 1)
    wi = jnp.arange(w).reshape(1, 1, 1, w)
    lo = (idx[:, 0::2] - 1).astype(jnp.int32)   # [B, 3] c0,h0,w0 0-based
    hi_ = idx[:, 1::2].astype(jnp.int32)        # [B, 3] exclusive ends
    mask = ((ci >= lo[:, 0].reshape(b, 1, 1, 1)) &
            (ci < hi_[:, 0].reshape(b, 1, 1, 1)) &
            (hi >= lo[:, 1].reshape(b, 1, 1, 1)) &
            (hi < hi_[:, 1].reshape(b, 1, 1, 1)) &
            (wi >= lo[:, 2].reshape(b, 1, 1, 1)) &
            (wi < hi_[:, 2].reshape(b, 1, 1, 1)))
    return {"Out": jnp.where(mask, x * value, x)}


register_op("scale_sub_region", ["X", "Indices"], ["Out"],
            infer=_scale_sub_region_infer,
            compute=_scale_sub_region_compute,
            no_grad_inputs=("Indices",))
