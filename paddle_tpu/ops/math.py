"""Linear-algebra and scalar math ops: mul, matmul, sum, scale, mean, clip...

Parity: reference ``mul_op.cc``, ``matmul_op.cc``, ``sum_op.cc``,
``scale_op.cc``, ``mean_op.cc``, ``clip_op.cc``, ``clip_by_norm_op.cc``,
``squared_l2_norm_op.cc``, ``l1_norm_op.cc``, ``sign_op.cc``,
``minus_op.cc``, ``cos_sim_op.cc``, ``isfinite_op.cc`` — TPU-native: every
matmul lowers to a single ``jnp.matmul``/``lax.dot_general`` so XLA tiles it
onto the MXU.  fp16 inputs request explicit fp32 accumulation via
``preferred_element_type``; bf16 inputs keep bf16 outputs (the MXU
accumulates partial products in fp32 internally) so backward cotangents
stay bf16 — see ``_mm_accum_dtype``.
"""

import numpy as np

import jax.numpy as jnp
from jax import lax

from ..core import convert_dtype, dtype_is_floating
from ..registry import register_op, set_output, in_var, same_shape_infer


def _flatten_to_2d(x, num_col_dims):
    lead = 1
    for s in x.shape[:num_col_dims]:
        lead *= s
    rest = 1
    for s in x.shape[num_col_dims:]:
        rest *= s
    return x.reshape(lead, rest)


def _mm_accum_dtype(a, b, ctx=None):
    # bf16 operands keep bf16 outputs: the TPU MXU accumulates partial
    # products in fp32 internally regardless, and requesting an explicit
    # fp32 output (then downcasting) makes every backward cotangent fp32
    # — the transposed dots then run as fp32*bf16, off the fast bf16 MXU
    # pipeline.  KNOWN BACKEND DIVERGENCE: off-TPU backends give no such
    # fp32-accumulation guarantee for bf16 dots, so bf16 numerics on the
    # CPU backend may accumulate at lower precision than the same program
    # on TPU.  Requesting fp32 outputs off-TPU was tried and rejected:
    # the fp32 cotangent cascade changes the emitted backward HLO
    # everywhere (the exact pessimization described above), a worse
    # trade than the documented precision gap — bf16-AMP on CPU is a
    # test-suite configuration, not a deployment target.  fp16
    # (GPU-style AMP) always gets explicit fp32 accumulation.
    if a.dtype == jnp.float16:
        return jnp.float32
    return None


# -- what a dense product must do (an op definition's ``work`` rule) ---------

def _size(shape):
    return int(np.prod(shape, dtype=np.int64))


def product_work(m, k, n, lhs, rhs, out, grad, weight_rhs=True):
    """The parts of the flattened product ``[m, k] x [k, n]`` as a ``work``
    rule returns them — ``(part, flops, least_bytes, (M, K, N))``, flops
    2·M·K·N of the product the part IS, least bytes each of its operands
    and its result once.  ``lhs``, ``rhs``, ``out`` are the three arrays'
    dtypes (the cotangent arrives in the output's, a gradient leaves in its
    input's).  ``grad`` () is the product itself, ``fwd``; else the slots
    that get a gradient, ``X`` -> ``dx`` = ``[m, n] x [n, k]`` and ``Y`` ->
    ``dw`` = ``[k, m] x [m, n]`` (``dx`` too where the right operand is an
    activation: ``weight_rhs`` False)."""
    nbytes = (m * k * np.dtype(lhs).itemsize + k * n * np.dtype(rhs).itemsize
              + m * n * np.dtype(out).itemsize)
    flops = 2 * m * k * n
    if not grad:
        return [("fwd", flops, nbytes, (m, k, n))]
    by_slot = {"X": ("dx", flops, nbytes, (m, n, k)),
               "Y": ("dw" if weight_rhs else "dx", flops, nbytes, (k, m, n))}
    return [by_slot[slot] for slot in grad]


def _mul_work(ins, attrs, grad):
    x, y = ins["X"][0], ins["Y"][0]
    xnc = attrs.get("x_num_col_dims", 1)
    ync = attrs.get("y_num_col_dims", 1)
    return product_work(_size(x.shape[:xnc]), _size(x.shape[xnc:]),
                        _size(y.shape[ync:]), x.dtype, y.dtype, x.dtype, grad)


# -- mul (fc's matmul: flatten then 2-D gemm; mul_op.cc) --------------------

def _mul_infer(op, block):
    x = in_var(op, block, "X")
    y = in_var(op, block, "Y")
    xnc = op.attrs.get("x_num_col_dims", 1)
    ync = op.attrs.get("y_num_col_dims", 1)
    out_shape = tuple(x.shape[:xnc]) + tuple(y.shape[ync:])
    set_output(op, block, "Out", out_shape, x.dtype)


def _mul_compute(ins, attrs, ctx, op_index):
    x, y = ins["X"][0], ins["Y"][0]
    xnc = attrs.get("x_num_col_dims", 1)
    ync = attrs.get("y_num_col_dims", 1)
    x2 = _flatten_to_2d(x, xnc)
    y2 = _flatten_to_2d(y, ync)
    out = jnp.matmul(x2, y2,
                     preferred_element_type=_mm_accum_dtype(x2, y2, ctx))
    out = out.astype(x.dtype)
    return {"Out": out.reshape(tuple(x.shape[:xnc]) + tuple(y.shape[ync:]))}


register_op("mul", ["X", "Y"], ["Out"], infer=_mul_infer, compute=_mul_compute,
            work=_mul_work)


# -- matmul (batched, with transpose flags; matmul_op.cc) -------------------

def _matmul_infer(op, block):
    x = in_var(op, block, "X")
    y = in_var(op, block, "Y")
    tx = op.attrs.get("transpose_X", False)
    ty = op.attrs.get("transpose_Y", False)
    xs, ys = list(x.shape), list(y.shape)
    if len(xs) == 1:
        xs = [1, xs[0]]
    if len(ys) == 1:
        ys = [ys[0], 1]
    if tx:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if ty:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    batch = xs[:-2] if len(xs) > len(ys) else ys[:-2]
    out = tuple(batch) + (xs[-2], ys[-1])
    if len(x.shape) == 1 and len(y.shape) == 1:
        out = (1,)
    set_output(op, block, "Out", out, x.dtype)


def _matmul_compute(ins, attrs, ctx, op_index):
    x, y = ins["X"][0], ins["Y"][0]
    tx = attrs.get("transpose_X", False)
    ty = attrs.get("transpose_Y", False)
    alpha = attrs.get("alpha", 1.0)
    squeeze_out = x.ndim == 1 and y.ndim == 1
    if x.ndim == 1:
        x = x[None, :]
    if y.ndim == 1:
        y = y[:, None]
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    out = jnp.matmul(x, y, preferred_element_type=_mm_accum_dtype(x, y, ctx))
    out = out.astype(ins["X"][0].dtype)
    if alpha != 1.0:
        out = out * alpha
    if squeeze_out:
        out = out.reshape(1)
    return {"Out": out}


def _matmul_work(ins, attrs, grad):
    """Batch dimensions multiplied into M.  A right operand without batch
    dimensions is a weight (its gradient contracts over every row: ``dw``);
    with them it is an activation, and both gradients are ``dx``."""
    xs, ys = list(ins["X"][0].shape), list(ins["Y"][0].shape)
    xs = [1] + xs if len(xs) == 1 else xs
    ys = ys + [1] if len(ys) == 1 else ys
    if attrs.get("transpose_X", False):
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if attrs.get("transpose_Y", False):
        ys[-1], ys[-2] = ys[-2], ys[-1]
    batch = xs[:-2] if len(xs) >= len(ys) else ys[:-2]
    x = ins["X"][0].dtype
    return product_work(_size(batch) * xs[-2], xs[-1], ys[-1], x,
                        ins["Y"][0].dtype, x, grad, weight_rhs=len(ys) == 2)


register_op("matmul", ["X", "Y"], ["Out"], infer=_matmul_infer,
            compute=_matmul_compute, work=_matmul_work)


# -- sum (variadic add; sum_op.cc) ------------------------------------------

def _sum_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", x.shape, x.dtype)


def _sum_compute(ins, attrs, ctx, op_index):
    from .selected_rows import SelectedRows, to_dense
    import jax.numpy as _jnp

    xs = [x for x in ins["X"] if x is not None]
    sparse = [x for x in xs if isinstance(x, SelectedRows)]
    dense = [x for x in xs if not isinstance(x, SelectedRows)]
    if sparse and not dense:
        # all-sparse: concatenation IS addition (reference sum_op
        # SelectedRows kernel appends row lists)
        rows = _jnp.concatenate([s.rows for s in sparse])
        vals = _jnp.concatenate([s.values for s in sparse])
        return {"Out": SelectedRows(rows, vals, sparse[0].height)}
    if sparse:
        dense = dense + [to_dense(s) for s in sparse]
    out = dense[0]
    for x in dense[1:]:
        out = out + x
    return {"Out": out}


register_op("sum", ["X"], ["Out"], infer=_sum_infer, compute=_sum_compute)


# -- scale ------------------------------------------------------------------

def _scale_compute(ins, attrs, ctx, op_index):
    from .selected_rows import SelectedRows, map_values

    x = ins["X"][0]
    scale = attrs.get("scale", 1.0)
    bias = attrs.get("bias", 0.0)
    if isinstance(x, SelectedRows):
        # bias-free scale commutes with duplicate-row merging; a biased
        # scale of a gradient would add the bias per DUPLICATE, which is
        # not the dense semantics — densify for that (rare) case
        if bias == 0.0:
            return {"Out": map_values(x, lambda v: v * scale)}
        from .selected_rows import to_dense

        x = to_dense(x)
    if attrs.get("bias_after_scale", True):
        return {"Out": x * scale + bias}
    return {"Out": (x + bias) * scale}


register_op("scale", ["X"], ["Out"], infer=same_shape_infer("X", "Out"),
            compute=_scale_compute)


# -- mean (scalar [1] output like mean_op.cc) -------------------------------

def _mean_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", (1,), x.dtype)


register_op(
    "mean", ["X"], ["Out"], infer=_mean_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.mean(ins["X"][0]).reshape(1)
    },
)


# -- minus / sign -----------------------------------------------------------

register_op(
    "minus", ["X", "Y"], ["Out"], infer=same_shape_infer("X", "Out"),
    compute=lambda ins, attrs, ctx, op_index: {"Out": ins["X"][0] - ins["Y"][0]},
)

register_op(
    "sign", ["X"], ["Out"], infer=same_shape_infer("X", "Out"),
    compute=lambda ins, attrs, ctx, op_index: {"Out": jnp.sign(ins["X"][0])},
)


# -- clip family ------------------------------------------------------------

def _clip_compute(ins, attrs, ctx, op_index):
    from .selected_rows import SelectedRows, merge_rows
    from .control_flow import _mask_to

    x = ins["X"][0]
    if isinstance(x, SelectedRows):
        # clip applies to the SUMMED gradient per row (dense semantics),
        # so duplicates merge first; padded slots stay exactly zero
        # (clip(0) may be nonzero when min > 0) so the sentinel rows
        # remain scatter-inert
        uniq, merged, valid = merge_rows(x)
        clipped = jnp.clip(merged, attrs["min"], attrs["max"])
        clipped = clipped * _mask_to(valid, clipped).astype(clipped.dtype)
        return {"Out": SelectedRows(uniq, clipped, x.height)}
    return {"Out": jnp.clip(x, attrs["min"], attrs["max"])}


register_op("clip", ["X"], ["Out"], infer=same_shape_infer("X", "Out"),
            compute=_clip_compute)


def _clip_by_norm_compute(ins, attrs, ctx, op_index):
    from .selected_rows import SelectedRows, map_values, merged_sumsq

    x = ins["X"][0]
    max_norm = attrs["max_norm"]
    if isinstance(x, SelectedRows):
        # reference clip_by_norm SelectedRows kernel: the norm is over
        # the MERGED rows (== the dense grad's norm); the scale then
        # applies uniformly, which commutes with merging
        norm = jnp.sqrt(merged_sumsq(x))
        scale = jnp.where(norm > max_norm,
                          max_norm / jnp.maximum(norm, 1e-12), 1.0)
        return {"Out": map_values(
            x, lambda v: v * scale.astype(v.dtype))}
    norm = jnp.sqrt(jnp.sum(x * x))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": x * scale.astype(x.dtype)}


register_op("clip_by_norm", ["X"], ["Out"], infer=same_shape_infer("X", "Out"),
            compute=_clip_by_norm_compute)


def _scalar_out_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", (1,), x.dtype)


def _squared_l2_norm_compute(ins, attrs, ctx, op_index):
    from .selected_rows import SelectedRows, merged_sumsq

    x = ins["X"][0]
    if isinstance(x, SelectedRows):
        # global-norm clipping's per-grad term: ||dense(grad)||^2
        # without materializing the dense gradient
        return {"Out": merged_sumsq(x).reshape(1)}
    return {"Out": jnp.sum(x * x).reshape(1)}


register_op(
    "squared_l2_norm", ["X"], ["Out"], infer=_scalar_out_infer,
    compute=_squared_l2_norm_compute,
)

register_op(
    "l1_norm", ["X"], ["Out"], infer=_scalar_out_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.sum(jnp.abs(ins["X"][0])).reshape(1)
    },
)

register_op(
    "squared_l2_distance", ["X", "Y"], ["sub_result", "Out"],
    infer=lambda op, block: (
        set_output(op, block, "sub_result", in_var(op, block, "X").shape,
                   in_var(op, block, "X").dtype),
        set_output(op, block, "Out", (in_var(op, block, "X").shape[0], 1),
                   in_var(op, block, "X").dtype),
    ),
    compute=lambda ins, attrs, ctx, op_index: (
        lambda sub: {"sub_result": sub,
                     "Out": jnp.sum(sub * sub, axis=tuple(range(1, sub.ndim)),
                                    keepdims=False).reshape(-1, 1)}
    )(ins["X"][0] - ins["Y"][0]),
)


# -- isfinite (debugging: FLAGS_check_nan_inf parity) -----------------------

register_op(
    "isfinite", ["X"], ["Out"],
    infer=lambda op, block: set_output(op, block, "Out", (1,), np.bool_),
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.all(
            jnp.stack([jnp.all(jnp.isfinite(x)) for x in ins["X"]])
        ).reshape(1)
    },
    grad=None,
)

# has_inf / has_nan: the isfinite family's other two members
# (reference isfinite_op.cc registers all three as OverflowOp variants)

register_op(
    "has_inf", ["X"], ["Out"],
    infer=lambda op, block: set_output(op, block, "Out", (1,), np.bool_),
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.any(
            jnp.stack([jnp.any(jnp.isinf(x)) for x in ins["X"]])
        ).reshape(1)
    },
    grad=None,
)

register_op(
    "has_nan", ["X"], ["Out"],
    infer=lambda op, block: set_output(op, block, "Out", (1,), np.bool_),
    compute=lambda ins, attrs, ctx, op_index: {
        "Out": jnp.any(
            jnp.stack([jnp.any(jnp.isnan(x)) for x in ins["X"]])
        ).reshape(1)
    },
    grad=None,
)


# -- cos_sim ----------------------------------------------------------------

def _cos_sim_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", (x.shape[0], 1), x.dtype)
    set_output(op, block, "XNorm", (x.shape[0], 1), x.dtype)
    y = in_var(op, block, "Y")
    set_output(op, block, "YNorm", (y.shape[0], 1), y.dtype)


def _cos_sim_compute(ins, attrs, ctx, op_index):
    x, y = ins["X"][0], ins["Y"][0]
    xn = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True))
    out = jnp.sum(x * y, axis=-1, keepdims=True) / (xn * yn)
    return {"Out": out, "XNorm": xn, "YNorm": yn}


register_op("cos_sim", ["X", "Y"], ["Out", "XNorm", "YNorm"],
            infer=_cos_sim_infer, compute=_cos_sim_compute)


# -- piecewise_lr (in-graph step-function LR; layers.piecewise_decay) -------

def _piecewise_lr_compute(ins, attrs, ctx, op_index):
    step = ins["Step"][0]
    boundaries = attrs["boundaries"]
    values = attrs["values"]
    out = jnp.full_like(step, values[-1])
    # walk from the right so earlier boundaries win
    for b, v in zip(reversed(boundaries), reversed(values[:-1])):
        out = jnp.where(step < b, v, out)
    return {"Out": out}


register_op(
    "piecewise_lr", ["Step"], ["Out"],
    infer=lambda op, block: set_output(
        op, block, "Out", in_var(op, block, "Step").shape, "float32"
    ),
    compute=_piecewise_lr_compute, grad=None,
)
