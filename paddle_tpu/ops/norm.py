"""Normalization ops: batch_norm, layer_norm, rms_norm, lrn, norm (L2),
group_norm.

Parity: reference ``paddle/fluid/operators/batch_norm_op.{cc,cu.cc}``
(train/infer modes, momentum moving stats, NCHW/NHWC data_layout),
``layer_norm_op.cc`` (begin_norm_axis), ``lrn_op.cc``, ``norm_op.cc`` —
TPU-native: each is a handful of jnp reductions that XLA fuses into one
kernel; gradients via auto-vjp reproduce the saved-stat backward the
reference hand-writes (vjp through rsqrt of the saved variance).

batch_norm's moving-average update is part of the same traced program, so
MeanOut/VarianceOut write back to the persistable stat vars in the scope
(the reference does this in-place through the same-name output trick,
python/paddle/fluid/layers/nn.py batch_norm).
"""

import jax.numpy as jnp
from jax import lax

from ..registry import register_op, set_output, in_var

__all__ = []


# -- batch_norm -------------------------------------------------------------

def _bn_infer(op, block):
    x = in_var(op, block, "X")
    c = x.shape[1] if op.attrs.get("data_layout", "NCHW") == "NCHW" \
        else x.shape[-1]
    set_output(op, block, "Y", x.shape, x.dtype)
    set_output(op, block, "MeanOut", (c,), x.dtype)
    set_output(op, block, "VarianceOut", (c,), x.dtype)
    set_output(op, block, "SavedMean", (c,), x.dtype)
    set_output(op, block, "SavedVariance", (c,), x.dtype)


def shifted_one_pass_stats(xf, shift, red_axes, bshape=None):
    """Per-channel (mean, var) in ONE fused HBM pass: both reductions of
    E[(x-c)^2]-(E[x-c])^2 are independent so XLA fuses them (the
    two-pass exact form needs a second full read after the mean
    barrier).  ``shift`` (fp32 [C] or None) — typically the running mean
    — kills the catastrophic cancellation of the naive E[x^2]-E[x]^2
    whenever it tracks the batch mean.  Clamped at 0.  Shared by
    batch_norm and the fused-conv-BN decomposition (transpiler.fusion)
    so the two paths cannot drift numerically."""
    if shift is not None:
        s32 = shift.astype(jnp.float32)
        if bshape is None:
            bshape = [1] * xf.ndim
            c_axis = [i for i in range(xf.ndim) if i not in red_axes][0]
            bshape[c_axis] = xf.shape[c_axis]
        xs = xf - s32.reshape(bshape)
    else:
        s32 = 0.0
        xs = xf
    m1 = jnp.mean(xs, axis=red_axes)
    var = jnp.maximum(jnp.mean(jnp.square(xs), axis=red_axes)
                      - jnp.square(m1), 0.0)
    return m1 + s32, var


def _bn_axes(x, attrs):
    """(c_axis, reduction axes, broadcast shape) for a BN input under the
    op's data_layout — shared by forward and the fused backward so the
    two can never disagree on reduction axes."""
    layout = attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    red_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]
    return c_axis, red_axes, bshape


def _bn_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or attrs.get("use_global_stats",
                                                       False)
    c_axis, red_axes, bshape = _bn_axes(x, attrs)

    # statistics accumulate in fp32 INSIDE the kernel regardless of the
    # activation dtype, so bf16 activations flow through unconverted (the
    # op is AMP-gray: blacklisting it would cost two full-activation cast
    # passes around every conv) while running stats stay accurate.  XLA
    # fuses the f32 cast into the reduction — no fp32 materialization.
    xf = x.astype(jnp.float32)
    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = mean
        saved_var = var
    else:
        from ..flags import flag
        if flag("bn_two_pass"):
            # two-pass variance: E[(x-mean)^2] — exact but costs a second
            # full read of the activation (the mean must finish first, so
            # XLA cannot fuse the two reductions into one pass)
            use_mean = jnp.mean(xf, axis=red_axes)
            use_var = jnp.mean(
                jnp.square(xf - use_mean.reshape(bshape)), axis=red_axes
            )
        else:
            # one-pass variance shifted by the running mean (cuDNN's
            # form) — measured ~8% off a ResNet-50 step on a v5e vs the
            # two-pass form; FLAGS_bn_two_pass restores the exact form
            use_mean, use_var = shifted_one_pass_stats(
                xf, mean, red_axes, bshape)
        mean_out = momentum * mean + (1.0 - momentum) * use_mean
        var_out = momentum * var + (1.0 - momentum) * use_var
        saved_mean = use_mean
        saved_var = use_var

    inv_std = lax.rsqrt(use_var.astype(jnp.float32) + eps)
    y = (xf - use_mean.reshape(bshape).astype(jnp.float32)) * \
        (inv_std * scale.astype(jnp.float32)).reshape(bshape) + \
        bias.astype(jnp.float32).reshape(bshape)
    return {"Y": y.astype(x.dtype), "MeanOut": mean_out,
            "VarianceOut": var_out, "SavedMean": saved_mean,
            "SavedVariance": saved_var}


def _bn_grad_maker(op, no_grad_set):
    """Hand-written fused BN backward (reference ``batch_norm_op.cu``'s
    three-term kernel) instead of the generic vjp: differentiating the
    recomputed two-pass variance costs ~2x the activation traffic of the
    closed-form dx/dgamma/dbeta."""
    from ..framework import grad_var_name

    x = op.inputs["X"][0]
    outs = {}
    for slot, names in (("GRAD::X", op.inputs["X"]),
                        ("GRAD::Scale", op.inputs["Scale"]),
                        ("GRAD::Bias", op.inputs["Bias"])):
        outs[slot] = ["" if n in no_grad_set else grad_var_name(n)
                      for n in names]
    if not any(n for ns in outs.values() for n in ns):
        return []
    return [dict(
        type="batch_norm_grad",
        inputs={"X": [x], "Scale": op.inputs["Scale"],
                "Out::SavedMean": op.outputs["SavedMean"],
                "Out::SavedVariance": op.outputs["SavedVariance"],
                "GRAD::Y": [grad_var_name(op.outputs["Y"][0])]},
        outputs=outs,
        attrs=dict(op.attrs),
    )]


def _bn_grad_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    scale = ins["Scale"][0]
    mean = ins["Out::SavedMean"][0]
    var = ins["Out::SavedVariance"][0]
    dy = ins["GRAD::Y"][0]
    eps = attrs.get("epsilon", 1e-5)
    c_axis, red, bshape = _bn_axes(x, attrs)
    n = 1
    for i in red:
        n *= x.shape[i]

    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    mu = mean.astype(jnp.float32).reshape(bshape)
    rstd = lax.rsqrt(var.astype(jnp.float32) + eps).reshape(bshape)
    xhat = (xf - mu) * rstd
    dbeta = jnp.sum(dyf, axis=red)
    dgamma = jnp.sum(dyf * xhat, axis=red)
    g = scale.astype(jnp.float32).reshape(bshape) * rstd
    if attrs.get("is_test", False) or attrs.get("use_global_stats", False):
        # running stats are constants w.r.t. x
        dx = g * dyf
    else:
        # classic fused form: dx = g*(dy - mean(dy) - xhat*mean(dy*xhat))
        dx = g * (dyf - (dbeta / n).reshape(bshape)
                  - xhat * (dgamma / n).reshape(bshape))
    return {"GRAD::X": dx.astype(x.dtype),
            "GRAD::Scale": dgamma.astype(scale.dtype),
            "GRAD::Bias": dbeta.astype(scale.dtype)}


register_op(
    "batch_norm", ["X", "Scale", "Bias", "Mean", "Variance"],
    ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
    infer=_bn_infer, compute=_bn_compute, grad=_bn_grad_maker,
    no_grad_inputs=("Mean", "Variance"),
)

def _bn_grad_infer(gop, block):
    x = in_var(gop, block, "X")
    scale = in_var(gop, block, "Scale")
    for slot, ref in (("GRAD::X", x), ("GRAD::Scale", scale),
                      ("GRAD::Bias", scale)):
        for name in gop.outputs.get(slot, []):
            if name:
                block.create_var(name=name, shape=ref.shape,
                                 dtype=ref.dtype, persistable=False)


register_op(
    "batch_norm_grad",
    ["X", "Scale", "Out::SavedMean", "Out::SavedVariance", "GRAD::Y"],
    ["GRAD::X", "GRAD::Scale", "GRAD::Bias"],
    infer=_bn_grad_infer, compute=_bn_grad_compute, grad=None,
)


# -- layer_norm -------------------------------------------------------------

def _ln_infer(op, block):
    x = in_var(op, block, "X")
    axis = op.attrs.get("begin_norm_axis", 1)
    rows = x.shape[:axis]
    set_output(op, block, "Y", x.shape, x.dtype)
    set_output(op, block, "Mean", rows, x.dtype)
    set_output(op, block, "Variance", rows, x.dtype)


def _ln_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    scale = ins["Scale"][0] if ins.get("Scale") and ins["Scale"][0] is not None \
        else None
    bias = ins["Bias"][0] if ins.get("Bias") and ins["Bias"][0] is not None \
        else None
    axis = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    # statistics in fp32 regardless of activation dtype (AMP-gray op:
    # bf16 activations pass through; XLA fuses the casts into the
    # reduction/normalize chain)
    red = tuple(range(axis, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=red, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32).reshape(
            (1,) * axis + x.shape[axis:])
    if bias is not None:
        y = y + bias.astype(jnp.float32).reshape(
            (1,) * axis + x.shape[axis:])
    return {"Y": y.astype(x.dtype), "Mean": mean.reshape(x.shape[:axis]),
            "Variance": var.reshape(x.shape[:axis])}


register_op(
    "layer_norm", ["X", "Scale", "Bias"], ["Y", "Mean", "Variance"],
    infer=_ln_infer, compute=_ln_compute,
)


# -- rms_norm ----------------------------------------------------------------

def _rms_compute(ins, attrs, ctx, op_index):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the LAST axis (a hidden
    vector, or one head's slice of it: the per-head QK-norm of a decoder
    block is this op over ``[B, T, H, D]`` with a ``[D]`` gain, or over
    ``[B, T, H * D]`` with that gain: ``_rms_by_group``).  Like
    layer_norm an AMP-gray op: the mean of squares is taken in float32
    whatever the activations' dtype, and the output keeps that dtype."""
    x = ins["X"][0]
    xf = x.astype(jnp.float32)
    if ins["Scale"][0].shape[0] != x.shape[-1]:
        return {"Y": _rms_by_group(xf, ins["Scale"][0], attrs).astype(
            x.dtype)}
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                       + attrs.get("epsilon", 1e-6))
    return {"Y": (y * ins["Scale"][0].astype(jnp.float32)).astype(x.dtype)}


def _rms_by_group(xf, scale, attrs):
    """A gain narrower than the last axis: one norm a ``d``-wide group of it
    (a per-head norm over a projection's ``[B, T, H * D]`` output WHERE IT
    LIES).  The groups' sums and their way back are products with the
    groups' indicator, exact in float32 at the highest precision: a view as
    ``[.., H, D]`` is a relayout on a TPU, and XLA laid the float32 gradient
    of such a view transposed and copied it back (PERF.md 6.27)."""
    d = scale.shape[0]
    n = xf.shape[-1] // d
    member = (jnp.arange(n * d)[:, None] // d
              == jnp.arange(n)[None, :]).astype(jnp.float32)

    def over(a, b):
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    inv = lax.rsqrt(over(jnp.square(xf), member) / d
                    + attrs.get("epsilon", 1e-6))
    return xf * over(inv, member.T) * jnp.tile(scale.astype(jnp.float32), n)


def _rms_infer(op, block):
    x = in_var(op, block, "X")
    scale = in_var(op, block, "Scale")
    if x.shape[-1] % scale.shape[0]:
        raise ValueError("rms_norm: the gain %s is as wide as X's last axis "
                         "%s or a whole divisor of it (one norm a group)"
                         % (scale.shape, x.shape))
    set_output(op, block, "Y", x.shape, x.dtype)


register_op("rms_norm", ["X", "Scale"], ["Y"], infer=_rms_infer,
            compute=_rms_compute)


# -- group_norm (parity extension; reference gained it right after 0.15) ----

def _gn_infer(op, block):
    x = in_var(op, block, "X")
    g = op.attrs.get("groups", 1)
    set_output(op, block, "Y", x.shape, x.dtype)
    set_output(op, block, "Mean", (x.shape[0], g), x.dtype)
    set_output(op, block, "Variance", (x.shape[0], g), x.dtype)


def _gn_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    scale = ins["Scale"][0] if ins.get("Scale") and ins["Scale"][0] is not None \
        else None
    bias = ins["Bias"][0] if ins.get("Bias") and ins["Bias"][0] is not None \
        else None
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    if attrs.get("data_layout", "NCHW") == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    red = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=red, keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    if attrs.get("data_layout", "NCHW") == "NHWC":
        y = jnp.moveaxis(y, 1, -1)
    return {"Y": y, "Mean": mean.reshape(n, g), "Variance": var.reshape(n, g)}


register_op(
    "group_norm", ["X", "Scale", "Bias"], ["Y", "Mean", "Variance"],
    infer=_gn_infer, compute=_gn_compute,
)


# -- lrn (local response normalization across channels) ---------------------

def _lrn_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", x.shape, x.dtype)
    set_output(op, block, "MidOut", x.shape, x.dtype)


def _lrn_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]  # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    half = n // 2
    sq = jnp.square(x)
    # sliding window sum over the channel axis
    window_sum = lax.reduce_window(
        sq, 0.0, lax.add, (1, n, 1, 1), (1, 1, 1, 1),
        [(0, 0), (half, n - 1 - half), (0, 0), (0, 0)],
    )
    mid = k + alpha * window_sum
    return {"Out": x * jnp.power(mid, -beta), "MidOut": mid}


register_op("lrn", ["X"], ["Out", "MidOut"],
            infer=_lrn_infer, compute=_lrn_compute)


# -- norm (L2 normalize along axis; norm_op.cc) -----------------------------

def _norm_infer(op, block):
    x = in_var(op, block, "X")
    axis = op.attrs.get("axis", 1)
    nshape = list(x.shape)
    nshape[axis] = 1
    set_output(op, block, "Out", x.shape, x.dtype)
    set_output(op, block, "Norm", tuple(nshape), x.dtype)


def _norm_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    axis = attrs.get("axis", 1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": x / norm, "Norm": norm}


register_op("norm", ["X"], ["Out", "Norm"],
            infer=_norm_infer, compute=_norm_compute)


# -- bilinear_interp (align_corners=True era semantics) ---------------------

def _interp_infer(op, block):
    x = in_var(op, block, "X")
    oh = op.attrs.get("out_h", -1)
    ow = op.attrs.get("out_w", -1)
    set_output(op, block, "Out", (x.shape[0], x.shape[1], oh, ow), x.dtype)


def _bilinear_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]  # NCHW
    if ins.get("OutSize") and ins["OutSize"][0] is not None:
        raise NotImplementedError(
            "dynamic OutSize needs static shapes under XLA; set out_h/out_w"
        )
    oh, ow = attrs["out_h"], attrs["out_w"]
    n, c, h, w = x.shape
    # align_corners=True ratios (reference bilinear_interp_op.cc at 0.15)
    rh = (h - 1.0) / (oh - 1.0) if oh > 1 else 0.0
    rw = (w - 1.0) / (ow - 1.0) if ow > 1 else 0.0
    ys = jnp.arange(oh) * rh
    xs = jnp.arange(ow) * rw
    y0 = jnp.floor(ys).astype(jnp.int32)
    x0 = jnp.floor(xs).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (ys - y0).astype(x.dtype)
    wx = (xs - x0).astype(x.dtype)
    top = x[:, :, y0, :][:, :, :, x0] * (1 - wx) + \
        x[:, :, y0, :][:, :, :, x1] * wx
    bot = x[:, :, y1, :][:, :, :, x0] * (1 - wx) + \
        x[:, :, y1, :][:, :, :, x1] * wx
    out = top * (1 - wy)[None, None, :, None] + bot * wy[None, None, :, None]
    return {"Out": out}


register_op("bilinear_interp", ["X", "OutSize"], ["Out"],
            infer=_interp_infer, compute=_bilinear_compute,
            no_grad_inputs=("OutSize",))


def _nearest_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    oh, ow = attrs["out_h"], attrs["out_w"]
    n, c, h, w = x.shape
    rh = (h - 1.0) / (oh - 1.0) if oh > 1 else 0.0
    rw = (w - 1.0) / (ow - 1.0) if ow > 1 else 0.0
    ys = jnp.round(jnp.arange(oh) * rh).astype(jnp.int32)
    xs = jnp.round(jnp.arange(ow) * rw).astype(jnp.int32)
    return {"Out": x[:, :, ys, :][:, :, :, xs]}


register_op("nearest_interp", ["X", "OutSize"], ["Out"],
            infer=_interp_infer, compute=_nearest_compute,
            no_grad_inputs=("OutSize",))
