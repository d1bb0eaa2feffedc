"""Fake quantization ops (QAT) and real int8 execution.

Parity: reference ``operators/fake_quantize_op.cc`` (fake_quantize_abs_max,
fake_quantize_range_abs_max) and ``operators/fake_dequantize_op.cc``
(fake_dequantize_max_abs).  Quantize-dequantize in one op ("fake"): the
tensor stays float but carries int8-grid rounding error, so training
learns quantization-robust weights.

TPU-first notes: gradients use the straight-through estimator (identity
through the rounding), implemented as a custom grad instead of the
reference's GradOpDescMaker pair; the range_abs_max sliding window
collapses to a running max state var (window bookkeeping is host-side
bookkeeping the XLA graph does not need — the max over the window is
what the quantizer consumes).

Real execution (ISSUE 14): ``dequant_matmul`` is the inference-side op
the ``quantize_inference`` program pass rewrites matmul/mul/FC weights
into — int8 weights with per-output-channel dequant scales, executed as
a fused dequant-matmul.  Two modes:

* ``weight_only`` — weights dequantize into the f32 accumulator feeding
  the dot (int8 values are exact in f32); activations keep their dtype.
* ``dynamic`` — activations additionally quantize to int8 (per-row
  abs-max grid, or a trained QAT ``XScale`` when the pass found one) and
  the dot runs int8 x int8 with an int32 accumulator.

Both are XLA ``dot_general`` bodies (``xla_dequant_matmul``).
"""

import numpy as np

import jax.numpy as jnp
from jax import lax

from ..registry import register_op, set_output, in_var
from ..framework import grad_var_name
from .math import _flatten_to_2d, _size, product_work

__all__ = []


def _quant_range(bit_length):
    return float((1 << (int(bit_length) - 1)) - 1)


def _abs_max_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", x.shape, x.dtype)
    axis = op.attrs.get("quant_axis", -1)
    scale_shape = (x.shape[axis],) if axis is not None and axis >= 0 \
        else (1,)
    set_output(op, block, "OutScale", scale_shape, x.dtype)


def _abs_max_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    rng = _quant_range(attrs.get("bit_length", 8))
    axis = attrs.get("quant_axis", -1)
    if axis is not None and axis >= 0:
        # per-channel grid along ``axis`` (conv filters axis 0, fc/mul
        # weights their output axis): one abs-max per channel, so a wide
        # FC layer's small-magnitude columns stop being over-clipped by
        # the single per-tensor max — the same grid the inference-side
        # quantize_inference pass deploys
        red = tuple(i for i in range(x.ndim) if i != axis)
        scale = jnp.max(jnp.abs(x), axis=red)
        scale = jnp.maximum(scale, 1e-12)
        bshape = [1] * x.ndim
        bshape[axis] = scale.shape[0]
        sb = scale.reshape(bshape)
        q = jnp.clip(jnp.round(x / sb * rng), -rng, rng)
        return {"Out": q * sb / rng, "OutScale": scale}
    scale = jnp.max(jnp.abs(x)).reshape(1)
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.round(x / scale * rng)
    q = jnp.clip(q, -rng, rng)
    return {"Out": q * scale / rng, "OutScale": scale}


def _ste_grad_infer(op, block):
    g = in_var(op, block, "GRAD::Out")
    set_output(op, block, "GRAD::X", g.shape, g.dtype)


register_op(
    "ste_identity_grad", ["GRAD::Out"], ["GRAD::X"],
    infer=_ste_grad_infer,
    compute=lambda ins, attrs, ctx, op_index: {
        "GRAD::X": ins["GRAD::Out"][0]},
    grad=None,
)


def _quant_grad_maker(op, no_grad_set):
    """Straight-through estimator: dL/dX = dL/dOut (identity through
    the rounding), the standard QAT gradient."""
    x_name = op.inputs["X"][0]
    if x_name in no_grad_set:
        return []
    out_name = op.outputs["Out"][0]
    return [{
        "type": "ste_identity_grad",
        "inputs": {"GRAD::Out": [grad_var_name(out_name)]},
        "outputs": {"GRAD::X": [grad_var_name(x_name)]},
        "attrs": {},
    }]


register_op(
    "fake_quantize_abs_max", ["X"], ["Out", "OutScale"],
    infer=_abs_max_infer, compute=_abs_max_compute,
    grad=_quant_grad_maker,
)


def _range_abs_max_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", x.shape, x.dtype)
    set_output(op, block, "OutScale", (1,), x.dtype)


def _range_abs_max_compute(ins, attrs, ctx, op_index):
    """Running-max variant: in training the scale is
    max(current |x|_max, InScale) — the monotone envelope of the
    reference's window max; in test mode InScale is used as-is."""
    x = ins["X"][0]
    in_scales = ins.get("InScale")
    in_scale = in_scales[0] if in_scales and in_scales[0] is not None \
        else jnp.zeros((1,), x.dtype)
    rng = _quant_range(attrs.get("bit_length", 8))
    if attrs.get("is_test", False) or ctx.is_test:
        scale = jnp.maximum(in_scale.reshape(1), 1e-12)
    else:
        cur = jnp.max(jnp.abs(x)).reshape(1)
        scale = jnp.maximum(jnp.maximum(cur, in_scale.reshape(1)), 1e-12)
    q = jnp.clip(jnp.round(x / scale * rng), -rng, rng)
    return {"Out": q * scale / rng, "OutScale": scale}


register_op(
    "fake_quantize_range_abs_max", ["X", "InScale"], ["Out", "OutScale"],
    infer=_range_abs_max_infer, compute=_range_abs_max_compute,
    grad=_quant_grad_maker, no_grad_inputs=("InScale",),
)


def _dequant_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", x.shape, x.dtype)


def _dequant_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    scale = ins["Scale"][0]
    max_range = float(attrs["max_range"])
    return {"Out": x * scale.reshape(()) / max_range}


register_op(
    "fake_dequantize_max_abs", ["X", "Scale"], ["Out"],
    infer=_dequant_infer, compute=_dequant_compute,
    no_grad_inputs=("Scale",),
)


# ---------------------------------------------------------------------------
# real int8 execution: fused dequant-matmul (ISSUE 14)
# ---------------------------------------------------------------------------

def xla_dequant_matmul(x2, qw, scale, mode="weight_only", xscale=None,
                       bit_length=8):
    """The fused dequant-matmul: ``x2`` [M, K] float,
    ``qw`` [K, N] int8, ``scale`` [N] f32 dequant multipliers
    (``w ~= qw * scale``).  ``weight_only`` dequantizes into the f32
    accumulator (int8 values are exact in f32; one GEMM, scale applied
    per output channel); ``dynamic`` quantizes activations to int8 too
    (per-row abs-max grid, or the trained ``xscale`` envelope when QAT
    calibration exists) and accumulates the int8 x int8 dot in int32
    via ``preferred_element_type``."""
    scale = scale.astype(jnp.float32)
    if mode == "weight_only":
        acc = jnp.matmul(x2.astype(jnp.float32), qw.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        return acc * scale
    if mode != "dynamic":
        raise ValueError("unknown dequant_matmul mode %r" % mode)
    rng = _quant_range(bit_length)
    xf = x2.astype(jnp.float32)
    if xscale is not None:
        # trained QAT running abs-max envelope -> static activation grid
        sx = jnp.maximum(xscale.astype(jnp.float32).reshape(()),
                         1e-12) / rng
    else:
        sx = jnp.maximum(jnp.max(jnp.abs(xf), axis=1, keepdims=True),
                         1e-12) / rng
    qx = jnp.clip(jnp.round(xf / sx), -rng, rng).astype(jnp.int8)
    acc = lax.dot_general(qx, qw, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * scale


def _dequant_matmul_infer(op, block):
    x = in_var(op, block, "X")
    qw = in_var(op, block, "QWeight")
    xnc = op.attrs.get("x_num_col_dims", 1)
    out_shape = tuple(x.shape[:xnc]) + (qw.shape[-1],)
    set_output(op, block, "Out", out_shape, x.dtype)


def _dequant_matmul_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    qw = ins["QWeight"][0]
    scale = ins["Scale"][0]
    xscales = ins.get("XScale")
    xscale = xscales[0] if xscales else None
    xnc = attrs.get("x_num_col_dims", 1)
    mode = attrs.get("mode", "weight_only")
    bits = attrs.get("bit_length", 8)
    acc = xla_dequant_matmul(_flatten_to_2d(x, xnc), qw, scale, mode=mode,
                             xscale=xscale, bit_length=bits)
    out = acc.astype(x.dtype).reshape(
        tuple(x.shape[:xnc]) + (qw.shape[-1],))
    return {"Out": out}


def _dequant_matmul_work(ins, attrs, grad):
    x, qw = ins["X"][0], ins["QWeight"][0]
    xnc = attrs.get("x_num_col_dims", 1)
    return product_work(_size(x.shape[:xnc]), _size(x.shape[xnc:]),
                        qw.shape[-1], x.dtype, qw.dtype, x.dtype, grad)


register_op(
    "dequant_matmul", ["X", "QWeight", "Scale", "XScale"], ["Out"],
    infer=_dequant_matmul_infer, compute=_dequant_matmul_compute,
    grad=None, no_grad_inputs=("QWeight", "Scale", "XScale"),
    work=_dequant_matmul_work,
)
