"""Activation ops — the reference's functor-based family
(``activation_op.cc``, ~25 activations + parameterized variants like
``leaky_relu``, ``elu``, ``brelu``, ``prelu_op.cc``, ``soft_relu``) —
TPU-native: one-liner jnp/lax bodies; XLA fuses them into producers, and
their vjp-derived gradients match the reference's analytic grad kernels.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import register_op, same_shape_infer, set_output, in_var


def _register_act(name, fn):
    register_op(
        name, ["X"], ["Out"], infer=same_shape_infer("X", "Out"),
        compute=lambda ins, attrs, ctx, op_index: {
            "Out": fn(ins["X"][0], attrs)
        },
    )


_SIMPLE = {
    "relu": lambda x, a: jnp.maximum(x, 0),
    "sigmoid": lambda x, a: jax.nn.sigmoid(x),
    "logsigmoid": lambda x, a: jax.nn.log_sigmoid(x),
    "tanh": lambda x, a: jnp.tanh(x),
    "tanh_shrink": lambda x, a: x - jnp.tanh(x),
    "exp": lambda x, a: jnp.exp(x),
    "log": lambda x, a: jnp.log(x),
    "sqrt": lambda x, a: jnp.sqrt(x),
    "rsqrt": lambda x, a: jax.lax.rsqrt(x),
    "abs": lambda x, a: jnp.abs(x),
    "ceil": lambda x, a: jnp.ceil(x),
    "floor": lambda x, a: jnp.floor(x),
    "round": lambda x, a: jnp.round(x),
    "cos": lambda x, a: jnp.cos(x),
    "sin": lambda x, a: jnp.sin(x),
    "square": lambda x, a: x * x,
    "reciprocal": lambda x, a: 1.0 / x,
    "softplus": lambda x, a: jax.nn.softplus(x),
    "softsign": lambda x, a: x / (1 + jnp.abs(x)),
    "relu6": lambda x, a: jnp.clip(x, 0, a.get("threshold", 6.0)),
    "leaky_relu": lambda x, a: jnp.where(x >= 0, x, a.get("alpha", 0.02) * x),
    "elu": lambda x, a: jnp.where(
        x >= 0, x, a.get("alpha", 1.0) * (jnp.exp(jnp.minimum(x, 0.0)) - 1)),
    "brelu": lambda x, a: jnp.clip(x, a.get("t_min", 0.0), a.get("t_max", 24.0)),
    "soft_relu": lambda x, a: jnp.log(
        1 + jnp.exp(jnp.clip(x, -a.get("threshold", 40.0),
                             a.get("threshold", 40.0)))),
    "pow": lambda x, a: jnp.power(x, a.get("factor", 1.0)),
    "stanh": lambda x, a: a.get("scale_b", 1.7159) * jnp.tanh(
        a.get("scale_a", 2.0 / 3.0) * x),
    "hard_sigmoid": lambda x, a: jnp.clip(
        a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0),
    "swish": lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x),
    "gelu": lambda x, a: jax.nn.gelu(x, approximate=False),
    "thresholded_relu": lambda x, a: jnp.where(
        x > a.get("threshold", 1.0), x, 0.0),
    "hard_shrink": lambda x, a: jnp.where(
        jnp.abs(x) > a.get("threshold", 0.5), x, 0.0),
    "softshrink": lambda x, a: jnp.where(
        x > a.get("lambda", 0.5), x - a.get("lambda", 0.5),
        jnp.where(x < -a.get("lambda", 0.5), x + a.get("lambda", 0.5), 0.0)),
}

for _name, _fn in _SIMPLE.items():
    _register_act(_name, _fn)


# -- prelu (per-channel learnable alpha; prelu_op.cc) -----------------------

def _prelu_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Out", x.shape, x.dtype)


def _prelu_compute(ins, attrs, ctx, op_index):
    x, alpha = ins["X"][0], ins["Alpha"][0]
    mode = attrs.get("mode", "all")
    if mode == "channel":
        a = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    elif mode == "element":
        a = alpha.reshape((1,) + x.shape[1:])
    else:
        a = alpha.reshape(())
    return {"Out": jnp.where(x >= 0, x, a * x)}


register_op("prelu", ["X", "Alpha"], ["Out"], infer=_prelu_infer,
            compute=_prelu_compute)


# -- softmax (softmax_op.cc: applied on the last dim) -----------------------

def _softmax_compute(ins, attrs, ctx, op_index):
    axis = attrs.get("axis", -1)
    return {"Out": jax.nn.softmax(ins["X"][0], axis=axis)}


register_op("softmax", ["X"], ["Out"], infer=same_shape_infer("X", "Out"),
            compute=_softmax_compute)


def _log_softmax_compute(ins, attrs, ctx, op_index):
    axis = attrs.get("axis", -1)
    return {"Out": jax.nn.log_softmax(ins["X"][0], axis=axis)}


register_op("log_softmax", ["X"], ["Out"], infer=same_shape_infer("X", "Out"),
            compute=_log_softmax_compute)


# -- maxout (maxout_op.cc) --------------------------------------------------

def _maxout_infer(op, block):
    x = in_var(op, block, "X")
    groups = op.attrs["groups"]
    n, c = x.shape[0], x.shape[1]
    set_output(op, block, "Out", (n, c // groups) + tuple(x.shape[2:]), x.dtype)


def _maxout_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    g = attrs["groups"]
    n, c = x.shape[0], x.shape[1]
    x = x.reshape((n, c // g, g) + x.shape[2:])
    return {"Out": jnp.max(x, axis=2)}


register_op("maxout", ["X"], ["Out"], infer=_maxout_infer,
            compute=_maxout_compute)


# -- swiglu: the gated product of a SiLU feed-forward ------------------------

def _swiglu_compute(ins, attrs, ctx, op_index):
    """``silu(X) * Y`` in float32, returned in X's dtype."""
    x, y = ins["X"][0], ins["Y"][0]
    out = jax.nn.silu(x.astype(jnp.float32)) * y.astype(jnp.float32)
    return {"Out": out.astype(x.dtype)}


# every ``swiglu`` feeds a down projection: stored, the product's forward and
# its weight gradient read ONE ``[T, F]`` array; fused into them its float32
# sigmoid ran once per output tile of each (a ``10240 x 4096 x 2560`` dW at
# 66 TFLOP/s beside siblings at 128-138: ledger, PR 51).  A narrow output
# stays inline: the shared experts' 768- and 1024-wide rows gain 0.07-0.4 ms
# a step stored, and the five more arrays held to the backward cost
# ``train_mtp_8k``'s head a prefetched operand, 2.6 ms (PERF.md 6.29)
_STORED_WIDTH = 2048


def _swiglu_stored(ins, attrs):
    return ("Out",) if ins["X"][0].shape[-1] >= _STORED_WIDTH else ()


register_op("swiglu", ["X", "Y"], ["Out"], infer=same_shape_infer("X", "Out"),
            compute=_swiglu_compute, stored=_swiglu_stored)


# -- rotary position embedding ----------------------------------------------

def scaled_frequencies(dim, theta, freq_scaling):
    """The ``dim / 2`` frequencies of YaRN's blend by parts, float64 (numpy:
    worked out when the op is traced, bound as a constant): ``w0_i =
    theta^(-2i/dim)`` kept where a frequency turns more than ``beta_fast``
    times in ``original_length`` positions, divided by ``factor`` where it
    turns fewer than ``beta_slow`` times, and blended linearly over the
    indices between — ``low = max(floor(c(beta_fast)), 0)``, ``high =
    min(ceil(c(beta_slow)), dim - 1)`` with ``c(r) = dim ln(original_length /
    (2 pi r)) / (2 ln theta)``; ``high`` + 0.001 where the two meet.  The
    blend is static: it holds at every length."""
    factor, length = (float(freq_scaling[k])
                      for k in ("factor", "original_length"))

    def turns(r):
        return dim * math.log(length / (r * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(turns(float(freq_scaling["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(freq_scaling["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    base = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * base + ramp * base / factor


def rotary_tables(length, dim, theta, freq_scaling=None, scale=1.0):
    """(cos, sin) ``[length, dim]`` of positions 0..length-1 in the
    rotate-half convention: frequency i = theta^(-2i/dim) — or, with
    ``freq_scaling``, ``scaled_frequencies``' — turns the pair (x[i], x[i +
    dim/2]); both tables times ``scale``."""
    if freq_scaling is None:
        inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    else:
        inv = jnp.asarray(scaled_frequencies(dim, theta, freq_scaling),
                          jnp.float32)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)
    if scale == 1.0:
        return jnp.cos(angle), jnp.sin(angle)
    return scale * jnp.cos(angle), scale * jnp.sin(angle)


def _rotary_compute(ins, attrs, ctx, op_index):
    """Rotate ``X`` ``[B, T, ..., D]`` by its position along axis 1 over all
    D dimensions, in float32; the output keeps X's dtype.  Frequency i
    turns the pair (x[i], x[i + D/2]) (rotate-half, the default) or, with
    ``interleaved``, the neighbours (x[2i], x[2i + 1]), in place.  The
    frequencies are ``theta^(-2i/D)`` or, with the attribute
    ``freq_scaling`` (``{factor, original_length, beta_fast, beta_slow}``),
    YaRN's blend of them by parts (``scaled_frequencies``: float64 at trace
    time, a constant of the step); ``scale`` multiplies cos and sin.  The
    gradient (``jax.vjp`` of this) turns back by the same frequencies, times
    the same scale."""
    x = ins["X"][0]
    d = x.shape[-1]
    cos, sin = rotary_tables(x.shape[1], d, float(attrs.get("theta", 1e4)),
                             attrs.get("freq_scaling"),
                             float(attrs.get("scale", 1.0)))
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d,)
    xf = x.astype(jnp.float32)
    if attrs.get("interleaved", False):
        # the tables repeat each frequency as [f0..f(D/2-1), f0..]: the
        # neighbours' order is [f0, f0, f1, f1, ..]
        cos, sin = (jnp.repeat(t[:, :d // 2], 2, -1) for t in (cos, sin))
        pair = xf.reshape(xf.shape[:-1] + (d // 2, 2))
        half = jnp.stack([-pair[..., 1], pair[..., 0]], -1).reshape(xf.shape)
    else:
        half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return {"Out": (xf * cos.reshape(shape)
                    + half * sin.reshape(shape)).astype(x.dtype)}


register_op("rotary_embedding", ["X"], ["Out"],
            infer=same_shape_infer("X", "Out"), compute=_rotary_compute)
