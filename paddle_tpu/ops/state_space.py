"""State-space (Mamba) layer ops: the selective scan and the short causal
depthwise convolution before it.

``selective_scan`` — for ``X`` (the convolved input) and ``Delta`` ``[B, T,
E]``, ``A`` ``[E, N]`` (negative), ``B`` and ``C`` ``[B, T, N]``, ``D`` and
``DeltaBias`` ``[E]``, all taken in float32::

    delta_t = softplus(Delta_t + DeltaBias)
    s_t = exp(delta_t (x) 1 * A) * s_{t-1} + (delta_t * X_t) (x) B_t
    Out_t = s_t C_t + D * X_t                        s_0 = 0

``Out`` ``[B, T, E]`` float32; ``State`` ``[B, E, N]`` the state after the
last step; ``Starts`` ``[B, ceil(T / chunk), N, E]`` the state every chunk of
``chunk`` steps starts on, which is what the gradient op recomputes a chunk
from.  The step-size's softplus is the op's (the activation a step size has
wherever the recurrence is used), so that a mixed-precision program's
``delta`` is made in float32 from the projection's output and never rounded.

Bodies, from what the op can observe: on a TPU, one device, the chunked
Pallas kernels of ``ops/pallas/selective_scan.py`` (``selective_scan:chunked``
in ``kernel_bodies``; ``selective_scan_grad:chunked``), elsewhere an XLA body
— a ``lax.scan`` over the chunks of a ``lax.scan`` over their steps, each
chunk rematerialised in the backward — whose gradient is its ``jax.vjp``
(``:xla``).

``causal_conv1d`` — ``Out_t = act(Bias + sum_j W[j] * X_{t - (K-1) + j})``
over time, depthwise (a channel reads itself), zeros before the start; ``X``
``[B, T, E]``, ``W`` ``[K, E]``, ``act`` none or ``silu``: ``K`` shifted
multiply-adds, which XLA fuses into one pass; float32 inside, the input's
dtype outside.
"""

import jax
import jax.numpy as jnp

from ..registry import (register_op, set_output, in_var,
                        _generic_grad_infer)

# Where the chunked Pallas kernels are the body (tests add "cpu":
# interpreted).
_KERNEL_PLATFORMS = ("tpu",)


def _scan_infer(op, block):
    x = in_var(op, block, "X")
    a = in_var(op, block, "A")
    b = in_var(op, block, "B")
    if len(x.shape) != 3 or len(a.shape) != 2 or a.shape[0] != x.shape[2] \
            or tuple(b.shape) != (x.shape[0], x.shape[1], a.shape[1]):
        raise ValueError(
            "selective_scan expects X and Delta [B, T, E], A [E, N], B and "
            "C [B, T, N]; got X %s, A %s, B %s" % (x.shape, a.shape, b.shape))
    chunk = int(op.attrs["chunk"])
    bt, t, e = x.shape
    set_output(op, block, "Out", (bt, t, e), "float32")
    set_output(op, block, "State", (bt, e, a.shape[1]), "float32")
    set_output(op, block, "Starts", (bt, -(-t // chunk), a.shape[1], e),
               "float32")


def scan_xla(delta, x, a, b, c, d, chunk):
    """The XLA body: ``(y, the final state [B, E, N], the chunks' starting
    states [B, T / chunk, N, E])`` in float32."""
    bt, t, e = x.shape
    n = a.shape[1]
    pad = -(-t // chunk) * chunk - t

    def by_chunks(v):              # [B, T, ..] -> [chunks, chunk, B, ..]
        v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((-1, chunk) + v.shape[1:])

    def step(s, inp):
        dl, xt, bt_, ct = inp
        s = jnp.exp(dl[..., None] * a) * s \
            + (dl * xt)[..., None] * bt_[:, None, :]
        return s, jnp.sum(s * ct[:, None, :], -1)

    @jax.checkpoint
    def one_chunk(s, inp):
        out, ys = jax.lax.scan(step, s, inp)
        return out, (ys, s)
    state, (ys, starts) = jax.lax.scan(
        one_chunk, jnp.zeros((bt, e, n), jnp.float32),
        tuple(by_chunks(v) for v in (delta, x, b, c)))
    y = jnp.moveaxis(ys.reshape((-1,) + ys.shape[2:]), 0, 1)[:, :t] + d * x
    return y, state, jnp.transpose(starts, (1, 0, 3, 2))


def _scan_args(ins, attrs):
    f32 = jnp.float32
    x, pre, a, b, c, d = (ins[s][0].astype(f32)
                          for s in ("X", "Delta", "A", "B", "C", "D"))
    bias = (ins.get("DeltaBias") or [None])[0]
    if bias is not None:
        pre = pre + bias.astype(f32)
    return x, pre, a, b, c, d, int(attrs["chunk"])


def _kernels(ctx, op_type, x, a, chunk):
    """Whether the chunked kernels are the body; notes which under
    ``op_type``."""
    from ..compile_cache import note_kernel_body
    from .pallas import kernel_allowed, selective_scan as ss

    chunked = kernel_allowed(ctx, _KERNEL_PLATFORMS) \
        and getattr(ctx, "mesh", None) is None \
        and ss.supported(x.shape, a.shape[1], chunk)
    note_kernel_body(op_type, "chunked" if chunked else "xla")
    return chunked


def _scan_compute(ins, attrs, ctx, op_index):
    x, pre, a, b, c, d, chunk = _scan_args(ins, attrs)
    delta = jax.nn.softplus(pre)
    if _kernels(ctx, "selective_scan", x, a, chunk):
        from .pallas import interpret_mode, selective_scan as ss
        y, state, starts = ss.forward(delta, x, a, b, c, d, chunk,
                                      interpret_mode(ctx))
    else:
        y, state, starts = scan_xla(delta, x, a, b, c, d, chunk)
    return {"Out": y, "State": state, "Starts": starts}


def _scan_grad_compute(ins, attrs, ctx, op_index):
    """The kernels' backward from the forward op's own ``Starts``; the XLA
    body differentiates itself."""
    from ..registry import _generic_grad_compute

    x, pre, a, b, c, d, chunk = _scan_args(ins, attrs)
    dy = (ins.get("GRAD::Out") or [None])[0]
    starts = (ins.get("Out::Starts") or [None])[0]
    if not _kernels(ctx, "selective_scan_grad", x, a, chunk) \
            or dy is None or starts is None:
        return _generic_grad_compute(ins, attrs, ctx, op_index)
    from .pallas import interpret_mode, selective_scan as ss

    ddelta, dx, da, db, dc, dd = ss.backward(
        jax.nn.softplus(pre), x, a, b, c, d, starts, dy, chunk,
        interpret_mode(ctx))
    dpre = ddelta * jax.nn.sigmoid(pre)
    grads = {"X": dx, "Delta": dpre, "A": da, "B": db, "C": dc, "D": dd}
    if ins.get("DeltaBias"):
        grads["DeltaBias"] = jnp.sum(dpre, (0, 1))
    return {"GRAD::" + slot: [g.astype(ins[slot][0].dtype)]
            for slot, g in grads.items()}


register_op("selective_scan", ["X", "Delta", "A", "B", "C", "D", "DeltaBias"],
            ["Out", "State", "Starts"], infer=_scan_infer,
            compute=_scan_compute)

# the gradient op the default grad maker emits: on the kernels' body the one
# backward kernel over the forward's own chunk-start states
register_op("selective_scan_grad", (), (), infer=_generic_grad_infer,
            compute=_scan_grad_compute, grad=None,
            doc="gradient of selective_scan")


# -- causal_conv1d -----------------------------------------------------------

def _conv_infer(op, block):
    x = in_var(op, block, "X")
    w = in_var(op, block, "W")
    if len(x.shape) != 3 or len(w.shape) != 2 or w.shape[1] != x.shape[2]:
        raise ValueError("causal_conv1d expects X [B, T, E] and W [K, E]; "
                         "got %s and %s" % (x.shape, w.shape))
    if op.attrs.get("activation") not in (None, "", "silu"):
        raise ValueError("causal_conv1d: activation is silu or none, got %r"
                         % (op.attrs.get("activation"),))
    set_output(op, block, "Out", x.shape, x.dtype)


def _conv_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0]
    w = ins["W"][0].astype(jnp.float32)
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), [(0, 0), (k - 1, 0), (0, 0)])
    y = sum(w[j] * jax.lax.slice_in_dim(xp, j, j + t, axis=1)
            for j in range(k))
    bias = (ins.get("Bias") or [None])[0]
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if attrs.get("activation") == "silu":
        y = jax.nn.silu(y)
    return {"Out": y.astype(x.dtype)}


register_op("causal_conv1d", ["X", "W", "Bias"], ["Out"], infer=_conv_infer,
            compute=_conv_compute)
