"""The backward of a vocabulary head — ``mul`` (+ bias) into
``softmax_with_cross_entropy`` — as ONE kernel: the logits' gradient is made
once, tile by tile, and feeds the bias's sum and both products.

Op by op the chain is three consumers of the stored ``[N, V]`` logits —
``softmax_with_cross_entropy_grad``, ``elementwise_add_grad``, ``mul_grad`` —
and XLA never writes the gradient between them: it rebuilds it (bias add,
``exp``, the label's compare, smoothing, the row's scale) inside the dX
product, inside the dW product and inside the bias's column reduction, three
reads of the logits and three times the elementwise work.  At a contraction
of 512 that work is of the order of the products' own (PERF.md 6.21).

Here a grid step holds one ``[bn, bv]`` tile of the stored logits and forms

    g = ct * exp(z + b - lse) - onehot(label) * ct * (1 - eps) - ct * eps / V

in float32 — the gradient of ``loss = (1 - eps) * nll + eps * (lse -
mean(z))`` (``eps`` 0: plain cross entropy) times the row's ``Loss@GRAD`` —
and from that one tile adds, all in float32,

* ``db[block] += column sums of g`` (the float32 values, as the bias's
  gradient sums them today),
* ``dW[:, block] += x^T g`` and ``dX[rows] += g w^T`` with ``g`` rounded to the
  operands' dtype, as ``mul_grad``'s cast of ``Out@GRAD`` rounds it today.

The grid is (vocabulary blocks, row blocks), rows inside: a ``[D, bv]`` block
of dW and its ``[1, bv]`` of db stay in VMEM over the rows and are written
once; the WHOLE float32 dX ``[N, D]`` is one resident output block (a v5e
core has 128 MiB of VMEM; 32 MiB at ``[16384, 512]``), added to by every tile
and written back when the grid ends.  The logits are read once.  ``x`` comes
transposed from XLA (a product contracting dim 0 of both operands would
transpose the block in every step); ``w`` is the forward's own ``[D, V]``
(contracting dim 1 of both is the MXU's transposed load: no bundle more in
Mosaic's schedule).  On a v5e at ``[16384, 512] x [512, 32000]`` the kernel
reads 6.29 ms in the step against an MXU floor of 5.46 (PERF.md 6.21).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import run_traced

_LANES = 128
# What a grid step may hold, and the limit the kernel is compiled under: the
# resident dX beside a step's tiles (below).
_VMEM_LIMIT = 100 * 1024 * 1024
# Rows and vocabulary columns of a tile, at most: the float32 tile and its
# rounded copy are values of the body (6 bytes an element beside the
# double-buffered 2 of the stored logits).
_MAX_ROWS = 1024
_MAX_COLS = 2048
# The widest operands the kernel is the faster body for: the elementwise work
# it saves is a fixed cost an element of the logits, the products' grow with
# the width, and XLA's own run nearer the MXU's peak than the kernel's (83%)
# once they hide it — measured ahead at 512, behind at 2048 (PERF.md 6.21).
_MAX_WIDTH = 1024


def _largest_divisor(n, unit, most):
    """The largest multiple of ``unit`` that divides ``n`` and is at most
    ``most`` (None: there is none)."""
    for k in range(min(most, n) // unit, 0, -1):
        if n % (k * unit) == 0:
            return k * unit
    return None


def blocks(n, d, v):
    """(bn, bv): rows and vocabulary columns of a tile for ``n`` rows of
    ``d``-wide operands over ``v`` columns — whole lane tiles that divide
    ``n`` and ``v`` — or None where there are none."""
    bn = _largest_divisor(n, _LANES, _MAX_ROWS)
    bv = _largest_divisor(v, _LANES, _MAX_COLS)
    if bn is None or bv is None or d % _LANES or d > _MAX_WIDTH:
        return None
    return bn, bv


def vmem_bytes(n, d, bn, bv, itemsize):
    """What a grid step holds: the resident float32 dX, the dW block's
    accumulator and output, and the double-buffered tiles of the logits,
    ``x^T`` and ``w`` with the body's float32 tile and its rounded copy."""
    return (n * d * 4 + d * bv * (4 + 2 * itemsize)
            + bn * bv * (2 * itemsize + 4 + itemsize)
            + 2 * (d * bn + bv * d) * itemsize
            + 3 * 2 * bn * _LANES * 4)


def supported(n, d, v, dtype):
    """Whether the kernel takes ``n`` rows of ``d``-wide ``dtype`` operands
    over ``v`` columns: float32 or bf16, whole tiles, no wider than it wins
    at, inside the VMEM limit."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    tile = blocks(n, d, v)
    return tile is not None and vmem_bytes(
        n, d, *tile, dtype.itemsize) <= _VMEM_LIMIT * 7 // 8


def _kernel(z_ref, bias_ref, lse_ref, ct_ref, label_ref, xt_ref, w_ref,
            dx_ref, dw_ref, db_ref, dw_s, *, bn, bv, ni, eps, v):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (i == 0))
    def _():
        dx_ref[...] = jnp.zeros(dx_ref.shape, jnp.float32)

    @pl.when(i == 0)
    def _():
        dw_s[...] = jnp.zeros(dw_s.shape, jnp.float32)
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)

    ct = ct_ref[...]
    z = z_ref[...].astype(jnp.float32) + bias_ref[...]
    hit = (j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
           == label_ref[...])
    smooth = ct * (eps / v)
    g = ct * jnp.exp(z - lse_ref[...]) \
        - jnp.where(hit, ct * (1.0 - eps) + smooth, smooth)
    db_ref[...] += jnp.sum(g, axis=0, keepdims=True)
    gb = g.astype(xt_ref.dtype)
    dw_s[...] += jnp.dot(xt_ref[...], gb, preferred_element_type=jnp.float32)
    rows = pl.ds(pl.multiple_of(i * bn, bn), bn)
    dx_ref[rows, :] += jax.lax.dot_general(
        gb, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _():
        dw_ref[...] = dw_s[...].astype(dw_ref.dtype)


def _call(z, bias, lse, ct, label, xt, w, *, eps, interpret):
    (n, v), d = z.shape, xt.shape[0]
    bn, bv = blocks(n, d, v)
    col = pl.BlockSpec((bn, 1), lambda j, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, bn=bn, bv=bv, ni=n // bn, eps=eps, v=v),
        grid=(v // bv, n // bn),
        in_specs=[pl.BlockSpec((bn, bv), lambda j, i: (i, j)),
                  pl.BlockSpec((1, bv), lambda j, i: (0, j)),
                  col, col, col,
                  pl.BlockSpec((d, bn), lambda j, i: (0, i)),
                  pl.BlockSpec((d, bv), lambda j, i: (0, j))],
        out_specs=[pl.BlockSpec((n, d), lambda j, i: (0, 0),
                                pipeline_mode=pl.Buffered(1)),
                   pl.BlockSpec((d, bv), lambda j, i: (0, j)),
                   pl.BlockSpec((1, bv), lambda j, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((n, d), jnp.float32),
                   jax.ShapeDtypeStruct((d, v), xt.dtype),
                   jax.ShapeDtypeStruct((1, v), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(z, bias, lse, ct, label, xt, w)


def head_grad(x, w, z, bias, lse, label, ct, eps=0.0, interpret=False):
    """(dX ``[N, D]``, dW ``[D, V]`` in the operands' dtype, db ``[V]``
    float32) of a head's loss: ``x`` ``[N, D]`` and ``w`` ``[D, V]`` the
    product's operands, ``z`` ``[N, V]`` its stored output, ``bias`` ``[V]``
    float32 (zeros: a head without one), ``lse`` ``[N]`` the rows'
    log-sum-exp of ``z + bias`` (float32), ``label`` ``[N]`` integers, ``ct``
    ``[N]`` the rows' ``Loss@GRAD`` (float32), ``eps`` the uniform label
    smoothing."""
    n, v = z.shape

    def column(a, dtype):
        return a.reshape(n, 1).astype(dtype)
    dx, dw, db = run_traced(
        "head_grad", _call,
        (z, bias.reshape(1, v).astype(jnp.float32), column(lse, jnp.float32),
         column(ct, jnp.float32), column(label, jnp.int32), x.T, w),
        eps=float(eps), interpret=bool(interpret))
    return dx.astype(x.dtype), dw, db.reshape(v)
