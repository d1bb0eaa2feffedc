"""Short-sequence attention over the projections' own layout (fwd + bwd).

``flash_attention.py`` tiles ONE (batch, head) pair per grid step and
streams K/V blocks past a resident Q block: right at T=4096, where a
head's scores alone are 64 MB, and wrong at T=64, where a grid step
holds 8 KB of work and a training step would take ~110,000 of them.
Short sequences turn the problem round: one batch row's Q, K and V for
ALL heads (64 x 512 bf16 = 64 KB each) fit VMEM many times over, in the
layout the q/k/v projections already write.  So this kernel takes
``[B, T, H*D]`` operands, a grid step takes a block of batch rows with
all their heads, a head is a static slice of the last axis, and the
scores, the softmax and the probabilities live in VMEM only.  The
``[B, H, T, D]`` head split / merge copies the Fluid program makes
around the op cancel against the op's own merge / split
(``ops/attention.py``), so nothing is relaid out on either side.

Semantics are ``flash_attention.reference_attention``'s, to the
operand: products take the input dtype with float32 accumulation, the
softmax and its statistics are float32, the weights are normalised and
dropped BEFORE the cast for the PV product; the structural masks
(``k_len`` per row, ``causal``, suffix-causal when ``Tq < Tk``), the
fully-masked-row contract (zeros out, zero gradients) and the dropout
counter hash (``flash_attention._keep_mask`` on ``b*H + h``, global
``gq``, ``gk``) are the same functions, so the mask is bit for bit the
one the XLA body draws.

The backward is ONE kernel: with a block's Q, K, V and dO resident it
recomputes the weights once and writes dQ, dK and dV (the long-sequence
kernel needs a dQ and a dK/dV pass because its grid splits the rows;
here a row is whole).  It takes nothing from the forward: a row's
scores are all there, so the softmax statistics cost two reductions
over VMEM, not a log-sum-exp written to HBM and read back, and ``delta
= rowsum(y * dy)`` comes from the recomputed weights, so O is not read
again either.  That is what lets ``ops/attention.py`` register the
op's gradient as this one kernel over the program's own Q, K, V and
dO, where the generic gradient would re-run the forward kernel under
``jax.vjp`` (XLA does not merge two identical custom calls).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention_xla import _NEG_INF, _causal_valid, _keep_mask

# what one grid step may hold: Pallas double-buffers every block, and
# the default scoped-VMEM limit of a v5e core is 16 MiB; the kernel
# asks for no more, so 12 MiB of blocks and per-head temporaries leaves
# the compiler its room
_VMEM_BUDGET = 12 * 1024 * 1024
_MAX_ROWS = 8


def _row_bytes(tq, tk, n_head, d, dtype):
    """VMEM bytes one batch row costs the BACKWARD kernel (the larger of
    the two): Q, dO, dQ are ``[Tq, H*D]``, K, V, dK, dV ``[Tk, H*D]``,
    each block double-buffered; per head about six float32 ``[Tq, Tk]``
    temporaries are live (scores, weights, the keep mask's hash, dy, dS,
    and a cast), padded to the 128-lane tile."""
    itemsize = np.dtype(dtype).itemsize
    hd = n_head * d
    blocks = 2 * (3 * tq + 4 * tk) * hd * itemsize
    temps = 6 * tq * max(tk, 128) * 4
    return blocks + temps


def supported(q_shape, k_shape, dtype):
    """Whether the packed kernel takes these ``[B, H, T, D]`` shapes:
    one row's backward blocks fit ``_VMEM_BUDGET`` and the tiles are
    ones Mosaic lays out (sequence lengths in whole sublane groups, the
    packed axis in whole lane tiles).  At H*D = 512 in bf16 a row costs
    1.1 MB at T=64, 2.1 MB at T=128, 5.0 MB at T=256, 8.6 MB at T=384
    and 13.0 MB at T=512: the bound admits self-attention up to T=480
    (336 in float32) and stops there; measured on the v5e up to 384."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    _, h, tq, d = q_shape
    tk = k_shape[2]
    if tq < 8 or tk < 8 or tq % 8 or tk % 8 or (h * d) % 128:
        return False
    if np.dtype(dtype) not in (np.dtype(jnp.bfloat16), np.dtype("float32")):
        return False
    return _row_bytes(tq, tk, h, d, dtype) <= _VMEM_BUDGET


def _block_rows(b, tq, tk, n_head, d, dtype):
    """Batch rows a grid step takes: the largest divisor of B that fits
    the budget, at most ``_MAX_ROWS`` (32 steps at b=256 already hide
    the ~0.35 us a grid step costs)."""
    most = max(1, min(_MAX_ROWS, _VMEM_BUDGET //
                      _row_bytes(tq, tk, n_head, d, dtype)))
    return max(r for r in range(1, most + 1) if b % r == 0)


def _masks(klen_ref, off_ref, *, bb, tq, tk, causal):
    """What every head of a block shares: the validity mask
    ``[bb, Tq, Tk]``, the position grids, and the rows' global batch
    index ``[bb, 1, 1]`` (for the dropout hash under a mesh the shard's
    first global row rides in ``off_ref[0]``)."""
    gq = jax.lax.broadcasted_iota(jnp.int32, (bb, tq, tk), 1)
    gk = jax.lax.broadcasted_iota(jnp.int32, (bb, tq, tk), 2)
    klen = klen_ref[...]                               # [bb, 1, 1]
    valid = gk < klen
    if causal:
        valid = valid & _causal_valid(gq, gk, klen, tq, tk)
    rows = off_ref[0] + pl.program_id(0) * bb + \
        jax.lax.broadcasted_iota(jnp.int32, (bb, 1, 1), 0)
    return valid, gq, gk, rows


def _qk(a, b):
    """[bb, M, D] x [bb, N, D] -> [bb, M, N], float32 accumulation."""
    return jax.lax.dot_general(a, b, (((2,), (2,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _pv(a, b):
    """[bb, M, N] x [bb, N, D] -> [bb, M, D], float32 accumulation."""
    return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """[bb, M, N] x [bb, M, D] -> [bb, N, D]: contracts the rows."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _weights(q_ref, k_ref, sl, valid, scale):
    """One head's scaled Q slice and softmax weights ``[bb, Tq, Tk]``
    (float32, before dropout; a fully masked row is all zeros)."""
    q = (q_ref[:, :, sl].astype(jnp.float32) * scale).astype(q_ref.dtype)
    s = jnp.where(valid, _qk(q, k_ref[:, :, sl]), _NEG_INF)
    p = jnp.where(valid, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)),
                  0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return q, p * (1.0 / jnp.maximum(l, 1e-37))


def _fwd_kernel(klen_ref, seed_ref, off_ref, q_ref, k_ref, v_ref, o_ref, *,
                n_head, d, scale, causal, rate, bb, tq, tk):
    valid, gq, gk, rows = _masks(klen_ref, off_ref, bb=bb, tq=tq, tk=tk,
                                 causal=causal)
    for h in range(n_head):
        sl = slice(h * d, (h + 1) * d)
        _, y = _weights(q_ref, k_ref, sl, valid, scale)
        if rate:
            bh = rows * off_ref[2] + (off_ref[1] + h)
            y = jnp.where(_keep_mask(seed_ref[0], bh, gq, gk, rate), y, 0.0)
        o_ref[:, :, sl] = _pv(y.astype(q_ref.dtype),
                              v_ref[:, :, sl]).astype(o_ref.dtype)


def _bwd_kernel(klen_ref, seed_ref, off_ref, q_ref, k_ref, v_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *, n_head, d, scale, causal, rate,
                bb, tq, tk):
    in_dtype = q_ref.dtype
    valid, gq, gk, rows = _masks(klen_ref, off_ref, bb=bb, tq=tq, tk=tk,
                                 causal=causal)
    for h in range(n_head):
        sl = slice(h * d, (h + 1) * d)
        q, y = _weights(q_ref, k_ref, sl, valid, scale)
        k = k_ref[:, :, sl]
        do = do_ref[:, :, sl]
        g = _qk(do, v_ref[:, :, sl])                   # dL/d(dropped y)
        if rate:
            bh = rows * off_ref[2] + (off_ref[1] + h)
            keep = _keep_mask(seed_ref[0], bh, gq, gk, rate)
            y_drop = jnp.where(keep, y, 0.0)
            g = jnp.where(keep, g, 0.0)
        else:
            y_drop = y
        dv_ref[:, :, sl] = _tn(y_drop.astype(in_dtype),
                               do).astype(dv_ref.dtype)
        delta = jnp.sum(y * g, axis=-1, keepdims=True)
        ds = (y * (g - delta)).astype(in_dtype)
        dq_ref[:, :, sl] = (_pv(ds, k) * scale).astype(dq_ref.dtype)
        dk_ref[:, :, sl] = _tn(ds, q).astype(dk_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "kernel", "n_out", "n_head", "causal", "rate", "scale", "interpret"))
def _call(kernel, arrays, n_out, k_len, seed, offsets, n_head, causal, rate,
          scale, interpret):
    """Run ``kernel`` over blocks of batch rows of the packed ``arrays``
    (Q-shaped and K-shaped ``[B, T, H*D]``); the first ``n_out`` outputs
    take the first ``n_out`` arrays' shapes.  Jitted so that a step's 18
    attentions trace and lower three kernels (self, causal, cross), not
    18: a head loop unrolled eight times is ~30 ms of tracing a call."""
    q, k = arrays[0], arrays[1]
    b, tq, hd = q.shape
    tk = k.shape[1]
    d = hd // n_head
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # the XLA body multiplies q by the scale ROUNDED to q's dtype
    scale = float(np.asarray(scale, dtype=q.dtype))
    bb = _block_rows(b, tq, tk, n_head, d, q.dtype)
    if k_len is None:
        klen = jnp.full((b,), tk, jnp.int32)
    else:
        klen = jnp.minimum(k_len.astype(jnp.int32).reshape(b), tk)
    if seed is None:
        seed = jnp.zeros((), jnp.uint32)
    off = jnp.stack([jnp.asarray(o, jnp.int32)
                     for o in (offsets or (0, 0, n_head))])

    def row(i):
        return (i, 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    specs = [pl.BlockSpec((bb,) + a.shape[1:], row) for a in arrays]
    return pl.pallas_call(
        functools.partial(kernel, n_head=n_head, d=d, scale=scale,
                          causal=causal, rate=rate, bb=bb, tq=tq, tk=tk),
        grid=(b // bb,),
        in_specs=[pl.BlockSpec((bb, 1, 1), row), smem, smem] + specs,
        out_specs=specs[:n_out],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in arrays[:n_out]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(klen.reshape(b, 1, 1), seed.astype(jnp.uint32).reshape(1), off,
      *arrays)


def _forward(q, k, v, k_len, seed, offsets, n_head, causal, rate, scale,
             interpret):
    return _call(_fwd_kernel, (q, k, v), 1, k_len, seed, offsets, n_head,
                 causal, rate, scale, interpret)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def packed_attention(q, k, v, k_len, seed, offsets, n_head, causal=False,
                     dropout_rate=0.0, scale=None, interpret=False):
    """Attention over packed heads.  q ``[B, Tq, H*D]``; k/v ``[B, Tk,
    H*D]``; k_len ``[B]`` int32 valid-key counts (None = all valid); seed
    uint32 scalar.  ``offsets`` = (first global batch row, first global
    head, global head count) of this shard, int32 scalars, for the
    dropout hash under a mesh (None = unsharded).  Returns
    ``[B, Tq, H*D]`` in q's dtype."""
    return _forward(q, k, v, k_len, seed, offsets, n_head, causal,
                    dropout_rate, scale, interpret)


def packed_attention_bwd(q, k, v, dout, k_len, seed, offsets, n_head,
                         causal=False, dropout_rate=0.0, scale=None,
                         interpret=False):
    """(dQ, dK, dV) of :func:`packed_attention` for the cotangent
    ``dout`` ``[B, Tq, H*D]``, in one kernel, from the forward's inputs
    alone."""
    # dQ, dK, dV take the shapes of the first three arrays: Q, K, V
    return tuple(_call(_bwd_kernel, (q, k, v, dout.astype(q.dtype)), 3,
                       k_len, seed, offsets, n_head, causal, dropout_rate,
                       scale, interpret))


def _vjp_fwd(q, k, v, k_len, seed, offsets, n_head, causal, rate, scale,
             interpret):
    out = _forward(q, k, v, k_len, seed, offsets, n_head, causal, rate,
                   scale, interpret)
    return out, (q, k, v, k_len, seed, offsets)


def _vjp_bwd(n_head, causal, rate, scale, interpret, res, dout):
    q, k, v, k_len, seed, offsets = res
    return packed_attention_bwd(q, k, v, dout, k_len, seed, offsets, n_head,
                                causal, rate, scale, interpret) \
        + (None, None, None)


packed_attention.defvjp(_vjp_fwd, _vjp_bwd)
