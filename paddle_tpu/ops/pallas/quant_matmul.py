"""Fused dequant-matmul Pallas kernels (int8 weights, ISSUE 14).

The ``dequant_matmul`` op's hand-tiled body: int8 weights stay int8 in
HBM (the whole point — 1/4 the weight bytes of the f32 master copies
the bf16 AMP path re-reads every step) and dequantize **in register**
on the way into the dot:

* ``weight_only`` — the weight tile casts int8 -> f32 inside VMEM and
  feeds an f32-accumulated MXU dot; the per-output-channel dequant
  scale multiplies the accumulator before it leaves the kernel.
  Activations keep their dtype (bf16/f32).
* ``dynamic`` — the activation tile additionally quantizes to int8 in
  register (per-row abs-max grid over the full K it already holds) and
  the dot runs int8 x int8 with ``preferred_element_type=int32``; both
  grids apply to the int32 accumulator in one fused epilogue.

Tiling: grid over (M, N) blocks with the full (padded) K resident per
block — serving matmuls are K<=8k where a K-resident [K, 128] int8
stripe plus its f32 cast is well under the VMEM budget, and keeping K
whole means the dynamic mode's per-row abs-max needs no cross-block
reduction.  K pads to the 128 lane, M to the f32 sublane, N to the
128-lane output tile; padding is zeros, which neither dot nor the
abs-max grid observes.

On CPU the kernels run in interpreter mode (numerical parity tests);
the XLA fallback (``ops/quantize.xla_dequant_matmul``) is the
measured-A/B alternative the autotune decision table selects against.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VMEM working-set budget (bytes); the chip's scoped limit is 16MB,
# leave headroom for Mosaic's own buffers (same budget as conv_bn.py)
_VMEM_BUDGET = 11 * 2 ** 20
_BN = 128          # output-channel (lane) block
_MAX_BM = 256      # row block cap


def _ceil_to(x, m):
    return ((x + m - 1) // m) * m


def _pick_bm(m, kp, itemsize):
    """Largest row block whose double-buffered IO fits the budget next
    to the K-resident weight stripe."""
    resident = kp * _BN * (1 + 4) + _BN * 4      # int8 qw + f32 cast + s
    bm = min(_MAX_BM, _ceil_to(max(m, 1), 8))
    while bm > 8:
        io = 2 * bm * kp * max(itemsize, 4) + 2 * bm * _BN * 4
        if resident + io <= _VMEM_BUDGET:
            break
        bm //= 2
    return max(8, bm)


def supported(m, k, n, dtype):
    """Shape gate: K must stay VMEM-resident per output stripe and the
    tiles must be worthwhile; anything else falls back to the XLA
    dot_general path."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return False
    if k < 128 or n < 128 or m < 1:
        return False   # tiny problems: dispatch overhead beats the fusion
    kp = _ceil_to(k, 128)
    resident = kp * _BN * (1 + 4) + _BN * 4
    min_io = 2 * 8 * kp * 4 + 2 * 8 * _BN * 4
    return resident + min_io <= _VMEM_BUDGET


def _wo_kernel(x_ref, qw_ref, s_ref, o_ref):
    # int8 values are exact in f32: dequant IS the cast, the channel
    # scale rides the accumulator epilogue
    acc = jnp.dot(x_ref[...].astype(jnp.float32),
                  qw_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[...] = acc * s_ref[...]


def _dyn_kernel(x_ref, qw_ref, s_ref, o_ref, *, rng):
    x = x_ref[...].astype(jnp.float32)
    # per-row grid over the FULL K (resident in this block); zero
    # padding never raises an abs-max
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                     1e-12) / rng
    qx = jnp.clip(jnp.round(x / sx), -rng, rng).astype(jnp.int8)
    acc = jax.lax.dot_general(qx, qw_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    o_ref[...] = acc.astype(jnp.float32) * sx * s_ref[...]


def dequant_matmul(x2, qw, scale, mode="weight_only", bit_length=8,
                   interpret=False):
    """Fused dequant-matmul: ``x2`` [M, K] bf16/f32, ``qw`` [K, N] int8,
    ``scale`` [N] f32 dequant multipliers.  Returns the f32 accumulator
    [M, N] (callers cast to the activation dtype)."""
    m, k = x2.shape
    n = qw.shape[1]
    kp = _ceil_to(k, 128)
    np_ = _ceil_to(n, _BN)
    bm = _pick_bm(m, kp, jnp.dtype(x2.dtype).itemsize)
    mp = _ceil_to(m, bm)
    if (mp, kp) != (m, k):
        x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        qw = jnp.pad(qw, ((0, kp - k), (0, np_ - n)))
    if np_ != n:
        scale = jnp.pad(scale, (0, np_ - n))
    # the scale rides as [1, N]: a 1-D (128,) block over [N] meets XLA's
    # T(1024) layout for 1-D f32 operands and Mosaic refuses it
    scale = scale.astype(jnp.float32).reshape(1, np_)
    if mode == "weight_only":
        kernel = _wo_kernel
    elif mode == "dynamic":
        rng = float((1 << (int(bit_length) - 1)) - 1)
        kernel = functools.partial(_dyn_kernel, rng=rng)
    else:
        raise ValueError("unknown dequant_matmul mode %r" % mode)
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // _BN),
        in_specs=[pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
                  pl.BlockSpec((kp, _BN), lambda i, j: (0, j)),
                  pl.BlockSpec((1, _BN), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, _BN), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(x2, qw, scale)
    return out[:m, :n]
