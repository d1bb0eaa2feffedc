"""Fused LayerNorm Pallas kernel (fwd + hand-fused vjp).

Parity target: reference ``layer_norm_op.{cc,cu}`` — mean/var reduction,
normalize, affine, and the three-term backward, each a separate CUDA
kernel there; here one VMEM-resident tile pass per direction.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import block_rows, pad_rows


def _fwd_kernel(x_ref, gamma_ref, beta_ref, y_ref, mu_ref, rstd_ref, *,
                eps):
    # statistics in f32 whatever the activation dtype (bf16 under AMP):
    # same contract as the XLA body in ops/norm.py
    x = x_ref[...].astype(jnp.float32)        # [BN, D]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    y_ref[...] = (xhat * gamma_ref[...] + beta_ref[...]).astype(y_ref.dtype)
    mu_ref[...] = mu
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, gamma_ref, mu_ref, rstd_ref, dy_ref,
                dx_ref, dgamma_ref, dbeta_ref):
    x = x_ref[...].astype(jnp.float32)
    g = dy_ref[...].astype(jnp.float32)
    mu = mu_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mu) * rstd
    gg = g * gamma_ref[...]
    m1 = jnp.mean(gg, axis=-1, keepdims=True)
    m2 = jnp.mean(gg * xhat, axis=-1, keepdims=True)
    dx_ref[...] = ((gg - m1 - xhat * m2) * rstd).astype(dx_ref.dtype)
    # partial reductions accumulated across grid steps
    dgamma_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)
    dbeta_ref[...] += jnp.sum(g, axis=0, keepdims=True)


def _row(v):
    return v.astype(jnp.float32).reshape(1, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm(x, gamma, beta, eps=1e-5, interpret=False):
    return _fwd(x, gamma, beta, eps, interpret)[0]


def _fwd(x, gamma, beta, eps, interpret):
    n, d = x.shape
    if n == 0:
        z = jnp.zeros((0, d), x.dtype)
        z1 = jnp.zeros((0, 1), jnp.float32)
        return z, (x, gamma, z1, z1)
    bn, n_pad = block_rows(n, row_bytes=4 * d * 4, max_rows=512)
    # gamma/beta (and the dgamma/dbeta accumulators) ride as [1, D],
    # the 2-D form Mosaic tiles natively (see softmax_xent._specs)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(n_pad // bn,),
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_pad, d), x.dtype),
                   jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n_pad, 1), jnp.float32)],
        interpret=interpret,
    )(pad_rows(x, n_pad), _row(gamma), _row(beta))
    return y[:n], (x, gamma, mu[:n], rstd[:n])


def _bwd(eps, interpret, res, dy):
    x, gamma, mu, rstd = res
    n, d = x.shape
    if n == 0:
        return (jnp.zeros((0, d), x.dtype), jnp.zeros_like(gamma),
                jnp.zeros_like(gamma))
    bn, n_pad = block_rows(n, row_bytes=4 * d * 4, max_rows=512)

    def kernel(x_ref, gamma_ref, mu_ref, rstd_ref, dy_ref,
               dx_ref, dgamma_ref, dbeta_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            dgamma_ref[...] = jnp.zeros_like(dgamma_ref)
            dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

        _bwd_kernel(x_ref, gamma_ref, mu_ref, rstd_ref, dy_ref,
                    dx_ref, dgamma_ref, dbeta_ref)

    dx, dgamma, dbeta = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                  pl.BlockSpec((bn, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_pad, d), x.dtype),
                   jax.ShapeDtypeStruct((1, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        interpret=interpret,
    )(pad_rows(x, n_pad), _row(gamma), pad_rows(mu, n_pad),
      pad_rows(rstd, n_pad), pad_rows(dy, n_pad))
    return (dx[:n], dgamma.reshape(d).astype(gamma.dtype),
            dbeta.reshape(d).astype(gamma.dtype))


layer_norm.defvjp(_fwd, _bwd)
