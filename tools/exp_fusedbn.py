"""Fused bn+relu -> 1x1-conv(matmul) -> stats Pallas kernel experiment.

A/B per ResNet-50 1x1 layer shape (b128): XLA chain (normalize+relu,
matmul, one-pass stats of output) vs one Pallas kernel doing all three in
a single HBM pass over the activation.  Decides whether the fused kernel
ships in ops/pallas/conv_bn.py.
"""
import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, mean_ref, rstd_ref, gamma_ref, beta_ref,
            z_ref, sum_ref, sumsq_ref, *, apply_bn, relu, m, bm):
    i = pl.program_id(1)  # m block (inner)
    x = x_ref[...]
    # rows beyond m (partial last block) are undefined: zero them so the
    # stats epilogue stays clean (their z rows are write-masked anyway)
    tail = (i + 1) * bm > m
    rows_ok = (i * bm + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)) < m
    if apply_bn:
        xf = x.astype(jnp.float32)
        xf = (xf - mean_ref[...]) * rstd_ref[...] * gamma_ref[...] \
            + beta_ref[...]
        if relu:
            xf = jnp.maximum(xf, 0.0)
        xf = jnp.where(rows_ok, xf, 0.0)
        x = xf.astype(x_ref.dtype)
    else:
        if relu:
            x = jnp.maximum(x, 0.0)
        x = jnp.where(rows_ok, x, jnp.zeros_like(x))
    z = jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    z_ref[...] = z.astype(z_ref.dtype)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sumsq_ref[...] = jnp.zeros_like(sumsq_ref)

    sum_ref[...] += jnp.sum(z, axis=0)
    sumsq_ref[...] += jnp.sum(z * z, axis=0)


def fused_bn_matmul_stats(x, w, mean, rstd, gamma, beta, apply_bn=True,
                          relu=True, bm=None, bn=None, interpret=False):
    m, k = x.shape
    n = w.shape[1]
    if bn is None:
        bn = n if n <= 2048 else 512
    if bm is None:
        # biggest m-block fitting VMEM: double-buffered x and out blocks,
        # resident w, and the fp32 dot accumulator on the stack
        bm = 8192
        while bm > 128 and (2 * bm * k * 2 + k * bn * 2 + 2 * bm * bn * 2
                            + bm * bn * 4) > 13 * 2**20:
            bm //= 2
    bm = min(bm, m)
    grid = (pl.cdiv(n, bn), pl.cdiv(m, bm))
    zeros1 = jnp.zeros((1, k), jnp.float32)
    args = (x, w) + ((mean.reshape(1, k), rstd.reshape(1, k),
                      gamma.reshape(1, k), beta.reshape(1, k))
                     if apply_bn else (zeros1, zeros1, zeros1, zeros1))
    z, s, ss = pl.pallas_call(
        functools.partial(_kernel, apply_bn=apply_bn, relu=relu,
                          m=m, bm=bm),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda j, i: (i, 0)),
                  pl.BlockSpec((k, bn), lambda j, i: (0, j)),
                  pl.BlockSpec((1, k), lambda j, i: (0, 0)),
                  pl.BlockSpec((1, k), lambda j, i: (0, 0)),
                  pl.BlockSpec((1, k), lambda j, i: (0, 0)),
                  pl.BlockSpec((1, k), lambda j, i: (0, 0))],
        out_specs=[pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
                   pl.BlockSpec((bn,), lambda j, i: (j,)),
                   pl.BlockSpec((bn,), lambda j, i: (j,))],
        out_shape=[jax.ShapeDtypeStruct((m, n), x.dtype),
                   jax.ShapeDtypeStruct((n,), jnp.float32),
                   jax.ShapeDtypeStruct((n,), jnp.float32)],
        interpret=interpret,
    )(*args)
    return z, s, ss


def xla_chain(x, w, mean, rstd, gamma, beta, apply_bn=True, relu=True):
    if apply_bn:
        xf = x.astype(jnp.float32)
        xf = (xf - mean) * rstd * gamma + beta
        if relu:
            xf = jnp.maximum(xf, 0.0)
        x = xf.astype(x.dtype)
    elif relu:
        x = jnp.maximum(x, 0.0)
    z = (x @ w).astype(x.dtype)
    zf = z.astype(jnp.float32)
    return z, jnp.sum(zf, axis=0), jnp.sum(zf * zf, axis=0)


SHAPES = [  # (M, K, N) for b128 ResNet-50 1x1 convs
    (128 * 56 * 56, 256, 64),
    (128 * 56 * 56, 64, 256),
    (128 * 28 * 28, 512, 128),
    (128 * 28 * 28, 128, 512),
    (128 * 14 * 14, 1024, 256),
    (128 * 14 * 14, 256, 1024),
    (128 * 7 * 7, 2048, 512),
    (128 * 7 * 7, 512, 2048),
]


def bench_one(fn, args, iters=30):
    f = jax.jit(fn)
    z, s, ss = f(*args)
    jax.block_until_ready(z)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            z, s, ss = f(*args)
        np.asarray(s)  # fetch-sync
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    return best, (z, s, ss)


def bench_chain(mode, m, k_out, k_mid, depth, dtype, iters=10):
    """Chain `depth` bottleneck pairs (k_out->k_mid->k_out) inside one jit
    so per-dispatch latency amortizes; returns seconds per pair."""
    ws = []
    for d in range(depth):
        ws.append((
            (jax.random.normal(jax.random.key(2 * d), (k_out, k_mid),
                               jnp.float32) * (1.0 / k_out ** 0.5)
             ).astype(dtype),
            (jax.random.normal(jax.random.key(2 * d + 1), (k_mid, k_out),
                               jnp.float32) * (1.0 / k_mid ** 0.5)
             ).astype(dtype),
        ))

    def norm_params(s, ss, c):
        mean = s / m
        var = jnp.maximum(ss / m - mean * mean, 0.0)
        return mean, jax.lax.rsqrt(var + 1e-5)

    ones = {k_mid: jnp.ones((k_mid,), jnp.float32),
            k_out: jnp.ones((k_out,), jnp.float32)}
    zeros = {k_mid: jnp.zeros((k_mid,), jnp.float32),
             k_out: jnp.zeros((k_out,), jnp.float32)}

    def one(mode, x, w, mean, rstd, c):
        if mode.startswith("pallas"):
            return fused_bn_matmul_stats(x, w, mean, rstd, ones[c],
                                         zeros[c])
        return xla_chain(x, w, mean.reshape(1, -1), rstd.reshape(1, -1),
                         ones[c].reshape(1, -1), zeros[c].reshape(1, -1))

    def op_nchw(x4, w, mean, rstd, c):
        # models the framework op boundary: NCHW logical in/out, kernel
        # works on [M, C] row-major — transposes between chained ops must
        # cancel in XLA for this integration to be viable
        b, cc, h, wd = x4.shape
        x2 = x4.transpose(0, 2, 3, 1).reshape(-1, cc)
        z, s, ss = fused_bn_matmul_stats(x2, w, mean, rstd, ones[c],
                                         zeros[c])
        z4 = z.reshape(b, h, wd, w.shape[1]).transpose(0, 3, 1, 2)
        return z4, s, ss

    def step(x):
        # x enters raw (pre-BN); stats computed on the fly like the net does
        zf = x.astype(jnp.float32)
        if mode == "pallas_nchw":
            s = jnp.sum(zf, (0, 2, 3))
            ss = jnp.sum(zf * zf, (0, 2, 3))
        else:
            s, ss = jnp.sum(zf, 0), jnp.sum(zf * zf, 0)
        for wa, wb in ws:
            mean, rstd = norm_params(s, ss, k_out)
            if mode == "pallas_nchw":
                z, s, ss = op_nchw(x, wa, mean, rstd, k_out)
                mean, rstd = norm_params(s, ss, k_mid)
                x, s, ss = op_nchw(z, wb, mean, rstd, k_mid)
            else:
                z, s, ss = one(mode, x, wa, mean, rstd, k_out)
                mean, rstd = norm_params(s, ss, k_mid)
                x, s, ss = one(mode, z, wb, mean, rstd, k_mid)
        return x, s

    f = jax.jit(step)
    if mode == "pallas_nchw":
        b = 128
        h = int((m // b) ** 0.5)
        x0 = jax.random.normal(jax.random.key(9), (b, k_out, h, h),
                               jnp.float32).astype(dtype)
    else:
        x0 = jax.random.normal(jax.random.key(9), (m, k_out), jnp.float32
                               ).astype(dtype)
    x, s = f(x0)
    jax.block_until_ready(x)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            x, s = f(x0)
        np.asarray(s)
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    return best / depth


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bf16")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--chain", action="store_true")
    args = ap.parse_args()
    if args.chain:
        dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
        # (M, k_out, k_mid) per ResNet-50 stage at b128
        for m, k_out, k_mid, depth in [
                (128 * 56 * 56, 256, 64, 6),
                (128 * 28 * 28, 512, 128, 8),
                (128 * 14 * 14, 1024, 256, 12),
                (128 * 7 * 7, 2048, 512, 12)]:
            # interleave the modes: run-to-run noise is larger than
            # the effect size in any single window
            tx = tp = tn = 1e9
            for _ in range(3):
                tx = min(tx, bench_chain("xla", m, k_out, k_mid, depth,
                                         dtype))
                tp = min(tp, bench_chain("pallas", m, k_out, k_mid, depth,
                                         dtype))
                tn = min(tn, bench_chain("pallas_nchw", m, k_out, k_mid,
                                         depth, dtype))
            gb = (2 * m * k_out + 2 * m * k_mid) * (
                2 if dtype == jnp.bfloat16 else 4) / 1e9
            print("M%7d %4d<->%4d: xla %.3f ms/pair (%.0f GB/s)  pallas "
                  "%.3f (%.0f GB/s, %.2fx)  nchw %.3f (%.2fx)" %
                  (m, k_out, k_mid, tx * 1e3, gb / tx, tp * 1e3, gb / tp,
                   tx / tp, tn * 1e3, tx / tn))
        return
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    tot_x, tot_p = 0.0, 0.0
    for (m, k, n) in SHAPES:
        key = jax.random.key(0)
        x = jax.random.normal(key, (m, k), jnp.float32).astype(dtype)
        w = (jax.random.normal(jax.random.key(1), (k, n), jnp.float32)
             * 0.05).astype(dtype)
        mean = jnp.zeros((k,), jnp.float32) + 0.1
        rstd = jnp.ones((k,), jnp.float32)
        gamma = jnp.ones((k,), jnp.float32)
        beta = jnp.zeros((k,), jnp.float32)
        if args.check:
            zp, sp, ssp = fused_bn_matmul_stats(x, w, mean, rstd, gamma,
                                                beta)
            zx, sx, ssx = xla_chain(x, w, mean.reshape(1, k),
                                    rstd.reshape(1, k), gamma.reshape(1, k),
                                    beta.reshape(1, k))
            err = np.abs(np.asarray(zp, np.float32)
                         - np.asarray(zx, np.float32)).max()
            serr = np.abs(np.asarray(sp) - np.asarray(sx)).max() / m
            print("  check M%d K%d N%d: z err %.4g  s err %.4g" %
                  (m, k, n, err, serr))
            continue
        tx, _ = bench_one(
            lambda x, w: xla_chain(x, w, mean.reshape(1, k),
                                   rstd.reshape(1, k), gamma.reshape(1, k),
                                   beta.reshape(1, k)), (x, w))
        tp, _ = bench_one(
            lambda x, w: fused_bn_matmul_stats(x, w, mean, rstd, gamma,
                                               beta), (x, w))
        tot_x += tx
        tot_p += tp
        gb = (m * k + m * n) * x.dtype.itemsize / 1e9
        print("M%7d K%5d N%5d: xla %.3f ms (%.0f GB/s)  pallas %.3f ms "
              "(%.0f GB/s)  speedup %.2fx" %
              (m, k, n, tx * 1e3, gb / tx, tp * 1e3, gb / tp, tx / tp))
    if tot_p:
        print("TOTAL: xla %.3f ms  pallas %.3f ms  speedup %.2fx" %
              (tot_x * 1e3, tot_p * 1e3, tot_x / tot_p))


if __name__ == "__main__":
    main()
