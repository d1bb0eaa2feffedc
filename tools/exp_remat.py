"""Remat A/B experiment: can rematerialization remove HBM bytes from the
ResNet-50 train step?

PERF.md's roofline analysis puts the b256 bf16 step at ~91% of the v5e's
HBM bandwidth with est. MXU utilization ~28% — compute is cheap, bytes
are not.  jax.checkpoint trades FLOPs for bytes: instead of storing
every intra-block activation for backward, store a subset and recompute
the rest.  Variants:

  base        store everything (XLA CSEs the auto-vjp recompute away)
  names       per-block jax.checkpoint saving ONLY conv outputs
              (checkpoint_name + save_only_these_names): BN/ReLU
              recomputed in backward — elementwise recompute, removes
              the normalized-activation stores
  full        per-block jax.checkpoint saving nothing but block
              boundaries: one extra forward of FLOPs, maximum byte cut
  offload     save_and_offload_only_these_names is TPU-host offload —
              not measured

Usage: python tools/exp_remat.py [--batch 256] [--iters 20]
"""
import argparse
import functools
import time

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

CFG = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2), (3, 512, 2048, 2)]


def conv(x, w, stride):
    kh = w.shape[0]
    pad = (kh - 1) // 2
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "HWIO", "NCHW"))
    return checkpoint_name(y, "conv_out")


def bn_relu(x, gamma, beta, relu=True):
    red = (0, 2, 3)
    bshape = [1, x.shape[1], 1, 1]
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=red)
    var = jnp.maximum(jnp.mean(jnp.square(xf), axis=red) - jnp.square(mean),
                      0.0)
    y = (xf - mean.reshape(bshape)) * jax.lax.rsqrt(var.reshape(bshape) + 1e-5)
    y = y * gamma.reshape(bshape) + beta.reshape(bshape)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def init_params(rng):
    params = []

    def w(sh):
        nonlocal rng
        rng, sub = jax.random.split(rng)
        return jax.random.normal(sub, sh, jnp.float32) * 0.05

    params.append(dict(w=w((7, 7, 3, 64)), g=jnp.ones(64), b=jnp.zeros(64)))
    in_c = 64
    for n, mid, out, stride in CFG:
        for i in range(n):
            blk = dict(
                w1=w((1, 1, in_c, mid)), g1=jnp.ones(mid), b1=jnp.zeros(mid),
                w2=w((3, 3, mid, mid)), g2=jnp.ones(mid), b2=jnp.zeros(mid),
                w3=w((1, 1, mid, out)), g3=jnp.ones(out), b3=jnp.zeros(out),
            )
            if i == 0:
                blk["wp"] = w((1, 1, in_c, out))
                blk["gp"] = jnp.ones(out)
                blk["bp"] = jnp.zeros(out)
            params.append(blk)
            in_c = out
    params.append(dict(fc=w((2048, 1000))))
    return params


def block(p, x, stride, cdtype):
    def cast(a):
        return a.astype(cdtype)

    sc = x
    y = conv(x, cast(p["w1"]), 1)
    y = bn_relu(y, p["g1"], p["b1"])
    y = conv(y, cast(p["w2"]), stride)
    y = bn_relu(y, p["g2"], p["b2"])
    y = conv(y, cast(p["w3"]), 1)
    y = bn_relu(y, p["g3"], p["b3"], relu=False)
    if "wp" in p:
        sc = conv(sc, cast(p["wp"]), stride)
        sc = bn_relu(sc, p["gp"], p["bp"], relu=False)
    return jnp.maximum(y + sc, 0.0)


def forward(params, x, cdtype, mode):
    blk = block
    if mode == "names":
        blk = jax.checkpoint(
            block, static_argnums=(2, 3),
            policy=jax.checkpoint_policies.save_only_these_names("conv_out"))
    elif mode == "full":
        blk = jax.checkpoint(block, static_argnums=(2, 3))

    p = params[0]
    x = conv(x.astype(cdtype), p["w"].astype(cdtype), 2)
    x = bn_relu(x, p["g"], p["b"])
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    i = 1
    for n, mid, out, stride in CFG:
        for j in range(n):
            x = blk(params[i], x, stride if j == 0 else 1, cdtype)
            i += 1
    x = jnp.mean(x.astype(jnp.float32), axis=(2, 3))
    return x @ params[-1]["fc"]


def loss_fn(params, x, labels, cdtype, mode):
    lp = jax.nn.log_softmax(forward(params, x, cdtype, mode))
    return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=("cdtype", "mode"),
                   donate_argnums=(0, 1))
def step(params, vel, x, labels, cdtype, mode):
    loss, grads = jax.value_and_grad(loss_fn)(params, x, labels, cdtype, mode)
    new_p, new_v = [], []
    for p, v in zip(params, vel):
        np_, nv_ = {}, {}
        for k in p:
            nv_[k] = 0.9 * v[k] + grads[len(new_p)][k]
            np_[k] = p[k] - 1e-3 * nv_[k]
        new_p.append(np_)
        new_v.append(nv_)
    return loss, new_p, new_v


def analyze(mode, batch, cdtype):
    params = init_params(jax.random.key(0))
    vel = [{k: jnp.zeros_like(v) for k, v in p.items()} for p in params]
    x = jax.random.normal(jax.random.key(1), (batch, 3, 224, 224),
                          jnp.float32)
    labels = jax.random.randint(jax.random.key(2), (batch,), 0, 1000)
    lowered = step.lower(params, vel, x, labels, cdtype, mode)
    c = lowered.compile()
    ca = c.cost_analysis()
    print("  %s: %.2f GB accessed, %.2f TFLOP per step" %
          (mode, ca.get("bytes accessed", 0) / 1e9, ca.get("flops", 0) / 1e12))
    return params, vel, x, labels


def run(mode, batch, iters, cdtype_name):
    cdtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[cdtype_name]
    params, vel, x, labels = analyze(mode, batch, cdtype)
    for _ in range(3):
        loss, params, vel = step(params, vel, x, labels, cdtype, mode)
    float(loss)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, params, vel = step(params, vel, x, labels, cdtype, mode)
        float(loss)  # fetch-sync
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    ips = batch / best
    print("%s %s b%d: %.1f img/s (%.2f ms/step) vs2610=%.3f" %
          (mode, cdtype_name, batch, ips, best * 1e3, ips / 2610.0))
    return ips


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bf16")
    ap.add_argument("--modes", default="base,names,full")
    args = ap.parse_args()
    for mode in args.modes.split(","):
        run(mode, args.batch, args.iters, args.dtype)
