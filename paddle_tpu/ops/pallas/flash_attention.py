"""Flash attention Pallas kernel — fused blockwise attention (fwd + bwd).

The reference's entire attention story is ``nets.scaled_dot_product_attention``
(``python/paddle/fluid/nets.py:323``): materialize the [B, H, Tq, Tk] score
matrix, softmax it, optionally dropout, then a second batched matmul.  On TPU
that round-trips O(T^2) scores through HBM three times per direction.  This
kernel is the single-chip sibling of ``parallel/ring_attention.py``'s online
softmax: Q blocks stay VMEM-resident, K/V stream through VMEM tiles, and the
softmax normalizer is accumulated online, so HBM traffic is O(T*D) and the
QK^T / PV products run back-to-back on the MXU without score materialization.

Masking is structural rather than a dense additive bias: a per-batch key
length (padding) and an optional causal flag — exactly the two mask shapes
the Transformer model builds (padding_attn_bias + causal_mask).  Causal
with Tq == Tk is top-aligned self-attention; with Tq < Tk the queries are
the suffix of the klen valid keys (query i at global position
klen - Tq + i) — the KV-cache decode shape, where a single-token or
chunked query attends a longer cache without the full-length-call
workaround.

Dropout on the attention weights is computed *inside* the kernel from a
counter-based hash of (head, query, key) positions, so the backward kernels
regenerate the identical mask without ever materializing it.  Semantics are
the reference dropout default ``downgrade_in_infer`` (``dropout_op.cc``):
training masks without upscaling, eval scales weights by (1 - p) — applied
by the op as an output scale, since it commutes with the PV matmul.  The hash is a
murmur3-style integer finalizer — deterministic, pure jnp (works in Pallas
interpret mode on CPU), and keyed on the executor-threaded PRNG so separate
ops/steps decorrelate.

Backward follows the standard flash decomposition: host-side
``delta = rowsum(dO * O)`` (this identity holds under dropout too, because
sum_j g_j y_j = dO . O), then one kernel producing dQ (grid over Q blocks)
and one producing dK/dV (grid over K blocks), each recomputing the
probabilities from the saved log-sum-exp.

Long-sequence scope: K/V live fully in VMEM per (batch, head) — fine up to
Tk ~ 8-16k at D=64; beyond that sequence parallelism (ring attention over
the ``sp`` mesh axis) is the intended scaling path, per SURVEY.md §5.

Short-sequence sibling: ``packed_attention.py``.  This kernel's grid is
(B*H, Tq/bq); at T=64 that is 2048 steps of 8 KB each, three calls an
op.  There one batch row's Q, K, V for ALL heads fit VMEM in the
projections' own ``[B, T, H*D]`` layout, so the sibling tiles batch rows
instead and the ``fused_attention`` op picks it by shape; it shares this
module's ``_keep_mask`` / ``_causal_valid``, so the masks are the same.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_POS_BIG = 1e30


def _ceil_to(x, m):
    return ((x + m - 1) // m) * m


def _mix32(h):
    """murmur3 finalizer on uint32 — decorrelates position-derived indices."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def _keep_mask(seed, bh, gq, gk, rate):
    """Deterministic dropout keep-mask for global positions gq[.,1] x gk[1,.]
    (or any broadcastable pair).  ``seed`` uint32 scalar, ``bh`` int32 scalar.
    Returns bool, True = keep.  Pure jnp: identical in Pallas kernels, in
    interpret mode, and in the XLA fallback path."""
    h = (gq.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)) ^ \
        (gk.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
    h = h ^ (seed + jnp.uint32(bh) * jnp.uint32(0x9E3779B1))
    h = _mix32(h)
    # top 24 bits -> uniform in [0, 1)
    thresh = jnp.uint32(int(rate * float(1 << 24)))
    return (h >> jnp.uint32(8)) >= thresh


def _causal_valid(gq, gk, klen, tq, tk):
    """Causal mask term for query/key position grids: top-aligned when
    Tq == Tk (self-attention over equally padded sequences), suffix-
    aligned otherwise — query i sits at global key position
    ``klen - tq + i``, so decode queries see exactly the cache prefix.
    ``klen`` is a scalar (kernel) or broadcastable array (fallback).
    A batch row with klen < Tq has queries below the valid window;
    their rows are FULLY masked and come back as zeros (the fully-
    masked-row contract the kernels already honor for klen == 0), never
    NaN — callers that care should keep Tq <= min(klen)."""
    if tq == tk:
        return gq >= gk
    return gq + (klen - tq) >= gk


def _dot(a, b, in_dtype):
    """MXU matmul with fp32 accumulation; operands in the input dtype so
    bf16 inputs (the AMP path) hit the bf16 MXU pipeline."""
    return jax.lax.dot_general(
        a.astype(in_dtype), b.astype(in_dtype),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd_kernel(klen_ref, seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale, causal, rate, bq, bk, nk, tq, tk, in_dtype):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
    klen = klen_ref[bh]
    seed = seed_ref[0].astype(jnp.uint32)
    gq = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(ki, carry):
        m, l, o = carry
        kb = k_ref[0, pl.dslice(ki * bk, bk), :]       # [bk, d]
        vb = v_ref[0, pl.dslice(ki * bk, bk), :]
        s = _dot(q, kb, in_dtype)                      # [bq, bk] f32
        gk = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = gk < klen
        if causal:
            valid = valid & _causal_valid(gq, gk, klen, tq, tk)
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        if rate:
            # downgrade_in_infer (the reference dropout default): train
            # masks WITHOUT upscaling; eval scales by (1-p) (attention.py)
            keep = _keep_mask(seed, bh, gq, gk, rate)
            p = jnp.where(keep, p, 0.0)
        # PV on the MXU in the input dtype (p is an attention weight; bf16
        # is plenty and keeps the AMP path on the fast pipeline)
        pv = jax.lax.dot_general(
            p.astype(in_dtype), vb.astype(in_dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        o = o * corr + pv
        return m_new, l, o

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    o0 = jnp.zeros((bq, q_ref.shape[-1]), jnp.float32)
    m, l, o = jax.lax.fori_loop(0, nk, body, (m0, l0, o0))
    valid_row = l > 0.0
    o_ref[0] = (o / jnp.where(valid_row, l, 1.0)).astype(o_ref.dtype)
    # +BIG sentinel for fully-masked rows zeroes their backward p=exp(s-lse)
    lse_ref[0] = jnp.where(valid_row,
                           m + jnp.log(jnp.maximum(l, 1e-37)), _POS_BIG)


def _dq_kernel(klen_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, *, scale, causal, rate, bq, bk, nk,
               tq, tk, in_dtype):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0]
    klen = klen_ref[bh]
    seed = seed_ref[0].astype(jnp.uint32)
    lse = lse_ref[0]                                   # [bq, 1]
    delta = delta_ref[0]
    gq = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(ki, dq):
        kb = k_ref[0, pl.dslice(ki * bk, bk), :]
        vb = v_ref[0, pl.dslice(ki * bk, bk), :]
        s = _dot(q, kb, in_dtype)
        gk = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = gk < klen
        if causal:
            valid = valid & _causal_valid(gq, gk, klen, tq, tk)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)                           # masked rows: lse=+BIG
        g = _dot(do, vb, in_dtype)                     # dL/dy_jk pre-dropout
        if rate:
            keep = _keep_mask(seed, bh, gq, gk, rate)
            g = jnp.where(keep, g, 0.0)
        ds = p * (g - delta)                           # [bq, bk]
        dq = dq + jax.lax.dot_general(
            ds.astype(in_dtype), kb.astype(in_dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dq

    dq = jax.lax.fori_loop(
        0, nk, body, jnp.zeros((bq, q_ref.shape[-1]), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(klen_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, *, scale, causal, rate, bq, bk,
                nq, tq, tk, in_dtype):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    kb = k_ref[0]                                      # [bk, d]
    vb = v_ref[0]
    klen = klen_ref[bh]
    seed = seed_ref[0].astype(jnp.uint32)
    gk = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    d = kb.shape[-1]

    def body(qi, carry):
        dk, dv = carry
        qb = q_ref[0, pl.dslice(qi * bq, bq), :].astype(jnp.float32) * scale
        dob = do_ref[0, pl.dslice(qi * bq, bq), :]
        lse = lse_ref[0, pl.dslice(qi * bq, bq), :]    # [bq, 1]
        delta = delta_ref[0, pl.dslice(qi * bq, bq), :]
        s = _dot(qb, kb, in_dtype)                     # [bq, bk]
        gq = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        valid = gk < klen
        if causal:
            valid = valid & _causal_valid(gq, gk, klen, tq, tk)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)
        if rate:
            keep = _keep_mask(seed, bh, gq, gk, rate)
            p_drop = jnp.where(keep, p, 0.0)
        else:
            p_drop = p
        # dV += P_drop^T @ dO
        dv = dv + jax.lax.dot_general(
            p_drop.astype(in_dtype), dob.astype(in_dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        g = _dot(dob, vb, in_dtype)
        if rate:
            g = jnp.where(keep, g, 0.0)
        ds = p * (g - delta)
        # dK += dS^T @ Q*scale
        dk = dk + jax.lax.dot_general(
            ds.astype(in_dtype), qb.astype(in_dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nq, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pick_blocks(tq, tk):
    bq = min(256, _ceil_to(tq, 8))
    bk = min(512, _ceil_to(tk, 128 if tk >= 128 else 8))
    return bq, _ceil_to(tq, bq), bk, _ceil_to(tk, bk)


def supported(q_shape, k_shape, dtype, max_seq=None):
    """Whether the kernel can take these shapes (VMEM budget for the
    per-(b,h) resident K/V + Q/dO blocks); callers fall back to XLA.
    ``max_seq`` overrides the flag's sequence gate (a tuned per-shape
    ruling was measured at its own length; the VMEM budget below still
    applies)."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    tq, d = q_shape[2], q_shape[3]
    tk = k_shape[2]
    if tq < 1 or tk < 1 or d < 1 or d > 512:
        return False
    from ...flags import flag

    # the flag's sequence gate is a SELECTION default, not a compile
    # limit: on the v5e the whole 6-layer Transformer-base step at
    # T=4096 (b=2, bf16 AMP) compiles in ~35 s and runs with this kernel
    # on (PERF.md, bring-up).  The gate stays at its old value only
    # because nothing on this machine has yet measured where the kernel
    # beats XLA; ROADMAP D4 replaces it with a measured selection.
    if max(tq, tk) > (max_seq if max_seq is not None
                      else flag("pallas_attention_max_seq")):
        return False
    bq, tq_pad, bk, tk_pad = _pick_blocks(tq, tk)
    itemsize = 2 if dtype == jnp.bfloat16 else 4
    # the worst resident set is the dK/dV kernel: full K/V blocks plus the
    # full padded Q, dO, lse, delta per (b, h) grid step — budget THAT,
    # not just the forward (a Tq >> Tk cross-attention would otherwise
    # pass the gate and blow VMEM at backward compile time).  Pallas
    # DOUBLE-BUFFERS every grid block (including the whole-row K/V
    # "blocks"), so the resident set counts twice.
    resident = 2 * tk_pad * d * itemsize              # K + V per (b, h)
    resident += 2 * tq_pad * d * itemsize             # Q + dO (dkv kernel)
    resident += 2 * tq_pad * 4                        # lse + delta
    blocks = (3 * bq * d + 2 * bq * bk) * 4           # O block + scores
    return 2 * (resident + blocks) < 10 * 1024 * 1024


def _pad_t(x, t_pad):
    t = x.shape[1]
    if t == t_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_attention(q, k, v, k_len, seed, causal=False, dropout_rate=0.0,
                    scale=None, interpret=False):
    """Fused attention.  q [B,H,Tq,D]; k/v [B,H,Tk,D]; k_len [B] int32 valid
    key counts (None = all valid); seed uint32 scalar (dropout counter key).
    Returns [B,H,Tq,D] in q's dtype."""
    return _flash_fwd(q, k, v, k_len, seed, causal, dropout_rate, scale,
                      interpret)[0]


def _prep(q, k, v, k_len, seed):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    if k_len is None:
        klen = jnp.full((b,), tk, jnp.int32)
    else:
        klen = jnp.minimum(k_len.astype(jnp.int32).reshape(b), tk)
    # scalar operands ride 1-D in SMEM: a [B*H, 1] column pads every
    # row to a 512-byte SMEM word line — 1 MiB at B*H = 2048, the whole
    # scalar memory of a v5e core
    klen = jnp.repeat(klen, h)
    if seed is None:
        seed = jnp.zeros((), jnp.uint32)
    seed = seed.astype(jnp.uint32).reshape(1)
    return qf, kf, vf, klen, seed


def _flash_fwd(q, k, v, k_len, seed, causal, rate, scale, interpret):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq, tq_pad, bk, tk_pad = _pick_blocks(tq, tk)
    qf, kf, vf, klen, seedv = _prep(q, k, v, k_len, seed)
    qf, kf, vf = _pad_t(qf, tq_pad), _pad_t(kf, tk_pad), _pad_t(vf, tk_pad)
    bhn, nq, nk = b * h, tq_pad // bq, tk_pad // bk
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, rate=rate, bq=bq, bk=bk,
        nk=nk, tq=tq, tk=tk, in_dtype=q.dtype)
    o, lse = pl.pallas_call(
        kern,
        grid=(bhn, nq),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, bq, d), lambda bhi, qi: (bhi, qi, 0)),
                  pl.BlockSpec((1, tk_pad, d), lambda bhi, qi: (bhi, 0, 0)),
                  pl.BlockSpec((1, tk_pad, d), lambda bhi, qi: (bhi, 0, 0))],
        out_specs=[pl.BlockSpec((1, bq, d), lambda bhi, qi: (bhi, qi, 0)),
                   pl.BlockSpec((1, bq, 1), lambda bhi, qi: (bhi, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct((bhn, tq_pad, d), q.dtype),
                   jax.ShapeDtypeStruct((bhn, tq_pad, 1), jnp.float32)],
        interpret=interpret,
    )(klen, seedv, qf, kf, vf)
    out = o[:, :tq].reshape(b, h, tq, d)
    return out, (q, k, v, k_len, seed, out, lse)


def _flash_bwd(causal, rate, scale, interpret, res, dout):
    q, k, v, k_len, seed, out, lse = res
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq, tq_pad, bk, tk_pad = _pick_blocks(tq, tk)
    qf, kf, vf, klen, seedv = _prep(q, k, v, k_len, seed)
    qf, kf, vf = _pad_t(qf, tq_pad), _pad_t(kf, tk_pad), _pad_t(vf, tk_pad)
    bhn, nq, nk = b * h, tq_pad // bq, tk_pad // bk
    dof = _pad_t(dout.reshape(bhn, tq, d), tq_pad)
    # delta_i = sum_j g_ij y_ij = dO . O (holds under dropout: see module doc)
    delta = jnp.sum(dof.astype(jnp.float32) *
                    _pad_t(out.reshape(bhn, tq, d), tq_pad)
                    .astype(jnp.float32), axis=-1,
                    keepdims=True)                     # [bhn, tq_pad, 1]

    common = dict(scale=scale, causal=causal, rate=rate, bq=bq, bk=bk,
                  tq=tq, tk=tk, in_dtype=q.dtype)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(bhn, nq),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, bq, d), lambda bhi, qi: (bhi, qi, 0)),
                  pl.BlockSpec((1, tk_pad, d), lambda bhi, qi: (bhi, 0, 0)),
                  pl.BlockSpec((1, tk_pad, d), lambda bhi, qi: (bhi, 0, 0)),
                  pl.BlockSpec((1, bq, d), lambda bhi, qi: (bhi, qi, 0)),
                  pl.BlockSpec((1, bq, 1), lambda bhi, qi: (bhi, qi, 0)),
                  pl.BlockSpec((1, bq, 1), lambda bhi, qi: (bhi, qi, 0))],
        out_specs=pl.BlockSpec((1, bq, d), lambda bhi, qi: (bhi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bhn, tq_pad, d), q.dtype),
        interpret=interpret,
    )(klen, seedv, qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **common),
        grid=(bhn, nk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, tq_pad, d), lambda bhi, ki: (bhi, 0, 0)),
                  pl.BlockSpec((1, bk, d), lambda bhi, ki: (bhi, ki, 0)),
                  pl.BlockSpec((1, bk, d), lambda bhi, ki: (bhi, ki, 0)),
                  pl.BlockSpec((1, tq_pad, d), lambda bhi, ki: (bhi, 0, 0)),
                  pl.BlockSpec((1, tq_pad, 1), lambda bhi, ki: (bhi, 0, 0)),
                  pl.BlockSpec((1, tq_pad, 1), lambda bhi, ki: (bhi, 0, 0))],
        out_specs=[pl.BlockSpec((1, bk, d), lambda bhi, ki: (bhi, ki, 0)),
                   pl.BlockSpec((1, bk, d), lambda bhi, ki: (bhi, ki, 0))],
        out_shape=[jax.ShapeDtypeStruct((bhn, tk_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((bhn, tk_pad, d), v.dtype)],
        interpret=interpret,
    )(klen, seedv, qf, kf, vf, dof, lse, delta)

    dq = dq[:, :tq].reshape(b, h, tq, d)
    dk = dk[:, :tk].reshape(b, h, tk, d)
    dv = dv[:, :tk].reshape(b, h, tk, d)
    return dq, dk, dv, None, None


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def gather_pages(pool, table, scale=None):
    """Materialize per-slot K or V views from a paged pool.

    ``pool`` [P, H, ps, D] (float or int8), ``table`` [S, max_pages]
    int32 physical page ids, ``scale`` [P, H, ps] f32 per-token-row
    dequant scales (required when the pool is int8).  Returns
    [S, H, max_pages*ps, D] in f32 for int8 pools, pool dtype otherwise.
    One gather per pool — XLA fuses it into the attention consumer, so
    the transient view never round-trips HBM as a separate buffer."""
    s, mp = table.shape
    p, h, ps, d = pool.shape
    pages = pool[table.reshape(-1)]              # [S*mp, H, ps, D]
    kv = pages.reshape(s, mp, h, ps, d).transpose(0, 2, 1, 3, 4) \
        .reshape(s, h, mp * ps, d)
    if pool.dtype == jnp.int8:
        sc = scale[table.reshape(-1)].reshape(s, mp, h, ps) \
            .transpose(0, 2, 1, 3).reshape(s, h, mp * ps)
        kv = kv.astype(jnp.float32) * sc[..., None]
    return kv


def paged_attention(q, k_pool, v_pool, table, k_len, k_scale=None,
                    v_scale=None, causal=True, scale=None,
                    use_pallas=False, interpret=False):
    """The paged-attention path: gather each slot's pages into the
    contiguous [S, H, Tmax, D] view the bottom-aligned suffix-query
    kernels already handle (Tq <= Tk, query i at global position
    klen - Tq + i), then dispatch to the flash kernel or the XLA
    fallback.  Paging changes where K/V LIVE (page pool + table), not
    the attention math — so the klen-aware mask work from the decode
    kernels is reused verbatim.  ``use_pallas`` is the caller's FINAL
    choice (the op applies ``supported()`` on the gathered shape and
    records the body it took)."""
    k = gather_pages(k_pool, table, k_scale)
    v = gather_pages(v_pool, table, v_scale)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    if use_pallas:
        return flash_attention(q, k, v, k_len, None, causal, 0.0, scale,
                               interpret)
    return reference_attention(q, k, v, k_len, None, causal, 0.0, scale)


def reference_attention(q, k, v, k_len, seed, causal=False, dropout_rate=0.0,
                        scale=None, selected=None, with_lse=False,
                        window=None):
    """XLA fallback with bit-identical semantics (same hash dropout mask):
    used when the pallas flag is off or shapes exceed the VMEM budget.
    K/V of fewer heads than Q are read by whole groups of query heads;
    ``selected`` is the packed per-query key mask of
    ``ops/sparse_select.py``.  ``window`` (with ``causal``, self-attention)
    keeps of a query's keys the nearest ``window``: key ``s`` counts for
    query ``t`` iff ``t - window < s <= t``.  ``with_lse`` also returns the
    rows' log-sum-exp ``[B, H, Tq, 1]`` (+1e30 for a row with no valid key,
    as the kernels write it)."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if k.shape[1] != h:
        # grouped-query heads: one group of query heads at a time through
        # the same body
        if dropout_rate:
            raise ValueError("grouped-query attention has no weight dropout")
        g = h // k.shape[1]
        q5 = q.reshape(b, k.shape[1], g, tq, d)
        outs = [reference_attention(q5[:, :, i], k, v, k_len, seed, causal,
                                    0.0, scale, selected, True, window)
                for i in range(g)]
        out = jnp.stack([o for o, _ in outs], 2).reshape(b, h, tq,
                                                         v.shape[3])
        lse = jnp.stack([l for _, l in outs], 2).reshape(b, h, tq, 1)
        return (out, lse) if with_lse else out
    # operands stay in the input dtype (bf16 under AMP -> bf16 MXU pass);
    # scores/softmax accumulate fp32 via preferred_element_type
    s = jnp.einsum("bhqd,bhkd->bhqk", q * jnp.asarray(scale, q.dtype), k,
                   preferred_element_type=jnp.float32)
    gq = jnp.arange(tq)[:, None]
    gk = jnp.arange(tk)[None, :]
    valid = jnp.ones((b, 1, tq, tk), bool)
    klen = (jnp.full((b,), tk, jnp.int32) if k_len is None
            else jnp.minimum(k_len.astype(jnp.int32).reshape(b), tk))
    if k_len is not None:
        valid = gk[None, None] < klen.reshape(b, 1, 1, 1)
    if causal:
        valid = valid & _causal_valid(gq[None, None], gk[None, None],
                                      klen.reshape(b, 1, 1, 1), tq, tk)
    if window is not None:
        valid = valid & (gq - gk < window)[None, None]
    if selected is not None:
        from ..sparse_select import unpack_key_mask
        valid = valid & unpack_key_mask(selected, tk)[:, None]
    s = jnp.where(valid, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    y = p / jnp.maximum(l, 1e-37)
    if dropout_rate:
        if seed is None:
            seed = jnp.zeros((), jnp.uint32)
        bh = jnp.arange(b * h, dtype=jnp.int32).reshape(b, h, 1, 1)
        keep = _keep_mask(seed.astype(jnp.uint32),
                          bh, gq[None, None], gk[None, None], dropout_rate)
        # downgrade_in_infer: train-time mask without upscale
        y = jnp.where(keep, y, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", y.astype(q.dtype), v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    if not with_lse:
        return out
    return out, jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-37)),
                          _POS_BIG)
