"""The XLA attention body and what every attention body shares with it.

The reference's entire attention story is ``nets.scaled_dot_product_attention``
(``python/paddle/fluid/nets.py:323``): the [B, H, Tq, Tk] score matrix, its
softmax, optionally dropout, a second batched matmul.  ``reference_attention``
is that composition under this program's structural masks, and the body the
``fused_attention`` op lowers to wherever no kernel's rule takes the call
(``ops/attention.py``); the Pallas kernels (``ops/pallas/packed_attention.py``,
``streamed_attention.py``) and the ring (``parallel/ring_attention.py``) are
held to its results.

Masking is structural rather than a dense additive bias: a per-batch key
length (padding) and an optional causal flag — exactly the two mask shapes
the Transformer model builds (padding_attn_bias + causal_mask).  Causal
with Tq == Tk is top-aligned self-attention; with Tq < Tk the queries are
the suffix of the klen valid keys (query i at global position
klen - Tq + i) — the KV-cache decode shape, where a single-token or
chunked query attends a longer cache without the full-length-call
workaround.

Dropout on the attention weights comes from a counter-based hash of (head,
query, key) positions (``_keep_mask``), so a kernel's backward regenerates
the identical mask without ever materializing it, and every body drops the
same weights.  Semantics are the reference dropout default
``downgrade_in_infer`` (``dropout_op.cc``): training masks without
upscaling, eval scales weights by (1 - p) — applied by the op as an output
scale, since it commutes with the PV matmul.  The hash is a murmur3-style
integer finalizer — deterministic, pure jnp (the same in a Mosaic kernel, in
Pallas interpret mode and here), and keyed on the executor-threaded PRNG so
separate ops/steps decorrelate.

``paged_attention`` gathers a paged KV pool into the contiguous view and runs
the same body.
"""

import jax.numpy as jnp

_NEG_INF = -1e30
_POS_BIG = 1e30


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
    interpret mode, and in the XLA body."""
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
    ``klen`` is a scalar (kernel) or broadcastable array (XLA body).
    A batch row with klen < Tq has queries below the valid window;
    their rows are FULLY masked and come back as zeros (the fully-
    masked-row contract every body honors for klen == 0), never
    NaN — callers that care should keep Tq <= min(klen)."""
    if tq == tk:
        return gq >= gk
    return gq + (klen - tq) >= gk


def reference_attention(q, k, v, k_len, seed, causal=False, dropout_rate=0.0,
                        scale=None, selected=None, with_lse=False,
                        window=None):
    """The XLA body: q [B,H,Tq,D]; k/v [B,Hkv,Tk,D(v)]; k_len [B] int32
    valid key counts (None = all valid); seed uint32 scalar (the dropout
    hash's key).  Returns [B,H,Tq,Dv] in q's dtype.  K/V of fewer heads
    than Q are read by whole groups of query heads; ``selected`` is the
    packed per-query key mask of
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
        from .sparse_select import unpack_key_mask
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
                    v_scale=None, causal=True, scale=None):
    """The paged-attention path: gather each slot's pages into the
    contiguous [S, H, Tmax, D] view the bottom-aligned suffix-query
    mask already handles (Tq <= Tk, query i at global position
    klen - Tq + i), then the XLA body.  Paging changes where K/V LIVE
    (page pool + table), not the attention math."""
    k = gather_pages(k_pool, table, k_scale)
    v = gather_pages(v_pool, table, v_scale)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    return reference_attention(q, k, v, k_len, None, causal, 0.0, scale)
