"""Blockwise attention with K and V STREAMED by blocks, grouped-query heads
and a per-query set of selected keys.

Sibling of ``flash_attention.py``, whose kernels keep one (batch, head)'s
whole K and V resident in VMEM: at T = 8192, D = 128 that is 2 x 2 x 2 MB
double-buffered and its ``supported()`` says no.  Here the key blocks are a
grid axis (the innermost, ``arbitrary``), the online-softmax state lives in
VMEM scratch across it, and only one ``[bk, D]`` block of K and of V is in
flight: any sequence length the HBM holds fits.

* **Grouped-query heads.** K/V carry ``H / g`` heads; query head ``h`` reads
  K/V head ``h // g`` through the block index maps — K/V are never repeated
  in HBM.  The dK/dV kernel's reduction axis runs over the ``g`` query heads
  of a group times the query blocks.
* **Selected keys.** ``selected`` is the packed bit mask of
  ``ops/sparse_select.py`` (``[B, Tq, W]`` int32; key ``s`` is bit ``(s %
  4096) // 128`` of word ``(s // 4096) * 128 + s % 128``).  One ``[bq, 128]``
  tile of words covers 4096 keys, so it stays put in VMEM for ``4096 / bk``
  consecutive key blocks, and each 128-key slab of a block is one shift and
  one ``and`` of the tile.  An unselected key contributes exactly nothing
  (its probability is set to 0, not to exp(-1e30 - m)); a block none of whose
  keys is selected leaves the state as it was.
* **Causal.** Blocks wholly above the diagonal run no arithmetic
  (``pl.when``) and fetch nothing: their index maps clamp to the last block
  the row of blocks needs, and Pallas skips a fetch whose block index did
  not change.

No dropout and no per-row key length: every position is real (the op falls
back to the XLA body otherwise).  Backward is the standard flash
decomposition (``delta = rowsum(dO * O)``, one dQ kernel, one dK/dV kernel,
probabilities recomputed from the saved log-sum-exp).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..sparse_select import KEYS_PER_TILE, LANES

_NEG_INF = -1e30
_POS_BIG = 1e30


def _pick_blocks(t):
    """Largest of 512, 256, 128 that divides ``t``."""
    for b in (512, 256, 128):
        if t % b == 0:
            return b
    return None


def supported(q_shape, k_shape, dtype, causal, has_klen, rate):
    """Whether the streamed kernels take this call: self-attention
    (Tq == Tk) over whole 128-key slabs, heads in whole groups, a head size
    of whole lane tiles, no dropout and no padding mask."""
    if len(q_shape) != 4 or len(k_shape) != 4 or has_klen or rate:
        return False
    b, h, tq, d = q_shape
    if k_shape[0] != b or k_shape[2] != tq or k_shape[3] != d:
        return False
    if h % k_shape[1] or d % LANES or d > 256:
        return False
    return _pick_blocks(tq) is not None


def _valid(sel_ref, qi, ki, bq, bk, causal):
    """bool ``[bq, bk]``: which (query, key) pairs of block (qi, ki) count;
    None when all do."""
    valid = None
    if sel_ref is not None:
        words = sel_ref[0]                                      # [bq, 128]
        first_plane = (ki % (KEYS_PER_TILE // bk)) * (bk // LANES)
        slabs = [(jax.lax.shift_right_logical(
            words, jnp.full(words.shape, first_plane + p, jnp.int32)) & 1) == 1
            for p in range(bk // LANES)]
        valid = slabs[0] if len(slabs) == 1 else jnp.concatenate(slabs, 1)
    if causal:
        gq = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        gk = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = gq >= gk if valid is None else valid & (gq >= gk)
    return valid


def _scores(q_ref, k_ref, scale, in_dtype):
    q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(in_dtype)
    return jax.lax.dot_general(q, k_ref[0, 0].astype(in_dtype),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b, contract, in_dtype):
    return jax.lax.dot_general(a.astype(in_dtype), b.astype(in_dtype),
                               (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _split(refs, has_sel, n_in):
    """(selected ref or None, the other inputs, outputs and scratch)."""
    if has_sel:
        return refs[0], refs[1:n_in], refs[n_in:]
    return None, refs[:n_in - 1], refs[n_in - 1:]


def _fwd_kernel(*refs, scale, causal, has_sel, bq, bk, nk, in_dtype):
    sel_ref, (q_ref, k_ref, v_ref), (o_ref, lse_ref, m_s, l_s, acc_s) = \
        _split(refs, has_sel, 4)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def block():
        s = _scores(q_ref, k_ref, scale, in_dtype)
        valid = _valid(sel_ref, qi, ki, bq, bk, causal)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + _dot(
            p, v_ref[0, 0], ((1,), (0,)), in_dtype)
        m_s[...] = m_new

    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(block)
    else:
        block()

    @pl.when(ki == nk - 1)
    def _():
        l = l_s[...]
        row = l > 0.0
        o_ref[0, 0] = (acc_s[...] / jnp.where(row, l, 1.0)).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            row, m_s[...] + jnp.log(jnp.maximum(l, 1e-37)), _POS_BIG)


def _probs(sel_ref, q_ref, k_ref, lse_ref, qi, ki, scale, causal, bq, bk,
           in_dtype):
    s = _scores(q_ref, k_ref, scale, in_dtype)
    p = jnp.exp(s - lse_ref[0, 0])                   # empty rows: lse = +BIG
    valid = _valid(sel_ref, qi, ki, bq, bk, causal)
    return p if valid is None else jnp.where(valid, p, 0.0)


def _dq_kernel(*refs, scale, causal, has_sel, bq, bk, nk, in_dtype):
    sel_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), \
        (dq_ref, acc_s) = _split(refs, has_sel, 7)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def block():
        p = _probs(sel_ref, q_ref, k_ref, lse_ref, qi, ki, scale, causal,
                   bq, bk, in_dtype)
        g = _dot(do_ref[0, 0], v_ref[0, 0], ((1,), (1,)), in_dtype)
        ds = p * (g - delta_ref[0, 0])
        acc_s[...] += _dot(ds, k_ref[0, 0], ((1,), (0,)), in_dtype)

    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(block)
    else:
        block()

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0, 0] = (acc_s[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, has_sel, bq, bk, nq, nr, in_dtype):
    sel_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), \
        (dk_ref, dv_ref, dk_s, dv_s) = _split(refs, has_sel, 7)
    ki, r = pl.program_id(2), pl.program_id(3)
    qi = r % nq

    @pl.when(r == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def block():
        p = _probs(sel_ref, q_ref, k_ref, lse_ref, qi, ki, scale, causal,
                   bq, bk, in_dtype)
        do = do_ref[0, 0]
        dv_s[...] += _dot(p, do, ((0,), (0,)), in_dtype)
        g = _dot(do, v_ref[0, 0], ((1,), (1,)), in_dtype)
        ds = p * (g - delta_ref[0, 0])
        q = (q_ref[0, 0].astype(jnp.float32) * scale)
        dk_s[...] += _dot(ds, q, ((0,), (0,)), in_dtype)

    if causal:
        pl.when(qi * bq + bq - 1 >= ki * bk)(block)
    else:
        block()

    @pl.when(r == nr - 1)
    def _():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


def _params():
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))


def _geometry(q, k):
    b, h, t, d = q.shape
    bq = bk = _pick_blocks(t)
    return b, h, t, d, h // k.shape[1], bq, bk, t // bq, t // bk


def _row_specs(g, bq, bk, d, causal):
    """Block specs of a grid (batch, query head, query block, key block):
    (a query-row block ``[bq, d]``, a ``[bq, 1]`` column of it, a K/V block
    of the head's group, the selection's word tile).  Under ``causal`` the
    key index clamps to the last block the query block needs, so a skipped
    step fetches nothing."""
    per_tile = KEYS_PER_TILE // bk

    def key_block(qi, ki):
        return jnp.minimum(ki, (qi * bq + bq - 1) // bk) if causal else ki

    def q_map(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def kv_map(bi, hi, qi, ki):
        return (bi, hi // g, key_block(qi, ki), 0)

    def sel_map(bi, hi, qi, ki):
        return (bi, qi, key_block(qi, ki) // per_tile)
    return (pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bq, 1), q_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, bq, LANES), sel_map))


def _forward(q, k, v, selected, causal, scale, interpret):
    b, h, t, d, g, bq, bk, nq, nk = _geometry(q, k)
    row, col, kv, sel = _row_specs(g, bq, bk, d, causal)
    has_sel = selected is not None
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          has_sel=has_sel, bq=bq, bk=bk, nk=nk,
                          in_dtype=q.dtype),
        grid=(b, h, nq, nk),
        in_specs=([sel] if has_sel else []) + [row, kv, kv],
        out_specs=[row, col],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(*(((selected,) if has_sel else ()) + (q, k, v)))
    return out, lse


def _backward(q, k, v, selected, out, lse, dout, causal, scale, interpret):
    b, h, t, d, g, bq, bk, nq, nk = _geometry(q, k)
    per_tile = KEYS_PER_TILE // bk
    has_sel = selected is not None
    dout = dout.astype(q.dtype)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1,
                    keepdims=True)
    common = dict(scale=scale, causal=causal, has_sel=has_sel, bq=bq, bk=bk,
                  in_dtype=q.dtype)
    head = (selected,) if has_sel else ()

    # -- dQ: grid (B, H, query blocks, key blocks) -----------------------------
    row, col, kv, sel = _row_specs(g, bq, bk, d, causal)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(b, h, nq, nk),
        in_specs=([sel] if has_sel else []) + [row, kv, kv, row, col, col],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(*(head + (q, k, v, dout, lse, delta)))

    # -- dK, dV: grid (B, KV heads, key blocks, group heads x query blocks) ----
    def first_q(ki):               # the first query block key block ki reaches
        return (ki * bk) // bq

    def clamp_q(ki, r):
        qi = r % nq
        return jnp.maximum(qi, first_q(ki)) if causal else qi

    def q_map2(bi, hk, ki, r):
        return (bi, hk * g + r // nq, clamp_q(ki, r), 0)

    def kv_map2(bi, hk, ki, r):
        return (bi, hk, ki, 0)

    def sel_map2(bi, hk, ki, r):
        return (bi, clamp_q(ki, r), ki // per_tile)
    row2 = pl.BlockSpec((1, 1, bq, d), q_map2)
    col2 = pl.BlockSpec((1, 1, bq, 1), q_map2)
    kv2 = pl.BlockSpec((1, 1, bk, d), kv_map2)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, nr=g * nq, **common),
        grid=(b, h // g, nk, g * nq),
        in_specs=([pl.BlockSpec((1, bq, LANES), sel_map2)] if has_sel else [])
        + [row2, kv2, kv2, row2, col2, col2],
        out_specs=[kv2, kv2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(*(head + (q, k, v, dout, lse, delta)))
    return dq, dk, dv


def _scale(q, scale):
    return scale if scale is not None else q.shape[-1] ** -0.5


def forward(q, k, v, selected, causal=False, scale=None, interpret=False):
    """q ``[B, H, T, D]``; k, v ``[B, H / g, T, D]``; ``selected`` the packed
    key mask ``[B, T, W]`` int32 or None.  Returns the output ``[B, H, T,
    D]`` in q's dtype and the rows' log-sum-exp ``[B, H, T, 1]`` float32,
    which ``backward`` wants back."""
    return _forward(q, k, v, selected, causal, _scale(q, scale), interpret)


def backward(q, k, v, selected, out, lse, dout, causal=False, scale=None,
             interpret=False):
    """(dQ, dK, dV) from the forward's operands and results."""
    return _backward(q, k, v, selected, out, lse, dout, causal,
                     _scale(q, scale), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def streamed_attention(q, k, v, selected, causal=False, scale=None,
                       interpret=False):
    """``forward``'s output alone, differentiable (``jax.grad`` runs
    ``backward`` on the saved log-sum-exp)."""
    return forward(q, k, v, selected, causal, scale, interpret)[0]


def _fwd_rule(q, k, v, selected, causal, scale, interpret):
    out, lse = forward(q, k, v, selected, causal, scale, interpret)
    return out, (q, k, v, selected, out, lse)


def _bwd_rule(causal, scale, interpret, res, dout):
    q, k, v, selected, out, lse = res
    return backward(q, k, v, selected, out, lse, dout, causal, scale,
                    interpret) + (None,)


streamed_attention.defvjp(_fwd_rule, _bwd_rule)
