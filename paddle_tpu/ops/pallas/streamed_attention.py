"""Blockwise attention with K and V STREAMED by blocks, grouped-query heads
and a per-query set of selected keys.

Sibling of ``flash_attention.py``, whose kernels keep one (batch, head)'s
whole K and V resident in VMEM: at T = 8192, D = 128 that is 2 x 2 x 2 MB
double-buffered and its ``supported()`` says no.  Here the key blocks are a
grid axis (the innermost, ``arbitrary``), the online-softmax state lives in
VMEM scratch across it, and only one ``[bk, D]`` block of K and of V is in
flight: any sequence length the HBM holds fits.

* **Keys wider than values.** Q and K are ``Dk`` wide, V, O and dO ``Dv``
  (latent attention as training computes it: 192-wide keys — 128 from the
  latent and a 64-wide rotary part the caller has already joined on — over
  128-wide values); ``Dk == Dv`` is the common case.  The scores are ONE
  contraction over ``Dk``: ``Dv`` is whole lane tiles, ``Dk`` whole
  half-tiles (a 192-wide block is the array's full last axis, which Mosaic
  lays out in two lane tiles; the MXU's 128-deep passes make the 64 odd
  columns cost a pass either way, so a second product for the rotary part
  would save no pass and add a kernel operand).
* **One grid step serves a K/V head's query group.** K/V carry ``H / g``
  heads; the ``g`` query heads that read one of them are contiguous in
  ``[B, H, T, D]``, so a step's Q / O / dO / dQ block is ``[1, g', bq, D]``
  — ``g'`` heads, a divisor of ``g`` (``_heads_per_step``: all of them where
  the blocks fit the VMEM budget) — and the step loops over them (a rolled
  ``fori_loop`` of at most four heads' text a turn, whatever ``g'``).  What
  the heads of a group share is done ONCE a step: the K/V block and the
  selection's word tile are fetched once, and which (query, key) pairs
  count is worked out once, into a float32 ``[bq, bk]`` scratch that holds
  0 for a pair that counts and -1e30 for one that does not; a head adds it
  to its scores.  Forward and dQ run on the grid (B, H / g', query blocks,
  key blocks); dK/dV on (B, H / g, key blocks, (g / g') x query blocks),
  every head of every step adding into the one float32 dK and dV of the
  key block.  ``g = 1`` (plain heads, with a selection or without: the
  body of long plain-head self-attention whose K/V the resident kernel
  cannot hold) is a loop of one.
* **Selected keys.** ``selected`` is the packed bit mask of
  ``ops/sparse_select.py`` (``[B, Tq, W]`` int32; key ``s`` is bit ``(s %
  4096) // 128`` of word ``(s // 4096) * 128 + s % 128``).  One ``[bq, 128]``
  tile of words covers 4096 keys, so it stays put in VMEM for ``4096 / bk``
  consecutive key blocks, and each 128-key slab of a block is one shift and
  one ``and`` of the tile.  An unselected key contributes exactly nothing
  (-1e30 swallows any score, and ``exp`` of it is 0.0 — also in a row none
  of whose keys counted yet, whose running maximum is not subtracted); a
  block none of whose keys is selected leaves the state as it was.
* **Causal.** Only a block pair the diagonal crosses (``ki * bk + bk - 1 >
  qi * bq``) compares positions; below it every pair counts, and with no
  selection either a head adds nothing to its scores.  Blocks wholly above
  the diagonal run no arithmetic (``pl.when``) and fetch nothing: their
  index maps clamp to the last block the row of blocks needs, and Pallas
  skips a fetch whose block index did not change.

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

# What a grid step may hold in VMEM, and the limit the kernels are compiled
# under (Mosaic's default scoped limit is 16 MiB; a v5e core has 128 MiB).
# At the long-document cell's shape — g = 8 heads a step, bq = bk = 512,
# D = 128, bf16 — ``_step_bytes`` reads 26 MiB: a head's Q, dO and dQ blocks
# are 128 KB each but its ``[bq, 1]`` float32 columns (log-sum-exp, delta)
# pad to 128 lanes, 256 KB each, and all five are double-buffered (2 MB a
# head with the float32 accumulator, 16 MB the group); the rest is the K/V
# blocks, the word tile, the pairs' scratch and a head's temporaries.
_VMEM_BUDGET = 48 * 1024 * 1024
# Heads whose text one turn of the head loop holds: the scheduler runs a
# head's products on the MXU under its neighbour's softmax on the VPU, which
# a loop of single heads forbids.  A layer's three kernels at the cell's
# shape read 23.6 ms with 1, 22.4 with 2, 21.7 with 4 and 21.4 with all 8
# (28.2 before the grouping); the step's compile is the same to its own
# noise (30-35 s) with any of them.
_HEADS_UNROLLED = 4


def _pick_blocks(t):
    """Largest of 512, 256, 128 that divides ``t``."""
    for b in (512, 256, 128):
        if t % b == 0:
            return b
    return None


def supported(q_shape, k_shape, dtype, causal, has_klen, rate, dv=None):
    """Whether the streamed kernels take this call: self-attention
    (Tq == Tk) over whole 128-key slabs, heads in whole groups, values
    (``dv`` wide; as wide as the keys by default) of whole lane tiles and
    keys of whole half-tiles, no dropout and no padding mask."""
    if len(q_shape) != 4 or len(k_shape) != 4 or has_klen or rate:
        return False
    b, h, tq, d = q_shape
    dv = d if dv is None else dv
    if k_shape[0] != b or k_shape[2] != tq or k_shape[3] != d:
        return False
    if h % k_shape[1] or d % (LANES // 2) or dv % LANES or max(d, dv) > 256:
        return False
    return _pick_blocks(tq) is not None


def _lanes(d):
    """Lanes a ``d``-wide row takes in VMEM (whole tiles)."""
    return -(-d // LANES) * LANES


def _step_bytes(gh, bq, bk, dk, itemsize, dv=None):
    """VMEM bytes of a grid step that serves ``gh`` heads of ``dk``-wide
    keys and ``dv``-wide values (``dk`` by default), by the hungriest of
    the three kernels (dQ: the Q, dQ and dO row blocks and two columns a
    head)."""
    dk, dv = _lanes(dk), _lanes(dk if dv is None else dv)
    column = bq * LANES * 4              # [bq, 1] float32 pads to 128 lanes
    head = 2 * (bq * (2 * dk + dv) * itemsize + 2 * column) \
        + bq * max(dk, dv) * 4
    shared = 2 * (bk * (dk + dv) * itemsize + bq * LANES * 4) + bq * bk * 4
    temporaries = 8 * bq * bk * 4        # scores, probabilities, their casts
    return gh * head + shared + temporaries


def _heads_per_step(g, bq, bk, dk, itemsize, dv=None):
    """``g'``: the most heads of a group of ``g`` one grid step serves — the
    largest divisor of ``g`` whose blocks and scratch fit the budget."""
    return max(n for n in range(1, g + 1)
               if g % n == 0 and (n == 1 or _step_bytes(
                   n, bq, bk, dk, itemsize, dv) <= _VMEM_BUDGET))


def _scores(q, k, scale, in_dtype):
    q = (q.astype(jnp.float32) * scale).astype(in_dtype)
    return jax.lax.dot_general(q, k.astype(in_dtype),
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


def _each_head(head, sel_ref, bias_s, qi, ki, gh, bq, bk, causal):
    """Block pair (qi, ki) for the step's ``gh`` heads: ``head(h, bias)``
    for each, where ``bias`` is ``bias_s`` — float32 ``[bq, bk]``, 0.0 for a
    (query, key) pair that counts and -1e30 for one that does not, written
    here once for all the heads — or None when every pair counts.  Nothing
    runs for a pair wholly above the diagonal."""
    together = max(n for n in range(1, _HEADS_UNROLLED + 1) if gh % n == 0)

    def heads(bias):
        def body(i, carry):
            for j in range(together):
                head(i * together + j, bias)
            return carry
        jax.lax.fori_loop(0, gh // together, body, 0)

    def below_diagonal():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        keys = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        return rows - keys >= ki * bk - qi * bq

    runs = ki * bk <= qi * bq + bq - 1
    crossed = runs & (ki * bk + bk - 1 > qi * bq)
    if sel_ref is not None:
        def block():
            words = sel_ref[0]                                  # [bq, 128]
            first_plane = (ki % (KEYS_PER_TILE // bk)) * (bk // LANES)
            for p in range(bk // LANES):
                bit = jax.lax.shift_right_logical(
                    words, jnp.full(words.shape, first_plane + p, jnp.int32))
                bias_s[:, p * LANES:(p + 1) * LANES] = jnp.where(
                    (bit & 1) == 1, 0.0, _NEG_INF)
            if causal:
                @pl.when(crossed)
                def _():
                    bias_s[...] = jnp.where(below_diagonal(), bias_s[...],
                                            _NEG_INF)
            heads(bias_s)
        if causal:
            pl.when(runs)(block)
        else:
            block()
    elif causal:
        @pl.when(crossed)
        def _():
            bias_s[...] = jnp.where(below_diagonal(), 0.0, _NEG_INF)
            heads(bias_s)

        @pl.when(runs & jnp.logical_not(crossed))
        def _():
            heads(None)
    else:
        heads(None)


def _fwd_kernel(*refs, scale, causal, has_sel, gh, bq, bk, nk, in_dtype):
    sel_ref, (q_ref, k_ref, v_ref), \
        (o_ref, lse_ref, m_s, l_s, acc_s, bias_s) = _split(refs, has_sel, 4)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def head(h, bias):
        s = _scores(q_ref[0, h], k_ref[0, 0], scale, in_dtype)
        if bias is not None:
            s = s + bias[...]
        m = m_s[h]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        if bias is None:
            p = jnp.exp(s - m_new)
        else:
            # a row with no key yet keeps m = -1e30: subtract 0.0 there, so
            # that its keys' exp(-1e30) is 0.0 and not exp(0)
            p = jnp.exp(s - jnp.where(m_new > _NEG_INF, m_new, 0.0))
        corr = jnp.exp(m - m_new)
        l_s[h] = l_s[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[h] = acc_s[h] * corr + _dot(p, v_ref[0, 0], ((1,), (0,)),
                                          in_dtype)
        m_s[h] = m_new

    _each_head(head, sel_ref, bias_s, qi, ki, gh, bq, bk, causal)

    @pl.when(ki == nk - 1)
    def _():
        l = l_s[...]
        row = l > 0.0
        o_ref[0] = (acc_s[...] / jnp.where(row, l, 1.0)).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            row, m_s[...] + jnp.log(jnp.maximum(l, 1e-37)), _POS_BIG)


def _probs(q, k, lse, bias, scale, in_dtype):
    s = _scores(q, k, scale, in_dtype)
    if bias is not None:
        s = s + bias[...]
    return jnp.exp(s - lse)                          # empty rows: lse = +BIG


def _dq_kernel(*refs, scale, causal, has_sel, gh, bq, bk, nk, in_dtype):
    sel_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), \
        (dq_ref, acc_s, bias_s) = _split(refs, has_sel, 7)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def head(h, bias):
        p = _probs(q_ref[0, h], k_ref[0, 0], lse_ref[0, h], bias, scale,
                   in_dtype)
        g = _dot(do_ref[0, h], v_ref[0, 0], ((1,), (1,)), in_dtype)
        ds = p * (g - delta_ref[0, h])
        acc_s[h] += _dot(ds, k_ref[0, 0], ((1,), (0,)), in_dtype)

    _each_head(head, sel_ref, bias_s, qi, ki, gh, bq, bk, causal)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = (acc_s[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, has_sel, gh, bq, bk, nq, nr, in_dtype):
    sel_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), \
        (dk_ref, dv_ref, dk_s, dv_s, bias_s) = _split(refs, has_sel, 7)
    ki, r = pl.program_id(2), pl.program_id(3)

    @pl.when(r == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def head(h, bias):
        q = q_ref[0, h]
        p = _probs(q, k_ref[0, 0], lse_ref[0, h], bias, scale, in_dtype)
        do = do_ref[0, h]
        dv_s[...] += _dot(p, do, ((0,), (0,)), in_dtype)
        g = _dot(do, v_ref[0, 0], ((1,), (1,)), in_dtype)
        ds = p * (g - delta_ref[0, h])
        dk_s[...] += _dot(ds, q.astype(jnp.float32) * scale, ((0,), (0,)),
                          in_dtype)

    _each_head(head, sel_ref, bias_s, r % nq, ki, gh, bq, bk, causal)

    @pl.when(r == nr - 1)
    def _():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BUDGET)


def _geometry(q, k, v):
    """(B, H, T, Dk, Dv, heads a K/V head, heads a grid step, bq, bk, query
    blocks, key blocks)."""
    b, h, t, dk = q.shape
    dv = v.shape[3]
    bq = bk = _pick_blocks(t)
    g = h // k.shape[1]
    gh = _heads_per_step(g, bq, bk, dk, q.dtype.itemsize, dv)
    return b, h, t, dk, dv, g, gh, bq, bk, t // bq, t // bk


def _row_specs(g, gh, bq, bk, causal):
    """Block specs of a grid (batch, block of ``gh`` query heads, query
    block, key block): (the heads' query-row blocks ``[gh, bq, d]`` for a
    width ``d``, a ``[gh, bq, 1]`` column of them, the ``d``-wide K/V block
    of the heads' group, the selection's word tile).  Under ``causal`` the
    key index clamps to the last block the query block needs, so a skipped
    step fetches nothing."""
    per_tile = KEYS_PER_TILE // bk

    def key_block(qi, ki):
        return jnp.minimum(ki, (qi * bq + bq - 1) // bk) if causal else ki

    def q_map(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def kv_map(bi, hi, qi, ki):
        return (bi, hi * gh // g, key_block(qi, ki), 0)

    def sel_map(bi, hi, qi, ki):
        return (bi, qi, key_block(qi, ki) // per_tile)
    return (lambda d: pl.BlockSpec((1, gh, bq, d), q_map),
            pl.BlockSpec((1, gh, bq, 1), q_map),
            lambda d: pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, bq, LANES), sel_map))


def _forward(q, k, v, selected, causal, scale, interpret):
    b, h, t, dk, dv, g, gh, bq, bk, nq, nk = _geometry(q, k, v)
    row, col, kv, sel = _row_specs(g, gh, bq, bk, causal)
    has_sel = selected is not None
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          has_sel=has_sel, gh=gh, bq=bq, bk=bk, nk=nk,
                          in_dtype=q.dtype),
        grid=(b, h // gh, nq, nk),
        in_specs=([sel] if has_sel else []) + [row(dk), kv(dk), kv(dv)],
        out_specs=[row(dv), col],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((gh, bq, 1), jnp.float32),
                        pltpu.VMEM((gh, bq, 1), jnp.float32),
                        pltpu.VMEM((gh, bq, dv), jnp.float32),
                        pltpu.VMEM((bq, bk), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(*(((selected,) if has_sel else ()) + (q, k, v)))
    return out, lse


def _backward(q, k, v, selected, out, lse, dout, causal, scale, interpret):
    b, h, t, dk, dv, g, gh, bq, bk, nq, nk = _geometry(q, k, v)
    per_tile = KEYS_PER_TILE // bk
    has_sel = selected is not None
    dout = dout.astype(q.dtype)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1,
                    keepdims=True)
    common = dict(scale=scale, causal=causal, has_sel=has_sel, gh=gh, bq=bq,
                  bk=bk, in_dtype=q.dtype)
    head = (selected,) if has_sel else ()
    pairs = pltpu.VMEM((bq, bk), jnp.float32)

    # -- dQ: grid (B, blocks of gh heads, query blocks, key blocks) -----------
    row, col, kv, sel = _row_specs(g, gh, bq, bk, causal)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(b, h // gh, nq, nk),
        in_specs=([sel] if has_sel else [])
        + [row(dk), kv(dk), kv(dv), row(dv), col, col],
        out_specs=row(dk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((gh, bq, dk), jnp.float32), pairs],
        compiler_params=_params(), interpret=interpret,
    )(*(head + (q, k, v, dout, lse, delta)))

    # -- dK, dV: grid (B, KV heads, key blocks, the group's blocks of gh heads
    # x query blocks) ----------------------------------------------------------
    def first_q(ki):               # the first query block key block ki reaches
        return (ki * bk) // bq

    def clamp_q(ki, r):
        qi = r % nq
        return jnp.maximum(qi, first_q(ki)) if causal else qi

    def q_map2(bi, hk, ki, r):
        return (bi, hk * (g // gh) + r // nq, clamp_q(ki, r), 0)

    def kv_map2(bi, hk, ki, r):
        return (bi, hk, ki, 0)

    def sel_map2(bi, hk, ki, r):
        return (bi, clamp_q(ki, r), ki // per_tile)
    def row2(d):
        return pl.BlockSpec((1, gh, bq, d), q_map2)

    def kv2(d):
        return pl.BlockSpec((1, 1, bk, d), kv_map2)
    col2 = pl.BlockSpec((1, gh, bq, 1), q_map2)
    nr = g // gh * nq
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, nr=nr, **common),
        grid=(b, h // g, nk, nr),
        in_specs=([pl.BlockSpec((1, bq, LANES), sel_map2)] if has_sel else [])
        + [row2(dk), kv2(dk), kv2(dv), row2(dv), col2, col2],
        out_specs=[kv2(dk), kv2(dv)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dk), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32), pairs],
        compiler_params=_params(), interpret=interpret,
    )(*(head + (q, k, v, dout, lse, delta)))
    return dq, dk, dv


def _scale(q, scale):
    return scale if scale is not None else q.shape[-1] ** -0.5


def forward(q, k, v, selected, causal=False, scale=None, interpret=False):
    """q ``[B, H, T, Dk]``; k ``[B, H / g, T, Dk]``, v ``[B, H / g, T,
    Dv]``; ``selected`` the packed key mask ``[B, T, W]`` int32 or None.
    Returns the output ``[B, H, T, Dv]`` in q's dtype and the rows'
    log-sum-exp ``[B, H, T, 1]`` float32, which ``backward`` wants back."""
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
