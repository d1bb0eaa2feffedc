"""Per-query top-k key selection with a query block's scores held in VMEM.

The Pallas body of ``select_topk_keys`` (``ops/sparse_select.py``, whose
``topk_key_mask`` is the definition).  The XLA body finds a row's k-th
largest score bit by bit — 32 counting passes over the value's bits, then
one per bit of the index for the ties at that value — and every pass is a
fusion that reads the whole ``[B, T, Tk]`` key matrix from HBM.  Here a grid
step loads one block of rows ``[bq, Tk]`` ONCE, keeps the rows' integer keys
in a VMEM scratch and makes the same decisions there: the passes are bound
by the VPU (a compare, a select and an add per vreg), not by HBM.

* **Keys.** A float's bits as a signed int32 whose order is the floats'
  order (``sparse_select._sortable`` with the top bit flipped: Mosaic's
  compares are signed); candidates sit at ``INT_MIN + 1`` and above, every
  other position of a scanned slab at ``INT_MIN``.  The scratch is laid out
  ``[Tk / 128, bq, 128]``: a 128-key slab is a leading index.
* **Passes.** ``count(key >= c)`` per row: lane-wise compare-and-add over
  the block's slabs, one cross-lane sum per row at the end.  The accepted
  threshold's own count says whether any row has more keys at the threshold
  than it may take; only then are the index's bits searched, over the same
  scratch rewritten as (0 above the threshold, ``-1 - index`` at it,
  ``INT_MIN`` below), again as ``count(. >= c)``.  A block whose rows all
  have at most ``k`` candidates makes no pass at all.
* **Causal.** A block of rows scans the slabs up to its last row's, in
  groups of ``_GROUP``; the keys beyond are never candidates and their
  words stay 0.
* **Output.** The packed words of ``sparse_select.pack_key_mask``: a slab is
  one bit plane of its 4096-key tile's 128 words, so packing is a shift and
  an ``or`` per slab.  And the count of keys each row selected.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..sparse_select import KEYS_PER_TILE, LANES, packed_width
from . import block_rows

_INT_MIN = -2 ** 31
# rows a block may hold: a per-row quantity broadcast over the lanes is
# ``bq / 8`` vregs, and a pass keeps three of them live beside the slab
_MAX_ROWS = 64
# the row block's bytes; the pipeline holds two of them and the scratch a
# third
_ROW_BLOCK_BYTES = 2 * 1024 * 1024
# slabs a loop iteration handles
_GROUP = 4
_PLANES = KEYS_PER_TILE // LANES


def _rows(t, tk):
    """Rows of a block: whole sublane tiles of ``[., Tk]`` float32 inside
    the budget, at most ``_MAX_ROWS``; 0 when not even eight rows fit."""
    if tk * 4 * 8 > _ROW_BLOCK_BYTES:
        return 0
    return block_rows(t, tk * 4, _MAX_ROWS, _ROW_BLOCK_BYTES)[0]


def supported(x_shape, dtype):
    """Whether the kernel takes these scores: float32 ``[B, T, Tk]`` with
    ``Tk`` in whole 128-key slabs, ``T`` in whole sublane tiles, and a row
    block inside the VMEM budget."""
    if len(x_shape) != 3 or jnp.dtype(dtype) != jnp.float32:
        return False
    _, t, tk = x_shape
    return tk > 0 and tk % LANES == 0 and t > 0 and t % 8 == 0 \
        and _rows(t, tk) > 0


def _group(nslab):
    return next(g for g in (_GROUP, 2, 1) if nslab % g == 0)


def _kernel(x_ref, words_ref, count_ref, key_ref, lim_ref, *, k, causal, bq,
            nslab, group):
    first = pl.program_id(1) * bq                  # the block's first row
    # groups of slabs that hold a candidate of the block's last row
    groups = jnp.minimum((first + bq - 1) // (LANES * group) + 1,
                         nslab // group) if causal else nslab // group
    row = first + lax.broadcasted_iota(jnp.int32, (bq, LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)

    def each_slab(body, carry):
        """``carry = body(j, carry)`` over the scanned slabs."""
        def step(g, carry):
            for u in range(group):
                carry = body(g * group + u, carry)
            return carry
        return lax.fori_loop(0, groups, step, carry)

    def build(j, _):
        start = pl.multiple_of(j * LANES, LANES)
        bits = lax.bitcast_convert_type(x_ref[0, :, pl.ds(start, LANES)],
                                        jnp.int32)
        key = jnp.maximum(bits ^ ((bits >> 31) & 0x7FFFFFFF), _INT_MIN + 1)
        if causal:
            key = jnp.where(lane + j * LANES <= row, key, _INT_MIN)
        key_ref[j] = key
        return 0
    each_slab(build, 0)

    def count_ge(c):
        """int32 ``[bq, 1]``: the row's entries of the scratch at or above
        ``c`` (``[bq, 1]``)."""
        cb = jnp.broadcast_to(c, (bq, LANES))
        acc = each_slab(
            lambda j, acc: acc + jnp.where(key_ref[j] >= cb, 1, 0),
            jnp.zeros((bq, LANES), jnp.int32))
        return jnp.sum(acc, axis=-1, keepdims=True)

    def search(nbits, step, state):
        """A row's largest value below ``2 ** nbits`` that ``step`` takes,
        bit by bit from the top: ``step(value with the next bit set, state)
        -> state``, and ``state[0]`` is the value so far."""
        def bit(i, state):
            return step(state[0] | (jnp.int32(1) << (nbits - 1 - i)), state)
        return lax.fori_loop(0, nbits, bit, state)

    zero = jnp.zeros((bq, 1), jnp.int32)
    # every candidate, unless the passes below say otherwise
    lim_ref[...] = jnp.full((bq, LANES), _INT_MIN + 1, jnp.int32)

    def passes():
        # the k-th largest key's bits, as topk_key_mask's unsigned ones
        def value(c, carry):
            n = count_ge(c ^ _INT_MIN)
            ok = n >= k
            return jnp.where(ok, c, carry[0]), jnp.where(ok, n, carry[1])
        thr, at_or_above = search(32, value, (zero, zero))
        thr = jnp.maximum(thr ^ _INT_MIN, _INT_MIN + 1)
        lim_ref[...] = jnp.broadcast_to(thr, (bq, LANES))

        # a row with more than k keys at or above its threshold takes the
        # ties at it by index; a block with no such row takes them all
        @pl.when(jnp.max(at_or_above) > k)
        def _():
            thr_b = jnp.broadcast_to(thr, (bq, LANES))

            def rank(j, _):
                key = key_ref[j]
                key_ref[j] = jnp.where(
                    key > thr_b, 0,
                    jnp.where(key == thr_b, -1 - (lane + j * LANES),
                              _INT_MIN))
                return 0
            each_slab(rank, 0)

            # index < cut  <=>  -1 - index >= -cut
            def index(c, carry):
                return (jnp.where(count_ge(-c) <= k, c, carry[0]),)
            cut, = search(int(nslab * LANES).bit_length(), index, (zero,))
            lim_ref[...] = jnp.broadcast_to(-cut, (bq, LANES))

    if nslab * LANES > k:
        if causal:
            pl.when(first + bq > k)(passes)
        else:
            passes()

    lim = lim_ref[...]
    count = jnp.zeros((bq, LANES), jnp.int32)
    for tile in range(-(-nslab // _PLANES)):
        def plane(p, carry):
            words, count = carry
            bit = jnp.where(key_ref[tile * _PLANES + p] >= lim, 1, 0)
            return words | (bit << p), count + bit
        planes = jnp.clip(groups * group - tile * _PLANES, 0, _PLANES)
        words, count = lax.fori_loop(
            0, planes, plane, (jnp.zeros((bq, LANES), jnp.int32), count))
        words_ref[0, :, tile * LANES:(tile + 1) * LANES] = words
    count_ref[0] = jnp.sum(count, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def select_topk_words(scores, k, causal=True, interpret=False):
    """scores float32 ``[B, T, Tk]`` -> (the packed words of
    ``pack_key_mask(topk_key_mask(scores, k, causal))``, int32 ``[B, T,
    W]``; the number of keys each row selected, int32 ``[B, T, 1]``)."""
    b, t, tk = scores.shape
    bq, nslab = _rows(t, tk), tk // LANES
    w = packed_width(tk)
    return pl.pallas_call(
        functools.partial(_kernel, k=int(k), causal=bool(causal), bq=bq,
                          nslab=nslab, group=_group(nslab)),
        grid=(b, -(-t // bq)),
        in_specs=[pl.BlockSpec((1, bq, tk), lambda bi, qi: (bi, qi, 0))],
        out_specs=[pl.BlockSpec((1, bq, w), lambda bi, qi: (bi, qi, 0)),
                   pl.BlockSpec((1, bq, 1), lambda bi, qi: (bi, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, w), jnp.int32),
                   jax.ShapeDtypeStruct((b, t, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((nslab, bq, LANES), jnp.int32),
                        pltpu.VMEM((bq, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(scores)
