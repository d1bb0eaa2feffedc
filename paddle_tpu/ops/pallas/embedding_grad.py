"""The dense gradient of ``lookup_table`` — ``N`` rows of ``GRAD::Out`` added
into a ``[V, D]`` table — by sorted segments into VMEM-resident blocks.

The generic gradient is ``jax.vjp`` of ``jnp.take``: one scattered add.  XLA's
TPU lowering of it sorts the ids, gathers the rows in that order and hands
them to ONE opaque scatter fusion that adds them into the table's buffer in
HBM, whose time follows the shape in ways only the chip shows: 0.03-0.19 us a
row added at widths 512 and 2048, and at width 2560 0.40 us a row OF THE
TABLE however many are added (10.0 ms for ``[25,008, 2560]``; PERF.md 6.20).
Here the table's gradient is made block by block in VMEM and each block
written ONCE:

* XLA sorts the ids (``lax.sort_key_val``) and **gathers the rows in bulk**
  in sorted order, as float32, padded to whole chunks of ``chunk`` rows (a
  padding row belongs to no segment).  A ``padding_idx`` row is zeroed in
  that gather, as ``lookup_table`` zeroes it.
* The table is walked in blocks of ``block`` rows.  The sorted rows of a
  block are a contiguous range; a grid step — a **work item** — is the part
  of one block's range that lies in one chunk, so a step holds ONE block of
  the table ``[block, D]`` float32 and ONE chunk of rows ``[chunk, D]`` in
  VMEM, both picked in the index maps from scalars prefetched to SMEM.
  Items are ordered by block, so a block stays resident over its items: it
  starts as zeros at its first item, every row of the item is added to its
  place by a one-row read-modify-write in VMEM (float32), and Pallas writes
  the block back when the next item names another.  A block no id falls in
  is one item with no rows: zeros, no row loop.
* The grid is the static bound ``blocks + chunks`` (a block has one item and
  one more for every chunk boundary inside its range); the items past the
  live ones repeat the last live item's block and chunk — no fetch, no
  write — and have no rows.

Every row of the table is written exactly once and every gradient row read
once: ``V x D x 4`` bytes out, ``N x D x 4`` in.  The sums are float32 in the
order of the sort (stable: a row's addends arrive in the order of their
positions), whatever ``GRAD::Out``'s dtype.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import run_traced

_LANES = 128
_SUBLANES = 8
# Bytes of a resident table block and of a chunk of rows (each double-
# buffered: four of them a grid step), and the limit the kernel is compiled
# under.
_BLOCK_BYTES = 5 * 512 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024
# Sorted ids the kernel's SMEM (1 MiB) holds beside the items' scalars.
_MAX_ROWS = 128 * 1024


def block_rows(d):
    """Rows of a table block, and of a chunk of sorted rows, at width
    ``d``: what ``_BLOCK_BYTES`` of float32 hold, in whole sublane tiles."""
    return max(_SUBLANES, _BLOCK_BYTES // (4 * d) // _SUBLANES * _SUBLANES)


def supported(n, v, d, w_dtype, g_dtype):
    """Whether the kernel takes ``n`` rows of ``g_dtype`` into a ``[v, d]``
    table of ``w_dtype``: a float32 table whose rows are whole lane tiles
    and whose height is whole sublane tiles (a block's last, partial, tile
    row would be written past the table), float32 or bf16 rows, the ids
    inside SMEM and a step's four blocks inside the VMEM limit."""
    return jnp.dtype(w_dtype) == jnp.dtype(jnp.float32) \
        and jnp.dtype(g_dtype) in (jnp.dtype(jnp.float32),
                                   jnp.dtype(jnp.bfloat16)) \
        and 0 < n <= _MAX_ROWS and d % _LANES == 0 and v % _SUBLANES == 0 \
        and 4 * block_rows(d) * d * 4 <= _VMEM_LIMIT * 3 // 4


def _kernel(sid_ref, blk_ref, chunk_ref, lo_ref, hi_ref, rows_ref, out_ref,
            *, block, chunk):
    i = pl.program_id(0)
    b = blk_ref[i]

    @pl.when((i == 0) | (b != blk_ref[jnp.maximum(i - 1, 0)]))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    base, first = b * block, chunk_ref[i] * chunk

    def add(r, carry):
        out_ref[pl.ds(sid_ref[r] - base, 1), :] += \
            rows_ref[pl.ds(r - first, 1), :]
        return carry
    lax.fori_loop(lo_ref[i], hi_ref[i], add, 0)


def _segments(sid, blk, chunk_of, lo, hi, rows, *, v, block, chunk,
              interpret):
    d = rows.shape[1]
    return pl.pallas_call(
        functools.partial(_kernel, block=block, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(blk.shape[0],),
            in_specs=[pl.BlockSpec(
                (chunk, d), lambda i, sid, blk, ch, lo, hi: (ch[i], 0))],
            out_specs=pl.BlockSpec(
                (block, d), lambda i, sid, blk, ch, lo, hi: (blk[i], 0))),
        out_shape=jax.ShapeDtypeStruct((v, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(sid, blk, chunk_of, lo, hi, rows)


def _rank(ordered, queries, side):
    """``jnp.searchsorted``: a handful of queries against all of ``ordered``
    at once (one fusion), not the binary search's loop of a dozen tiny
    kernels, unless the comparisons would be many."""
    few = ordered.shape[0] * queries.shape[0] <= 1 << 24
    return jnp.searchsorted(
        ordered, queries, side=side,
        method="compare_all" if few else "scan").astype(jnp.int32)


def _items(sid, n, v, block, chunk, chunks):
    """The work items' scalars, ``blocks + chunks`` of each: the table block,
    the chunk of sorted rows, and the range ``[lo, hi)`` of sorted positions
    the item adds."""
    blocks = -(-v // block)
    # off[b]: the first sorted position whose id is in block b or beyond
    off = _rank(sid, jnp.arange(blocks + 1, dtype=jnp.int32) * block, "left")
    start, end = off[:-1], off[1:]
    first = jnp.minimum(lax.div(start, chunk), chunks - 1)
    last = jnp.maximum(first, lax.div(jnp.maximum(end, 1) - 1, chunk))
    count = last - first + 1
    item0 = jnp.cumsum(count) - count          # a block's first item
    live = item0[-1] + count[-1]
    at = jnp.arange(blocks + chunks, dtype=jnp.int32)
    i = jnp.minimum(at, live - 1)
    blk = _rank(item0, i, "right") - 1
    chunk_of = first[blk] + (i - item0[blk])
    lo = jnp.maximum(start[blk], chunk_of * chunk)
    hi = jnp.minimum(end[blk], (chunk_of + 1) * chunk)
    return blk, chunk_of, lo, jnp.where(at >= live, lo, jnp.maximum(hi, lo))


def embedding_grad(ids, gout, v, padding_idx=None, interpret=False):
    """``[v, D]`` float32: row ``r`` the float32 sum of ``gout``'s rows whose
    id is ``r`` (``ids`` ``[N]`` integers in ``[0, v)``, ``gout`` ``[N, D]``;
    rows whose id is ``padding_idx`` add nothing)."""
    n, d = gout.shape
    block = chunk = block_rows(d)
    chunks = -(-n // chunk)
    sid, perm = lax.sort_key_val(ids.astype(jnp.int32),
                                 jnp.arange(n, dtype=jnp.int32))
    pad = chunks * chunk - n
    rows = gout[jnp.pad(perm, (0, pad))].astype(jnp.float32)
    if padding_idx is not None and padding_idx != -1:
        rows = rows * (jnp.pad(sid, (0, pad)) != padding_idx)[:, None] \
            .astype(jnp.float32)
    blk, chunk_of, lo, hi = _items(sid, n, v, block, chunk, chunks)
    (out,) = run_traced(
        "embedding_grad", _segments, (sid, blk, chunk_of, lo, hi, rows),
        v=v, block=block, chunk=chunk, interpret=interpret)
    return out
