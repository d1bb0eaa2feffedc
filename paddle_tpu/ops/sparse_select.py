"""Learned sparse selection: which keys each query may attend.

Two ops, neither differentiable (a selection is a set; the indexer that
ranks the keys is trained, if at all, by a loss of its own):

* ``indexer_score`` — a lightning indexer's relevance of key ``s`` to query
  ``t``: ``I[t, s] = scale * sum_j w[t, j] * relu(q[t, j] . k[s])`` over a
  few small heads ``j`` and ONE shared key head.
* ``select_topk_keys`` — per query the ``k`` highest-scoring keys among the
  causal ones (all of them while ``t < k``), ties to the lower index, as a
  **packed bit mask**.

Why a bit mask and not ``[B, T, k]`` indices: a blockwise attention kernel
asks "is key s selected for query t" for a tile of (t, s) at a time, which
from indices is a search per element and from a mask is a load; and the mask
is what is saved for the backward — 1 bit a pair (8 MB a layer at T = 8192)
against 4 bytes a selected key (67 MB).

Which body runs where.  ``topk_key_mask`` below — plain ``jax.numpy``, 32
counting passes over the value's bits and one per bit of the index for the
ties, each pass a fusion over the whole ``[B, T, Tk]`` key matrix — is the
**definition** of ``select_topk_keys``, and the body of every CPU trace, of
a trace under a mesh and of scores the kernel does not take.  A TPU trace
on one device whose float32 scores come in whole 128-key slabs
(``_kernel_applicable``: nothing but what the op can observe, and
``FLAGS_pallas_kernels=False`` says no) lowers to
``ops/pallas/topk_select.py``: the same decisions with a block of rows held
in VMEM, every score read once, the same words bit for bit.
``compile_cache.stats()["kernel_bodies"]`` says which one a trace took
(``select_topk_keys:pallas`` / ``:xla``).

Layout (``pack_key_mask``): int32 words ``[B, T, W]``, ``W = ceil(Tk / 4096)
* 128``; key ``s`` is bit ``(s % 4096) // 128`` of word ``(s // 4096) * 128 +
s % 128``.  One bit plane of a 128-word tile is thus the mask of 128
CONSECUTIVE keys, lane for lane: a kernel unpacks a 128-key slab of a block
with one shift and one ``and`` of the tile, no gather and no lane shuffle.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..registry import register_op, set_output, in_var

LANES = 128
KEYS_PER_TILE = 32 * LANES


def packed_width(tk):
    return -(-tk // KEYS_PER_TILE) * LANES


def pack_key_mask(sel):
    """bool ``[..., Tk]`` -> int32 ``[..., packed_width(Tk)]``."""
    tk = sel.shape[-1]
    tiles = -(-tk // KEYS_PER_TILE)
    pad = [(0, 0)] * (sel.ndim - 1) + [(0, tiles * KEYS_PER_TILE - tk)]
    bits = jnp.pad(sel, pad).reshape(sel.shape[:-1] + (tiles, 32, LANES))
    words = jnp.sum(bits.astype(jnp.uint32)
                    << jnp.arange(32, dtype=jnp.uint32)[:, None], axis=-2,
                    dtype=jnp.uint32)
    return lax.bitcast_convert_type(words, jnp.int32).reshape(
        sel.shape[:-1] + (tiles * LANES,))


def unpack_key_mask(words, tk):
    """int32 ``[..., W]`` -> bool ``[..., tk]``."""
    tiles = words.shape[-1] // LANES
    w = lax.bitcast_convert_type(words, jnp.uint32).reshape(
        words.shape[:-1] + (tiles, 1, LANES))
    bits = (w >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1] + (tiles * KEYS_PER_TILE,)
                        )[..., :tk].astype(bool)


# -- indexer_score -------------------------------------------------------------

def _score_infer(op, block):
    q = in_var(op, block, "Q")
    k = in_var(op, block, "K")
    w = in_var(op, block, "W")
    if len(q.shape) != 4 or len(k.shape) != 3 or len(w.shape) != 3 \
            or q.shape[3] != k.shape[2] or tuple(w.shape) != tuple(q.shape[:3]):
        raise ValueError(
            "indexer_score expects Q [B, T, Hi, Di], K [B, Tk, Di] (one "
            "shared key head) and W [B, T, Hi]; got %s / %s / %s"
            % (q.shape, k.shape, w.shape))
    set_output(op, block, "Out", (q.shape[0], q.shape[1], k.shape[1]),
               "float32")


# Queries a block.  A block's scores ``[block, Tk]`` float32 are summed over
# the heads in place, which is fast only while XLA keeps them in VMEM: at
# Tk = 8192, 512 rows (16 MB) keep their place in one layer of four once
# the selection is a kernel and 21 ms a step go to ``indexer_score``; 256
# rows keep it in all four, 9.4 ms (PERF.md 6.8).
_SCORE_BLOCK = 256


def index_scores(q, k, w, scale):
    """``[B, T, Tk]`` float32.  Query blocks in turn, so that the per-head
    products ``[B, Hi, block, Tk]`` never exist for the whole sequence."""
    b, t, hi, _ = q.shape

    def block(qw):
        qb, wb = qw                                    # [B, r, Hi, Di], [B, r, Hi]
        s = jnp.einsum("brjd,bsd->brjs", qb, k,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("brjs,brj->brs", jnp.maximum(s, 0.0),
                          wb.astype(jnp.float32) * scale)
    if t <= _SCORE_BLOCK or t % _SCORE_BLOCK:
        return block((q, w))
    n = t // _SCORE_BLOCK
    out = lax.map(block, (
        q.reshape(b, n, _SCORE_BLOCK, hi, -1).swapaxes(0, 1),
        w.reshape(b, n, _SCORE_BLOCK, hi).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, t, -1)


def _score_compute(ins, attrs, ctx, op_index):
    return {"Out": index_scores(ins["Q"][0], ins["K"][0], ins["W"][0],
                                float(attrs.get("scale", 1.0)))}


register_op("indexer_score", ["Q", "K", "W"], ["Out"], infer=_score_infer,
            compute=_score_compute, grad=None)


# -- select_topk_keys ----------------------------------------------------------

def _select_infer(op, block):
    x = in_var(op, block, "X")
    if len(x.shape) != 3:
        raise ValueError("select_topk_keys expects scores [B, T, Tk], got %s"
                         % (x.shape,))
    set_output(op, block, "Out",
               (x.shape[0], x.shape[1], packed_width(x.shape[2])), "int32")
    set_output(op, block, "Share", (1,), "float32")


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_key_mask(scores, k, causal=True):
    """bool ``[B, T, Tk]``: per row the ``k`` largest of the candidate
    entries (``s <= t`` under ``causal``), every candidate where there are
    no more than ``k``; equal scores go to the lower index.

    No sort: the k-th largest value is found bit by bit (32 counting
    passes over the row, each one fused compare-and-sum), then the ties at
    that value are cut at the index that fills the row up to ``k``, found
    the same way over the index's bits."""
    t, tk = scores.shape[-2:]
    idx = jnp.arange(tk, dtype=jnp.int32)
    cand = (idx[None, :] <= jnp.arange(t, dtype=jnp.int32)[:, None]) \
        if causal else jnp.ones((t, tk), bool)
    # candidates sit at 1.. so that 0 is below them all
    u = jnp.where(cand, jnp.maximum(_sortable(scores), jnp.uint32(1)),
                  jnp.uint32(0))

    def count(pred):
        return jnp.sum(pred, axis=-1, keepdims=True, dtype=jnp.int32)

    def value_bit(i, thr):
        c = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(count(u >= c) >= k, c, thr)
    thr = lax.fori_loop(0, 32, value_bit,
                        jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    above = u > thr
    tie = (u == thr) & cand
    need = k - count(above)
    nbits = int(tk).bit_length()

    def index_bit(i, cut):
        c = cut | (jnp.int32(1) << (nbits - 1 - i))
        return jnp.where(count(tie & (idx < c)) <= need, c, cut)
    cut = lax.fori_loop(0, nbits, index_bit, jnp.zeros_like(need))
    return cand & (above | (tie & (idx < cut)))


# the platforms whose traces take the Pallas body: on the CPU the op keeps
# the XLA body (the interpreter is for the kernel's own tests)
_KERNEL_PLATFORMS = ("tpu",)


def _kernel_applicable(ctx, x_shape, dtype):
    """The Pallas body's rule, from what the op can observe: a TPU trace on
    one device (the rows are independent, but a per-shard lowering is not
    written), no ``FLAGS_pallas_kernels=False``, and scores its
    ``supported()`` takes."""
    from .pallas import kernel_allowed, topk_select

    return kernel_allowed(ctx, _KERNEL_PLATFORMS) \
        and getattr(ctx, "mesh", None) is None \
        and topk_select.supported(x_shape, dtype)


def _select_compute(ins, attrs, ctx, op_index):
    from ..compile_cache import note_kernel_body

    x = ins["X"][0]
    k, causal = int(attrs["k"]), attrs.get("causal", True)
    if _kernel_applicable(ctx, x.shape, x.dtype):
        from .pallas import interpret_mode, topk_select

        note_kernel_body("select_topk_keys", "pallas")
        words, count = topk_select.select_topk_words(
            x, k, causal, interpret_mode(ctx))
        selected = jnp.sum(count, dtype=jnp.float32)
    else:
        note_kernel_body("select_topk_keys", "xla")
        sel = topk_key_mask(x, k, causal)
        words, selected = pack_key_mask(sel), jnp.sum(sel, dtype=jnp.float32)
    t, tk = x.shape[-2:]
    pairs = t * (t + 1) // 2 if causal and t == tk else t * tk
    share = selected / (x.shape[0] * pairs)
    return {"Out": words, "Share": share.reshape(1)}


register_op("select_topk_keys", ["X"], ["Out", "Share"], infer=_select_infer,
            compute=_select_compute, grad=None)
