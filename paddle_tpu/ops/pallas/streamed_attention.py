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
  latent and a 64-wide rotary part — over 128-wide values); ``Dk == Dv`` is
  the common case.  Over ``[B, H, T, D]`` operands the scores are ONE
  contraction over ``Dk``: ``Dv`` is whole lane tiles, ``Dk`` whole
  half-tiles (a 192-wide block is the array's full last axis, which Mosaic
  lays out in two lane tiles).  That form wants the rotary part JOINED onto
  every head's keys and the heads in front of the sequence, which a latent
  block's three projections do not write: the split, the rotation, the
  broadcast, the join and four transposes a block, and their gradients, were
  ~50 ms of a 328 ms step (PERF.md 6.25).  So the kernels have a second way
  to address their operands — **the projections' own layout**, the section
  of that name below: Q ``[B, T, H * (nope + rope)]``, the key/value
  projection's ``[B, T, H * (nope + dv)]`` and the shared key part ``[B, T,
  rope]`` as three arrays, the score two products summed in float32.  The
  second product saves no MXU pass (the 128-deep passes make the 64 odd
  columns cost one either way); it is there for the copies it removes.
* **Grouped heads where the projections wrote them.** ``forward`` /
  ``backward`` with ``n_head``: Q ``[B, T, H * D]``, K ``[B, T, H / g *
  D]``, V ``[B, T, H / g * Dv]`` and the result ``[B, T, H * Dv]`` — the
  SAME kernels (``_fwd_kernel``, ``_grad_kernel``) on the same grid with the
  same heads a step, a step's heads a COLUMN block ``[1, bq, kh * g' * D]``
  / ``[1, bk, kh * D]`` / the resident ``[1, T, kh * D]`` of dK and dV
  (``_row_specs(flat=True)``) and a head read as whole lane tiles of it
  (``_head``).  With ``rot`` (``half_turn_tables``, resident whole) the
  kernels rotate q and k themselves (``_Rotation``): a query block's heads
  ONCE, as the block's first step loads them, and a step's K/V heads each
  time their block is loaded — rotate-half of a 128-wide head is one
  64-lane roll a tile and a signed-sine table — into VMEM scratch the head
  loop reads; dQ and dK are turned back from their float32 sums where they
  are written.  ``D`` and ``Dv`` whole lane tiles; the dQ + dK/dV pair
  stays 4-D.
* **One grid step serves several heads.** K/V carry ``H / g`` heads; the
  ``g`` query heads that read one of them are contiguous in ``[B, H, T,
  D]``.  A step serves ``kh`` K/V heads and ``g'`` query heads of each
  (``_heads_per_step``, from the operands' shapes and the VMEM budget):
  its Q / O / dO / dQ block is ``[1, kh * g', bq, D]``, its K/V block
  ``[1, kh, bk, D]``, query head ``j`` of the step reads K/V head ``j //
  g'`` of the step, and the step loops over its heads (a rolled
  ``fori_loop`` of at most four heads' text a turn, however many).  Heads
  that share a K/V head (``g`` > 1): ``kh`` = 1 and ``g'`` a divisor of
  ``g``, all of them where the blocks fit.  Plain heads (``g`` = 1, with a
  selection or without: the body of long plain-head self-attention whose
  K/V the resident kernel cannot hold, and of latent attention): ``g'`` =
  1 and ``kh`` a divisor of the heads, each head of the step with its own
  K/V block and, in the backward, its own float32 dK and dV.  The forward
  and the backward each have the heads a step their own blocks allow
  (``step_heads``, ``grad_step``).  What the heads of a
  step share is done ONCE a step: the selection's word tile is fetched
  once (and a group's K/V block), and which (query, key) pairs count is
  worked out once, into a float32 ``[bq, bk]`` scratch that holds 0 for a
  pair that counts and -1e30 for one that does not; a head adds it to its
  scores.  So is what a step costs whatever it holds: its prologue (the
  blocks' DMAs issued and awaited, the ``pl.when`` tests, the
  accumulators' first and last touch) and, under ``causal``, the empty
  steps above the diagonal.  The forward and the backward run on the grid
  (B, H / (kh x g'), query blocks, key blocks), query-major; head ``j`` of
  a backward step adds into the float32 dK and dV of K/V head ``j // g'``.
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
* **Window.** ``window`` (with ``causal``): key ``s`` counts for query ``t``
  iff ``t - window < s <= t``.  The pairs' scratch gains the lower edge in
  the blocks it crosses; a block wholly below it runs nothing and fetches
  nothing, its index clamped from below as the diagonal clamps it from
  above (at 512-key blocks a 512-key window needs two of a row's blocks).
  A row may meet its first key in the SECOND block that runs for it, which
  the forward's state allows as it does under a selection.  A call without
  a window is bound, traced and lowered as it was before there was one: no
  operand, scratch or comparison is added, and ``window`` is not among its
  statics.

* **The forward's state.** A head of the step keeps, in VMEM across the key
  blocks, its float32 accumulator and two ``[bq, 128]`` float32 arrays all
  of whose lanes are live: the running maximum ``m``, every lane of a row
  equal, and the running sum ``l``, lane ``j`` the sum over the keys ``j mod
  128``.  A block pair makes ONE cross-lane reduction a row (the maximum,
  after Mosaic has taken the scores' lane tiles down to one by vector
  maxima), whose result every lane holds, so ``m_new``, ``corr = exp(m -
  m_new)`` and the test for a row with no key yet are full-width vector
  operations, the subtraction from the scores and the accumulator's rescale
  read the same registers once a lane tile, and ``l = l * corr +`` the
  probabilities' lane tiles added up: plain vector adds.  A row's lanes of
  ``l`` are added up once, where the output and the log-sum-exp are
  written: the same float32 addends as a row sum a pair, in another order.
  (Until PR 37 ``m`` and ``l`` were ``[bq, 1]`` columns, one live lane of
  128, and a pair paid a cross-lane sum and two lane broadcasts a row group
  more.  Mosaic's schedule of a four-head turn of the loop at 128-wide keys
  was 7,242 bundles against the two products' 4,096 MXU cycles and is
  4,088; on the chip the row sum cost 2.0 ms of a 7.45 ms call at the
  long-document shape, the column maximum and its broadcasts 3.4, the
  rescale 2.2 — overlapping — and scaling q every pair nothing, which is
  why it stays where it was: PERF.md 6.17.)

No dropout and no per-row key length: every position is real (the op falls
back to the XLA body otherwise).

* **The backward** is the flash decomposition (``delta = rowsum(dO * O)``
  by XLA, probabilities recomputed from the saved log-sum-exp) in ONE
  kernel: a head's block pair computes ``s = q k^T``, ``p = exp(s - lse)``,
  ``g = dO v^T`` and ``ds = p (g - delta)`` once and makes the three
  gradient products from them — ``dV += p^T dO``, ``dQ += ds k``, ``dK +=
  ds^T q``: five products a pair.  dQ sums over a row's key blocks, in a
  float32 ``[heads of the step, bq, Dk]`` scratch written at the row's last
  block; dK and dV sum over the QUERY blocks, so the step's K/V heads'
  WHOLE float32 dK ``[T, Dk]`` and dV ``[T, Dv]`` stay in VMEM scratch
  across both inner axes (and across a group's head blocks, where ``g'`` <
  ``g``): zeroed at the head group's first step, a pair adding into its key
  block's rows, cast into the dK and dV output blocks — a K/V head's whole
  ``[T, d]``, indexed by (batch, head block) alone, ONE buffer each — at the
  group's last step, which Pallas writes back once.  No float32 gradient
  ever reaches HBM.  A key block's additions arrive query blocks ascending
  with the step's heads inside, the order the dK/dV kernel below has, so
  the two bodies agree to the bit wherever they split a group's heads over
  steps alike.  Where not even one K/V head's resident gradients fit the
  budget (``T x lanes(Dk + Dv) x (4 + itemsize)`` bytes: 32k tokens at
  128-wide bf16 heads) the backward is the two kernels the fused one came
  from — ``_dq`` on the same grid and ``_dkv`` on (B, H / (g x kh), key
  blocks, (g / g') x query blocks), each recomputing ``s``, ``p``, ``g``
  and ``ds``: seven products a pair — at the forward's heads a step
  (``grad_step``: the operands' shapes and the budget decide, nothing
  else).

A step program calls these kernels once a block with the same shapes.  Each
``pallas_call`` — the forward and the fused backward; three with the two
backward kernels — is traced ONCE a signature — the operands'
shapes and dtypes, a selection or none, the heads a step, ``causal``,
``scale``, ``interpret`` — into a jaxpr that ``pallas.traced`` keeps, and
every call evaluates that jaxpr on its own operands (``pallas.run_traced``,
which ``grouped_experts`` shares).  All sites of a
signature then bind the SAME ``pallas_call`` equation, so jax lowers it to
Mosaic once (its per-equation lowering cache is keyed on the equation's
params; a ``pallas_call`` built anew carries new index maps and a new
partial of its kernel and never hits) and inlines the result at each site
under that site's own name stack — the ``fluid[<op type>]`` scope by which
the device trace is read.  Not a ``jax.jit`` around the kernels: that would
lower once too, into shared functions under no block's scope.
``compile_cache.stats()["kernel_traces"]["streamed_attention"]`` counts the
sites and the traces (12 and 2 in a step of six plain-head blocks).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_BUDGET, run_traced
from ..sparse_select import KEYS_PER_TILE, LANES

_NEG_INF = -1e30
_POS_BIG = 1e30

# What a grid step may hold in VMEM, and the limit the kernels are compiled
# under (Mosaic's default scoped limit is 16 MiB; a v5e core has 128 MiB).
# The forward's heads a step are decided by ``_step_bytes``, the hungrier of
# the dQ and dK/dV kernels (which run at those heads where they are the
# backward).  At the long-document cell's shape — g = 8 heads a step, bq =
# bk = 512, D = 128, bf16 — it reads 26 MiB: a head's Q, dO and dQ blocks
# are 128 KB each but its ``[bq, 1]`` float32 columns (log-sum-exp, delta)
# pad to 128 lanes, 256 KB each, and all five are double-buffered (2 MB a
# head with the float32 accumulator, 16 MB the group); the rest is the K/V
# blocks, the word tile, the pairs' scratch and a head's temporaries.  At
# the latent-attention cell's — 32 plain heads, keys 192 (two lane tiles)
# over values 128, bf16 — a head of the step is 4 MiB by dK/dV, the
# hungrier there (Q and dO blocks and two columns 1.75, its own K and V
# blocks 0.75, its float32 dK and dV and their output blocks 1.5): 25.5 MiB
# for 4 heads, 41.5 for 8, which the rule takes and Mosaic compiles; 16 do
# not fit.
# The fused backward's step (``_fused_step_bytes``) holds dQ's blocks and,
# resident, a K/V head's whole float32 dK and dV with the output blocks they
# are cast into, ``T x lanes(Dk + Dv) x (4 + 2)`` bytes in bf16 — by cell,
# the rule's heads a step, its sum and the least limit Mosaic compiles under:
#   long document (T 8192, 128 + 128): 12 MiB a K/V head; 1 x 8: 32.0 / 31
#   latent (T 8192, 192 -> 256 lanes + 128): 18 MiB a head; 2 x 1: 46.5 / 44
#     (4 x 1 would take 89)
#   looped (T 4096, 128 + 128): 6 MiB a head; 4 x 1: 37.5 / 37 (8 x 1: 72)
#   hybrid (T 4096, 64 -> 128 lanes + 128): 6 MiB; 1 x 2: 14.0
# and 32k tokens of 128-wide heads are 48 MiB a K/V head: the two kernels.
# A block's forward / dQ / dK/dV alone on a v5e, ms a call (dQ and dK/dV
# with ``backward``'s delta), by plain heads a step, with the forward's
# state in columns (PR 32):
#   1: 11.56 / 12.10 / 13.95 (the kernels before plain heads shared a step,
#   to 0.01)   2: 10.92 / 11.16 / 12.64   4: 8.81 / 10.71 / 11.94
#   8: 8.38 / 10.56 / 11.87 — the same bits out of all of them.
# With the state per lane (PR 37; each kernel alone, its operands given):
#   long-document shape, 1 x 8:   3.96 (was 7.45) / 6.07 / 7.67
#   latent shape, 8 x 1:          6.54 (was 8.38) / 10.44 / 11.58
#   16 plain heads of 128 at T = 4096, 8 x 1:   0.62 (was 1.16) / 0.81 / 1.10
# The whole backward alone, the two kernels against the fused one by heads
# a step (PR 39; ms a call with delta, the same bits wherever a group's
# heads share steps alike):
#   long-document shape: 13.34 | 1 x 8: 9.66   1 x 4: 10.04   1 x 2: 11.20
#   latent shape:        20.96 | 1 x 1: 17.71  2 x 1: 16.66
#                                (4 x 1 under a 96 MiB limit: 16.13)
#   looped cell's shape:  1.887 | 4 x 1: 1.399  2 x 1: 1.513
#                                (8 x 1 under 80 MiB: 1.480 — no faster)
#   hybrid, 512-key window: 3.179 | 1 x 2: 2.457;  no window: 5.392 | 4.084
_VMEM_BUDGET = VMEM_BUDGET
# Heads whose text one turn of the head loop holds: the scheduler runs a
# head's products on the MXU under its neighbour's softmax on the VPU, which
# a loop of single heads forbids.  A layer's three kernels at the
# long-document cell's shape read 23.6 ms with 1, 22.4 with 2, 21.7 with 4
# and 21.4 with all 8 (28.2 before the grouping); the step's compile is the
# same to its own noise (30-35 s) with any of them.  At the latent cell's,
# 8 plain heads a step: 33.4 ms with 2, 30.8 with 4, 31.2 with all 8 (the
# forward 10.50 / 8.38 / 9.05); 4 heads a step one at a time 34.9, by twos
# 33.9, all four 31.5.  (All read with the forward's state in columns; with
# it per lane the schedule runs a turn's heads one after the other, each at
# its products' MXU cycles, and the turn's size was not read again.)
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


def _step_bytes(gh, bq, bk, dk, itemsize, dv=None, kh=1):
    """VMEM bytes of a grid step of the forward, dQ or dK/dV kernel that
    serves ``kh`` K/V heads of ``dk``-wide keys and ``dv``-wide values
    (``dk`` by default), each with ``gh`` query heads, by the hungrier of dQ
    (a query head's Q, dQ and dO row blocks, two columns and its float32
    accumulator; a K/V head's two blocks) and dK/dV (a query head's Q and
    dO blocks and two columns; a K/V head's two blocks, its float32 dK and
    dV and their two output blocks).  The forward, whose heads a step this
    decides whichever body the backward takes, holds less than dQ whatever
    the widths: a head's Q and O blocks, one column, ``m`` and ``l`` (a
    column's bytes each, whose lanes are all live now) and the accumulator.
    (``_fused_step_bytes`` is the fused backward's.)"""
    dk, dv = _lanes(dk), _lanes(dk if dv is None else dv)
    column = bq * LANES * 4              # [bq, 1] float32 pads to 128 lanes
    kv = 2 * bk * (dk + dv) * itemsize
    dq = kh * gh * (2 * (bq * (2 * dk + dv) * itemsize + 2 * column)
                    + bq * max(dk, dv) * 4) + kh * kv
    dkv = kh * gh * 2 * (bq * (dk + dv) * itemsize + 2 * column) \
        + kh * (2 * kv + bk * (dk + dv) * 4)
    shared = 2 * bq * LANES * 4 + bq * bk * 4   # the word tile, the pairs
    temporaries = 8 * bq * bk * 4        # scores, probabilities, their casts
    return max(dq, dkv) + shared + temporaries


def _fused_step_bytes(gh, bq, bk, t, dk, itemsize, dv=None, kh=1):
    """VMEM bytes of a grid step of the fused backward that serves ``kh``
    K/V heads, each with ``gh`` query heads: a query head's Q, dO and dQ row
    blocks, two columns and its float32 dQ accumulator; a K/V head's two
    blocks and, RESIDENT across the head group's steps, its whole float32
    dK ``[t, dk]`` and dV ``[t, dv]`` with the one-buffered output blocks
    they are cast into; the word tile, the pairs' scratch and the head
    loop's temporaries (Mosaic keeps few of a pair's ``[bq, bk]`` values
    whole: the least limit it compiles under reads 1-2.5 MiB UNDER this sum
    at the cells' shapes)."""
    dk, dv = _lanes(dk), _lanes(dk if dv is None else dv)
    column = bq * LANES * 4
    rows = kh * gh * (2 * (bq * (2 * dk + dv) * itemsize + 2 * column)
                      + bq * dk * 4)
    kv = kh * (2 * bk * (dk + dv) * itemsize + t * (dk + dv) * (4 + itemsize))
    shared = 2 * bq * LANES * 4 + bq * bk * 4
    temporaries = 2 * bq * bk * 4
    return rows + kv + shared + temporaries


def _most_heads(g, hk, fits):
    """(``kh``, ``g'``), the most heads a step by ``fits(kh, g')``: heads
    that share a K/V head, one K/V head and the largest divisor of ``g``;
    plain heads (``g`` = 1), the largest divisor of ``hk``.  None where not
    even one head fits."""
    def most(n, ok):
        return max((m for m in range(1, n + 1) if n % m == 0 and ok(m)),
                   default=None)
    if g > 1:
        gh = most(g, lambda m: fits(1, m))
        return None if gh is None else (1, gh)
    kh = most(hk, lambda m: fits(m, 1))
    return None if kh is None else (kh, 1)


def _heads_per_step(g, hk, bq, bk, dk, itemsize, dv=None):
    """(``kh``, ``g'``): the K/V heads one grid step of the forward (and of
    the dQ and dK/dV kernels) serves, of ``hk``, and the query heads of
    each, of its group of ``g`` — the most whose blocks and scratch fit the
    budget, one head where none does."""
    return _most_heads(g, hk, lambda kh, gh: kh * gh == 1 or _step_bytes(
        gh, bq, bk, dk, itemsize, dv, kh) <= _VMEM_BUDGET)


def _fused_heads_per_step(g, hk, bq, bk, t, dk, itemsize, dv=None):
    """The same for the fused backward, whose step also holds its K/V
    heads' whole float32 dK and dV; None where one K/V head's do not fit
    (about 32k tokens at 128-wide heads), which is where the dQ and dK/dV
    kernels are the backward's body."""
    return _most_heads(g, hk, lambda kh, gh: _fused_step_bytes(
        gh, bq, bk, t, dk, itemsize, dv, kh) <= _VMEM_BUDGET)


def _scores(q, k, scale, in_dtype):
    q = (q.astype(jnp.float32) * scale).astype(in_dtype)
    return jax.lax.dot_general(q, k.astype(in_dtype),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b, contract, in_dtype):
    return jax.lax.dot_general(a.astype(in_dtype), b.astype(in_dtype),
                               (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _lane_tiles(x):
    """The 128-lane tiles of ``x``'s last axis (whole vector registers: a
    slice at a tile's edge moves nothing)."""
    return [x[:, i * LANES:(i + 1) * LANES]
            for i in range(x.shape[-1] // LANES)]


# Whole-number division of a traced index that is never negative (a grid
# index, a head of the step's loop).  Not ``//`` and ``%``: they round to the
# floor through ``sign`` and a select, a dozen scalar operations each in the
# kernel's text — 480 of them took 6.6 s of a step's lowering when the head
# loop used ``//``.
_div, _rem = jax.lax.div, jax.lax.rem


def _split(refs, has_sel, n_in):
    """(selected ref or None, the other inputs, outputs and scratch)."""
    if has_sel:
        return refs[0], refs[1:n_in], refs[n_in:]
    return None, refs[:n_in - 1], refs[n_in - 1:]


def _each_head(head, sel_ref, bias_s, qi, ki, kh, gh, bq, bk, causal,
               window=None, first=None):
    """Block pair (qi, ki) for the step's ``kh * gh`` query heads:
    ``head(h, kv, bias)`` for each, where ``kv`` is the one of the step's
    ``kh`` K/V heads that query head ``h`` reads (``h // gh``) and ``bias``
    is ``bias_s`` — float32 ``[bq, bk]``, 0.0 for a (query, key) pair that
    counts and -1e30 for one that does not, written here once for all the
    heads — or None when every pair counts.  Nothing runs for a pair wholly
    above the diagonal, nor, under ``window`` (key ``s`` counts for query
    ``t`` iff ``t - window < s <= t``), for one wholly below the window's
    lower edge.  ``first()``, where given, runs once before the heads of a
    pair that runs."""
    n = kh * gh
    together = max(m for m in range(1, _HEADS_UNROLLED + 1) if n % m == 0)

    def heads(bias):
        if first is not None:
            first()

        def body(i, carry):
            for j in range(together):
                h = i * together + j
                head(h, _div(h, gh) if kh > 1 else 0, bias)
            return carry
        jax.lax.fori_loop(0, n // together, body, 0)

    def below_diagonal():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        keys = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ahead = rows - keys                 # t - s - (qi * bq - ki * bk)
        if window is None:
            return ahead >= ki * bk - qi * bq
        return (ahead >= ki * bk - qi * bq) \
            & (ahead < ki * bk - qi * bq + window)

    runs = ki * bk <= qi * bq + bq - 1
    crossed = runs & (ki * bk + bk - 1 > qi * bq)
    if window is not None:
        # the block's last key is inside the first query's window; the
        # lower edge crosses a block whose first key is outside the last
        # query's
        runs = runs & (ki * bk + bk - 1 > qi * bq - window)
        crossed = runs & ((ki * bk + bk - 1 > qi * bq)
                          | (ki * bk <= qi * bq + bq - 1 - window))
    if sel_ref is not None:
        def block():
            words = sel_ref[0]                                  # [bq, 128]
            first_plane = _rem(ki, KEYS_PER_TILE // bk) * (bk // LANES)
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


def _softmax_pair(h, s, bias, values, m_s, l_s, acc_s, in_dtype):
    """Head ``h``'s online-softmax state over one more block of keys, whose
    scores are ``s`` ``[bq, bk]`` float32 and whose values ``values()``
    hands over (``bias``: ``_each_head``'s)."""
    def across(x, width):
        # [bq, 128], every lane of a row equal, as [bq, width]: the same
        # registers named width / 128 times
        return jnp.tile(x, (1, width // LANES))

    bk = s.shape[1]
    if bias is not None:
        s = s + bias[...]
    # the block's maximum a row (its lane tiles by vector maxima, then
    # ONE cross-lane step), held by every lane of the row as m is
    m = m_s[h]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    if bias is None:
        p = jnp.exp(s - across(m_new, bk))
    else:
        # a row with no key yet keeps m = -1e30: subtract 0.0 there, so
        # that its keys' exp(-1e30) is 0.0 and not exp(0)
        p = jnp.exp(s - across(
            jnp.where(m_new > _NEG_INF, m_new, 0.0), bk))
    corr = jnp.exp(m - m_new)
    # lane j sums the keys j mod 128; the lanes are added up at the end
    l_s[h] = l_s[h] * corr + functools.reduce(jnp.add, _lane_tiles(p))
    acc_s[h] = acc_s[h] * across(corr, acc_s.shape[-1]) + _dot(
        p, values(), ((1,), (0,)), in_dtype)
    m_s[h] = m_new


def _softmax_rows(m_s, l_s, acc_s):
    """(the step's heads' outputs ``[n, bq, Dv]`` float32, their rows'
    log-sum-exp ``[n, bq, 1]``) after a row's last block of keys, each made
    when it is called."""
    l = jnp.sum(l_s[...], axis=-1, keepdims=True)
    row = l > 0.0
    return (lambda: acc_s[...] / jnp.where(row, l, 1.0),
            lambda: jnp.where(
                row, m_s[:, :, :1] + jnp.log(jnp.maximum(l, 1e-37)),
                _POS_BIG))


def _columns(ref, at, width):
    """``width`` columns at ``at`` of a block ``[1, rows, columns]``: whole
    lane tiles, ``at`` static or a multiple of 128 made of the head loop's
    own index (the loop stays rolled)."""
    if not isinstance(at, int):
        at = pl.multiple_of(at, LANES)
    return ref[0, :, pl.ds(at, width)]


def _head(ref, h, n):
    """Head ``h`` of a block that holds ``n``: ``ref[0, h]`` of ``[1, n,
    rows, d]`` — heads in front of the sequence — or the head's columns of
    ``[1, rows, n * d]``, where a projection wrote them."""
    if len(ref.shape) == 4:
        return ref[0, h]
    d = ref.shape[2] // n
    return _columns(ref, h * d, d)


def _store_heads(ref, x, back=None):
    """``x`` ``[n, rows, d]`` float32 into a block of ``n`` heads of either
    layout; through ``back`` (the queries' gradients over ``[1, rows, n *
    d]``: each head's rotation turned back) where given."""
    if len(ref.shape) == 4:
        ref[0] = x.astype(ref.dtype)
        return
    d = x.shape[2]
    for h in range(x.shape[0]):
        y = x[h] if back is None else back(x[h])
        ref[0, :, h * d:(h + 1) * d] = y.astype(ref.dtype)


class _Rotation:
    """Q and K of a grid step, rotated by their rows' positions where the
    kernel was handed the tables (``rot``: the resident ``[T, 2 * d]`` of
    ``half_turn_tables``; ``scratch``: the rotated queries ``[n, bq, d]`` and
    keys ``[kh, bk, d]``), as they lie otherwise.  A query block's heads are
    rotated ONCE, at the block's first step; the step's K/V heads each time
    their block is loaded (XLA's pass over K where it lies ran at a fifth of
    its bytes' floor: PERF.md 6.27); both rounded where ``rotary_embedding``
    rounded.  Gradients are turned back from their float32 sums."""

    def __init__(self, q_ref, k_ref, rot, scratch, n, kh, qi, ki, bq, bk):
        self.q_ref, self.k_ref, self.n, self.kh = q_ref, k_ref, n, kh
        self.at = (qi, bq), (ki, bk)
        self.on = bool(rot)
        if self.on:
            (self.rot_ref,), (self.q_s, self.k_s) = rot, scratch

    def _rows(self, block, size):
        return self.rot_ref[pl.ds(pl.multiple_of(block * size, size), size)]

    def _load(self, ref, into, heads, at):
        table = self._rows(*at)
        for h in range(heads):
            into[h] = _turned(_head(ref, h, heads).astype(jnp.float32),
                              table, 1.0, True).astype(into.dtype)

    def load_queries(self):
        if self.on:
            self._load(self.q_ref, self.q_s, self.n, self.at[0])

    def load_keys(self):
        """``_each_head``'s ``first`` as its keyword (a call without a
        rotation binds no such argument)."""
        return {"first": lambda: self._load(
            self.k_ref, self.k_s, self.kh, self.at[1])} if self.on else {}

    def query(self, h):
        return self.q_s[h] if self.on else _head(self.q_ref, h, self.n)

    def key(self, kv):
        return self.k_s[kv] if self.on else _head(self.k_ref, kv, self.kh)

    def back(self, block, size):
        """The turn back of a ``[size, d]`` float32 gradient of the rows of
        ``block``, or None."""
        return (lambda x: _turned(x, self._rows(block, size), -1.0,
                                  True)) if self.on else None


def _fwd_kernel(*refs, scale, causal, has_sel, kh, gh, bq, bk, nk, in_dtype,
                window=None, rotated=False):
    sel_ref, (q_ref, k_ref, v_ref, *rot), \
        (o_ref, lse_ref, m_s, l_s, acc_s, bias_s, *turned) = _split(
            refs, has_sel, 4 + rotated)
    qi, ki = pl.program_id(2), pl.program_id(3)
    src = _Rotation(q_ref, k_ref, rot, turned, kh * gh, kh, qi, ki, bq, bk)

    @pl.when(ki == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
        src.load_queries()

    def head(h, kv, bias):
        _softmax_pair(h, _scores(src.query(h), src.key(kv), scale, in_dtype),
                      bias, lambda: _head(v_ref, kv, kh), m_s, l_s, acc_s,
                      in_dtype)

    _each_head(head, sel_ref, bias_s, qi, ki, kh, gh, bq, bk, causal,
               window, **src.load_keys())

    @pl.when(ki == nk - 1)
    def _():
        out, lse = _softmax_rows(m_s, l_s, acc_s)
        _store_heads(o_ref, out())
        lse_ref[0] = lse()


def _probs(q, k, lse, bias, scale, in_dtype):
    s = _scores(q, k, scale, in_dtype)
    if bias is not None:
        s = s + bias[...]
    return jnp.exp(s - lse)                          # empty rows: lse = +BIG


def _dq_kernel(*refs, scale, causal, has_sel, kh, gh, bq, bk, nk, in_dtype,
               window=None):
    sel_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), \
        (dq_ref, acc_s, bias_s) = _split(refs, has_sel, 7)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def head(h, kv, bias):
        p = _probs(q_ref[0, h], k_ref[0, kv], lse_ref[0, h], bias, scale,
                   in_dtype)
        g = _dot(do_ref[0, h], v_ref[0, kv], ((1,), (1,)), in_dtype)
        ds = p * (g - delta_ref[0, h])
        acc_s[h] += _dot(ds, k_ref[0, kv], ((1,), (0,)), in_dtype)

    _each_head(head, sel_ref, bias_s, qi, ki, kh, gh, bq, bk, causal,
               window)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = (acc_s[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, has_sel, kh, gh, bq, bk, nq, nr,
                in_dtype, window=None):
    sel_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), \
        (dk_ref, dv_ref, dk_s, dv_s, bias_s) = _split(refs, has_sel, 7)
    ki, r = pl.program_id(2), pl.program_id(3)

    @pl.when(r == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def rows(kv):                  # K/V head kv's rows of dk_s and dv_s
        return pl.ds(kv * bk, bk)

    def head(h, kv, bias):
        q = q_ref[0, h]
        p = _probs(q, k_ref[0, kv], lse_ref[0, h], bias, scale, in_dtype)
        do = do_ref[0, h]
        dv_s[rows(kv)] += _dot(p, do, ((0,), (0,)), in_dtype)
        g = _dot(do, v_ref[0, kv], ((1,), (1,)), in_dtype)
        ds = p * (g - delta_ref[0, h])
        dk_s[rows(kv)] += _dot(ds, q.astype(jnp.float32) * scale,
                               ((0,), (0,)), in_dtype)

    _each_head(head, sel_ref, bias_s, _rem(r, nq), ki, kh, gh, bq, bk,
               causal, window)

    @pl.when(r == nr - 1)
    def _():
        for kv in range(kh):
            dk_ref[0, kv] = dk_s[rows(kv)].astype(dk_ref.dtype)
            dv_ref[0, kv] = dv_s[rows(kv)].astype(dv_ref.dtype)


def _grad_kernel(*refs, scale, causal, has_sel, kh, gh, parts, bq, bk, nq,
                 nk, in_dtype, window=None, rotated=False):
    """dQ, dK and dV of a block pair from ONE ``s``, ``p``, ``g`` and ``ds``
    (five products a pair).  dQ adds up across the key blocks in ``acc_s``;
    the step's K/V heads' float32 dK and dV stay in ``dk_s`` and ``dv_s``
    (K/V head ``kv``'s rows at ``kv * T``) across the query blocks, the key
    blocks and the ``parts`` head blocks of a group, a pair adding into its
    key block's rows."""
    sel_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rot), \
        (dq_ref, dk_ref, dv_ref, acc_s, dk_s, dv_s, bias_s, *turned) = _split(
            refs, has_sel, 7 + rotated)
    hi, qi, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    n = kh * gh
    src = _Rotation(q_ref, k_ref, rot, turned, n, kh, qi, ki, bq, bk)
    first, last = (qi == 0) & (ki == 0), (qi == nq - 1) & (ki == nk - 1)
    if parts > 1:
        first = first & (_rem(hi, parts) == 0)
        last = last & (_rem(hi, parts) == parts - 1)
    t = nk * bk

    def rows(kv, block):           # K/V head kv's key block of dk_s and dv_s
        return pl.ds(pl.multiple_of(kv * t + block * bk, bk), bk)

    def each_block(body):      # body(i) for every key block i, rolled
        def step(i, carry):
            body(i)
            return carry
        jax.lax.fori_loop(0, nk, step, 0)

    @pl.when(first)
    def _():
        def zero(i):
            for kv in range(kh):
                dk_s[rows(kv, i)] = jnp.zeros((bk, dk_s.shape[1]), jnp.float32)
                dv_s[rows(kv, i)] = jnp.zeros((bk, dv_s.shape[1]), jnp.float32)
        each_block(zero)

    @pl.when(ki == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
        src.load_queries()

    def head(h, kv, bias):
        q, k, do = src.query(h), src.key(kv), _head(do_ref, h, n)
        p = _probs(q, k, lse_ref[0, h], bias, scale, in_dtype)
        dv_s[rows(kv, ki)] += _dot(p, do, ((0,), (0,)), in_dtype)
        g = _dot(do, _head(v_ref, kv, kh), ((1,), (1,)), in_dtype)
        ds = (p * (g - delta_ref[0, h])).astype(in_dtype)
        acc_s[h] += _dot(ds, k, ((1,), (0,)), in_dtype)
        dk_s[rows(kv, ki)] += _dot(ds, q.astype(jnp.float32) * scale,
                                   ((0,), (0,)), in_dtype)

    _each_head(head, sel_ref, bias_s, qi, ki, kh, gh, bq, bk, causal,
               window, **src.load_keys())

    @pl.when(ki == nk - 1)
    def _():
        _store_heads(dq_ref, acc_s[...] * scale, src.back(qi, bq))

    @pl.when(last)
    def _():
        def write(i):
            block = pl.ds(pl.multiple_of(i * bk, bk), bk)
            for kv in range(kh):
                for ref, sums, back in ((dk_ref, dk_s, src.back(i, bk)),
                                        (dv_ref, dv_s, None)):
                    x = sums[rows(kv, i)]
                    x = (x if back is None else back(x)).astype(ref.dtype)
                    if len(ref.shape) == 4:
                        ref[0, kv, block] = x
                    else:
                        d = sums.shape[1]
                        ref[0, block, kv * d:(kv + 1) * d] = x
        each_block(write)


def _params(vmem, *inner):
    """The kernels' compiler parameters; ``inner`` the semantics of the
    grid's axes after the batch's, all ``parallel`` but the last by
    default."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) + (
            inner or ("parallel", "parallel", "arbitrary")),
        vmem_limit_bytes=vmem)


def _geometry(q, k, v, n_head=None):
    """(B, H, T, Dk, Dv, query heads a K/V head, bq, bk, query blocks, key
    blocks) of ``[B, H, T, D]`` operands or — ``n_head`` given — of the
    projections' ``[B, T, H * D]``."""
    if n_head is None:
        b, h, t, dk = q.shape
        hk, dv = k.shape[1], v.shape[3]
    else:
        b, t, h = q.shape[0], q.shape[1], n_head
        dk = q.shape[2] // h
        hk = k.shape[2] // dk
        dv = v.shape[2] // hk
    bq = bk = _pick_blocks(t)
    return b, h, t, dk, dv, h // hk, bq, bk, t // bq, t // bk


def step_heads(q, k, v, n_head=None):
    """(K/V heads, query heads of each) that one grid step of the forward
    serves for these operands."""
    b, h, t, dk, dv, g, bq, bk, nq, nk = _geometry(q, k, v, n_head)
    return _heads_per_step(g, h // g, bq, bk, dk, q.dtype.itemsize, dv)


def grad_step(q, k, v, n_head=None):
    """(body, (K/V heads, query heads of each) a grid step) of the backward
    for these operands: ``streamed_fused``, the one kernel, where a K/V
    head's float32 dK and dV fit the budget beside a step's blocks;
    ``streamed``, the dQ and dK/dV kernels with the forward's heads a step,
    where they do not."""
    b, h, t, dk, dv, g, bq, bk, nq, nk = _geometry(q, k, v, n_head)
    heads = _fused_heads_per_step(g, h // g, bq, bk, t, dk,
                                  q.dtype.itemsize, dv)
    if heads is None:
        return "streamed", step_heads(q, k, v, n_head)
    return "streamed_fused", heads


def _row_specs(g, kh, gh, bq, bk, causal, window=None, flat=False, t=None):
    """Block specs of a grid (batch, block of ``kh * gh`` query heads, query
    block, key block): (the heads' query-row blocks ``[kh * gh, bq, d]`` for
    a width ``d``, a ``[kh * gh, bq, 1]`` column of them, the ``d``-wide K/V
    blocks ``[kh, bk, d]`` of the heads' K/V heads, the selection's word
    tile, the K/V heads' whole one-buffered ``[kh, t, d]``).  ``flat``: the
    operands are the projections' ``[B, T, heads * d]`` and a step's heads a
    COLUMN block, ``[bq, kh * gh * d]`` / ``[bk, kh * d]`` / ``[t, kh * d]``
    (the column stays ``[B, H, T, 1]``'s).  Under ``causal`` the key index
    clamps to the last block the query block needs — and under ``window``
    to the first, from below — so a skipped step fetches nothing."""
    per_tile = KEYS_PER_TILE // bk

    def key_block(qi, ki):
        if not causal:
            return ki
        ki = jnp.minimum(ki, _div(qi * bq + bq - 1, bk))
        if window is None:
            return ki
        return jnp.maximum(ki, _div(jnp.maximum(qi * bq - window + 1, 0),
                                    bk))

    def q_map(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def kv_map(bi, hi, qi, ki):    # in blocks of kh; kh > 1 only if gh == g
        return (bi, _div(hi * gh, g), key_block(qi, ki), 0)

    def sel_map(bi, hi, qi, ki):
        return (bi, qi, _div(key_block(qi, ki), per_tile))

    def whole_map(bi, hi, qi, ki):
        return (bi, _div(hi * gh, g), 0, 0)

    def spec(heads, rows, index, **mode):
        # a block of ``heads`` heads' ``rows`` rows, by width
        if not flat:
            return lambda d: pl.BlockSpec((1, heads, rows, d), index, **mode)

        def columns(*at):
            bi, hi, ri, _ = index(*at)
            return (bi, ri, hi)
        return lambda d: pl.BlockSpec((1, rows, heads * d), columns, **mode)
    return (spec(kh * gh, bq, q_map),
            pl.BlockSpec((1, kh * gh, bq, 1), q_map),
            spec(kh, bk, kv_map),
            pl.BlockSpec((1, bq, LANES), sel_map),
            spec(kh, t, whole_map, pipeline_mode=pl.Buffered(1)))


def _given(*operands):
    """The operands that are there (``selected`` is None without a
    selection)."""
    return [x for x in operands if x is not None]


def _windowed(window):
    """A kernel's ``window`` argument where there is one (a kernel without
    is bound as it was before there was one)."""
    return {} if window is None else {"window": window}


def _rotating(rot, n, kh, bq, bk, dk, dtype):
    """What a kernel over the projections' layout gains where q and k are
    rotated (``rot`` the tables ``[T, 2 * dk]``, ``half_turn_tables``): (its
    keyword, the tables' spec — the whole array, resident, one buffer —, the
    rotated queries' and keys' scratch), each empty without."""
    if rot is None:
        return {}, [], []
    return ({"rotated": True},
            [pl.BlockSpec(rot.shape, lambda bi, hi, qi, ki: (0, 0),
                          pipeline_mode=pl.Buffered(1))],
            [pltpu.VMEM((n, bq, dk), dtype), pltpu.VMEM((kh, bk, dk), dtype)])


def _forward(selected, q, k, v, rot=None, *, heads, vmem, causal, scale,
             interpret, window=None, n_head=None):
    b, h, t, dk, dv, g, bq, bk, nq, nk = _geometry(q, k, v, n_head)
    kh, gh = heads
    flat = n_head is not None
    row, col, kv, sel, _ = _row_specs(g, kh, gh, bq, bk, causal, window,
                                      flat)
    has_sel = selected is not None
    n = kh * gh
    rotated, tables, queries = _rotating(rot, n, kh, bq, bk, dk, q.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          has_sel=has_sel, kh=kh, gh=gh, bq=bq, bk=bk, nk=nk,
                          in_dtype=q.dtype, **_windowed(window), **rotated),
        grid=(b, h // n, nq, nk),
        in_specs=([sel] if has_sel else []) + [row(dk), kv(dk), kv(dv)]
        + tables,
        out_specs=[row(dv), col],
        out_shape=[jax.ShapeDtypeStruct(
            (b, t, h * dv) if flat else (b, h, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, bq, LANES), jnp.float32),
                        pltpu.VMEM((n, bq, LANES), jnp.float32),
                        pltpu.VMEM((n, bq, dv), jnp.float32),
                        pltpu.VMEM((bq, bk), jnp.float32)] + queries,
        compiler_params=_params(vmem), interpret=interpret,
    )(*_given(selected, q, k, v, rot))


def _dq(selected, q, k, v, dout, lse, delta, *, heads, vmem, causal,
        scale, interpret, window=None):
    """dQ: grid (B, blocks of kh x gh heads, query blocks, key blocks)."""
    b, h, t, dk, dv, g, bq, bk, nq, nk = _geometry(q, k, v)
    kh, gh = heads
    n = kh * gh
    has_sel = selected is not None
    row, col, kv, sel, _ = _row_specs(g, kh, gh, bq, bk, causal, window)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          has_sel=has_sel, kh=kh, gh=gh, bq=bq, bk=bk, nk=nk,
                          in_dtype=q.dtype, **_windowed(window)),
        grid=(b, h // n, nq, nk),
        in_specs=([sel] if has_sel else [])
        + [row(dk), kv(dk), kv(dv), row(dv), col, col],
        out_specs=row(dk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((n, bq, dk), jnp.float32),
                        pltpu.VMEM((bq, bk), jnp.float32)],
        compiler_params=_params(vmem), interpret=interpret,
    )(*_given(selected, q, k, v, dout, lse, delta))


def _dkv(selected, q, k, v, dout, lse, delta, *, heads, vmem, causal,
         scale, interpret, window=None):
    """dK, dV: grid (B, blocks of kh K/V heads, key blocks, a group's blocks
    of gh heads x query blocks); kh > 1 only if gh == g."""
    b, h, t, dk, dv, g, bq, bk, nq, nk = _geometry(q, k, v)
    kh, gh = heads
    n = kh * gh
    per_tile = KEYS_PER_TILE // bk
    has_sel = selected is not None

    def first_q(ki):               # the first query block key block ki reaches
        return _div(ki * bk, bq)

    def clamp_q(ki, r):
        qi = _rem(r, nq)
        if not causal:
            return qi
        qi = jnp.maximum(qi, first_q(ki))
        if window is None:
            return qi
        # the last query block whose window reaches key block ki
        return jnp.minimum(qi, _div(ki * bk + bk + window - 2, bq))

    def q_map(bi, hk, ki, r):
        return (bi, hk * (g // gh) + _div(r, nq), clamp_q(ki, r), 0)

    def kv_map(bi, hk, ki, r):
        return (bi, hk, ki, 0)

    def sel_map(bi, hk, ki, r):
        return (bi, clamp_q(ki, r), _div(ki, per_tile))

    def row(d):
        return pl.BlockSpec((1, n, bq, d), q_map)

    def kv(d):
        return pl.BlockSpec((1, kh, bk, d), kv_map)
    col = pl.BlockSpec((1, n, bq, 1), q_map)
    nr = g // gh * nq
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          has_sel=has_sel, kh=kh, gh=gh, bq=bq, bk=bk, nq=nq,
                          nr=nr, in_dtype=q.dtype, **_windowed(window)),
        grid=(b, h // (g * kh), nk, nr),
        in_specs=([pl.BlockSpec((1, bq, LANES), sel_map)] if has_sel else [])
        + [row(dk), kv(dk), kv(dv), row(dv), col, col],
        out_specs=[kv(dk), kv(dv)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((kh * bk, dk), jnp.float32),
                        pltpu.VMEM((kh * bk, dv), jnp.float32),
                        pltpu.VMEM((bq, bk), jnp.float32)],
        compiler_params=_params(vmem), interpret=interpret,
    )(*_given(selected, q, k, v, dout, lse, delta))


def _grad(selected, q, k, v, dout, lse, delta, rot=None, *, heads, vmem,
          causal, scale, interpret, window=None, n_head=None):
    """dQ, dK, dV by the fused kernel, on the forward's grid: (B, blocks of
    kh x gh heads, query blocks, key blocks).  The dK and dV blocks are a
    K/V head's whole ``[T, d]``, their index a function of the batch and
    the head block alone, so they stay in VMEM — one buffer, written back
    once — while the two inner axes (and a group's head blocks, where gh <
    g) run, none of which is ``parallel`` therefore."""
    b, h, t, dk, dv, g, bq, bk, nq, nk = _geometry(q, k, v, n_head)
    kh, gh = heads
    n = kh * gh
    has_sel = selected is not None
    row, col, kv, sel, whole = _row_specs(g, kh, gh, bq, bk, causal, window,
                                          n_head is not None, t)
    rotated, tables, queries = _rotating(rot, n, kh, bq, bk, dk, q.dtype)
    return pl.pallas_call(
        functools.partial(_grad_kernel, scale=scale, causal=causal,
                          has_sel=has_sel, kh=kh, gh=gh, parts=g // gh, bq=bq,
                          bk=bk, nq=nq, nk=nk, in_dtype=q.dtype,
                          **_windowed(window), **rotated),
        grid=(b, h // n, nq, nk),
        in_specs=([sel] if has_sel else [])
        + [row(dk), kv(dk), kv(dv), row(dv), col, col] + tables,
        out_specs=[row(dk), whole(dk), whole(dv)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((n, bq, dk), jnp.float32),
                        pltpu.VMEM((kh * t, dk), jnp.float32),
                        pltpu.VMEM((kh * t, dv), jnp.float32),
                        pltpu.VMEM((bq, bk), jnp.float32)] + queries,
        compiler_params=_params(
            vmem, "parallel" if gh == g else "arbitrary", "arbitrary",
            "arbitrary"),
        interpret=interpret,
    )(*_given(selected, q, k, v, dout, lse, delta, rot))


# ---------------------------------------------------------------------------
# Latent attention over the projections' own layout
# ---------------------------------------------------------------------------
#
# Q ``[B, T, H * (nope + rope)]`` as the query projection writes it, a head's
# columns ``[q_nope | q_rope]``; KV ``[B, T, H * (nope + dv)]`` as the
# key/value projection writes it, a head's columns ``[k_nope | v]``; the ONE
# key part every head reads, ``[B, T, rope]``, an operand of its own.  A grid
# step's heads are a COLUMN block — ``[1, bq, kh * (nope + rope)]``, ``[1,
# bk, kh * (nope + dv)]`` — and the shared block is fetched once for all of
# them, so nothing is split, joined, broadcast or transposed between a
# projection and the kernel.  ``nope`` and ``dv`` are whole lane tiles, so a
# head's keys and values start on a tile of the block (a dynamic index in
# whole tiles: the head loop stays rolled); ``rope`` is whole tiles too, or
# ONE half-tile: then a head is 1.5 tiles more than its neighbour, heads come
# in pairs, and the odd head's ``nope`` columns straddle tiles.  That shift —
# and the rotation of ``q_rope`` by its position, and the scale — is done
# once a QUERY block, into VMEM scratch the key blocks' loop reads (``qn_s``
# a head's scaled ``nope`` columns, ``qr_s`` its rotated ``rope`` columns in
# a whole lane tile, zero outside them), never once a key block.  The score is
# two products, ``qn k_nope^T + qr kr^T``, summed in float32: the MXU passes
# of the joined 192-deep product.  A half-tile ``rope`` never leaves its
# lanes: the shared block and the tables come with their 64 columns TWICE
# across a tile (``_twice``), an even head's ``qr`` lives in the lower half
# and an odd head's in the upper, and either picks its own copy in the
# product — so ``ds kr`` lands in both halves alike, and ``ds^T qr`` lands in
# the half of the head's parity: the shared part's gradient is a ``[T, 128]``
# float32 block, RESIDENT across the head blocks as well (all but the batch
# axis ``arbitrary``), whose halves XLA adds.  The rotation turns neighbours
# ``(x[2i], x[2i + 1])`` (``rotary_embedding``'s interleaved form): the
# partner by two lane rolls and a parity select, ``x cos + partner ssin`` with
# ``ssin`` the sine signed by parity; dQ's rotary columns turn back by ``x
# cos - partner ssin`` where dQ is written.

_HALF = LANES // 2


def in_place_supported(q_shape, kv_shape, rope, n_head, v_dim, has_klen, rate):
    """Whether the kernels take the projections' layout: whole 128-key
    slabs, ``nope`` and ``v_dim`` whole lane tiles, ``rope`` whole tiles or
    one half-tile (then an even number of heads), no dropout and no padding
    mask."""
    if len(q_shape) != 3 or len(kv_shape) != 3 or has_klen or rate:
        return False
    b, t, w = q_shape
    if tuple(kv_shape[:2]) != (b, t) or w % n_head or kv_shape[2] % n_head:
        return False
    nope = kv_shape[2] // n_head - v_dim
    if nope < LANES or nope % LANES or v_dim % LANES \
            or w // n_head != nope + rope:
        return False
    if rope % LANES and (rope != _HALF or n_head % 2):
        return False
    return rope > 0 and _pick_blocks(t) is not None


def _twice(x):
    """A half-tile's columns twice across a lane tile; whole tiles as they
    are."""
    return jnp.concatenate([x, x], -1) if x.shape[-1] == _HALF else x


def rotation_tables(t, rope, theta):
    """``[t, 2 * lanes(rope)]`` float32: the cosines of positions 0..t-1 for
    neighbouring pairs, then the sines signed by parity (``-sin`` on the
    even lane of a pair), each ``_twice``."""
    from ..activation import rotary_tables

    cos, sin = (jnp.repeat(x[:, :rope // 2], 2, -1)
                for x in rotary_tables(t, rope, theta))
    sin = sin * jnp.where(jnp.arange(rope) % 2 == 0, -1.0, 1.0)
    return jnp.concatenate([_twice(cos), _twice(sin)], -1)


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _lower_half(x):
    return _lane(x.shape) < _HALF


def _turned(x, rot_ref, sign, halves=False):
    """``x`` ``[bq, r]`` float32, every pair of neighbouring lanes rotated
    by its row's position (``sign`` 1.0) or back (-1.0), a lane tile at a
    time; ``halves``: the pairs are lane ``i`` and ``i + 64`` of the ONE tile
    (``rotary_embedding``'s rotate-half form of a 128-wide head), the
    partner one roll."""
    r = x.shape[1]

    def tile(i):
        lanes = slice(i * LANES, (i + 1) * LANES)
        y = x[:, lanes]
        partner = pltpu.roll(y, _HALF, 1) if halves else jnp.where(
            (_lane(y.shape) & 1) == 0,
            pltpu.roll(y, LANES - 1, 1), pltpu.roll(y, 1, 1))
        return y * rot_ref[:, lanes] + partner * (
            sign * rot_ref[:, r + i * LANES:r + (i + 1) * LANES])
    return jnp.concatenate([tile(i) for i in range(r // LANES)], 1)


class _Projections:
    """A grid step's ``n`` heads where the projections wrote them."""

    def __init__(self, q_ref, kv_ref, kr_ref, rot_ref, qn_s, qr_s, nope,
                 rope, dv, n, scale, in_dtype):
        self.q_ref, self.kv_ref, self.kr_ref = q_ref, kv_ref, kr_ref
        self.rot_ref, self.qn_s, self.qr_s = rot_ref, qn_s, qr_s
        self.nope, self.rope, self.dv, self.n = nope, rope, dv, n
        self.scale, self.in_dtype = scale, in_dtype

    def _tile(self, ref, i):
        return ref[0, :, i * LANES:(i + 1) * LANES].astype(jnp.float32)

    def _query(self, h):
        """Head ``h``'s (static) ``nope`` columns, the lane tiles that hold
        its ``rope`` columns, and which lanes of them are its own (None:
        all), float32."""
        nope, w = self.nope, self.nope + self.rope
        if w % LANES == 0:
            x = self.q_ref[0, :, h * w:(h + 1) * w].astype(jnp.float32)
            return x[:, :nope], x[:, nope:], None
        tiles, first = nope // LANES, h // 2 * (2 * w // LANES)
        if h % 2 == 0:
            own = self.q_ref[0, :, first * LANES:(first + tiles) * LANES]
            last = self._tile(self.q_ref, first + tiles)
            return own.astype(jnp.float32), last, _lower_half(last)
        # the odd head starts in the upper half of the even head's last tile
        swapped = [pltpu.roll(self._tile(self.q_ref, first + tiles + j),
                              _HALF, 1) for j in range(tiles + 1)]
        own = [jnp.where(_lower_half(a), a, b)
               for a, b in zip(swapped, swapped[1:])]
        last = self._tile(self.q_ref, first + 2 * tiles)
        return (jnp.concatenate(own, 1), last,
                jnp.logical_not(_lower_half(last)))

    def load_queries(self):
        """Once a query block: every head's scaled ``nope`` columns and its
        rotated, scaled ``rope`` columns, each rounded where the Fluid ops
        this replaces rounded it (the rotation's result, then the scaled
        operand of the product)."""
        def scaled(x):
            return (x * self.scale).astype(self.in_dtype)
        for h in range(self.n):
            own, last, mine = self._query(h)
            if self.rot_ref is not None:
                last = _turned(last, self.rot_ref, 1.0).astype(
                    self.in_dtype).astype(jnp.float32)
            if mine is not None:
                last = jnp.where(mine, last, 0.0)
            self.qn_s[h], self.qr_s[h] = scaled(own), scaled(last)

    def columns(self, ref, h, width, start=0):
        """Head ``h``'s ``width`` columns at ``start`` of its part of a
        block whose heads are ``ref.shape[2] / n`` wide: whole lane tiles,
        ``h`` the head loop's own index."""
        return _columns(ref, h * (ref.shape[2] // self.n) + start, width)

    def keys(self, kv):
        return self.columns(self.kv_ref, kv, self.nope)

    def values(self, kv):
        return self.columns(self.kv_ref, kv, self.dv, self.nope)

    def scores(self, h, kv):
        return _dot(self.qn_s[h], self.keys(kv), ((1,), (1,)),
                    self.in_dtype) + _dot(
            self.qr_s[h], self.kr_ref[0], ((1,), (1,)), self.in_dtype)

    def store_queries_gradient(self, dq_ref, acc_s, accr_s):
        """dQ's block from the heads' float32 sums: the ``rope`` columns
        turned back, the odd heads' columns shifted to where they lie."""
        def rope_part(h):
            x = accr_s[h] * self.scale
            if self.rot_ref is not None:
                x = _turned(x, self.rot_ref, -1.0)
            return x
        nope, w = self.nope, self.nope + self.rope
        tiles = nope // LANES
        for h in range(self.n):
            own = acc_s[h] * self.scale
            if w % LANES == 0:
                dq_ref[0, :, h * w:(h + 1) * w] = jnp.concatenate(
                    [own, rope_part(h)], 1).astype(dq_ref.dtype)
                continue
            if h % 2 == 0:
                even, even_rope = own, rope_part(h)
                continue
            # the pair's 2 * tiles + 1 lane tiles: the even head's, its rope
            # columns beside the odd head's first half-tile, the odd head's
            # columns a half-tile on, its rope columns in the last upper half
            swapped = [pltpu.roll(x, _HALF, 1) for x in _lane_tiles(own)]
            joints = [even_rope] + swapped + [rope_part(h)]
            first = h // 2 * (2 * w // LANES) * LANES
            dq_ref[0, :, first:first + 2 * w] = jnp.concatenate(
                [even] + [jnp.where(_lower_half(a), a, b)
                          for a, b in zip(joints, joints[1:])],
                1).astype(dq_ref.dtype)


def _in_place(refs, n_in, rotated, **sizes):
    """(``_Projections`` of a kernel's ``refs`` — Q, KV, the shared key
    block and, where the queries are ``rotated``, the tables first; the two
    query scratches last —, its other inputs, its outputs and scratch)."""
    q_ref, kv_ref, kr_ref = refs[:3]
    rot_ref = refs[3] if rotated else None
    first = 4 if rotated else 3
    return (_Projections(q_ref, kv_ref, kr_ref, rot_ref, *refs[-2:], **sizes),
            refs[first:n_in], refs[n_in:-2])


def _fwd_kernel_in_place(*refs, scale, causal, rotated, n, nope, rope, dv,
                         bq, bk, nk, in_dtype):
    src, _, (o_ref, lse_ref, m_s, l_s, acc_s, bias_s) = _in_place(
        refs, 4 if rotated else 3, rotated, nope=nope, rope=rope, dv=dv,
        n=n, scale=scale, in_dtype=in_dtype)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
        src.load_queries()

    def head(h, kv, bias):
        _softmax_pair(h, src.scores(h, kv), bias, lambda: src.values(kv),
                      m_s, l_s, acc_s, in_dtype)

    _each_head(head, None, bias_s, qi, ki, n, 1, bq, bk, causal)

    @pl.when(ki == nk - 1)
    def _():
        out, lse = _softmax_rows(m_s, l_s, acc_s)
        _store_heads(o_ref, out())
        lse_ref[0] = lse()


def _grad_kernel_in_place(*refs, scale, causal, rotated, n, nope, rope, dv,
                          bq, bk, nq, nk, in_dtype):
    """``_grad_kernel`` over the projections' layout: a head's float32 dK
    and dV are ONE resident ``[T, nope + dv]`` block (``dkv_s``), written
    back as the key/value projection's gradient; the shared key part's
    gradient adds up over ALL heads in its float32 output block, resident
    across the head blocks too."""
    src, (do_ref, lse_ref, delta_ref), \
        (dq_ref, dkv_ref, dkr_ref, acc_s, accr_s, dkv_s, bias_s) = _in_place(
            refs, 7 if rotated else 6, rotated, nope=nope, rope=rope, dv=dv,
            n=n, scale=scale, in_dtype=in_dtype)
    hi, qi, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first, last = (qi == 0) & (ki == 0), (qi == nq - 1) & (ki == nk - 1)
    t = nk * bk

    def rows(kv, block):           # head kv's key block of dkv_s
        return pl.ds(pl.multiple_of(kv * t + block * bk, bk), bk)

    def shared(block):             # a key block of dkr_ref
        return pl.ds(pl.multiple_of(block * bk, bk), bk)

    def each_block(body):
        def step(i, carry):
            body(i)
            return carry
        jax.lax.fori_loop(0, nk, step, 0)

    @pl.when(first)
    def _():
        def zero(i):
            for kv in range(n):
                dkv_s[rows(kv, i)] = jnp.zeros((bk, nope + dv), jnp.float32)
        each_block(zero)

    @pl.when(first & (hi == 0))
    def _():
        def zero(i):
            dkr_ref[0, shared(i)] = jnp.zeros((bk, dkr_ref.shape[2]),
                                              jnp.float32)
        each_block(zero)

    @pl.when(ki == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
        accr_s[...] = jnp.zeros(accr_s.shape, jnp.float32)
        src.load_queries()

    def head(h, kv, bias):
        qn, qr, k, kr = src.qn_s[h], src.qr_s[h], src.keys(kv), src.kr_ref[0]
        do = src.columns(do_ref, h, dv)
        s = src.scores(h, kv)
        if bias is not None:
            s = s + bias[...]
        p = jnp.exp(s - lse_ref[0, h])               # empty rows: lse = +BIG
        dkv_s[rows(kv, ki), nope:] += _dot(p, do, ((0,), (0,)), in_dtype)
        g = _dot(do, src.values(kv), ((1,), (1,)), in_dtype)
        ds = (p * (g - delta_ref[0, h])).astype(in_dtype)
        acc_s[h] += _dot(ds, k, ((1,), (0,)), in_dtype)
        accr_s[h] += _dot(ds, kr, ((1,), (0,)), in_dtype)
        dkv_s[rows(kv, ki), :nope] += _dot(ds, qn, ((0,), (0,)), in_dtype)
        dkr_ref[0, shared(ki)] += _dot(ds, qr, ((0,), (0,)), in_dtype)

    _each_head(head, None, bias_s, qi, ki, n, 1, bq, bk, causal)

    @pl.when(ki == nk - 1)
    def _():
        src.store_queries_gradient(dq_ref, acc_s, accr_s)

    @pl.when(last)
    def _():
        def write(i):
            block = shared(i)
            for kv in range(n):
                dkv_ref[0, block, kv * (nope + dv):(kv + 1) * (nope + dv)] = \
                    dkv_s[rows(kv, i)].astype(dkv_ref.dtype)
        each_block(write)


def _in_place_geometry(q, kv, n_head, v_dim):
    """(B, T, nope, rope, dv, bq, bk, query blocks, key blocks)."""
    b, t, w = q.shape
    nope = kv.shape[2] // n_head - v_dim
    bq = bk = _pick_blocks(t)
    return b, t, nope, w // n_head - nope, v_dim, bq, bk, t // bq, t // bk


def _in_place_bytes(n, bq, bk, t, nope, rope, dv, itemsize, grad):
    """VMEM bytes of a grid step of ``n`` heads over the projections'
    layout, forward or (``grad``) the fused backward: the column blocks of
    Q, KV and O (dO; dQ), the shared key block and the tables, a head's two
    columns and float32 sums, the queries' scratch, and — backward —
    RESIDENT, a head's whole float32 dK and dV ``[t, nope + dv]`` with the
    one-buffered output block they are cast into and the shared part's
    float32 ``[t, lanes(rope)]``."""
    r = _lanes(rope)
    column = bq * LANES * 4
    shared = 2 * bk * r * itemsize + 2 * bq * 2 * r * 4 + bq * bk * 4
    blocks = 2 * (bq * (nope + rope) + bk * (nope + dv) + bq * dv) * itemsize
    queries = bq * (nope + r) * itemsize
    if not grad:
        state = 4 * column + bq * dv * 4         # lse's block, m, l; acc
        return n * (blocks + queries + state) + shared + 8 * bq * bk * 4
    sums = 4 * column + bq * (nope + r) * 4      # lse, delta; dQ's sums
    resident = t * (nope + dv) * (4 + itemsize)
    return n * (blocks + 2 * bq * (nope + rope) * itemsize + queries + sums
                + resident) + shared + t * r * 4 + 2 * bq * bk * 4


def _in_place_heads(n_head, rope, fits):
    """The most heads a step by ``fits(n)`` (an even number where a head's
    ``rope`` columns are a half-tile); None where the fewest do not fit."""
    return max((m for m in range(1, n_head + 1)
                if n_head % m == 0 and not (rope % LANES and m % 2)
                and fits(m)), default=None)


def _in_place_heads_per_step(q, kv, n_head, v_dim):
    """Heads a grid step of the forward serves: the most that fit the
    budget, the fewest there are where none does."""
    b, t, nope, rope, dv, bq, bk, nq, nk = _in_place_geometry(
        q, kv, n_head, v_dim)
    fewest = 2 if rope % LANES else 1
    return _in_place_heads(n_head, rope, lambda n: n == fewest
                           or _in_place_bytes(n, bq, bk, t, nope, rope, dv,
                                              q.dtype.itemsize, False)
                           <= _VMEM_BUDGET)


def _in_place_fused_heads_per_step(q, kv, n_head, v_dim):
    """The same for the fused backward; None where the fewest heads'
    resident gradients do not fit."""
    b, t, nope, rope, dv, bq, bk, nq, nk = _in_place_geometry(
        q, kv, n_head, v_dim)
    return _in_place_heads(n_head, rope, lambda n: _in_place_bytes(
        n, bq, bk, t, nope, rope, dv, q.dtype.itemsize, True) <= _VMEM_BUDGET)


def _in_place_specs(n, bq, bk, rope, causal):
    """Block specs of the grid (batch, block of ``n`` heads, query block,
    key block) over ``[B, T, heads * width]`` arrays: (a query-row block of
    the step's heads at ``width`` columns a head, their ``[n, bq, 1]``
    column, the key rows' block likewise, the shared key block, the
    tables' block).  Under ``causal`` the key index clamps to the last
    block the query block needs, so a skipped step fetches nothing."""
    def key_block(qi, ki):
        return jnp.minimum(ki, _div(qi * bq + bq - 1, bk)) if causal else ki
    r = _lanes(rope)
    return (lambda width: pl.BlockSpec(
                (1, bq, n * width), lambda bi, hi, qi, ki: (bi, qi, hi)),
            pl.BlockSpec((1, n, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            lambda width: pl.BlockSpec(
                (1, bk, n * width),
                lambda bi, hi, qi, ki: (bi, key_block(qi, ki), hi)),
            pl.BlockSpec((1, bk, r),
                         lambda bi, hi, qi, ki: (bi, key_block(qi, ki), 0)),
            pl.BlockSpec((bq, 2 * r), lambda bi, hi, qi, ki: (qi, 0)))


def _forward_in_place(q, kv, kr, rot, *, n_head, v_dim, heads, vmem, causal,
                      scale, interpret):
    b, t, nope, rope, dv, bq, bk, nq, nk = _in_place_geometry(
        q, kv, n_head, v_dim)
    n, r = heads, _lanes(rope)
    row, col, keys, shared, tables = _in_place_specs(n, bq, bk, rope, causal)
    rotated = rot is not None
    return pl.pallas_call(
        functools.partial(_fwd_kernel_in_place, scale=scale, causal=causal,
                          rotated=rotated, n=n, nope=nope, rope=rope, dv=dv,
                          bq=bq, bk=bk, nk=nk, in_dtype=q.dtype),
        grid=(b, n_head // n, nq, nk),
        in_specs=[row(nope + rope), keys(nope + dv), shared]
        + ([tables] if rotated else []),
        out_specs=[row(dv), col],
        out_shape=[jax.ShapeDtypeStruct((b, t, n_head * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, n_head, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, bq, LANES), jnp.float32),
                        pltpu.VMEM((n, bq, LANES), jnp.float32),
                        pltpu.VMEM((n, bq, dv), jnp.float32),
                        pltpu.VMEM((bq, bk), jnp.float32),
                        pltpu.VMEM((n, bq, nope), q.dtype),
                        pltpu.VMEM((n, bq, r), q.dtype)],
        compiler_params=_params(vmem), interpret=interpret,
    )(*_given(q, kv, kr, rot))


def _grad_in_place(q, kv, kr, rot, dout, lse, delta, *, n_head, v_dim, heads,
                   vmem, causal, scale, interpret):
    """dQ, dKV and the shared key part's float32 gradient ``[B, T,
    lanes(rope)]`` by the fused kernel.  dKV's block is the step's heads'
    whole ``[T, n * (nope + dv)]``, resident while the two inner axes run;
    the shared part's is resident across the head blocks too, so only the
    batch axis is ``parallel``."""
    b, t, nope, rope, dv, bq, bk, nq, nk = _in_place_geometry(
        q, kv, n_head, v_dim)
    n, r = heads, _lanes(rope)
    row, col, keys, shared, tables = _in_place_specs(n, bq, bk, rope, causal)
    rotated = rot is not None
    return pl.pallas_call(
        functools.partial(_grad_kernel_in_place, scale=scale, causal=causal,
                          rotated=rotated, n=n, nope=nope, rope=rope, dv=dv,
                          bq=bq, bk=bk, nq=nq, nk=nk, in_dtype=q.dtype),
        grid=(b, n_head // n, nq, nk),
        in_specs=[row(nope + rope), keys(nope + dv), shared]
        + ([tables] if rotated else []) + [row(dv), col, col],
        out_specs=[row(nope + rope),
                   pl.BlockSpec((1, t, n * (nope + dv)),
                                lambda bi, hi, qi, ki: (bi, 0, hi),
                                pipeline_mode=pl.Buffered(1)),
                   pl.BlockSpec((1, t, r), lambda bi, hi, qi, ki: (bi, 0, 0),
                                pipeline_mode=pl.Buffered(1))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(kv.shape, kv.dtype),
                   jax.ShapeDtypeStruct((b, t, r), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, bq, nope), jnp.float32),
                        pltpu.VMEM((n, bq, r), jnp.float32),
                        pltpu.VMEM((n * t, nope + dv), jnp.float32),
                        pltpu.VMEM((bq, bk), jnp.float32),
                        pltpu.VMEM((n, bq, nope), q.dtype),
                        pltpu.VMEM((n, bq, r), q.dtype)],
        compiler_params=_params(vmem, "arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(*_given(q, kv, kr, rot, dout, lse, delta))


_run = functools.partial(run_traced, "streamed_attention")


def _statics(q, k, v, causal, scale, interpret, window=None, heads=None,
             n_head=None):
    """A signature's statics (``heads`` the forward's unless given);
    ``window`` and ``n_head`` are among them only where a call has one, so
    that a call without keeps the signature — and the one trace — it had."""
    if window is not None and (not causal or int(window) < 1):
        raise ValueError("a window (%r) is of causal attention, at least "
                         "one key wide" % (window,))
    if scale is None:
        scale = _geometry(q, k, v, n_head)[3] ** -0.5
    return dict(heads=heads or step_heads(q, k, v, n_head),
                vmem=_VMEM_BUDGET, causal=bool(causal), scale=float(scale),
                interpret=bool(interpret),
                **_windowed(None if window is None else int(window)),
                **({} if n_head is None else {"n_head": int(n_head)}))


def half_turn_tables(t, d, theta, freq_scaling=None, scale=1.0):
    """``[t, 2 * d]`` float32: ``rotary_embedding``'s cosines of positions
    0..t-1 in its rotate-half form, then its sines signed by half (``-sin``
    on the lower half of a head, whose partner is subtracted)."""
    from ..activation import rotary_tables

    cos, sin = rotary_tables(t, d, theta, freq_scaling, scale)
    return jnp.concatenate(
        [cos, sin * jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0)], -1)


def forward(q, k, v, selected, causal=False, scale=None, interpret=False,
            window=None, n_head=None, rot=None):
    """q ``[B, H, T, Dk]``; k ``[B, H / g, T, Dk]``, v ``[B, H / g, T,
    Dv]``; ``selected`` the packed key mask ``[B, T, W]`` int32 or None;
    ``window`` (with ``causal``) the keys a query reads back from its own.
    Returns the output ``[B, H, T, Dv]`` in q's dtype and the rows'
    log-sum-exp ``[B, H, T, 1]`` float32, which ``backward`` wants back.
    ``n_head`` given: the operands are grouped heads where the projections
    wrote them — q ``[B, T, H * Dk]``, k ``[B, T, H / g * Dk]``, v ``[B, T,
    H / g * Dv]``, the output ``[B, T, H * Dv]`` — and ``rot``
    (``half_turn_tables``; 128-wide heads) rotates every head of q and k by
    its rows' positions inside the kernel."""
    out, lse = _run(_forward, (selected, q, k, v, rot), **_statics(
        q, k, v, causal, scale, interpret, window, n_head=n_head))
    return out, lse


def backward(q, k, v, selected, out, lse, dout, causal=False, scale=None,
             interpret=False, window=None, n_head=None, rot=None):
    """(dQ, dK, dV) from the forward's operands and results, in the
    operands' layout (under ``rot``: of the unrotated q and k)."""
    dout = dout.astype(q.dtype)
    delta = dout.astype(jnp.float32) * out.astype(jnp.float32)
    if n_head is None:
        delta = jnp.sum(delta, -1, keepdims=True)
    else:
        # each head's columns added up by a product with the heads'
        # indicator (exact in float32 at the highest precision): a view as
        # [B, T, H, Dv] is a 128 MB relayout here
        member = (jnp.arange(delta.shape[2])[:, None]
                  // (delta.shape[2] // n_head)
                  == jnp.arange(n_head)[None, :]).astype(jnp.float32)
        delta = jnp.matmul(delta, member, precision=jax.lax.Precision.HIGHEST
                           ).transpose(0, 2, 1)[..., None]
    operands = (selected, q, k, v, dout, lse, delta, rot)
    body, heads = grad_step(q, k, v, n_head)
    statics = _statics(q, k, v, causal, scale, interpret, window, heads,
                       n_head)
    if body == "streamed_fused":
        return tuple(_run(_grad, operands, **statics))
    operands = operands[:-1]
    (dq,) = _run(_dq, operands, **statics)
    dk, dv = _run(_dkv, operands, **statics)
    return dq, dk, dv


def in_place_step(q, kv, n_head, v_dim):
    """(heads a grid step of the forward serves over the projections'
    layout, heads a step of the fused backward or None where the fewest
    heads' resident gradients do not fit the budget)."""
    return (_in_place_heads_per_step(q, kv, n_head, v_dim),
            _in_place_fused_heads_per_step(q, kv, n_head, v_dim))


def _in_place_operands(q, kv, k_shared, n_head, v_dim, rope_theta, heads,
                       causal, scale, interpret):
    """(the kernels' first four operands — the shared part ``_twice``, the
    rotation's tables or None —, a signature's statics)."""
    rot = None if rope_theta is None else rotation_tables(
        q.shape[1], k_shared.shape[2], float(rope_theta))
    if scale is None:
        scale = (q.shape[2] // n_head) ** -0.5
    return (q, kv, _twice(k_shared), rot), dict(
        n_head=int(n_head), v_dim=int(v_dim), heads=heads, vmem=_VMEM_BUDGET,
        causal=bool(causal), scale=float(scale), interpret=bool(interpret))


def forward_in_place(q, kv, k_shared, n_head, v_dim, rope_theta=None,
                     causal=False, scale=None, interpret=False):
    """q ``[B, T, H * (nope + rope)]``, kv ``[B, T, H * (nope + dv)]`` (a
    head's columns ``[k_nope | v]``), ``k_shared`` ``[B, T, rope]`` the key
    part every head reads, already rotated; ``rope_theta`` the base the
    queries' ``rope`` columns are rotated by, None for none.  Returns the
    output ``[B, T, H * dv]`` and the rows' log-sum-exp ``[B, H, T, 1]``
    float32."""
    operands, statics = _in_place_operands(
        q, kv, k_shared, n_head, v_dim, rope_theta,
        _in_place_heads_per_step(q, kv, n_head, v_dim), causal, scale,
        interpret)
    out, lse = _run(_forward_in_place, operands, **statics)
    return out, lse


def backward_in_place(q, kv, k_shared, n_head, v_dim, out, lse, dout,
                      rope_theta=None, causal=False, scale=None,
                      interpret=False):
    """(dQ, dKV, the gradient of the ROTATED shared key part ``[B, T,
    rope]`` in float32, all heads' sum) from ``forward_in_place``'s operands
    and results."""
    b, t, _ = q.shape
    dout = dout.astype(q.dtype)
    delta = jnp.sum((dout.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(b, t, n_head, v_dim), -1)
    operands, statics = _in_place_operands(
        q, kv, k_shared, n_head, v_dim, rope_theta,
        _in_place_fused_heads_per_step(q, kv, n_head, v_dim), causal, scale,
        interpret)
    dq, dkv, dkr = _run(
        _grad_in_place,
        operands + (dout, lse, delta.transpose(0, 2, 1)[..., None]),
        **statics)
    if k_shared.shape[2] == _HALF:
        # the even heads' sum and the odd heads'
        dkr = dkr[..., :_HALF] + dkr[..., _HALF:]
    return dq, dkv, dkr


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def streamed_attention(q, k, v, selected, causal=False, scale=None,
                       interpret=False, window=None):
    """``forward``'s output alone, differentiable (``jax.grad`` runs
    ``backward`` on the saved log-sum-exp)."""
    return forward(q, k, v, selected, causal, scale, interpret, window)[0]


def _fwd_rule(q, k, v, selected, causal, scale, interpret, window):
    out, lse = forward(q, k, v, selected, causal, scale, interpret, window)
    return out, (q, k, v, selected, out, lse)


def _bwd_rule(causal, scale, interpret, window, res, dout):
    q, k, v, selected, out, lse = res
    return backward(q, k, v, selected, out, lse, dout, causal, scale,
                    interpret, window) + (None,)


streamed_attention.defvjp(_fwd_rule, _bwd_rule)
