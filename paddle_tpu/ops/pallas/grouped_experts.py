"""The routed experts' products over the live tiles of ``moe_dispatch``'s
layout, as grouped Pallas kernels.

The layout (``ops/moe.py``): token-expert pairs sorted by expert, every
expert's group padded to whole tiles of ``tile`` rows, so a tile belongs to
ONE expert; ``TileExpert`` names it, ``NumTiles`` (a device scalar) says how
many tiles are live.  ``ops/moe.py``'s XLA body walks the live tiles in a
``fori_loop`` of per-tile operations — two slices of the layout, a row
gather, three ``[D, F]`` matrix slices, the products, a scattered add of 640
rows (most of a forward tile's time on a v5e: XLA's scatter takes ~140 ns a
row), and in the backward three read-modify-writes of an expert's float32
gradients.  Here:

* **A tile's expert is picked in the weights' index maps** from
  ``TileExpert``, handed over by scalar prefetch: no matrix slice is ever
  made, and the next tile's matrices stream in under this tile's products.
* **Rows are gathered in bulk**, a CHUNK of tiles at a time: one XLA gather
  of the chunk's rows before the kernels.  The chunks are walked by a
  ``fori_loop`` whose trip count is ``ceil(NumTiles / chunk)`` on the
  device, so the work still follows the pairs that exist and any number of
  live tiles up to the capacity is computed; the gathered rows are a
  chunk's, never the capacity's.  (Row DMAs inside the kernel are not to be
  had: Mosaic refuses a one-row slice of a ``[N, D]`` array in HBM's ``(8,
  128)`` / ``(16, 128)`` tiling, and the ``[N, 1, D]`` view it would take
  pads every row to a whole tile.)
* **Results are combined in VMEM, not by a scattered add**: the kernels
  whose result goes back to the tokens' rows (``_down_combine``,
  ``_dx_combine``) walk blocks of ``D`` OUTERMOST and keep ``[N, D-block]``
  float32 of the result resident across all tiles of the chunk; a tile's
  product lands in scratch and each of its rows is added to its token's row
  there (a dynamic one-row read-modify-write a row, ~10 ns).  A padding
  row's routing weight is zero, so it adds nothing wherever it points.
* **Dead tiles of a chunk cost a skipped grid step and no fetch**: every
  index map clamps to the chunk's last live tile, and Pallas skips a fetch
  whose block index did not change.

Five kernels, each traced and lowered once a step program
(``pallas.run_traced``), all under the calling op's own ``fluid[..]`` scope;
the forward takes the first two, the backward the other three:

* ``_gate_up`` — grid (tiles, blocks of ``F``): ``a = silu(x Wg) * (x Wu)``
  in the products' dtype, a chunk's ``[rows, F]``.
* ``_down_combine`` — grid (blocks of ``D``, tiles): ``Out[token] += c * (a
  Wd)``.
* ``_rows`` — grid (tiles, blocks of ``F``): recomputes ``g``, ``u``, ``a``
  from the rows (nothing but the layout is kept for the backward), ``h = dy
  Wd^T``, and from them the routing weights' gradient ``rowsum(a * h)``
  (which equals ``rowsum(dy * o)``, so ``o`` is not recomputed) and the
  operands of the other two: ``dg``, ``du`` and ``do = c * dy``, rounded to
  the products' dtype where the XLA body rounds them.
* ``_dx_combine`` — grid (blocks of ``D``, tiles): ``dX[token] += dg Wg^T +
  du Wu^T``.
* ``_weights`` — grid (blocks of ``F``, tiles): ``dWg += x^T dg``, ``dWu +=
  x^T du``, ``dWd += a^T do``, the chunk's ``x^T`` and ``a^T`` made by XLA.
  Tiles of one expert are adjacent, so an expert's float32 ``[D, F-block]``
  gradients stay in VMEM across them and are written once.

What is carried through the chunks — ``Out`` / ``dX`` and the three weight
gradients — is carried in place (``input_output_aliases``): a chunk after
the first fetches what the chunks before left (one DMA a resident block; of
the weight gradients only a chunk's FIRST tile can continue an expert), and
an expert no tile visits keeps the zeros its gradients start as.

Every blocking follows from the shapes and ``_VMEM_BUDGET`` (``_block``,
``_chunk_tiles``); precision is the XLA body's: operands in ``x``'s dtype,
float32 accumulation in every product, float32 routing weights, ``silu`` and
its derivative in float32, float32 sums into every result.  The one
difference: ``da = c * (dy Wd^T)`` multiplies by ``c`` in float32 after the
product where the XLA body rounds ``c * dy`` before it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_BUDGET, run_traced

_VMEM_BUDGET = VMEM_BUDGET
_LANES = 128
# Rows whose read-modify-write one turn of a combine's row loop spells out.
_ROWS_UNROLLED = 8

_run = functools.partial(run_traced, "grouped_experts")


def _chunk_tiles(held):
    """Tiles a chunk: what the held experts fill when the router is balanced
    (a tile an expert, as the configurations size their tiles) and a quarter
    more, so that the common step is ONE chunk — every further chunk re-reads
    and re-writes the whole of ``Out`` / ``dX``."""
    return held + -(-held // 4)


def _step_bytes(kind, n, tile, d, f, blk, itemsize):
    """VMEM bytes one grid step of kernel ``kind`` holds with blocks of
    ``blk`` columns (of ``F``: gate_up, rows, weights; of ``D``: down, dx):
    its blocks double-buffered, and the temporaries of its body."""
    col = tile * _LANES * 4                    # a [tile, 1] float32 column
    rows, rows32 = tile * d * itemsize, tile * d * 4
    part, part32 = tile * blk * itemsize, tile * blk * 4
    if kind == "gate_up":
        return 2 * (rows + 2 * d * blk * itemsize + part) + 2 * part32 + part
    if kind == "rows":
        return 2 * (2 * rows + 2 * col + 3 * d * blk * itemsize + rows
                    + 3 * part) + 6 * part32 + rows32
    if kind == "weights":
        return 2 * (2 * rows + 3 * part + 3 * d * blk * 4) + d * blk * 4
    wide = tile * f * itemsize
    if kind == "down":
        return 2 * (wide + col + f * blk * itemsize + n * blk * 4) \
            + 2 * part32
    return 2 * (2 * wide + 2 * f * blk * itemsize + n * blk * 4) + 2 * part32


def _block(kind, n, tile, d, f, itemsize):
    """The widest block — the whole width or a divisor of whole lane tiles —
    with which a step of ``kind`` fits the budget; None if none."""
    width = d if kind in ("down", "dx") else f
    for parts in range(1, width // _LANES + 1):
        if width % parts == 0 and (width // parts) % _LANES == 0 \
                and _step_bytes(kind, n, tile, d, f, width // parts,
                                itemsize) <= _VMEM_BUDGET:
            return width // parts
    return None


_KINDS = ("gate_up", "down", "rows", "dx", "weights")


def supported(x, gate, tile):
    """Whether the kernels take ``x`` ``[N, D]`` and ``gate`` ``[held, D,
    F]`` (arrays or their shapes-and-dtypes) at ``tile`` rows a tile: bf16
    or float32, widths and tiles of whole lane tiles (a tile's rows are the
    lanes of ``_weights``' transposed blocks), and a step of each kernel
    inside the VMEM budget."""
    dtype = jnp.dtype(x.dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) \
            or jnp.dtype(gate.dtype) != dtype:
        return False
    (n, d), (_, _, f) = x.shape, gate.shape
    if d % _LANES or f % _LANES or tile % _LANES:
        return False
    return all(_block(kind, n, tile, d, f, dtype.itemsize)
               for kind in _KINDS)


# -- the kernels -----------------------------------------------------------------
# Scalar prefetch of every kernel: ``te`` — the chunk's TileExpert; ``meta``
# — (live tiles of the chunk, whether its first tile continues the expert
# of the tile before it, whether chunks came before it); the combines also
# take ``tok``, the chunk's rows' token ids (clamped).

def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NN, _NT = ((1,), (0,)), ((1,), (1,))


def _products(x, wg, wu, dtype):
    g, u = _dot(x, wg, _NN), _dot(x, wu, _NN)
    return g, u, (jax.nn.silu(g) * u).astype(dtype)


def _gate_up_kernel(te_ref, meta_ref, x_ref, wg_ref, wu_ref, a_ref):
    @pl.when(pl.program_id(0) < meta_ref[0])
    def _():
        x = x_ref[...]
        a_ref[...] = _products(x, wg_ref[0], wu_ref[0], x.dtype)[2]


def _fetch(prev_ref, block_ref, sem, at):
    """What the chunks before left of a carried result, into the block this
    step keeps resident."""
    copy = pltpu.make_async_copy(prev_ref.at[at], block_ref, sem)
    copy.start()
    copy.wait()


def _combine(meta_ref, tok_ref, prev_ref, out_ref, part_ref, sem, product,
             *, tile, blk):
    """A combine's step (block ``j`` of ``D``, tile ``i``): at the chunk's
    first tile the resident ``[N, blk]`` block starts as zeros or as what
    the chunks before left; a live tile's ``product()`` ``[tile, blk]`` goes
    to scratch and row by row into its tokens' rows."""
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (meta_ref[2] == 0))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when((i == 0) & (meta_ref[2] == 1))
    def _():
        _fetch(prev_ref, out_ref, sem,
               (slice(None), pl.ds(pl.multiple_of(j * blk, _LANES), blk)))

    @pl.when(i < meta_ref[0])
    def _():
        part_ref[...] = product()
        base = i * tile

        def turn(t, carry):
            for k in range(_ROWS_UNROLLED):
                r = t * _ROWS_UNROLLED + k
                token = pl.ds(tok_ref[base + r], 1)
                out_ref[token, :] += part_ref[pl.ds(r, 1), :]
            return carry
        lax.fori_loop(0, tile // _ROWS_UNROLLED, turn, 0)


def _down_kernel(te_ref, meta_ref, tok_ref, a_ref, c_ref, wd_ref, prev_ref,
                 y_ref, part_ref, sem, **geometry):
    _combine(meta_ref, tok_ref, prev_ref, y_ref, part_ref, sem,
             lambda: _dot(a_ref[...], wd_ref[0], _NN) * c_ref[...],
             **geometry)


def _dx_kernel(te_ref, meta_ref, tok_ref, dg_ref, du_ref, wg_ref, wu_ref,
               prev_ref, dx_ref, part_ref, sem, **geometry):
    _combine(meta_ref, tok_ref, prev_ref, dx_ref, part_ref, sem,
             lambda: _dot(dg_ref[...], wg_ref[0], _NT)
             + _dot(du_ref[...], wu_ref[0], _NT), **geometry)


def _rows_kernel(te_ref, meta_ref, x_ref, dy_ref, c_ref, wg_ref, wu_ref,
                 wd_ref, dc_ref, do_ref, dg_ref, du_ref, a_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < meta_ref[0])
    def _():
        x, dy, c = x_ref[...], dy_ref[...], c_ref[...]
        dtype = x.dtype
        g, u, a = _products(x, wg_ref[0], wu_ref[0], dtype)
        h = _dot(dy, wd_ref[0], _NT)
        dc = jnp.sum(a.astype(jnp.float32) * h, -1, keepdims=True)
        da = h * c
        sig = jax.nn.sigmoid(g)
        a_ref[...] = a
        dg_ref[...] = (da * u * sig * (1.0 + g * (1.0 - sig))).astype(dtype)
        du_ref[...] = (da * g * sig).astype(dtype)

        @pl.when(j == 0)
        def _():
            do_ref[...] = (dy.astype(jnp.float32) * c).astype(dtype)
            dc_ref[...] = dc

        @pl.when(j > 0)
        def _():
            dc_ref[...] += dc


def _weights_kernel(te_ref, meta_ref, xt_ref, do_ref, dg_ref, du_ref, at_ref,
                    pg_ref, pu_ref, pd_ref, wg_ref, wu_ref, wd_ref, sem, *,
                    blk):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i < meta_ref[0])
    def _():
        # the chunk's first tile continues the expert of the chunk before
        # (meta[1]) or starts one; any other tile starts one if its expert
        # is not the tile's before it
        e = te_ref[i]

        @pl.when((i == 0) & (meta_ref[1] == 1))
        def _():
            cols = pl.ds(pl.multiple_of(j * blk, _LANES), blk)
            _fetch(pg_ref, wg_ref, sem, (pl.ds(e, 1), slice(None), cols))
            _fetch(pu_ref, wu_ref, sem, (pl.ds(e, 1), slice(None), cols))
            _fetch(pd_ref, wd_ref, sem, (pl.ds(e, 1), cols, slice(None)))

        @pl.when(((i == 0) & (meta_ref[1] == 0))
                 | (e != te_ref[jnp.maximum(i - 1, 0)]))
        def _():
            for out in (wg_ref, wu_ref, wd_ref):
                out[...] = jnp.zeros(out.shape, out.dtype)
        xt = xt_ref[...]
        wg_ref[0] += _dot(xt, dg_ref[...], _NN)
        wu_ref[0] += _dot(xt, du_ref[...], _NN)
        wd_ref[0] += _dot(at_ref[...], do_ref[...], _NN)


def _params(vmem):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=vmem)


_ANY = pl.BlockSpec(memory_space=pl.ANY)


def _specs(tile, d, blocks_first, nb):
    """Block specs of a grid of (tiles of the chunk, ``nb`` blocks of a
    width) — the blocks the outer axis if ``blocks_first`` — a dead tile's
    every index clamped to the last live tile's last step.  ``rows(w)``: a
    ``[tile, w]`` block of a chunk's rows; ``part(b)``: a ``[tile, b]``
    block of their blocked companions; ``lanes(h, blocked)``: a ``[h,
    tile]`` block of a transposed companion, its height the blocked width
    or whole; ``mats(shape, axis)``: an expert's block of a ``[held, ., .]``
    stack, blocked along ``axis``."""
    def split(a, b):
        return (b, a) if blocks_first else (a, b)

    def tile_of(i, meta):
        return jnp.minimum(i, meta[0] - 1)

    def block_of(i, j, meta):
        return j if blocks_first else jnp.where(i < meta[0], j, nb - 1)

    def rows(w):
        def index(a, b, te, meta, *_):
            return tile_of(split(a, b)[0], meta), 0
        return pl.BlockSpec((tile, w), index)

    def part(blk):
        def index(a, b, te, meta, *_):
            i, j = split(a, b)
            return tile_of(i, meta), block_of(i, j, meta)
        return pl.BlockSpec((tile, blk), index)

    def lanes(height, blocked):
        def index(a, b, te, meta, *_):
            i, j = split(a, b)
            return block_of(i, j, meta) if blocked else 0, tile_of(i, meta)
        return pl.BlockSpec((height, tile), index)

    def mats(shape, axis):
        def index(a, b, te, meta, *_):
            i, j = split(a, b)
            at = [te[tile_of(i, meta)], 0, 0]
            at[axis] = block_of(i, j, meta)
            return tuple(at)
        return pl.BlockSpec((1,) + shape, index)
    return rows, part, lanes, mats


def _gate_up(te, meta, x, gate, up, *, tile, blk, vmem, interpret):
    (r, d), f = x.shape, gate.shape[2]
    rows, part, _, mats = _specs(tile, d, False, f // blk)
    return pl.pallas_call(
        _gate_up_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(r // tile, f // blk),
            in_specs=[rows(d), mats((d, blk), 2), mats((d, blk), 2)],
            out_specs=part(blk)),
        out_shape=jax.ShapeDtypeStruct((r, f), x.dtype),
        compiler_params=_params(vmem), interpret=interpret,
    )(te, meta, x, gate, up)


def _resident(n, blk):
    return pl.BlockSpec((n, blk), lambda j, i, *_: (0, j))


def _scratch(tile, blk):
    return [pltpu.VMEM((tile, blk), jnp.float32), pltpu.SemaphoreType.DMA(())]


def _down_combine(te, meta, tok, a, c, down, y, *, tile, blk, vmem,
                  interpret):
    (r, f), (n, d) = a.shape, y.shape
    rows, _, _, mats = _specs(tile, d, True, d // blk)
    return pl.pallas_call(
        functools.partial(_down_kernel, tile=tile, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // blk, r // tile),
            in_specs=[rows(f), rows(1), mats((f, blk), 2), _ANY],
            out_specs=_resident(n, blk), scratch_shapes=_scratch(tile, blk)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        input_output_aliases={6: 0},
        compiler_params=_params(vmem), interpret=interpret,
    )(te, meta, tok, a, c, down, y)


def _dx_combine(te, meta, tok, dg, du, gate, up, dx, *, tile, blk, vmem,
                interpret):
    (r, f), (n, d) = dg.shape, dx.shape
    rows, _, _, mats = _specs(tile, d, True, d // blk)
    return pl.pallas_call(
        functools.partial(_dx_kernel, tile=tile, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // blk, r // tile),
            in_specs=[rows(f), rows(f), mats((blk, f), 1), mats((blk, f), 1),
                      _ANY],
            out_specs=_resident(n, blk), scratch_shapes=_scratch(tile, blk)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        input_output_aliases={7: 0},
        compiler_params=_params(vmem), interpret=interpret,
    )(te, meta, tok, dg, du, gate, up, dx)


def _rows(te, meta, x, dy, c, gate, up, down, *, tile, blk, vmem, interpret):
    (r, d), f = x.shape, gate.shape[2]
    rows, part, _, mats = _specs(tile, d, False, f // blk)
    wide = jax.ShapeDtypeStruct((r, f), x.dtype)
    return pl.pallas_call(
        _rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(r // tile, f // blk),
            in_specs=[rows(d), rows(d), rows(1), mats((d, blk), 2),
                      mats((d, blk), 2), mats((blk, d), 1)],
            out_specs=[rows(1), rows(d), part(blk), part(blk), part(blk)]),
        out_shape=[jax.ShapeDtypeStruct((r, 1), jnp.float32),
                   jax.ShapeDtypeStruct((r, d), x.dtype), wide, wide, wide],
        compiler_params=_params(vmem), interpret=interpret,
    )(te, meta, x, dy, c, gate, up, down)


def _weights(te, meta, xt, do, dg, du, at, d_gate, d_up, d_down, *, tile,
             blk, vmem, interpret):
    """Grid (blocks of ``F``, tiles of the chunk): the tiles innermost, so
    an expert's gradient blocks stay put across its tiles.  ``xt`` ``[D,
    rows]`` and ``at`` ``[F, rows]`` come TRANSPOSED (by XLA, once a chunk):
    a product that contracts the rows of both operands has Mosaic transpose
    the ``[tile, D]`` block in every step, three quarters of this kernel's
    time when it did."""
    (d, r), f = xt.shape, dg.shape[1]
    rows, part, lanes, mats = _specs(tile, d, True, f // blk)
    return pl.pallas_call(
        functools.partial(_weights_kernel, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(f // blk, r // tile),
            in_specs=[lanes(d, False), rows(d), part(blk), part(blk),
                      lanes(blk, True), _ANY, _ANY, _ANY],
            out_specs=[mats((d, blk), 2), mats((d, blk), 2),
                       mats((blk, d), 1)],
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32)
                   for v in (d_gate, d_up, d_down)],
        input_output_aliases={7: 0, 8: 1, 9: 2},
        compiler_params=_params(vmem), interpret=interpret,
    )(te, meta, xt, do, dg, du, at, d_gate, d_up, d_down)


# -- the chunks ------------------------------------------------------------------

def _statics(kind, x, gate, tile, interpret):
    (n, d), f = x.shape, gate.shape[2]
    return dict(tile=tile, vmem=_VMEM_BUDGET, interpret=interpret,
                blk=_block(kind, n, tile, d, f, x.dtype.itemsize))


def _chunks(x, weight, layout, tile, chunk):
    """(number of chunks, a function from a chunk's number to its rows'
    token ids — ``N`` for a padding row — and slots, the ids clamped, the
    rows' routing weights as a float32 column, and the kernels' scalars: the
    chunk's ``TileExpert`` and ``meta``)."""
    row_token, row_slot, tile_expert, num_tiles = layout
    n, tiles = x.shape[0], tile_expert.shape[0]
    pad = -tiles % chunk          # a chunk's slice never runs past the end
    row_token = jnp.pad(row_token, (0, pad * tile), constant_values=n)
    row_slot = jnp.pad(row_slot, (0, pad * tile))
    tile_expert = jnp.pad(tile_expert, (0, pad))
    num = num_tiles[0]

    def at(ci):
        t0 = ci * chunk
        rows = lax.dynamic_slice(row_token, (t0 * tile,), (chunk * tile,))
        slots = lax.dynamic_slice(row_slot, (t0 * tile,), (chunk * tile,))
        te = lax.dynamic_slice(tile_expert, (t0,), (chunk,))
        token = jnp.minimum(rows, n - 1)
        c = jnp.where(rows < n, weight[token, slots], 0.0)[:, None]
        before = tile_expert[jnp.maximum(t0 - 1, 0)]
        meta = jnp.stack([jnp.minimum(num - t0, chunk),
                          ((t0 > 0) & (before == te[0])).astype(jnp.int32),
                          (t0 > 0).astype(jnp.int32)])
        return rows, slots, token, c, te, meta
    return lax.div(num + (chunk - 1), chunk), at


def forward(x, weight, gate, up, down, layout, tile, interpret=False):
    """``ops.moe.expert_ffn`` by the kernels: (``[N, D]`` float32, the
    number of token-expert pairs computed)."""
    n_chunks, at = _chunks(x, weight, layout, tile,
                           _chunk_tiles(gate.shape[0]))

    def body(ci, carry):
        y, pairs = carry
        rows, _, token, c, te, meta = at(ci)
        (a,) = _run(_gate_up, (te, meta, x[token], gate, up),
                    **_statics("gate_up", x, gate, tile, interpret))
        (y,) = _run(_down_combine, (te, meta, token, a, c, down, y),
                    **_statics("down", x, gate, tile, interpret))
        return y, pairs + jnp.sum(rows < x.shape[0], dtype=jnp.float32)
    return lax.fori_loop(
        0, n_chunks, body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.float32)))


def backward(x, weight, gate, up, down, layout, tile, dy, interpret=False):
    """``ops.moe.expert_ffn_grad`` by the kernels: (dX, dWeight, dGate, dUp,
    dDown), all float32."""
    dy = dy.astype(x.dtype)
    n_chunks, at = _chunks(x, weight, layout, tile,
                           _chunk_tiles(gate.shape[0]))

    def body(ci, carry):
        dx, dw, mats = carry[0], carry[1], carry[2:]
        rows, slots, token, c, te, meta = at(ci)
        xt = x[token]
        dc, do, dg, du, a = _run(
            _rows, (te, meta, xt, dy[token], c, gate, up, down),
            **_statics("rows", x, gate, tile, interpret))
        (dx,) = _run(_dx_combine, (te, meta, token, dg, du, gate, up, dx),
                     **_statics("dx", x, gate, tile, interpret))
        mats = _run(_weights, (te, meta, xt.T, do, dg, du, a.T) + mats,
                    **_statics("weights", x, gate, tile, interpret))
        # a pair is one (token, slot): unique; a padding row is dropped
        dw = dw.at[rows, slots].add(dc[:, 0], mode="drop",
                                    unique_indices=True)
        return (dx, dw) + tuple(mats)
    zeros = [jnp.zeros(v.shape, jnp.float32)
             for v in (x, weight, gate, up, down)]
    return lax.fori_loop(0, n_chunks, body, tuple(zeros))
