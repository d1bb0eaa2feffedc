"""A state-space layer's selective scan, chunked over time, with its
backward.

The recurrence (Mamba's), for one sequence, ``E`` channels and ``N`` states a
channel, all float32::

    s_t = exp(delta_t (x) 1 * A) * s_{t-1} + (delta_t * x_t) (x) B_t
    y_t = s_t C_t + D * x_t                          s_0 = 0

``delta``, ``x``, ``y`` are ``[Bt, T, E]``, ``A`` ``[E, N]`` (negative), ``B``
and ``C`` ``[Bt, T, N]``, ``D`` ``[E]``.  ``T`` steps in a row, each a handful
of vector operations over ``[N, E]``: as a ``lax.scan`` that is ``T`` tiny
launches and a ``[T, E, N]`` residual for the backward (1.3 GB at 4096 x 5120
x 16); here time is a grid axis in chunks of ``chunk`` steps (the innermost,
``arbitrary``), the state lives in VMEM scratch across the chunks, and what
the forward keeps for the backward is the state at every chunk's START
(``[T / chunk, N, E]``: 21 MB at chunk 64).  The backward walks the chunks
from the last to the first: it recomputes a chunk's states from its start
into scratch, then runs the adjoint recurrence back through them.

* **Layout.** A state is ``[N, E]``: the channels on the lanes, the states a
  channel on the sublanes (16 = two sublane tiles).  ``delta_t`` and ``x_t``
  are rows, spread over the sublanes for nothing; ``B_t`` and ``C_t`` are
  columns, and a column spread over the lanes is a cross-lane operation a
  step.  So the caller's ``[Bt, T, N]`` arrays come in replicated over one
  lane tile, ``[Bt, T, N, 128]`` (33 MB each at the cell's size, read once:
  0.04 ms), ``b_ref[t]`` is two whole vector registers, and the kernel holds
  no broadcast of its own.  Likewise out: dB and dC leave the kernel as
  per-lane partial sums ``[Bt, T, N, 128]`` and XLA adds the lanes up.
* **Slabs.** A chunk's channels are taken ``slab`` lanes at a time (512
  forward: a ``[16, 512]`` state is eight vector registers and stays in
  them as the time loop's carry; 256 backward, where the adjoint, dA's
  running sum and two recomputed states are live together).  Time goes in
  groups of eight steps — one aligned ``[8, slab]`` load of ``delta`` and
  ``x`` and one store of ``y`` a group, the eight steps unrolled — inside a
  rolled ``fori_loop``.
* **Padding.** ``T`` is padded to whole chunks with ``delta = 0``: a step
  with no time in it leaves the state as it was (``exp(0) = 1``, ``0 * x =
  0``) and, with ``dy = 0``, adds nothing to any gradient.

``supported()`` says which calls the kernels take; the op keeps an XLA body
(``lax.scan`` over chunks) for the rest and for the CPU.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_BUDGET, run_traced

LANES = 128
_GROUP = 8          # steps a turn of the time loop: one sublane tile of rows


def _pick_slab(e, widest):
    """The widest of 512, 256, 128 lanes, at most ``widest``, that divides
    ``e``."""
    for s in (512, 256, 128):
        if s <= widest and e % s == 0:
            return s
    return None


def supported(x_shape, n_state, chunk):
    """Whether the kernels take this call: channels in whole lane tiles,
    states in whole sublane tiles, chunks of whole groups of steps."""
    if len(x_shape) != 3:
        return False
    return x_shape[2] % LANES == 0 and n_state % 8 == 0 \
        and chunk % _GROUP == 0 and chunk > 0


def _across(x, width):
    """``[N, 128]``, every lane of a row equal, as ``[N, width]``: the same
    registers named ``width / 128`` times."""
    return jnp.tile(x, (1, width // LANES))


def _lane_partial(x):
    """The lane tiles of ``x`` ``[N, w]`` added up: ``[N, 128]``, lane ``j``
    the sum over the channels ``j mod 128``."""
    return functools.reduce(jnp.add, [x[:, i * LANES:(i + 1) * LANES]
                                      for i in range(x.shape[1] // LANES)])


def _rows(rows):
    """Eight ``[1, w]`` rows as one ``[8, w]`` tile."""
    return jnp.concatenate(rows, axis=0)


def _fwd_kernel(delta_ref, x_ref, b_ref, c_ref, a_ref, d_ref,
                y_ref, state_ref, start_ref, s_scr, *, chunk, slab, n_chunks):
    ci = pl.program_id(1)
    n, e = s_scr.shape

    @pl.when(ci == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, jnp.float32)

    start_ref[0, 0] = s_scr[...]               # the state this chunk starts on
    for c in range(e // slab):
        cols = pl.ds(c * slab, slab)
        a = a_ref[:, cols]

        def group(gi, s, cols=cols, a=a):
            r0 = pl.multiple_of(gi * _GROUP, _GROUP)
            dl = delta_ref[0, pl.ds(r0, _GROUP), cols]          # [8, slab]
            xs = x_ref[0, pl.ds(r0, _GROUP), cols]
            dx = dl * xs
            ys = []
            for j in range(_GROUP):
                bt = _across(b_ref[0, r0 + j], slab)
                ct = _across(c_ref[0, r0 + j], slab)
                s = jnp.exp(jnp.broadcast_to(dl[j:j + 1], (n, slab)) * a) * s \
                    + jnp.broadcast_to(dx[j:j + 1], (n, slab)) * bt
                ys.append(jnp.sum(s * ct, axis=0, keepdims=True))
            y_ref[0, pl.ds(r0, _GROUP), cols] = _rows(ys) + d_ref[:, cols] * xs
            return s
        s_scr[:, cols] = jax.lax.fori_loop(0, chunk // _GROUP, group,
                                           s_scr[:, cols])

    @pl.when(ci == n_chunks - 1)
    def _():
        state_ref[0] = s_scr[...]


def _bwd_kernel(delta_ref, x_ref, b_ref, c_ref, a_ref, d_ref, dy_ref,
                start_ref, ddelta_ref, dx_ref, db_ref, dc_ref, da_ref, dd_ref,
                h_scr, da_scr, dd_scr, s_scr, *, chunk, slab, n_chunks):
    """One chunk, the grid's ``ci``-th from the END of the sequence.  The
    carries across chunks: ``h_scr`` — what the later steps hand back to
    this chunk's last state, ``a_{t+1} * g_{t+1}`` —, dA's running sum and
    dD's (eight partial rows)."""
    ci = pl.program_id(1)
    n, e = h_scr.shape
    groups = chunk // _GROUP

    @pl.when(ci == 0)
    def _():
        h_scr[...] = jnp.zeros(h_scr.shape, jnp.float32)
        da_scr[...] = jnp.zeros(da_scr.shape, jnp.float32)
        dd_scr[...] = jnp.zeros(dd_scr.shape, jnp.float32)

    for c in range(e // slab):
        cols = pl.ds(c * slab, slab)
        a = a_ref[:, cols]

        # the chunk's states again, from the one it started on: s_scr[j] is
        # the state BEFORE step j
        def again(gi, s, cols=cols, a=a):
            r0 = pl.multiple_of(gi * _GROUP, _GROUP)
            dl = delta_ref[0, pl.ds(r0, _GROUP), cols]
            dx = dl * x_ref[0, pl.ds(r0, _GROUP), cols]
            for j in range(_GROUP):
                s_scr[r0 + j] = s
                s = jnp.exp(jnp.broadcast_to(dl[j:j + 1], (n, slab)) * a) * s \
                    + jnp.broadcast_to(dx[j:j + 1], (n, slab)) \
                    * _across(b_ref[0, r0 + j], slab)
            return s
        s_scr[chunk] = jax.lax.fori_loop(0, groups, again,
                                         start_ref[0, 0, :, cols])

        def back(k, carry, cols=cols, a=a, first=(c == 0)):
            h, da = carry
            r0 = pl.multiple_of((groups - 1 - k) * _GROUP, _GROUP)
            dl = delta_ref[0, pl.ds(r0, _GROUP), cols]
            xs = x_ref[0, pl.ds(r0, _GROUP), cols]
            dys = dy_ref[0, pl.ds(r0, _GROUP), cols]
            dxs = dl * xs
            ddl, dxr = [None] * _GROUP, [None] * _GROUP
            for j in reversed(range(_GROUP)):
                dl_j = jnp.broadcast_to(dl[j:j + 1], (n, slab))
                dy_j = jnp.broadcast_to(dys[j:j + 1], (n, slab))
                at = jnp.exp(dl_j * a)
                g = _across(c_ref[0, r0 + j], slab) * dy_j + h
                dc = _lane_partial(dy_j * s_scr[r0 + j + 1])
                db = _lane_partial(
                    g * jnp.broadcast_to(dxs[j:j + 1], (n, slab)))
                if first:
                    dc_ref[0, r0 + j] = dc
                    db_ref[0, r0 + j] = db
                else:
                    dc_ref[0, r0 + j] += dc
                    db_ref[0, r0 + j] += db
                h = at * g
                dexp = h * s_scr[r0 + j]            # dL / d(delta_t * A)
                da = da + dexp * dl_j
                into = jnp.sum(g * _across(b_ref[0, r0 + j], slab), axis=0,
                               keepdims=True)       # dL / d(delta_t * x_t)
                ddl[j] = jnp.sum(dexp * a, axis=0, keepdims=True) \
                    + into * xs[j:j + 1]
                dxr[j] = into * dl[j:j + 1]
            ddelta_ref[0, pl.ds(r0, _GROUP), cols] = _rows(ddl)
            dx_ref[0, pl.ds(r0, _GROUP), cols] = _rows(dxr) \
                + d_ref[:, cols] * dys
            dd_scr[:, cols] += dys * xs
            return h, da
        h, da = jax.lax.fori_loop(0, groups, back,
                                  (h_scr[:, cols], da_scr[:, cols]))
        h_scr[:, cols] = h
        da_scr[:, cols] = da

    @pl.when(ci == n_chunks - 1)
    def _():
        da_ref[0] = da_scr[...]
        dd_ref[0] = dd_scr[...]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BUDGET)


def _forward(delta, x, b_rep, c_rep, a_t, d_row, *, chunk, interpret):
    """``delta``, ``x`` ``[Bt, Tp, E]`` (whole chunks); ``b_rep``, ``c_rep``
    ``[Bt, Tp, N, 128]``; ``a_t`` ``[N, E]``; ``d_row`` ``[1, E]``."""
    bt, tp, e = x.shape
    n = a_t.shape[0]
    nc = tp // chunk
    slab = _pick_slab(e, 512)

    def rows(bi, ci):
        return (bi, ci, 0)

    def reps(bi, ci):
        return (bi, ci, 0, 0)
    row = pl.BlockSpec((1, chunk, e), rows)
    rep = pl.BlockSpec((1, chunk, n, LANES), reps)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, slab=slab, n_chunks=nc),
        grid=(bt, nc),
        in_specs=[row, row, rep, rep,
                  pl.BlockSpec((n, e), lambda bi, ci: (0, 0)),
                  pl.BlockSpec((1, e), lambda bi, ci: (0, 0))],
        out_specs=[row, pl.BlockSpec((1, n, e), lambda bi, ci: (bi, 0, 0)),
                   pl.BlockSpec((1, 1, n, e), lambda bi, ci: (bi, ci, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bt, tp, e), jnp.float32),
                   jax.ShapeDtypeStruct((bt, n, e), jnp.float32),
                   jax.ShapeDtypeStruct((bt, nc, n, e), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, e), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(delta, x, b_rep, c_rep, a_t, d_row)


def _backward(delta, x, b_rep, c_rep, a_t, d_row, dy, starts, *, chunk,
              interpret):
    bt, tp, e = x.shape
    n = a_t.shape[0]
    nc = tp // chunk
    slab = _pick_slab(e, 256)

    def rows(bi, ci):              # the ci-th chunk from the end
        return (bi, nc - 1 - ci, 0)

    def reps(bi, ci):
        return (bi, nc - 1 - ci, 0, 0)
    row = pl.BlockSpec((1, chunk, e), rows)
    rep = pl.BlockSpec((1, chunk, n, LANES), reps)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, slab=slab, n_chunks=nc),
        grid=(bt, nc),
        in_specs=[row, row, rep, rep,
                  pl.BlockSpec((n, e), lambda bi, ci: (0, 0)),
                  pl.BlockSpec((1, e), lambda bi, ci: (0, 0)),
                  row, pl.BlockSpec((1, 1, n, e), reps)],
        out_specs=[row, row, rep, rep,
                   pl.BlockSpec((1, n, e), lambda bi, ci: (bi, 0, 0)),
                   pl.BlockSpec((1, _GROUP, e), lambda bi, ci: (bi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bt, tp, e), jnp.float32),
                   jax.ShapeDtypeStruct((bt, tp, e), jnp.float32),
                   jax.ShapeDtypeStruct((bt, tp, n, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((bt, tp, n, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((bt, n, e), jnp.float32),
                   jax.ShapeDtypeStruct((bt, _GROUP, e), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, e), jnp.float32),
                        pltpu.VMEM((n, e), jnp.float32),
                        pltpu.VMEM((_GROUP, e), jnp.float32),
                        pltpu.VMEM((chunk + 1, n, slab), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(delta, x, b_rep, c_rep, a_t, d_row, dy, starts)


_run = functools.partial(run_traced, "selective_scan")


def _whole_chunks(t, chunk):
    return -(-t // chunk) * chunk


def _operands(delta, x, a, b, c, d, chunk):
    """The kernels' operands from the caller's: time padded to whole chunks
    (``delta = 0`` there), ``B`` and ``C`` replicated over a lane tile, ``A``
    with the channels on the lanes."""
    t = x.shape[1]
    pad = _whole_chunks(t, chunk) - t

    def in_time(v):
        v = v.astype(jnp.float32)
        return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2)) \
            if pad else v

    def rep(v):
        v = in_time(v)
        return jnp.broadcast_to(v[..., None], v.shape + (LANES,))
    return (in_time(delta), in_time(x), rep(b), rep(c),
            a.astype(jnp.float32).T, d.astype(jnp.float32).reshape(1, -1))


def forward(delta, x, a, b, c, d, chunk=64, interpret=False):
    """``(y [Bt, T, E], the final state [Bt, E, N], the state at every
    chunk's start [Bt, ceil(T / chunk), N, E])``, float32."""
    t = x.shape[1]
    y, state, starts = _run(_forward, _operands(delta, x, a, b, c, d, chunk),
                            chunk=int(chunk), interpret=bool(interpret))
    return y[:, :t], jnp.swapaxes(state, 1, 2), starts


def backward(delta, x, a, b, c, d, starts, dy, chunk=64, interpret=False):
    """``(d delta, dx, dA, dB, dC, dD)`` from the forward's operands, the
    chunk-start states it kept and ``dy``."""
    t = x.shape[1]
    dy = dy.astype(jnp.float32)
    pad = _whole_chunks(t, chunk) - t
    if pad:
        dy = jnp.pad(dy, [(0, 0), (0, pad), (0, 0)])
    ddelta, dx, db, dc, da, dd = _run(
        _backward, _operands(delta, x, a, b, c, d, chunk) + (dy, starts),
        chunk=int(chunk), interpret=bool(interpret))
    return (ddelta[:, :t], dx[:, :t], jnp.sum(da, 0).T,
            jnp.sum(db[:, :t], -1), jnp.sum(dc[:, :t], -1),
            jnp.sum(dd, (0, 1)))
