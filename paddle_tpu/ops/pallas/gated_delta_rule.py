"""The gated delta rule's chunk algebra in VMEM: the forward op's kernel and
the gradient op's two.

The equations are ``ops/gated_delta_rule.py``'s (its ``rule_xla`` /
``rule_grad_xla`` are the definition, the CPU body and the body under a
mesh).  There a chunk's local parts — the decays, the same-sub-block pairs'
``[16, 16, Dk]`` exponentials, ``(I + A)^-1``, ``W``, ``U0``, ``P`` — are XLA
arrays over ALL chunks and go through HBM; here time is a grid axis in chunks
(the innermost, ``arbitrary``), a grid step takes ``hb`` heads' ``[C, hb *
D]`` blocks of the op's own ``[B, T, H * D]`` inputs in the dtype they arrive
in (bf16 under mixed precision), widens them, and everything a chunk makes
lives and dies in VMEM beside the running state:

* ``_forward`` — the prelude (L2 norms, ``scale``, the decay's activation),
  the cumulative decay, ``A``, ``(I + A)^-1``, ``W``, ``U0``, ``P``, then ``U
  = U0 - W S``, ``o = (Q e^G) S + P U``, ``S' = Diag(e^{G_C}) S + Ke^T U``
  and the finish (``rms_norm x sigmoid(gate)``); writes ``Out`` once,
  ``Starts`` where a group of chunks begins, ``State`` at the end.
* ``_states`` — the same step with no outputs: the state EVERY chunk starts
  on, transposed, ``[B, N, H, Dv, Dk]`` (134 MB at 4096 steps of 32 heads:
  written and read once by the gradient op, 0.3 ms of HBM time, and the only
  thing of a chunk that leaves VMEM).
* ``_backward`` — the chunks from the last to the first with ``dS``
  resident: a step makes its chunk's local parts again from the state
  ``_states`` kept and pulls them back by hand (the equations are beside
  the code; ``jax.vjp`` of the XLA body is what the tests hold them to).

The state is kept TRANSPOSED, ``St = S^T`` ``[Dv, Dk]``: the chunk's decay
``e^{G_C}`` is then a row over the lanes (no ``[1, Dk]`` -> ``[Dk, 1]``
transpose a chunk) and the products with ``S`` are the MXU's native forms.

Precision is the op's: float32 operands, every product three bf16 passes
made by hand (Mosaic's dot takes ``DEFAULT`` or ``HIGHEST`` only): ``hi =
bf16(x)``, ``lo = bf16(x - hi)``, ``hi.hi + hi.lo + lo.hi`` with float32
accumulation.  The cumulative sums are products with a 0/1 triangle (exact in
bf16) of the summand split THREE ways (24 bits: float32's own sum).  The
exponentials, the substitution and the reductions are float32.

``exp(G_i - G_j)`` of a same-sub-block pair is taken channel by channel as
the XLA body takes it, but never as a ``[16, 16, Dk]`` array: a loop over the
16 columns ``j`` of a sub-block on the step's ``[8, Dk]`` sublane tiles of
rows (a column past a sub-block's first tile has nothing in it: three
quarters of the work), ONE exponential for ``q . k`` and ``k . k`` both
(ROADMAP S16 a), a lane sum each.  ``beta`` rides on the rows (``Kb = beta
K``), so the sums ARE ``A``'s columns.  Pairs in different sub-blocks split
at the first row of ``i``'s, as there.  ``(I + A)^-1``: the 16-row
substitution runs on all the step's sub-blocks and heads at once, in the
same loop (column after column: ``X_i -= a_ij X_j`` for ``i > j``), then the
two block merges up to ``C = 64`` as masked whole-chunk products (``T - T (A
* mask) T``).  An operand that several products read is split into its bf16
``hi`` / ``lo`` once.

``supported()`` says which calls the kernels take: ``Dk`` and ``Dv`` whole
lane tiles, ``T`` whole chunks (a ragged tail goes to the XLA body, which
pads), ``chunk`` a multiple of ``SUB`` up to 128.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_BUDGET, run_traced

SUB = 16
LANES = 128
_F32, _BF16 = jnp.float32, jnp.bfloat16
_NEVER = -1e30      # an exponent for a pair that is not there: exp() = 0
# batched products over the heads of a step, [h, ., .] each
_NN = (((2,), (1,)), ((0,), (0,)))      # [h, m, k] x [h, k, n]
_NT = (((2,), (2,)), ((0,), (0,)))      # [h, m, k] x [h, n, k]
_TN = (((1,), (1,)), ((0,), (0,)))      # [h, k, m] x [h, k, n]
# what a head of a step takes of VMEM beside its state, in [C, 128] float32
# tiles: its blocks and live parts (the backward at four heads compiles under
# a limit of 16 MiB and not of 12, at eight under 32 and not 24)
_LIVE_TILES = 128


def supported(q_shape, v_shape, chunk):
    """Whether the kernels take this call."""
    if len(q_shape) != 4 or len(v_shape) != 4:
        return False
    t, dk, dv = q_shape[1], q_shape[3], v_shape[3]
    return dk % LANES == 0 and dv % LANES == 0 and chunk % SUB == 0 \
        and 0 < chunk <= LANES and t % chunk == 0 \
        and heads_per_step(q_shape[2], dk, dv, chunk) is not None


def heads_per_step(h, dk, dv, chunk):
    """Heads a grid step serves: the most of 4, 2, 1 that divide ``h`` and
    whose state, blocks and live parts fit ``VMEM_BUDGET``."""
    d = max(dk, dv)
    for hb in (4, 2, 1):
        held = hb * 4 * (3 * dk * dv + _LIVE_TILES * chunk * d)
        if h % hb == 0 and held <= VMEM_BUDGET:
            return hb
    return None


# -- the pieces ---------------------------------------------------------------

def _split(x):
    """``(hi, lo)`` bf16 of float32 ``x``; a pair is split already."""
    if isinstance(x, tuple):
        return x
    hi = x.astype(_BF16)
    return hi, (x - hi.astype(_F32)).astype(_BF16)


def _mm(a, b, dims=_NN):
    """``a . b`` of float32 operands, or their splits, in three bf16
    passes."""
    (ah, al), (bh, bl) = _split(a), _split(b)

    def dot(x, y):
        return jax.lax.dot_general(x, y, dims, preferred_element_type=_F32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _sum_along(x, tri):
    """``tri . x`` for a 0/1 matrix ``tri`` [C, C] (bf16: exact) and float32
    ``x`` [h, C, D] split three ways: a cumulative sum to float32's own
    rounding."""
    h, c, d = x.shape
    x1 = x.astype(_BF16)
    r1 = x - x1.astype(_F32)
    x2 = r1.astype(_BF16)
    x3 = (r1 - x2.astype(_F32)).astype(_BF16)
    tri = jnp.broadcast_to(tri, (h, c, c))
    return sum(jax.lax.dot_general(tri, p, _NN, preferred_element_type=_F32)
               for p in (x3, x2, x1))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _div(x, by):
    return jax.lax.div(x, jnp.int32(by))


def _rows_apart(ref, hb):
    """``ref[0]`` [hb, D] as [hb, 1, D]."""
    x = ref[0]
    return jnp.stack([x[h:h + 1] for h in range(hb)])


def _heads(ref, hb, width):
    """``ref[0]`` [C, hb * width] as float32 [hb, C, width]: the heads' lane
    tiles named apart, nothing moved."""
    x = ref[0]
    return jnp.stack([x[:, h * width:(h + 1) * width]
                      for h in range(hb)]).astype(_F32)


def _columns(ref, hb):
    """``ref[0, 0]`` [C, hb] as float32 [hb, C, 1]."""
    x = ref[0, 0].astype(_F32)
    return jnp.stack([x[:, h:h + 1] for h in range(hb)])


_HALF = SUB // 2        # a sub-block is two sublane tiles of rows


def _tiles(x):
    """[h, C, D] -> the sub-blocks' first and second sublane tiles, [h, C /
    SUB, 8, D] each."""
    x = _in_blocks(x, SUB)
    return x[:, :, :_HALF], x[:, :, _HALF:]


def _column(gt, kt, j):
    """Column ``j`` of every sub-block: ``(the tile that holds row j, j's row
    in it, [(tile, e^{G_i - G_j}, k_j e^{G_i - G_j})])`` over the tiles with
    rows ``i >= j`` (rows before ``j`` in ``j``'s own tile read a factor at
    most 1 of no meaning: every use masks them or meets a zero there)."""
    tj, rj = j // _HALF, j % _HALF
    g_j, k_j = gt[tj][:, :, rj:rj + 1], kt[tj][:, :, rj:rj + 1]
    pairs = []
    for t in range(tj, 2):
        e = jnp.exp(jnp.minimum(gt[t] - g_j, 0.0))
        pairs.append((t, e, k_j * e))
    return tj, rj, pairs


def _whole(tiles):
    """``_tiles``' inverse."""
    x = jnp.concatenate(tiles, 2)
    return x.reshape(x.shape[0], -1, x.shape[3])


def _in_blocks(x, rows):
    """[h, C, D] -> [h, C / rows, rows, D]."""
    h, c, d = x.shape
    return x.reshape(h, c // rows, rows, d)


def _prelude(q, k, gpre, alog, dt, scale):
    """The rule's ``q``, ``k``, ``g`` from the op's inputs (float32 [h, C,
    D]; ``alog``, ``dt`` [h, 1, D]) and what the pull-back reads again."""
    sq = jnp.sum(q * q, -1, keepdims=True)
    sk = jnp.sum(k * k, -1, keepdims=True)
    rq = jax.lax.rsqrt(jnp.maximum(sq, 1e-12))
    rk = jax.lax.rsqrt(jnp.maximum(sk, 1e-12))
    x = gpre + dt
    # ``log1p``: a gate far below zero decays by e^x a step, and 1 + e^x is
    # 1 in float32 from x = -17 down — 4096 such steps are a state 1e-3 off
    soft = jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))
    rate = -jnp.exp(alog)
    return q * (rq * scale), k * rk, rate * soft, (
        rq, rk, sq > 1e-12, sk > 1e-12, x, rate)


class _Local:
    """A chunk's local parts for the heads of a step (see ``local``)."""


def local(q, k, v, g, beta, *, with_p):
    """From the rule's operands of one chunk (float32 [h, C, D], ``beta``
    [h, C, 1]): ``gc`` the cumulative log-decay, the decayed products of
    ``kb = beta k`` and of ``q`` with ``k``, ``a`` (strictly lower) and ``p``
    (its diagonal included), ``t`` = ``(I + a)^-1`` (split),
    ``kd`` = ``k e^G``, ``qd``, ``ke`` = ``k e^{G_C - G}``, ``w``, ``u0`` and
    the decay of the whole chunk ``e^{G_C}`` [h, 1, Dk].  ``p``, ``qd`` only
    ``with_p``."""
    h, c, dk = k.shape
    n = c // SUB
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    lo = _Local()
    lo.gc = gc = _sum_along(g, (rows >= cols).astype(_BF16))
    first = jnp.broadcast_to(_in_blocks(gc, SUB)[:, :, :1],
                             (h, n, SUB, dk)).reshape(h, c, dk)
    lo.head = head = jnp.exp(gc - first)            # e^{G_i - G_n}, n i's first
    lo.kb = kb = beta * k
    lo.kbh, lo.qh = kb * head, (q * head if with_p else None)
    # pairs in different sub-blocks: split at the first row of i's
    row = _iota((c, 1), 0)
    off_a, off_q, lo.tails, lo.keys = [jnp.zeros((h, SUB, c), _F32)], \
        [jnp.zeros((h, SUB, c), _F32)], [None], [None]
    for b in range(1, n):
        at = slice(b * SUB, (b + 1) * SUB)
        tail = jnp.exp(jnp.where(row < b * SUB,
                                 gc[:, b * SUB:b * SUB + 1] - gc, _NEVER))
        lo.tails.append(tail)
        lo.keys.append(_split(k * tail))            # [h, C, Dk], 0 from i's on
        lhs = lo.kbh[:, at]
        if with_p:
            lhs = jnp.concatenate([lhs, lo.qh[:, at]], 1)
        both = _mm(lhs, lo.keys[b], _NT)            # [h, 16 | 32, C]
        off_a.append(both[:, :SUB])
        if with_p:
            off_q.append(both[:, SUB:])
    # pairs in the same sub-block: channel by channel, a column j at a time,
    # the sub-blocks' two sublane tiles apart (a column past the first tile
    # has nothing in it); and (I + a)^-1 of the sub-blocks by substitution
    tile = _iota((_HALF, 1), 0)
    at_col = _iota((n, _HALF, c), 2) - SUB * _iota((n, _HALF, c), 0)
    gt, kt, kbt = (_tiles(z) for z in (gc, k, kb))
    qt = _tiles(q) if with_p else None
    a_t = [jnp.zeros((h, n, _HALF, c), _F32)] * 2
    q_t = [jnp.zeros((h, n, _HALF, c), _F32)] * 2
    x_t = [jnp.broadcast_to((at_col == t * _HALF + tile).astype(_F32),
                            (h, n, _HALF, c)) for t in (0, 1)]
    for j in range(SUB):
        tj, rj, pairs = _column(gt, kt, j)
        for t, _, kj in pairs:
            col = jnp.where(tile + t * _HALF > j, jnp.sum(
                kbt[t] * kj, -1, keepdims=True), 0.0)    # strictly: a's rows
            a_t[t] = jnp.where(at_col == j, col, a_t[t])
            if with_p:
                q_t[t] = jnp.where(
                    (at_col == j) & (tile + t * _HALF >= j),
                    jnp.sum(qt[t] * kj, -1, keepdims=True), q_t[t])
            if j < SUB - 1 and (t == 1 or j < _HALF - 1):
                # X_i -= a_ij X_j for the rows i > j; X_j is final by now
                x_t[t] = x_t[t] - col * x_t[tj][:, :, rj:rj + 1]
    lo.a = a = jnp.concatenate(off_a, 1) + _whole(a_t)
    x, size = _whole(x_t), SUB
    while size < c:                     # [[T1, 0], [-T2 a21 T1, T2]]
        pair = (_div(rows, 2 * size) == _div(cols, 2 * size)) \
            & (_div(rows, size) != _div(cols, size))
        x_s = _split(x)
        x = x - _mm(_mm(x_s, jnp.where(pair, a, 0.0)), x_s)
        size *= 2
    lo.t = x = _split(x)
    lo.decay = decay = jnp.exp(gc)
    last = gc[:, c - 1:c]
    lo.fade = fade = jnp.exp(last - gc)
    lo.whole = jnp.exp(last)                        # [h, 1, Dk]
    lo.kd, lo.ke = k * decay, k * fade
    lo.rhs = jnp.concatenate([beta * lo.kd, beta * v], -1)
    wu = _mm(x, lo.rhs)
    lo.w, lo.u0 = wu[..., :dk], wu[..., dk:]
    if with_p:
        lo.p = jnp.concatenate(off_q, 1) + _whole(q_t)
        lo.qd = q * decay
    return lo


def _advance(st, st_s, lo):
    """``(U, St')`` of a chunk from the transposed state it starts on (and
    its split)."""
    u = lo.u0 - _mm(lo.w, st_s, _NT)
    return u, st * lo.whole + _mm(u, lo.ke, _TN)


def _rule_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, alog_ref, dt_ref,
                   hb, scale):
    dk, dv = q_ref.shape[-1] // hb, v_ref.shape[-1] // hb
    q, k, g = (_heads(r, hb, dk) for r in (q_ref, k_ref, g_ref))
    v = _heads(v_ref, hb, dv)
    q, k, g, kept = _prelude(q, k, g, _rows_apart(alog_ref, hb),
                             _rows_apart(dt_ref, hb), scale)
    return q, k, v, g, _columns(beta_ref, hb), kept


# -- the kernels --------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, alog_ref, dt_ref,
                gate_ref, gain_ref, out_ref, state_ref, starts_ref, st_scr,
                *, hb, group, n_chunks, scale, eps):
    ci = pl.program_id(2)
    dv = v_ref.shape[-1] // hb

    @pl.when(ci == 0)
    def _():
        st_scr[...] = jnp.zeros(st_scr.shape, _F32)

    @pl.when(jax.lax.rem(ci, group) == 0)
    def _():
        starts_ref[0, 0] = jnp.swapaxes(st_scr[...], 1, 2)
    q, k, v, g, beta, _ = _rule_operands(
        q_ref, k_ref, v_ref, g_ref, beta_ref, alog_ref, dt_ref, hb, scale)
    lo = local(q, k, v, g, beta, with_p=True)
    st = st_scr[...]
    st_s = _split(st)
    u, st_scr[...] = _advance(st, st_s, lo)
    o = _mm(lo.qd, st_s, _NT) + _mm(lo.p, u)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * gain_ref[...] * jax.nn.sigmoid(_heads(gate_ref, hb, dv))
    for h in range(hb):
        out_ref[0, :, h * dv:(h + 1) * dv] = o[h]

    @pl.when(ci == n_chunks - 1)
    def _():
        state_ref[0] = jnp.swapaxes(st_scr[...], 1, 2)


def _states_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, alog_ref, dt_ref,
                   all_ref, st_scr, *, hb, scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_scr[...] = jnp.zeros(st_scr.shape, _F32)

    all_ref[0, 0] = st_scr[...]
    q, k, v, g, beta, _ = _rule_operands(
        q_ref, k_ref, v_ref, g_ref, beta_ref, alog_ref, dt_ref, hb, scale)
    st = st_scr[...]
    _, st_scr[...] = _advance(st, _split(st),
                              local(q, k, v, g, beta, with_p=False))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BUDGET)


def _specs(q, v, beta, chunk, hb, at=lambda ci: ci):
    """``((B, H, Dk, Dv, chunks), the block specs of the op's inputs as the
    kernels take them)`` from ``q`` [B, T, H * Dk], ``v`` [B, T, H * Dv] and
    ``beta`` [B, H / hb, T, hb]; ``at`` maps a grid step's chunk index to
    the chunk it works on."""
    b, t = q.shape[:2]
    h = beta.shape[1] * hb
    dk, dv = q.shape[2] // h, v.shape[2] // h

    def wide(width):
        return pl.BlockSpec((1, chunk, hb * width),
                            lambda bi, hi, ci: (bi, at(ci), hi))

    def a_head(width):
        return pl.BlockSpec((1, hb, width), lambda bi, hi, ci: (hi, 0, 0))
    col = pl.BlockSpec((1, 1, chunk, hb),
                       lambda bi, hi, ci: (bi, hi, at(ci), 0))
    return (b, h, dk, dv, t // chunk), (wide(dk), wide(dv), col, a_head(dk))


def _forward(q, k, v, g, beta, alog, dt, gate, gain, *, chunk, hb, group,
             scale, eps, interpret):
    """``q``, ``k``, ``g`` [B, T, H * Dk], ``v``, ``gate`` [B, T, H * Dv] in
    the dtypes they arrive in; ``beta`` [B, H / hb, T, hb], ``alog``, ``dt``
    [H / hb, hb, Dk], ``gain`` [1, Dv] float32."""
    (b, h, dk, dv, n), (key, val, col, head) = _specs(q, v, beta, chunk, hb)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, group=group, n_chunks=n,
                          scale=scale, eps=eps),
        grid=(b, h // hb, n),
        in_specs=[key, key, val, key, col, head, head, val,
                  pl.BlockSpec((1, dv), lambda bi, hi, ci: (0, 0))],
        out_specs=[
            val,
            pl.BlockSpec((1, hb, dk, dv), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, hb, dk, dv), lambda bi, hi, ci: (
                bi, jax.lax.div(ci, group), hi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, _F32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), _F32),
                   jax.ShapeDtypeStruct((b, n // group, h, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(q, k, v, g, beta, alog, dt, gate, gain)


def _states(q, k, v, g, beta, alog, dt, *, chunk, hb, scale, interpret):
    """The transposed state every chunk starts on, [B, N, H, Dv, Dk]."""
    (b, h, dk, dv, n), (key, val, col, head) = _specs(q, v, beta, chunk, hb)
    return pl.pallas_call(
        functools.partial(_states_kernel, hb=hb, scale=scale),
        grid=(b, h // hb, n),
        in_specs=[key, key, val, key, col, head, head],
        out_specs=pl.BlockSpec((1, 1, hb, dv, dk),
                               lambda bi, hi, ci: (bi, ci, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, h, dv, dk), _F32),
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(q, k, v, g, beta, alog, dt)


def _unnorm(d, x, r, live, scale):
    """The gradient of ``x_raw`` from that of ``x = scale x_raw r``, ``r`` the
    reciprocal norm (a constant where the norm was under its floor)."""
    unit = x * (1.0 / scale)
    return (r * scale) * (d - jnp.where(
        live, unit * jnp.sum(d * unit, -1, keepdims=True), 0.0))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, alog_ref, dt_ref,
                gate_ref, gain_ref, dout_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dalog_ref,
                ddt_ref, dgate_ref, dgain_ref, dst_scr, *, hb, scale, eps):
    """One chunk, the grid's ``ci``-th from the END.  ``dst_scr`` carries
    ``dS^T`` of the state the chunk ends on.  The pull-back, with ``Kb = beta
    K``, ``Bk = beta Kd``, ``Bv = beta V`` (so ``A`` = strict(``Kb . K``
    decayed), ``W = T Bk``, ``U0 = T Bv``, ``T = (I + A)^-1``)::

        dU = P^T do + Ke dS'          dP = tril(do U^T)      dQd = do S^T
        dKe = U dS'^T                 d e^{G_C} = rowsum(dS' * S)
        dS = Qd^T do - W^T dU + Diag(e^{G_C}) dS'
        dW = -dU S^T                  dT = dW Bk^T + dU Bv^T
        dBk = T^T dW, dBv = T^T dU    dA = -T^T dT T^T (strictly lower)
        dbeta = rowsum(dBk Kd + dBv V + dKb K)

    and a decayed product ``M_ij = sum_d r_id k_jd e^{G_id - G_jd}`` pulls
    back as ``dr_i = sum_j dM_ij e_ij k_j``, ``dk_j = sum_i dM_ij e_ij r_i``,
    ``dG = r dr - k dk``."""
    ci = pl.program_id(2)
    dk_, dv_ = q_ref.shape[-1] // hb, v_ref.shape[-1] // hb

    @pl.when(ci == 0)
    def _():
        dst_scr[...] = jnp.zeros(dst_scr.shape, _F32)
        dalog_ref[...] = jnp.zeros(dalog_ref.shape, _F32)
        ddt_ref[...] = jnp.zeros(ddt_ref.shape, _F32)
        dgain_ref[...] = jnp.zeros(dgain_ref.shape, _F32)

    q, k, v, g, beta, (rq, rk, live_q, live_k, x, rate) = _rule_operands(
        q_ref, k_ref, v_ref, g_ref, beta_ref, alog_ref, dt_ref, hb, scale)
    lo = local(q, k, v, g, beta, with_p=True)
    h, c, _ = k.shape
    n = c // SUB
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    st, dst = st_ref[0, 0], dst_scr[...]
    st_s, dst_s = _split(st), _split(dst)
    u = lo.u0 - _mm(lo.w, st_s, _NT)
    u_s, p_s = _split(u), _split(lo.p)
    o = _mm(lo.qd, st_s, _NT) + _mm(p_s, u_s)
    # the finish
    dout = _heads(dout_ref, hb, dv_)
    gate = jax.nn.sigmoid(_heads(gate_ref, hb, dv_))
    norm = jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    y = o * norm
    dgate = dout * y * gain_ref[...] * gate * (1.0 - gate)
    dgain_ref[0, 0] += jnp.sum(dout * y * gate, 1)
    dy = dout * gain_ref[...] * gate
    do_s = _split((dy - y * jnp.mean(dy * y, -1, keepdims=True)) * norm)
    # the step of the state and the outputs
    du_s = _split(_mm(p_s, do_s, _TN) + _mm(lo.ke, dst_s, _NT))
    dp = jnp.where(rows >= cols, _mm(do_s, u_s, _NT), 0.0)
    dqd, dke = _mm(do_s, st_s), _mm(u_s, dst_s)
    dwhole = jnp.sum(dst * st, 1, keepdims=True)
    dw = -_mm(du_s, st_s)
    dst_scr[...] = dst * lo.whole + _mm(
        tuple(jnp.concatenate(z, 1) for z in zip(do_s, du_s)),
        jnp.concatenate([lo.qd, -lo.w], 1), _TN)
    # W, U0 and the inverse
    dwu_s = tuple(jnp.concatenate(z, -1) for z in zip(_split(dw), du_s))
    drhs = _mm(lo.t, dwu_s, _TN)
    dbk, dbv = drhs[..., :dk_], drhs[..., dk_:]
    da = jnp.where(rows > cols, -_mm(
        lo.t, _mm(_mm(dwu_s, lo.rhs, _NT), lo.t, _NT), _TN), 0.0)
    # the decayed products: pairs in different sub-blocks
    row = _iota((c, 1), 0)
    d_kbh, d_qh = [jnp.zeros((h, SUB, dk_), _F32)], \
        [jnp.zeros((h, SUB, dk_), _F32)]
    d_keys = jnp.zeros((h, c, dk_), _F32)
    for b in range(1, n):
        at = slice(b * SUB, (b + 1) * SUB)
        dboth_s = _split(jnp.concatenate([da[:, at], dp[:, at]], 1))
        d_lhs = _mm(dboth_s, lo.keys[b])                    # [h, 32, Dk]
        d_kbh.append(d_lhs[:, :SUB])
        d_qh.append(d_lhs[:, SUB:])
        d_keys = d_keys + lo.tails[b] * _mm(
            dboth_s, jnp.concatenate([lo.kbh[:, at], lo.qh[:, at]], 1), _TN)
    d_kb = jnp.concatenate(d_kbh, 1) * lo.head      # kb: the rows of a
    d_q = jnp.concatenate(d_qh, 1) * lo.head
    # pairs in the same sub-block, a column j at a time
    tile = _iota((_HALF, 1), 0)
    at_col = _iota((n, _HALF, c), 2) - SUB * _iota((n, _HALF, c), 0)
    gt, kt, kbt, qt, dat, dpt = (_tiles(z) for z in (
        lo.gc, k, lo.kb, q, da, dp))
    q_t = [jnp.zeros((h, n, _HALF, dk_), _F32)] * 2
    kb_t = [jnp.zeros((h, n, _HALF, dk_), _F32)] * 2
    keys_t = [jnp.zeros((h, n, _HALF, dk_), _F32)] * 2
    for j in range(SUB):
        tj, rj, pairs = _column(gt, kt, j)
        to_j = 0.0
        for t, e, kj in pairs:
            col_q = jnp.sum(jnp.where(at_col == j, dpt[t], 0.0), -1,
                            keepdims=True)
            col_a = jnp.sum(jnp.where(at_col == j, dat[t], 0.0), -1,
                            keepdims=True)
            q_t[t] = q_t[t] + col_q * kj
            kb_t[t] = kb_t[t] + col_a * kj
            to_j = to_j + (col_q * qt[t] + col_a * kbt[t]) * e
        keys_t[tj] = jnp.where(tile == rj, jnp.sum(to_j, 2, keepdims=True),
                               keys_t[tj])
    d_q = d_q + _whole(q_t)
    d_kb = d_kb + _whole(kb_t)
    d_keys = d_keys + _whole(keys_t)
    # the decays, and back through the cumulative sum
    # (the last row's ``Ke`` has no decay in it: e^{G_C - G_C})
    d_fade = jnp.where(row < c - 1, dke * lo.ke, 0.0)
    d_last = jnp.sum(d_fade, 1, keepdims=True) + dwhole * lo.whole
    d_gc = q * d_q + lo.kb * d_kb - k * d_keys + dbk * (beta * lo.kd) \
        + dqd * lo.qd - d_fade + jnp.where(row == c - 1, d_last, 0.0)
    dbeta = jnp.sum(dbk * lo.kd + d_kb * k, -1, keepdims=True) \
        + jnp.sum(dbv * v, -1, keepdims=True)
    d_g = _sum_along(d_gc, (rows <= cols).astype(_BF16))
    d_x = d_g * rate * jax.nn.sigmoid(x)
    dalog_ref[0, 0] += jnp.sum(d_g * g, 1)
    ddt_ref[0, 0] += jnp.sum(d_x, 1)
    d_q = _unnorm(d_q + dqd * lo.decay, q, rq, live_q, scale)
    d_k = _unnorm(d_keys + beta * (d_kb + dbk * lo.decay) + dke * lo.fade,
                  k, rk, live_k, 1.0)
    d_v = beta * dbv
    lane = _iota((c, hb), 1)
    d_beta = jnp.zeros((c, hb), _F32)
    for i in range(hb):
        at_dk = slice(i * dk_, (i + 1) * dk_)
        at_dv = slice(i * dv_, (i + 1) * dv_)
        dq_ref[0, :, at_dk] = d_q[i].astype(dq_ref.dtype)
        dk_ref[0, :, at_dk] = d_k[i].astype(dk_ref.dtype)
        dg_ref[0, :, at_dk] = d_x[i].astype(dg_ref.dtype)
        dv_ref[0, :, at_dv] = d_v[i].astype(dv_ref.dtype)
        dgate_ref[0, :, at_dv] = dgate[i].astype(dgate_ref.dtype)
        d_beta = jnp.where(lane == i, dbeta[i], d_beta)
    dbeta_ref[0, 0] = d_beta


def _backward(q, k, v, g, beta, alog, dt, gate, gain, dout, sts, *, chunk,
              hb, scale, eps, interpret):
    """The operands of ``_forward``, ``dout`` [B, T, H * Dv] and ``_states``'
    ``sts``; the array gradients in their inputs' dtypes, ``dbeta`` as
    ``beta`` came, the three parameters' as sums a batch row and head."""
    last = q.shape[1] // chunk - 1
    (b, h, dk, dv, n), (key, val, col, head) = _specs(
        q, v, beta, chunk, hb, lambda ci: last - ci)

    def sums(width):
        return pl.BlockSpec((1, 1, hb, width),
                            lambda bi, hi, ci: (bi, hi, 0, 0))

    def summed(width):
        return jax.ShapeDtypeStruct((b, h // hb, hb, width), _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, scale=scale, eps=eps),
        grid=(b, h // hb, n),
        in_specs=[key, key, val, key, col, head, head, val,
                  pl.BlockSpec((1, dv), lambda bi, hi, ci: (0, 0)), val,
                  pl.BlockSpec((1, 1, hb, dv, dk),
                               lambda bi, hi, ci: (bi, n - 1 - ci, hi, 0, 0))],
        out_specs=[key, key, val, key, col, sums(dk), sums(dk), val,
                   sums(dv)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g, beta)]
        + [summed(dk), summed(dk),
           jax.ShapeDtypeStruct(gate.shape, gate.dtype), summed(dv)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(q, k, v, g, beta, alog, dt, gate, gain, dout, sts)


_run = functools.partial(run_traced, "gated_delta_rule")


def _operands(q, k, v, g, beta, alog, dt, hb):
    """The op's inputs as the kernels take them: the heads folded into the
    lanes (no copy), ``beta`` a step's heads side by side, ``ALog`` a row a
    head as ``DtBias`` is."""
    b, t, h, dk = q.shape

    def fold(x):
        return x.reshape(b, t, -1)
    per_head = (h // hb, hb, dk)
    return (fold(q), fold(k), fold(v), fold(g),
            jnp.moveaxis(beta.astype(_F32).reshape(b, t, h // hb, hb), 2, 1),
            jnp.broadcast_to(alog.astype(_F32)[:, None], (h, dk)).reshape(
                per_head), dt.astype(_F32).reshape(per_head))


def forward(q, k, v, g, beta, alog, dt, gate, gain, *, chunk, group, scale,
            eps, interpret=False):
    """The op's ``(Out [B, T, H, Dv], State [B, H, Dk, Dv], Starts [B, N /
    group, H, Dk, Dv])``, float32, from its nine inputs."""
    b, t, h, dk = q.shape
    dv = v.shape[3]
    hb = heads_per_step(h, dk, dv, chunk)
    out, state, starts = _run(
        _forward, _operands(q, k, v, g, beta, alog, dt, hb)
        + (gate.reshape(b, t, -1), gain.astype(_F32).reshape(1, dv)),
        chunk=int(chunk), hb=hb, group=int(group), scale=float(scale),
        eps=float(eps), interpret=bool(interpret))
    return out.reshape(b, t, h, dv), state, starts


def backward(q, k, v, g, beta, alog, dt, gate, gain, dout, *, chunk, scale,
             eps, interpret=False):
    """The gradients of the op's nine inputs, in their dtypes, from them and
    ``dout`` [B, T, H, Dv]: the states the chunks start on made again
    (``_states``), then the chunks from the last (``_backward``)."""
    b, t, h, dk = q.shape
    dv = v.shape[3]
    hb = heads_per_step(h, dk, dv, chunk)
    statics = dict(chunk=int(chunk), hb=hb, scale=float(scale),
                   interpret=bool(interpret))
    ops = _operands(q, k, v, g, beta, alog, dt, hb)
    sts, = _run(_states, ops, **statics)
    dq, dk_, dv_, dg, dbeta, dalog, ddt, dgate, dgain = _run(
        _backward, ops + (gate.reshape(b, t, -1),
                          gain.astype(_F32).reshape(1, dv),
                          dout.reshape(b, t, -1), sts),
        eps=float(eps), **statics)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape),
            jnp.moveaxis(dbeta, 1, 2).reshape(beta.shape).astype(beta.dtype),
            jnp.sum(dalog, (0, 3)).reshape(h).astype(alog.dtype),
            jnp.sum(ddt, 0).reshape(h, dk).astype(dt.dtype),
            dgate.reshape(gate.shape),
            jnp.sum(dgain, (0, 1, 2)).astype(gain.dtype))
