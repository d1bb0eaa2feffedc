"""The gated delta rule with a decay for every key channel (Kimi Delta
Attention's recurrence), as a chunked op with its gradient op.

``gated_delta_rule`` — the rule: for ``q``, ``k`` ``[B, T, H, Dk]``, ``v`` ``[B, T, H,
Dv]``, the log-decay ``g`` ``[B, T, H, Dk]`` (``<= 0``) and ``beta`` ``[B, T,
H]``, all float32, a head at a time::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    Out_t = S_t^T q_t                                    S_0 = 0

``Out`` ``[B, T, H, Dv]`` float32; ``State`` ``[B, H, Dk, Dv]`` the state
after the last step; ``Starts`` ``[B, N / group, H, Dk, Dv]`` the state every
GROUP of chunks starts on (``N = ceil(T / chunk)`` chunks, ``group`` the
largest divisor of ``N`` up to ``GROUP`` = 16), which is what the gradient op
makes a group again from: 8 MB a layer at 4096 steps of 32 heads; no ``[T,
Dk, Dv]`` of states is ever kept, nor a chunk's.

The op is a delta-attention mixer's: what the mixer does to the rule's
operands and to its result is INSIDE it, in float32 from whatever the
projections and convolutions handed over (so a mixed-precision program rounds
none of it, and a step keeps the bf16 inputs and not five float32 copies of
them a layer; as Fluid ops around the rule they kept 1.3 GB a layer and the
delta-attention cell's step did not fit its chip).  All nine inputs are
required.  BEFORE the rule: ``Q`` and ``K`` are L2-normalised a head, ``x *
rsqrt(max(sum x^2, 1e-12))`` as ``layers.l2_normalize``, and ``q`` times the
attribute ``scale`` (a mixer's ``Dk^-0.5``); ``G`` is the gate's
PRE-activation and ``g = -exp(ALog) * softplus(G + DtBias)`` with ``ALog``
``[H]`` and ``DtBias`` ``[H, Dk]`` (the activation a decay has wherever the
rule is used, as ``selective_scan`` owns its step size's softplus).
``Beta``'s sigmoid is the caller's, in float32 (a value a head: nothing to
keep).  AFTER the rule, with ``OutGate`` ``[B, T, H, Dv]`` (a pre-activation)
and ``OutNorm`` ``[Dv]``: ``Out = rms_norm(o; OutNorm, epsilon) *
sigmoid(OutGate)`` a head, the mixer's gated head-wise norm, so that a step
keeps ``Out`` alone and not ``o``, its norm and the gate's two float32 copies
beside it.  The bare rule is ``rule_xla``, a function and no op: no program
builds it.  Every product inside takes float32 operands in three bf16
passes (``Precision.HIGH``), NOT ``highest``'s six: the state after 4096
steps differs by 1.4e-5 of its norm between the two (my chip run, PR 46).

ONE chunked body, matrix products.  Within a chunk of ``C`` steps that starts
on ``S``, with ``G_i`` the cumulative log-decay up to and with step ``i``::

    A_ij = beta_i (k_i * exp(G_i - G_j)) . k_j        (j < i, else 0)
    T = (I + A)^-1 Diag(beta)      W = T (K * exp(G))      U0 = T V
    P_ij = (q_i * exp(G_i - G_j)) . k_j               (j <= i, else 0)
    U = U0 - W S        Out = (Q * exp(G)) S + P U
    S' = Diag(exp(G_C)) S + (K * exp(G_C - G))^T U

Both lines that hold ``S`` are affine in it: ``S' = M S + N`` and ``Out = R S
+ Z`` (``_local`` gives the four, for ALL chunks at once: batched products).
Only ``S' = M S + N`` runs chunk after chunk — a ``lax.scan`` whose step is
one ``[Dk, Dk] x [Dk, Dv]`` product a head — and the outputs are one more
batched product over the states the chunks start on.  ``exp(G_i - G_j)`` is
never split into ``exp(G_i) exp(-G_j)`` over a whole chunk, whose second
factor overflows once a chunk's decay passes ``e^-88``: a chunk is cut into
sub-blocks of 16; a pair in DIFFERENT sub-blocks splits at the first row
``n`` of ``i``'s sub-block, ``exp(G_i - G_n) exp(G_n - G_j)`` with ``j < n <=
i``, both factors at most 1 (a product again); a pair in the SAME sub-block
takes its ``exp(G_i - G_j)`` channel by channel (16 x 16 x Dk elementwise
work a sub-block, one exponent, never positive).  ``(I + A)^-1`` is forward
substitution on the 16 x 16 diagonal blocks and block products up to ``C``:
no series whose terms cancel.

The gradient op walks the GROUPS of chunks backwards from the forward op's
own ``Starts``: a group is made again from the state it starts on — its
chunk-local parts, its states, its outputs — and pulled back by its
``jax.vjp`` (all chunks at once would hold 1.9 GB of chunk-local parts at
4096 steps of 32 heads; a group of 16 holds a quarter).  It reads its inputs
behind an ``optimization_barrier``, or XLA merges the recomputation with the
forward op's own and keeps that op's float32 operands alive in between.

Bodies by the op's own rule (``_kernels``): on a TPU, no mesh, ``Dk`` and
``Dv`` whole lane tiles, ``T`` whole chunks of at most 128 steps, both ops
lower to the kernels of ``ops/pallas/gated_delta_rule.py``
(``gated_delta_rule:chunked``, ``gated_delta_rule_grad:chunked`` in
``kernel_bodies``; ``kernel_traces["gated_delta_rule"]``), which run the SAME
chunk algebra with a chunk's local parts and the running state in VMEM: the
forward 2.2 ms a layer at the delta-attention cell's shape where this body
takes 10.5, the backward 6.1 for 35.1 (the ops alone, my chip runs, PR 47).
Everywhere
else — the CPU, a mesh, ``FLAGS_pallas_kernels=False``, a ragged last chunk
(this body pads it), narrower heads — the XLA body here
(``gated_delta_rule:xla``, ``gated_delta_rule_grad:xla``), which is also the
definition the kernels' tests hold them to.
"""

import jax
import jax.numpy as jnp

from ..registry import (register_op, set_output, in_var,
                        _generic_grad_infer)

# rows of a sub-block: the span over which a pair's decay is taken channel by
# channel, and the size of the blocks ``(I + A)^-1`` starts from
SUB = 16
# chunks whose local parts the gradient op holds at once
GROUP = 16
# the products' precision: three bf16 passes of float32 operands, exact to
# ~1e-5 of a result (my chip run, PR 46: the state after 4096 steps differs
# from ``highest``'s six passes by 1.4e-5 of its norm; one pass by 2.9e-3)
_HI = jax.lax.Precision.HIGH
_SLOTS = ("Q", "K", "V", "G", "Beta", "ALog", "DtBias", "OutGate", "OutNorm")


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _infer(op, block):
    q, k, v = (in_var(op, block, s) for s in ("Q", "K", "V"))
    g, beta = in_var(op, block, "G"), in_var(op, block, "Beta")
    chunk = int(op.attrs["chunk"])
    if len(q.shape) != 4 or tuple(k.shape) != tuple(q.shape) \
            or tuple(g.shape) != tuple(q.shape) \
            or tuple(v.shape[:3]) != tuple(q.shape[:3]) \
            or tuple(beta.shape) != tuple(q.shape[:3]):
        raise ValueError(
            "gated_delta_rule expects Q, K, G [B, T, H, Dk], V [B, T, H, Dv] "
            "and Beta [B, T, H]; got Q %s, K %s, V %s, G %s, Beta %s"
            % (q.shape, k.shape, v.shape, g.shape, beta.shape))
    if chunk < SUB or chunk % SUB or (chunk // SUB) & (chunk // SUB - 1):
        raise ValueError("gated_delta_rule: chunk is %d times a power of "
                         "two, got %d" % (SUB, chunk))
    b, t, h, dk = q.shape
    for slot, shape in (("ALog", (h,)), ("DtBias", (h, dk)),
                        ("OutGate", tuple(v.shape)),
                        ("OutNorm", (v.shape[3],))):
        if tuple(in_var(op, block, slot).shape) != shape:
            raise ValueError("gated_delta_rule: %s is %s, got %s" % (
                slot, shape, in_var(op, block, slot).shape))
    set_output(op, block, "Out", (b, t, h, v.shape[3]), "float32")
    set_output(op, block, "State", (b, h, dk, v.shape[3]), "float32")
    n = -(-t // chunk)
    set_output(op, block, "Starts", (b, n // _group(n), h, dk, v.shape[3]),
               "float32")


# -- the chunk-local parts ----------------------------------------------------

@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower ``a`` [..., C, C]: forward
    substitution on the ``SUB``-wide diagonal blocks (``t_i = e_i - sum_{j <
    i} a_ij t_j``, row after row, the blocks side by side), then ``[[T1, 0],
    [-T2 a21 T1, T2]]`` block by block up to ``C``.  Its gradient is the
    inverse's own, ``da = -T^T dT T^T``: two products, no pass back through
    the rows."""
    n = a.shape[-1] // SUB
    eye = jnp.eye(SUB, dtype=a.dtype)
    diag = jnp.stack([a[..., b * SUB:(b + 1) * SUB, b * SUB:(b + 1) * SUB]
                      for b in range(n)], -3)           # [.., n, SUB, SUB]
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-1])]
    for i in range(1, SUB):
        rows.append(eye[i] - jnp.sum(
            diag[..., i, :i, None] * jnp.stack(rows, -2), -2))
    inv = jnp.stack(rows, -2)
    parts, size = [inv[..., b, :, :] for b in range(n)], SUB
    while len(parts) > 1:
        merged = []
        for b in range(0, len(parts), 2):
            t1, t2 = parts[b], parts[b + 1]
            a21 = a[..., (b + 1) * size:(b + 2) * size,
                    b * size:(b + 1) * size]
            merged.append(jnp.concatenate([
                jnp.concatenate([t1, jnp.zeros_like(t1)], -1),
                jnp.concatenate([-_mm(_mm(t2, a21), t1), t2], -1)], -2))
        parts, size = merged, 2 * size
    return parts[0]


def _inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(_mm(tt, dt), tt),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


@jax.checkpoint
def _decayed_products(r, k, gc):
    """``M_ij = (r_i * exp(G_i - G_j)) . k_j`` for ``j <= i`` (0 above the
    diagonal) of one chunk's rows ``r``, keys ``k`` and cumulative log-decay
    ``gc``, each [..., C, D]; no exponent is ever positive."""
    c, d = r.shape[-2:]
    n = c // SUB
    lead = r.shape[:-2]
    rs, ks, gs = (x.reshape(lead + (n, SUB, d)) for x in (r, k, gc))
    first = gs[..., :1, :]                              # [.., n, 1, D]
    # pairs in different sub-blocks: split at the first row of i's
    rows = rs * jnp.exp(gs - first)                     # [.., n, SUB, D]
    before = (jnp.arange(c)[None, :]
              < (jnp.arange(n) * SUB)[:, None])[..., None]   # [n, C, 1]
    keys = k[..., None, :, :] * jnp.exp(jnp.where(
        before, first - gc[..., None, :, :], -jnp.inf))      # [.., n, C, D]
    off = _mm(rows, jnp.swapaxes(keys, -1, -2))         # [.., n, SUB, C]
    # pairs in the same sub-block: channel by channel
    low = (jnp.arange(SUB)[:, None] >= jnp.arange(SUB)[None, :])[..., None]
    same = jnp.sum(
        rs[..., :, None, :] * ks[..., None, :, :] * jnp.exp(jnp.where(
            low, gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf)), -1)
    same = same[..., None, :] * jnp.eye(n, dtype=r.dtype)[:, None, :, None]
    return off.reshape(lead + (c, c)) + same.reshape(lead + (c, c))


def _local(q, k, v, g, beta):
    """What a chunk does to the state ``S`` it starts on and to its outputs,
    for arrays [..., C, D] (``beta`` [..., C]): ``(M, N, R, Z)`` with ``S' =
    M S + N`` and ``Out = R S + Z`` — the module's equations with ``U``
    taken out: ``M = Diag(exp(G_C)) - Ke^T W``, ``N = Ke^T U0`` (``Ke = K *
    exp(G_C - G)``), ``R = Q * exp(G) - P W``, ``Z = P U0``."""
    c, dk = q.shape[-2:]
    gc = jnp.cumsum(g, -2)
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    a = jnp.where(strict, beta[..., None] * _decayed_products(k, k, gc), 0.0)
    t = _unit_lower_inverse(a) * beta[..., None, :]
    decay = jnp.exp(gc)
    last = gc[..., -1:, :]
    w, u0 = _mm(t, k * decay), _mm(t, v)
    p = _decayed_products(q, k, gc)
    ket = jnp.swapaxes(k * jnp.exp(last - gc), -1, -2)
    return (jnp.exp(last)[..., 0, :, None] * jnp.eye(dk, dtype=q.dtype)
            - _mm(ket, w), _mm(ket, u0), q * decay - _mm(p, w), _mm(p, u0))


def _chunks(s, loc, finish=lambda out: out):
    """The chunks of ``loc`` (chunks first) from the state ``s`` [B, H, Dk,
    Dv] the first starts on: ``(the state the last ends on, the state each
    starts on, their outputs [N, B, H, C, Dv])``.  Only ``S' = M S + N``
    runs chunk after chunk."""
    m, n, r, z = loc

    def step(s, mn):
        return _mm(mn[0], s) + mn[1], s
    end, starts = jax.lax.scan(step, s, (m, n))
    return end, starts, finish(_mm(r, starts) + z)


# -- layout: [B, T, H, D] <-> chunks first [N, B, H, C, D] --------------------

def _by_chunks(x, chunk):
    """[B, T, H, ..] -> [N, B, H, C, ..], zeros after ``T`` (a padded step
    has ``k = v = q = 0``, ``g = 0`` and ``beta = 0``: it leaves the state
    as it is)."""
    b, t = x.shape[:2]
    n = -(-t // chunk)
    x = jnp.pad(x, [(0, 0), (0, n * chunk - t)] + [(0, 0)] * (x.ndim - 2))
    x = x.reshape((b, n, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def _from_chunks(x, t):
    """[N, B, H, C, D] -> [B, T, H, D]."""
    x = jnp.moveaxis(jnp.moveaxis(x, 0, 1), 2, 3)
    return x.reshape((x.shape[0], -1) + x.shape[3:])[:, :t]


def _prelude(q, k, v, g, beta, a_log, dt_bias, gate, gain, scale):
    """The rule's five operands and the output's gate and gain in float32
    from the op's nine inputs: see the module's text."""
    q, k, v, g, beta, a_log, dt_bias, gate, gain = (
        x.astype(jnp.float32)
        for x in (q, k, v, g, beta, a_log, dt_bias, gate, gain))
    q, k = (x * jax.lax.rsqrt(jnp.maximum(
        jnp.sum(x * x, -1, keepdims=True), 1e-12)) for x in (q, k))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(g + dt_bias)
    return q * jnp.float32(scale), k, v, g, beta, gate, gain


def _finish(o, gate, gain, eps):
    """``rms_norm(o; gain) * sigmoid(gate)`` over the last axis."""
    return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * gain * jax.nn.sigmoid(gate)


def _inputs(ins):
    return tuple(ins[s][0] for s in _SLOTS)


def _group(n):
    """Chunks a group: the largest divisor of ``n`` chunks up to ``GROUP``."""
    return max(d for d in range(1, GROUP + 1) if n % d == 0)


def rule_xla(q, k, v, g, beta, chunk):
    """The bare rule's XLA body on [B, T, H, D] float32 operands: ``(o, the
    final state [B, H, Dk, Dv], the groups' starting states [B, N / group,
    H, Dk, Dv])``."""
    b, t, h = q.shape[:3]
    loc = _local(*(_by_chunks(x, chunk) for x in (q, k, v, g, beta)))
    state, starts, outs = _chunks(
        jnp.zeros((b, h, q.shape[-1], v.shape[-1]), jnp.float32), loc)
    return (_from_chunks(outs, t), state,
            jnp.moveaxis(starts[::_group(starts.shape[0])], 0, 1))


# the platforms the chunked kernels are the body on
_KERNEL_PLATFORMS = ("tpu",)


def _kernels(ctx, op_type, ins, chunk):
    """Whether the kernels of ``ops/pallas/gated_delta_rule.py`` are the
    body; notes which under ``op_type``."""
    from ..compile_cache import note_kernel_body
    from .pallas import kernel_allowed, gated_delta_rule as kernels

    chunked = kernel_allowed(ctx, _KERNEL_PLATFORMS) \
        and getattr(ctx, "mesh", None) is None \
        and kernels.supported(ins["Q"][0].shape, ins["V"][0].shape, chunk)
    note_kernel_body(op_type, "chunked" if chunked else "xla")
    return chunked


def _compute(ins, attrs, ctx, op_index):
    chunk = int(attrs["chunk"])
    if _kernels(ctx, "gated_delta_rule", ins, chunk):
        from .pallas import interpret_mode, gated_delta_rule as kernels
        out, state, starts = kernels.forward(
            *_inputs(ins), chunk=chunk,
            group=_group(ins["Q"][0].shape[1] // chunk),
            scale=attrs["scale"], eps=attrs["epsilon"],
            interpret=interpret_mode(ctx))
        return {"Out": out, "State": state, "Starts": starts}
    *ops, gate, gain = _prelude(*_inputs(ins), attrs["scale"])
    out, state, starts = rule_xla(*ops, chunk)
    return {"Out": _finish(out, gate, gain, attrs["epsilon"]),
            "State": state, "Starts": starts}


def rule_grad_xla(ops, gate, gain, eps, starts, dout):
    """The gradients of the rule's five chunked operands ``ops``
    (``_by_chunks``' layout), of ``gate`` (chunked as they) and of ``gain``
    — seven — from the forward's ``starts`` [N / group, B, H, Dk, Dv] and
    ``dout`` [N, B, H, C, Dv]: group after group of chunks from the last,
    each made again from the state it starts on and pulled back by its
    ``jax.vjp``."""
    n, group = dout.shape[0], dout.shape[0] // starts.shape[0]

    def grouped(x):
        return x.reshape((n // group, group) + x.shape[1:])

    def forward(s, ops_g, gate_g, gain):
        end, _, out = _chunks(s, _local(*ops_g), lambda out: _finish(
            out, gate_g, gain, eps))
        return end, out

    def back(ds, inp):
        s, ops_g, gate_g, dout_g = inp
        _, pull = jax.vjp(forward, s, ops_g, gate_g, gain)
        ds, *rest = pull((ds, dout_g))
        return ds, rest
    _, (grads, dgate, dgain) = jax.lax.scan(
        back, jnp.zeros_like(starts[0]),
        (starts, tuple(grouped(x) for x in ops), grouped(gate),
         grouped(dout)), reverse=True)
    return tuple(d.reshape((n,) + d.shape[2:])
                 for d in grads + (dgate,)) + (jnp.sum(dgain, 0),)


def _grad_compute(ins, attrs, ctx, op_index):
    if not ins.get("GRAD::Out") or not ins.get("Out::Starts"):
        raise ValueError("gated_delta_rule_grad needs Out's gradient and "
                         "the forward op's Starts")
    dout, starts = ins["GRAD::Out"][0], ins["Out::Starts"][0]
    chunk = int(attrs["chunk"])
    if _kernels(ctx, "gated_delta_rule_grad", ins, chunk):
        from .pallas import interpret_mode, gated_delta_rule as kernels
        grads = kernels.backward(
            *_inputs(ins), dout, chunk=chunk, scale=attrs["scale"],
            eps=attrs["epsilon"], interpret=interpret_mode(ctx))
        return {"GRAD::" + slot: [d] for slot, d in zip(_SLOTS, grads)}
    # behind a barrier: XLA would otherwise merge this recomputation with
    # the forward op's own and keep ~0.4 GB of float32 operands and
    # chunk-local parts a layer alive from the forward to here
    raw = jax.lax.optimization_barrier(_inputs(ins))
    (*ops, gate, gain), pull = jax.vjp(
        lambda *raw: _prelude(*raw, attrs["scale"]), *raw)
    *grads, dgain = rule_grad_xla(
        tuple(_by_chunks(x, chunk) for x in ops), _by_chunks(gate, chunk),
        gain, attrs["epsilon"], jnp.moveaxis(starts, 1, 0),
        _by_chunks(dout.astype(jnp.float32), chunk))
    grads = pull(tuple(
        _from_chunks(d if d.ndim == 5 else d[..., None],
                     x.shape[1]).reshape(x.shape)
        for d, x in zip(grads, ops + [gate])) + (dgain,))
    return {"GRAD::" + slot: [d] for slot, d in zip(_SLOTS, grads)}


register_op("gated_delta_rule", list(_SLOTS), ["Out", "State", "Starts"],
            infer=_infer, compute=_compute)

# the gradient op the default grad maker emits: group after group backwards
# from the forward's own group-start states
register_op("gated_delta_rule_grad", (), (), infer=_generic_grad_infer,
            compute=_grad_compute, grad=None,
            doc="gradient of gated_delta_rule")
