"""Fused attention op — one op, one algorithm, four bodies.

Capability parity target: the reference's only attention implementation,
``nets.scaled_dot_product_attention`` (``python/paddle/fluid/nets.py:323``) —
batched QK^T, softmax, optional dropout on the weights, PV.  TPU-first
redesign: a body chosen at trace time from what the op can observe (platform,
mesh, shapes), with identical semantics in every body (same structural masks,
same counter-hash dropout mask), so the choice changes schedule, not math.
Each body with its gradient, in the order the op asks:

* **streamed** — on a TPU, one device, self-attention with grouped-query
  heads (K/V of ``H / g`` heads), a ``Selected`` key set, values narrower
  than the keys, or plain heads the layer marked ``keep_lse``
  (``streams_plain_heads``): the blockwise kernels that stream K/V by blocks
  and apply the selection per block (``ops/pallas/streamed_attention.py``);
  the [B, H, T, T] scores of such a model's long rows do not fit HBM, so
  there is nothing to weigh it against.  A ``window`` attribute (causal
  self-attention over each query's nearest ``window`` keys: key ``s`` counts
  for query ``t`` iff ``t - window < s <= t``) runs on this body too, where a
  block of keys wholly outside the window is neither fetched nor computed.
  The forward hands the gradient op its output and the rows' log-sum-exp
  (the op's ``LSE`` output); the gradient is the kernels' own backward on
  them.  **In place** (``streamed_inplace``): the same kernels over the
  projections' own layout — the two operand forms below — where the rules
  ``_in_place_applicable`` (latent attention) and ``_grouped_body``
  (grouped heads) take it.
* **ring** — the mesh has a populated ``sp`` axis the sequence dims divide:
  sequence-parallel ring attention (``parallel/ring_attention.py``).  The
  gradient differentiates the body (``jax.vjp``).
* **packed** — on a TPU, when one batch row's blocks for the backward fit
  the VMEM budget (``ops/pallas/packed_attention.supported``: sequences up
  to 384 at H*D = 512 in bf16) and the shape is not the suffix-causal
  decode shape: one Pallas kernel over ``[B, T, H*D]``, the layout the
  q/k/v projections write.  The op merges the heads on entry and splits
  them on exit; against the model's own ``transpose(reshape(fc(x)))`` /
  ``reshape(transpose(.))`` these are a transpose of a transpose and a
  reshape of a reshape, which XLA removes, forward and in the gradient ops.
  Under a mesh the kernel runs per shard (``shard_map``: batch over the
  data axes, whole heads over ``tp``).  The gradient is the one backward
  kernel over the program's own Q, K, V and dO.
* **xla** — everything else, and every CPU trace: the XLA body
  (``ops/attention_xla.reference_attention``), which an op that keeps the
  log-sum-exp also falls back to.  The gradient differentiates the body.

A kernel is chosen by its rule alone (``_streamed_applicable``,
``_in_place_applicable``, ``_grouped_body``, ``_packed_applicable``, through
``ops.pallas.kernel_allowed``);
``FLAGS_pallas_kernels=False`` is the operator's one switch against all of
them ("no Pallas").  Which body a trace took is counted in
``compile_cache.stats()["kernel_bodies"]`` (``fused_attention:<body>``; the
streamed body's gradient ``fused_attention_grad:streamed_fused`` — one
backward kernel — or ``:streamed`` — dQ and dK/dV —, in place
``fused_attention:streamed_inplace`` and
``fused_attention_grad:streamed_fused_inplace``; and the heads a grid
step serves ``streamed_step:KxG`` forward, ``streamed_grad_step:KxG``
backward).

Masking is structural: an optional per-batch valid-key count ``KLen`` [B]
(the ``<name>@LEN`` companion of the key sequence) and a ``causal`` attr —
the two shapes every Transformer mask reduces to — and, for learned sparse
attention, an optional ``Selected`` input: per query the set of keys it
may read, as the packed bit mask of ``ops/sparse_select.py`` ([B, Tq, W]
int32); an unselected key contributes exactly nothing, in every body.
K and V may carry fewer heads than Q (grouped-query attention): query head
``h`` reads K/V head ``h // (H / Hkv)``.  V may be narrower or wider than
Q and K (``[B, Hkv, Tk, Dv]``; latent attention's 192-wide keys over
128-wide values): ``Out`` is ``[B, H, Tq, Dv]``, and the bodies are the
streamed kernel and the XLA one.  **The projections' layout** is
the op's second operand form, told from what it observes — rank-3 ``Q``
with the attributes ``n_head`` and ``v_dim`` — for latent attention as its
three projections write it: ``Q`` ``[B, T, H * (nope + rope)]``, a head's
columns ``[q_nope | q_rope]``; ``K`` the key/value projection's output
whole, ``[B, T, H * (nope + v_dim)]``, a head's columns ``[k_nope | v]``,
and no ``V`` (ONE variable read once, so Fluid's backward makes one gradient
array and no ``sum`` of two); ``KShared`` ``[B, T, rope]``, the one key
part every head reads; ``rope_theta``, by which the OP rotates ``q_rope``
and ``KShared`` (interleaved pairs; absent: no rotation).  ``Out`` is ``[B,
T, H * v_dim]``, what the output projection reads, ``LSE`` ``[B, H, T, 1]``;
the gradient op returns dQ, dK (keys' and values' columns together) and
dKShared in the same layouts.  Its XLA body IS the definition: the
composition a model wrote before — split, rotate, broadcast, join,
transpose, ``reference_attention`` — and its gradient differentiates that.
Its kernel body addresses the three arrays where they lie
(``streamed_attention``'s section of that name): nothing is materialised
between a projection and the kernel, the rotation of ``q_rope`` runs on the
query block inside the kernel and that of ``KShared`` (1 MB) by XLA inside
the op's scope.  The split score's second product saves no MXU pass; the
form is there for the ~50 ms of copies a step it removes (PERF.md 6.25).
**Grouped heads where the projections wrote them** is the third form —
rank-3 ``Q`` with ``n_head`` and a ``V`` input, no ``v_dim``, no
``KShared``: ``Q`` ``[B, T, H * D]``, ``K`` ``[B, T, Hkv * D]``, ``V`` ``[B,
T, Hkv * Dv]``, the three projections of a grouped-query (or plain-head)
block as they are, ``Hkv`` read off ``K``'s width.  ``Out`` is ``[B, T, H *
Dv]``, ``LSE`` ``[B, H, T, 1]``; the gradient op returns dQ, dK and dV in the
operands' layouts.  ``causal``, ``scale``, ``window``, ``Selected``, ``KLen``
and dropout mean what they mean over ``[B, H, T, D]``.  The rotation's law
rides on the op as ``rotary_embedding``'s own attributes under a prefix —
``rope_theta``, ``rope_freq_scaling``, ``rope_scale``, ``rope_interleaved``
(rotate-half unless set) — and the tables come from
``ops.activation.rotary_tables``, so the two ops cannot drift; without
``rope_theta`` nothing is rotated.  Its XLA body IS the definition
(``_split_heads``): view as heads, rotate q and k by ``_rotary_compute``
with its rounding points, heads to the front, ``reference_attention``, heads
back; the gradient differentiates that.  Its kernel body is the 4-D form's
OWN kernels reached through column blocks of the three arrays
(``streamed_attention``: ``_head``), q and k rotated INSIDE them
(``_Rotation``: a query block once as it is loaded, a key block each time —
an XLA pass over K where it lies was tried first and ran at a fifth of its
bytes' floor), dQ and dK turned back where they are written.
Where only the 4-D rule holds (64-wide keys, neighbouring pairs, a rotated
256-wide head, 32k tokens) the op goes to the 4-D kernels through its own
rotation and transposes.  The form is there for the rotations and
transposes it removes between the projections and the kernels (PERF.md
6.27).
``causal`` with
``Tq == Tk`` is aligned self-attention (query i sees keys <= i); with
``Tq < Tk`` the queries are the *suffix* of the valid keys — query i sits
at global position ``klen - Tq + i`` — which is the single-token /
chunked KV-cache decode shape the serving engine drives.  Eval-time dropout follows
the reference's ``downgrade_in_infer``: weights scale by (1 - p), which
commutes with the PV matmul into a single output scale.
"""

import jax.numpy as jnp

from ..registry import (register_op, set_output, in_var,
                        _generic_grad_infer)


def _in_place_infer(op, block, q, kv):
    """The projections' layout (the module docstring): Q ``[B, T, H * (nope
    + rope)]``, K ``[B, T, H * (nope + v_dim)]`` with no V, KShared ``[B, T,
    rope]`` or absent (``rope`` 0)."""
    n, dv = op.attrs.get("n_head"), op.attrs.get("v_dim")
    shared = in_var(op, block, "KShared")
    rope = 0 if shared is None else shared.shape[-1]
    if not n or not dv or in_var(op, block, "V") is not None \
            or len(kv.shape) != 3 or not op.outputs.get("LSE"):
        raise ValueError(
            "fused_attention over [B, T, H * D] operands takes K as the "
            "key/value projection's output whole, no V, the attributes "
            "n_head and v_dim and keeps the rows' log-sum-exp: build the op "
            "with layers.fused_attention(q, kv, n_head=, v_dim=)")
    nope = kv.shape[2] // n - dv
    if q.shape[2] % n or kv.shape[2] % n or nope < 1 \
            or q.shape[2] // n != nope + rope \
            or tuple(kv.shape[:2]) != tuple(q.shape[:2]) \
            or (shared is not None
                and tuple(shared.shape) != tuple(q.shape[:2]) + (rope,)):
        raise ValueError(
            "fused_attention: %d heads of [k_nope | v] in K %s with values "
            "%d wide, [q_nope | q_rope] in Q %s and the shared key part %s "
            "do not fit together" % (n, kv.shape, dv, q.shape,
                                     None if shared is None else shared.shape))
    if in_var(op, block, "Selected") is not None \
            or op.attrs.get("window") is not None:
        raise ValueError("fused_attention over [B, T, H * D] operands takes "
                         "no Selected and no window")
    set_output(op, block, "Out", tuple(q.shape[:2]) + (n * dv,), q.dtype)
    set_output(op, block, "LSE", (q.shape[0], n, q.shape[1], 1), "float32")


def _grouped_infer(op, block, q, k, v):
    """Grouped heads where the projections wrote them (the module
    docstring): Q ``[B, T, H * D]``, K ``[B, T, Hkv * D]``, V ``[B, T, Hkv *
    Dv]``, the attribute ``n_head``."""
    n = op.attrs.get("n_head")
    d = q.shape[2] // n if n and q.shape[2] % n == 0 else 0
    hk = k.shape[2] // d if d and k.shape[2] % d == 0 else 0
    if not hk or n % hk or len(k.shape) != 3 or len(v.shape) != 3 \
            or v.shape[2] % hk or op.attrs.get("v_dim") \
            or in_var(op, block, "KShared") is not None \
            or not op.outputs.get("LSE") \
            or not tuple(q.shape[:2]) == tuple(k.shape[:2]) \
            == tuple(v.shape[:2]):
        raise ValueError(
            "fused_attention over [B, T, H * D] operands with a V takes "
            "n_head heads of Q %s, a whole divisor of them in K %s and V %s "
            "over the same rows, no v_dim and no KShared, and keeps the "
            "rows' log-sum-exp: build the op with layers.fused_attention(q, "
            "k, v, n_head=)" % (q.shape, k.shape, v.shape))
    sel = in_var(op, block, "Selected")
    if sel is not None:
        from .sparse_select import packed_width
        want = tuple(q.shape[:2]) + (packed_width(q.shape[1]),)
        if tuple(sel.shape) != want:
            raise ValueError(
                "fused_attention: Selected must be the packed key mask %s "
                "(ops/sparse_select.py), got %s" % (want, sel.shape))
    window = op.attrs.get("window")
    if window is not None and (not op.attrs.get("causal", False)
                               or int(window) < 1):
        raise ValueError("fused_attention: a window (%r) is of causal "
                         "self-attention, at least one key wide" % (window,))
    if op.attrs.get("rope_theta") is None and (
            op.attrs.get("rope_freq_scaling") is not None
            or float(op.attrs.get("rope_scale", 1.0)) != 1.0):
        raise ValueError("fused_attention: rope_freq_scaling and rope_scale "
                         "belong to a rotation (rope_theta)")
    set_output(op, block, "Out", tuple(q.shape[:2]) + (n * (v.shape[2] // hk),),
               q.dtype)
    set_output(op, block, "LSE", (q.shape[0], n, q.shape[1], 1), "float32")


def _fused_attention_infer(op, block):
    q = in_var(op, block, "Q")
    k = in_var(op, block, "K")
    v = in_var(op, block, "V")
    if len(q.shape) == 3:
        if v is not None:
            return _grouped_infer(op, block, q, k, v)
        return _in_place_infer(op, block, q, k)
    if len(q.shape) != 4 or len(k.shape) != 4 or len(v.shape) != 4:
        raise ValueError(
            "fused_attention expects [B, H, T, D] Q/K/V, got %s/%s/%s"
            % (q.shape, k.shape, v.shape))
    if q.shape[3] != k.shape[3]:
        raise ValueError(
            "fused_attention Q/K head dims disagree: %s vs %s"
            % (q.shape, k.shape))
    if v.shape[2] != k.shape[2]:
        raise ValueError(
            "fused_attention V must be [B, H, Tk, Dv] matching K's length: "
            "got Q %s, K %s, V %s" % (q.shape, k.shape, v.shape))
    if v.shape[3] != q.shape[3] and not op.outputs.get("LSE"):
        raise ValueError(
            "fused_attention: values of another width than the keys (Q %s, "
            "V %s) run on the bodies that keep the rows' log-sum-exp: "
            "build the op with layers.fused_attention" % (q.shape, v.shape))
    if k.shape[1] != v.shape[1] or k.shape[1] < 1 \
            or q.shape[1] % k.shape[1]:
        raise ValueError(
            "fused_attention: K and V carry the same number of heads, and "
            "Q's heads are a whole multiple of it (grouped-query "
            "attention): got Q %s, K %s, V %s" % (q.shape, k.shape, v.shape))
    sel = in_var(op, block, "Selected")
    if sel is not None:
        from .sparse_select import packed_width
        want = (q.shape[0], q.shape[2], packed_width(k.shape[2]))
        if tuple(sel.shape) != want:
            raise ValueError(
                "fused_attention: Selected must be the packed key mask %s "
                "(ops/sparse_select.py), got %s" % (want, sel.shape))
    if op.attrs.get("causal", False) and q.shape[2] > k.shape[2]:
        # a suffix query cannot be longer than the key sequence it is a
        # suffix of; Tq < Tk is the decode/chunked-decode shape (queries
        # are the LAST Tq valid positions — bottom-aligned causal mask)
        raise ValueError(
            "fused_attention: causal=True requires Tq <= Tk (got %d vs "
            "%d)" % (q.shape[2], k.shape[2]))
    if op.attrs.get("window") is not None and (
            not op.attrs.get("causal", False) or q.shape[2] != k.shape[2]
            or int(op.attrs["window"]) < 1 or not op.outputs.get("LSE")):
        raise ValueError(
            "fused_attention: a window (%r) is of causal self-attention "
            "(Tq == Tk, got %d vs %d), at least one key wide, on the "
            "bodies that keep the rows' log-sum-exp: build the op with "
            "layers.fused_attention" % (op.attrs["window"], q.shape[2],
                                        k.shape[2]))
    set_output(op, block, "Out", tuple(q.shape[:3]) + (v.shape[3],), q.dtype)
    if op.outputs.get("LSE"):
        set_output(op, block, "LSE", tuple(q.shape[:3]) + (1,), "float32")


def _resident_kv_fits(tq, tk, d):
    """Whether one (batch, head)'s whole K and V, its Q and dO and a
    [256, 512] block of float32 scores fit, twice over (a pipeline's two
    buffers), under 10 MiB in bfloat16.  A boundary INHERITED from the
    resident-K/V flash kernel this op had until PR 45 — no body that is left
    has it — and kept because ``streams_plain_heads`` draws the line between
    ``keep_lse`` programs and the others with it; ROADMAP S15 (plain heads
    of 512 <= T <= 2048) moves it by measurement."""
    def ceil_to(x, m):
        return -(-x // m) * m

    if tq < 1 or tk < 1 or d < 1 or d > 512:
        return False
    bq = min(256, ceil_to(tq, 8))
    bk = min(512, ceil_to(tk, 128 if tk >= 128 else 8))
    tq_pad, tk_pad = ceil_to(tq, bq), ceil_to(tk, bk)
    resident = 2 * tk_pad * d * 2 + 2 * tq_pad * d * 2 + 2 * tq_pad * 4
    blocks = (3 * bq * d + 2 * bq * bk) * 4
    return 2 * (resident + blocks) < 10 * 1024 * 1024


def streams_plain_heads(q_shape, k_shape, v_shape, has_klen, rate):
    """Whether plain-head attention of these shapes belongs to the bodies
    that keep the rows' log-sum-exp (streamed on a TPU): values of another
    width than the keys, or self-attention the streamed kernel takes at a
    length past ``_resident_kv_fits`` (T above 3584 at D = 128, above 1536
    at D = 256).  Shapes alone, so that the layer can ask it when it builds
    the op (``keep_lse``) and the trace need not."""
    from .pallas import streamed_attention as sa

    if len(q_shape) != 4 or len(v_shape) != 4:
        return False
    if v_shape[3] != q_shape[3]:
        return True
    return sa.supported(q_shape, k_shape, jnp.bfloat16, True, has_klen,
                        rate) \
        and not _resident_kv_fits(q_shape[2], k_shape[2], q_shape[3])


def _attention_args(ins, attrs, ctx, op_index):
    """What the forward and its gradient both read off the op: Q, K, V,
    KLen, the masks' and dropout's attributes, the dropout hash's seed
    (from the FORWARD op's trace index), and the eval-time output scale
    (``downgrade_in_infer``: weights *= (1-p) == output *= (1-p))."""
    q, k, v = ins["Q"][0], ins["K"][0], (ins.get("V") or [None])[0]
    k_len = ins.get("KLen", [None])[0]
    causal = attrs.get("causal", False)
    rate = float(attrs.get("dropout_rate", 0.0))
    is_test = attrs.get("is_test", False) or ctx.is_test
    scale = attrs.get("scale", None)
    seed = post = None
    if rate and not is_test:
        import jax
        kd = jax.random.key_data(ctx.rng_key(op_index)).astype(jnp.uint32)
        seed = kd.reshape(-1)[0] ^ kd.reshape(-1)[-1]
    elif rate:
        post, rate = 1.0 - rate, 0.0
    return q, k, v, k_len, seed, causal, rate, scale, post


def _rotated(x, law, back=False):
    """``x`` ``[B, T, ..., D]`` rotated by its positions as the op
    ``rotary_embedding`` with the attributes ``law`` does (float32 inside,
    ``x``'s dtype out), or — ``back`` — by the negative angle, which is the
    rotation's gradient; ``law`` a bare base: interleaved pairs by it (the
    latent form's); None: ``x``."""
    if law is None:
        return x
    if not isinstance(law, dict):
        law = {"theta": float(law), "interleaved": True}
    from .activation import _rotary_compute

    def turn(x):
        return _rotary_compute({"X": [x]}, law, None, 0)["Out"]
    if not back:
        return turn(x)
    import jax
    return jax.vjp(turn, x)[1](x)[0]


def _in_place_parts(ins, attrs):
    """(Q, K, KShared or None, heads, nope, rope, v_dim, rope_theta) of the
    op over the projections' layout."""
    q, kv = ins["Q"][0], ins["K"][0]
    shared = (ins.get("KShared") or [None])[0]
    n, dv = int(attrs["n_head"]), int(attrs["v_dim"])
    nope = kv.shape[2] // n - dv
    return (q, kv, shared, n, nope, q.shape[2] // n - nope, dv,
            attrs.get("rope_theta"))


def _in_place_reference(ins, attrs, k_len, seed, causal, rate, scale):
    """The definition of the op over the projections' layout, as a model
    composed it from Fluid ops before the op took it: split each head's
    ``[nope | rope]`` and ``[k_nope | v]``, rotate the queries' ``rope``
    columns and the shared key part, join that onto every head's keys,
    heads to the front, the XLA body, heads back.  (Out, LSE)."""
    from . import attention_xla

    q, kv, shared, n, nope, rope, dv, theta = _in_place_parts(ins, attrs)
    b, t = q.shape[:2]
    q = q.reshape(b, t, n, nope + rope)
    kv = kv.reshape(b, t, n, nope + dv)
    k, v = kv[..., :nope], kv[..., nope:]
    if shared is not None:
        q = jnp.concatenate([q[..., :nope], _rotated(q[..., nope:], theta)],
                            -1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            _rotated(shared[:, :, None], theta), (b, t, n, rope))], -1)
    out, lse = attention_xla.reference_attention(
        *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), k_len, seed, causal,
        rate, scale, None, True)
    return out.transpose(0, 2, 1, 3).reshape(b, t, n * dv), lse


def _in_place_applicable(ctx, ins, attrs, has_klen, rate):
    """The rule of the streamed kernels over the projections' layout: a TPU
    trace on one device, no ``FLAGS_pallas_kernels=False``, a shared key
    part, a call ``in_place_supported`` takes, and the fewest heads' resident
    gradients inside the budget."""
    from .pallas import kernel_allowed, streamed_attention as sa

    q, kv, shared, n, nope, rope, dv, theta = _in_place_parts(ins, attrs)
    return kernel_allowed(ctx, _STREAMED_PLATFORMS) \
        and getattr(ctx, "mesh", None) is None and shared is not None \
        and sa.in_place_supported(q.shape, kv.shape, rope, n, dv, has_klen,
                                 rate) \
        and sa.in_place_step(q, kv, n, dv)[1] is not None


def _in_place_compute(ins, attrs, ctx, op_index):
    from ..compile_cache import note_kernel_body

    _, _, _, k_len, seed, causal, rate, scale, post = _attention_args(
        ins, attrs, ctx, op_index)
    if _in_place_applicable(ctx, ins, attrs, k_len is not None, rate):
        from .pallas import interpret_mode
        from .pallas import streamed_attention as sa

        q, kv, shared, n, nope, rope, dv, theta = _in_place_parts(ins, attrs)
        note_kernel_body("fused_attention", "streamed_inplace")
        note_kernel_body("streamed_step",
                         "%dx1" % sa.in_place_step(q, kv, n, dv)[0])
        out, lse = sa.forward_in_place(
            q, kv, _rotated(shared, theta), n, dv, theta, causal, scale,
            interpret_mode(ctx))
    else:
        note_kernel_body("fused_attention", "xla")
        out, lse = _in_place_reference(ins, attrs, k_len, seed, causal, rate,
                                       scale)
    if post is not None:
        out = out * jnp.asarray(post, out.dtype)
    return {"Out": out, "LSE": lse}


def _in_place_grad_compute(ins, attrs, ctx, op_index):
    """The streamed body's backward from the forward's own output and
    log-sum-exp; the XLA body differentiates itself."""
    from ..registry import _generic_grad_compute

    _, _, _, k_len, seed, causal, rate, scale, post = _attention_args(
        ins, attrs, ctx, attrs.get("__fwd_op_index__", op_index))
    dout, out, lse = ((ins.get(slot) or [None])[0]
                      for slot in ("GRAD::Out", "Out::Out", "Out::LSE"))
    if dout is None or out is None or lse is None \
            or not _in_place_applicable(ctx, ins, attrs, k_len is not None,
                                        rate):
        return _generic_grad_compute(ins, attrs, ctx, op_index)
    from ..compile_cache import note_kernel_body
    from .pallas import interpret_mode
    from .pallas import streamed_attention as sa

    q, kv, shared, n, nope, rope, dv, theta = _in_place_parts(ins, attrs)
    note_kernel_body("fused_attention_grad", "streamed_fused_inplace")
    note_kernel_body("streamed_grad_step",
                     "%dx1" % sa.in_place_step(q, kv, n, dv)[1])
    if post is not None:
        dout = dout * jnp.asarray(post, dout.dtype)
    dq, dkv, dshared = sa.backward_in_place(
        q, kv, _rotated(shared, theta), n, dv, out, lse, dout, theta, causal,
        scale, interpret_mode(ctx))
    return {"GRAD::Q": [dq], "GRAD::K": [dkv],
            "GRAD::KShared": [_rotated(dshared, theta, back=True).astype(
                shared.dtype)]}


# ---- grouped heads where the projections wrote them -----------------------

def _grouped_parts(ins, attrs):
    """(Q, K, V, heads, K/V heads, D, Dv, the rotation's law — the
    attributes of the ``rotary_embedding`` op that would do it — or None)
    of the op over ``[B, T, H * D]`` operands with a V."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    n = int(attrs["n_head"])
    d = q.shape[2] // n
    hk = k.shape[2] // d
    law = None
    if attrs.get("rope_theta") is not None:
        law = {"theta": float(attrs["rope_theta"])}
        if attrs.get("rope_freq_scaling") is not None:
            law["freq_scaling"] = attrs["rope_freq_scaling"]
        if float(attrs.get("rope_scale", 1.0)) != 1.0:
            law["scale"] = float(attrs["rope_scale"])
        if attrs.get("rope_interleaved", False):
            law["interleaved"] = True
    return q, k, v, n, hk, d, v.shape[2] // hk, law


def _split_heads(q, k, v, n, hk, law):
    """The op's definition up to the attention, as a model composed it from
    Fluid ops before the op took the projections' outputs: each viewed as
    heads, q and k rotated, heads to the front.  ``[B, H, T, D]`` each."""
    def heads(x, m, rotate):
        x = x.reshape(x.shape[:2] + (m, x.shape[2] // m))
        return (_rotated(x, law) if rotate else x).transpose(0, 2, 1, 3)
    return heads(q, n, True), heads(k, hk, True), heads(v, hk, False)


def _merge_heads(x):
    """``[B, H, T, D]`` as ``[B, T, H * D]``."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _grouped_body(ctx, ins, attrs, has_klen, rate):
    """The body of the op over grouped heads in the projections' layout, by
    its rule: ``streamed_inplace`` — the streamed kernels addressing the
    operands where they lie — on a TPU trace on one device with no
    ``FLAGS_pallas_kernels=False``, no ``KLen``, no dropout, keys and values
    whole lane tiles wide (a rotation: rotate-half of 128-wide heads), T
    whole key blocks and the fused backward's resident gradients inside the
    budget; ``streamed`` — the 4-D kernels through the op's own rotation and
    transposes — where only ``_streamed_applicable`` holds (64-wide keys,
    32k tokens); ``xla`` otherwise."""
    from .pallas import streamed_attention as sa
    from .sparse_select import LANES

    q, k, v, n, hk, d, dv, law = _grouped_parts(ins, attrs)
    b, t = q.shape[:2]
    if not _streamed_applicable(ctx, (b, n, t, d), (b, hk, t, d), q.dtype,
                                attrs.get("causal", False), has_klen, rate,
                                dv):
        return "xla"
    turns = law is None or (d == LANES and not law.get("interleaved"))
    if d % LANES == 0 and turns \
            and sa.grad_step(q, k, v, n)[0] == "streamed_fused":
        return "streamed_inplace"
    return "streamed"


def _grouped_tables(q, d, law):
    """``half_turn_tables`` of ``q``'s positions by ``law``; None without
    one."""
    from .pallas import streamed_attention as sa

    return None if law is None else sa.half_turn_tables(
        q.shape[1], d, law["theta"], law.get("freq_scaling"),
        law.get("scale", 1.0))


def _grouped_compute(ins, attrs, ctx, op_index):
    from . import attention_xla
    from ..compile_cache import note_kernel_body
    from .pallas import interpret_mode
    from .pallas import streamed_attention as sa

    _, _, _, k_len, seed, causal, rate, scale, post = _attention_args(
        ins, attrs, ctx, op_index)
    q, k, v, n, hk, d, dv, law = _grouped_parts(ins, attrs)
    selected = (ins.get("Selected") or [None])[0]
    window = attrs.get("window")
    body = _grouped_body(ctx, ins, attrs, k_len is not None, rate)
    note_kernel_body("fused_attention", body)
    if body == "streamed_inplace":
        note_kernel_body("streamed_step", "%dx%d" % sa.step_heads(q, k, v, n))
        out, lse = sa.forward(
            q, k, v, selected, causal, scale, interpret_mode(ctx), window, n,
            _grouped_tables(q, d, law))
    else:
        heads = _split_heads(q, k, v, n, hk, law)
        if body == "streamed":
            note_kernel_body("streamed_step", "%dx%d" % sa.step_heads(*heads))
            out, lse = sa.forward(*heads, selected, causal, scale,
                                  interpret_mode(ctx), window)
        else:
            out, lse = attention_xla.reference_attention(
                *heads, k_len, seed, causal, rate, scale, selected, True,
                window)
        out = _merge_heads(out)
    if post is not None:
        out = out * jnp.asarray(post, out.dtype)
    return {"Out": out, "LSE": lse}


def _grouped_grad_compute(ins, attrs, ctx, op_index):
    """A streamed body's backward from the forward's own output and
    log-sum-exp — in place, or around the 4-D kernels through the pull-back
    of the op's own rotation and transposes —; the XLA body differentiates
    itself."""
    from ..registry import _generic_grad_compute

    _, _, _, k_len, seed, causal, rate, scale, post = _attention_args(
        ins, attrs, ctx, attrs.get("__fwd_op_index__", op_index))
    dout, out, lse = ((ins.get(slot) or [None])[0]
                      for slot in ("GRAD::Out", "Out::Out", "Out::LSE"))
    body = _grouped_body(ctx, ins, attrs, k_len is not None, rate)
    if dout is None or out is None or lse is None or body == "xla":
        return _generic_grad_compute(ins, attrs, ctx, op_index)
    from ..compile_cache import note_kernel_body
    from .pallas import interpret_mode
    from .pallas import streamed_attention as sa

    q, k, v, n, hk, d, dv, law = _grouped_parts(ins, attrs)
    selected = (ins.get("Selected") or [None])[0]
    window = attrs.get("window")
    if post is not None:
        dout = dout * jnp.asarray(post, dout.dtype)
    if body == "streamed_inplace":
        note_kernel_body("fused_attention_grad", "streamed_fused_inplace")
        note_kernel_body("streamed_grad_step",
                         "%dx%d" % sa.grad_step(q, k, v, n)[1])
        dq, dk, dvalues = sa.backward(
            q, k, v, selected, out, lse, dout, causal, scale,
            interpret_mode(ctx), window, n, _grouped_tables(q, d, law))
    else:
        import jax

        heads, pull = jax.vjp(
            lambda q, k, v: _split_heads(q, k, v, n, hk, law), q, k, v)
        grad_body, step = sa.grad_step(*heads)
        note_kernel_body("fused_attention_grad", grad_body)
        note_kernel_body("streamed_grad_step", "%dx%d" % step)

        def split(x):
            return x.reshape(x.shape[:2] + (n, dv)).transpose(0, 2, 1, 3)
        dq, dk, dvalues = pull(sa.backward(
            *heads, selected, split(out), lse, split(dout), causal, scale,
            interpret_mode(ctx), window))
    return {"GRAD::Q": [dq], "GRAD::K": [dk], "GRAD::V": [dvalues]}


def _fused_attention_compute(ins, attrs, ctx, op_index):
    if ins["Q"][0].ndim == 3:
        if ins.get("V"):
            return _grouped_compute(ins, attrs, ctx, op_index)
        return _in_place_compute(ins, attrs, ctx, op_index)
    q, k, v, k_len, seed, causal, rate, scale, post = _attention_args(
        ins, attrs, ctx, op_index)
    selected = (ins.get("Selected") or [None])[0]

    from . import attention_xla
    from ..compile_cache import note_kernel_body

    if _keeps_lse(q, k, selected, attrs):
        # grouped heads, a selected key set, or plain heads the layer
        # marked (``streams_plain_heads``): the streamed kernel on a TPU
        # where it takes the call, the XLA body otherwise; either hands the
        # gradient op its rows' log-sum-exp
        from .pallas import interpret_mode
        from .pallas import streamed_attention as sa

        # None but for a window layer: the kernels bind no such argument
        window = attrs.get("window")
        if _streamed_applicable(ctx, q.shape, k.shape, q.dtype, causal,
                                k_len is not None, rate, v.shape[3]):
            note_kernel_body("fused_attention", "streamed")
            # K/V heads x query heads of each that a grid step serves
            note_kernel_body("streamed_step", "%dx%d" % sa.step_heads(q, k, v))
            out, lse = sa.forward(q, k, v, selected, causal, scale,
                                  interpret_mode(ctx), window)
        elif _plain(q, k, v, selected) and window is None \
                and _ring_selected(ctx, q.shape, k.shape, causal):
            # a sequence-parallel mesh keeps its ring, which keeps no
            # log-sum-exp: its gradient differentiates the body
            note_kernel_body("fused_attention", "ring")
            out = _ring_attention(ctx.mesh, q, k, v, k_len, seed, causal,
                                  rate, scale)
            lse = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
        else:
            note_kernel_body("fused_attention", "xla")
            out, lse = attention_xla.reference_attention(
                q, k, v, k_len, seed, causal, rate, scale, selected, True,
                window)
        if post is not None:
            out = out * jnp.asarray(post, out.dtype)
        return {"Out": out, "LSE": lse}
    if _ring_selected(ctx, q.shape, k.shape, causal):
        note_kernel_body("fused_attention", "ring")
        out = _ring_attention(ctx.mesh, q, k, v, k_len, seed, causal, rate,
                              scale)
    elif _packed_applicable(ctx, q.shape, k.shape, q.dtype, causal):
        note_kernel_body("fused_attention", "packed")
        (out,) = _packed_run(ctx, (q, k, v), k_len, seed, causal, rate,
                             scale)
    else:
        note_kernel_body("fused_attention", "xla")
        out = attention_xla.reference_attention(q, k, v, k_len, seed, causal,
                                                rate, scale)
    if post is not None:
        out = out * jnp.asarray(post, out.dtype)
    return {"Out": out}


def _fused_attention_grad_compute(ins, attrs, ctx, op_index):
    """The op's gradient, by the forward's body (the module docstring).
    The ring and the XLA body differentiate the forward
    (``registry._generic_grad_compute``: ``jax.vjp`` over the forward's
    compute).  On a kernel that would run the forward kernel a second time
    per attention — the vjp's forward is a second custom call with the same
    operands, and XLA does not merge custom calls — so the packed and the
    streamed bodies run their own backward kernels."""
    from ..registry import _generic_grad_compute

    if ins["Q"][0].ndim == 3:
        if ins.get("V"):
            return _grouped_grad_compute(ins, attrs, ctx, op_index)
        return _in_place_grad_compute(ins, attrs, ctx, op_index)
    fwd_index = attrs.get("__fwd_op_index__", op_index)
    q, k, v, k_len, seed, causal, rate, scale, post = _attention_args(
        ins, attrs, ctx, fwd_index)
    dout = (ins.get("GRAD::Out") or [None])[0]
    selected = (ins.get("Selected") or [None])[0]
    if _keeps_lse(q, k, selected, attrs):
        # the streamed body: its backward — one fused kernel, or dQ and
        # dK/dV where a K/V head's gradients do not fit VMEM — from the
        # forward's own output and log-sum-exp (the generic rule would run
        # the forward kernel a second time to get them); the XLA body and
        # the ring differentiate themselves
        out = (ins.get("Out::Out") or [None])[0]
        lse = (ins.get("Out::LSE") or [None])[0]
        if dout is None or out is None or lse is None \
                or not _streamed_applicable(ctx, q.shape, k.shape, q.dtype,
                                            causal, k_len is not None, rate,
                                            v.shape[3]):
            return _generic_grad_compute(ins, attrs, ctx, op_index)
        from ..compile_cache import note_kernel_body
        from .pallas import interpret_mode
        from .pallas import streamed_attention as sa

        body, heads = sa.grad_step(q, k, v)
        note_kernel_body("fused_attention_grad", body)
        note_kernel_body("streamed_grad_step", "%dx%d" % heads)
        if post is not None:
            dout = dout * jnp.asarray(post, dout.dtype)
        dq, dk, dv = sa.backward(q, k, v, selected, out, lse, dout, causal,
                                 scale, interpret_mode(ctx),
                                 attrs.get("window"))
        return {"GRAD::Q": [dq], "GRAD::K": [dk], "GRAD::V": [dv]}
    if dout is None or _ring_selected(ctx, q.shape, k.shape, causal) \
            or not _packed_applicable(ctx, q.shape, k.shape, q.dtype, causal):
        return _generic_grad_compute(ins, attrs, ctx, op_index)

    from ..compile_cache import note_kernel_body

    note_kernel_body("fused_attention_grad", "packed")
    dout = dout.astype(q.dtype)
    if post is not None:
        dout = dout * jnp.asarray(post, dout.dtype)
    dq, dk, dv = _packed_run(ctx, (q, k, v, dout), k_len, seed, causal,
                             rate, scale)
    return {"GRAD::Q": [dq], "GRAD::K": [dk], "GRAD::V": [dv]}


def _ring_selected(ctx, q_shape, k_shape, causal):
    mesh = getattr(ctx, "mesh", None)
    return mesh is not None and getattr(ctx, "sequence_parallel", True) \
        and _ring_applicable(mesh, q_shape, k_shape, causal)


# the platforms whose traces take the packed kernel: on the CPU the op
# keeps the XLA body (the interpreter is for the kernel's own tests)
_PACKED_PLATFORMS = ("tpu",)
# likewise for the streamed kernel
_STREAMED_PLATFORMS = ("tpu",)


def _keeps_lse(q, k, selected, attrs):
    """Whether the op is one of those whose bodies keep the rows'
    log-sum-exp for the gradient op (the layer gave it an ``LSE``
    output)."""
    return selected is not None or k.shape[1] != q.shape[1] \
        or attrs.get("keep_lse", False)


def _plain(q, k, v, selected):
    return selected is None and k.shape[1] == q.shape[1] \
        and v.shape[3] == q.shape[3]


def _streamed_applicable(ctx, q_shape, k_shape, dtype, causal, has_klen,
                         rate, dv=None):
    """The streamed kernel's rule: a TPU trace on one device (it has no
    per-shard lowering yet), no ``FLAGS_pallas_kernels=False``, and a call
    its ``supported()`` takes."""
    from .pallas import kernel_allowed, streamed_attention as sa

    return kernel_allowed(ctx, _STREAMED_PLATFORMS) \
        and getattr(ctx, "mesh", None) is None \
        and sa.supported(q_shape, k_shape, dtype, causal, has_klen, rate, dv)


def _packed_axes(ctx, b, h, d):
    """(batch axes, head axis) the packed kernel's shards split over: the
    populated data axes whose extent divides the batch, and ``tp`` when it
    leaves every shard whole heads in whole lane tiles."""
    from ..parallel.embedding import _data_axes, _narrow_batch_axes
    from ..parallel.mesh import AXIS_TP

    mesh = getattr(ctx, "mesh", None)
    if mesh is None:
        return (), None
    baxes = _narrow_batch_axes(ctx, _data_axes(ctx), b)
    tp = mesh.shape[AXIS_TP] if AXIS_TP in mesh.axis_names else 1
    haxis = AXIS_TP if tp > 1 and h % tp == 0 \
        and (h // tp * d) % 128 == 0 else None
    return baxes, haxis


def _packed_applicable(ctx, q_shape, k_shape, dtype, causal):
    """The packed short-sequence kernel's rule, from what the op can
    observe: a TPU trace, no ``FLAGS_pallas_kernels=False``, not the
    suffix-causal decode shape (K and V come from a cache there, not
    from a transpose: merging heads would ADD copies), and one shard's
    row fits the kernel's VMEM budget."""
    from .pallas import kernel_allowed, packed_attention as pa

    if not kernel_allowed(ctx, _PACKED_PLATFORMS):
        return False
    if causal and q_shape[2] < k_shape[2]:
        return False
    b, h, _, d = q_shape
    _, haxis = _packed_axes(ctx, b, h, d)
    if haxis is not None:
        h //= ctx.mesh.shape[haxis]
    return pa.supported((b, h) + tuple(q_shape[2:]),
                        (b, h) + tuple(k_shape[2:]), dtype)


def _packed_run(ctx, arrays, k_len, seed, causal, rate, scale):
    """Merge the heads of ``arrays`` — (Q, K, V) for the forward, (Q, K,
    V, dO) for the gradient, each ``[B, H, T, D]`` — into the projections'
    ``[B, T, H*D]`` layout, run the packed kernel, split the results
    again: (O,) or (dQ, dK, dV).  Under a mesh a Pallas call is a custom
    call GSPMD cannot partition (left bare it would gather the global
    batch onto every chip), so it runs per shard: batch over the data
    axes, whole heads over ``tp``, and the dropout hash offset by the
    shard's first global row and head as ``ring_attention_shard`` does."""
    from .pallas import interpret_mode
    from .pallas import packed_attention as pa

    b, h, _, d = arrays[0].shape
    interpret = interpret_mode(ctx)
    kernel = pa.packed_attention if len(arrays) == 3 \
        else pa.packed_attention_bwd

    def run(arrays, klen, seed, offsets):
        out = kernel(*arrays, klen, seed, offsets, arrays[0].shape[2] // d,
                     causal, rate, scale, interpret)
        return out if isinstance(out, tuple) else (out,)

    packed = tuple(x.transpose(0, 2, 1, 3).reshape(b, x.shape[2], h * d)
                   for x in arrays)
    baxes, haxis = _packed_axes(ctx, b, h, d)
    if not baxes and haxis is None:
        outs = run(packed, k_len, seed, None)
    else:
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..parallel.embedding import _shard_offset
        from ..parallel.mesh import shard_map_norep

        mesh = ctx.mesh
        if k_len is None:
            k_len = jnp.full((b,), arrays[1].shape[2], jnp.int32)
        if seed is None:
            seed = jnp.zeros((), jnp.uint32)

        def shard_body(arrays, klen, seed):
            heads = arrays[0].shape[2] // d
            first = lax.axis_index(haxis) * heads if haxis else 0
            return run(arrays, klen, seed,
                       (_shard_offset(mesh, baxes, arrays[0].shape[0]),
                        first, h))

        bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
        spec = P(bspec, None, haxis)
        fn = shard_map_norep(
            shard_body, mesh,
            in_specs=((spec,) * len(arrays), P(bspec), P()),
            out_specs=(spec,) * (1 if len(arrays) == 3 else 3))
        outs = fn(packed, k_len.astype(jnp.int32), seed.astype(jnp.uint32))
    return tuple(o.reshape(b, o.shape[1], h, d).transpose(0, 2, 1, 3)
                 for o in outs)


def _ring_applicable(mesh, q_shape, k_shape, causal):
    """Ring attention lowers this op when the mesh has a populated ``sp``
    axis and the sequence dims divide it (the ParallelExecutor threads the
    mesh into the trace exactly when its BuildStrategy allows sp)."""
    from ..parallel.mesh import AXIS_SP

    if AXIS_SP not in mesh.axis_names:
        return False
    sp = mesh.shape[AXIS_SP]
    if sp <= 1:
        return False
    b, _, tq, _ = q_shape
    tk = k_shape[2]
    if tq % sp or tk % sp:
        return False
    if causal and tq != tk:
        return False
    return True


def _ring_attention(mesh, q, k, v, k_len, seed, causal, rate, scale):
    """Lower to sequence-parallel ring attention over the mesh's ``sp``
    axis (parallel/ring_attention.py), composing with ``dp`` batch
    sharding when the batch divides it.  Masks and dropout use GLOBAL
    positions, so the result is loss-parity-exact with the single-chip
    kernel."""
    import functools

    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP, shard_map_norep
    from ..parallel.ring_attention import ring_attention_shard

    b, h = q.shape[0], q.shape[1]
    tk = k.shape[2]
    bspec = None
    if AXIS_DP in mesh.axis_names and mesh.shape[AXIS_DP] > 1 \
            and b % mesh.shape[AXIS_DP] == 0:
        bspec = AXIS_DP
    # heads shard over tp when present (tensor-parallel QKV projections
    # leave Q/K/V head-sharded; the ring treats heads as batch, so the
    # composition is a pure spec change plus the dropout head offset)
    hspec = None
    if AXIS_TP in mesh.axis_names and mesh.shape[AXIS_TP] > 1 \
            and h % mesh.shape[AXIS_TP] == 0:
        hspec = AXIS_TP
    if k_len is None:
        k_len = jnp.full((b,), tk, jnp.int32)
    if seed is None:
        seed = jnp.zeros((), jnp.uint32)
    body = functools.partial(
        ring_attention_shard, axis_name=AXIS_SP, causal=causal, scale=scale,
        dropout_rate=rate, batch_axis_name=bspec, head_axis_name=hspec)

    def shard_body(q, k, v, klen, seed):
        return body(q, k, v, k_len=klen, seed=seed)

    spec = P(bspec, hspec, AXIS_SP, None)
    fn = shard_map_norep(
        shard_body, mesh,
        in_specs=(spec, spec, spec, P(bspec), P()), out_specs=spec)
    return fn(q, k, v, k_len.astype(jnp.int32), seed.astype(jnp.uint32))


register_op(
    "fused_attention", ["Q", "K", "V", "KLen", "Selected", "KShared"],
    ["Out", "LSE"],
    infer=_fused_attention_infer, compute=_fused_attention_compute,
    no_grad_inputs=("KLen", "Selected"), stateful_random=True,
)

# the gradient op the default grad maker emits for fused_attention
# (``fused_attention_grad``, the forward's slots + Out::Out + GRAD::Out):
# the same op in the program, its compute chosen like the forward's
register_op(
    "fused_attention_grad", (), (),
    infer=_generic_grad_infer, compute=_fused_attention_grad_compute,
    grad=None, doc="gradient of fused_attention",
)


# ---------------------------------------------------------------------------
# paged attention (ISSUE 16): attention over a block-indexed KV pool
# ---------------------------------------------------------------------------

def _paged_attention_infer(op, block):
    q = in_var(op, block, "Q")
    kc = in_var(op, block, "KCache")
    table = in_var(op, block, "PageTable")
    if q is None or kc is None or table is None:
        raise ValueError("paged_attention needs Q, KCache/VCache and "
                         "PageTable inputs")
    if len(q.shape) != 4 or len(kc.shape) != 4 or len(table.shape) != 2:
        raise ValueError(
            "paged_attention expects Q [S, H, Tq, D], KCache "
            "[P, H, ps, D], PageTable [S, max_pages]; got %s / %s / %s"
            % (q.shape, kc.shape, table.shape))
    tmax = table.shape[1] * kc.shape[2]
    if q.shape[2] > tmax:
        raise ValueError(
            "paged_attention: Tq %d exceeds the paged capacity %d"
            % (q.shape[2], tmax))
    import numpy as np
    if np.dtype(kc.dtype) == np.dtype("int8") \
            and in_var(op, block, "KScale") is None:
        raise ValueError(
            "paged_attention: int8 KV pools need KScale/VScale inputs")
    set_output(op, block, "Out", q.shape, q.dtype)


def _paged_attention_compute(ins, attrs, ctx, op_index):
    q = ins["Q"][0]
    k_pool = ins["KCache"][0]
    v_pool = ins["VCache"][0]
    table = ins["PageTable"][0].astype(jnp.int32)
    k_len = ins.get("KLen", [None])[0]
    k_scale = ins.get("KScale", [None])[0]
    v_scale = ins.get("VScale", [None])[0]
    scale = attrs.get("scale", None)

    from . import attention_xla

    out = attention_xla.paged_attention(
        q, k_pool, v_pool, table, k_len, k_scale, v_scale,
        causal=attrs.get("causal", True), scale=scale)
    return {"Out": out}


register_op(
    "paged_attention",
    ["Q", "KCache", "VCache", "PageTable", "KLen", "KScale", "VScale"],
    ["Out"],
    infer=_paged_attention_infer, compute=_paged_attention_compute,
    grad=None,
    no_grad_inputs=("PageTable", "KLen", "KScale", "VScale"),
)
