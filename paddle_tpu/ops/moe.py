"""Routed experts on one chip's share of an expert-parallel layer.

A layer has ``total`` experts; this chip holds ``held`` of them, numbers
``first .. first + held - 1``.  The router is whole on every chip and
routes over ALL experts; the chip computes the part of the layer's result
that its own experts give, for the tokens routed to them.  Nothing here
stands in for the other chips or for the exchange with them.

Three ops:

* ``moe_router`` — scores over all experts in float32, ``softmax(X W)``
  (the default) or ``sigmoid(X W)``; the top ``k`` a token, chosen on the
  scores plus an optional per-expert ``Bias`` that moves the SELECTION only
  (bias-corrected, auxiliary-loss-free balancing: no gradient, no optimizer
  state, set by a rule outside the loss); their weights are the UNBIASED
  scores renormalised over the ``k``, times ``scale``.
* ``moe_dispatch`` — from the routed ids alone, the layout of the work:
  token-expert pairs of held experts sorted by expert, every expert's group
  padded to whole tiles of ``tile`` rows, so that a tile belongs to ONE
  expert.  Shapes are static (the worst case: every pair lands here); how
  many tiles are live is a device scalar.
* ``moe_expert_ffn`` — dropless grouped products: for each live tile gather
  its tokens, ``(silu(x Wg_e) * (x Wu_e)) Wd_e``, scale by the pair's routing
  weight and add into the token's row.  The work follows the pairs that
  exist (a device-side trip count), not the worst case, and no pair is ever
  dropped.  The gradient has the same form and recomputes a tile's products
  from its tokens: nothing but the layout is kept for the backward.

Which body ``moe_expert_ffn`` and its gradient run (``_bodies``; the note is
``compile_cache.stats()["kernel_bodies"]["moe_expert_ffn:<body>"]``):

* ``grouped`` — ``ops/pallas/grouped_experts.py``: on a TPU, one device, no
  ``FLAGS_pallas_kernels=False``, shapes its ``supported()`` takes
  (bf16 or float32, widths and tile of whole lane tiles, a step of each
  kernel inside the VMEM budget: both expert cells').  Rows gathered a chunk
  of tiles at a time, a tile's expert picked in the weights' index maps,
  results combined row by row in VMEM: five Pallas kernels, no per-tile XLA
  operation.
* ``loop`` — ``expert_ffn`` / ``expert_ffn_grad`` below: everywhere else (the
  CPU, a mesh, whatever ``supported()`` refuses), and the reference the
  kernels' tests compare with.  A ``fori_loop`` over the live tiles of
  per-tile XLA operations.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..registry import (register_op, set_output, in_var,
                        _generic_grad_infer)

# -- moe_router ----------------------------------------------------------------


def _router_infer(op, block):
    x = in_var(op, block, "X")
    w = in_var(op, block, "W")
    if len(x.shape) != 2 or len(w.shape) != 2 or x.shape[1] != w.shape[0]:
        raise ValueError("moe_router expects X [N, D] and W [D, E], got %s "
                         "/ %s" % (x.shape, w.shape))
    if op.attrs.get("score_func", "softmax") not in _ROUTER_SCORES:
        raise ValueError("moe_router: score_func is one of %s, got %r"
                         % (sorted(_ROUTER_SCORES), op.attrs["score_func"]))
    k = int(op.attrs["top_k"])
    set_output(op, block, "TopkIdx", (x.shape[0], k), "int32")
    set_output(op, block, "TopkWeight", (x.shape[0], k), "float32")


_ROUTER_SCORES = {"softmax": lambda z: jax.nn.softmax(z, -1),
                  "sigmoid": jax.nn.sigmoid}


def _router_compute(ins, attrs, ctx, op_index):
    x = ins["X"][0].astype(jnp.float32)
    w = ins["W"][0].astype(jnp.float32)
    score = attrs.get("score_func", "softmax")
    p = _ROUTER_SCORES[score](
        jnp.matmul(x, w, precision=lax.Precision.HIGHEST))
    bias = (ins.get("Bias") or [None])[0]
    k = int(attrs["top_k"])
    if bias is None:
        top, idx = lax.top_k(p, k)
    else:
        _, idx = lax.top_k(p + lax.stop_gradient(bias.astype(jnp.float32)),
                           k)
        top = jnp.take_along_axis(p, idx, -1)
    total = jnp.sum(top, -1, keepdims=True)
    if score == "sigmoid":
        total = total + 1e-20     # independent scores can all be ~0
    weight = top / total
    scale = float(attrs.get("scale", 1.0))
    if scale != 1.0:
        weight = weight * scale
    return {"TopkIdx": idx.astype(jnp.int32), "TopkWeight": weight}


register_op("moe_router", ["X", "W", "Bias"], ["TopkIdx", "TopkWeight"],
            infer=_router_infer, compute=_router_compute,
            no_grad_inputs=("Bias",))


# -- moe_dispatch --------------------------------------------------------------

def dispatch_capacity(pairs, held, tile):
    """Rows of the padded layout in the worst case: every pair routed to a
    held expert, and every group's last tile all but empty."""
    return (-(-pairs // tile) + held) * tile


def _dispatch_infer(op, block):
    idx = in_var(op, block, "TopkIdx")
    held, tile = int(op.attrs["held"]), int(op.attrs["tile"])
    cap = dispatch_capacity(idx.shape[0] * idx.shape[1], held, tile)
    set_output(op, block, "RowToken", (cap,), "int32")
    set_output(op, block, "RowSlot", (cap,), "int32")
    set_output(op, block, "TileExpert", (cap // tile,), "int32")
    set_output(op, block, "NumTiles", (1,), "int32")
    set_output(op, block, "Counts", (held,), "int32")


def dispatch_layout(idx, first, held, tile):
    n, k = idx.shape
    cap = dispatch_capacity(n * k, held, tile)
    e = idx.reshape(-1) - first
    local = jnp.where((e >= 0) & (e < held), e, held)       # held = elsewhere
    counts = jnp.sum(local[:, None] == jnp.arange(held + 1), 0,
                     dtype=jnp.int32)
    padded = -(-counts[:held] // tile) * tile
    ends = jnp.cumsum(padded)
    starts = ends - padded
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    group = local[order]
    rank = jnp.arange(n * k, dtype=jnp.int32) \
        - (jnp.cumsum(counts) - counts)[group]
    dest = jnp.where(group < held,
                     jnp.append(starts, 0)[group] + rank, cap)
    row_token = jnp.full((cap,), n, jnp.int32).at[dest].set(
        order // k, mode="drop")
    row_slot = jnp.zeros((cap,), jnp.int32).at[dest].set(
        order % k, mode="drop")
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(cap // tile, dtype=jnp.int32) * tile, side="right"),
        held - 1).astype(jnp.int32)
    return {"RowToken": row_token, "RowSlot": row_slot,
            "TileExpert": tile_expert,
            "NumTiles": (ends[-1:] // tile).astype(jnp.int32),
            "Counts": counts[:held]}


def _dispatch_compute(ins, attrs, ctx, op_index):
    return dispatch_layout(ins["TopkIdx"][0], int(attrs["first"]),
                           int(attrs["held"]), int(attrs["tile"]))


register_op("moe_dispatch", ["TopkIdx"],
            ["RowToken", "RowSlot", "TileExpert", "NumTiles", "Counts"],
            infer=_dispatch_infer, compute=_dispatch_compute, grad=None)


# -- moe_expert_ffn ------------------------------------------------------------

_LAYOUT = ("RowToken", "RowSlot", "TileExpert", "NumTiles")


def _ffn_infer(op, block):
    x = in_var(op, block, "X")
    g = in_var(op, block, "Gate")
    if len(x.shape) != 2 or len(g.shape) != 3 or g.shape[1] != x.shape[1]:
        raise ValueError("moe_expert_ffn expects X [N, D] and Gate/Up "
                         "[held, D, F], Down [held, F, D]; got %s / %s"
                         % (x.shape, g.shape))
    set_output(op, block, "Out", x.shape, x.dtype)
    set_output(op, block, "Pairs", (1,), "float32")


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


def _tile(x, weight, layout, t, tile):
    """One tile's rows: (token ids, which are real, their inputs in the
    products' dtype, their routing weights, the tile's expert)."""
    row_token, row_slot, tile_expert, _ = layout
    rows = lax.dynamic_slice(row_token, (t * tile,), (tile,))
    slots = lax.dynamic_slice(row_slot, (t * tile,), (tile,))
    real = rows < x.shape[0]
    at = jnp.minimum(rows, x.shape[0] - 1)
    return rows, slots, real, x[at], \
        jnp.where(real, weight[at, slots], 0.0), tile_expert[t]


def _products(xt, gate, up, down, e):
    g = _dot(xt, gate[e], ((1,), (0,)))
    u = _dot(xt, up[e], ((1,), (0,)))
    a = (jax.nn.silu(g) * u).astype(xt.dtype)
    return g, u, a, _dot(a, down[e], ((1,), (0,)))


def expert_ffn(x, weight, gate, up, down, layout, tile):
    """(``[N, D]`` float32: the held experts' part of the layer's result;
    the number of token-expert pairs computed)."""
    def body(t, carry):
        y, pairs = carry
        rows, _, real, xt, c, e = _tile(x, weight, layout, t, tile)
        o = _products(xt, gate, up, down, e)[3]
        y = y.at[rows].add(o * c[:, None], mode="drop", unique_indices=True)
        return y, pairs + jnp.sum(real, dtype=jnp.float32)
    return lax.fori_loop(
        0, layout[3][0], body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.float32)))


def expert_ffn_grad(x, weight, gate, up, down, layout, tile, dy):
    """(dX, dWeight, dGate, dUp, dDown) of ``expert_ffn``'s first output,
    all float32, each tile's forward recomputed from its tokens."""
    dy = dy.astype(x.dtype)

    def body(t, carry):
        dx, dw, dg_w, du_w, dd_w = carry
        rows, slots, real, xt, c, e = _tile(x, weight, layout, t, tile)
        g, u, a, o = _products(xt, gate, up, down, e)
        dyt = jnp.where(real[:, None],
                        dy[jnp.minimum(rows, x.shape[0] - 1)], 0)
        dc = jnp.sum(dyt.astype(jnp.float32) * o, -1)
        do = (dyt.astype(jnp.float32) * c[:, None]).astype(xt.dtype)
        da = _dot(do, down[e], ((1,), (1,)))
        sig = jax.nn.sigmoid(g)
        dg = (da * u * sig * (1.0 + g * (1.0 - sig))).astype(xt.dtype)
        du = (da * g * sig).astype(xt.dtype)
        dxt = _dot(dg, gate[e], ((1,), (1,))) + _dot(du, up[e], ((1,), (1,)))
        return (dx.at[rows].add(dxt, mode="drop", unique_indices=True),
                dw.at[rows, slots].add(dc, mode="drop", unique_indices=True),
                dg_w.at[e].add(_dot(xt, dg, ((0,), (0,)))),
                du_w.at[e].add(_dot(xt, du, ((0,), (0,)))),
                dd_w.at[e].add(_dot(a, do, ((0,), (0,)))))
    zeros = [jnp.zeros(v.shape, jnp.float32)
             for v in (x, weight, gate, up, down)]
    return lax.fori_loop(0, layout[3][0], body, tuple(zeros))


def _ffn_args(ins, ctx):
    """Operands of the products in bfloat16 where the AMP policy lists the
    op white — the routing weights and every sum stay float32 — and as
    they come otherwise."""
    x, weight = ins["X"][0], ins["TopkWeight"][0].astype(jnp.float32)
    mats = [ins[s][0] for s in ("Gate", "Up", "Down")]
    if ctx.amp is not None \
            and ctx.amp.lists.colour("moe_expert_ffn") == "white":
        x = x.astype(jnp.bfloat16)
        mats = [m.astype(jnp.bfloat16) for m in mats]
    else:
        mats = [m.astype(x.dtype) for m in mats]
    return x, weight, mats, tuple(ins[s][0] for s in _LAYOUT)


# Where the grouped Pallas kernels are the body (tests add "cpu": interpreted).
_GROUPED_PLATFORMS = ("tpu",)


def _bodies(ctx, op_type, x, gate, tile):
    """(``expert_ffn``, ``expert_ffn_grad``) or the grouped kernels' pair of
    the same signatures, from what the op can observe: a TPU trace on one
    device (a per-shard lowering is not written), no
    ``FLAGS_pallas_kernels=False``, and shapes the kernels' ``supported()``
    takes.  Notes which under ``op_type``."""
    from ..compile_cache import note_kernel_body
    from .pallas import grouped_experts, interpret_mode, kernel_allowed

    if kernel_allowed(ctx, _GROUPED_PLATFORMS) \
            and getattr(ctx, "mesh", None) is None \
            and grouped_experts.supported(x, gate, tile):
        note_kernel_body(op_type, "grouped")
        interpret = interpret_mode(ctx)
        return (functools.partial(grouped_experts.forward,
                                  interpret=interpret),
                functools.partial(grouped_experts.backward,
                                  interpret=interpret))
    note_kernel_body(op_type, "loop")
    return expert_ffn, expert_ffn_grad


def _ffn_compute(ins, attrs, ctx, op_index):
    x, weight, mats, layout = _ffn_args(ins, ctx)
    tile = int(attrs["tile"])
    forward, _ = _bodies(ctx, "moe_expert_ffn", x, mats[0], tile)
    y, pairs = forward(x, weight, *mats, layout, tile)
    return {"Out": y.astype(ins["X"][0].dtype), "Pairs": pairs.reshape(1)}


def _ffn_grad_compute(ins, attrs, ctx, op_index):
    x, weight, mats, layout = _ffn_args(ins, ctx)
    tile = int(attrs["tile"])
    _, backward = _bodies(ctx, "moe_expert_ffn_grad", x, mats[0], tile)
    grads = backward(x, weight, *mats, layout, tile, ins["GRAD::Out"][0])
    return {"GRAD::" + slot: [g.astype(ins[slot][0].dtype)]
            for slot, g in zip(("X", "TopkWeight", "Gate", "Up", "Down"),
                               grads)}


register_op("moe_expert_ffn",
            ["X", "TopkWeight", "Gate", "Up", "Down"] + list(_LAYOUT),
            ["Out", "Pairs"], infer=_ffn_infer, compute=_ffn_compute,
            no_grad_inputs=_LAYOUT)

# the gradient op the default grad maker emits: a loop of its own, not the
# vjp of the forward's (a loop with a device-side trip count has none)
register_op("moe_expert_ffn_grad", (), (), infer=_generic_grad_infer,
            compute=_ffn_grad_compute, grad=None,
            doc="gradient of moe_expert_ffn")
