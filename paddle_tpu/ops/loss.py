"""Loss ops.

Parity: reference ``cross_entropy_op.cc``,
``softmax_with_cross_entropy_op.cc`` (the fused hot op named in the north
star), ``sigmoid_cross_entropy_with_logits_op.cc``, ``huber_loss_op.cc``,
``smooth_l1_loss_op.cc``, ``hinge_loss_op.cc``, ``log_loss_op.cc``,
``rank_loss_op.cc``, ``margin_rank_loss_op.cc`` — TPU-native: the fused
softmax+CE is written as logsumexp-based log-softmax so its vjp is exactly
the numerically-stable ``softmax - onehot`` kernel the reference hand-writes.

A vocabulary head's backward — ``softmax_with_cross_entropy_grad`` ->
``elementwise_add_grad`` (the bias; optional) -> ``mul_grad`` of the same
logits — lowers as ONE body where ``_head_chain_rule`` (below) takes it:
``ops/pallas/head_grad.py`` makes a tile of the logits' gradient once and
feeds the bias's sum and both products, where XLA rebuilds it inside each of
the three (14.4 -> 6.3 ms a step at ``[16384, 512] x [512, 32000]``;
PERF.md 6.21).  The Fluid program is not edited: the rule reads it at trace
time, and ``compile_cache.stats()["kernel_bodies"]`` says which body a chain
took (``mul_grad:head_fused`` / ``mul_grad:head_by_op``).  A test forces
either body through ``_HEAD_PLATFORMS`` (and ``compile_cache.clear()``: the
patch is in no cache key).
"""

import numpy as np

import jax
import jax.numpy as jnp

from ..registry import (fluid_scope_name, get_op_def, in_var, note_work,
                        register_chain, register_op, same_shape_infer,
                        set_output)


def _rowwise_out_infer(op, block, x_slot="X"):
    x = in_var(op, block, x_slot)
    set_output(op, block, "Out" if "Out" in op.outputs else "Loss",
               tuple(x.shape[:-1]) + (1,), x.dtype)


# -- cross_entropy (takes probabilities; cross_entropy_op.cc) ---------------

def _cross_entropy_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Y", tuple(x.shape[:-1]) + (1,), x.dtype)


def _cross_entropy_compute(ins, attrs, ctx, op_index):
    x, label = ins["X"][0], ins["Label"][0]
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x), axis=-1, keepdims=True)
    else:
        idx = label.reshape(label.shape[:-1] + (1,)) if label.shape[-1] == 1 \
            else label[..., None]
        picked = jnp.take_along_axis(x, idx.astype(jnp.int32), axis=-1)
        loss = -jnp.log(picked)
        loss = loss.reshape(x.shape[:-1] + (1,))
    return {"Y": loss}


register_op(
    "cross_entropy", ["X", "Label"], ["Y"], infer=_cross_entropy_infer,
    compute=_cross_entropy_compute, no_grad_inputs=("Label",),
)


# -- softmax_with_cross_entropy (fused; the hot op) -------------------------

def _swce_infer(op, block):
    logits = in_var(op, block, "Logits")
    set_output(op, block, "Softmax", logits.shape, logits.dtype)
    set_output(op, block, "Loss", tuple(logits.shape[:-1]) + (1,), logits.dtype)


def _swce_compute(ins, attrs, ctx, op_index):
    logits, label = ins["Logits"][0], ins["Label"][0]
    eps = float(attrs.get("label_smooth_eps", 0.0))
    if eps and not attrs.get("soft_label", False):
        # fused uniform label smoothing: target = (1-eps)*onehot + eps/C;
        # loss = (1-eps)*nll + eps*(lse - mean(logits)).  Keeps the [N, C]
        # soft-label tensor out of HBM (vs one_hot + label_smooth +
        # soft_label CE, which materializes it three times).
        lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        log_sm = logits - lse
        idx = label if label.shape[-1] == 1 else label[..., None]
        # the label's logit as a masked row sum (the same float: zeros
        # added), which XLA folds into the pass that sums the exponentials;
        # ``take_along_axis(log_sm, idx)`` is a gather, whose operand it
        # writes out whole — 2.1 GB of float32 at [16384, 32000], which rode
        # in the bias gradient's reduction until the head's backward became
        # one kernel (PERF.md 6.21)
        hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1) == idx
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1,
                         keepdims=True) - lse
        uniform = lse[..., 0:1] - jnp.mean(logits, axis=-1, keepdims=True)
        loss = (1.0 - eps) * -picked + eps * uniform
        ignore = attrs.get("ignore_index", -100)
        if ignore != -100:
            loss = jnp.where(idx == ignore, 0.0, loss)
        return {"Softmax": jnp.exp(log_sm), "Loss": loss}
    log_sm = jax.nn.log_softmax(logits, axis=-1)
    softmax = jnp.exp(log_sm)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * log_sm, axis=-1, keepdims=True)
    else:
        idx = label if label.shape[-1] == 1 else label[..., None]
        picked = jnp.take_along_axis(log_sm, idx.astype(jnp.int32), axis=-1)
        ignore = attrs.get("ignore_index", -100)
        loss = -picked
        if ignore != -100:
            # any index (including negative ones like -1) may be ignored;
            # -100 is the "none" sentinel (matches the sigmoid variant).
            # Negative ignored labels wrap through take_along_axis but the
            # picked value is discarded by this mask, so the loss is exact.
            loss = jnp.where(idx == ignore, 0.0, loss)
    return {"Softmax": softmax, "Loss": loss}


register_op(
    "softmax_with_cross_entropy", ["Logits", "Label"], ["Softmax", "Loss"],
    infer=_swce_infer, compute=_swce_compute, no_grad_inputs=("Label",),
)


# -- a vocabulary head's backward as ONE body --------------------------------
#
# ``softmax_with_cross_entropy_grad`` -> ``elementwise_add_grad`` (the bias;
# optional) -> ``mul_grad`` of the same logits.  Op by op XLA rebuilds the
# ``[N, V]`` gradient inside each of its three consumers; the chain rule
# below lowers the two or three ops with ``ops/pallas/head_grad.py``, which
# makes a tile of it once and feeds the bias's sum and both products.

# Where the kernel may be the chain's body (tests add "cpu": interpreted).
_HEAD_PLATFORMS = ("tpu",)


def _only(names):
    """A slot's one name (None: a hole, no name or several)."""
    names = list(names or ())
    return names[0] if len(names) == 1 and names[0] else None


def head_chain(ops, i):
    """``(elementwise_add_grad or None, mul_grad)`` where ``ops[i]`` — a
    ``softmax_with_cross_entropy_grad`` — and the one or two ops after it
    are a head's backward: hard labels, no ``ignore_index``, no gradient
    arriving through ``Softmax``, the logits' gradient handed to the
    gradient of the bias add and of the ``mul`` that made them (or of the
    ``mul`` alone), every result named.  None otherwise."""
    op = ops[i]
    if op.attrs.get("soft_label", False) \
            or op.attrs.get("ignore_index", -100) != -100 \
            or any(op.inputs.get("GRAD::Softmax", ())):
        return None
    made, grad = _only(op.inputs.get("Logits")), \
        _only(op.outputs.get("GRAD::Logits"))
    rest = ops[i + 1:i + 3]
    add = None
    if rest and rest[0].type == "elementwise_add_grad":
        add, rest = rest[0], rest[1:]
        if _only(add.inputs.get("Out::Out")) != made \
                or _only(add.inputs.get("GRAD::Out")) != grad \
                or not _only(add.outputs.get("GRAD::Y")):
            return None
        made, grad = _only(add.inputs.get("X")), \
            _only(add.outputs.get("GRAD::X"))
    if not rest or rest[0].type != "mul_grad" or not made or not grad:
        return None
    mul = rest[0]
    if _only(mul.inputs.get("Out::Out")) != made \
            or _only(mul.inputs.get("GRAD::Out")) != grad \
            or not _only(mul.outputs.get("GRAD::X")) \
            or not _only(mul.outputs.get("GRAD::Y")):
        return None
    return add, mul


def _read_outside(program, chain, names):
    """Whether an op of ``program`` that is not of ``chain`` reads one of
    ``names``."""
    inside = {id(op) for op in chain}
    return any(n in names
               for block in program.blocks for op in block.ops
               if id(op) not in inside
               for slot in op.inputs.values() for n in slot)


def _head_shards(ctx, names, batch):
    """The mesh axes the head's rows are split over, for a per-shard kernel
    whose dW and db are summed over them: () on one device; the populated
    data axes under a mesh that has no other populated axis, divides the
    batch and keeps the variables ``names`` (the weight, the bias) whole on
    every device; None where that cannot be said (the chain then stays op by
    op)."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is None:
        return ()
    from ..parallel.embedding import _data_axes, _extent

    axes = _data_axes(ctx)
    specs = getattr(ctx, "state_specs", None) or {}
    split = any(e is not None for n in names for e in specs.get(n) or ())
    if not axes or split or batch % mesh.devices.size \
            or _extent(mesh, axes) != mesh.devices.size:
        return None
    return axes


def _head_operands(op, add, mul, env, ctx):
    """The chain's operands as its ops see them (the AMP policy's casts
    applied), flattened to the kernel's ``[N, D] x [D, V]`` — (x, w, the
    stored product, the bias or zeros, the float32 logits, labels, the rows'
    ``Loss@GRAD``) — or None where they are not a float32 loss over a 2-D
    weight's bf16 product (float32 products: XLA's run one bf16 pass by
    default, Mosaic's several)."""
    from .math import _flatten_to_2d

    def seen(o, *slots):
        ins = {s: [env[_only(o.inputs[s])]] for s in slots}
        if ctx.amp is not None:
            ins = ctx.amp.cast_inputs(o.type, ins)
        return [ins[s][0] for s in slots]
    logits, ct = seen(op, "Logits", "GRAD::Loss")
    label = env[_only(op.inputs["Label"])]
    x, w, z = seen(mul, "X", "Y", "Out::Out")
    xnc = mul.attrs.get("x_num_col_dims", 1)
    if w.ndim != 2 or mul.attrs.get("y_num_col_dims", 1) != 1 \
            or xnc != x.ndim - 1 or logits.dtype != jnp.float32 \
            or not x.dtype == w.dtype == z.dtype == jnp.bfloat16 \
            or label.size * w.shape[1] != z.size:
        return None
    bias = jnp.zeros(w.shape[1:], jnp.float32)
    if add is not None:
        (bias,) = seen(add, "Y")
        if bias.dtype != jnp.float32 or bias.shape != w.shape[1:] \
                or add.attrs.get("axis", -1) not in (-1, z.ndim - 1):
            return None
    n = label.size
    return (_flatten_to_2d(x, xnc), w, z.reshape(n, -1), bias,
            logits.reshape(n, -1), label.reshape(n), ct.reshape(n))


def _row_lse(logits, eps):
    """The rows' log-sum-exp by the expression the forward op computed it
    with (``_swce_compute``'s two hard-label branches), so that XLA merges
    the two and the backward makes no pass of its own over the logits."""
    if eps:
        return jax.scipy.special.logsumexp(logits, axis=-1)
    m = jnp.max(logits, axis=-1, keepdims=True)
    return (jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True))
            + m)[:, 0]


def _head_chain_rule(ops, i, env, ctx, kept):
    """``registry.compute_ops``'s rule at a
    ``softmax_with_cross_entropy_grad``: the two or three ops of a head's
    backward by the one kernel — under ``mul_grad``'s Fluid scope, its
    ``X@GRAD`` and ``Y@GRAD`` and the bias's ``Y@GRAD`` written, the ``[N,
    V]`` gradients in between never whole — where nothing else reads those
    or the ``Softmax`` output, on a platform of ``_HEAD_PLATFORMS``, at a
    shape the kernel takes (per shard under a data-parallel mesh).
    ``kernel_bodies`` says ``mul_grad:head_fused`` then, and
    ``mul_grad:head_by_op`` for a head's chain left op by op."""
    from ..compile_cache import note_kernel_body
    from .pallas import head_grad as hg, interpret_mode, kernel_allowed

    chain = head_chain(ops, i)
    if chain is None:
        return 0
    op, (add, mul) = ops[i], chain
    ours = [o for o in (op, add, mul) if o is not None]
    hidden = {_only(o.outputs[s]) for o, s in zip(ours[:-1], (
        "GRAD::Logits", "GRAD::X"))} | set(op.inputs.get("Out::Softmax", ()))
    args = axes = None
    if kernel_allowed(ctx, _HEAD_PLATFORMS) and not hidden.intersection(kept) \
            and not _read_outside(ctx.program, ours, hidden):
        args = _head_operands(op, add, mul, env, ctx)
    if args is not None:
        axes = _head_shards(ctx, [_only(o.inputs["Y"]) for o in ours[1:]],
                            env[_only(mul.inputs["X"])].shape[0])
    fused = axes is not None and hg.supported(
        args[0].shape[0] // (ctx.mesh.devices.size if axes else 1),
        *args[1].shape, args[0].dtype)
    note_kernel_body("mul_grad", "head_fused" if fused else "head_by_op")
    if not fused:
        return 0
    eps = float(op.attrs.get("label_smooth_eps", 0.0))
    interpret = interpret_mode(ctx)

    def run(x, w, z, bias, lse, label, ct):
        dx, dw, db = hg.head_grad(x, w, z, bias, lse, label, ct, eps,
                                  interpret)
        if axes:
            dw, db = jax.lax.psum((dw, db), axes)
        return dx, dw, db
    if axes:
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import shard_map_norep

        rows = P(axes if len(axes) > 1 else axes[0])
        run = shard_map_norep(
            run, ctx.mesh, in_specs=(rows, P(), rows, P(), rows, rows, rows),
            out_specs=(rows, P(), P()))
    x, w, z, bias, logits, label, ct = args
    with jax.named_scope(fluid_scope_name(mul)):
        # the kernel stands for ``mul_grad``'s two products (and the bias's
        # sum): the same parts the op-by-op spelling notes
        note_work(mul, get_op_def("mul").work(
            {"X": [x], "Y": [w]}, {}, ("X", "Y")))
        dx, dw, db = run(x, w, z, bias, _row_lse(logits, eps), label, ct)
    env[_only(mul.outputs["GRAD::X"])] = dx.reshape(
        env[_only(mul.inputs["X"])].shape)
    env[_only(mul.outputs["GRAD::Y"])] = dw
    if add is not None:
        env[_only(add.outputs["GRAD::Y"])] = db
    return len(ours)


register_chain("softmax_with_cross_entropy_grad", _head_chain_rule)


# -- exit_gate_loss: a looped model's expected loss over its exit gate -------

def _exit_gate_infer(op, block):
    passes = len(op.inputs["CE"])
    if len(op.inputs.get("H", ())) != passes - 1:
        raise ValueError(
            "exit_gate_loss: %d passes' losses take the hidden states of "
            "the %d passes before the last (the last pass takes the rest "
            "of the exit mass, its own gate enters no loss), got %d"
            % (passes, passes - 1, len(op.inputs.get("H", ()))))
    set_output(op, block, "Loss", (1,), "float32")
    set_output(op, block, "Stats", (2 * passes,), "float32")


def _exit_gate_compute(ins, attrs, ctx, op_index):
    """``Loss`` = mean over tokens of ``sum_t p_t CE_t - beta H(p)``, where
    ``lam_t = sigmoid(H_t W + B)`` is pass t's exit gate, ``p_t = lam_t
    prod_{j<t} (1 - lam_j)`` for t < P and ``p_P = prod_{j<P} (1 -
    lam_j)`` the exit distribution of a token over the P passes, and
    ``H(p) = -sum_t p_t log p_t``.  All in float32 and through the logs
    of the gates (``log p_t = logsigmoid(z_t) + sum_{j<t} logsigmoid(-z_j)``:
    a gate that saturates gives no ``0 log 0``); the gate's product is a
    float32 one at the highest precision.  ``Stats`` = the P passes' mean
    cross entropy, then their mean exit mass."""
    ce = [c.astype(jnp.float32).reshape(-1) for c in ins["CE"]]
    w = ins["W"][0].astype(jnp.float32)
    b = ins["B"][0].astype(jnp.float32)
    stay = jnp.zeros_like(ce[0])        # log prod_{j<t} (1 - lam_j)
    logp = []
    for h in ins["H"]:
        z = jnp.matmul(h.astype(jnp.float32).reshape(-1, h.shape[-1]), w,
                       precision=jax.lax.Precision.HIGHEST).reshape(-1) + b
        logp.append(jax.nn.log_sigmoid(z) + stay)
        stay = stay + jax.nn.log_sigmoid(-z)
    logp = jnp.stack(logp + [stay])                          # [P, N]
    p, ce = jnp.exp(logp), jnp.stack(ce)
    beta = float(attrs.get("beta", 0.0))
    loss = jnp.mean(jnp.sum(p * (ce + beta * logp), 0))
    return {"Loss": loss.reshape(1),
            "Stats": jnp.concatenate([jnp.mean(ce, 1), jnp.mean(p, 1)])}


register_op("exit_gate_loss", ["H", "CE", "W", "B"], ["Loss", "Stats"],
            infer=_exit_gate_infer, compute=_exit_gate_compute)


# -- sigmoid_cross_entropy_with_logits --------------------------------------

def _scewl_compute(ins, attrs, ctx, op_index):
    x, label = ins["X"][0], ins["Label"][0]
    # stable: max(x,0) - x*z + log(1+exp(-|x|))
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    if ignore != -100:
        loss = jnp.where(label == ignore, 0.0, loss)
    return {"Out": loss}


register_op(
    "sigmoid_cross_entropy_with_logits", ["X", "Label"], ["Out"],
    infer=same_shape_infer("X", "Out"), compute=_scewl_compute,
    no_grad_inputs=("Label",),
)


# -- huber / smooth_l1 / hinge / log_loss / rank losses ---------------------

def _huber_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Residual", x.shape, x.dtype)
    set_output(op, block, "Out", x.shape, x.dtype)


def _huber_compute(ins, attrs, ctx, op_index):
    x, y = ins["X"][0], ins["Y"][0]
    d = attrs.get("delta", 1.0)
    r = y - x
    loss = jnp.where(jnp.abs(r) <= d, 0.5 * r * r, d * (jnp.abs(r) - 0.5 * d))
    return {"Residual": r, "Out": loss}


register_op("huber_loss", ["X", "Y"], ["Residual", "Out"],
            infer=_huber_infer, compute=_huber_compute,
            no_grad_inputs=("Y",))


def _smooth_l1_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "Diff", x.shape, x.dtype)
    set_output(op, block, "Out", (x.shape[0], 1), x.dtype)


def _smooth_l1_compute(ins, attrs, ctx, op_index):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if ins.get("InsideWeight") and ins["InsideWeight"][0] is not None:
        diff = diff * ins["InsideWeight"][0]
    ad = jnp.abs(diff)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if ins.get("OutsideWeight") and ins["OutsideWeight"][0] is not None:
        loss = loss * ins["OutsideWeight"][0]
    out = jnp.sum(loss.reshape(x.shape[0], -1), axis=1, keepdims=True)
    return {"Diff": diff, "Out": out}


register_op(
    "smooth_l1_loss", ["X", "Y", "InsideWeight", "OutsideWeight"],
    ["Diff", "Out"], infer=_smooth_l1_infer, compute=_smooth_l1_compute,
    no_grad_inputs=("Y", "InsideWeight", "OutsideWeight"),
)


def _hinge_compute(ins, attrs, ctx, op_index):
    logits, labels = ins["Logits"][0], ins["Labels"][0]
    return {"Loss": jnp.maximum(1.0 - (2.0 * labels - 1.0) * logits, 0.0)}


register_op("hinge_loss", ["Logits", "Labels"], ["Loss"],
            infer=lambda op, block: set_output(
                op, block, "Loss", in_var(op, block, "Logits").shape,
                in_var(op, block, "Logits").dtype),
            compute=_hinge_compute, no_grad_inputs=("Labels",))


def _log_loss_compute(ins, attrs, ctx, op_index):
    p, label = ins["Predicted"][0], ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return {"Loss": loss}


register_op("log_loss", ["Predicted", "Labels"], ["Loss"],
            infer=lambda op, block: set_output(
                op, block, "Loss", in_var(op, block, "Predicted").shape,
                in_var(op, block, "Predicted").dtype),
            compute=_log_loss_compute, no_grad_inputs=("Labels",))


def _rank_loss_compute(ins, attrs, ctx, op_index):
    label, left, right = ins["Label"][0], ins["Left"][0], ins["Right"][0]
    d = left - right
    return {"Out": jnp.log1p(jnp.exp(d)) - label * d}


register_op("rank_loss", ["Label", "Left", "Right"], ["Out"],
            infer=lambda op, block: set_output(
                op, block, "Out", in_var(op, block, "Left").shape,
                in_var(op, block, "Left").dtype),
            compute=_rank_loss_compute, no_grad_inputs=("Label",))


def _margin_rank_loss_compute(ins, attrs, ctx, op_index):
    label, x1, x2 = ins["Label"][0], ins["X1"][0], ins["X2"][0]
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    act = (out > 0).astype(x1.dtype)
    return {"Out": out, "Activated": act}


register_op(
    "margin_rank_loss", ["Label", "X1", "X2"], ["Out", "Activated"],
    infer=lambda op, block: (
        set_output(op, block, "Out", in_var(op, block, "X1").shape,
                   in_var(op, block, "X1").dtype),
        set_output(op, block, "Activated", in_var(op, block, "X1").shape,
                   in_var(op, block, "X1").dtype),
    ),
    compute=_margin_rank_loss_compute, no_grad_inputs=("Label",),
)


# -- modified_huber_loss (reference modified_huber_loss_op.cc) --------------

def _mhl_infer(op, block):
    x = in_var(op, block, "X")
    set_output(op, block, "IntermediateVal", x.shape, x.dtype)
    set_output(op, block, "Out", x.shape, x.dtype)


def _mhl_compute(ins, attrs, ctx, op_index):
    x, y = ins["X"][0], ins["Y"][0]  # y in {0, 1}
    inter = x * (2.0 * y - 1.0)      # x * y' with y' in {-1, +1}
    loss = jnp.where(inter < -1.0, -4.0 * inter,
                     jnp.where(inter < 1.0, (1.0 - inter) ** 2, 0.0))
    return {"IntermediateVal": inter, "Out": loss}


register_op("modified_huber_loss", ["X", "Y"], ["IntermediateVal", "Out"],
            infer=_mhl_infer, compute=_mhl_compute, no_grad_inputs=("Y",))


# -- lambda_cost: LambdaRank listwise cost (v1 legacy LambdaCost layer,
# reference legacy/gserver/layers/CostLayer.cpp LambdaCost) --------------

def _lambda_cost_infer(op, block):
    x = in_var(op, block, "Score")
    set_output(op, block, "Out", (x.shape[0], 1), x.dtype)


def _lambda_cost_compute(ins, attrs, ctx, op_index):
    """Per-list LambdaRank: for each document pair (i, j) with
    rel_i > rel_j, loss += |deltaNDCG_ij| * log(1 + exp(-(s_i - s_j))).
    Scores/relevances are padded [B, T, 1]; Length masks the pad.
    deltaNDCG swaps positions i,j in the DCG of the model's ranking,
    normalized by the ideal DCG over the top ``ndcg_num``."""
    score = ins["Score"][0].reshape(ins["Score"][0].shape[0], -1)
    rel = ins["Rel"][0].reshape(score.shape).astype(score.dtype)
    length = ins.get("Length", [None])[0]
    b, t = score.shape
    ndcg_num = int(attrs.get("ndcg_num", 5))
    pos = jnp.arange(t)
    valid = (jnp.ones((b, t), bool) if length is None
             else pos[None, :] < length.reshape(b, 1))
    neg_inf = jnp.asarray(-1e9, score.dtype)
    s = jnp.where(valid, score, neg_inf)
    r = jnp.where(valid, rel, 0.0)

    # rank of each doc under the model scores (0 = best)
    order = jnp.argsort(-s, axis=1)
    rank = jnp.argsort(order, axis=1)
    disc = 1.0 / jnp.log2(2.0 + rank.astype(score.dtype))   # [B, T]
    gain = (2.0 ** r - 1.0)
    # ideal DCG over the top ndcg_num of the TRUE relevances
    r_sorted = -jnp.sort(-r, axis=1)
    ideal_disc = 1.0 / jnp.log2(2.0 + jnp.arange(t, dtype=score.dtype))
    topk_mask = (jnp.arange(t) < ndcg_num).astype(score.dtype)
    idcg = jnp.sum((2.0 ** r_sorted - 1.0) * ideal_disc * topk_mask,
                   axis=1, keepdims=True)
    idcg = jnp.maximum(idcg, 1e-8)

    # |deltaNDCG| of swapping i and j = |g_i - g_j| * |d_i - d_j| / idcg
    dg = jnp.abs(gain[:, :, None] - gain[:, None, :])
    dd = jnp.abs(disc[:, :, None] - disc[:, None, :])
    delta = dg * dd / idcg[:, :, None]

    diff = score[:, :, None] - score[:, None, :]
    pair_loss = jnp.log1p(jnp.exp(-jnp.clip(diff, -30.0, 30.0)))
    better = (rel[:, :, None] > rel[:, None, :]) & \
        valid[:, :, None] & valid[:, None, :]
    out = jnp.sum(jnp.where(better, delta * pair_loss, 0.0), axis=(1, 2))
    return {"Out": out.reshape(b, 1)}


register_op("lambda_cost", ["Score", "Rel", "Length"], ["Out"],
            infer=_lambda_cost_infer, compute=_lambda_cost_compute,
            no_grad_inputs=("Rel", "Length"))
