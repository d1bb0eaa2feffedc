"""Neural-network layers — the user-facing op DSL.

Parity: reference ``python/paddle/fluid/layers/nn.py`` (7k LoC, 123 public
fns).  This module covers the dense/MLP/classification core; conv/pool/norm
live in ``conv.py``, sequence layers in ``sequence.py``, control flow in
``control_flow.py``.  Layers append ops to the default main program and
create parameters via LayerHelper exactly like the reference.
"""

from ..framework import Variable
from ..layer_helper import LayerHelper

__all__ = [
    "fc",
    "embedding",
    "dropout",
    "softmax",
    "cross_entropy",
    "square_error_cost",
    "softmax_with_cross_entropy",
    "exit_gate_loss",
    "fused_attention",
    "selective_scan",
    "causal_conv1d",
    "gated_delta_rule",
    "paged_attention",
    "rms_norm",
    "rotary_embedding",
    "swiglu",
    "select_keys",
    "routed_experts",
    "one_hot",
    "topk",
    "matmul",
    "mul",
    "label_smooth",
    "log",
    "relu",
    "l2_normalize",
    "prelu",
    "maxout",
    "cos_sim",
    "sampling_id",
    "smooth_l1",
    "margin_rank_loss",
    "clip",
    "clip_by_norm",
    "mean",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "nce",
    "hsigmoid",
    "bilinear_tensor_product",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "flatten",
    "sum",
    "multiplex",
    "rank_loss",
    "sigmoid_cross_entropy_with_logits",
    "gaussian_random",
    "mean_iou",
    "dice_loss",
    "image_resize_short",
    "lstm_unit",
    "gru_unit",
    "autoincreased_step_counter",
]


def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    is_test=False,
    name=None,
):
    """Fully-connected layer (reference nn.py:fc): per-input weight matmul
    (mul op), summed, plus bias and activation.  On TPU each mul is a single
    MXU gemm; multiple inputs become independent gemms XLA can batch."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype()

    mul_results = []
    for input_var, p_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        param_num_flatten = num_flatten_dims
        w_rows = 1
        for s in input_shape[param_num_flatten:]:
            w_rows *= s
        param_shape = [w_rows, size]
        w = helper.create_parameter(attr=p_attr, shape=param_shape, dtype=dtype)
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [input_var], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(tmp)

    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="sum", inputs={"X": mul_results}, outputs={"Out": [pre_bias]}
        )
    if helper.bias_attr and helper.kwargs.get("bias_attr") is not False:
        pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    else:
        pre_act = pre_bias
    return helper.append_activation(pre_act)


def embedding(
    input,
    size,
    is_sparse=False,
    is_distributed=False,
    padding_idx=None,
    param_attr=None,
    dtype="float32",
):
    """Embedding lookup (reference nn.py:embedding / lookup_table_op.cc).
    ``is_sparse`` selects the SelectedRows-style sparse-gradient path;
    ``is_distributed`` marks the table for mesh sharding (the pserver
    remote-prefetch equivalent — see parallel/embedding docs)."""
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(
        attr=helper.param_attr, shape=size, dtype=dtype, is_bias=False
    )
    tmp = helper.create_variable_for_type_inference(dtype)
    padding_idx = (
        -1 if padding_idx is None
        else padding_idx if padding_idx >= 0
        else (size[0] + padding_idx)
    )
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [tmp]},
        attrs={"is_sparse": is_sparse, "is_distributed": is_distributed,
               "padding_idx": padding_idx},
    )
    return tmp


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="softmax", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def square_error_cost(input, label):
    """Per-sample squared error (input - label)^2 (reference
    nn.py:1083 square_error_cost / squared_l2_distance_op.cc)."""
    helper = LayerHelper("square_error_cost", input=input)
    minus_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="elementwise_sub",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [minus_out]},
        attrs={"axis": -1},
    )
    square_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="square",
        inputs={"X": [minus_out]},
        outputs={"Out": [square_out]},
    )
    return square_out


def softmax_with_cross_entropy(
    logits, label, soft_label=False, ignore_index=-100,
    numeric_stable_mode=True, return_softmax=False, label_smooth_eps=0.0,
):
    """``label_smooth_eps`` is a TPU-side extension: uniform label smoothing
    fused into the loss kernel (loss = (1-eps)*nll + eps*(lse - mean logits))
    so the [N, C] one-hot/soft-label tensor the reference materializes
    (one_hot + label_smooth + soft_label CE) never exists in HBM."""
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_variable_for_type_inference(dtype=logits.dtype)
    loss = helper.create_variable_for_type_inference(dtype=logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index,
               "label_smooth_eps": float(label_smooth_eps)},
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def exit_gate_loss(hiddens, token_losses, beta=0.0, param_attr=None,
                   bias_attr=None, name=None):
    """The expected loss of a model that runs its layers ``P`` times and
    may leave after any pass: ``token_losses`` are the passes' per-token
    cross entropies ``[B, T, 1]`` (NOT their means), ``hiddens`` the
    passes' states ``[B, T, D]``.  A learned gate ``lam_t = sigmoid(h_t w
    + b)`` (``w`` [D, 1], ``b`` [1], one gate for all passes) gives each
    token its exit distribution ``p_1 = lam_1, p_t = lam_t prod_{j<t} (1 -
    lam_j)``, the last pass taking the rest (so the last state's gate
    enters no loss, and the op is not handed that state); the loss is the
    mean over tokens of ``sum_t p_t CE_t - beta H(p)``, in float32.
    Returns ``(loss [1], stats [2P])``: ``stats``
    holds the passes' mean cross entropy and then their mean exit mass,
    and carries no gradient."""
    helper = LayerHelper("exit_gate_loss", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[hiddens[0].shape[-1], 1],
                                dtype="float32")
    b = helper.create_parameter(attr=helper.bias_attr, shape=[1],
                                dtype="float32", is_bias=True)
    loss = helper.create_variable_for_type_inference(dtype="float32")
    stats = helper.create_variable_for_type_inference(dtype="float32")
    stats.stop_gradient = True
    helper.append_op(
        type="exit_gate_loss",
        inputs={"H": list(hiddens[:-1]), "CE": list(token_losses),
                "W": [w], "B": [b]},
        outputs={"Loss": [loss], "Stats": [stats]},
        attrs={"beta": float(beta)})
    return loss, stats


def fused_attention(q, k, v=None, k_len=None, causal=False, dropout_rate=0.0,
                    is_test=False, scale=None, selected=None, name=None,
                    window=None, n_head=None, v_dim=None, k_shared=None,
                    rope_theta=None, rope_freq_scaling=None, rope_scale=1.0,
                    rope_interleaved=False):
    """Flash attention over head-split tensors q/k/v [B, H, T, D], or —
    ``n_head`` given — over the projections' own outputs [B, T, H * D]
    (grouped heads with ``v``, latent attention without).

    k and v may carry fewer heads than q (grouped-query attention: H a
    whole multiple of theirs), and v another width than q and k (``[B, H,
    T, Dv]``: the result is ``[B, H, T, Dv]``).  ``selected`` is
    ``select_keys``'s packed mask of the keys each query may read, beside
    ``k_len`` and ``causal``.  With any of the three, or for plain-head
    self-attention past ``ops.attention.streams_plain_heads``'s length
    (T above 3584 at D = 128), a TPU trace takes the kernel
    that streams K/V by blocks (``ops/pallas/streamed_attention.py``) and
    the CPU the XLA body.  ``window`` (an int, with ``causal``, Tq == Tk, no
    ``k_len`` and no dropout) keeps of a query's keys the nearest
    ``window``: key ``s`` counts for query ``t`` iff ``t - window < s <=
    t``; it runs on those two bodies too.

    ``k_len`` [B] int masks padded key positions; ``causal`` adds the
    autoregressive mask.  One op, identical semantics in every body; the
    body — streamed, ring, packed or XLA, each with its gradient — is
    chosen at trace time by the op's own rules over platform, mesh and
    shapes (``ops/attention.py`` says which and why);
    ``FLAGS_pallas_kernels=False`` keeps every Pallas body off.

    **Grouped heads where the projections wrote them** (``n_head`` and
    ``v`` given): ``q`` ``[B, T, n_head * D]``, ``k`` ``[B, T, Hkv * D]``,
    ``v`` ``[B, T, Hkv * Dv]`` — the three projections' outputs as they are,
    ``Hkv`` a whole divisor of ``n_head`` read off ``k``'s width — and the
    result ``[B, T, n_head * Dv]``, what the output projection reads: no
    reshape, rotation or transpose is asked for between the projections and
    the op.  ``causal``, ``scale``, ``window``, ``selected``, ``k_len`` and
    dropout mean what they mean over ``[B, H, T, D]``.  ``rope_theta`` given,
    the OP rotates every head of ``q`` and ``k`` by its position, as
    ``rotary_embedding(x, theta=rope_theta, freq_scaling=rope_freq_scaling,
    scale=rope_scale, interleaved=rope_interleaved)`` over the ``[B, T, H,
    D]`` view would (a per-head norm of q or k comes BEFORE the op:
    ``rms_norm(x, group=D)``, over the projection as it lies).  On a TPU the
    streamed kernels address the three arrays where they lie and rotate q
    and k as they load their blocks (``D`` and
    ``Dv`` whole lane tiles of 128 — a rotation: rotate-half of 128-wide
    heads —, T whole 128-key blocks, no ``k_len``, no dropout, no mesh);
    anything else takes the 4-D kernels or the XLA body behind the op's own
    rotation and transposes, which is the definition.

    **The projections' layout of latent attention** (``n_head`` and ``v_dim``
    given, ``v`` None): ``q`` is the query projection's output ``[B, T,
    n_head * (nope + rope)]``, a head's columns ``[q_nope | q_rope]``;
    ``k`` the key/value projection's output WHOLE, ``[B, T, n_head * (nope
    + v_dim)]``, a head's columns ``[k_nope | v]`` (one variable read once:
    its gradient is one array); ``k_shared`` ``[B, T, rope]`` the ONE key
    part every head reads beside its own (absent: ``rope`` is 0);
    ``rope_theta`` the base by which the op rotates each head's ``q_rope``
    and ``k_shared`` by their positions, neighbouring pairs
    (``rotary_embedding(.., interleaved=True)``), None for no rotation.
    The result is ``[B, T, n_head * v_dim]``, what the output projection
    reads: no split, join, broadcast or transpose is asked for between the
    projections and the op.  On a TPU the streamed kernels address these
    arrays where they lie (``nope`` and ``v_dim`` whole lane tiles of 128,
    ``rope`` whole tiles or one half-tile, T whole 128-key blocks, no
    ``k_len``, no dropout, no mesh); anything else takes the XLA body,
    which is that composition written out.  No ``selected``, no
    ``window``."""
    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    in_place = n_head is not None
    latent = in_place and v is None
    if latent and (v_dim is None or selected is not None
                   or window is not None):
        raise ValueError(
            "fused_attention over the projections' outputs (n_head given) "
            "without v takes k as the key/value projection's output whole "
            "with v_dim, and no selected or window")
    if not in_place and rope_theta is not None \
            or not latent and not (k_shared is None and v_dim is None):
        raise ValueError("fused_attention: rope_theta belongs to the "
                         "projections' layout (n_head given), k_shared and "
                         "v_dim to its latent form (no v)")
    inputs = {"Q": [q], "K": [k]}
    if not latent:
        inputs["V"] = [v]
    if k_len is not None:
        inputs["KLen"] = [k_len]
    if selected is not None:
        inputs["Selected"] = [selected]
    if k_shared is not None:
        inputs["KShared"] = [k_shared]
    attrs = {"causal": causal, "dropout_rate": float(dropout_rate),
             "is_test": is_test}
    if scale is not None:
        attrs["scale"] = float(scale)
    if in_place:
        attrs["n_head"] = int(n_head)
    if latent:
        attrs["v_dim"] = int(v_dim)
    if rope_theta is not None:
        attrs["rope_theta"] = float(rope_theta)
    if not latent:
        # rotary_embedding's own attributes, kept as that layer keeps them
        if rope_freq_scaling is not None:
            attrs["rope_freq_scaling"] = {
                key: float(rope_freq_scaling[key]) for key in (
                    "factor", "original_length", "beta_fast", "beta_slow")}
        if float(rope_scale) != 1.0:
            attrs["rope_scale"] = float(rope_scale)
        if rope_interleaved:
            attrs["rope_interleaved"] = True
    outputs = {"Out": [out]}
    from ..ops.attention import streams_plain_heads
    marked = not in_place and selected is None and k.shape[1] == q.shape[1] \
        and streams_plain_heads(q.shape, k.shape, v.shape, k_len is not None,
                                float(dropout_rate))
    if window is not None:
        if k_len is not None or dropout_rate:
            raise ValueError("fused_attention: a window takes no k_len and "
                             "no dropout")
        # only an op with a window carries the attribute: a program
        # without one keeps its text, and its compiled module its name
        attrs["window"] = int(window)
        marked = True
    if marked:
        attrs["keep_lse"] = True
    if marked or in_place or selected is not None \
            or k.shape[1] != q.shape[1]:
        # the bodies of grouped-query / selected-key / long plain-head
        # attention, and every body over the projections' layout, keep the
        # rows' log-sum-exp for their gradient op
        lse = helper.create_variable_for_type_inference(dtype="float32")
        lse.stop_gradient = True
        outputs["LSE"] = [lse]
    helper.append_op(
        type="fused_attention", inputs=inputs, outputs=outputs, attrs=attrs,
    )
    return out


def selective_scan(x, delta, a, b, c, d, delta_bias=None, chunk=64,
                   name=None):
    """A state-space (Mamba) layer's recurrence over time, in float32:
    ``step_t = softplus(delta_t + delta_bias)``, ``s_t = exp(step_t (x) 1 *
    a) * s_{t-1} + (step_t * x_t) (x) b_t`` from ``s_0 = 0``, ``y_t = s_t c_t
    + d * x_t``.  ``x`` and ``delta`` (the step size BEFORE its softplus) are
    ``[B, T, E]``, ``a`` ``[E, N]`` (negative), ``b`` and ``c`` ``[B, T, N]``,
    ``d`` and ``delta_bias`` ``[E]`` — variables, whoever made them.  Returns
    ``(y [B, T, E], state [B, E, N])``: ``state`` is ``s_T`` and carries no
    gradient.  On a TPU the op and its gradient are Pallas kernels chunked
    over time, ``chunk`` steps a chunk, the backward recomputing a chunk
    from the state the forward kept at its start
    (``ops/pallas/selective_scan.py``); elsewhere an XLA ``lax.scan`` over
    chunks.  Under mixed precision the op and its gradient are float32."""
    helper = LayerHelper("selective_scan", name=name)
    y, state, starts = (helper.create_variable_for_type_inference(
        dtype="float32") for _ in range(3))
    state.stop_gradient = starts.stop_gradient = True
    inputs = {"X": [x], "Delta": [delta], "A": [a], "B": [b], "C": [c],
              "D": [d]}
    if delta_bias is not None:
        inputs["DeltaBias"] = [delta_bias]
    helper.append_op(type="selective_scan", inputs=inputs,
                     outputs={"Out": [y], "State": [state],
                              "Starts": [starts]},
                     attrs={"chunk": int(chunk)})
    return y, state


def gated_delta_rule(q, k, v, g, beta, a_log, dt_bias, out_gate, scale,
                     chunk=64, epsilon=1e-6, out_norm_attr=None, name=None):
    """A delta-attention mixer's recurrence with what the mixer does around
    it, in float32, a head at a time: the gated delta rule with a decay for
    every key channel, ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
    + beta_t k_t v_t^T`` from ``S_0 = 0``, ``o_t = S_t^T q_t``.  ``q``, ``k``
    and the gate's pre-activation ``g`` are ``[B, T, H, Dk]``, ``v`` and the
    output gate's pre-activation ``out_gate`` ``[B, T, H, Dv]``, ``beta``
    ``[B, T, H]``, ``a_log`` ``[H]``, ``dt_bias`` ``[H, Dk]`` — variables,
    whoever made them.  The op L2-normalises ``q`` and ``k`` a head,
    multiplies ``q`` by ``scale``, makes the log-decays ``-exp(a_log) *
    softplus(g + dt_bias)`` and returns ``rms_norm(o; gain, epsilon) *
    sigmoid(out_gate)`` a head, the gain ``[Dv]`` a parameter of
    ``out_norm_attr`` (initialised to 1).  Returns ``(out [B, T, H, Dv],
    state [B, H, Dk, Dv])``: ``state`` is ``S_T`` and carries no gradient.
    Chunked over time, ``chunk`` steps a chunk (16 times a power of two),
    matrix products inside; the backward makes a group of chunks again from
    the state the forward kept at its start (``ops/gated_delta_rule.py``).
    Under mixed precision the op takes its operands as they come and is
    float32 inside, and so is its gradient."""
    from ..initializer import ConstantInitializer

    helper = LayerHelper("gated_delta_rule", param_attr=out_norm_attr,
                         name=name)
    out, state, starts = (helper.create_variable_for_type_inference(
        dtype="float32") for _ in range(3))
    state.stop_gradient = starts.stop_gradient = True
    gain = helper.create_parameter(
        attr=helper.param_attr, shape=[v.shape[-1]], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    helper.append_op(type="gated_delta_rule",
                     inputs={"Q": [q], "K": [k], "V": [v], "G": [g],
                             "Beta": [beta], "ALog": [a_log],
                             "DtBias": [dt_bias], "OutGate": [out_gate],
                             "OutNorm": [gain]},
                     outputs={"Out": [out], "State": [state],
                              "Starts": [starts]},
                     attrs={"chunk": int(chunk), "scale": float(scale),
                            "epsilon": float(epsilon)})
    return out, state


def causal_conv1d(x, width, act=None, param_attr=None, bias_attr=None,
                  name=None):
    """A short causal depthwise convolution over time: ``y_t = act(bias +
    sum_j w[j] * x_{t - (width - 1) + j})`` for ``x`` ``[B, T, E]``, every
    channel reading itself alone and zeros before the start; ``w`` ``[width,
    E]``, ``bias`` ``[E]`` (``bias_attr=False``: none), ``act`` None or
    ``"silu"``.  ``width`` shifted multiply-adds."""
    helper = LayerHelper("causal_conv1d", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    e = x.shape[-1]
    inputs = {"X": [x], "W": [helper.create_parameter(
        attr=helper.param_attr, shape=[int(width), e], dtype="float32")]}
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(
            attr=helper.bias_attr, shape=[e], dtype="float32", is_bias=True)]
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="causal_conv1d", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"activation": act or ""})
    return out


def rms_norm(x, epsilon=1e-6, param_attr=None, name=None, group=None):
    """``x * rsqrt(mean(x^2) + epsilon) * gain`` over the LAST axis, with a
    learned gain of that axis's size (initialised to 1) and no bias: the
    pre-norm of a decoder block over ``[B, T, D]``, and its per-head
    QK-norm over ``[B, T, H, Dh]``.  ``group`` (a whole divisor of the last
    axis): one norm a ``group``-wide slice of it under ONE gain of that
    width — the per-head norm over a projection's ``[B, T, H * Dh]`` output
    as it lies, with no view as heads.  Statistics in float32 whatever the
    activations' dtype."""
    from ..initializer import ConstantInitializer

    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    gain = helper.create_parameter(
        attr=helper.param_attr, shape=[group or x.shape[-1]], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="rms_norm", inputs={"X": [x], "Scale": [gain]},
                     outputs={"Y": [out]}, attrs={"epsilon": float(epsilon)})
    return out


def rotary_embedding(x, theta=10000.0, interleaved=False, freq_scaling=None,
                     scale=1.0, name=None):
    """Rotary position embedding of ``x`` ``[B, T, ..., D]``: position =
    index along axis 1, all D dimensions rotated, frequencies
    ``theta^(-2i/D)``; rotate-half convention (dimension i pairs with i +
    D/2) or, ``interleaved``, neighbouring pairs (2i, 2i + 1).  A model
    that rotates part of a head slices that part out and joins it back.

    ``freq_scaling`` — ``{"factor", "original_length", "beta_fast",
    "beta_slow"}`` — takes the frequencies from YaRN's blend by parts
    instead: a frequency that turns more than ``beta_fast`` times in
    ``original_length`` positions stays, one that turns fewer than
    ``beta_slow`` times is divided by ``factor``, those between are blended
    linearly by index (``ops.activation.scaled_frequencies``: worked out in
    float64 when the step is traced and bound as a constant; the blend is
    static, whatever the length).  ``scale`` multiplies cos and sin (YaRN's
    attention factor: a score of two rotated sides carries its square).
    The gradient turns back by the same frequencies times the same scale.
    An op without the two keeps its text."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    attrs = {"theta": float(theta)}
    if interleaved:
        attrs["interleaved"] = True
    if freq_scaling is not None:
        attrs["freq_scaling"] = {
            k: float(freq_scaling[k]) for k in (
                "factor", "original_length", "beta_fast", "beta_slow")}
    if float(scale) != 1.0:
        attrs["scale"] = float(scale)
    helper.append_op(type="rotary_embedding", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def swiglu(x, y, name=None):
    """``silu(x) * y``: the gated product of a SiLU feed-forward."""
    helper = LayerHelper("swiglu", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="swiglu", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def select_keys(index_q, index_k, index_w, top_k, scale=1.0, causal=True,
                name=None):
    """Learned sparse selection (a lightning indexer's): per query the
    ``top_k`` keys with the highest ``scale * sum_j w[t, j] * relu(q[t, j] .
    k[s])`` among the causal ones — all of them while ``t < top_k`` —
    ties to the lower index.

    ``index_q`` [B, T, Hi, Di], ``index_k`` [B, T, Di] (one shared key
    head), ``index_w`` [B, T, Hi].  Returns ``(selected, share)``: the
    packed bit mask ``fused_attention(selected=...)`` takes ([B, T, W]
    int32; a mask, not indices, because a blockwise kernel reads it by
    tiles and it is 1 bit a pair to keep for the backward) and the share
    of the causal pairs selected ([1] float32).  Not differentiable:
    nothing flows back into the indexer or its inputs.

    The selection's definition is the XLA body
    (``ops.sparse_select.topk_key_mask``: counting passes over the score
    matrix in HBM), which every CPU trace and every trace under a mesh
    runs; a TPU trace on one device with ``T`` in whole 128-key slabs runs
    ``ops/pallas/topk_select.py``, which makes the same decisions with a
    block of queries' scores held in VMEM and returns the same words."""
    helper = LayerHelper("select_keys", name=name)
    scores = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        type="indexer_score",
        inputs={"Q": [index_q], "K": [index_k], "W": [index_w]},
        outputs={"Out": [scores]}, attrs={"scale": float(scale)})
    selected = helper.create_variable_for_type_inference(dtype="int32")
    share = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        type="select_topk_keys", inputs={"X": [scores]},
        outputs={"Out": [selected], "Share": [share]},
        attrs={"k": int(top_k), "causal": causal})
    for v in (scores, selected, share):
        v.stop_gradient = True
    return selected, share


def routed_experts(x, num_experts, top_k, expert_width, held=None, first=0,
                   tile=256, router_attr=None, gate_attr=None, up_attr=None,
                   down_attr=None, score_func="softmax", bias_attr=None,
                   weight_scale=1.0, shared_width=None, shared_attrs=None,
                   name=None):
    """A layer of routed SiLU-gated experts, or one chip's share of it.

    ``x`` [N, D] tokens.  The router (``[D, num_experts]``, float32 product
    and scores: ``score_func`` ``softmax`` or ``sigmoid``) picks ``top_k``
    experts a token and renormalises their scores over the ``top_k``, times
    ``weight_scale``.  With ``bias_attr`` it picks on the scores plus a
    ``[num_experts]`` correction bias (zeros at start, never trained by the
    loss: no gradient, no optimizer state) and weighs by the scores alone.
    ``shared_width`` adds a shared expert of that width that EVERY token
    takes with weight 1 — ``(silu(x Ws_g) * (x Ws_u)) Ws_d``, plain ``mul``
    and ``swiglu`` ops under ``shared_attrs`` (gate, up, down); a layer cut
    into shares has it in one of them.  This program holds ``held`` experts,
    numbers ``first .. first + held - 1`` (all of them by default), as
    stacked parameters ``[held, D, expert_width]`` (gate, up) and ``[held,
    expert_width, D]`` (down), and returns
    ``sum_{e routed to t and held} c[t, e] * (silu(x Wg_e) * (x Wu_e)) Wd_e``
    — what the experts held elsewhere would add is left out.  Dropless:
    every routed pair of a held expert is computed, whatever the imbalance
    (``ops/moe.py``: pairs sorted by expert into tiles of ``tile`` rows, a
    loop over the live tiles).

    Returns ``(out [N, D], counts [held] int32 tokens routed to each held
    expert, pairs [1] float32 token-expert pairs computed)``."""
    held = num_experts if held is None else held
    helper = LayerHelper("routed_experts", name=name)
    d = x.shape[-1]

    def param(attr, shape):
        return helper.create_parameter(attr=attr, shape=shape,
                                       dtype="float32")
    router = param(router_attr, [d, num_experts])
    router_in = {"X": [x], "W": [router]}
    router_attrs = {"top_k": int(top_k)}
    if score_func != "softmax":
        router_attrs["score_func"] = score_func
    if float(weight_scale) != 1.0:
        router_attrs["scale"] = float(weight_scale)
    if bias_attr is not None:
        from ..initializer import ConstantInitializer
        bias_attr.trainable = False
        bias = helper.create_parameter(
            attr=bias_attr, shape=[num_experts], dtype="float32",
            default_initializer=ConstantInitializer(0.0))
        bias.stop_gradient = True
        router_in["Bias"] = [bias]
    gate = param(gate_attr, [held, d, expert_width])
    up = param(up_attr, [held, d, expert_width])
    down = param(down_attr, [held, expert_width, d])

    def tmp(dtype):
        return helper.create_variable_for_type_inference(dtype=dtype)
    idx, weight = tmp("int32"), tmp("float32")
    helper.append_op(type="moe_router", inputs=router_in,
                     outputs={"TopkIdx": [idx], "TopkWeight": [weight]},
                     attrs=router_attrs)
    idx.stop_gradient = True
    layout = {s: [tmp("int32")] for s in
              ("RowToken", "RowSlot", "TileExpert", "NumTiles", "Counts")}
    helper.append_op(type="moe_dispatch", inputs={"TopkIdx": [idx]},
                     outputs=layout,
                     attrs={"first": int(first), "held": int(held),
                            "tile": int(tile)})
    for v in layout.values():
        v[0].stop_gradient = True
    counts = layout.pop("Counts")[0]
    out, pairs = tmp(x.dtype), tmp("float32")
    pairs.stop_gradient = True
    inputs = {"X": [x], "TopkWeight": [weight], "Gate": [gate], "Up": [up],
              "Down": [down]}
    inputs.update(layout)
    helper.append_op(type="moe_expert_ffn", inputs=inputs,
                     outputs={"Out": [out], "Pairs": [pairs]},
                     attrs={"tile": int(tile)})
    if shared_width is not None:
        g_attr, u_attr, d_attr = shared_attrs or (None, None, None)

        def proj(v, size, attr):
            return fc(v, size=size, bias_attr=False, param_attr=attr)
        out = elementwise_add(out, proj(
            swiglu(proj(x, shared_width, g_attr),
                   proj(x, shared_width, u_attr)), d, d_attr))
    return out, counts, pairs


def paged_attention(q, k_cache, v_cache, page_table, k_len=None,
                    k_scale=None, v_scale=None, causal=True, scale=None,
                    name=None):
    """Attention over a block-indexed KV pool (serving's paged cache).

    ``q`` [S, H, Tq, D] attends the pages ``page_table`` [S, max_pages]
    maps for each slot out of the shared pool ``k_cache``/``v_cache``
    [P, H, page_size, D]; ``k_len`` [S] is each slot's valid length
    (entries past it — including stale speculative tokens — are
    masked).  int8 pools dequantize through ``k_scale``/``v_scale``
    [P, H, page_size].  Causal ``Tq > 1`` is the bottom-aligned
    suffix-query shape speculative verify uses."""
    helper = LayerHelper("paged_attention", name=name)
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    inputs = {"Q": [q], "KCache": [k_cache], "VCache": [v_cache],
              "PageTable": [page_table]}
    if k_len is not None:
        inputs["KLen"] = [k_len]
    if k_scale is not None:
        inputs["KScale"] = [k_scale]
        inputs["VScale"] = [v_scale]
    attrs = {"causal": causal}
    if scale is not None:
        attrs["scale"] = float(scale)
    helper.append_op(
        type="paged_attention", inputs=inputs, outputs={"Out": [out]},
        attrs=attrs,
    )
    return out


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        type="one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"depth": depth},
    )
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(dtype=input.dtype)
    indices = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="top_k", inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]}, attrs={"k": k},
    )
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="matmul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
               "alpha": float(alpha)},
    )
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="mul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"x_num_col_dims": x_num_col_dims,
               "y_num_col_dims": y_num_col_dims},
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(
        type="label_smooth", inputs=inputs, outputs={"Out": [out]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


def _unary_layer(op_type):
    def layer(x, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_variable_for_type_inference(dtype=x.dtype)
        helper.append_op(type=op_type, inputs={"X": [x]},
                         outputs={"Out": [out]})
        return out

    layer.__name__ = op_type
    return layer


log = _unary_layer("log")
relu = _unary_layer("relu")


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def _elementwise_layer(op_type):
    def layer(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, name=name, act=act)
        out = helper.create_variable_for_type_inference(dtype=x.dtype)
        helper.append_op(
            type=op_type, inputs={"X": [x], "Y": [y]},
            outputs={"Out": [out]}, attrs={"axis": axis},
        )
        return helper.append_activation(out)

    layer.__name__ = op_type
    return layer


elementwise_add = _elementwise_layer("elementwise_add")
elementwise_sub = _elementwise_layer("elementwise_sub")
elementwise_mul = _elementwise_layer("elementwise_mul")
elementwise_div = _elementwise_layer("elementwise_div")


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    """x / sqrt(sum(x^2, axis)) (reference nn.py:l2_normalize)."""
    from . import tensor as tensor_layers

    helper = LayerHelper("l2_normalize", name=name)
    sq = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="square", inputs={"X": [x]}, outputs={"Out": [sq]})
    ssum = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="reduce_sum", inputs={"X": [sq]}, outputs={"Out": [ssum]},
        attrs={"dim": [axis], "keep_dim": True, "reduce_all": False},
    )
    norm = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="clip", inputs={"X": [ssum]}, outputs={"Out": [norm]},
        attrs={"min": epsilon, "max": 3.4e38},
    )
    rsq = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="sqrt", inputs={"X": [norm]}, outputs={"Out": [rsq]})
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="elementwise_div", inputs={"X": [x], "Y": [rsq]},
        outputs={"Out": [out]}, attrs={"axis": 0},
    )
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name, param_attr=param_attr)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    elif mode == "element":
        alpha_shape = [int(_prod(x.shape[1:]))]
    else:
        raise ValueError("mode must be all|channel|element")
    from ..initializer import ConstantInitializer

    alpha = helper.create_parameter(
        attr=helper.param_attr, shape=alpha_shape, dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25),
    )
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="prelu", inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]}, attrs={"mode": mode},
    )
    return out


def _prod(xs):
    p = 1
    for x in xs:
        p *= x
    return p


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="maxout", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"groups": groups},
    )
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    out = helper.create_variable_for_type_inference(dtype=X.dtype)
    xnorm = helper.create_variable_for_type_inference(dtype=X.dtype)
    ynorm = helper.create_variable_for_type_inference(dtype=X.dtype)
    helper.append_op(
        type="cos_sim", inputs={"X": [X], "Y": [Y]},
        outputs={"Out": [out], "XNorm": [xnorm], "YNorm": [ynorm]},
    )
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("sampling_id")
    out = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="sampling_id", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"min": min, "max": max, "seed": seed},
    )
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    diff = helper.create_variable_for_type_inference(dtype=x.dtype)
    loss = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(
        type="smooth_l1_loss", inputs=inputs,
        outputs={"Diff": [diff], "Out": [loss]},
        attrs={"sigma": sigma if sigma is not None else 1.0},
    )
    return loss


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    """Pairwise hinge max(0, -label*(left-right) + margin) (reference
    margin_rank_loss_op.cc / nn.py margin_rank_loss; label is +-1)."""
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=left.dtype)
    act = helper.create_variable_for_type_inference(dtype=left.dtype)
    helper.append_op(
        type="margin_rank_loss",
        inputs={"Label": [label], "X1": [left], "X2": [right]},
        outputs={"Out": [out], "Activated": [act]},
        attrs={"margin": float(margin)},
    )
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="clip", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"min": float(min), "max": float(max)},
    )
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="clip_by_norm", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"max_norm": float(max_norm)},
    )
    return out


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None, name=None):
    """Noise-contrastive estimation loss (reference nn.py:3968 /
    nce_op.cc): per-sample cost [B, 1] over the true classes plus
    ``num_neg_samples`` uniform negatives."""
    helper = LayerHelper("nce", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dim = input.shape[-1]
    num_true = label.shape[-1] if len(label.shape) > 1 else 1
    num_neg = int(num_neg_samples) if num_neg_samples else 10
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype)
    inputs = {"Input": [input], "Label": [label], "Weight": [w]}
    if helper.kwargs.get("bias_attr") is not False:
        b = helper.create_parameter(attr=helper.bias_attr,
                                    shape=[num_total_classes, 1],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight]
    cost = helper.create_variable_for_type_inference(input.dtype)
    logits = helper.create_variable_for_type_inference(input.dtype)
    labels_out = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="nce", inputs=inputs,
        outputs={"Cost": [cost], "SampleLogits": [logits],
                 "SampleLabels": [labels_out]},
        attrs={"num_total_classes": int(num_total_classes),
               "num_neg_samples": num_neg, "num_true": int(num_true)})
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """Hierarchical sigmoid loss over a complete binary tree (reference
    nn.py:4065 / hierarchical_sigmoid_op.cc): per-sample cost [B, 1]."""
    helper = LayerHelper("hsigmoid", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_classes - 1, dim],
                                dtype=input.dtype)
    inputs = {"X": [input], "W": [w], "Label": [label]}
    if helper.kwargs.get("bias_attr") is not False:
        b = helper.create_parameter(attr=helper.bias_attr,
                                    shape=[num_classes - 1, 1],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="hierarchical_sigmoid", inputs=inputs,
        outputs={"Out": [out]},
        attrs={"num_classes": int(num_classes)})
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    """out_k = x . W_k . y (reference bilinear_tensor_product_op.cc)."""
    helper = LayerHelper("bilinear_tensor_product", input=x,
                         param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    dx, dy = x.shape[-1], y.shape[-1]
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[size, dx, dy], dtype=x.dtype)
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if helper.kwargs.get("bias_attr") is not False:
        b = helper.create_parameter(attr=helper.bias_attr, shape=[1, size],
                                    dtype=x.dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out]})
    return helper.append_activation(out)


# ---------------------------------------------------------------------------
# parity tail: the reference nn.py names not covered above
# ---------------------------------------------------------------------------

elementwise_max = _elementwise_layer("elementwise_max")
elementwise_min = _elementwise_layer("elementwise_min")
elementwise_pow = _elementwise_layer("elementwise_pow")


def flatten(x, axis=1, name=None):
    """Collapse dims before/after ``axis`` into a 2-D matrix (reference
    nn.py:6181 / flatten_op.cc)."""
    helper = LayerHelper("flatten", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="flatten", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"axis": int(axis)})
    return out


def sum(x):
    """Elementwise sum of a list of tensors (reference nn.py:6630 /
    sum_op.cc; dense path — SelectedRows inputs ride ops/selected_rows)."""
    if isinstance(x, Variable):
        x = [x]
    helper = LayerHelper("sum")
    out = helper.create_variable_for_type_inference(dtype=x[0].dtype)
    helper.append_op(type="sum", inputs={"X": [v for v in x]},
                     outputs={"Out": [out]})
    return out


def multiplex(inputs, index):
    """Row-wise select among candidate tensors by index (reference
    nn.py:4353 / multiplex_op.cc)."""
    if not isinstance(inputs, (list, tuple)) or len(inputs) < 2:
        raise ValueError("multiplex needs at least 2 candidate tensors")
    helper = LayerHelper("multiplex")
    out = helper.create_variable_for_type_inference(dtype=inputs[0].dtype)
    helper.append_op(
        type="multiplex",
        inputs={"X": [v for v in inputs], "Ids": [index]},
        outputs={"Out": [out]})
    return out


def rank_loss(label, left, right, name=None):
    """RankNet pairwise loss (reference nn.py:5759 / rank_loss_op.cc)."""
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=left.dtype)
    helper.append_op(
        type="rank_loss",
        inputs={"Label": [label], "Left": [left], "Right": [right]},
        outputs={"Out": [out]})
    return out


def sigmoid_cross_entropy_with_logits(x, label, name=None):
    """Per-element binary CE on logits (reference nn.py:7030 /
    sigmoid_cross_entropy_with_logits_op.cc)."""
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": [x], "Label": [label]},
        outputs={"Out": [out]})
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    """Normal-random tensor (reference nn.py:6519 / gaussian_random_op.cc;
    randomness rides the executor's counter PRNG, ``seed`` kept for API
    parity)."""
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(
        type="gaussian_random", inputs={}, outputs={"Out": [out]},
        attrs={"shape": list(shape), "mean": float(mean),
               "std": float(std), "seed": int(seed), "dtype": dtype})
    return out


def mean_iou(input, label, num_classes):
    """Mean intersection-over-union metric (reference nn.py:5611 /
    mean_iou_op.cc).  Returns (mean_iou, out_wrong, out_correct)."""
    helper = LayerHelper("mean_iou")
    iou = helper.create_variable_for_type_inference(dtype="float32")
    wrong = helper.create_variable_for_type_inference(dtype="int32")
    correct = helper.create_variable_for_type_inference(dtype="int32")
    helper.append_op(
        type="mean_iou",
        inputs={"Predictions": [input], "Labels": [label]},
        outputs={"OutMeanIou": [iou], "OutWrong": [wrong],
                 "OutCorrect": [correct]},
        attrs={"num_classes": int(num_classes)})
    return iou, wrong, correct


def dice_loss(input, label, epsilon=1e-5):
    """Dice loss for binary segmentation (reference nn.py:5180): built
    from one_hot + reductions exactly as the reference composes it."""
    from . import tensor as tensor_layers
    from .ops import scale as scale_layer
    label = one_hot(label, depth=input.shape[-1])
    reduce_dim = list(range(1, len(input.shape)))
    inse = tensor_layers.reduce_sum(elementwise_mul(input, label),
                                    dim=reduce_dim)
    denom = elementwise_add(
        tensor_layers.reduce_sum(input, dim=reduce_dim),
        tensor_layers.reduce_sum(label, dim=reduce_dim))
    one = tensor_layers.fill_constant(shape=[1], dtype=input.dtype, value=1.0)
    score = elementwise_sub(
        one, elementwise_div(
            scale_layer(inse, scale=2.0),
            elementwise_add(denom, tensor_layers.fill_constant(
                shape=[1], dtype=input.dtype, value=float(epsilon)))))
    return tensor_layers.reduce_mean(score)


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """Resize so the SHORT image side equals ``out_short_len``, keeping
    aspect ratio (reference nn.py:5323)."""
    from .cnn import image_resize
    in_shape = input.shape
    if len(in_shape) != 4:
        raise ValueError("image_resize_short expects NCHW input")
    hw = in_shape[2:4]
    short_idx = hw.index(min(hw))
    out_shape = list(hw)
    out_shape[short_idx] = int(out_short_len)
    out_shape[1 - short_idx] = int(
        round(float(hw[1 - short_idx]) / hw[short_idx] * out_short_len))
    return image_resize(input, out_shape=out_shape, resample=resample)


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """One LSTM step: fc([x_t, h_prev]) -> 4 gates -> lstm_unit op
    (reference nn.py:3008 / lstm_unit_op.cc).  Returns (hidden, cell)."""
    if len(x_t.shape) != 2 or len(hidden_t_prev.shape) != 2 or \
            len(cell_t_prev.shape) != 2:
        raise ValueError("lstm_unit takes 2-D x_t/hidden/cell")
    from .tensor import concat
    size = int(cell_t_prev.shape[1])
    concat_in = concat([x_t, hidden_t_prev], axis=1)
    fc_out = fc(concat_in, size=4 * size, param_attr=param_attr,
                bias_attr=bias_attr, name=name)
    helper = LayerHelper("lstm_unit", name=name)
    h = helper.create_variable_for_type_inference(dtype=x_t.dtype)
    c = helper.create_variable_for_type_inference(dtype=x_t.dtype)
    helper.append_op(
        type="lstm_unit",
        inputs={"X": [fc_out], "C_prev": [cell_t_prev]},
        outputs={"H": [h], "C": [c]},
        attrs={"forget_bias": float(forget_bias)})
    return h, c


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid"):
    """One GRU step over a pre-projected input (reference nn.py:751 /
    gru_unit_op.cc: ``input`` is the fc-transformed x, ``size`` = 3x the
    hidden dim).  Returns (hidden, reset_hidden_prev, gate)."""
    h_dim = size // 3
    helper = LayerHelper("gru_unit", param_attr=param_attr,
                         bias_attr=bias_attr)
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[h_dim, 3 * h_dim],
                                dtype=input.dtype)
    inputs = {"Input": [input], "HiddenPrev": [hidden], "Weight": [w]}
    if helper.kwargs.get("bias_attr") is not False:
        b = helper.create_parameter(attr=helper.bias_attr,
                                    shape=[1, 3 * h_dim],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    h = helper.create_variable_for_type_inference(dtype=input.dtype)
    gate = helper.create_variable_for_type_inference(dtype=input.dtype)
    rhp = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="gru_unit", inputs=inputs,
        outputs={"Hidden": [h], "Gate": [gate], "ResetHiddenPrev": [rhp]},
        attrs={"activation": activation,
               "gate_activation": gate_activation})
    return h, rhp, gate


def autoincreased_step_counter(counter_name=None, begin=1, step=1,
                               dtype="int64"):
    """A persistable counter advanced once per executed step (reference
    nn.py:4541).  The LR schedulers' ``_decay_step_counter`` delegates
    here — one counter builder, two callers."""
    helper = LayerHelper("step_counter")
    block = helper.main_program.global_block()
    name = counter_name or "@STEP_COUNTER@"
    counter = block._find_var_recursive(name)
    if counter is None:
        counter = block.create_var(name=name, shape=(1,), dtype=dtype,
                                   persistable=True)
        startup_blk = helper.startup_program.global_block()
        startup_blk.create_var(name=name, shape=(1,), dtype=dtype,
                               persistable=True)
        from ..initializer import Constant
        Constant(value=float(begin - step))(counter, startup_blk)
        helper.append_op(
            type="increment", inputs={"X": [counter]},
            outputs={"Out": [counter]}, attrs={"step": float(step)})
        counter.stop_gradient = True
    return counter
