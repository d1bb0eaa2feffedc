"""Decoder-only language models parameterised by their sizes and built
from ``layers`` functions into a Fluid ``Program``: one family, three kinds
of block.  Two are here, pre-norm blocks with routed SiLU-gated experts —
with a third decoder whose MIXER differs by layer (delta attention or latent
attention) over the same two FFN halves, and a fourth whose ATTENTION half
differs by layer (a window of the nearest keys or every causal key, each
under its own rotation) over the experts; the third kind of block, the
sandwich-norm block of a looped dense decoder, is in
:mod:`.looped_decoder`.  What the kinds share exists once, here: the
bias-free projection (``_proj``), the SiLU-gated products of a dense FFN
(``_gated_ffn``: the latent kind's leading dense layer and every block of
the looped kind), the untied head's per-token cross entropy
(``_head_token_loss``: its mean is the two expert kinds' loss, the looped
kind weighs the passes' by an exit gate) and the declaration of the step's
counters (``_declare_step_stats``).  The two expert kinds also share the
routed-expert half of a block (``_expert_half``) and its counters
(``_moe_step_stats``); the decoders over grouped heads share their attention
half (``_grouped_attention``: the selected-key block's with a per-head norm
and its indexer's selection, the window / full decoder's with a window or
none).

**Selected-key blocks** (``decoder_block`` / ``decoder_lm``), for ``x``
[B, T, D]:

1. ``h = rms_norm(x)``; ``q = h Wq`` -> ``n_head`` heads of ``head_dim``,
   ``k = h Wk``, ``v = h Wv`` -> ``n_kv_head`` heads; no biases.  ``q`` and
   ``k`` go through a per-head RMSNorm and rotary positions (all
   ``head_dim`` dimensions, rotate-half, ``rope_theta``).
2. A **frozen** indexer (``trainable=False``: no gradient, no optimizer
   state) ranks the keys for every query: ``qI = h WIq`` (``index_heads`` of
   ``index_dim``), ``kI = layer_norm(h WIk)`` (one shared key head), ``w = h
   WIw``; rotary on ``qI``, ``kI``; ``layers.select_keys`` keeps the
   ``index_topk`` best causal keys a query.
3. ``x += concat_h(attention over the selected keys) Wo``.
4. ``h2 = rms_norm(x)``; ``x += routed_experts(h2)`` — the router
   (softmax) over all ``num_experts``, of which this program holds the
   share ``(held, total, first)``: ``held`` experts, numbers ``first ..
   first + held - 1``, of ``total``.

**Latent-attention blocks** (``latent_block`` / ``latent_decoder_lm``; the
sizes are a ``LatentSizes``):

1. ``h = rms_norm(x)``; queries through a low-rank path, ``cq =
   rms_norm(h Wqa)``, ``q = cq Wqb`` -> ``n_head`` heads of ``[nope |
   rope]``; keys and values from one latent, ``[ckv | kr] = h Wkva``,
   ``ckv = rms_norm(ckv)``, ``[k_nope_i | v_i] = ckv Wkvb`` a head.  Rotary
   (interleaved pairs) on each head's ``rope`` part of the query and on
   ``kr``, ONE rotary key all heads share; ``k_i = [k_nope_i | rope(kr)]``.
   Causal attention with keys ``nope + rope`` wide over values ``v_dim``
   wide; ``x += concat_i(o_i) Wo``.  Everything between the three products
   and ``Wo`` is ONE op, ``layers.fused_attention`` over the products'
   outputs where they lie (``n_head=``, ``v_dim=``, ``k_shared=``,
   ``rope_theta=``): the program asks for no split of a head's parts, no
   join, no broadcast and no transpose.
2. ``h2 = rms_norm(x)``; the first ``n_dense`` layers: ``x += (silu(h2 Wg)
   * (h2 Wu)) Wd`` of ``dense_width``; the others: ``x +=
   routed_experts(h2)`` with a sigmoid router that selects on ``score +
   bias`` (the bias is never trained by the loss), weighs by the scores
   renormalised over the ``k`` times ``route_scale``, plus a shared expert
   every token takes.
3. A **multi-token-prediction module**: ``h' = [rms_norm(Emb(tok[t+1])) |
   rms_norm(x_last[t])] Weh``, one more expert block, a final RMSNorm of
   its own, the SAME embedding and head, cross entropy against
   ``tok[t+2]``; the step minimises ``L_main + mtp_weight * L_mtp``, so the
   shared tables' gradients are the two uses' sum.

**Delta-attention / latent-attention blocks**
(``linear_latent_decoder_lm``; the sizes are a ``DeltaSizes`` and a
``LatentSizes``), one mixer a layer from a list:

1. ``"kda"`` (``_delta_attention``): ``h = rms_norm(x)``; ``q~, k~, v =
   silu(causal_conv1d(h Wq | h Wk | h Wv))`` (depthwise, no bias), heads of
   ``head_dim``; the gate's pre-activation ``(h Wfa) Wfb``, ``beta =
   sigmoid(h Wb)`` a head, the output gate's ``(h Wga) Wgb``.
   ``layers.gated_delta_rule`` does the rest in float32: ``q, k`` L2-normed
   a head, ``q`` times ``head_dim^-0.5``, the log-decay ``g = -exp(A_log) *
   softplus(. + dt_bias)`` for every key channel, the rule ``S_t = (I -
   beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``, ``o = S^T q``, and
   ``rms_norm(o) * sigmoid(output gate)`` a head; ``x += (.) Wo``.
2. ``"mla"``: ``_latent_attention`` with ``q_rank`` None — the queries ONE
   product ``h Wq`` — and ``rope_theta`` None — no rotation of either side,
   the shared key part joined as it is (NoPE: position comes from the delta
   layers alone).
3. The first ``n_dense`` blocks' FFN half is ``_dense_half``, the others'
   ``_expert_half`` with the latent kind's router (sigmoid, a frozen bias, a
   scale, a shared expert).  No module; one loss.

**Window / full attention blocks** (``window_moe_decoder_lm``), one kind
a layer from a list:

1. ``_grouped_attention`` without the per-head norm and without a
   selection: ``q``, ``k`` rotated (rotate-half, all ``head_dim``
   dimensions) by the layer kind's law ``ropes[kind] = (theta,
   freq_scaling, scale)`` — ``"window"``: a query reads its nearest
   ``window`` keys (``fused_attention(window=)``); ``"full"``: every causal
   key.  The kind changes those two arguments and nothing else.
2. ``_expert_half`` with the softmax router, no shared expert.

After the last block a final RMSNorm, an untied head over ``vocab_size``
rows and the mean next-token cross entropy in float32.
"""

import collections

from .. import layers
from ..initializer import ConstantInitializer, NormalInitializer
from ..param_attr import ParamAttr

__all__ = ["decoder_block", "decoder_lm", "LatentSizes", "latent_block",
           "latent_decoder_lm", "DeltaSizes", "linear_latent_decoder_lm",
           "window_moe_decoder_lm"]

_INIT_STD = 0.02
# the fields of ``decoder_lm``'s step counters, as the executor names them in
# a StepStats record when the caller fetches them to the host
STEP_STATS = ("moe_pairs_routed", "moe_pairs_computed",
              "moe_max_expert_tokens", "selected_key_share")
# ... and of ``latent_decoder_lm``'s
LATENT_STEP_STATS = STEP_STATS[:3] + ("mtp_loss",)
# ... and of ``linear_latent_decoder_lm``'s
LINEAR_STEP_STATS = STEP_STATS[:3] + ("delta_state_rms", "decay_mean",
                                      "beta_mean")
# ... and of ``window_moe_decoder_lm``'s
WINDOW_STEP_STATS = STEP_STATS[:3] + ("window_pair_share",)

# the sizes of a latent-attention block's attention half
LatentSizes = collections.namedtuple(
    "LatentSizes", "n_head q_rank kv_rank nope_dim rope_dim v_dim")
# ... and of a delta-attention block's: heads of ``head_dim`` keys and
# values, the short convolution's taps, the two gates' rank, the rule's chunk
DeltaSizes = collections.namedtuple(
    "DeltaSizes", "n_head head_dim conv_width gate_rank chunk")


def _attr(name, trainable=True):
    return ParamAttr(name=name, trainable=trainable,
                     initializer=NormalInitializer(0.0, _INIT_STD))


def _proj(x, size, name, trainable=True):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=_attr(name, trainable))


def _expert_half(x, prefix, expert_share, expert_width, experts_per_token,
                 rms_eps, expert_tile, **router):
    """The routed-expert half of a block of either kind: ``x +=
    routed_experts(rms_norm(x))`` over the share ``(held, total, first)``;
    ``router`` is what ``layers.routed_experts`` takes beyond the softmax
    default (score function, bias, scale, shared expert).  Returns ``(x,
    counters)``: ``pairs_routed``, ``pairs_computed``,
    ``max_expert_tokens`` as [1] float32 variables."""
    held, total, first = expert_share
    d = x.shape[-1]
    h2 = layers.rms_norm(x, rms_eps, ParamAttr(name=prefix + "ln2.g"))
    y, counts, pairs = layers.routed_experts(
        layers.reshape(h2, shape=[-1, d]), total, experts_per_token,
        expert_width, held=held, first=first, tile=expert_tile,
        router_attr=_attr(prefix + "moe.router"),
        gate_attr=_attr(prefix + "moe.gate"),
        up_attr=_attr(prefix + "moe.up"),
        down_attr=_attr(prefix + "moe.down"), **router)
    x = layers.elementwise_add(x, layers.reshape(y, shape=[-1] + list(
        x.shape[1:])))
    counts = layers.cast(counts, "float32")
    return x, {"pairs_routed": layers.reduce_sum(counts, keep_dim=True),
               "pairs_computed": pairs,
               "max_expert_tokens": layers.reduce_max(counts, keep_dim=True)}


def _head_token_loss(x, labels, vocab_size):
    """The untied head ``out_w`` (one parameter, whoever calls) over the
    normed state ``x`` and each token's cross entropy [B, T, 1] in
    float32."""
    return layers.softmax_with_cross_entropy(
        _proj(x, vocab_size, "out_w"), labels)


def _head_loss(x, labels, vocab_size, rms_eps, norm_name):
    """Final RMSNorm (``norm_name``), the head and the mean cross
    entropy."""
    x = layers.rms_norm(x, rms_eps, ParamAttr(name=norm_name))
    return layers.mean(_head_token_loss(x, labels, vocab_size))


def _over_layers(stats, key, reduce):
    return reduce(layers.concat([st[key] for st in stats], axis=0),
                  keep_dim=True)


def _declare_step_stats(loss, step_stats, names):
    """Declare ``step_stats``, ONE float32 variable a caller fetches with
    the loss, as the step's counters on the loss's program, under
    ``names`` (``Program.step_stats``)."""
    step_stats.stop_gradient = True
    loss.block.program.step_stats = (step_stats.name, names)
    return step_stats


def _moe_step_stats(stats, last):
    """The expert blocks' counters and one more [1] variable as one [4]
    variable: pairs routed and computed (the layers' sums), the fullest
    held expert's tokens (the layers' largest) and what ``last()`` builds
    after them."""
    return layers.concat([
        _over_layers(stats, "pairs_routed", layers.reduce_sum),
        _over_layers(stats, "pairs_computed", layers.reduce_sum),
        _over_layers(stats, "max_expert_tokens", layers.reduce_max),
        last()], axis=0)


def _heads(v, n, width):
    return layers.reshape(v, shape=[0, 0, n, width])


def _grouped_attention(x, prefix, n_head, n_kv_head, head_dim, rope, rms_eps,
                       window=None, qk_norm=False, select=None):
    """The attention half of a block over grouped heads, whichever keys
    count: ``x += concat_h(attention(q, k, v)) Wo`` with ``h = rms_norm(x)``,
    ``q = h Wq`` (``n_head`` heads of ``head_dim``), ``k = h Wk``, ``v = h
    Wv`` (``n_kv_head`` heads), no biases; ``q`` and ``k`` through a per-head
    RMSNorm (``qk_norm``: ``rms_norm(group=head_dim)``, where they lie) and
    rotated by
    ``rope``, the law as ``(theta, freq_scaling, scale)``
    (``layers.rotary_embedding``'s).  The three projections go to ONE op as
    they lie — ``fused_attention`` over grouped heads in the projections'
    layout, the rotation inside it — and its result is what ``Wo`` reads:
    nothing is rotated or transposed between them.  Causal; of a query's
    keys count the nearest ``window`` (an int), or those ``select(h)`` —
    returning ``(packed key mask, share of the causal pairs selected)`` —
    selects, or all.  Returns ``(x, ctx, mask, share)``: ``ctx`` [B, T,
    n_head * head_dim] is what ``Wo`` reads; ``mask`` and ``share`` are
    ``select``'s, None without one."""
    h = layers.rms_norm(x, rms_eps, ParamAttr(name=prefix + "ln1.g"))
    q = _proj(h, n_head * head_dim, prefix + "attn.q")
    k = _proj(h, n_kv_head * head_dim, prefix + "attn.k")
    v = _proj(h, n_kv_head * head_dim, prefix + "attn.v")
    if qk_norm:
        q, k = (layers.rms_norm(t, rms_eps, ParamAttr(name=prefix + name),
                                group=head_dim)
                for t, name in ((q, "attn.q_g"), (k, "attn.k_g")))
    mask, share = select(h) if select is not None else (None, None)
    theta, freq_scaling, scale = rope
    ctx = layers.fused_attention(
        q, k, v, causal=True, scale=head_dim ** -0.5, selected=mask,
        window=window, n_head=n_head, rope_theta=theta,
        rope_freq_scaling=freq_scaling, rope_scale=scale)
    return (layers.elementwise_add(x, _proj(ctx, x.shape[-1],
                                            prefix + "attn.o")),
            ctx, mask, share)


def decoder_block(x, prefix, n_head, n_kv_head, head_dim, expert_share,
                  expert_width, experts_per_token, index_heads, index_dim,
                  index_topk, rope_theta=1e7, rms_eps=1e-6, expert_tile=256):
    """One block over ``x`` [B, T, D] with parameters named ``prefix +
    ...``.  Returns ``(x, stats)``; ``stats`` holds the block's counters as
    [1] float32 variables: ``pairs_routed`` (token-expert pairs routed to
    the held experts), ``pairs_computed`` (pairs the grouped products
    computed: equal, the layer is dropless), ``max_expert_tokens`` (the
    fullest held expert's tokens) and ``selected_share`` (share of the
    causal pairs the indexer selected), and ``selected``, the packed key
    mask itself."""
    def rope(v):             # the indexer's 64-wide heads: no op takes them
        return layers.rotary_embedding(v, theta=rope_theta)

    def select(h):
        # the frozen indexer: its inputs carry no gradient either (the
        # selection is a set), so h's gradient comes from q, k, v alone
        qi = rope(_heads(_proj(h, index_heads * index_dim, prefix + "idx.q",
                               False), index_heads, index_dim))
        ki = layers.layer_norm(
            _proj(h, index_dim, prefix + "idx.k", False), begin_norm_axis=2,
            param_attr=ParamAttr(name=prefix + "idx.k_g", trainable=False),
            bias_attr=ParamAttr(name=prefix + "idx.k_b", trainable=False))
        ki = rope(ki)
        wi = _proj(h, index_heads, prefix + "idx.w", False)
        return layers.select_keys(
            qi, ki, wi, index_topk,
            scale=index_heads ** -0.5 * index_dim ** -0.5)

    x, _, selected, share = _grouped_attention(
        x, prefix, n_head, n_kv_head, head_dim, (rope_theta, None, 1.0),
        rms_eps, qk_norm=True, select=select)
    x, stats = _expert_half(x, prefix, expert_share, expert_width,
                            experts_per_token, rms_eps, expert_tile)
    stats.update(selected_share=share, selected=selected)
    return x, stats


def decoder_lm(tokens, labels, vocab_size, n_layer, d_model, n_head,
               n_kv_head, head_dim, expert_share, expert_width,
               experts_per_token, index_heads, index_dim, index_topk,
               rope_theta=1e7, rms_eps=1e-6, expert_tile=256):
    """The training graph over ``tokens`` / ``labels`` [B, T, 1] int64 (every
    position real, one document a row).  ``expert_share`` is ``(held,
    total, first)``.  Returns ``(loss, stats, selected)``: the mean
    next-token cross entropy; a [4] float32 variable a caller fetches WITH
    the loss — pairs routed to the held experts and pairs computed (summed
    over the layers), the fullest held expert's tokens (the largest over
    the layers), the share of causal pairs selected (the layers' mean);
    fetched to the host with the monitor on they also go into that step's
    StepStats record under ``STEP_STATS``' names (``Program.step_stats``)
    — and the first layer's packed key mask."""
    x = layers.embedding(tokens, size=[vocab_size, d_model],
                         param_attr=_attr("tok_emb"))
    stats = []
    for i in range(n_layer):
        x, st = decoder_block(
            x, "l%d." % i, n_head, n_kv_head, head_dim, expert_share,
            expert_width, experts_per_token, index_heads, index_dim,
            index_topk, rope_theta, rms_eps, expert_tile)
        stats.append(st)
    loss = _head_loss(x, labels, vocab_size, rms_eps, "ln_f.g")
    step_stats = _declare_step_stats(loss, _moe_step_stats(
        stats, lambda: _over_layers(stats, "selected_share",
                                    layers.reduce_mean)), STEP_STATS)
    return loss, step_stats, stats[0]["selected"]


def _gated_ffn(h, prefix, width):
    """``(silu(h Wg) * (h Wu)) Wd``: a dense SiLU-gated FFN's products."""
    return _proj(layers.swiglu(_proj(h, width, prefix + "mlp.gate"),
                               _proj(h, width, prefix + "mlp.up")),
                 h.shape[-1], prefix + "mlp.down")


def _dense_half(x, prefix, width, rms_eps):
    """``x += gated_ffn(rms_norm(x))``."""
    h2 = layers.rms_norm(x, rms_eps, ParamAttr(name=prefix + "ln2.g"))
    return layers.elementwise_add(x, _gated_ffn(h2, prefix, width))


def _latent_attention(x, prefix, sizes, rope_theta, rms_eps):
    """``x += latent attention(rms_norm(x))``: see the module's text.
    ``sizes.q_rank`` None: the queries are ONE product ``h Wq``
    (``attn.q``); ``rope_theta`` None: no rotation of either side, the
    shared key part joined as it is (NoPE)."""
    n, nope, rope, dv = (sizes.n_head, sizes.nope_dim, sizes.rope_dim,
                         sizes.v_dim)

    def norm(v, name):
        return layers.rms_norm(v, rms_eps, ParamAttr(name=prefix + name))

    h = norm(x, "ln1.g")
    if sizes.q_rank is None:
        q = _proj(h, n * (nope + rope), prefix + "attn.q")
    else:
        cq = norm(_proj(h, sizes.q_rank, prefix + "attn.q_a"), "attn.q_a_g")
        q = _proj(cq, n * (nope + rope), prefix + "attn.q_b")
    ckv, kr = layers.split(_proj(h, sizes.kv_rank + rope,
                                 prefix + "attn.kv_a"),
                           [sizes.kv_rank, rope], dim=-1)
    kv = _proj(norm(ckv, "attn.kv_a_g"), n * (nope + dv),
               prefix + "attn.kv_b")
    # the op takes the three products where they lie: each head's [q_nope |
    # q_rope] of q and [k_nope | v] of kv, the ONE shared key part kr, the
    # rotation of q_rope and kr inside it
    ctx = layers.fused_attention(q, kv, causal=True,
                                 scale=(nope + rope) ** -0.5, n_head=n,
                                 v_dim=dv, k_shared=kr, rope_theta=rope_theta)
    return layers.elementwise_add(x, _proj(ctx, x.shape[-1],
                                           prefix + "attn.o"))


def _delta_attention(x, prefix, sizes, rms_eps):
    """``x += delta attention(rms_norm(x))``: see the module's text.
    Returns ``(x, state, g, beta)``: the layer's final state [B, H, Dk, Dv],
    the gate's pre-activation [B, T, H, Dk] with the two parameters that
    make the decays of it (``(g, a_log, dt_bias)``), and the rule's step
    sizes [B, T, H]."""
    n, dh = sizes.n_head, sizes.head_dim
    h = layers.rms_norm(x, rms_eps, ParamAttr(name=prefix + "ln1.g"))

    def heads(v, width=dh):
        return layers.reshape(v, shape=[0, 0, n, width])

    def mixed(name):
        return heads(layers.causal_conv1d(
            _proj(h, n * dh, prefix + "kda." + name), sizes.conv_width,
            act="silu", param_attr=_attr(prefix + "kda.%s_conv" % name),
            bias_attr=False))

    def low_rank(name):
        return heads(_proj(
            _proj(h, sizes.gate_rank, prefix + "kda.%s_a" % name), n * dh,
            prefix + "kda.%s_b" % name))

    def vector(name, shape):
        return layers.create_parameter(shape, "float32", attr=ParamAttr(
            name=prefix + "kda." + name,
            initializer=ConstantInitializer(0.0)))
    # the rule takes its operands as the products and convolutions left
    # them and makes the norms, the scale, the decays -exp(A_log) softplus(.
    # + dt_bias) and the gated head-wise norm of its result in float32
    # itself; beta goes to float32 before its sigmoid
    gate = (low_rank("f"), vector("A_log", [n]), vector("dt_bias", [n, dh]))
    beta = layers.sigmoid(layers.cast(_proj(h, n, prefix + "kda.b"),
                                      "float32"))
    o, state = layers.gated_delta_rule(
        mixed("q"), mixed("k"), mixed("v"), gate[0], beta, gate[1], gate[2],
        low_rank("g"), dh ** -0.5, chunk=sizes.chunk, epsilon=rms_eps,
        out_norm_attr=ParamAttr(name=prefix + "kda.o_g"))
    o = layers.reshape(o, shape=[0, 0, n * dh])
    return (layers.elementwise_add(x, _proj(o, x.shape[-1],
                                            prefix + "kda.o")),
            state, gate, beta)


def _decay_mean(gate):
    """The mean decay ``exp(-exp(A_log) softplus(g + dt_bias))`` of a
    delta-attention layer's ``(g, a_log, dt_bias)`` as a [1] float32
    variable, off the gradient's path (the op makes the decays itself and
    hands none out)."""
    g, a_log, dt_bias = gate
    g = layers.cast(g, "float32")
    g.stop_gradient = True
    return _mean(layers.exp(layers.elementwise_mul(
        layers.softplus(layers.elementwise_add(g, dt_bias)),
        layers.scale(layers.exp(a_log), scale=-1.0), axis=2)))


def _sigmoid_experts(prefix, expert_share, expert_width, experts_per_token,
                     expert_tile, route_scale, shared_width):
    """``_expert_half``'s arguments after the prefix for the latent kinds'
    router — sigmoid scores, a selection-only bias, the scale, a shared
    expert — as a dict."""
    return dict(
        expert_share=expert_share, expert_width=expert_width,
        experts_per_token=experts_per_token, expert_tile=expert_tile,
        score_func="sigmoid", weight_scale=route_scale,
        bias_attr=ParamAttr(name=prefix + "moe.bias"),
        shared_width=shared_width,
        shared_attrs=tuple(_attr(prefix + "moe.shared." + m)
                           for m in ("gate", "up", "down")))


def latent_block(x, prefix, sizes, rope_theta, rms_eps, dense_width=None,
                 experts=None):
    """One latent-attention block over ``x`` [B, T, D]: the attention half
    and a dense gated FFN of ``dense_width``, or the routed-expert half
    with ``experts`` — ``_expert_half``'s arguments after the prefix, as a
    dict.  Returns ``(x, counters or None)``."""
    x = _latent_attention(x, prefix, sizes, rope_theta, rms_eps)
    if experts is None:
        return _dense_half(x, prefix, dense_width, rms_eps), None
    return _expert_half(x, prefix, rms_eps=rms_eps, **experts)


def latent_decoder_lm(tokens, labels, labels2, vocab_size, n_layer, n_dense,
                      d_model, sizes, dense_width, expert_share,
                      expert_width, experts_per_token, shared_width,
                      route_scale=1.0, mtp_weight=0.3, rope_theta=1e4,
                      rms_eps=1e-6, expert_tile=256):
    """The training graph over ``tokens`` / ``labels`` (the next token) /
    ``labels2`` (the next but one) [B, T, 1] int64, every position real:
    ``n_dense`` dense and ``n_layer - n_dense`` expert blocks, and the
    multi-token-prediction module.  Returns ``(loss, stats)``: ``L_main +
    mtp_weight * L_mtp`` and a [4] float32 variable a caller fetches WITH
    the loss — pairs routed to the held experts and pairs computed (summed
    over the expert layers, the module's too), the fullest held expert's
    tokens, and ``L_mtp`` — under ``LATENT_STEP_STATS``' names
    (``Program.step_stats``)."""
    def experts(prefix):
        return _sigmoid_experts(prefix, expert_share, expert_width,
                                experts_per_token, expert_tile, route_scale,
                                shared_width)

    def embed(ids):
        return layers.embedding(ids, size=[vocab_size, d_model],
                                param_attr=_attr("tok_emb"))
    x = embed(tokens)
    stats = []
    for i in range(n_layer):
        prefix = "l%d." % i
        x, st = latent_block(
            x, prefix, sizes, rope_theta, rms_eps, dense_width,
            None if i < n_dense else experts(prefix))
        if st is not None:
            stats.append(st)
    loss_main = _head_loss(x, labels, vocab_size, rms_eps, "ln_f.g")

    # the module: the next token's embedding beside the trunk's last
    # hidden state (before its final norm), one more expert block
    joined = layers.concat([
        layers.rms_norm(embed(labels), rms_eps,
                        ParamAttr(name="mtp.enorm.g")),
        layers.rms_norm(x, rms_eps, ParamAttr(name="mtp.hnorm.g"))], axis=2)
    y, st = latent_block(_proj(joined, d_model, "mtp.eh_proj"), "mtp.",
                         sizes, rope_theta, rms_eps,
                         experts=experts("mtp."))
    stats.append(st)
    loss_mtp = _head_loss(y, labels2, vocab_size, rms_eps, "mtp.ln_f.g")
    loss = layers.elementwise_add(loss_main,
                                  layers.scale(loss_mtp, scale=mtp_weight))
    return loss, _declare_step_stats(loss, _moe_step_stats(
        stats, lambda: layers.reshape(loss_mtp, shape=[1])),
        LATENT_STEP_STATS)


def _mean(v):
    """The mean of ``v`` as a [1] float32 variable, off the gradient's
    path."""
    out = layers.reshape(layers.reduce_mean(v, keep_dim=False), shape=[1])
    out.stop_gradient = True
    return out


def linear_latent_decoder_lm(tokens, labels, vocab_size, d_model, mixers,
                             n_dense, delta_sizes, latent_sizes, dense_width,
                             expert_share, expert_width, experts_per_token,
                             shared_width, route_scale=1.0, rms_eps=1e-5,
                             expert_tile=256):
    """The training graph over ``tokens`` / ``labels`` (the next token) [B,
    T, 1] int64, every position real: one block a name of ``mixers`` —
    ``"kda"`` (``_delta_attention`` with ``delta_sizes``) or ``"mla"``
    (``_latent_attention`` with ``latent_sizes``, no rotation) — over a
    dense FFN of ``dense_width`` in the first ``n_dense`` blocks and the
    routed-expert half in the others (the router and the shared expert as
    ``latent_decoder_lm``'s).  Returns ``(loss, stats, state)``: the mean
    next-token cross entropy; a [6] float32 variable a caller fetches WITH
    the loss, under ``LINEAR_STEP_STATS``' names (``Program.step_stats``) —
    pairs routed to the held experts and pairs computed (summed over the
    expert layers), the fullest held expert's tokens, and of the FIRST
    ``"kda"`` block the RMS of its final state, its mean decay and its mean
    ``beta`` —; and that block's final state [B, H, Dk, Dv]."""
    x = layers.embedding(tokens, size=[vocab_size, d_model],
                         param_attr=_attr("tok_emb"))
    stats, first = [], None
    for i, mixer in enumerate(mixers):
        prefix = "l%d." % i
        if mixer == "kda":
            x, state, gate, beta = _delta_attention(x, prefix, delta_sizes,
                                                    rms_eps)
            first = first or (state, gate, beta)
        elif mixer == "mla":
            x = _latent_attention(x, prefix, latent_sizes, None, rms_eps)
        else:
            raise ValueError("a mixer is 'kda' or 'mla', got %r" % (mixer,))
        if i < n_dense:
            x = _dense_half(x, prefix, dense_width, rms_eps)
            continue
        x, st = _expert_half(x, prefix, rms_eps=rms_eps, **_sigmoid_experts(
            prefix, expert_share, expert_width, experts_per_token,
            expert_tile, route_scale, shared_width))
        stats.append(st)
    loss = _head_loss(x, labels, vocab_size, rms_eps, "ln_f.g")
    state, gate, beta = first
    return loss, _declare_step_stats(loss, _moe_step_stats(
        stats, lambda: layers.concat([
            layers.sqrt(_mean(layers.square(state))), _decay_mean(gate),
            _mean(beta)], axis=0)), LINEAR_STEP_STATS), state


def attention_pair_share(program):
    """Of the causal (query, key) pairs of ``program``'s ``fused_attention``
    ops, the share that counts under the ops' own masks — a ``window``
    keeps ``t - window < s <= t`` — read off what each op is given: its
    query's length (``[B, H, T, D]`` or the projections' ``[B, T, H * D]``)
    and its attributes."""
    counted = causal = 0
    for op in program.global_block().ops:
        if op.type != "fused_attention":
            continue
        shape = program.global_block().var(op.input("Q")[0]).shape
        t = shape[2] if len(shape) == 4 else shape[1]
        w = min(int(op.attr("window") or t), t)
        causal += t * (t + 1) // 2
        counted += w * (w + 1) // 2 + (t - w) * w
    return counted / max(causal, 1)


def window_moe_decoder_lm(tokens, labels, vocab_size, d_model, mixers, n_head,
                          n_kv_head, head_dim, window, ropes, expert_share,
                          expert_width, experts_per_token, rms_eps=1e-6,
                          expert_tile=256):
    """The training graph over ``tokens`` / ``labels`` (the next token) [B,
    T, 1] int64, every position real: one block a name of ``mixers`` —
    ``"window"`` (a query reads its nearest ``window`` keys) or ``"full"``
    (every causal key) — whose attention half is ``_grouped_attention`` with
    that kind's rotation, ``ropes[kind] = (theta, freq_scaling, scale)``
    (``layers.rotary_embedding``), over the routed-expert half with the
    softmax router.  Returns ``(loss, stats, contexts)``: the mean next-token
    cross entropy; a [4] float32 variable a caller fetches WITH the loss,
    under ``WINDOW_STEP_STATS``' names (``Program.step_stats``) — pairs
    routed to the held experts and pairs computed (summed over the layers),
    the fullest held expert's tokens, and the share of the layers' causal
    pairs that count under their masks (``attention_pair_share``) —; and
    each block's attention output before ``Wo`` [B, T, n_head * head_dim]."""
    x = layers.embedding(tokens, size=[vocab_size, d_model],
                         param_attr=_attr("tok_emb"))
    stats, contexts = [], []
    for i, mixer in enumerate(mixers):
        if mixer not in ("window", "full"):
            raise ValueError("a mixer is 'window' or 'full', got %r"
                             % (mixer,))
        prefix = "l%d." % i
        x, ctx, _, _ = _grouped_attention(
            x, prefix, n_head, n_kv_head, head_dim, ropes[mixer], rms_eps,
            window=window if mixer == "window" else None)
        contexts.append(ctx)
        x, st = _expert_half(x, prefix, expert_share, expert_width,
                             experts_per_token, rms_eps, expert_tile)
        stats.append(st)
    loss = _head_loss(x, labels, vocab_size, rms_eps, "ln_f.g")
    return loss, _declare_step_stats(loss, _moe_step_stats(
        stats, lambda: layers.fill_constant(
            [1], "float32", attention_pair_share(loss.block.program))),
        WINDOW_STEP_STATS), contexts
