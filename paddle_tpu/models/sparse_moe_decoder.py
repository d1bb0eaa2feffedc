"""A decoder-only language model of pre-norm blocks with grouped-query
attention over learned-selected keys and routed SiLU-gated experts,
parameterised by its sizes — the block of today's sparse-attention
mixture-of-experts models, built from ``layers`` functions into a Fluid
``Program``.

One block, for ``x`` [B, T, D]:

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
4. ``h2 = rms_norm(x)``; ``x += routed_experts(h2)`` — the router over all
   ``num_experts``, of which this program holds the share
   ``(held, total, first)``: ``held`` experts, numbers ``first .. first +
   held - 1``, of ``total``.

After the last block a final RMSNorm, an untied head over ``vocab_size``
rows and the mean next-token cross entropy in float32.
"""

from .. import layers
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr

__all__ = ["decoder_block", "decoder_lm"]

_INIT_STD = 0.02
# the fields of ``decoder_lm``'s step counters, as the executor names them in
# a StepStats record when the caller fetches them to the host
STEP_STATS = ("moe_pairs_routed", "moe_pairs_computed",
              "moe_max_expert_tokens", "selected_key_share")


def _attr(name, trainable=True):
    return ParamAttr(name=name, trainable=trainable,
                     initializer=NormalInitializer(0.0, _INIT_STD))


def _proj(x, size, name, trainable=True):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=_attr(name, trainable))


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
    held, total, first = expert_share
    d = x.shape[-1]

    def heads(v, n, width):
        return layers.reshape(v, shape=[0, 0, n, width])

    def rope(v):
        return layers.rotary_embedding(v, theta=rope_theta)

    h = layers.rms_norm(x, rms_eps, ParamAttr(name=prefix + "ln1.g"))
    q = heads(_proj(h, n_head * head_dim, prefix + "attn.q"), n_head,
              head_dim)
    k = heads(_proj(h, n_kv_head * head_dim, prefix + "attn.k"), n_kv_head,
              head_dim)
    v = heads(_proj(h, n_kv_head * head_dim, prefix + "attn.v"), n_kv_head,
              head_dim)
    q = rope(layers.rms_norm(q, rms_eps, ParamAttr(name=prefix + "attn.q_g")))
    k = rope(layers.rms_norm(k, rms_eps, ParamAttr(name=prefix + "attn.k_g")))

    # the frozen indexer: its inputs carry no gradient either (the
    # selection is a set), so h's gradient comes from q, k, v alone
    qi = rope(heads(_proj(h, index_heads * index_dim, prefix + "idx.q",
                          False), index_heads, index_dim))
    ki = layers.layer_norm(
        _proj(h, index_dim, prefix + "idx.k", False), begin_norm_axis=2,
        param_attr=ParamAttr(name=prefix + "idx.k_g", trainable=False),
        bias_attr=ParamAttr(name=prefix + "idx.k_b", trainable=False))
    ki = rope(ki)
    wi = _proj(h, index_heads, prefix + "idx.w", False)
    selected, share = layers.select_keys(
        qi, ki, wi, index_topk,
        scale=index_heads ** -0.5 * index_dim ** -0.5)

    def to_bhtd(t):
        return layers.transpose(t, perm=[0, 2, 1, 3])
    ctx = layers.fused_attention(to_bhtd(q), to_bhtd(k), to_bhtd(v),
                                 causal=True, scale=head_dim ** -0.5,
                                 selected=selected)
    ctx = layers.reshape(to_bhtd(ctx), shape=[0, 0, n_head * head_dim])
    x = layers.elementwise_add(x, _proj(ctx, d, prefix + "attn.o"))

    h2 = layers.rms_norm(x, rms_eps, ParamAttr(name=prefix + "ln2.g"))
    y, counts, pairs = layers.routed_experts(
        layers.reshape(h2, shape=[-1, d]), total, experts_per_token,
        expert_width, held=held, first=first, tile=expert_tile,
        router_attr=_attr(prefix + "moe.router"),
        gate_attr=_attr(prefix + "moe.gate"),
        up_attr=_attr(prefix + "moe.up"),
        down_attr=_attr(prefix + "moe.down"))
    x = layers.elementwise_add(x, layers.reshape(y, shape=[-1] + list(
        x.shape[1:])))
    counts = layers.cast(counts, "float32")
    return x, {"pairs_routed": layers.reduce_sum(counts, keep_dim=True),
               "pairs_computed": pairs,
               "max_expert_tokens": layers.reduce_max(counts, keep_dim=True),
               "selected_share": share, "selected": selected}


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
    x = layers.rms_norm(x, rms_eps, ParamAttr(name="ln_f.g"))
    logits = _proj(x, vocab_size, "out_w")
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))

    def over_layers(key, reduce):
        return reduce(layers.concat([st[key] for st in stats], axis=0),
                      keep_dim=True)
    step_stats = layers.concat([
        over_layers("pairs_routed", layers.reduce_sum),
        over_layers("pairs_computed", layers.reduce_sum),
        over_layers("max_expert_tokens", layers.reduce_max),
        over_layers("selected_share", layers.reduce_mean)], axis=0)
    step_stats.stop_gradient = True
    loss.block.program.step_stats = (step_stats.name, STEP_STATS)
    return loss, step_stats, stats[0]["selected"]
