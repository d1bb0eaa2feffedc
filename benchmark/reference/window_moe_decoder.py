"""The plain reference of ``mellum2_12b_a2_5b``: Mellum2-12B-A2.5B-Instruct
(config.json at huggingface.co/JetBrains, ``model_type`` ``mellum``) in
straightforward ``jax.numpy`` float32 — pre-norm blocks of grouped-query
attention whose KIND differs by layer (``layer_types``: three layers that read
a query's nearest ``sliding_window`` keys under the plain rotation to one that
reads every causal key under YaRN's rotation with its attention factor) over
softmax-routed SiLU-gated experts, none shared — with its loss, its gradient
and Adam.

It imports nothing of ``paddle_tpu`` and takes nothing the program made:
weights come from the generator (seeded); which kind each layer is it reads
where the builder reads it (the flops module's ``mixers``, from
``layer_types``).  The products' one switch
(``f32_matmul`` / ``lowp_matmul``), the RMSNorm, the gated products, the
head's loss and Adam are those of the two older decoder references, imported,
not written again.  No kernels, no cache, no blocks skipped: attention is an
explicit mask over whole rows of scores and a softmax, every held expert runs
over EVERY token with a routing weight that is zero where the token was not
routed to it.  Only to fit the chip, query rows (and the head's rows) are
taken in blocks of ``block_rows`` and blocks, experts and layers are
rematerialised in the backward pass; neither changes a number.

The layer, for ``x`` [T, D] (one document a row) of kind ``layer_types[l]``:

1. ``h = rms(x; g1)``; ``q = h Wq`` [T, H, Dh], ``k = h Wk``, ``v = h Wv``
   [T, Hkv, Dh]; no bias, no per-head norm.
2. ``q, k = R(q), R(k)``, rotate-half over all Dh dimensions: ``out_i = x_i
   c_i - x_{i + Dh/2} s_i``, ``out_{i + Dh/2} = x_{i + Dh/2} c_i + x_i s_i``,
   ``c_i = a cos(t w_i)``, ``s_i = a sin(t w_i)`` (``frequencies``):
   ``sliding_attention``: ``w_i = theta^(-2i/Dh)``, ``a = 1``;
   ``full_attention``: YaRN's blend by parts as ``transformers``'
   ``_compute_yarn_parameters`` computes it — ``w0_i = theta^(-2i/Dh)``,
   ``c(r) = Dh ln(L0 / (2 pi r)) / (2 ln theta)``, ``low = max(floor(
   c(beta_fast)), 0)``, ``high = min(ceil(c(beta_slow)), Dh - 1)``, ``ramp_i
   = clip((i - low) / (high - low), 0, 1)``, ``w_i = (1 - ramp_i) w0_i +
   ramp_i w0_i / factor`` — and ``a = attention_factor`` on cos AND sin, so
   a score carries ``a^2``.
3. Scores ``q k^T / sqrt(Dh)``; key ``s`` counts for query ``t`` iff ``s <=
   t`` (full) or ``t - window < s <= t`` (sliding); softmax; ``ctx = P v``;
   query head ``j`` reads K/V head ``j // (H / Hkv)``.  ``x += ctx Wo``.
4. ``h2 = rms(x; g2)``; ``p = softmax(h2 Wr)`` over ALL experts; the ``k``
   largest (ties to the lower index); ``c = p / sum p`` over them
   (``norm_topk_prob``); ``x += sum_{e chosen, e held} c_e (silu(h2 Wg_e) *
   (h2 Wu_e)) Wd_e``: the share ``(held, first)`` says which experts are
   held; the others' part is left out.

After the last layer ``loss`` = mean cross entropy of ``rms(x; gf) Wout``
over the held rows of the vocabulary against the next token.

``cfg["fault"]`` plants one fault (the generator's ``FAULTS``: what the limits
of ``correct`` stand against); a configuration has none.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp

from benchmark.reference.latent_moe_decoder import (      # noqa: F401
    _head_loss_sum, gated_ffn)
from benchmark.reference.sparse_moe_decoder import (      # noqa: F401
    NEG, _cached, _sizes, adam_init, adam_step, f32_matmul, lowp_matmul,
    rms_norm)

# a published layer type's kind, by the reference's own reading (the builder
# reads ``flops/mellum2_12b_a2_5b.py``'s: a wrong mapping there fails
# ``correct``), and a kind's key in ``rope_parameters``
_KIND_OF = {"sliding_attention": "window", "full_attention": "full"}
_TYPE_OF = {"window": "sliding_attention", "full": "full_attention"}


def mixers(cfg):
    """``"window"`` or ``"full"`` for each of the ``num_hidden_layers``
    leading published layers, from ``layer_types``."""
    return [_KIND_OF[t]
            for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def share_of(cfg):
    """(experts held here, the first one's number)."""
    return cfg["num_experts_held"], cfg.get("first_local_expert", 0)


def param_spec(cfg):
    """name -> (shape, init) in a fixed order; inits are read by
    :mod:`benchmark.weights`.  Every matrix is a 2-D leaf — the experts'
    too, one leaf an expert and matrix, under the expert's number in the
    whole layer (the program stacks the ones it holds)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f = cfg["moe_intermediate_size"]
    held, first = share_of(cfg)
    spec = {"tok_emb": ((v, d), "embedding")}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        spec[p + "ln1.g"] = ((d,), "ones")
        spec[p + "attn.q"] = ((d, h * dh), "xavier")
        spec[p + "attn.k"] = ((d, hk * dh), "xavier")
        spec[p + "attn.v"] = ((d, hk * dh), "xavier")
        spec[p + "attn.o"] = ((h * dh, d), "xavier")
        spec[p + "ln2.g"] = ((d,), "ones")
        spec[p + "moe.router"] = ((d, cfg["num_experts"]), "xavier")
        for e in range(first, first + held):
            spec[p + "moe.e%d.gate" % e] = ((d, f), "xavier")
            spec[p + "moe.e%d.up" % e] = ((d, f), "xavier")
            spec[p + "moe.e%d.down" % e] = ((f, d), "xavier")
    spec["ln_f.g"] = ((d,), "ones")
    spec["out_w"] = ((d, v), "xavier")
    return spec


# ---------------------------------------------------------------------------
# the rotation
# ---------------------------------------------------------------------------

def frequencies(cfg, kind):
    """(``w`` [Dh / 2] float64, ``a``) of a layer of ``kind``
    (``"window"`` / ``"full"``), from ``rope_parameters``."""
    rope, dh = cfg["rope_parameters"][_TYPE_OF[kind]], cfg["head_dim"]
    theta, fault = float(rope["rope_theta"]), cfg.get("fault")
    w0 = theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    if rope["rope_type"] == "default":
        return w0, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError("unknown rope_type %r" % (rope["rope_type"],))
    if fault == "full_plain_rotation":
        return w0, float(rope["attention_factor"])

    def turns(r):
        return dh * math.log(rope["original_max_position_embeddings"]
                             / (r * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns(rope["beta_fast"])), 0)
    high = min(math.ceil(turns(rope["beta_slow"])), dh - 1)
    if fault == "ramp_ends_swapped":
        low, high = turns(rope["beta_slow"]), turns(rope["beta_fast"])
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dh // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return ((1.0 - ramp) * w0 + ramp * w0 / rope["factor"],
            float(rope["attention_factor"]))


def rotary(x, w, a):
    """``x`` [T, H, D]: position = row, rotate-half over all D by the
    frequencies ``w`` [D / 2], cos and sin times ``a``."""
    t, d = x.shape[0], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(w, jnp.float32)[None, :]
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return a * (x * jnp.cos(angle) + half * jnp.sin(angle))


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def attention(p, pre, h, cfg, kind, block_rows, mm, first=False):
    """The heads' outputs [T, H * Dh], before ``Wo``.  ``first``: the
    stack's first layer (one planted fault is of that layer alone)."""
    t = h.shape[0]
    nh, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    fault = cfg.get("fault")
    w, a = frequencies(cfg, kind)
    q = rotary(mm(h, p[pre + "attn.q"]).reshape(t, nh, dh), w, a)
    k = rotary(mm(h, p[pre + "attn.k"]).reshape(t, hk, dh), w,
               1.0 if fault == "factor_on_query_alone" else a)
    v = mm(h, p[pre + "attn.v"]).reshape(t, hk, dh)
    # query head j reads key/value head j // g
    if fault == "kv_head_by_remainder":
        k, v = (jnp.tile(x, (1, nh // hk, 1)) for x in (k, v))
    else:
        k, v = (jnp.repeat(x, nh // hk, axis=1) for x in (k, v))
    kh, vh = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # [H,Dh,T], [H,T,Dh]
    window = cfg["sliding_window"] if kind == "window" else None
    if fault == "window_ignored_in_one_layer" and first:
        window = None
    if window is not None and fault == "window_one_key_wide":
        window += 1

    def block(args):
        row0, qb = args
        rows = (row0 + jnp.arange(qb.shape[0]))[:, None]
        keys = jnp.arange(t)[None, :]
        counts = keys <= rows
        if window is not None:
            counts &= keys > rows - window
        s = mm(qb.transpose(1, 0, 2), kh) * dh ** -0.5       # [H, R, T]
        pr = jax.nn.softmax(jnp.where(counts[None], s, NEG), -1)
        return mm(pr, vh).transpose(1, 0, 2).reshape(qb.shape[0], nh * dh)
    r = min(block_rows, t)
    out = jax.lax.map(jax.checkpoint(block), (
        jnp.arange(t // r) * r, q.reshape(t // r, r, nh, dh)))
    return out.reshape(t, nh * dh)


def route(p, pre, h2, cfg, mm):
    """(expert ids [T, k], weights [T, k] renormalised over the k)."""
    prob = jax.nn.softmax(mm(h2, p[pre + "moe.router"]), -1)
    top, idx = jax.lax.top_k(prob, cfg["num_experts_per_tok"])
    if cfg.get("fault") == "weights_not_renormalised":
        return idx, top
    return idx, top / jnp.sum(top, -1, keepdims=True)


def experts(p, pre, h2, cfg, share, mm):
    """(the held experts' part of the layer's result [T, D], the
    token-expert pairs routed to the held experts)."""
    held, first = share
    idx, c = route(p, pre, h2, cfg, mm)
    pairs = jnp.sum((idx >= first) & (idx < first + held)).astype(
        jnp.float32)
    mats = [jnp.stack([p[pre + "moe.e%d.%s" % (e, m)]
                       for e in range(first, first + held)])
            for m in ("gate", "up", "down")]

    def one(y, em):
        e, wg, wu, wd = em
        ce = jnp.sum(jnp.where(idx == e, c, 0.0), -1)        # 0: not routed
        return y + ce[:, None] * gated_ffn(h2, wg, wu, wd, mm), None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h2),
                        (first + jnp.arange(held), *mats))
    return y, pairs


def layer(p, pre, x, cfg, kind, share, block_rows, mm=f32_matmul,
          first=False):
    """One block over ``x`` [T, D] with the expert share ``(held, first)``.
    Returns (x, the attention half's ``ctx`` [T, H * Dh], pairs routed to
    the held experts)."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p[pre + "ln1.g"], eps)
    ctx = attention(p, pre, h, cfg, kind, block_rows, mm, first)
    x = x + mm(ctx, p[pre + "attn.o"])
    y, pairs = experts(p, pre, rms_norm(x, p[pre + "ln2.g"], eps), cfg,
                       share, mm)
    return x + y, ctx, pairs


def context_layers(cfg):
    """The layers whose ``ctx`` is compared: the first of each kind."""
    kinds = mixers(cfg)
    return [kinds.index(k) for k in ("window", "full") if k in kinds]


# ---------------------------------------------------------------------------
# loss, gradient (Adam is sparse_moe_decoder's)
# ---------------------------------------------------------------------------

def loss_sum(p, tokens, labels, cfg, block_rows, mm=f32_matmul):
    """(sum of the next-token losses, (pairs routed to the held experts over
    all layers, the ``ctx`` [n, T, H * Dh] of ``context_layers``)) of one
    document ``tokens`` [T]."""
    share = share_of(cfg)
    x = p["tok_emb"][tokens]
    pairs, kept = jnp.float32(0.0), {}
    for i, kind in enumerate(mixers(cfg)):
        x, ctx, n = jax.checkpoint(
            lambda p, x, i=i, kind=kind: layer(
                p, "l%d." % i, x, cfg, kind, share, block_rows, mm,
                first=i == 0))(p, x)
        pairs = pairs + n
        if i in context_layers(cfg):
            kept[i] = jax.lax.stop_gradient(ctx)
    loss = _head_loss_sum(p, x, "ln_f.g", labels, cfg, block_rows, mm)
    return loss, (pairs, jnp.stack([kept[i] for i in context_layers(cfg)]))


def loss_and_grad(p, batch, cfg, block_rows, mm=f32_matmul):
    """(the mean loss over the batch's positions, the compared layers'
    ``ctx`` [n, rows, T, H * Dh], the pairs routed to the held experts, the
    gradient with respect to every leaf), one document at a time."""
    rows, t = batch["tok"].shape

    def make():
        def doc_loss(p, tok, lbl):
            loss, aux = loss_sum(p, tok, lbl, cfg, block_rows, mm)
            return loss / (rows * t), aux

        def step(p, tok, lbl, loss, grad):
            (l, aux), g = jax.value_and_grad(doc_loss, has_aux=True)(
                p, tok, lbl)
            return loss + l, aux, jax.tree.map(jnp.add, grad, g)
        return jax.jit(step, donate_argnums=(4,))
    step = _cached(("window_moe_grad", _sizes(cfg), rows, t, mm), make)
    loss = jnp.zeros((), jnp.float32)
    grad = jax.tree.map(jnp.zeros_like, p)
    pairs, contexts = jnp.float32(0.0), []
    for r in range(rows):
        loss, (n, ctx), grad = step(p, batch["tok"][r], batch["lbl"][r],
                                    loss, grad)
        pairs = pairs + n
        contexts.append(ctx)
    return loss, jnp.stack(contexts, 1), pairs, grad
