"""The plain reference of ``kimi_linear_48b_a3b``: Kimi-Linear-48B-A3B-Instruct
(config.json at huggingface.co/moonshotai, arXiv:2510.26692) in
straightforward ``jax.numpy`` float32 — pre-norm blocks whose mixer is Kimi
Delta Attention (a gated delta rule with a decay for every key channel) or,
in every fourth layer, latent attention without a query rank and without
positions (NoPE), over a leading dense gated FFN and then sigmoid-routed
SiLU-gated experts with a selection-only bias and a shared expert — with its
loss, its gradient and Adam.

It imports nothing of ``paddle_tpu`` and takes nothing the program made:
weights come from the generator (seeded).  The products' one switch
(``f32_matmul`` / ``lowp_matmul``), the RMSNorm, the router, the experts, the
head's loss and Adam are those of the two older decoder references, imported,
not written again (the experts' share is taken exactly as
``latent_moe_decoder``'s takes it).  No kernels, no cache, no chunk algebra:
**the delta rule is the token-by-token recurrence**, a ``lax.scan`` over the
steps (cut into runs of ``SCAN_RUN`` steps that the backward pass
rematerialises: that changes no number); attention is a causal softmax over
whole rows of 192-wide scores; every held expert runs over EVERY token with a
routing weight that is zero where the token was not routed to it.  Only to
fit the chip, query rows (and the head's rows) are taken in blocks of
``block_rows`` and blocks, experts and layers are rematerialised.

A delta-attention layer, for ``x`` [T, D], ``h = rms(x; g1)``, ``H`` heads of
``Dh`` keys and values:

1. ``q~, k~, v = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))``:
   causal, depthwise, ``short_conv_kernel_size`` taps, no bias, zeros before
   the start; ``q = q~ / ||q~|| * Dh^-0.5`` and ``k = k~ / ||k~||`` a head
   (``x * rsqrt(max(sum x^2, 1e-12))``).
2. ``g_t = -exp(A_log[n]) * softplus((h Wfa) Wfb + dt_bias)`` [H, Dh], a
   log-decay for every key channel; ``beta_t = sigmoid(h Wb)`` [H].
3. ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
   v_t^T`` from ``S_0 = 0`` (computed as ``S~ = Diag(exp(g_t)) S_{t-1}``,
   ``u = beta_t (v_t - S~^T k_t)``, ``S_t = S~ + k_t u^T``); ``o_t = S_t^T
   q_t``.
4. ``x += ((rms_head(o_t; go) * sigmoid((h Wga) Wgb)) Wo``.

A latent layer: ``q = h Wq`` [T, H, 192] (no query rank); ``[c 512 | kr 64]
= h Wkva``, ``[k_nope 128 | v 128] = rms(c; gkva) Wkvb`` a head, ``k_i =
[k_nope_i | kr]`` with the ONE ``kr`` all heads share and NO rotation of
either side; ``o[t, i] = softmax_{s <= t}(q[t, i] . k[s, i] * 192^-0.5)
v[s, i]``; ``x += concat(o) Wo``.

Then ``h2 = rms(x; g2)`` and the dense FFN (the first
``first_k_dense_replace`` layers) or the experts' share, as
``latent_moe_decoder``'s layer.  After the last layer ``loss`` = mean cross
entropy of ``rms(x; gf) Wout`` against the next token.

``cfg["fault"]`` plants one fault (the generator's ``FAULTS``: what the
limits of ``correct`` stand against); a configuration has none.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.latent_moe_decoder import (      # noqa: F401
    _head_loss_sum, adam_init, adam_step, experts, frozen, gated_ffn,
    rotary_pairs)
from benchmark.reference.sparse_moe_decoder import (      # noqa: F401
    NEG, _cached, _sizes, f32_matmul, lowp_matmul, rms_norm)

SCAN_RUN = 64


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def share_of(cfg):
    """(experts held here, the first one's number)."""
    return cfg["num_experts_held"], cfg.get("first_local_expert", 0)


def mixers(cfg):
    """The mixer of each of the ``num_hidden_layers`` leading published
    layers, from ``linear_attn_config``'s one-based lists."""
    lin = cfg["linear_attn_config"]
    out = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if (i in lin["kda_layers"]) == (i in lin["full_attn_layers"]):
            raise ValueError("layer %d is in one of kda_layers and "
                             "full_attn_layers" % i)
        out.append("kda" if i in lin["kda_layers"] else "mla")
    return out


def _moe_cfg(cfg):
    """``cfg`` under the keys ``latent_moe_decoder``'s router reads."""
    return dict(cfg, num_experts_per_tok=cfg["num_experts_per_token"])


def param_spec(cfg):
    """name -> (shape, init) in a fixed order; the generator reads the
    inits.  Every matrix is a 2-D leaf — the experts' too, one leaf an
    expert and matrix, under the expert's number in the whole layer (the
    program stacks the ones it holds)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    n, dh, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    rank = dh                   # the two gates' inner width is a head's
    h, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, first = share_of(cfg)
    spec = {"tok_emb": ((v, d), "embedding")}
    for i, mixer in enumerate(mixers(cfg)):
        p = "l%d." % i
        spec[p + "ln1.g"] = ((d,), "ones")
        if mixer == "kda":
            for m in "qkv":
                spec[p + "kda." + m] = ((d, n * dh), "xavier")
                spec[p + "kda.%s_conv" % m] = ((taps, n * dh), "xavier")
            spec[p + "kda.f_a"] = ((d, rank), "xavier")
            spec[p + "kda.f_b"] = ((rank, n * dh), "xavier")
            spec[p + "kda.A_log"] = ((n,), "a_log")
            spec[p + "kda.dt_bias"] = ((n, dh), "dt_bias")
            spec[p + "kda.b"] = ((d, n), "xavier")
            spec[p + "kda.g_a"] = ((d, rank), "xavier")
            spec[p + "kda.g_b"] = ((rank, n * dh), "xavier")
            spec[p + "kda.o_g"] = ((dh,), "ones")
            spec[p + "kda.o"] = ((n * dh, d), "xavier")
        else:
            spec[p + "attn.q"] = ((d, h * (nope + rope)), "xavier")
            spec[p + "attn.kv_a"] = ((d, kvr + rope), "xavier")
            spec[p + "attn.kv_a_g"] = ((kvr,), "ones")
            spec[p + "attn.kv_b"] = ((kvr, h * (nope + dv)), "xavier")
            spec[p + "attn.o"] = ((h * dv, d), "xavier")
        spec[p + "ln2.g"] = ((d,), "ones")
        if i < cfg["first_k_dense_replace"]:
            spec[p + "mlp.gate"] = ((d, f), "xavier")
            spec[p + "mlp.up"] = ((d, f), "xavier")
            spec[p + "mlp.down"] = ((f, d), "xavier")
            continue
        spec[p + "moe.router"] = ((d, cfg["num_experts"]), "xavier")
        spec[p + "moe.bias"] = ((cfg["num_experts"],), "zeros")
        for e in ["e%d" % k for k in range(first, first + held)] + ["shared"]:
            spec[p + "moe.%s.gate" % e] = ((d, fe), "xavier")
            spec[p + "moe.%s.up" % e] = ((d, fe), "xavier")
            spec[p + "moe.%s.down" % e] = ((fe, d), "xavier")
    spec["ln_f.g"] = ((d,), "ones")
    spec["out_w"] = ((d, v), "xavier")
    return spec


# ---------------------------------------------------------------------------
# mixers
# ---------------------------------------------------------------------------

def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.maximum(
        jnp.sum(x * x, -1, keepdims=True), 1e-12))


def conv(u, w, ahead=0):
    """``silu(sum_j w[j] * u[t - (K-1) + j + ahead])``, zeros outside;
    ``ahead`` is 0 (a planted fault reads one step ahead)."""
    k, t = w.shape[0], u.shape[0]
    up = jnp.pad(u, [(k - 1 - ahead, ahead), (0, 0)])
    return jax.nn.silu(sum(w[j] * up[j:j + t] for j in range(k)))


def delta_rule(q, k, v, g, beta, mm, plain=False):
    """The recurrence over ``q``, ``k``, ``g`` [T, H, Dk], ``v`` [T, H, Dv]
    and ``beta`` [T, H], a step at a time: (``o`` [T, H, Dv], the final
    state [H, Dk, Dv]).  ``plain`` (a planted fault) drops the delta term:
    ``S_t = Diag(alpha) S + beta k v^T``."""
    t, n, dk = q.shape
    pad = -(-t // SCAN_RUN) * SCAN_RUN - t

    def runs(x):
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape((-1, SCAN_RUN) + x.shape[1:])

    def step(s, inp):
        qt, kt, vt, gt, bt = inp
        s = jnp.exp(gt)[:, :, None] * s
        seen = 0.0 if plain else mm(kt[:, None, :], s)[:, 0, :]
        u = bt[:, None] * (vt - seen)
        s = s + kt[:, :, None] * u[:, None, :]
        return s, mm(qt[:, None, :], s)[:, 0, :]

    @jax.checkpoint
    def one_run(s, inp):
        return jax.lax.scan(step, s, inp)
    # a padded step has k = v = q = 0, g = 0, beta = 0: the state stays
    state, o = jax.lax.scan(
        one_run, jnp.zeros((n, dk, v.shape[-1]), jnp.float32),
        tuple(runs(x) for x in (q, k, v, g, beta)))
    return o.reshape((-1,) + o.shape[2:])[:t], state


def delta_attention(p, pre, h, cfg, mm):
    """The mixer's output [T, D] before the residual, the final state [H,
    Dk, Dv], the mean decay and the mean ``beta``."""
    lin, fault = cfg["linear_attn_config"], cfg.get("fault")
    n, dh = lin["num_heads"], lin["head_dim"]
    t = h.shape[0]
    ahead = 1 if fault == "conv_tap_ahead" else 0
    q, k, v = (conv(mm(h, p[pre + "kda." + m]), p[pre + "kda.%s_conv" % m],
                    ahead).reshape(t, n, dh) for m in "qkv")
    q = l2_normalize(q) * dh ** -0.5
    if fault != "keys_unnormalised":
        k = l2_normalize(k)
    g = -jnp.exp(p[pre + "kda.A_log"])[:, None] * jax.nn.softplus(
        mm(mm(h, p[pre + "kda.f_a"]), p[pre + "kda.f_b"]).reshape(t, n, dh)
        + p[pre + "kda.dt_bias"])
    if fault == "decay_one_a_head":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(mm(h, p[pre + "kda.b"]))
    if fault == "beta_one":
        beta = jnp.ones_like(beta)
    o, state = delta_rule(q, k, v, g, beta, mm,
                          plain=fault == "delta_term_dropped")
    gate = jax.nn.sigmoid(mm(mm(h, p[pre + "kda.g_a"]),
                             p[pre + "kda.g_b"]).reshape(t, n, dh))
    o = rms_norm(o, p[pre + "kda.o_g"], cfg["rms_norm_eps"]) * gate
    return (mm(o.reshape(t, n * dh), p[pre + "kda.o"]), state,
            jnp.mean(jnp.exp(g)), jnp.mean(beta))


def latent_attention(p, pre, h, cfg, block_rows, mm):
    """The heads' outputs through ``Wo`` [T, D]."""
    t = h.shape[0]
    nh, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    q = mm(h, p[pre + "attn.q"]).reshape(t, nh, nope + rope)
    kva = mm(h, p[pre + "attn.kv_a"])
    ckv = rms_norm(kva[:, :kvr], p[pre + "attn.kv_a_g"], cfg["rms_norm_eps"])
    kr = kva[:, kvr:]
    if cfg.get("fault") == "latent_keys_rotated":
        theta = float(cfg["rope_theta"])
        q = jnp.concatenate([q[..., :nope],
                             rotary_pairs(q[..., nope:], theta)], -1)
        kr = rotary_pairs(kr, theta)
    kv = mm(ckv, p[pre + "attn.kv_b"]).reshape(t, nh, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kr[:, None, :], (t, nh, rope))], -1)
    kh = k.transpose(1, 2, 0)                                # [H, Dk, T]
    vh = kv[..., nope:].transpose(1, 0, 2)                   # [H, T, Dv]
    scale = (nope + rope) ** -0.5

    def block(args):
        row0, qb = args
        rows = row0 + jnp.arange(qb.shape[0])
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        s = mm(qb.transpose(1, 0, 2), kh) * scale            # [H, R, T]
        pr = jax.nn.softmax(jnp.where(causal[None], s, NEG), -1)
        return mm(pr, vh).transpose(1, 0, 2).reshape(qb.shape[0], nh * dv)
    r = min(block_rows, t)
    out = jax.lax.map(jax.checkpoint(block), (
        jnp.arange(t // r) * r, q.reshape(t // r, r, nh, nope + rope)))
    return mm(out.reshape(t, nh * dv), p[pre + "attn.o"])


def layer(p, pre, x, cfg, mixer, share, block_rows, mm=f32_matmul,
          dense=False, shared=True):
    """One block over ``x`` [T, D].  Returns (x, pairs routed to the held
    experts, the delta rule's (final state, mean decay, mean beta) or
    None)."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p[pre + "ln1.g"], eps)
    if mixer == "kda":
        y, *rule = delta_attention(p, pre, h, cfg, mm)
    else:
        y, rule = latent_attention(p, pre, h, cfg, block_rows, mm), None
    x = x + y
    h2 = rms_norm(x, p[pre + "ln2.g"], eps)
    if dense:
        return x + gated_ffn(h2, p[pre + "mlp.gate"], p[pre + "mlp.up"],
                             p[pre + "mlp.down"], mm), jnp.float32(0.0), rule
    y, pairs = experts(p, pre, h2, _moe_cfg(cfg), share, mm, shared)
    return x + y, pairs, rule


# ---------------------------------------------------------------------------
# loss, gradient (Adam is latent_moe_decoder's)
# ---------------------------------------------------------------------------

def loss_sum(p, tokens, labels, cfg, block_rows, mm=f32_matmul):
    """(sum of the next-token losses, [pairs routed to the held experts over
    all expert layers, the first delta layer's mean decay, mean beta], that
    layer's final state [H, Dk, Dv]) of one document ``tokens`` [T]."""
    share = share_of(cfg)
    x = p["tok_emb"][tokens]
    pairs, first = jnp.float32(0.0), None
    for i, mixer in enumerate(mixers(cfg)):
        x, n, rule = jax.checkpoint(
            lambda p, x, i=i, mixer=mixer: layer(
                p, "l%d." % i, x, cfg, mixer, share, block_rows, mm,
                dense=i < cfg["first_k_dense_replace"]))(p, x)
        pairs = pairs + n
        first = first or rule
    state, decay, beta = first
    state = jax.lax.stop_gradient(state)
    loss = _head_loss_sum(p, x, "ln_f.g", labels, cfg, block_rows, mm)
    return loss, (jnp.stack([pairs, decay, beta]), state)


def loss_and_grad(p, batch, cfg, block_rows, mm=f32_matmul):
    """(the mean loss over the batch's positions, the first delta layer's
    final states [rows, H, Dk, Dv], [pairs routed to the held experts, that
    layer's state RMS, mean decay, mean beta] (the last three the rows'
    means), the gradient with respect to the trainable leaves), one document
    at a time."""
    rows, t = batch["tok"].shape

    def make():
        def doc_loss(train, rest, tok, lbl):
            loss, aux = loss_sum({**train, **rest}, tok, lbl, cfg,
                                 block_rows, mm)
            return loss / (rows * t), aux

        def step(train, rest, tok, lbl, loss, grad):
            (l, aux), g = jax.value_and_grad(doc_loss, has_aux=True)(
                train, rest, tok, lbl)
            return loss + l, aux, jax.tree.map(jnp.add, grad, g)
        return jax.jit(step, donate_argnums=(5,))
    step = _cached(("linear_latent_grad", _sizes(cfg), rows, t, mm), make)
    train = {n: v for n, v in p.items() if not frozen(n)}
    rest = {n: v for n, v in p.items() if frozen(n)}
    loss = jnp.zeros((), jnp.float32)
    grad = jax.tree.map(jnp.zeros_like, train)
    stats, states = [], []
    for r in range(rows):
        loss, (st, state), grad = step(train, rest, batch["tok"][r],
                                       batch["lbl"][r], loss, grad)
        stats.append(st)
        states.append(state)
    stats, states = jnp.stack(stats), jnp.stack(states)
    return (loss, states, jnp.concatenate([
        jnp.sum(stats[:, :1], 0), jnp.sqrt(jnp.mean(jnp.square(states)))[None],
        jnp.mean(stats[:, 1:], 0)]), grad)
