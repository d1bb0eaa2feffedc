"""The plain reference of ``keye_vl2_30b_a3b``: the language model of
Keye-VL-2.0-30B-A3B (config.json at huggingface.co/Kwai-Keye) in
straightforward ``jax.numpy`` float32 — pre-norm blocks, grouped-query
attention over the keys a lightning indexer selects, routed SiLU-gated
experts — with its loss, gradients and Adam.

It imports nothing of ``paddle_tpu`` and takes nothing the program made:
weights come from :mod:`benchmark.weights` (seeded).  No kernels, no cache,
no dispatch: attention is a masked softmax over whole rows of scores, the
selection is ``lax.top_k`` over whole rows of index scores, and every held
expert runs over EVERY token with a routing weight that is zero where the
token was not routed to it.  Every matrix product goes through ``mm`` so
that the control can run the same mathematics with its products in a lower
precision (``lowp_matmul``).  Only to fit the chip, query rows (and the
head's rows) are taken in blocks of ``block_rows``, and blocks, experts and
layers are rematerialised in the backward pass; neither changes a number.

The layer, for ``x`` [T, D] (one document a row; a batch is a ``vmap``):

1. ``h = rms(x; g1)``; ``q = h Wq`` [T, H, Dh], ``k = h Wk``, ``v = h Wv``
   [T, Hkv, Dh]; ``q``, ``k`` through a per-head RMSNorm (``gq``, ``gk``)
   and rotary positions over all Dh dimensions (rotate-half, theta).
   ``mrope_section`` splits the frequencies over three position components
   that are EQUAL for a text token, so this is plain 1-D rotary.
2. Indexer: ``qI = h WIq`` [T, Hi, Di], ``kI = layer_norm(h WIk)`` [T, Di],
   ``w = h WIw`` [T, Hi], rotary on ``qI``, ``kI``;
   ``I[t, s] = sum_j w[t, j] Hi^-0.5 relu(qI[t, j] . kI[s]) Di^-0.5``.
3. ``S_t`` = the ``topk`` positions ``s <= t`` with the largest ``I[t, s]``
   (all of them while ``t < topk``), ties to the lower index.
4. ``o[t, h] = softmax_{s in S_t}(q[t, h] . k[s, h // g] / sqrt(Dh)) v``;
   ``x += concat(o) Wo``.
5. ``h2 = rms(x; g2)``; ``p = softmax(h2 Wr)`` over ALL experts; ``E_t`` =
   top ``k``; ``c = p / sum_{E_t} p``; ``x += sum_{e in E_t, e held}
   c[t, e] (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e`` — the share ``(held,
   first)`` says which experts are held; the others' part is left out.

After the last layer a final RMSNorm, ``logits = x Wout`` over the held
rows of the vocabulary, loss = mean next-token cross entropy.  The
indexer's leaves (``.idx.``) are frozen: no gradient, no Adam state.
"""

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
NEG = -1e30


# ---------------------------------------------------------------------------
# matrix products: the one place precision is chosen
# ---------------------------------------------------------------------------

def f32_matmul(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _fake_quant(x, kind):
    """Round ``x`` to ``kind`` with one dynamic scale per tensor, and pass
    the gradient straight through."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if kind == "fp8":
        s = 448.0 / amax
        q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    elif kind == "int8":
        s = 127.0 / amax
        q = jnp.round(x * s) / s
    elif kind == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        raise ValueError("unknown control precision %r" % (kind,))
    return x + jax.lax.stop_gradient(q - x)


_LOWP = {}


def lowp_matmul(kind):
    """The control's product: both operands rounded to ``kind``,
    accumulated in float32.  One function per kind."""
    if kind not in _LOWP:
        def mm(a, b):
            return jnp.matmul(_fake_quant(a, kind), _fake_quant(b, kind),
                              precision=jax.lax.Precision.HIGHEST)
        _LOWP[kind] = mm
    return _LOWP[kind]


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def share_of(cfg):
    """(experts held here, the first one's number)."""
    return cfg["num_local_experts"], cfg.get("first_local_expert", 0)


def param_spec(cfg):
    """name -> (shape, init) in a fixed order; inits are read by
    :mod:`benchmark.weights`.  Every matrix is a 2-D leaf — the experts'
    too, one leaf an expert and matrix, under the expert's number in the
    whole layer (the program stacks the ones it holds)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    f = cfg["moe_intermediate_size"]
    held, first = share_of(cfg)
    spec = {"tok_emb": ((v, d), "embedding")}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d." % i
        spec[p + "ln1.g"] = ((d,), "ones")
        spec[p + "attn.q"] = ((d, h * dh), "xavier")
        spec[p + "attn.k"] = ((d, hk * dh), "xavier")
        spec[p + "attn.v"] = ((d, hk * dh), "xavier")
        spec[p + "attn.q_g"] = ((dh,), "ones")
        spec[p + "attn.k_g"] = ((dh,), "ones")
        spec[p + "attn.o"] = ((h * dh, d), "xavier")
        spec[p + "idx.q"] = ((d, hi * di), "xavier")
        spec[p + "idx.k"] = ((d, di), "xavier")
        spec[p + "idx.k_g"] = ((di,), "ones")
        spec[p + "idx.k_b"] = ((di,), "zeros")
        spec[p + "idx.w"] = ((d, hi), "xavier")
        spec[p + "ln2.g"] = ((d,), "ones")
        spec[p + "moe.router"] = ((d, cfg["num_experts"]), "xavier")
        for e in range(first, first + held):
            spec[p + "moe.e%d.gate" % e] = ((d, f), "xavier")
            spec[p + "moe.e%d.up" % e] = ((d, f), "xavier")
            spec[p + "moe.e%d.down" % e] = ((f, d), "xavier")
    spec["ln_f.g"] = ((d,), "ones")
    spec["out_w"] = ((d, v), "xavier")
    return spec


def frozen(name):
    """The indexer's leaves: no gradient, no Adam state."""
    return ".idx." in name


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def layer_norm(x, gain, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * gain + bias


def rotary(x, theta):
    """``x`` [T, ..., D]: position = row, rotate-half over all D."""
    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1).reshape(
        (t,) + (1,) * (x.ndim - 2) + (d,))
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def index_parts(p, pre, h, cfg, mm):
    """(qI [T, Hi, Di], kI [T, Di], w [T, Hi]) of the indexer."""
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    t = h.shape[0]
    qi = rotary(mm(h, p[pre + "idx.q"]).reshape(t, hi, di),
                cfg["rope_theta"])
    ki = rotary(layer_norm(mm(h, p[pre + "idx.k"]), p[pre + "idx.k_g"],
                           p[pre + "idx.k_b"]), cfg["rope_theta"])
    return qi, ki, mm(h, p[pre + "idx.w"])


def select_rows(qi, ki, w, row0, topk, mm):
    """bool [R, T]: the selected keys of query rows ``row0 .. row0 + R -
    1`` (``qi`` [R, Hi, Di], ``w`` [R, Hi] are those rows')."""
    r, hi, di = qi.shape
    t = ki.shape[0]
    s = mm(qi.transpose(1, 0, 2), ki.T)                      # [Hi, R, T]
    score = jnp.sum(jax.nn.relu(s) * (hi ** -0.5 * di ** -0.5)
                    * w.T[:, :, None], 0)                    # [R, T]
    rows = row0 + jnp.arange(r)
    causal = jnp.arange(t)[None, :] <= rows[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), min(topk, t))
    picked = jnp.zeros((r, t), bool).at[jnp.arange(r)[:, None], idx].set(True)
    return picked & causal


def attention(p, pre, h, cfg, block_rows, mm):
    """(attention output [T, H * Dh] before Wo, nothing else kept)."""
    t = h.shape[0]
    nh, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = rotary(rms_norm(mm(h, p[pre + "attn.q"]).reshape(t, nh, dh),
                        p[pre + "attn.q_g"], eps), theta)
    k = rotary(rms_norm(mm(h, p[pre + "attn.k"]).reshape(t, hk, dh),
                        p[pre + "attn.k_g"], eps), theta)
    v = mm(h, p[pre + "attn.v"]).reshape(t, hk, dh)
    # query head h reads key/value head h // g
    kh = jnp.repeat(k, nh // hk, axis=1).transpose(1, 2, 0)  # [H, Dh, T]
    vh = jnp.repeat(v, nh // hk, axis=1).transpose(1, 0, 2)  # [H, T, Dh]
    qi, ki, w = index_parts(p, pre, jax.lax.stop_gradient(h), cfg, mm)
    topk = cfg["sa_config"]["topk"]

    def block(args):
        row0, qb, qib, wb = args
        sel = select_rows(qib, ki, wb, row0, topk, mm)
        s = mm(qb.transpose(1, 0, 2), kh) * dh ** -0.5       # [H, R, T]
        s = jnp.where(sel[None], s, NEG)
        pr = jnp.where(sel[None], jax.nn.softmax(s, -1), 0.0)
        return mm(pr, vh).transpose(1, 0, 2).reshape(qb.shape[0], nh * dh)
    r = min(block_rows, t)
    n = t // r
    out = jax.lax.map(jax.checkpoint(block), (
        jnp.arange(n) * r, q.reshape(n, r, nh, dh),
        qi.reshape((n, r) + qi.shape[1:]), w.reshape(n, r, -1)))
    return out.reshape(t, nh * dh)


def route(p, pre, h2, cfg, mm):
    """(expert ids [T, k], weights [T, k] renormalised over the k)."""
    prob = jax.nn.softmax(mm(h2, p[pre + "moe.router"]), -1)
    top, idx = jax.lax.top_k(prob, cfg["num_experts_per_tok"])
    return idx, top / jnp.sum(top, -1, keepdims=True)


def experts(p, pre, h2, cfg, share, mm):
    """The held experts' part of the layer's result, [T, D]."""
    held, first = share
    idx, c = route(p, pre, h2, cfg, mm)
    mats = [jnp.stack([p[pre + "moe.e%d.%s" % (e, m)]
                       for e in range(first, first + held)])
            for m in ("gate", "up", "down")]

    def one(y, em):
        e, wg, wu, wd = em
        ce = jnp.sum(jnp.where(idx == e, c, 0.0), -1)        # 0: not routed
        return y + ce[:, None] * mm(
            jax.nn.silu(mm(h2, wg)) * mm(h2, wu), wd), None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h2),
                        (first + jnp.arange(held), *mats))
    return y


def layer(p, pre, x, cfg, share, block_rows, mm=f32_matmul):
    """One block over ``x`` [T, D] with the expert share ``(held,
    first)``."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p[pre + "ln1.g"], eps)
    x = x + mm(attention(p, pre, h, cfg, block_rows, mm), p[pre + "attn.o"])
    h2 = rms_norm(x, p[pre + "ln2.g"], eps)
    return x + experts(p, pre, h2, cfg, share, mm)


def first_selection(p, tokens, cfg, mm=f32_matmul):
    """bool [B, T, T]: the first layer's selected keys."""
    topk = cfg["sa_config"]["topk"]

    def one(tok):
        h = rms_norm(p["tok_emb"][tok], p["l0.ln1.g"], cfg["rms_norm_eps"])
        qi, ki, w = index_parts(p, "l0.", h, cfg, mm)
        r = min(cfg["reference_block_rows"], tok.shape[0])
        n = tok.shape[0] // r
        sel = jax.lax.map(
            lambda a: select_rows(a[1], ki, a[2], a[0], topk, mm),
            (jnp.arange(n) * r, qi.reshape((n, r) + qi.shape[1:]),
             w.reshape(n, r, -1)))
        return sel.reshape(tok.shape[0], -1)
    return jax.jit(lambda toks: jax.lax.map(one, toks))(tokens)


def routed_pairs(p, tokens, cfg, mm=f32_matmul):
    """Token-expert pairs the first layer routes to the held experts."""
    held, first = share_of(cfg)

    def one(tok):
        x = p["tok_emb"][tok]
        h = rms_norm(x, p["l0.ln1.g"], cfg["rms_norm_eps"])
        x = x + mm(attention(p, "l0.", h, cfg, cfg["reference_block_rows"],
                             mm), p["l0.attn.o"])
        idx, _ = route(p, "l0.", rms_norm(x, p["l0.ln2.g"],
                                          cfg["rms_norm_eps"]), cfg, mm)
        return jnp.sum((idx >= first) & (idx < first + held))
    return int(jnp.sum(jax.jit(lambda t: jax.lax.map(one, t))(tokens)))


# ---------------------------------------------------------------------------
# loss, gradient, Adam
# ---------------------------------------------------------------------------

def loss_sum(p, tokens, labels, cfg, block_rows, mm=f32_matmul):
    """Sum of the token losses of one document ``tokens`` [T]."""
    x = p["tok_emb"][tokens]
    share = share_of(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda p, x, pre="l%d." % i: layer(p, pre, x, cfg, share,
                                               block_rows, mm))(p, x)
    x = rms_norm(x, p["ln_f.g"], cfg["rms_norm_eps"])

    def rows_loss(xl):
        logp = jax.nn.log_softmax(mm(xl[0], p["out_w"]), -1)
        return -jnp.sum(jnp.take_along_axis(logp, xl[1][:, None], -1))
    r = min(block_rows, x.shape[0])
    return jnp.sum(jax.lax.map(jax.checkpoint(rows_loss), (
        x.reshape(-1, r, x.shape[1]), labels.reshape(-1, r))))


_JITTED = {}


def _cached(key, make):
    if key not in _JITTED:
        _JITTED[key] = make()
    return _JITTED[key]


def _sizes(cfg):
    return tuple(sorted((k, str(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, dict))))


def loss_and_grad(p, batch, cfg, block_rows, mm=f32_matmul):
    """Mean loss over the batch's positions and its gradient with respect
    to the trainable leaves, one document at a time."""
    rows, t = batch["tok"].shape

    def make():
        def doc_loss(train, rest, tok, lbl):
            return loss_sum({**train, **rest}, tok, lbl, cfg, block_rows,
                            mm) / (rows * t)

        def step(train, rest, tok, lbl, loss, grad):
            l, g = jax.value_and_grad(doc_loss)(train, rest, tok, lbl)
            return loss + l, jax.tree.map(jnp.add, grad, g)
        return jax.jit(step, donate_argnums=(5,))
    step = _cached(("grad", _sizes(cfg), rows, t, mm), make)
    train = {n: v for n, v in p.items() if not frozen(n)}
    rest = {n: v for n, v in p.items() if frozen(n)}
    loss = jnp.zeros((), jnp.float32)
    grad = jax.tree.map(jnp.zeros_like, train)
    for r in range(rows):
        loss, grad = step(train, rest, batch["tok"][r], batch["lbl"][r],
                          loss, grad)
    return loss, grad


def adam_init(p):
    train = {n: v for n, v in p.items() if not frozen(n)}
    return {"m": jax.tree.map(jnp.zeros_like, train),
            "v": jax.tree.map(jnp.zeros_like, train), "t": 0}


def adam_step(p, grad, state, cfg):
    """Adam at a constant rate, epsilon outside the bias correction (as
    the program's ``adam`` op); frozen leaves pass through."""
    b1, b2, eps = cfg["adam_beta1"], cfg["adam_beta2"], cfg["adam_epsilon"]
    t = state["t"] + 1
    lr_t = cfg["learning_rate"] * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)

    def make():
        def upd(p, g, m, v, lr_t):
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            p = jax.tree.map(
                lambda w, a, b: w - lr_t * a / (jnp.sqrt(b) + eps), p, m, v)
            return p, m, v
        return jax.jit(upd)
    upd = _cached(("adam", b1, b2, eps), make)
    train = {n: p[n] for n in grad}
    train, m, v = upd(train, grad, state["m"], state["v"], jnp.float32(lr_t))
    return {**p, **train}, {"m": m, "v": v, "t": t}
