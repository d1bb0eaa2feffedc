"""The plain reference: post-norm Transformer blocks in straightforward
``jax.numpy`` float32 (Vaswani et al. 2017, arXiv:1706.03762, sections 3
and 5.4), used by both configurations:

* ``encdec_*``  — encoder-decoder with sinusoid positions, label-smoothed
  cross-entropy over the non-padding target positions, Adam with the noam
  schedule (``transformer_base``);
* ``declm_*``   — decoder-only LM with learned positions (``decoder_base``).

It imports nothing of ``paddle_tpu`` and takes nothing the program made:
weights come from :mod:`benchmark.weights` (seeded), the sinusoid table is
computed here.  No kernels, no cache, no batching tricks; every matrix
product goes through the ``mm`` argument so the *control* can run the same
mathematics with its products in a lower precision (``lowp_matmul``).

Departures from the paper, all following the configuration files'
``assumed`` lists: three unshared 32000-row tables; no dropout (the
configurations run rate 0.0); Adam's epsilon outside the bias correction.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

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
    """The control's product: both operands rounded to ``kind`` (fp8 e4m3,
    int8 or bf16), accumulated in float32.  One function per kind, so that
    what is jitted over it is traced once."""
    if kind not in _LOWP:
        def mm(a, b):
            return jnp.matmul(_fake_quant(a, kind), _fake_quant(b, kind),
                              precision=jax.lax.Precision.HIGHEST)
        _LOWP[kind] = mm
    return _LOWP[kind]


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def sinusoid_table(n_position, d_model):
    pos = np.arange(n_position, dtype=np.float64)[:, None]
    dim = np.arange(d_model // 2, dtype=np.float64)[None, :]
    angle = pos / np.power(10000.0, 2 * dim / d_model)
    table = np.zeros((n_position, d_model))
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return jnp.asarray(table, jnp.float32)


def layer_norm(x, gain, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * gain + bias


def attention(p, pre, xq, xkv, k_len, causal, n_head, mm):
    """Multi-head scaled dot-product attention; keys at or past ``k_len``
    are masked, ``causal`` hides keys after the query."""
    b, tq, d = xq.shape
    tk = xkv.shape[1]
    dh = d // n_head

    def heads(x, t):
        return x.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)
    q = heads(mm(xq, p[pre + "q"]), tq) * (dh ** -0.5)
    k = heads(mm(xkv, p[pre + "k"]), tk)
    v = heads(mm(xkv, p[pre + "v"]), tk)
    s = mm(q, k.transpose(0, 1, 3, 2))
    valid = jnp.arange(tk)[None, None, None, :] < k_len[:, None, None, None]
    if causal:
        valid = valid & (jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None])
    s = jnp.where(valid, s, NEG)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(valid, w, 0.0)
    ctx = mm(w, v).transpose(0, 2, 1, 3).reshape(b, tq, d)
    return mm(ctx, p[pre + "o"])


def ffn(p, pre, x, mm):
    h = jax.nn.relu(mm(x, p[pre + "fc1_w"]) + p[pre + "fc1_b"])
    return mm(h, p[pre + "fc2_w"]) + p[pre + "fc2_b"]


def _sublayer(p, ln, x, out):
    return layer_norm(x + out, p[ln + "_g"], p[ln + "_b"])


# ---------------------------------------------------------------------------
# encoder-decoder (transformer_base)
# ---------------------------------------------------------------------------

def encdec_param_spec(cfg):
    """name -> (shape, init) in a fixed order; inits are read by
    :mod:`benchmark.weights`."""
    d, di, v = cfg["d_model"], cfg["d_inner"], cfg["vocab_size"]
    spec = {"src_emb": ((v, d), "embedding"), "tgt_emb": ((v, d), "embedding")}

    def attn(pre):
        for w in "qkvo":
            spec[pre + w] = ((d, d), "xavier")

    def norm(pre):
        spec[pre + "_g"] = ((d,), "ones")
        spec[pre + "_b"] = ((d,), "zeros")

    def feed(pre):
        spec[pre + "fc1_w"] = ((d, di), "xavier")
        spec[pre + "fc1_b"] = ((di,), "zeros")
        spec[pre + "fc2_w"] = ((di, d), "xavier")
        spec[pre + "fc2_b"] = ((d,), "zeros")
    for i in range(cfg["n_layer"]):
        e = "enc.%d." % i
        attn(e + "attn."); norm(e + "ln1"); feed(e + "ffn."); norm(e + "ln2")
    for i in range(cfg["n_layer"]):
        e = "dec.%d." % i
        attn(e + "self."); norm(e + "ln1")
        attn(e + "cross."); norm(e + "ln2")
        feed(e + "ffn."); norm(e + "ln3")
    spec["out_w"] = ((d, v), "xavier")
    spec["out_b"] = ((v,), "zeros")
    return spec


def _embed(table, ids, pos_table, d_model):
    return table[ids] * (d_model ** 0.5) + pos_table[None, :ids.shape[1]]


def encdec_logits(p, batch, cfg, mm=f32_matmul):
    """Decoder logits [B, T, V] for a batch {src, src_len, tgt, tgt_len}."""
    nh, d = cfg["n_head"], cfg["d_model"]
    if cfg.get("dropout"):
        raise ValueError("the plain reference has no dropout: it cannot "
                         "follow masks the program draws inside its step")
    pos = sinusoid_table(cfg["max_len"], d)
    x = _embed(p["src_emb"], batch["src"], pos, d)
    for i in range(cfg["n_layer"]):
        e = "enc.%d." % i
        a = attention(p, e + "attn.", x, x, batch["src_len"], False, nh, mm)
        x = _sublayer(p, e + "ln1", x, a)
        x = _sublayer(p, e + "ln2", x, ffn(p, e + "ffn.", x, mm))
    enc = x
    y = _embed(p["tgt_emb"], batch["tgt"], pos, d)
    for i in range(cfg["n_layer"]):
        e = "dec.%d." % i
        a = attention(p, e + "self.", y, y, batch["tgt_len"], True, nh, mm)
        y = _sublayer(p, e + "ln1", y, a)
        a = attention(p, e + "cross.", y, enc, batch["src_len"], False, nh,
                      mm)
        y = _sublayer(p, e + "ln2", y, a)
        y = _sublayer(p, e + "ln3", y, ffn(p, e + "ffn.", y, mm))
    return mm(y, p["out_w"]) + p["out_b"]


def smoothed_xent(logits, labels, eps):
    """-sum_k q_k log p_k with q = (1-eps) one_hot + eps / V."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return -(1.0 - eps) * picked - eps * jnp.mean(logp, -1)


def encdec_loss_sum(p, batch, cfg, mm=f32_matmul):
    """(sum of the token losses over non-padding target positions, their
    count) — sums, so that row blocks add up."""
    logits = encdec_logits(p, batch, cfg, mm)
    t = batch["tgt"].shape[1]
    mask = (jnp.arange(t)[None, :] < batch["tgt_len"][:, None]
            ).astype(jnp.float32)
    tok = smoothed_xent(logits, batch["lbl"], cfg["label_smooth_eps"])
    return jnp.sum(tok * mask), jnp.sum(mask)


_JITTED = {}


def _cached(key, make):
    """One jitted function per (what, sizes, product): a run traces each
    once, and the persistent compile cache serves its compile."""
    if key not in _JITTED:
        _JITTED[key] = make()
    return _JITTED[key]


def _sizes(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def encdec_loss_and_grad(p, batch, cfg, block_rows, mm=f32_matmul):
    """Mean loss over the batch's target tokens and its gradient, computed
    in blocks of ``block_rows`` rows so that the reference's footprint
    stays under the program's."""
    rows = batch["src"].shape[0]
    n_tok = jnp.sum(jnp.minimum(batch["tgt_len"], batch["tgt"].shape[1])
                    ).astype(jnp.float32)

    def make():
        def block_loss(params, blk, n_tok):
            total, _ = encdec_loss_sum(params, blk, cfg, mm)
            return total / n_tok

        def step(params, blk, n_tok, loss, grad):
            l, g = jax.value_and_grad(block_loss)(params, blk, n_tok)
            return loss + l, jax.tree.map(jnp.add, grad, g)
        return jax.jit(step, donate_argnums=(4,))
    step = _cached(("encdec_grad", _sizes(cfg), mm), make)
    loss = jnp.zeros((), jnp.float32)
    grad = jax.tree.map(jnp.zeros_like, p)
    for lo in range(0, rows, block_rows):
        blk = {k: v[lo:lo + block_rows] for k, v in batch.items()}
        loss, grad = step(p, blk, n_tok, loss, grad)
    return loss, grad


# ---------------------------------------------------------------------------
# Adam with the noam schedule (section 5.3)
# ---------------------------------------------------------------------------

def noam_lr(step, d_model, warmup):
    """``step`` counts from 1."""
    return d_model ** -0.5 * min(step ** -0.5, step * warmup ** -1.5)


def adam_init(p):
    zeros = jax.tree.map(jnp.zeros_like, p)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, p), "t": 0}


def adam_step(p, grad, state, cfg):
    b1, b2, eps = cfg["adam_beta1"], cfg["adam_beta2"], cfg["adam_epsilon"]
    t = state["t"] + 1
    lr = noam_lr(t, cfg["d_model"], cfg["warmup_steps"])
    lr_t = lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)

    def make():
        def upd(p, g, m, v, lr_t):
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            p = jax.tree.map(
                lambda w, a, b: w - lr_t * a / (jnp.sqrt(b) + eps), p, m, v)
            return p, m, v
        return jax.jit(upd)
    upd = _cached(("adam", b1, b2, eps), make)
    p, m, v = upd(p, grad, state["m"], state["v"], jnp.float32(lr_t))
    return p, {"m": m, "v": v, "t": t}


# ---------------------------------------------------------------------------
# decoder-only LM (decoder_base)
# ---------------------------------------------------------------------------

def declm_param_spec(cfg):
    d, di, v = cfg["d_model"], cfg["d_inner"], cfg["vocab_size"]
    spec = {"tok_emb": ((v, d), "embedding"),
            "pos_emb": ((cfg["max_len"], d), "embedding")}
    for i in range(cfg["n_layer"]):
        e = "l.%d." % i
        for w in "qkvo":
            spec[e + "attn." + w] = ((d, d), "xavier")
        spec[e + "ln1_g"] = ((d,), "ones")
        spec[e + "ln1_b"] = ((d,), "zeros")
        spec[e + "ffn.fc1_w"] = ((d, di), "xavier")
        spec[e + "ffn.fc1_b"] = ((di,), "zeros")
        spec[e + "ffn.fc2_w"] = ((di, d), "xavier")
        spec[e + "ffn.fc2_b"] = ((d,), "zeros")
        spec[e + "ln2_g"] = ((d,), "ones")
        spec[e + "ln2_b"] = ((d,), "zeros")
    spec["out_w"] = ((d, v), "xavier")
    spec["out_b"] = ((v,), "zeros")
    return spec


def declm_logits(p, tokens, lengths, cfg, mm=f32_matmul):
    """Full causal forward: logits [B, T, V] for tokens [B, T] of which
    the first ``lengths`` are valid."""
    nh = cfg["n_head"]
    t = tokens.shape[1]
    x = p["tok_emb"][tokens] + p["pos_emb"][None, :t]
    for i in range(cfg["n_layer"]):
        e = "l.%d." % i
        a = attention(p, e + "attn.", x, x, lengths, True, nh, mm)
        x = _sublayer(p, e + "ln1", x, a)
        x = _sublayer(p, e + "ln2", x, ffn(p, e + "ffn.", x, mm))
    return mm(x, p["out_w"]) + p["out_b"]
